"""Rehearsal of a later PR's per-layer entry under a layer the manifest has
not named before: work units (tokens, records) in a mean reading."""


def read(run):
    untraced = run.get("untraced")
    if not untraced or not untraced.get("readings"):
        return None
    return untraced["units"] / untraced["readings"]
