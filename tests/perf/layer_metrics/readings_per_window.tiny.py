"""``readings_per_window.tiny``: a per-layer metric added the way a later
PR adds one — this file and an entry in the manifest, no edit to the
harness.  A count, so the CPU rehearsal could report it; the harness
reports per-layer metrics only from a chip."""


def read(run):
    return float(run["untraced"]["readings"]) if run.get("untraced") else None
