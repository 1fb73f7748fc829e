"""What a looped model (``models/long_seq_transformer.py`` with ``loop_steps``
over 1; ``docs/designs/looped_layers.md``) adds to a step, for the ``.loop``
readers under ``layer_metrics/``: the device time of the passes' exits and of
the loop's own ops, by the model's scopes (``perf/scope_shares.py``).  (The
exit distribution the loss saw is the program's counter,
``telemetry/router_load.py::read_exits``, and no metric: every pass runs in
training whatever the gate says, so it moves no rate.)

Each reader returns None where the program has nothing to read (no trace, a
program without ``op_scopes``, a model that is not looped) and never raises
for it."""

from __future__ import annotations

from perf import scope_shares

# the scope around a pass's exit norm and gate, the head and the loss
EXIT_PARTS = ("exit", *scope_shares.HEAD_AND_LOSS)
# the scope around the loop itself (``telemetry/op_scopes.py::LOOP``): an op
# of a part inside the loop is that part's, so what is left under this name
# is the loop's own, the carry's copies and the stacked exits
LOOP_PART = "loop"


def exit_heads_share(run) -> float | None:
    """Percent of busy time in the passes' exits: the exit norm and gate
    (scope ``exit``), the head (``lm_head``, applied inside the loss a pass
    at a time and made again in the backward pass) and the loss, every
    phase but the optimizer's."""
    return scope_shares.share(
        run,
        lambda part, phase, kind: any(
            element in EXIT_PARTS for element in part.split("/")
        )
        and phase != "optimizer" and kind != "collective",
    )


def loop_overhead_share(run) -> float | None:
    """Percent of busy time in the loop's own ops, which are no block's and
    no exit's: 0.0 where the passes are unrolled."""
    return scope_shares.share(
        run, lambda part, phase, kind: part == LOOP_PART and kind != "collective"
    )
