"""Device time by the model's own scopes, as shares of device-busy time: the
arithmetic shared by the ``.scope_lm`` / ``.scope_vision`` readers under
``layer_metrics/``.

The program hands out its compiled train programs' op -> scope map
(``elasticdl_tpu/telemetry/op_scopes.py``: every instruction's part of the
model, its phase — forward, backward, recompute, optimizer — and its kind —
kernel, matmul, collective, other); ``op_scopes.attribute`` joins it to the
trace's per-op self times.  Both are read here after the window, once a
``run``, and kept in it.

A reader returns None only where there is no trace or the program has no
``op_scopes`` (the parent of the PR that added it: these files are laid over
its checkout too), and 0.0 where the part took no time: a later PR that
fuses, renames or removes a kernel cannot make one of these vanish from a
line.  Each share is in percent of ``trace["busy_s"]``; in a cell the four
phases, the collectives and ``unattributed`` add up to 100."""

from __future__ import annotations

_KEY = "_scope_shares"
PHASES = ("forward", "backward", "recompute", "optimizer")
HEAD_AND_LOSS = ("lm_head", "loss")


def attributed(run) -> dict | None:
    """``op_scopes.attribute`` of the traced window, taken once per ``run``:
    ``{"scopes": {(part, phase, kind): seconds}, "unattributed": seconds,
    "fused_across": seconds}``."""
    if _KEY not in run:
        run[_KEY] = None
        trace = run.get("trace")
        if trace is not None and trace.get("busy_s"):
            try:
                from elasticdl_tpu.telemetry import op_scopes
            except ImportError:
                op_scopes = None
            read = getattr(op_scopes, "read", None)
            maps = read() if read is not None else None
            if maps:
                run[_KEY] = op_scopes.attribute(trace["op_self_s"], maps)
    return run[_KEY]


def share(run, chosen) -> float | None:
    """Percent of busy time in the scopes ``chosen(part, phase, kind)``
    picks; collectives count to no phase."""
    found = attributed(run)
    if found is None:
        return None
    seconds = sum(
        s for (part, phase, kind), s in found["scopes"].items()
        if chosen(part, phase, kind)
    )
    return 100.0 * seconds / run["trace"]["busy_s"]


def _under(part: str, module: str) -> bool:
    """``module`` is an element of the part's path (``block/attn/rope`` and
    ``mtp/block/attn`` are under ``attn``)."""
    return module in part.split("/")


def phase_share(run, phase: str) -> float | None:
    return share(run, lambda p, ph, k: ph == phase and k != "collective")


def forward_share(run) -> float | None:
    return phase_share(run, "forward")


def backward_share(run) -> float | None:
    return phase_share(run, "backward")


def recompute_share(run) -> float | None:
    """The forward that ``remat_layers`` runs again inside the backward."""
    return phase_share(run, "recompute")


def optimizer_share(run) -> float | None:
    """The optax update outside what XLA fused into a weight gradient (a
    fusion is its matmul's: ``fused_across_share``)."""
    return phase_share(run, "optimizer")


def collective_share(run) -> float | None:
    return share(run, lambda p, ph, k: k == "collective")


def head_loss_share(run) -> float | None:
    """``lm_head`` and ``loss``, forward, backward and recomputed."""
    return share(
        run,
        lambda p, ph, k: p.split("/")[-1] in HEAD_AND_LOSS
        and ph != "optimizer" and k != "collective",
    )


def attention_other_share(run) -> float | None:
    """Under ``attn`` and neither kernel nor matmul: rotary positions, the
    joins, the folds, norms, casts."""
    return share(run, lambda p, ph, k: _under(p, "attn") and k == "other")


def experts_other_share(run) -> float | None:
    """Under ``moe`` and neither kernel nor matmul, outside the optimizer:
    routing, permutations, scatter-adds, casts."""
    return share(
        run,
        lambda p, ph, k: _under(p, "moe") and k == "other"
        and ph != "optimizer",
    )


def block_other_share(run) -> float | None:
    """Anything under a block (the module's too) that is neither kernel nor
    matmul: layout and element-wise passes as one number."""
    return share(run, lambda p, ph, k: _under(p, "block") and k == "other")


def fused_across_share(run) -> float | None:
    """Ops that hold more than one top-level part (``lm_head``'s weight
    gradient with its optimizer update): how much of the split rests on the
    anchor rule."""
    found = attributed(run)
    if found is None:
        return None
    return 100.0 * found["fused_across"] / run["trace"]["busy_s"]


def unattributed_share(run) -> float | None:
    found = attributed(run)
    if found is None:
        return None
    return 100.0 * found["unattributed"] / run["trace"]["busy_s"]
