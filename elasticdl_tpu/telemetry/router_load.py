"""The expert router's load, read on demand.

``layers/moe.py`` sows, every step, each expert layer's per-expert pair
counts, the number of (token, slot) pairs its dispatch gave a row to, the
rows of the buffer they were laid out in beside the rows of the full one
(``buffer_rows``: the rung of ``ops/grouped_matmul.py``'s ladder that the
step took, summed over the devices) and, where the layer holds a share of
its experts, the pairs routed to the absent ones, into the ``router_stats``
collection (where the router's selection bias lives too).  They leave the
step as device arrays inside the train state; the trainer holds them
(``SPMDTrainer.state``) and nothing on the train path reads them.  :func:`read` fetches the newest when
somebody asks — an evaluation milestone (``LocalExecutor.evaluate``, where
the program reads the loss anyway), a benchmark's reader after its window.
"""

from __future__ import annotations

import weakref

import jax
import numpy as np

# the collection ``layers/moe.py`` sows into
ROUTER_STATS = "router_stats"
# the collection a model declares (one scalar a name) to have
# ``trainer/step.py`` leave its loss there by the parts its ``loss.parts``
# names: a multi-token-prediction model's ``main`` and ``mtp``
LOSS_PARTS = "loss_parts"
# what a loss function saw on its way that is no term of the loss, a scalar a
# name: the key ``loss.parts`` returns it under and the collection a model
# declares to have ``trainer/step.py`` leave it there.  A looped model's
# (``models/long_seq_transformer.py::looped_parts``): each pass's own mean
# cross-entropy ``ce_t`` and mean exit probability ``exit_t``, ``t`` from 1
LOSS_OBSERVED = "loss_observed"
# the collection a sparse-attention layer (``layers/attention.py``) sows
# what its selection did into, a scalar a name and layer: the selected keys
# a query (``kept_keys``, a mean over batch and queries), the queries at
# which a tie was broken at the last place (``ties_broken``) and the share of
# query blocks whose tie search ran (``tie_search_blocks``: the blocks that
# held such a query)
SELECTION_STATS = "selection_stats"
# the collection a window-attention layer (``layers/attention.py``) sows the
# score blocks its kernels' plan ``visited``, ``masked`` (the diagonal and
# the window's trailing edge) and ``skipped`` this step into, over the batch
# and the heads (``ops/attention.py::flash_block_plan``)
BLOCK_PLAN = "block_plan"
# the collection a gated-delta-rule layer (``layers/gated_delta.py``) sows
# the step's mean decay ``exp(g)`` and mean write strength ``beta`` into, a
# scalar a name and layer
DELTA_STATE = "delta_state"

_watched = None


def watch(trainer):
    """Remember (weakly) the trainer whose state :func:`read` looks at."""
    global _watched
    _watched = weakref.ref(trainer)


def _state(model_state):
    if model_state is not None:
        return model_state
    trainer = _watched() if _watched is not None else None
    return None if trainer is None else trainer.state.model_state


def read_loss_parts(model_state=None) -> dict | None:
    """The newest train step's loss by its parts (``{"main": ..., "mtp":
    ...}``: the next token's loss and the weighted second-token loss, whose
    sum the step reported), one host readback.  None for a model that
    declares no ``LOSS_PARTS`` collection."""
    parts = (_state(model_state) or {}).get(LOSS_PARTS)
    if not parts:
        return None
    return {k: float(v) for k, v in jax.device_get(parts).items()}


def observed_names(passes: int) -> list[str]:
    return [
        f"{kind}_{t}" for t in range(1, passes + 1) for kind in ("ce", "exit")
    ]


def read_exits(model_state=None) -> dict | None:
    """The newest train step's passes of a looped model, one host readback:
    ``{"cross_entropy": [a pass ...], "exit_distribution": [...],
    "exit_step_mean": sum_t t * p_t}``, means over the step's tokens.  None
    for a model that is not looped."""
    observed = (_state(model_state) or {}).get(LOSS_OBSERVED)
    if not observed:
        return None
    observed = {k: float(v) for k, v in jax.device_get(observed).items()}
    passes = range(1, len(observed) // 2 + 1)
    exits = [observed[f"exit_{t}"] for t in passes]
    return {
        "cross_entropy": [observed[f"ce_{t}"] for t in passes],
        "exit_distribution": exits,
        "exit_step_mean": sum(t * p for t, p in zip(passes, exits)),
    }


def read_selection(model_state=None) -> dict | None:
    """The newest step's selection by layer, one host readback:
    ``{"kept_keys": [a layer ...], "ties_broken": [...],
    "tie_search_blocks": [...]}`` in the order of the layers' names.  None
    for a model without sparse attention."""
    stats = (_state(model_state) or {}).get(SELECTION_STATS)
    if not stats:
        return None
    out: dict = {}
    leaves = jax.tree_util.tree_leaves_with_path(jax.device_get(stats))
    # (block_10 after block_2: by the number in the layer's name)
    for path, leaf in sorted(
        leaves, key=lambda item: int("0" + "".join(filter(str.isdigit, item[0][0].key)))
    ):
        out.setdefault(path[-1].key, []).append(float(leaf))
    return out


def read_block_plan(model_state=None) -> dict | None:
    """The newest step's window layers' block plans summed, one host
    readback: ``{"layers", "visited", "masked", "skipped",
    "skipped_share"}``.  None for a model without a window layer."""
    stats = (_state(model_state) or {}).get(BLOCK_PLAN)
    if not stats:
        return None
    out = {"layers": 0, "visited": 0, "masked": 0, "skipped": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        jax.device_get(stats)
    ):
        out[path[-1].key] += int(leaf)
        out["layers"] += path[-1].key == "visited"
    out["skipped_share"] = out["skipped"] / (out["visited"] + out["skipped"])
    return out


def read_delta_state(model_state=None) -> dict | None:
    """The newest step's gated-delta-rule layers, one host readback:
    ``{"layers", "decay_mean", "beta_mean"}``, each a mean over the layers
    (a decay of 0 is a state that forgets everything a step, a ``beta`` of 0
    one that is never written).  None for a model without such a layer."""
    stats = (_state(model_state) or {}).get(DELTA_STATE)
    if not stats:
        return None
    found: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        jax.device_get(stats)
    ):
        found.setdefault(path[-1].key, []).append(float(leaf))
    return {
        "layers": len(found["decay_mean"]),
        **{name: sum(of) / len(of) for name, of in found.items()},
    }


def read(model_state=None) -> dict | None:
    """The newest step's router load, one host readback: the worst layer's
    busiest expert over the mean load, experts that got no pair, the pairs
    routed to experts held here and to absent ones, and the pairs routed to
    held experts less the pairs dispatched (0 by construction), and the
    rows of the buffers the dispatch walked, as a count and as a share of
    the full rung's (1.0 where every layer took its largest or only rung).
    None for a model without experts."""
    stats = (_state(model_state) or {}).get(ROUTER_STATS)
    if not stats:
        return None
    flat = {
        jax.tree_util.keystr(path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(stats)
        )
    }
    counts = [v for k, v in flat.items() if "expert_counts" in k]
    held = sum(int(v) for k, v in flat.items() if "rows_held" in k)
    absent = sum(int(v) for k, v in flat.items() if "absent_pairs" in k)
    pairs = sum(int(c.sum()) for c in counts)
    if not pairs:
        return None  # no step has run yet
    buffer_rows, full_rows = (
        sum(int(v[i]) for k, v in flat.items() if "buffer_rows" in k)
        for i in (0, 1)
    )
    return {
        "layers": len(counts),
        "pairs": pairs,
        "max_over_mean": max(float(c.max() / c.mean()) for c in counts),
        "experts_without_tokens": sum(int((c == 0).sum()) for c in counts),
        "held_pairs": pairs - absent,
        "absent_pairs": absent,
        "dropped_pairs": pairs - absent - held,
        "buffer_rows": buffer_rows,
        # (a state sown before the counter existed has no rows to share)
        "buffer_share": buffer_rows / full_rows if full_rows else None,
    }
