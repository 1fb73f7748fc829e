"""Time the expert layer's sum of rows into their tokens alone, on the chip,
at one rung's shape: XLA's float32 scatter-add (what ``layers/moe.py`` ran
up to PR 48, with the float32 product before it and the cast after) beside
``ops/grouped_matmul.py::sum_by_token`` (the ``expert_rows_sum`` kernel, and
what XLA runs before it: the spans' table and the rows' one-hot lines).

A builder's tool for choosing the kernel's tiles: its times are one call's
alone, never a ledger number (``perf/run.py`` is the benchmark; nothing
under ``perf/`` imports this).  ``--shape rows,tokens,d,slots`` is a rung of
``rows`` rows of width ``d`` in ``--dtype`` for ``tokens`` tokens of
``slots`` experts each; ``--groups`` experts are held, and the router is a
uniform draw over as many experts as leave ``--fill`` of the rows held (a
balanced load fills half of a low rung); ``--hot`` of the tokens pick the
first held expert besides, as a collapsing router's do.  The rows are laid out by the
layer's own ``group_order`` / ``group_layout``, so they come in expert order
and out of token order as a step's do.  A geometry of ``--sweep`` is
``token_tile,span``: the tokens of a grid step and the rows fetched a group
and round, in place of the module's constants.

    python benchmarks/expert_rows_sweep.py --shape 34816,16384,2048,8 \\
        --groups 16 --sweep "128,32;128,16;256,32;256,48"

One JSON line a form and weighting (``combine``: float32 weights;
``dispatch``: weight 1, the dispatch's transpose): milliseconds a call, for
the kernel form the kernel's own beside every other op of its program, GB/s
counted as the held rows read once and the tokens written once, and the
largest difference from the scatter-add's result in units of the result's
largest magnitude (one bfloat16 rounding where the order of a token's terms
moved its sum).  A geometry Mosaic refuses is reported with its error, not
skipped in silence.

Exits 3 where JAX finds no TPU: a time from the CPU is not a kernel time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def scatter_add(rows, row_token, tokens, row_weight=None):
    """The plain form: float32 products added a row at a time."""
    import jax.numpy as jnp

    values = rows.astype(jnp.float32)
    if row_weight is not None:
        values = values * row_weight[:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[row_token].add(
        values, mode="drop"
    ).astype(rows.dtype)


def rung(rows, tokens, width, slots, groups, fill, dtype, hot=0.0, seed=0):
    """A rung's rows, their tokens and weights, and the rows that are held.
    ``hot``: the share of the tokens that also pick the first held expert,
    as a router does that is collapsing onto it."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.layers import moe
    from elasticdl_tpu.ops import grouped_matmul as gmm_ops

    routed = max(groups, round(tokens * slots * groups / (fill * rows)))
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    scores = jax.random.uniform(keys[0], (tokens, routed))
    scores = scores.at[:, 0].add(jax.random.uniform(keys[3], (tokens,)) < hot)
    _, top = jax.lax.top_k(scores, slots)
    group_ids = jnp.where(top < groups, top, groups).reshape(-1).astype(jnp.int32)
    order = gmm_ops.group_order(group_ids, groups)
    needed = int(gmm_ops.tiles_needed(order.sizes, gmm_ops.TILE_ROWS))
    if needed * gmm_ops.TILE_ROWS > rows:
        raise ValueError(
            f"{needed} tiles of {gmm_ops.TILE_ROWS} rows do not fit {rows}"
        )
    layout = gmm_ops.group_layout(
        group_ids, groups, gmm_ops.TILE_ROWS, rows, order, True
    )
    weights = jax.random.uniform(keys[1], (tokens, slots), jnp.float32, 0.05, 1.0)
    row_weight, row_token = moe._rows_of(weights, layout.row_pair)
    values = jax.random.normal(keys[2], (rows, width), jnp.float32).astype(dtype)
    held = int(jnp.sum(row_token < tokens))
    return values, row_token, row_weight, (group_ids, order.sizes), held, routed


def time_rung(
    shape, groups, fill, dtype, geometry, calls, time_plain=True, hot=0.0
):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import grouped_matmul as gmm_ops

    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    from attention_sweep import OTHER, traced_kernel_ms  # the trace's reader

    rows, tokens, width, slots = shape
    values, row_token, row_weight, groups_of, held, routed = rung(
        rows, tokens, width, slots, groups, fill, dtype, hot
    )
    constants = (gmm_ops._SUM_TOKENS, gmm_ops._SPAN_ROWS)
    if geometry is not None:
        gmm_ops._SUM_TOKENS, gmm_ops._SPAN_ROWS = geometry
    nbytes = (held + tokens) * width * values.dtype.itemsize
    lines = []
    for weighting, weight in (("combine", row_weight), ("dispatch", None)):
        forms = {
            "scatter_add": jax.jit(
                lambda v, t, w: scatter_add(v, t, tokens, w)
            ),
            "kernel": jax.jit(
                lambda v, t, w: gmm_ops.sum_by_token(
                    v, t, tokens,
                    gmm_ops.token_spans(*groups_of, tokens, gmm_ops.TILE_ROWS),
                    w,
                )
            ),
        }
        results = {}
        for name, form in forms.items():
            results[name] = jax.block_until_ready(
                form(values, row_token, weight)
            )  # compiles
            if name == "scatter_add" and not time_plain:
                continue
            names = (gmm_ops.ROWS_SUM,) if name == "kernel" else ()
            ms, _ = traced_kernel_ms(
                lambda: form(values, row_token, weight), calls, names
            )
            total = sum(ms.values())
            line = {
                "shape": list(shape), "held_rows": held, "routed": routed,
                "hot": hot,
                "weighting": weighting, "form": name,
                "ms": round(total, 4),
                "gb_per_s": round(nbytes / total / 1e6, 1) if total else None,
            }
            if names:
                line["kernel_ms"] = round(ms[gmm_ops.ROWS_SUM], 4)
                line["other_ops_ms"] = round(ms[OTHER], 4)
            lines.append(line)
        want = results["scatter_add"].astype(jnp.float32)
        for line in lines:
            if line["weighting"] == weighting and line["form"] in results:
                got = results[line["form"]].astype(jnp.float32)
                line["max_err_over_max"] = float(
                    jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
                )
    gmm_ops._SUM_TOKENS, gmm_ops._SPAN_ROWS = constants
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", default="34816,16384,2048,8")
    parser.add_argument("--groups", type=int, default=16)
    parser.add_argument("--fill", type=float, default=0.5)
    parser.add_argument(
        "--hot", type=float, default=0.0,
        help="share of the tokens that also pick the first held expert",
    )
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument(
        "--sweep", default="",
        help='"token_tile,span;..."; empty: the module\'s own constants',
    )
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("expert_rows_sweep: no TPU; a CPU time is not a kernel time",
              file=sys.stderr)
        return 3
    shape = tuple(int(n) for n in args.shape.split(","))
    geometries = [
        tuple(int(n) for n in geometry.split(","))
        for geometry in args.sweep.split(";") if geometry
    ] or [None]
    from elasticdl_tpu.ops import grouped_matmul as gmm_ops

    constants = (gmm_ops._SUM_TOKENS, gmm_ops._SPAN_ROWS)
    for geometry in geometries:
        # (a geometry Mosaic refused leaves its constants behind)
        gmm_ops._SUM_TOKENS, gmm_ops._SPAN_ROWS = constants
        try:
            lines = time_rung(
                shape, args.groups, args.fill, jnp.dtype(args.dtype), geometry,
                args.calls, time_plain=geometry == geometries[0], hot=args.hot,
            )
        except Exception as ex:  # noqa: BLE001: Mosaic's refusal, reported
            lines = [{
                "shape": list(shape),
                "error": f"{type(ex).__name__}: {ex}"[:600],
            }]
        for line in lines:
            print(json.dumps({"geometry": geometry, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
