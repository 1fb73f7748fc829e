"""A drive of the three dispatch disciplines side by side, the only one in
the repo; its CPU output is a count of gaps and stalls, not a ledger number
(``perf/run.py`` is the benchmark), and it goes or stays with ROADMAP D2.

Measures the host-side dispatch overhead the device plane never sees:
the steady-state gap between dispatches WITHIN a task and the boundary
stall BETWEEN tasks, across the three execution disciplines — serial,
``--device_prefetch``, and ``--device_prefetch --boundary_fusion``.

Usage:
  python benchmarks/dispatch_overhead_bench.py [--tasks N] [--batches N]
      [--rows N] [--dim N] [--k N] [--iters N] [--fetch-ms F]
      [--bookkeeping-ms F] [--pipeline-depth N]

CPU-runnable by construction: the "model" is a jitted tanh/matmul tower
over ``(rows, dim)`` float32 batches — enough device work for overlap
to matter without a real model compile — the host stream sleeps
``fetch_ms`` per batch (standing in for record decode) and the
per-task boundary bookkeeping sleeps ``bookkeeping_ms`` (standing in
for the report RPC + milestone checks + memory sample).  All three
windows drive the REAL runtimes (``stacking.run_stacked_steps``,
``device_pipeline.run_pipelined_steps`` / ``run_pipelined_task_stream``)
with identical data, so the numbers isolate the dispatch-loop
discipline, not the workload.

Prints ONE JSON line:

  {"config": {...},
   "windows": {<mode>: {"wall_ms", "records_per_sec", "dispatches",
                        "boundaries", "boundary_stall_ms",
                        "mean_boundary_stall_ms",
                        "median_dispatch_gap_ms"}},
   "boundary_stall_vs_serial": {"prefetch": r, "fused": r}}

where ``boundary_stall_ms`` is the heartbeat counter's per-window delta
(the same number production ships and mirrors as
``elasticdl_boundary_stall_ms_total``) and ``median_dispatch_gap_ms``
is the consumer-thread gap between consecutive intra-task dispatches.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class _BenchTrainer:
    """The minimal trainer surface the canonical-shape dispatch loops
    touch: pad/mask policy, placement, and the two jitted programs
    (weighted single step + stacked scan stand-in).  Like a real
    trainer it CARRIES STATE across dispatches, so the jitted chain
    serializes on device and blocking on the final state at a window's
    end waits for every dispatch in the window — without it, XLA's
    async dispatch would let a window's compute leak past its wall
    clock (and into the next window's measurements)."""

    def __init__(self, rows: int, dim: int, iters: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        self._jax = jax
        self._np = np
        w = (np.eye(dim) * 0.9 + 0.01).astype(np.float32)
        self._w = jax.device_put(w)
        self.state = jax.device_put(np.zeros((dim,), np.float32))

        def _tower(x):
            for _ in range(iters):
                x = jnp.tanh(x @ self._w)
            return x

        def _step(state, f, l, m):
            return state + _tower(f).sum(0) * (l.sum() + m.sum()) * 1e-6

        def _stacked(state, f, l, wts):
            flat = f.reshape((-1, f.shape[-1]))
            return state + _tower(flat).sum(0) * (
                l.sum() + wts.sum()
            ) * 1e-6

        self._step = jax.jit(_step)
        self._stacked = jax.jit(_stacked)

    def pad_to(self, x, rows: int):
        n = x.shape[0]
        if n == rows:
            return x
        pad = self._np.zeros((rows - n,) + x.shape[1:], x.dtype)
        return self._np.concatenate([x, pad])

    def row_mask(self, n: int, rows: int):
        mask = self._np.zeros((rows,), self._np.float32)
        mask[:n] = 1.0
        return mask

    def place_batch(self, x):
        return self._jax.device_put(x)

    def place_stacked(self, x):
        return self._jax.device_put(x)

    def train_step(self, f, l, m):
        self.state = self._step(self.state, f, l, m)
        return self.state

    def train_steps_stacked(self, f, l, wts):
        self.state = self._stacked(self.state, f, l, wts)
        return self.state

    def sync(self):
        self._jax.block_until_ready(self.state)


def _window_stats(
    wall_secs: float, stamps, dispatches_per_task: int,
    records: int, before: dict, after: dict,
):
    boundaries = after.get("boundaries", 0) - before.get("boundaries", 0)
    stall = after.get("boundary_stall_ms", 0) - before.get(
        "boundary_stall_ms", 0
    )
    intra = [
        (b - a) * 1000.0
        for i, (a, b) in enumerate(zip(stamps, stamps[1:]))
        # gaps that cross a task boundary are the boundary stall's job
        if (i + 1) % dispatches_per_task != 0
    ]
    return {
        "wall_ms": round(wall_secs * 1000.0, 1),
        "records_per_sec": round(records / wall_secs, 1),
        "dispatches": len(stamps),
        "boundaries": boundaries,
        "boundary_stall_ms": stall,
        "mean_boundary_stall_ms": round(stall / boundaries, 2)
        if boundaries
        else None,
        "median_dispatch_gap_ms": round(statistics.median(intra), 2)
        if intra
        else None,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--tasks", type=int, default=6)
    parser.add_argument("--batches", type=int, default=8)
    parser.add_argument("--rows", type=int, default=256)
    parser.add_argument("--dim", type=int, default=256)
    parser.add_argument("--k", type=int, default=2)
    parser.add_argument("--iters", type=int, default=8)
    parser.add_argument("--fetch-ms", type=float, default=2.0)
    parser.add_argument("--bookkeeping-ms", type=float, default=5.0)
    parser.add_argument("--pipeline-depth", type=int, default=None)
    args = parser.parse_args()
    if args.batches % args.k:
        parser.error("--batches must be a multiple of --k (full groups "
                     "only: partial-group handling is parity-pinned in "
                     "tests, not measured here)")

    import numpy as np

    from elasticdl_tpu.trainer import device_pipeline as dp
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    rng = np.random.default_rng(7)
    features = rng.standard_normal(
        (args.rows, args.dim), dtype=np.float32
    )
    labels = rng.standard_normal((args.rows,), dtype=np.float32)
    trainer = _BenchTrainer(args.rows, args.dim, args.iters)
    get_trainer = lambda: trainer  # noqa: E731

    def batches():
        for _ in range(args.batches):
            time.sleep(args.fetch_ms / 1000.0)
            yield features, labels

    def bookkeeping():
        time.sleep(args.bookkeeping_ms / 1000.0)

    # warm both the jitted program and the staging totals (arms the
    # boundary clock for the serial window too, so all three windows
    # measure with identical instrumentation state)
    dp.run_pipelined_steps(
        get_trainer, batches(), args.k, canonical_rows=args.rows
    )
    trainer.sync()
    dp.clear_boundary_mark()

    dispatches_per_task = args.batches // args.k
    records_per_window = args.tasks * args.batches * args.rows
    windows = {}

    for mode in ("serial", "prefetch", "fused"):
        stamps: list = []
        post = lambda: stamps.append(time.monotonic())  # noqa: E731
        before = dp.heartbeat_snapshot()
        t0 = time.monotonic()
        if mode == "fused":
            dp.run_pipelined_task_stream(
                get_trainer,
                ((i, None, batches()) for i in range(args.tasks)),
                args.k,
                post_group=post,
                canonical_rows=args.rows,
                task_done=lambda _tid, _task, _n: bookkeeping(),
                pipeline_depth=args.pipeline_depth,
            )
        else:
            for _ in range(args.tasks):
                run_stacked_steps(
                    get_trainer,
                    batches(),
                    args.k,
                    post_group=post,
                    canonical_rows=args.rows,
                    device_prefetch=(mode == "prefetch"),
                    pipeline_depth=args.pipeline_depth,
                )
                # runtime arm order: mark as soon as the task drained,
                # so the bookkeeping is inside the measured gap
                dp.note_task_boundary()
                bookkeeping()
        trainer.sync()
        wall = time.monotonic() - t0
        dp.clear_boundary_mark()
        windows[mode] = _window_stats(
            wall, stamps, dispatches_per_task,
            records_per_window, before, dp.heartbeat_snapshot(),
        )

    serial_stall = windows["serial"]["boundary_stall_ms"] or 1
    out = {
        "config": {
            "tasks": args.tasks,
            "batches_per_task": args.batches,
            "rows": args.rows,
            "dim": args.dim,
            "k": args.k,
            "iters": args.iters,
            "fetch_ms": args.fetch_ms,
            "bookkeeping_ms": args.bookkeeping_ms,
            "pipeline_depth": args.pipeline_depth
            or dp.resolve_pipeline_depth(),
        },
        "windows": windows,
        "boundary_stall_vs_serial": {
            mode: round(
                windows[mode]["boundary_stall_ms"] / serial_stall, 3
            )
            for mode in ("prefetch", "fused")
        },
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
