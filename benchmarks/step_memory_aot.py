"""What a cell's train step holds at its peak, without a chip:

    JAX_PLATFORMS=cpu python3 benchmarks/step_memory_aot.py --workload ouro_2p6b_seq4096x2 [--depth N] [--json PATH]

builds the step the way ``perf/aot_memory.py`` does (by running it: the
program it compiles for the described ``v5e:2x2`` is kept on the way) and
prints ``telemetry/op_scopes.py::live_bytes`` of it: bytes at the peak by
owner x phase x role, the instruction at the peak, and the reading over
XLA's own peak.  A builder's tool whose output is no ledger number: what it
prints is what the compiler plans, never what a chip's allocator read
(``perf/run.py --trace 1`` with ``perf/layer_metrics/memory_entries.json``
appended does that)."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--depth", type=int, default=3, help="0: whole parts")
    parser.add_argument("--json", default=None, help="write the reading here")
    args, passed_on = parser.parse_known_args()
    from perf import aot_memory  # pins JAX to the CPU before it starts

    import jax

    from elasticdl_tpu.telemetry import op_scopes

    compiled = []
    compile_ = jax.stages.Lowered.compile

    def kept(lowered, *more, **options):
        compiled.append(compile_(lowered, *more, **options))
        return compiled[-1]

    jax.stages.Lowered.compile = kept
    sys.argv = [sys.argv[0], *passed_on]
    try:
        aot_memory.main()
    finally:
        jax.stages.Lowered.compile = compile_
    reading = op_scopes.live_bytes(compiled[-1])
    if reading is None:
        print("the compiled text is not scheduled: nothing to read", file=sys.stderr)
        return 1
    print(op_scopes.memory_table(reading, args.depth))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(reading, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
