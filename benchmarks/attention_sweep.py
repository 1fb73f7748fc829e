"""Sweep the flash-attention kernel geometry on a bench transformer config.

VERDICT r4 weak #6's alternative acceptance is taking the seq-8192
config's exposed headroom (block size / grid / VMEM knobs in
``ops/attention.py``).  This sweeps (``_SEQ_CHUNK``, ``block_q``,
``block_k``) on the FULL train step of a bench config — the same
fori_loop + data-dependent-readback timing as bench.py, so dispatch
latency and unreliable device sync cannot inflate anything — and prints
one JSON line of tokens/sec per geometry, best first.

Usage:
  python benchmarks/attention_sweep.py [config_name] [--steps N]
  (default config: transformer_seq8192)

Each geometry recompiles the step, so the sweep list is small and
targeted.  The current defaults
(chunk 2048, 512x512 blocks) are the r3-measured optimum; this exists
to re-test them at seq 8192 where the backward's chunk-carried scratch
changes the picture.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# (seq_chunk, block_q, block_k)
SWEEP = [
    (2048, 512, 512),  # current defaults (r3 optimum at seq <= 2048)
    (2048, 1024, 512),
    (2048, 512, 1024),
    (4096, 512, 512),
    (4096, 1024, 1024),
    (1024, 512, 512),
]


def main() -> int:
    args = sys.argv[1:]
    steps = 10
    positional = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--steps":
            i += 1
            steps = int(args[i])
        elif a.startswith("--steps="):
            steps = int(a.split("=", 1)[1])
        elif not a.startswith("--"):
            positional.append(a)
        i += 1
    name = positional[0] if positional else "transformer_seq8192"

    import jax

    import bench
    from elasticdl_tpu.ops import attention as attention_mod
    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.parallel.mesh import MeshConfig
    from elasticdl_tpu.trainer.local_executor import build_optimizer
    from elasticdl_tpu.utils.model_utils import get_model_spec

    mesh = MeshConfig.from_string("").create()
    cfg = bench._configs(max(1, mesh.devices.size))[name]
    spec = get_model_spec(
        "", cfg["model_def"], model_params=cfg.get("model_params")
    )
    rules = ()
    if spec.sharding_rules is not None:
        rules = tuple(spec.sharding_rules(mesh))

    orig_flash = attention_mod.flash_attention
    orig_chunk = attention_mod._SEQ_CHUNK
    tokens_per_step = cfg["batch"] * cfg.get("tokens_per_sample", 1)
    results = []
    for seq_chunk, bq, bk in SWEEP:
        attention_mod._SEQ_CHUNK = seq_chunk

        def patched(q, k, v, **kw):
            kw.setdefault("block_q", bq)  # noqa: B023 — rebound per loop
            kw.setdefault("block_k", bk)  # noqa: B023
            return orig_flash(q, k, v, **kw)

        attention_mod.flash_attention = patched
        try:
            trainer = SPMDTrainer(
                mesh,
                spec.build_model(),
                spec.loss,
                build_optimizer(spec, None),
                cfg["features"],
                rules=rules,
                compute_dtype="bfloat16",
            )
            pf = trainer.place_batch(cfg["features"])
            pl = trainer.place_batch(cfg["labels"])
            step_fn = trainer._train_step

            def many(state, f, l):
                return jax.lax.fori_loop(
                    0, steps, lambda _i, s: step_fn(s, f, l)[0], state
                )

            compiled = (
                jax.jit(many, donate_argnums=(0,))
                .lower(trainer.state, pf, pl)
                .compile()
            )
            state = compiled(trainer.state, pf, pl)  # warm
            int(jax.device_get(state.step))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                state = compiled(state, pf, pl)
                int(jax.device_get(state.step))
                best = min(best, time.perf_counter() - t0)
            rate = steps * tokens_per_step / best
            results.append(
                {
                    "seq_chunk": seq_chunk,
                    "block_q": bq,
                    "block_k": bk,
                    "tokens_per_sec_per_chip": round(rate),
                }
            )
            print(
                f"sweep: chunk={seq_chunk} bq={bq} bk={bk} -> "
                f"{rate:.0f} tok/s",
                file=sys.stderr,
            )
        except Exception as ex:  # noqa: BLE001 — a geometry may OOM VMEM
            results.append(
                {
                    "seq_chunk": seq_chunk,
                    "block_q": bq,
                    "block_k": bk,
                    "error": str(ex)[:160],
                }
            )
            print(
                f"sweep: chunk={seq_chunk} bq={bq} bk={bk} FAILED: "
                f"{str(ex)[:160]}",
                file=sys.stderr,
            )
        finally:
            attention_mod.flash_attention = orig_flash
            attention_mod._SEQ_CHUNK = orig_chunk

    results.sort(
        key=lambda r: -(r.get("tokens_per_sec_per_chip") or 0)
    )
    print(json.dumps({"config": name, "steps": steps, "sweep": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
