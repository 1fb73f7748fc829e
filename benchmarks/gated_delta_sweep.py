"""The two gated-delta-rule kernels alone, on the chip, at one shape: their
values and gradients against the plain chunked form and against the float32
recurrence a step at a time, and their times.

A builder's tool for tuning a kernel: its times are a kernel's alone, never
a ledger number (``perf/run.py`` is the benchmark; nothing under ``perf/``
imports this).  It runs ``ops/gated_delta.py::gated_delta_chunked`` forward +
gradients at ``--shape B,T,Hk/Hv,D`` in bfloat16, once a chunk size of
``--chunks``, under the profiler, and reads each kernel's device time from
the trace by the name its ``pallas_call`` carries (``gdn_fwd``, ``gdn_bwd``;
``benchmarks/attention_sweep.py``'s reader).  The comparison runs on the
first ``--check_steps`` steps of the same inputs (the recurrence is a
``lax.scan`` over tokens): the kernels against ``_chunked_plain`` (the same
roundings, ``T`` by a triangular solve: agreement to a bfloat16 rounding of
the results) and against the recurrence in float32 on the same
bfloat16-rounded inputs (what the chunked form's own roundings cost).

    python benchmarks/gated_delta_sweep.py --shape 1,16384,16/32,128 \\
        --chunks 64,128

One JSON line a chunk size: milliseconds a call for each kernel and for every
other op of the program, each kernel's share of its roofline as
``perf/gdn_rooflines.py`` counts it, and the relative errors (norm of the
difference over the norm) of ``o`` and of the five gradients.  Exits 3
without a TPU, 1 where an error passes ``--limit``."""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

NAMES = ("q", "k", "v", "g", "beta")


def sequential(q, k, v, g, beta):
    """The recurrence a token at a time in float32: the plain reference's own
    (``perf/references/qwen3_next.py::delta_rule``), a key head repeated for
    the value heads it serves."""
    import jax
    import jax.numpy as jnp

    from perf.references import qwen3_next

    per = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(x.astype(jnp.float32), per, axis=2) for x in (q, k))
    with jax.default_matmul_precision("highest"):
        return qwen3_next.delta_rule(q, k, v.astype(jnp.float32), g, beta)


def inputs(batch, steps, keys, values, width, seed=0):
    """L2-normalised ``q`` (over ``sqrt(width)``) and ``k``, ``v``, a decay's
    log as the layer's initialisers give it, ``beta`` in (0, 1)."""
    import jax
    import jax.numpy as jnp

    rng = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(rng[0], (batch, steps, keys, width))) * width**-0.5
    k = unit(jax.random.normal(rng[1], (batch, steps, keys, width)))
    v = jax.random.normal(rng[2], (batch, steps, values, width))
    a = jnp.exp(jax.random.uniform(rng[3], (values,), minval=0.0, maxval=2.77))
    dt = jnp.exp(
        jax.random.uniform(rng[4], (batch, steps, values), minval=-6.9, maxval=-2.3)
    )
    g = -a * dt
    beta = jax.nn.sigmoid(jax.random.normal(rng[5], (batch, steps, values)))
    bf16 = jnp.bfloat16
    return q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta


def relative(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shape", default="1,16384,16/32,128")
    parser.add_argument("--chunks", default="64,128")
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--check_steps", type=int, default=2048)
    parser.add_argument("--limit", type=float, default=0.03)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU", "backend": jax.default_backend()}))
        return 3
    from attention_sweep import traced_kernel_ms

    from elasticdl_tpu.ops import gated_delta as ops
    from perf import gdn_rooflines, peaks

    batch, steps, heads, width = args.shape.split(",")
    keys, values = (int(n) for n in heads.split("/"))
    batch, steps, width = int(batch), int(steps), int(width)
    operands = inputs(batch, steps, keys, values, width)
    short = tuple(x[:, : args.check_steps] for x in operands)
    weight = jax.random.normal(
        jax.random.PRNGKey(9), (batch, steps, values, width), jnp.float32
    )
    table = peaks.peaks_for(jax.devices()[0].device_kind)
    spec = {
        "linear_key_heads": keys, "linear_value_heads": values,
        "linear_key_dim": width, "linear_value_dim": width,
    }

    def with_grads(function, weight):
        def loss(*operands):
            o = function(*operands)
            return jnp.sum(o.astype(jnp.float32) * weight), o

        return jax.jit(jax.grad(loss, argnums=tuple(range(5)), has_aux=True))

    want_grads, want = with_grads(sequential, weight[:, : args.check_steps])(*short)
    failed = False
    for chunk in (int(c) for c in args.chunks.split(",")):
        kernels = functools.partial(
            ops.gated_delta_chunked, chunk=chunk, interpret=False
        )

        def plain(q, k, v, g, beta, chunk=chunk):
            flat = [x.reshape(*x.shape[:2], -1) for x in (q, k, v)]
            gamma = jnp.cumsum(
                g.reshape(g.shape[0], -1, chunk, g.shape[2]), axis=2
            ).reshape(g.shape)
            return ops._chunked_plain(*flat, gamma, beta, keys, chunk).reshape(
                v.shape
            )

        line = {
            "shape": args.shape, "chunk": chunk,
            "device": jax.devices()[0].device_kind,
        }
        try:
            timed = with_grads(kernels, weight)
            jax.block_until_ready(timed(*operands))
            ms, _ = traced_kernel_ms(
                lambda: timed(*operands), args.calls, gdn_rooflines.KERNELS
            )
            line["ms"] = {name: round(value, 4) for name, value in ms.items()}
            line["roofline_pct"] = {
                kernel: round(
                    100.0 * gdn_rooflines.least_seconds(
                        kernel, batch * steps, {**spec, "chunk": chunk}, table
                    )["least_s"] / (ms[kernel] / 1e3), 2,
                )
                for kernel in gdn_rooflines.KERNELS
            }
            got_grads, got = with_grads(kernels, weight[:, : args.check_steps])(
                *short
            )
            plain_grads, plain_o = with_grads(
                plain, weight[:, : args.check_steps]
            )(*short)
            for other, o, grads in (
                ("plain", plain_o, plain_grads), ("recurrence", want, want_grads),
            ):
                errors = {"o": relative(got, o)}
                errors.update(
                    (f"d{name}", relative(a, b))
                    for name, a, b in zip(NAMES, got_grads, grads)
                )
                line[f"against_{other}"] = {
                    name: round(value, 5) for name, value in errors.items()
                }
                failed |= max(errors.values()) > args.limit
        except Exception as error:  # a geometry Mosaic refuses is reported
            line["error"] = f"{type(error).__name__}: {str(error)[:400]}"
            failed = True
        print(json.dumps(line), flush=True)
    return int(failed)


if __name__ == "__main__":
    sys.exit(main())
