"""Time the rotary positions alone, on the chip, at one attention shape: the
single-pass kernel (``ops/rotary.py``) beside the plain ``jnp`` form
(``layers/attention.py::rope_plain``), forward and backward.

A builder's tool for choosing the kernel's tile: its times are one pass's
alone, never a ledger number (``perf/run.py`` is the benchmark; nothing
under ``perf/`` imports this).  At ``--shape B,S,H/KV,W`` (``H/KV``: query
heads over key heads, each rotated in a call of its own, as a layer does)
in ``--dtype`` it runs each form's forward and its vector-Jacobian product
under the profiler, on arrays folded ``(batch, heads, tokens, width)`` as the
attention kernels take them, and reads the device time from the trace with
``attention_sweep.py``'s reader: the kernels by the names their
``pallas_call``s carry (``rope_fwd``, ``rope_bwd``), the plain form as every
op of its program, the angles' too.
A geometry of ``--sweep`` is ``rows,lanes``: the rows of a grid step and the
lanes of its heads together at most, in place of the module's constants.

    python benchmarks/rope_sweep.py --shape 1,16384,32/4,128 \\
        --sweep "256,1024;512,1024;512,2048;1024,512;1024,1024"

``--width N`` rotates ``N`` lanes a head in place of the shape's ``W`` (64:
an array's one head; several such heads the kernel does not take, PR 62 having
measured them: docs/designs/rotary_kernel.md, and the plain form's line
stands alone), ``--interleave 1`` by adjacent pairs, and
``--tail NOPE`` gives the query heads ``NOPE`` lanes that pass through
ahead of the rotating ones (the key heads have none: latent attention's q
at ``--shape 1,8192,32/1,64 --interleave 1 --tail 128`` beside its one
shared rotary key); the plain form there slices the tail out, turns it and
joins it back, as the layer did.

One JSON line per array and form: milliseconds a call and GB/s counted as
the array read once and written once (the tables' bytes are the kernel's to
pay and not counted), and whether the kernel's values and gradient are the
plain form's bit for bit.  A geometry Mosaic refuses is reported with its
error, not skipped in silence.

Exits 3 where JAX finds no TPU: a time from the CPU is not a kernel time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

KERNELS = ("rope_fwd", "rope_bwd")
PLAIN = "plain"


def time_array(
    batch, seq, heads, width, dtype, mrope, geometry, calls, time_plain=True,
    interleave=False, skip=0,
):
    """One array's lines: the kernel's and, with ``time_plain``, the plain
    form's (it is run for the comparison either way).  ``width`` lanes of a
    head rotate behind ``skip`` that pass through."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.layers.attention import rope_plain
    from elasticdl_tpu.ops import rotary

    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    from attention_sweep import OTHER, traced_kernel_ms  # the trace's reader

    keys = jax.random.split(jax.random.PRNGKey(heads), 3)
    x, g = (
        jax.random.normal(
            key, (batch, heads, seq, skip + width), jnp.float32
        ).astype(dtype)
        for key in keys[:2]
    )
    sections = ()
    positions = jnp.arange(seq)
    if mrope:
        sections = (width // 8, 3 * width // 16, 3 * width // 16)
        positions = jax.random.randint(keys[2], (batch, 3, seq), 0, seq)
    theta = 1e4

    def kernel(x):
        return rotary.rotate(
            x, positions, theta, sections, interleave, skip, False
        )

    def plain(x):
        rows = x.transpose(0, 2, 1, 3)
        turned = rope_plain(
            rows[..., skip:], positions, theta, interleave, sections
        )
        if skip:
            turned = jnp.concatenate([rows[..., :skip], turned], axis=-1)
        return turned.transpose(0, 2, 1, 3)

    def both_ways(form):
        return jax.jit(lambda x, g: (form(x), jax.vjp(form, x)[1](g)[0]))

    constants = (rotary._ROWS, rotary._BLOCK_LANES)
    if geometry is not None:
        rotary._ROWS, rotary._BLOCK_LANES = geometry
    lines = []
    nbytes = 2 * x.size * x.dtype.itemsize
    try:
        results = {}
        forms = (("kernel", kernel), (PLAIN, plain))
        tile = rotary.rotate_tile((batch, seq, heads, skip + width), skip)
        if tile is None:
            forms = forms[1:]  # a shape the kernel does not take
        for name, form in forms:
            step = both_ways(form)
            results[name] = jax.block_until_ready(step(x, g))  # compiles
            if name == PLAIN and not time_plain:
                continue
            # the plain form's forward and backward are not told apart by
            # name: every op of its program together, two passes
            names = KERNELS if name == "kernel" else ()
            ms, _ = traced_kernel_ms(lambda: step(x, g), calls, names)
            parts = {k: ms[k] for k in names} or {"fwd+bwd": ms[OTHER]}
            passes = len(KERNELS) // len(parts)
            lines.append({
                "array": [batch, heads, seq, skip + width], "form": name,
                "interleave": interleave, "skip": skip,
                "tile": list(tile) if names else None,
                "ms": {k: round(v, 4) for k, v in parts.items()},
                "gb_per_s": {
                    k: round(passes * nbytes / v / 1e6, 1)
                    for k, v in parts.items() if v
                },
                "other_ops_ms": round(ms[OTHER], 4) if names else 0.0,
            })
        if "kernel" in results:
            lines[0]["equal_values"] = bool(
                jnp.array_equal(results["kernel"][0], results[PLAIN][0])
            )
            lines[0]["equal_gradient"] = bool(
                jnp.array_equal(results["kernel"][1], results[PLAIN][1])
            )
    finally:
        rotary._ROWS, rotary._BLOCK_LANES = constants
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", default="1,16384,32/4,128")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument(
        "--sweep", default="",
        help='"rows,lanes;..."; empty: the module\'s own constants',
    )
    parser.add_argument("--mrope", type=int, default=0)
    parser.add_argument(
        "--width", type=int, default=0,
        help="lanes of a head that rotate; 0: the shape's W",
    )
    parser.add_argument("--interleave", type=int, default=0)
    parser.add_argument(
        "--tail", type=int, default=0,
        help="lanes of a query head that pass through ahead of the others",
    )
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("rope_sweep: no TPU; a CPU time is not a kernel time",
              file=sys.stderr)
        return 3
    batch, seq, heads, width = args.shape.split(",")
    width = args.width or int(width)
    heads, _, kv_heads = heads.partition("/")
    dtype = jnp.dtype(args.dtype)
    geometries = [
        tuple(int(n) for n in geometry.split(","))
        for geometry in args.sweep.split(";") if geometry
    ] or [None]
    for geometry in geometries:
        for h, skip in ((heads, args.tail), (kv_heads, 0)):
            if not h:
                continue
            try:
                lines = time_array(
                    int(batch), int(seq), int(h), width, dtype,
                    bool(args.mrope), geometry, args.calls,
                    time_plain=geometry == geometries[0],
                    interleave=bool(args.interleave), skip=skip,
                )
            except Exception as ex:  # noqa: BLE001: Mosaic's refusal, reported
                lines = [{
                    "array": [int(batch), int(h), int(seq), skip + width],
                    "geometry": geometry,
                    "error": f"{type(ex).__name__}: {ex}"[:600],
                }]
            for line in lines:
                print(json.dumps({"geometry": geometry, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
