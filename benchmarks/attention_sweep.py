"""Time the three flash kernels alone, on the chip, at one attention shape.

A builder's tool for tuning a kernel: its times are a kernel's alone, never
a ledger number (``perf/run.py`` is the benchmark; nothing under ``perf/``
imports this).  It runs
``flash_attention`` forward + gradients at ``--shape B,S,H,D`` (``D`` as
``192:128`` for scores of 192 beside values of 128: latent attention; ``H``
as ``32/4`` for 32 query heads over 4 key/value heads) in
``--dtype`` under the profiler and reads each kernel's device time from the
trace by the name its ``pallas_call`` carries (``flash_fwd``, ``flash_dq``,
``flash_dkv``), once per geometry in ``--sweep``.  A geometry is
``block_q,block_k,chunk``: the blocks are ``flash_attention``'s own
arguments, the rows of a staged chunk replace the module's constant
(``_CHUNK_BYTES``; ``_SEQ_CHUNK`` in a copy from before PR 28) for the
call.  The kernels are independent calls, so the best geometry is read per
kernel from the table.  ``--impl path/to/attention.py`` times another copy
of the module (the parent commit's, say) in the same process.

    python benchmarks/attention_sweep.py --shape 1,8192,12,64 \\
        --sweep "512,512,2048;1024,512,2048" [--impl chip_parent/...py]

PR 36 (heads read out of the layer's own layout) ran, parent's copy beside
the change in one call:

    python benchmarks/attention_sweep.py --shape 8,1024,12,64 \
        [--impl chip_parent/elasticdl_tpu/ops/attention.py]
    python benchmarks/attention_sweep.py --shape 1,8192,12,64 \
        [--impl chip_parent/elasticdl_tpu/ops/attention.py]

One JSON line per (implementation, geometry): ``flash_layout`` (``"lanes"``
or ``"folded"``: how the kernels address a head at this shape), milliseconds
a call for each kernel and each kernel's share of the bf16 peak, counted as
``perf/kernel_rooflines.py`` counts it (a third of the analytic causal
attention FLOPs a kernel; the recomputed scores are not counted).  A
geometry Mosaic refuses is reported with its error, not skipped in silence.
``--topk N`` times the SELECTED-SET kernels alone (``dsa_fwd``, ``dsa_dq``,
``dsa_dkv``: the same three over a mask, ``selected_flash_attention``) at
that shape, over the set an indexer of 16 heads of 64 with seeded weights
selects (``ops/sparse_attention.py::index_select``, outside the timed
calls), and counts the selected pairs' FLOPs, as ``perf/dsa_rooflines.py``
does; its first line is the selection alone, search then check
(``dsa_index`` and ``dsa_index_hinted`` on the first's threshold and the same
operands: the two times side by side, the share of blocks whose tie search
ran and of blocks whose hint held, which must be all; exit 1 otherwise;
``--select-impl`` times another copy of ``ops/sparse_attention.py``), and
its second the indexer's loss alone (``dsa_kl``: the value variant and the
gradient variant, a call of each):

    python benchmarks/attention_sweep.py --shape 1,16384,32/4,128 --topk 2048

``--window N`` times the WINDOW kernels alone (``swa_fwd``, ``swa_dq``,
``swa_dkv``: the same three where a query reads its last ``N`` keys,
``flash_attention(..., window=N)``), counts the FLOPs of the pairs inside
the window, as ``perf/window_rooflines.py`` does, and prints the block plan
beside the times (``blocks``: ``flash_block_plan``'s visited, masked and
skipped a head at the geometry's blocks), so that the window against the
dense kernels at one shape is two calls whose ratio the plan predicts:

    python benchmarks/attention_sweep.py --shape 1,16384,32/4,128 --window 2048

Exits 3 where JAX finds no TPU: a time from the CPU is not a kernel time.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
SELECTED_KERNELS = ("dsa_fwd", "dsa_dq", "dsa_dkv")
WINDOW_KERNELS = ("swa_fwd", "swa_dq", "swa_dkv")
# (matched by substring in this order: the hinted name holds the other)
SELECTION_KERNELS = ("dsa_index_hinted", "dsa_index")


OTHER = "other_ops"


def kernel_ms(trace_dir: str, calls: int, kernels=KERNELS) -> dict:
    """Device milliseconds a call of each flash kernel, from the op line
    of the first device plane of the trace under ``trace_dir``, and of
    every other op of the program together (``other_ops``: the copies that
    fold heads on either side of a kernel, the reduction that follows a
    grouped dK/dV, the loss)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    total = dict.fromkeys(kernels + (OTHER,), 0)
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                # "%transpose_jvp_flash_dkv__.1 = ...": under a bare jit
                # the op's name wraps the kernel's in its transformations
                name = event.name.partition(" = ")[0]
                kernel = next((k for k in kernels if k in name), OTHER)
                total[kernel] += int(event.duration_ns)
        break
    return {k: ns / 1e6 / calls for k, ns in total.items()}


def traced_kernel_ms(call, calls: int, kernels) -> dict:
    """:func:`kernel_ms` of ``calls`` calls of ``call()`` (compiled before)
    under a profiler trace of their own, and the last call's result."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="attention_sweep_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = call()
            jax.block_until_ready(out)
        return kernel_ms(trace_dir, calls, kernels), out
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def load_impl(path: str | None):
    if path is None:
        from elasticdl_tpu.ops import attention

        return attention
    spec = importlib.util.spec_from_file_location(
        "attention_impl_" + re.sub(r"\W", "_", path), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def flash_layout_of(module, q, k, v) -> str:
    """How ``module``'s kernels address a head at these shapes; a copy from
    before PR 36 has only the folded form."""
    layout = getattr(module, "flash_layout", None)
    return layout(q, k, v) if layout else "folded"


def time_geometry(
    module, geometry, shape, dtype, causal, calls, kv_heads=0, topk=0,
    window=0,
):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    d_qk, d_v = shape[3:]
    batch, seq, heads = shape[:3]
    q, k, v, w = (
        jax.random.normal(
            key, (batch, seq, h, d), jnp.float32
        ).astype(dtype)
        for key, h, d in zip(
            keys, (heads, kv_heads or heads, kv_heads or heads, heads),
            (d_qk, d_qk, d_v, d_v),
        )
    )
    masks = ()
    if topk:
        from elasticdl_tpu.ops import sparse_attention as sparse_ops

        qi, ki, wi = indexer_operands(keys[4:], batch, seq, dtype)
        mask = jax.jit(
            lambda qi, ki, wi: sparse_ops.index_select(qi, ki, wi, topk)[0]
        )(qi, ki, wi)
        masks = (mask, sparse_ops.transpose_mask(mask))
    kw = {"window": window} if window else {}
    chunk_name = (
        "_CHUNK_BYTES" if hasattr(module, "_CHUNK_BYTES") else "_SEQ_CHUNK"
    )
    module_chunk = getattr(module, chunk_name)
    if geometry is not None:
        block_q, block_k, chunk = geometry
        kw.update(block_q=block_q, block_k=block_k)
        if chunk_name == "_CHUNK_BYTES":
            # rows to bytes, as ``_flash_geometry`` turns them back
            chunk *= min(shape[-2:]) * dtype.itemsize
        setattr(module, chunk_name, chunk)
        # the module's jitted wrappers cache a trace by shapes and blocks,
        # which the chunk constant is not among
        jax.clear_caches()

    def loss(q, k, v):
        if topk:  # the module's own blocks: the mask's
            out, _ = module.selected_flash_attention(q, k, v, *masks)
        else:
            out = module.flash_attention(q, k, v, causal=causal, **kw)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    try:
        jax.block_until_ready(step(q, k, v))  # compiles
        ms, _ = traced_kernel_ms(
            lambda: step(q, k, v), calls, kernels_of(topk, window)
        )
        return ms, flash_layout_of(module, q, k, v)
    finally:
        setattr(module, chunk_name, module_chunk)


def kernels_of(topk: int, window: int) -> tuple:
    if topk:
        return SELECTED_KERNELS
    return WINDOW_KERNELS if window else KERNELS


def indexer_operands(keys, batch, seq, dtype):
    """A seeded indexer of 16 heads of 64: queries, its one key head, head
    weights."""
    import jax

    return (
        jax.random.normal(keys[0], (batch, seq, 16, 64)).astype(dtype),
        jax.random.normal(keys[1], (batch, seq, 64)).astype(dtype),
        jax.random.normal(keys[2], (batch, seq, 16)) / 32,
    )


def time_selection(sparse_ops, batch, seq, dtype, topk, calls) -> dict:
    """The selection alone, search then check: ``index_select_threshold``
    and ``index_select_hinted`` on its threshold and the same operands.
    Every pair of the two masks, ``lse`` and the counters must be equal and
    the hint must hold in every block; milliseconds a call of each kernel.
    A copy of the module from before PR 40 (``--select-impl``) has the
    search alone."""
    import jax
    import jax.numpy as jnp

    qi, ki, wi = indexer_operands(
        jax.random.split(jax.random.PRNGKey(0), 7)[4:], batch, seq, dtype
    )
    hinted = hasattr(sparse_ops, "index_select_hinted")

    @jax.jit
    def both(qi, ki, wi):
        if not hinted:
            _, _, kept, ties = sparse_ops.index_select(qi, ki, wi, topk)
            return {"kept_keys": jnp.mean(kept), "ties_broken": jnp.sum(ties)}
        mask, lse, kept, ties, searched, threshold = (
            sparse_ops.index_select_threshold(qi, ki, wi, topk)
        )
        again = sparse_ops.index_select_hinted(qi, ki, wi, threshold, topk)
        return {
            "kept_keys": jnp.mean(again[2]),
            "ties_broken": jnp.sum(ties),
            "tie_search_blocks": jnp.mean(searched),
            "hint_held": jnp.mean(again[4]),
            "unequal": sum(
                jnp.sum(a != b) for a, b in zip(again, (mask, lse, kept, ties))
            ),
        }

    jax.block_until_ready(both(qi, ki, wi))  # compiles
    ms, out = traced_kernel_ms(
        lambda: both(qi, ki, wi), calls, SELECTION_KERNELS
    )
    return {
        **{name: float(value) for name, value in out.items()},
        "ms": {k: round(v, 4) for k, v in ms.items() if v},
    }


def time_kl(sparse_ops, shape, kv_heads, dtype, topk, calls) -> dict:
    """The indexer's loss alone (``dsa_kl``) over the same seeded operands
    and set: milliseconds a call of the value variant and of the gradient
    variant, each in a program of its own (both carry the one name), and
    whether the gradient variant's value is the value variant's."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import attention

    batch, seq, heads, d_qk, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    q, k = (
        jax.random.normal(key, (batch, seq, h, d_qk), jnp.float32).astype(dtype)
        for key, h in zip(keys, (heads, kv_heads or heads))
    )
    qi, ki, wi = indexer_operands(keys[4:], batch, seq, dtype)
    mask, lse_i, _, _ = jax.jit(
        lambda qi, ki, wi: sparse_ops.index_select(qi, ki, wi, topk)
    )(qi, ki, wi)
    _, lse = jax.jit(attention.selected_flash_attention)(
        q, k, k, mask, sparse_ops.transpose_mask(mask)
    )
    operands = (q, k, lse, mask, qi, ki, wi, lse_i)
    programs = {
        "value": jax.jit(sparse_ops.indexer_kl),
        # (the call behind ``indexer_kl``'s backward rule and, since PR 43,
        # behind ``indexer_kl_with_grads``: a copy from before has it too)
        "with_grads": jax.jit(
            lambda *xs: sparse_ops._kl_call(*xs, None, None, True)
        ),
    }
    ms, made = {}, {}
    for name, program in programs.items():
        jax.block_until_ready(program(*operands))  # compiles
        times, made[name] = traced_kernel_ms(
            lambda: program(*operands), calls, (sparse_ops.INDEXER_KL,)
        )
        ms[name] = round(times[sparse_ops.INDEXER_KL], 4)
    return {
        "ms": ms,
        "kl": float(made["value"]),
        "same_value": bool(made["value"] == made["with_grads"][0]),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--shape", default="1,8192,12,64",
        help="B,S,H,D; D as Dqk:Dv, H as H/KV for grouped heads",
    )
    parser.add_argument(
        "--topk", type=int, default=0,
        help="> 0: the selected-set kernels over a seeded indexer's set",
    )
    parser.add_argument(
        "--window", type=int, default=0,
        help="> 0: the window kernels, a query reading its last N keys",
    )
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument(
        "--sweep", default="",
        help="'block_q,block_k,chunk;...'; empty: the module's own choice",
    )
    parser.add_argument("--impl", default=None, help="another attention.py")
    parser.add_argument(
        "--select-impl", default=None,
        help="with --topk: another sparse_attention.py for the selection",
    )
    parser.add_argument("--label", default=None)
    parser.add_argument("--non-causal", action="store_true")
    parser.add_argument("--calls", type=int, default=5)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU: {device.platform}", file=sys.stderr)
        return 3
    from perf.peaks import peaks_for  # the benchmark's one table of peaks

    peak = peaks_for(device.device_kind)["bf16_flops_per_s"]
    batch, seq, grouped, widths = args.shape.split(",")
    d_qk, d_v = (int(x) for x in (widths.split(":") * 2)[:2])
    heads, kv_heads = (int(x) for x in (grouped.split("/") * 2)[:2])
    batch, seq = int(batch), int(seq)
    shape = (batch, seq, heads, d_qk, d_v)
    causal = not args.non_causal
    # forward + backward: six matmuls of 2*S*S*D a head (three over the
    # scores' width, three over the values'), half of them under the
    # diagonal; a third of that a kernel
    kernel_flops = 6 * batch * heads * seq * seq * (d_qk + d_v) / 3
    if causal:
        kernel_flops /= 2
    if args.topk:  # the selected pairs, sum_t min(t + 1, topk), of seq^2
        kept = min(args.topk, seq)
        pairs = kept * (kept + 1) // 2 + (seq - kept) * kept
        kernel_flops = 2 * batch * heads * pairs * (d_qk + d_v)
    if args.window and (args.topk or not causal):
        parser.error("--window goes with causal attention and without --topk")
    window = args.window if args.window < seq else 0  # no window: the dense kernels
    if window:  # the pairs inside the window, sum_t min(t + 1, window)
        pairs = window * (window + 1) // 2 + (seq - window) * window
        kernel_flops = 2 * batch * heads * pairs * (d_qk + d_v)
    kernels = kernels_of(args.topk, window)
    module = load_impl(args.impl)
    geometries = [
        tuple(int(x) for x in g.split(","))
        for g in args.sweep.split(";")
        if g.strip()
    ] or [None]
    status = 0
    if args.topk:
        if args.select_impl:
            sparse_ops = load_impl(args.select_impl)
        else:
            from elasticdl_tpu.ops import sparse_attention as sparse_ops
        selection = time_selection(
            sparse_ops, batch, seq, jnp.dtype(args.dtype), args.topk,
            args.calls,
        )
        print(
            json.dumps(
                {
                    "impl": args.select_impl
                    or "elasticdl_tpu.ops.sparse_attention",
                    "shape": [batch, seq, 16, 64], "topk": args.topk,
                    "device_kind": device.device_kind,
                    "selection": selection,
                }
            ),
            flush=True,
        )
        if selection.get("unequal") or selection.get("hint_held", 1.0) < 1.0:
            status = 1  # identical operands: the hint holds and nothing differs
        print(
            json.dumps(
                {
                    "impl": args.select_impl
                    or "elasticdl_tpu.ops.sparse_attention",
                    "shape": list(shape), "kv_heads": kv_heads,
                    "topk": args.topk, "device_kind": device.device_kind,
                    "indexer_kl": time_kl(
                        sparse_ops, shape, kv_heads, jnp.dtype(args.dtype),
                        args.topk, args.calls,
                    ),
                }
            ),
            flush=True,
        )
    for geometry in geometries:
        line = {
            "impl": args.label or args.impl or "elasticdl_tpu.ops.attention",
            "shape": list(shape),
            "kv_heads": kv_heads,
            "topk": args.topk,
            "window": window,
            "dtype": args.dtype,
            "causal": causal,
            "geometry": geometry,
            "device_kind": device.device_kind,
        }
        try:
            ms, line["flash_layout"] = time_geometry(
                module, geometry, shape, jnp.dtype(args.dtype), causal,
                args.calls, kv_heads, args.topk, window,
            )
            if hasattr(module, "flash_block_plan") and not args.topk:
                blocks = tuple(
                    module._pick_block(seq, (geometry or (512, 512))[i])
                    for i in (0, 1)
                )
                line["blocks"] = module.flash_block_plan(
                    seq, seq, *blocks, causal, *((window,) if window else ())
                )
        except Exception as ex:  # noqa: BLE001 — Mosaic refuses a geometry
            line["error"] = f"{type(ex).__name__}: {str(ex)[:300]}"
        else:
            line["ms"] = {k: round(v, 4) for k, v in ms.items()}
            line["ms_total"] = round(sum(ms[k] for k in kernels), 4)
            line["roofline_pct"] = {
                k: round(100 * kernel_flops / (ms[k] / 1e3) / peak, 2)
                for k in kernels
                if ms[k]
            }
        print(json.dumps(line), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
