"""Benchmark: training throughput of the framework's SPMD step on real
hardware, across the BASELINE.md model set.

Prints ONE COMPACT JSON line (last line of stdout, <= ~1500 bytes —
the driver records only a ~2000-char stdout tail, and r4's 4KB line
got truncated into an unparseable artifact):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "detail": "BENCH_full.json", "models": {<short-key summaries>}}
and writes the full per-config detail (all measured fields, error
texts, budget decompositions, the short-key legend) to
``BENCH_full.json`` next to this file.

Headline metric: ResNet-50 (cifar10 shapes) samples/sec/chip — the
strongest MXU witness of the set (VERDICT r1) — with per-model extras for
the MNIST CNN and DeepFM (sharded-embedding path) plus MFU where the
device's peak FLOPs are known.

``vs_baseline`` anchors come from ``benchmarks/baseline.json``, measured
by the in-repo ``benchmarks/baseline_tf.py``: the reference's
training-loop design (TF2 ``tf.function`` GradientTape step,
``elasticdl/python/worker/worker.py:656-669``) on host CPU — the
reference trains on CPU pods (base image ``image_builder.py:206-208``).
Re-measure any time with ``python benchmarks/baseline_tf.py``.

MEASUREMENT NOTE: the step loop runs STEPS steps inside one compiled
``fori_loop`` (dispatch amortized, nothing elidable — each iteration's
state feeds the next) and the barrier is a host readback of
``state.step``, which data-depends on every step.

Exit code: 0 only when the device answered and every config and phase
ran; an unreachable device or any config/phase that raised exits 1 (the
artifact is still written, with ``error`` markers naming what failed).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

STEPS = 30
# repetitions per model: the best repetition is the headline, median and
# spread are recorded beside it; reps are cheap next to the compile
REPEATS = 5

# bf16 peak FLOPs/sec per chip by device kind substring (public specs);
# MFU is reported only when the kind matches.
PEAK_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
]


def _peak_flops(device) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def _causal_attn_flops(layers: int, batch: int, seq: int, d_model: int):
    """Analytic train-step FLOPs of causal flash attention.

    XLA's cost analysis cannot see inside a pallas custom call, so the
    attention matmuls would otherwise be missing from MFU entirely
    (verified empirically: the gpt2s lowered flops count matches the
    non-attention matmuls alone, ~664 MFLOPs/token).  Per layer, causal:
    forward QK^T + PV = 2*B*T^2*d; backward recompute + dQ/dK/dV ~= 2x
    forward.  Total 6*L*B*T^2*d — slightly conservative (the flash
    backward recomputes the score matrix, ~7x/6 of this)."""
    return 6 * layers * batch * seq * seq * d_model


def _configs(n_chips: int = 1):
    import numpy as np

    rng = np.random.RandomState(0)
    # sequences per step: a multiple of the dp size (plain device_put has
    # no padding fallback), at least 8 per chip
    seq_batch = 8 * n_chips
    cfgs = {
        "mnist": dict(
            model_def="mnist_functional_api.mnist_functional_api.custom_model",
            features={"image": rng.rand(256, 28, 28).astype(np.float32)},
            labels=rng.randint(0, 10, 256).astype(np.int32),
            batch=256,
        ),
        "resnet50_cifar10": dict(
            model_def="resnet50_subclass.resnet50_subclass.custom_model",
            # bf16 compute (f32 params/BN stats); 2048 saturates the tiny
            # 32x32 convs — throughput plateaus there (26% MFU is the
            # roofline for this shape: early stages are bandwidth-bound)
            model_params=dict(dtype="bfloat16"),
            features={"image": rng.rand(2048, 32, 32, 3).astype(np.float32)},
            labels=rng.randint(0, 10, 2048).astype(np.int32),
            batch=2048,
        ),
        # CTR-realistic batch (4096): at small batches the per-step
        # dispatch floor, not the embedding+FM math, dominates both sides
        "deepfm": dict(
            model_def="deepfm_edl_embedding.deepfm_edl_embedding.custom_model",
            features={
                "feature": rng.randint(0, 5383, (4096, 10)).astype(np.int64)
            },
            labels=rng.randint(0, 2, 4096).astype(np.int32),
            batch=4096,
        ),
        # the sharded-embedding TPU shape (docs/designs/
        # sharded_embeddings.md): a 100M-row x 64-dim table (25.6 GB
        # f32 — larger than any single HBM) row-sharded P(dp, None)
        # over the pod by the model's declared sharding_rules, batch
        # ids spanning the full vocab so every step exercises the
        # gather -> all-to-all; plain SGD (slot-free) keeps optimizer
        # state off the table
        "deepfm_100m": dict(
            model_def=(
                "deepfm_sharded_embedding"
                ".deepfm_sharded_embedding.custom_model"
            ),
            model_params=dict(input_dim=100_000_000),
            features={
                "feature": rng.randint(
                    0, 100_000_000, (4096, 10)
                ).astype(np.int64)
            },
            labels=rng.randint(0, 2, 4096).astype(np.int32),
            batch=4096,
        ),
        # ImageNet-shape ResNet-50 (BASELINE.md config 3, single chip);
        # batch 128 measured best on v5e (2678 samples/s vs 2609 @256,
        # 2524 @512, all bf16 — r02's 1435 @128 was f32 compute: input
        # casting alone left every conv in f32 via dtype promotion)
        "imagenet_resnet50": dict(
            model_def="imagenet_resnet50.imagenet_resnet50.custom_model",
            model_params=dict(dtype="bfloat16"),
            features={
                "image": rng.rand(128, 224, 224, 3).astype(np.float32)
            },
            labels=rng.randint(0, 1000, 128).astype(np.int32),
            batch=128,
        ),
        # long-context showcase: seq 8192 sized so attention DOMINATES
        # the FLOPs (per token/layer: attn 6*T*d = 25.2 MFLOPs vs dense
        # 6*12*d^2 = 18.9 MFLOPs at d=512) — this measures the flash
        # kernel, not the dispatch floor (r02's 1-layer/64-dim seq2048
        # config measured nothing and was dropped per VERDICT #5)
        "transformer_seq8192": dict(
            model_def="long_seq_transformer.long_seq_transformer.custom_model",
            model_params=dict(
                vocab_size=32768,
                embed_dim=512,
                num_heads=8,
                num_layers=6,
                dtype="bfloat16",
            ),
            features={
                "tokens": rng.randint(
                    0, 32768, (4 * n_chips, 8192)
                ).astype(np.int32)
            },
            labels=rng.randint(0, 32768, (4 * n_chips, 8192)).astype(
                np.int32
            ),
            batch=4 * n_chips,
            tokens_per_sample=8192,
            attn_flops_per_step=_causal_attn_flops(
                layers=6, batch=4 * n_chips, seq=8192, d_model=512
            ),
        ),
        # GPT-2-small-shape LM (124M params): the honest large-model MFU
        # witness — 12 layers x 768 dim, 32k vocab, seq 2048, pallas
        # flash attention in BOTH directions
        "transformer_gpt2s_seq2048": dict(
            model_def="long_seq_transformer.long_seq_transformer.custom_model",
            model_params=dict(
                vocab_size=32768,
                embed_dim=768,
                num_heads=12,
                num_layers=12,
                dtype="bfloat16",
            ),
            features={
                "tokens": rng.randint(0, 32768, (seq_batch, 2048)).astype(
                    np.int32
                )
            },
            labels=rng.randint(0, 32768, (seq_batch, 2048)).astype(np.int32),
            batch=seq_batch,
            tokens_per_sample=2048,
            attn_flops_per_step=_causal_attn_flops(
                layers=12, batch=seq_batch, seq=2048, d_model=768
            ),
        ),
    }
    # the 100M-row shape needs ~3.2 GB of table per chip at 8 chips
    # (plus transient gradient residency); on smaller pods the shard
    # cannot fit next to the other configs' programs, so the config is
    # declared only where it can run rather than recorded as a
    # guaranteed error
    if n_chips < 8:
        cfgs.pop("deepfm_100m")
    return cfgs


# loop-body-counted-once cross-check, done once PER CONFIG: compile the
# LONE step of the config and compare its flops against the loop
# program's body flops.  Detects an XLA unroll of the while loop (which
# would multiply the loop analysis by the unroll factor).  Keyed per
# config because unroll decisions are per-program — one global cache
# would stamp the first config's unroll factor onto every model (ADVICE
# r3 finding 1).  A failed check degrades to scale 1.0 rather than
# killing the metric.
_LOOP_FLOPS_SCALE: dict = {}


def _loop_flops_scale(name, trainer, pf, pl, loop_body_flops) -> float:
    if name in _LOOP_FLOPS_SCALE:
        return _LOOP_FLOPS_SCALE[name]
    scale = 1.0
    try:
        cost = (
            trainer._train_step.lower(trainer.state, pf, pl)
            .compile()
            .cost_analysis()
        )
        single = float((cost or {}).get("flops", 0.0))
        if single > 0 and loop_body_flops > 0:
            ratio = loop_body_flops / single
            if ratio > 1.5:  # loop body counted more than once
                scale = 1.0 / round(ratio)
                print(
                    f"bench: loop cost analysis counts the body "
                    f"{ratio:.1f}x the single step; scaling flops by "
                    f"{scale}",
                    file=sys.stderr,
                )
    except Exception:  # noqa: BLE001 — best-effort cross-check
        pass
    _LOOP_FLOPS_SCALE[name] = scale
    return scale


def _measure(name, cfg, mesh):
    import jax

    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.trainer.local_executor import build_optimizer
    from elasticdl_tpu.utils.model_utils import get_model_spec

    spec = get_model_spec(
        "", cfg["model_def"], model_params=cfg.get("model_params")
    )
    rules = ()
    if spec.sharding_rules is not None:
        rules = tuple(spec.sharding_rules(mesh))
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

    # STEPS train steps inside ONE compiled program (lax.fori_loop): a
    # single dispatch covers the whole measured window, so per-call
    # dispatch latency cannot masquerade as device throughput — and
    # nothing can be elided, because each iteration's state feeds the
    # next.
    step_fn = trainer._train_step

    def many_steps(state, feats, labels):
        return jax.lax.fori_loop(
            0,
            STEPS,
            lambda _i, s: step_fn(s, feats, labels)[0],
            state,
        )

    compiled = (
        jax.jit(many_steps, donate_argnums=(0,))
        .lower(trainer.state, pf, pl)
        .compile()
    )
    state = trainer.state

    def _sync(chained_state):
        # the barrier: a host readback of a scalar that data-depends
        # on the final optimizer update (state.step covers every step
        # through the carry chain)
        return int(jax.device_get(chained_state.step))

    state = compiled(state, pf, pl)  # warmup call (STEPS steps)
    _sync(state)
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        state = compiled(state, pf, pl)
        _sync(state)
        times.append(time.perf_counter() - t0)

    # the BEST repetition stays the headline; median + spread are
    # recorded so round-over-round movement can be told from noise
    times.sort()
    dt = times[0]
    median = times[len(times) // 2]
    n_chips = max(1, mesh.devices.size)
    result = {
        "samples_per_sec_per_chip": round(
            STEPS * cfg["batch"] / dt / n_chips, 1
        ),
        "samples_per_sec_per_chip_median": round(
            STEPS * cfg["batch"] / median / n_chips, 1
        ),
        # how much slower the worst repetition ran vs the best: the
        # band any single-run number lives in
        "spread_pct": round((times[-1] / times[0] - 1) * 100, 1),
        "batch": cfg["batch"],
    }
    if "tokens_per_sample" in cfg:
        result["tokens_per_sec_per_chip"] = round(
            STEPS * cfg["batch"] * cfg["tokens_per_sample"] / dt / n_chips
        )
    try:
        # per-STEP flops from the ALREADY-COMPILED loop program: its
        # cost analysis counts the fori_loop body once (verified against
        # a single-step compile by _loop_flops_scale below — an XLA
        # unroll of the while loop would silently multiply flops) and
        # the compiled module is the SPMD-partitioned per-device
        # program, so no global-vs-device divisor guesswork.  The
        # single-step lowered analysis returns None on this backend.
        cost = compiled.cost_analysis()
        flops = float((cost or {}).get("flops", 0.0)) * STEPS
        flops *= _loop_flops_scale(name, trainer, pf, pl, flops / STEPS)
        if flops > 0:
            # pallas kernels are opaque custom calls with no flops in
            # the cost analysis: add the config's analytic attention
            # flops (global, so they shard evenly over the chips).
            # Only on top of a SUCCESSFUL base analysis — attention
            # flops alone would report a plausible-looking but grossly
            # understated MFU
            flops += cfg.get("attn_flops_per_step", 0.0) * STEPS / n_chips
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        flops = 0.0
    peak = _peak_flops(mesh.devices.flatten()[0])
    if flops:
        # algorithmic (cost-analysis) FLOPs — where XLA lowers convs to
        # fast algorithms the derived MFU can exceed 1 and carries no
        # utilization signal (the tiny Cin=1 MNIST convs do this), so
        # only the raw rate is reported in that case
        result["model_tflops_per_sec_per_chip"] = round(
            flops / dt / 1e12, 2
        )
        if peak:
            mfu = flops / dt / peak
            if mfu <= 1.0:
                result["mfu"] = round(mfu, 4)
    return result


def _probe_dispatch_secs() -> float:
    """Fresh-buffer dispatch round-trip, UNCACHED (the stamp for
    comparing measurement windows): the shared probe behind the auto-k
    sizing, so the stamps stay comparable to the overhead it measures."""
    from elasticdl_tpu.trainer.stacking import probe_dispatch_overhead

    return probe_dispatch_overhead(trials=2)


def _measure_e2e(
    gen_name,
    model_def,
    batch,
    num_records,
    records_per_task,
    extra_argv=(),
    num_shards=8,
):
    """End-to-end throughput through the REAL training path: EDLIO shard
    files on disk -> reader -> vectorized decode -> batching -> host
    placement -> jitted SPMD step, driven by LocalExecutor exactly as
    ``elasticdl train --distribution_strategy=Local`` runs it
    (BASELINE.md's metric; the step-only configs above exclude the whole
    data plane).

    Measurement window: first-task mark (jit compilation done) -> a
    DEVICE-SYNCED final mark.  Dispatches are async and the prefetching
    host pipeline runs ahead, so per-task host marks alone would credit
    records the chip hasn't consumed yet; the window closes with a host
    readback of ``state.step`` — which data-depends on every dispatched
    optimizer step — so every counted record's update exists on device.

    Also measures the two pipeline ceilings and reports them as
    ``budget`` (VERDICT r3 #1): the host decode rate (pipeline iterated
    with no device) and the device-path rate (pre-decoded batches
    through stack/place/dispatch/sync) — the e2e rate should sit within
    ~85% of min(host, device_path); any further gap would be overlap
    slack in the runtime, not a roofline.
    """
    import tempfile

    import jax

    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.trainer.state import Modes
    from elasticdl_tpu.utils.args import parse_master_args

    marks = []
    final = []

    class _TimedExecutor(LocalExecutor):
        def _train_task(self, task, batches=None):
            n = super()._train_task(task, batches)
            marks.append((time.perf_counter(), n))
            return n

        def evaluate(self, tag="final"):
            # no validation_data in the bench config: this is the
            # post-training hook — close the window with a sync that
            # data-depends on every step
            if self._trainer is not None and not final:
                int(jax.device_get(self._trainer.state.step))
                final.append(time.perf_counter())
            return {}

    with tempfile.TemporaryDirectory() as td:
        data_dir = getattr(synthetic, gen_name)(
            os.path.join(td, "data"),
            num_records=num_records,
            num_shards=num_shards,
            seed=0,
        )
        argv = [
            "--model_def",
            model_def,
            "--training_data",
            data_dir,
            "--minibatch_size",
            str(batch),
            "--records_per_task",
            str(records_per_task),
            "--num_epochs",
            "1",
        ] + list(extra_argv)
        probe_e2e_start = _probe_dispatch_secs()
        executor = _TimedExecutor(parse_master_args(argv))
        executor.run()

        if len(marks) < 3 or not final:
            raise RuntimeError(
                f"e2e needs >= 3 tasks for a steady-state window, got "
                f"{len(marks)}"
            )
        steady_records = sum(n for _, n in marks[1:])
        dt = final[0] - marks[0][0]
        n_chips = max(1, len(jax.devices()))
        e2e_rate = steady_records / dt / n_chips

        # dispatch-overhead stamp at the budget windows' start (a third
        # was taken before the e2e window): the e2e window and the
        # budget floors are measured minutes apart, so drift between
        # them is visible in the artifact instead of leaving
        # e2e_vs_roofline unexplainable (VERDICT r4 weak #2)
        probe_before = _probe_dispatch_secs()

        # ---- budget: host decode ceiling ------------------------------
        reader = executor._train_reader
        shards = reader.create_shards()
        from elasticdl_tpu.data.fast_pipeline import build_task_batches
        from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

        disp = TaskDispatcher(
            shards, records_per_task=records_per_task, num_epochs=1
        )
        host_records = 0
        t0 = time.perf_counter()
        for _ in range(3):
            _tid, task = disp.get(0)
            if task is None:
                break
            for _feats, labels in build_task_batches(
                reader,
                task,
                executor._spec,
                Modes.TRAINING,
                reader.metadata,
                batch,
                shuffle_records=True,
            ):
                host_records += int(labels.shape[0])
        host_rate = host_records / (time.perf_counter() - t0) / n_chips

        # ---- budget: device-path floor --------------------------------
        # pre-decoded batches through the exact dispatch path the run
        # uses (stack/pad -> place -> stacked dispatch), synced at end:
        # what the device path could sustain if decode were free.  Each
        # iteration dispatches a DIFFERENT task's staged batches: the
        # e2e path ships fresh buffers every dispatch, so the floor
        # must too.
        from elasticdl_tpu.trainer.stacking import run_stacked_steps

        disp2 = TaskDispatcher(
            shards, records_per_task=records_per_task, num_epochs=1
        )
        k = getattr(executor._args, "steps_per_dispatch", 1) or 1
        trainer = executor._trainer
        from elasticdl_tpu.parallel.mesh import batch_divisor

        staged_tasks = []
        for _ in range(3):
            _tid, task = disp2.get(0)
            if task is None:
                break
            staged_tasks.append(
                list(
                    build_task_batches(
                        reader,
                        task,
                        executor._spec,
                        Modes.TRAINING,
                        reader.metadata,
                        batch,
                        shuffle_records=True,
                        stack_k=k if (k == "auto" or int(k) > 1) else None,
                        stack_divisor=batch_divisor(trainer.mesh),
                    )
                )
            )
        dev_records = 0
        t0 = time.perf_counter()
        for staged in staged_tasks:
            dev_records += run_stacked_steps(lambda: trainer, staged, k)
        int(jax.device_get(trainer.state.step))
        dev_rate = dev_records / (time.perf_counter() - t0) / n_chips
        probe_after = _probe_dispatch_secs()

        # ---- anatomy window: SEPARATE short instrumented runs ---------
        # (--step_anatomy blocks each dispatch on its outputs, so it
        # must never share a window with the rate measurements above);
        # measured once with device prefetch OFF and once ON, so the
        # artifact embeds both e2e_vs_roofline numerators — the next
        # TPU round verifies the >= 0.9 ROADMAP gate against the ON
        # ratio and still sees the serial-staging baseline it beat
        try:
            # shared dataset for BOTH windows (identical content by
            # seed; generating it twice doubled the disk work) — still
            # inside the anatomy error-marker contract: a generation
            # failure becomes a marker, never a lost config
            anatomy_data = getattr(synthetic, gen_name)(
                os.path.join(td, "anatomy_data"),
                num_records=records_per_task * 2,
                num_shards=2,
                seed=1,
            )
        except Exception as ex:  # noqa: BLE001 — annotation, not rates
            marker = {"error": f"{type(ex).__name__}: {ex}"}
            anatomy_section = {
                "prefetch_off": dict(marker),
                "prefetch_on": dict(marker),
            }
        else:
            anatomy_section = {
                "prefetch_off": _measure_anatomy_window(
                    td,
                    gen_name,
                    model_def,
                    batch,
                    records_per_task,
                    extra_argv,
                    device_prefetch=False,
                    data_dir=anatomy_data,
                ),
                "prefetch_on": _measure_anatomy_window(
                    td,
                    gen_name,
                    model_def,
                    batch,
                    records_per_task,
                    extra_argv,
                    device_prefetch=True,
                    data_dir=anatomy_data,
                ),
            }

    roofline = min(host_rate, dev_rate)
    return {
        "e2e_samples_per_sec_per_chip": round(e2e_rate, 1),
        "batch": batch,
        "records_measured": steady_records,
        "tasks_measured": len(marks) - 1,
        "anatomy": anatomy_section,
        "budget": {
            "host_pipeline_records_per_sec": round(host_rate),
            "device_path_records_per_sec": round(dev_rate),
            "binding": "host"
            if host_rate < dev_rate
            else "device_path",
            # e2e over the overlapped-pipeline roofline: < ~0.85 would
            # mean runtime slack, not a data-plane limit
            "e2e_vs_roofline": round(e2e_rate / roofline, 3),
            # fresh-buffer dispatch floor at e2e start / budget start /
            # budget end; a large shift means conditions moved between
            # the e2e window and its budget, so the ratio carries that
            # skew rather than runtime slack
            "probe_dispatch_secs_e2e_start": round(probe_e2e_start, 4),
            "probe_dispatch_secs_before": round(probe_before, 4),
            "probe_dispatch_secs_after": round(probe_after, 4),
        },
    }


def _measure_anatomy_window(
    td,
    gen_name,
    model_def,
    batch,
    records_per_task,
    extra_argv,
    device_prefetch=None,
    data_dir=None,
):
    """Per-dispatch phase anatomy of the SAME e2e configuration over a
    small fresh dataset (two tasks): the measured
    host_fetch/assemble/h2d/device_compute/bookkeeping split behind the
    budget's e2e_vs_roofline ratio.  ``device_prefetch`` overrides the
    config's own flag (argparse last-wins) so the on/off pair measures
    the pipelining delta; the caller generates the dataset ONCE and
    passes ``data_dir`` so the pair shares it (identical content by
    seed anyway).  Returns the report's overall goodput section, or an
    error marker (which keeps the other windows' numbers and makes the
    run exit 1)."""
    import os as _os

    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.telemetry import anatomy as anatomy_mod
    from elasticdl_tpu.telemetry import tracing, worker_hooks
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    mode = {True: "on", False: "off", None: "cfg"}[device_prefetch]
    try:
        if data_dir is None:
            data_dir = getattr(synthetic, gen_name)(
                _os.path.join(td, "anatomy_data"),
                num_records=records_per_task * 2,
                num_shards=2,
                seed=1,
            )
        telemetry_dir = _os.path.join(td, f"anatomy_telemetry_{mode}")
        override = []
        if device_prefetch is not None:
            override = [
                "--device_prefetch",
                "true" if device_prefetch else "false",
            ]
        args = parse_master_args(
            [
                "--model_def",
                model_def,
                "--training_data",
                data_dir,
                "--minibatch_size",
                str(batch),
                "--records_per_task",
                str(records_per_task),
                "--num_epochs",
                "1",
                "--telemetry_dir",
                telemetry_dir,
                "--step_anatomy",
                "true",
            ]
            + list(extra_argv)
            + override
        )
        # boundary_stall is a process-global monotone counter
        # (heartbeat-shipped in production): per-window attribution is
        # a before/after diff over this window's own wall clock
        from elasticdl_tpu.trainer import device_pipeline as _dp

        snap_before = _dp.heartbeat_snapshot()
        wall_t0 = time.perf_counter()
        LocalExecutor(args).run()
        wall_ms = (time.perf_counter() - wall_t0) * 1000.0
        from elasticdl_tpu.telemetry.events import read_events
        from elasticdl_tpu.telemetry.report import (
            goodput_section,
            memory_section,
        )

        events = read_events(
            _os.path.join(telemetry_dir, "events.jsonl")
        )
        section = goodput_section(events)
        if not section:
            return {"error": "no step_anatomy events recorded"}
        overall = dict(section["overall"])
        snap_after = _dp.heartbeat_snapshot()
        stall_ms = snap_after.get("boundary_stall_ms", 0) - snap_before.get(
            "boundary_stall_ms", 0
        )
        overall["boundary_stall"] = {
            "boundaries": snap_after.get("boundaries", 0)
            - snap_before.get("boundaries", 0),
            "stall_ms": stall_ms,
            # of the window's own wall, NOT the dispatch-phase sum: the
            # counter measures BETWEEN dispatches, outside the anatomy
            # taxonomy's sum-exact per-dispatch phases
            "share_of_wall": round(stall_ms / wall_ms, 4) if wall_ms else 0,
        }
        memory = memory_section(events)
        if memory:
            # the falsifiable headroom numbers the sharded-embedding
            # work inherits: per-component peaks + the unaccounted
            # residual vs its budget, measured on the SAME run the
            # roofline ratio comes from
            overall["memory"] = {
                "components": {
                    name: slot["peak_bytes"]
                    for name, slot in memory["components"].items()
                },
                "host_rss_peak_bytes": memory["host_rss_peak_bytes"],
                "unaccounted_bytes": memory["unaccounted_bytes"],
                "unaccounted_over_budget": memory[
                    "unaccounted_over_budget"
                ],
            }
        return overall
    except Exception as ex:  # noqa: BLE001 — marked, counted by _failures
        return {"error": f"{type(ex).__name__}: {ex}"}
    finally:
        # the instrumented run installed process-global recorders bound
        # to this tempdir; later configs must not inherit them — and the
        # model_state ledger callback closes over the whole trainer, so
        # unregistering it here releases the previous config's
        # params/opt-state pytree
        from elasticdl_tpu.telemetry import memory as memory_mod

        anatomy_mod.uninstall()
        worker_hooks.uninstall()
        tracing.uninstall()
        memory_mod.unregister_component(memory_mod.COMPONENT_MODEL_STATE)
        memory_mod.uninstall()


E2E_CONFIGS = {
    # --steps_per_dispatch: one scanned dispatch per k minibatches —
    # per-dispatch overhead would otherwise dominate the measurement
    # and hide the data plane
    "mnist_e2e": dict(
        gen_name="gen_mnist",
        model_def="mnist_functional_api.mnist_functional_api.custom_model",
        batch=256,
        # 8 shards x 16384 = exactly two 32-batch tasks per shard: one
        # scan shape for the whole window (163840 left 4096-record
        # remainder tasks whose 16-step scan compiled mid-window)
        num_records=131072,
        records_per_task=8192,
        # auto sizing: with the uint8 wire (device_parse normalization
        # on-chip) a 256-record batch is ~200KB, so auto allows 36 steps
        # per dispatch (7MB put target) — the 32-batch tasks here yield
        # one ~6.3MB group each.
        # device_prefetch: the e2e window measures the PIPELINED path —
        # next group staged while the current one computes, batch
        # buffers donated (the anatomy section carries the on/off pair)
        extra_argv=(
            "--steps_per_dispatch",
            "auto",
            "--device_prefetch",
            "true",
        ),
    ),
    "deepfm_e2e": dict(
        gen_name="gen_frappe",
        model_def="deepfm_edl_embedding.deepfm_edl_embedding.custom_model",
        batch=4096,
        # 8 shards x 262144 = exactly one 64-batch task per shard: every
        # dispatch group shares one scan shape, so the steady window
        # carries zero recompiles (a ragged remainder task would compile
        # a second scan length mid-window).  auto resolves k=64
        # (MAX_AUTO_K) with int16 wire ids (batch_parse narrowing),
        # keeping the stacked put at ~6.3MB while maximizing records
        # per dispatch (budget.device_path in the artifact).
        num_records=2097152,
        records_per_task=262144,
        extra_argv=(
            "--steps_per_dispatch",
            "auto",
            "--device_prefetch",
            "true",
        ),
    ),
}


def _measure_accuracy():
    """Train mnist and deepfm-frappe ON THE CHIP for roughly the
    reference's step budget and report final eval accuracy (BASELINE.md
    acceptance; the reference bar is mnist > 0.8 after ~937 steps,
    worker_ps_interaction_test.py — our synthetic datasets are easier,
    so the same thresholds are conservative).  Runs by default;
    ``--no-accuracy`` skips it."""
    import tempfile

    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    out = {}
    configs = {
        # 937 steps x batch 64 = the reference's budget
        "mnist": dict(
            gen_name="gen_mnist",
            model_def=(
                "mnist_functional_api.mnist_functional_api.custom_model"
            ),
            train_records=59968,
            eval_records=4096,
            batch=64,
            threshold=0.8,
        ),
        # BASELINE.md config 4's OTHER half: census_dnn_model — the
        # feature-column path (hash-bucket + embedding_column host
        # transform, device-side DenseFeatures), per-record dataset_fn,
        # no batch_parse fast path.  Probed on-chip: 0.818 @ 256 steps
        # (VERDICT r3 #5).
        "census": dict(
            gen_name="gen_census",
            model_def=(
                "census_dnn_model.census_functional_api.custom_model"
            ),
            train_records=32768,
            eval_records=4096,
            batch=256,
            threshold=0.8,
            epochs=2,
            extra_argv=("--num_epochs", "2"),
        ),
        # vocab 512 (data + model): per-id observation counts high enough
        # for the factorization to generalize — same recipe as the
        # config-4 acceptance test (test_recordio_gen_real.py)
        "deepfm_frappe": dict(
            gen_name="gen_frappe",
            model_def=(
                "deepfm_edl_embedding.deepfm_edl_embedding.custom_model"
            ),
            train_records=131072,
            eval_records=8192,
            batch=512,
            threshold=0.8,
            gen_kwargs=dict(vocab_size=512),
            extra_argv=("--model_params", "input_dim=512"),
        ),
    }
    for name, cfg in configs.items():
        with tempfile.TemporaryDirectory() as td:
            gen = getattr(synthetic, cfg["gen_name"])
            gen_kwargs = cfg.get("gen_kwargs", {})
            train_dir = gen(
                os.path.join(td, "t"),
                num_records=cfg["train_records"],
                num_shards=8,
                seed=0,
                **gen_kwargs,
            )
            eval_dir = gen(
                os.path.join(td, "e"),
                num_records=cfg["eval_records"],
                num_shards=1,
                seed=1,
                **gen_kwargs,
            )
            args = parse_master_args(
                [
                    "--model_def",
                    cfg["model_def"],
                    "--training_data",
                    train_dir,
                    "--validation_data",
                    eval_dir,
                    "--minibatch_size",
                    str(cfg["batch"]),
                    "--records_per_task",
                    str(cfg["batch"] * 16),
                    "--steps_per_dispatch",
                    "16",
                ]
                + list(cfg.get("extra_argv", ()))
            )
            results = LocalExecutor(args).run()
        acc = float(results.get("accuracy", results.get("accuracy_logits", 0.0)))
        out[name] = {
            "accuracy": round(acc, 4),
            "steps": cfg["train_records"]
            // cfg["batch"]
            * cfg.get("epochs", 1),
            "pass": acc >= cfg["threshold"],
            "threshold": cfg["threshold"],
        }
    return out


def _run_cpu_bench_script(name: str) -> dict:
    """Run a benchmarks/ script in a CPU subprocess (kill-and-relaunch
    jobs must never touch the chip the throughput configs are timing)
    and parse its one-line JSON."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", name
    )
    proc = subprocess.run(
        [sys.executable, script],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"no JSON from {name} (rc={proc.returncode}): "
        f"{proc.stderr[-300:]}"
    )


def _measure_reform():
    """Elastic re-formation latency (BASELINE.md config 5)."""
    return _run_cpu_bench_script("reform_bench.py")


def _measure_preemption_accuracy():
    """BASELINE.md config 5's CONJUNCTIVE acceptance: a worker SIGKILLed
    mid-run, exactly-once records, AND final accuracy over the bar
    (VERDICT r3 #3)."""
    return _run_cpu_bench_script("preemption_accuracy_bench.py")


# ---- compact artifact ------------------------------------------------------

# the driver records only a ~2000-char TAIL of stdout: r4's single ~4KB
# JSON line lost its front half — metric/value and every step config —
# and the canonical artifact recorded `parsed: null` (VERDICT r4 weak
# #1).  The LAST line is now a compact (<= ~1500B, pinned by
# tests/test_bench_artifact.py) summary carrying EVERY config's headline
# numbers and gate verdicts; the full detail goes to BENCH_full.json,
# which the compact line names in `detail`.
COMPACT_KEY_LEGEND = {
    "r": "rate (samples/sec/chip; e2e: through the full data plane)",
    "med": "median-repetition rate",
    "sp": "spread_pct (worst vs best repetition)",
    "mfu": "model flops utilization",
    "tok": "tokens/sec/chip",
    "vsb": "vs_baseline (reference TF2 step on host CPU)",
    "vs": "e2e rate / device-resident step rate at the same batch",
    "roof": "e2e rate / min(host decode, device path) budget roofline",
    "roofm": (
        "measured live roofline ratio from the --step_anatomy window "
        "with --device_prefetch ON (binding path busy time / dispatch "
        "wall; phases in full detail)"
    ),
    "roofm0": (
        "same measured roofline ratio with --device_prefetch OFF — the "
        "serial-staging baseline the pipelining is gated against"
    ),
    "bst": (
        "boundary_stall share of the roofm window's wall (device-idle "
        "time between tasks; --boundary_fusion's target)"
    ),
    "bst0": "boundary_stall share of the roofm0 (prefetch OFF) window",
    "bind": "binding budget ceiling: h=host decode, d=device path",
    "acc": "[accuracy, 1 if >= threshold]",
    "s": "seconds",
    "ok": "1 = gate passed",
    "err": "1 = config failed (error text in full detail)",
    "ts_vs_local": "task-stream worker e2e rate / LocalExecutor's (CPU)",
    "lockstep_vs_local": (
        "2-process lockstep e2e rate / LocalExecutor's (CPU; "
        "every-process-reads-every-task decode overhead)"
    ),
}


def _pipeline_config() -> dict:
    """The device-pipeline knobs this run resolved (env-driven, so the
    artifact must record them — two rounds with different depths are
    not comparable without it)."""
    from elasticdl_tpu.trainer.device_pipeline import (
        resolve_boundary_fusion,
        resolve_device_prefetch,
        resolve_pipeline_depth,
    )

    return {
        "device_prefetch_env": resolve_device_prefetch(),
        "boundary_fusion_env": resolve_boundary_fusion(),
        "pipeline_depth": resolve_pipeline_depth(),
    }


def _round_sig(x: float, sig: int = 4) -> float:
    """Round to ``sig`` significant digits (byte economy in the compact
    line: 234517.3 -> 234500)."""
    if not x:
        return 0
    import math

    d = sig - 1 - math.floor(math.log10(abs(x)))
    out = round(x, d)
    return int(out) if d <= 0 else out


def _compact_models(models: dict) -> dict:
    out = {}
    for name, m in models.items():
        if not isinstance(m, dict):
            continue
        if "error" in m:
            out[name] = {"err": 1}
            continue
        c = {}
        if name == "accuracy":
            for k, v in m.items():
                if isinstance(v, dict) and "accuracy" in v:
                    c[k] = [v["accuracy"], int(bool(v.get("pass")))]
                elif isinstance(v, dict) and "error" in v:
                    # a failed gate must stay visible in the compact
                    # artifact — silent truncation is the r4 bug class
                    c[k] = {"err": 1}
            out[name] = c
            continue
        if name == "elastic_reform":
            c["s"] = m.get("reform_latency_secs")
            c["ok"] = int(bool(m.get("records_ok", True)))
            out[name] = c
            continue
        if name == "accuracy_under_preemption":
            c["acc"] = m.get("accuracy")
            c["ok"] = int(bool(m.get("pass", m.get("records_ok"))))
            out[name] = c
            continue
        if name == "runtime_ratios":
            c["ts_vs_local"] = m.get("taskstream_vs_local")
            c["lockstep_vs_local"] = m.get("lockstep_e2e_vs_local")
            out[name] = c
            continue
        rate = m.get("samples_per_sec_per_chip")
        if rate is not None:
            c["r"] = _round_sig(rate)
        med = m.get("samples_per_sec_per_chip_median")
        if med is not None:
            c["med"] = _round_sig(med)
        if m.get("spread_pct") is not None:
            c["sp"] = round(m["spread_pct"], 1)
        if m.get("mfu") is not None:
            c["mfu"] = round(m["mfu"], 3)
        if m.get("tokens_per_sec_per_chip") is not None:
            c["tok"] = _round_sig(m["tokens_per_sec_per_chip"])
        if m.get("vs_baseline") is not None:
            c["vsb"] = m["vs_baseline"]
        e2e = m.get("e2e_samples_per_sec_per_chip")
        if e2e is not None:
            c["r"] = _round_sig(e2e)
        if m.get("vs_step_only") is not None:
            c["vs"] = m["vs_step_only"]
        budget = m.get("budget") or {}
        if budget.get("e2e_vs_roofline") is not None:
            c["roof"] = budget["e2e_vs_roofline"]
        if budget.get("binding"):
            c["bind"] = budget["binding"][0]
        anatomy = m.get("anatomy") or {}
        # the MEASURED live ratios from the instrumented anatomy
        # windows (per-dispatch phase sums), vs `roof`'s inferred
        # ceiling-run ratio — full phase detail in BENCH_full.json.
        # roofm = device prefetch ON (the production path), roofm0 =
        # OFF (the serial-staging baseline it is gated against)
        on = anatomy.get("prefetch_on") or {}
        off = anatomy.get("prefetch_off") or {}
        if on.get("e2e_vs_roofline") is not None:
            c["roofm"] = on["e2e_vs_roofline"]
        elif anatomy.get("e2e_vs_roofline") is not None:
            # pre-split artifact shape (single window)
            c["roofm"] = anatomy["e2e_vs_roofline"]
        if off.get("e2e_vs_roofline") is not None:
            c["roofm0"] = off["e2e_vs_roofline"]
        # boundary-stall share of each anatomy window's wall — the
        # between-task idle the roofm ratio cannot see (it is outside
        # the per-dispatch phase sum)
        on_stall = (on.get("boundary_stall") or {}).get("share_of_wall")
        if on_stall is not None:
            c["bst"] = on_stall
        off_stall = (off.get("boundary_stall") or {}).get("share_of_wall")
        if off_stall is not None:
            c["bst0"] = off_stall
        out[name] = c
    return out


def _device_preflight(timeout_secs: float = 240.0, probe_argv=None):
    """Probe device init ONCE in a subprocess before anything else (the
    probe exits before this process touches JAX, so the chip is free
    again): a backend init that hangs or fails then costs one bounded
    wait and leaves a structured ``device_unreachable`` artifact instead
    of a hung bench with nothing on disk.  Returns None when the device
    answers, else the payload main() stamps into BENCH_full.json before
    exiting 1."""
    import subprocess

    argv = probe_argv or [
        sys.executable,
        "-c",
        "import jax; print(jax.devices()[0].device_kind)",
    ]
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=timeout_secs
        )
    except subprocess.TimeoutExpired:
        reason = f"device init did not answer within {timeout_secs:.0f}s"
    else:
        if proc.returncode == 0:
            return None
        reason = f"device init failed: {proc.stderr.strip()[-160:]}"
    return {"reason": reason, "timeout_secs": timeout_secs}


def _failures(node, path=()) -> list[str]:
    """Dotted paths of every ``error`` marker in the results tree: a
    config or phase that raised keeps its place in the artifact, and
    makes the run exit non-zero."""
    if not isinstance(node, dict):
        return []
    if "error" in node:
        return [".".join(path)]
    out = []
    for key, child in node.items():
        out.extend(_failures(child, path + (str(key),)))
    return out


def main() -> int:
    preflight = _device_preflight()
    if preflight is not None:
        reason = preflight["reason"]
        print(f"bench: {reason}", file=sys.stderr)
        # stamped device_unreachable ARTIFACT: the driver and the next
        # round see why, when and under what budget the device never
        # answered
        unreachable = dict(preflight)
        unreachable["stamped_at"] = time.time()
        payload = {
            "metric": "resnet50_cifar10_train_samples_per_sec_per_chip",
            "value": None,
            "unit": "samples/sec/chip",
            "vs_baseline": None,
            "error": reason,
            "device_unreachable": unreachable,
        }
        full_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_full.json"
        )
        try:
            with open(full_path, "w") as f:
                json.dump(payload, f, indent=1)
                f.write("\n")
        except OSError as ex:
            print(
                f"bench: could not write {full_path}: {ex}", file=sys.stderr
            )
        print(json.dumps(payload, separators=(",", ":")))
        return 1

    import jax  # noqa: F401 — device init before timing

    from elasticdl_tpu.parallel.elastic import configure_compilation_cache
    from elasticdl_tpu.parallel.mesh import MeshConfig

    configure_compilation_cache()

    # accuracy runs by default (BASELINE.md acceptance lives in the
    # recorded bench artifact); --no-accuracy skips it for quick loops
    accuracy_mode = "--no-accuracy" not in sys.argv[1:]
    mesh = MeshConfig.from_string("").create()  # all local devices on dp

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks",
        "baseline.json",
    )
    baselines = {}
    baseline_batches = {}
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            payload = json.load(f)
        baselines = payload.get("samples_per_sec", {})
        baseline_batches = payload.get("batch_sizes", {})

    device_kind = getattr(
        mesh.devices.flatten()[0], "device_kind", "unknown"
    )

    models = {}
    for name, cfg in _configs(max(1, mesh.devices.size)).items():
        try:
            models[name] = _measure(name, cfg, mesh)
        except Exception as ex:  # noqa: BLE001 — one config must not
            # take the other configs' numbers down with it; the error
            # marker makes the run exit 1 (_failures)
            print(f"bench config {name} failed: {ex}", file=sys.stderr)
            models[name] = {"error": str(ex)[:200]}
            continue
        base = baselines.get(name)
        # a stale anchor measured at a different batch is apples-to-
        # oranges: drop it loudly rather than report a skewed ratio
        base_batch = baseline_batches.get(name, cfg["batch"])
        if base and base_batch != cfg["batch"]:
            print(
                f"baseline for {name} measured at batch {base_batch}, "
                f"bench runs {cfg['batch']}; re-run benchmarks/"
                f"baseline_tf.py — dropping the vs_baseline anchor",
                file=sys.stderr,
            )
            base = None
        if base:
            models[name]["vs_baseline"] = round(
                models[name]["samples_per_sec_per_chip"] / base, 2
            )

    for name, cfg in E2E_CONFIGS.items():
        try:
            models[name] = _measure_e2e(**cfg)
        except Exception as ex:  # noqa: BLE001 — same isolation as above
            print(f"bench config {name} failed: {ex}", file=sys.stderr)
            models[name] = {"error": str(ex)[:200]}
    # the data plane keeps the chip fed when e2e holds ~80%+ of the
    # device-resident step rate at the same batch
    for e2e, step in (("mnist_e2e", "mnist"), ("deepfm_e2e", "deepfm")):
        rate = models.get(e2e, {}).get("e2e_samples_per_sec_per_chip")
        step_rate = models.get(step, {}).get("samples_per_sec_per_chip")
        if rate and step_rate:
            models[e2e]["vs_step_only"] = round(rate / step_rate, 3)

    if accuracy_mode:
        try:
            models["accuracy"] = _measure_accuracy()
        except Exception as ex:  # noqa: BLE001 — same isolation as above
            print(f"bench accuracy mode failed: {ex}", file=sys.stderr)
            models["accuracy"] = {"error": str(ex)[:200]}

    try:
        models["elastic_reform"] = _measure_reform()
    except Exception as ex:  # noqa: BLE001 — same isolation as above
        print(f"bench config elastic_reform failed: {ex}", file=sys.stderr)
        models["elastic_reform"] = {"error": str(ex)[:200]}

    # relative e2e throughput of the three runtimes on host CPU
    # (taskstream_vs_local: VERDICT r5 #3; lockstep_e2e_vs_local: #8)
    try:
        models["runtime_ratios"] = _run_cpu_bench_script(
            "runtime_ratio_bench.py"
        )
    except Exception as ex:  # noqa: BLE001 — same isolation as above
        print(f"bench runtime_ratios failed: {ex}", file=sys.stderr)
        models["runtime_ratios"] = {"error": str(ex)[:200]}

    if accuracy_mode:
        try:
            models["accuracy_under_preemption"] = (
                _measure_preemption_accuracy()
            )
        except Exception as ex:  # noqa: BLE001 — same isolation as above
            print(
                f"bench accuracy_under_preemption failed: {ex}",
                file=sys.stderr,
            )
            models["accuracy_under_preemption"] = {"error": str(ex)[:200]}

    # the headline must survive its own config failing (the whole point
    # of the per-config isolation above)
    head = models.get("resnet50_cifar10") or {}
    full = {
        "metric": "resnet50_cifar10_train_samples_per_sec_per_chip",
        "value": head.get("samples_per_sec_per_chip"),
        "unit": "samples/sec/chip",
        # null (not 0.0) when no anchor exists — a consumer must
        # not read "baseline missing" as "infinitely regressed"
        "vs_baseline": head.get("vs_baseline"),
        "device": device_kind,
        "models": models,
        "config": _pipeline_config(),
        "compact_key_legend": COMPACT_KEY_LEGEND,
        "baseline_source": (
            "benchmarks/baseline.json "
            "(tf2 GradientTape step, host CPU; "
            "regenerate: python benchmarks/baseline_tf.py)"
        ),
    }
    full_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_full.json"
    )
    try:
        with open(full_path, "w") as f:
            json.dump(full, f, indent=1)
            f.write("\n")
    except OSError as ex:
        # a read-only checkout must not cost the run its artifact: the
        # compact line below needs only in-memory data
        print(f"bench: could not write {full_path}: {ex}", file=sys.stderr)

    # LAST line: the compact summary — the ONLY line the driver is
    # guaranteed to capture whole (2000-char stdout tail)
    print(
        json.dumps(
            {
                "metric": full["metric"],
                "value": full["value"],
                "unit": full["unit"],
                "vs_baseline": full["vs_baseline"],
                "device": device_kind,
                "detail": "BENCH_full.json",
                "models": _compact_models(models),
            },
            separators=(",", ":"),
        )
    )
    failed = _failures(models)
    if failed:
        print(f"bench: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
