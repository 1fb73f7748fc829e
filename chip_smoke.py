#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

drives the main path once, on ONE chip, at the full width of the
GPT-2-small-shape LM (``long_seq_transformer``: 12 layers x 768, 12 heads,
32k vocabulary, seq 2048, 8 sequences per chip — the one zoo model that
runs the Pallas flash kernels in both directions):

1. ``train``   ``elasticdl_tpu.client train --distribution_strategy Local``
               from EDLIO shards generated here from a seed: a few tens of
               steps with a mid-run and a final evaluation.
2. ``cache``   the same command as 1 in a second process: every compile
               request is served by the persistent compile cache.  Straight
               after 1: where the cache directory is capped (192 MiB on the
               chip tool's machine) the kernel run's float32 reference
               programs, 115 MB one of them, push run 1's entries out.
3. ``kernel``  compiled ``flash_attention`` forward and gradients against
               ``mha_reference`` at the smoke model's shape and the
               benchmark cells' three.

It exits 0 — and prints, as the LAST line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`` —
only if every run passed: platform ``tpu``, kernels compiled (not
interpreted), native EDLIO codec, finite eval loss below its starting value,
no compile inside the steady window.  It exits non-zero and prints no result
when JAX finds no accelerator, when any run fails, or when the rest of the
repository is not beside it.

The parent never imports JAX (a process that has touched JAX holds the chip):
each run is its own child process, one after another, and the child calls
the normal entry point.  The children's platform is pinned here
(``JAX_PLATFORMS`` + ``--jax_platform``), whatever the environment says.

``--size tiny`` REHEARSES the same control flow on the CPU backend at a tiny
width (kernels interpreted) before chip time is spent.  A rehearsal is not a
chip result: it prints no result line.

``--runs bind,dp4,workers4,kill`` are the builder's runs on a four-chip host:
a bare probe of the one-chip-per-process binding, the same model as one
process over ``dp=4``, as four one-chip lockstep workers under
``AllreduceStrategy``, and the latter with one worker SIGKILLed mid-run
(skipped when ``bind`` ran and failed: they could only time out).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

DEFAULT_RUNS = ("train", "cache", "kernel")
ALL_RUNS = DEFAULT_RUNS + ("bind", "dp4", "workers4", "kill")

# the whole smoke must end inside the driver's 1200 s, compilation included
TOTAL_BUDGET_SECS = 1100.0
RUN_BUDGET_SECS = {
    "train": 600.0,
    "kernel": 300.0,
    "cache": 400.0,
    "bind": 400.0,
    "dp4": 400.0,
    "workers4": 400.0,
    "kill": 500.0,
}

EXIT_FAILED = 1
EXIT_NO_REPO = 2
EXIT_NO_DEVICE = 3

MODEL_DEF = "long_seq_transformer.long_seq_transformer.custom_model"
# gen_sequence's alphabet: under a larger model vocabulary the loss falls
# well below ln(vocab) within tens of steps
DATA_ALPHABET = 256
STEPS_PER_TASK = 4

SIZES = {
    # GPT-2-small's width and depth (12 x 768, 12 heads) at seq 2048: the
    # full width of a model the repo supports
    "full": dict(
        platform="tpu",
        model_params=(
            "vocab_size=32768;embed_dim=768;num_heads=12;num_layers=12;"
            "dtype=bfloat16"
        ),
        vocab=32768,
        heads=12,
        seq_len=2048,
        per_chip_batch=8,
        steps=40,
        evaluation_steps=8,
        four_chip_steps=20,
        # hosts dispatch ahead of the devices and the chief reports a task
        # when its steps are ENQUEUED: the kill run is long enough that
        # tasks are still unleased when the worker dies
        kill_run_steps=48,
        kill_step=6,
        # (B, S, H, D), (B, S, H, D of q and k, D of v), that with the
        # key/value heads, or that with a window: the smoke model's shape, then the one each LM
        # cell of the benchmark hands the kernels, whole (the float32
        # reference is taken a few query heads at a time).  Heads read out
        # of the layer's own layout ("lanes") at width 64, the folded form
        # at 128, grouped and not, and at 192 | 128 (PR 36)
        kernel_shapes=(
            (8, 2048, 12, 64),
            (8, 1024, 12, 64),
            (1, 8192, 12, 64),
            (2, 4096, 16, 128),
            (1, 8192, 32, 128, 128, 2),
            # latent attention's two widths: scores of 192, values of 128
            (1, 8192, 32, 192, 128),
            # trinity_mini_seq16384: the dense kernels at 16,384 tokens (the
            # full layer; past 1 MiB an array they stream chunks) and the
            # window kernels at a window of 2,048
            (1, 16384, 32, 128, 128, 4),
            (1, 16384, 32, 128, 128, 4, 2048),
        ),
        # sparse attention at its cell's shape (keye_vl2_seq16384): batch,
        # tokens, heads, width, key/value heads, the indexer's heads and
        # width, keys a query
        sparse_shape=(1, 16384, 32, 128, 4, 16, 64, 2048),
        # the gated short convolution's pass at its cell's shape
        # (lfm2_24b_a2b_seq4096x4): batch, tokens, channels, taps
        short_conv_shape=(4, 4096, 2048, 3),
        # the expert layer's rows added into their tokens at its largest
        # low rung (trinity_mini_seq16384): rows, tokens, width, experts a
        # token, experts held, experts routed over
        expert_rows_shape=(34816, 16384, 2048, 8, 16, 128),
    ),
    # the rehearsal: same control flow, CPU backend, interpreted kernels
    "tiny": dict(
        platform="cpu",
        model_params=(
            "vocab_size=512;embed_dim=64;num_heads=2;num_layers=2;"
            "dtype=bfloat16"
        ),
        vocab=512,
        heads=2,
        seq_len=128,
        per_chip_batch=2,
        steps=12,
        evaluation_steps=4,
        four_chip_steps=8,
        kill_run_steps=24,
        kill_step=2,
        kernel_shapes=(
            (2, 256, 2, 32),
            (1, 512, 2, 64),
            (1, 256, 4, 128, 128, 2),
            (1, 256, 2, 48, 32),
            (1, 256, 4, 128, 128, 2, 80),
        ),
        sparse_shape=(1, 256, 4, 32, 2, 2, 16, 48),
        short_conv_shape=(3, 64, 256, 3),
        expert_rows_shape=(640, 200, 64, 2, 4, 16),
    ),
}

# flash_attention vs mha_reference (float32 math at HIGHEST matmul
# precision), bf16 inputs.  bf16 keeps 8 mantissa bits (eps = 2^-8 ~ 3.9e-3):
# outputs and gradients are rounded to bf16 once on each side, the kernel's
# in-block matmuls take bf16 operands (the probabilities and their gradient
# rounded once; float32 accumulation), and the backward re-reads a bf16
# ``out``.  Agreement to a few eps of the largest magnitude
# is what "the same function" means here:
#   max|flash - ref| <= KERNEL_TOL * max(1, max|ref|)
KERNEL_TOL = 2e-2


# ---- parent -----------------------------------------------------------------


def _say(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _child_env(size: str, run: str, workdir: str) -> dict:
    cfg = SIZES[size]
    env = dict(os.environ)
    # the platform is pinned HERE, whatever the environment says
    env["JAX_PLATFORMS"] = cfg["platform"]
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, env.get("PYTHONPATH", "")) if p
    )
    xla_flags = [
        flag
        for flag in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in flag
    ]
    if cfg["platform"] == "cpu":
        # the rehearsal's stand-in for "one chip per process" (dp4: four)
        n = 4 if run == "dp4" else 1
        xla_flags.append(f"--xla_force_host_platform_device_count={n}")
    if run == "dp4":
        # the compiled step is read back from XLA's own dump; the
        # persistent cache would skip the compile that writes it
        xla_flags += [
            f"--xla_dump_to={os.path.join(workdir, 'dp4_hlo')}",
            "--xla_dump_hlo_as_text",
            "--xla_dump_hlo_module_re=.*train_step.*",
        ]
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["XLA_FLAGS"] = " ".join(xla_flags)
    return env


def _run_child(run: str, size: str, workdir: str, budget_secs: float):
    """One run = one child process in its own session.  Returns ``(exit
    code, report)``: ``(0, report)`` when the child ran to its end, else
    the code this script should exit with and ``None``.  Every process
    the child started dies with it."""
    report_path = os.path.join(workdir, f"{run}.report.json")
    log_path = os.path.join(workdir, f"{run}.log")
    argv = [
        sys.executable,
        os.path.abspath(__file__),
        "--child",
        run,
        "--size",
        size,
        "--workdir",
        workdir,
        "--report",
        report_path,
    ]
    _say(f"run {run!r} ({size}) starting")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            argv,
            env=_child_env(size, run, workdir),
            stdout=log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=budget_secs)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            # the child's whole session: master, workers, standbys
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    wall = time.monotonic() - t0
    if rc != 0:
        with open(log_path, errors="replace") as log:
            tail = log.readlines()[-60:]
        sys.stderr.write("".join(tail))
        if rc == EXIT_NO_DEVICE:
            _say(
                f"JAX found no {SIZES[size]['platform']!r} device "
                "(platform pinned by this script); nothing ran"
            )
            return EXIT_NO_DEVICE, None
        _say(
            f"run {run!r} "
            + (
                f"did not finish within {budget_secs:.0f}s"
                if rc is None
                else f"exited {rc}"
            )
        )
        return EXIT_FAILED, None
    with open(report_path) as f:
        report = json.load(f)
    report["wall_secs"] = round(wall, 1)
    return 0, report


def _keep(workdir: str, dest: str):
    """Copy what explains a run — logs, reports, telemetry, the compiled
    dp4 module — out of the tempdir; never the data or the exports."""
    os.makedirs(dest, exist_ok=True)
    for name in sorted(os.listdir(workdir)):
        src = os.path.join(workdir, name)
        if name.endswith("_telemetry"):
            shutil.copytree(src, os.path.join(dest, name), dirs_exist_ok=True)
        elif name.endswith((".log", ".json", ".jsonl")):
            shutil.copy(src, dest)
    for src in glob.glob(
        os.path.join(workdir, "dp4_hlo", "*train_step*after_optimizations.txt")
    ):
        shutil.copy(src, dest)


def _parent(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "elasticdl_tpu")):
        _say(
            "the elasticdl_tpu package is not beside this script; "
            "nothing to smoke"
        )
        return EXIT_NO_REPO
    runs = [r for r in args.runs.split(",") if r]
    unknown = [r for r in runs if r not in ALL_RUNS]
    if unknown or not runs:
        _say(f"unknown runs {unknown}; valid: {', '.join(ALL_RUNS)}")
        return EXIT_FAILED
    # data, exports and XLA dumps are bulky: always a removed tempdir
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    deadline = time.monotonic() + TOTAL_BUDGET_SECS
    failed = []
    device = None
    try:
        for run in runs:
            if run in ("workers4", "kill") and "bind" in failed:
                _say(f"skipping run {run!r}: the binding probe failed")
                failed.append(run)
                continue
            budget = min(RUN_BUDGET_SECS[run], deadline - time.monotonic())
            if budget <= 0:
                _say(f"out of time before run {run!r}")
                return EXIT_FAILED
            code, report = _run_child(run, args.size, workdir, budget)
            if report is None:
                if code == EXIT_NO_DEVICE or not args.keep_going:
                    return code
                failed.append(run)
                continue
            print(json.dumps(report), flush=True)
            device = device or report.get("device")
            if report["failures"]:
                _say(f"run {run!r} FAILED: " + "; ".join(report["failures"]))
                failed.append(run)
                if not args.keep_going:
                    return EXIT_FAILED
    finally:
        if args.keep:
            _keep(workdir, args.keep)
        shutil.rmtree(workdir, ignore_errors=True)
    if failed:
        _say(f"FAILED runs: {', '.join(failed)}")
        return EXIT_FAILED
    if args.size != "full":
        _say(
            f"rehearsal of {', '.join(runs)} passed on the CPU backend — "
            "not a chip result, no result line"
        )
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---- children ---------------------------------------------------------------
# Everything below runs in a child process; only here is JAX imported.


def _devices_or_exit(platform: str):
    import jax

    try:
        return jax.devices()
    except RuntimeError as ex:
        # the one failure with its own exit code: no accelerator
        print(
            f"chip_smoke: no {platform!r} backend: {ex}",
            file=sys.stderr,
            flush=True,
        )
        sys.exit(EXIT_NO_DEVICE)


def _ensure_data(workdir, cfg, name, num_records, seed):
    """EDLIO shards generated from a seed (the chip machine has no
    network and no checkout to fetch from); reused by later runs."""
    from elasticdl_tpu.data import recordio
    from elasticdl_tpu.data.recordio_gen import synthetic

    # a fresh checkout has no _native.so: build it before the first byte
    # is written, so no part of the smoke goes through the Python codec
    recordio.ensure_native_codec()
    out = os.path.join(workdir, "data", f"{name}_{num_records}")
    if not os.path.isdir(out):
        synthetic.gen_sequence(
            out,
            num_records=num_records,
            num_shards=2,
            seed=seed,
            seq_len=cfg["seq_len"],
            vocab=DATA_ALPHABET,
        )
    return out


class _CacheEvents:
    """jax.monitoring counts of persistent-compile-cache traffic."""

    PREFIX = "/jax/compilation_cache/"

    def __init__(self):
        from jax import monitoring

        self.counts = {
            "compile_requests_use_cache": 0,
            "cache_hits": 0,
            "cache_misses": 0,
        }
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs):
        if event.startswith(self.PREFIX):
            key = event[len(self.PREFIX) :]
            if key in self.counts:
                self.counts[key] += 1


def _telemetry(tdir):
    from elasticdl_tpu.telemetry.events import EVENTS_FILENAME, read_events
    from elasticdl_tpu.telemetry.tracing import SPANS_FILENAME, read_spans

    return (
        read_events(os.path.join(tdir, EVENTS_FILENAME)),
        read_spans(os.path.join(tdir, SPANS_FILENAME)),
    )


def _steady_window_compiles(events, spans, warm_step, until_last_step=False):
    """Compile spans ending inside each process's steady window: from
    the start of step ``warm_step`` (every program kind has had its first
    dispatch by then) to the end of the run (``until_last_step``: to the
    last step, leaving out a multi-process job's epilogue programs)."""
    inside = 0
    procs = {e.get("process_id", 0) for e in events if e["event"] == "step"}
    for proc in procs:
        steps = [
            e
            for e in events
            if e["event"] == "step"
            and e.get("process_id", 0) == proc
            and e.get("generation", 0) == 0
        ]
        warm = [e["monotonic"] for e in steps if e["step"] >= warm_step]
        if not warm:
            raise RuntimeError(
                f"process {proc} recorded no step >= {warm_step}"
            )
        start = min(warm)
        end = max(e["monotonic"] for e in steps) if until_last_step else None
        for span in spans:
            if (
                span["span"] == "compile"
                and span.get("process_id", 0) == proc
                and span.get("generation", 0) == 0
                and span["end"] > start
                and (end is None or span["end"] < end)
            ):
                inside += 1
    return inside


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


def _common_report(run, cfg, device, failures):
    """The fields and checks every run shares; ``device`` is
    ``{"platform", "kind", "count"}`` as JAX reported it to the process
    that ran on it."""
    import jax

    from elasticdl_tpu.data import recordio
    from elasticdl_tpu.ops.attention import kernel_interpret
    from elasticdl_tpu.telemetry import compile_tracker

    codec = "native" if recordio.native_available() else "python"
    interpret = None
    if device["platform"] != cfg["platform"]:
        failures.append(
            f"ran on platform {device['platform']!r}, pinned "
            f"{cfg['platform']!r}"
        )
    else:
        interpret = kernel_interpret(device["platform"])
    if codec != "native":
        failures.append("EDLIO decoded by the Python codec")
    if cfg["platform"] == "tpu":
        if interpret:
            failures.append("pallas kernels ran interpreted")
        if not re.search(r"v5 ?(lite|e)", str(device["kind"]), re.IGNORECASE):
            failures.append(f"device kind {device['kind']!r} is not a v5e")
    return {
        "run": run,
        "device": device,
        "jax": jax.__version__,
        "codec": codec,
        "kernel_interpret": interpret,
        "compile_count": compile_tracker.compile_count(),
        "compile_secs": round(compile_tracker.compile_secs_total(), 2),
        "failures": failures,
    }


def _train_argv(cfg, workdir, tdir, batch, steps, mesh_shape=""):
    train = _ensure_data(workdir, cfg, "train", batch * steps, seed=0)
    evald = _ensure_data(workdir, cfg, "eval", batch * 2, seed=1)
    argv = [
        "train",
        "--model_def",
        MODEL_DEF,
        "--model_params",
        cfg["model_params"],
        "--training_data",
        train,
        "--validation_data",
        evald,
        "--minibatch_size",
        str(batch),
        "--records_per_task",
        str(batch * STEPS_PER_TASK),
        "--num_epochs",
        "1",
        "--evaluation_steps",
        str(cfg["evaluation_steps"]),
        "--distribution_strategy",
        "Local",
        "--jax_platform",
        cfg["platform"],
        "--telemetry_dir",
        tdir,
        "--trace_sample_rate",
        "1.0",
    ]
    if mesh_shape:
        argv += ["--mesh_shape", mesh_shape]
    return argv


def _child_train(run, cfg, workdir):
    """Runs 1 (train), 3 (cache: the same command again) and 4 (dp4)."""
    from elasticdl_tpu import client

    devices = _devices_or_exit(cfg["platform"])
    failures = []
    if run == "dp4":
        if len(devices) < 4:
            raise RuntimeError(f"dp4 needs 4 devices, found {len(devices)}")
        n, mesh_shape, steps = 4, "dp=4", cfg["four_chip_steps"]
        devices = devices[:4]
    else:
        n, mesh_shape, steps = len(devices), "", cfg["steps"]
    batch = cfg["per_chip_batch"] * n
    tdir = os.path.join(workdir, f"{run}_telemetry")
    shutil.rmtree(tdir, ignore_errors=True)
    cache_events = _CacheEvents()
    result = client.run(_train_argv(cfg, workdir, tdir, batch, steps, mesh_shape))

    from elasticdl_tpu.parallel.elastic import describe_devices

    report = _common_report(run, cfg, describe_devices(devices), failures)
    report["peak_bytes_in_use"] = _peak_bytes(devices)
    loss = float(result["loss"])
    ceiling = math.log(cfg["vocab"])
    if not math.isfinite(loss):
        failures.append(f"eval loss {loss} is not finite")
    elif loss >= ceiling:
        failures.append(
            f"eval loss {loss:.3f} did not fall below its starting value "
            f"ln(vocab) = {ceiling:.3f}"
        )
    if result["steps"] != steps:
        failures.append(f"took {result['steps']} steps, expected {steps}")
    if result["device"] != report["device"]:
        failures.append(
            f"the run's own result names {result['device']}, "
            f"JAX reports {report['device']}"
        )
    events, spans = _telemetry(tdir)
    steady = _steady_window_compiles(
        events, spans, warm_step=cfg["evaluation_steps"] + 1
    )
    if steady:
        failures.append(f"{steady} compile(s) inside the steady window")
    report.update(
        steps=result["steps"],
        eval_loss=round(loss, 4),
        eval_accuracy=round(float(result.get("accuracy", float("nan"))), 4),
        loss_ceiling=round(ceiling, 4),
        steady_window_compiles=steady,
        cache=cache_events.counts,
    )
    if cfg["platform"] == "tpu" and run == "train":
        # the chip's device_kind must be in both peak tables, the
        # package's (the operator's goodput report) and the benchmark's,
        # with one peak (neither assumes a peak for an unknown kind)
        from elasticdl_tpu.telemetry import anatomy
        from perf.peaks import peaks_for  # raises for an unknown kind

        report["peak_flops"] = {
            "anatomy": anatomy.peak_flops_per_chip(),
            "perf": peaks_for(devices[0].device_kind)["bf16_flops_per_s"],
        }
        if report["peak_flops"]["anatomy"] != report["peak_flops"]["perf"]:
            failures.append(
                f"the package's peak table misses the device kind or "
                f"disagrees with perf/peaks.json: {report['peak_flops']}"
            )
    if run == "cache":
        counts = cache_events.counts
        if counts["cache_misses"] or not counts["cache_hits"]:
            failures.append(
                f"second process compiled instead of hitting the "
                f"persistent cache: {counts}"
            )
    if run == "dp4":
        report["dp4"] = _check_dp4(cfg, workdir, report, failures)
    return report


def _check_dp4(cfg, workdir, report, failures) -> dict:
    """What the compiled four-chip step sees, from XLA's own dump, and
    what the four chips held."""
    dump_dir = os.path.join(workdir, "dp4_hlo")
    names = sorted(os.listdir(dump_dir)) if os.path.isdir(dump_dir) else []
    dumps = [
        n
        for n in names
        if "train_step" in n
        and n.endswith("after_optimizations.txt")
    ]
    if not dumps:
        failures.append(
            f"no compiled train_step module among XLA's dumps: {names[:20]}"
        )
        return {"dumps": names[:20]}
    with open(os.path.join(dump_dir, dumps[-1])) as f:
        hlo = f.read()
    per_chip, whole = cfg["per_chip_batch"], cfg["per_chip_batch"] * 4
    seq = cfg["seq_len"]
    out = {
        "hlo": dumps[-1],
        "batch_param_per_chip": f"s32[{per_chip},{seq}]" in hlo,
        "batch_param_global": f"s32[{whole},{seq}]" in hlo,
    }
    if not out["batch_param_per_chip"] or out["batch_param_global"]:
        failures.append(f"the batch is not split four ways: {out}")
    if cfg["platform"] == "tpu":
        # the attention custom calls' operands: (batch, seq, heads * d)
        # where the kernels read heads out of the layer's layout, folded to
        # (batch * heads, seq, d) where they cannot
        calls = re.findall(
            r"= \(?bf16\[(\d+),(\d+),(\d+)\][^\n]*custom_call_target="
            r'"tpu_custom_call"',
            hlo,
        )
        lead = sorted({int(c[0]) for c in calls})
        out["attention_call_leading_dim"] = lead
        if lead not in ([per_chip], [per_chip * cfg["heads"]]):
            failures.append(
                f"attention custom calls lead with {lead}, expected the "
                f"per-chip batch {per_chip} or batch*heads "
                f"{per_chip * cfg['heads']} (global would be {whole} or "
                f"{whole * cfg['heads']})"
            )
        peaks = report["peak_bytes_in_use"]
        if not all(peaks) or max(peaks) > 1.5 * min(peaks):
            failures.append(f"uneven or missing per-chip memory: {peaks}")
    return out


def _child_kernel(run, cfg, workdir):
    """Run 3: the compiled kernels against the reference."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops.attention import (
        flash_attention,
        flash_layout,
        mha_reference,
    )
    from elasticdl_tpu.parallel.elastic import configure_compilation_cache
    from elasticdl_tpu.telemetry import compile_tracker

    devices = _devices_or_exit(cfg["platform"])
    configure_compilation_cache()
    compile_tracker.install()
    failures = []
    shapes = {}
    layouts = {}

    def weighted(fn):
        return lambda q, k, v, w: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    def with_gradients(fn):
        return jax.jit(
            lambda q, k, v, w: (
                fn(q, k, v),
                *jax.grad(weighted(fn), argnums=(0, 1, 2))(q, k, v, w),
            )
        )

    def reference_by_heads(reference, q, k, v, w):
        """The float32 reference's output and gradients, a few query heads
        of one key/value head at a time: it holds a few (B, heads, S, S)
        float32 arrays, which ``limit`` keeps to 1 GiB each."""
        heads, group = q.shape[2], q.shape[2] // k.shape[2]
        limit = max(1, 2**28 // (q.shape[0] * q.shape[1] * k.shape[1]))
        among = group if group > 1 else heads
        step = max(
            n for n in range(1, min(among, limit) + 1) if among % n == 0
        )
        fn = with_gradients(reference)
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        outs, dqs = [], []
        dk, dv = jnp.zeros_like(k), jnp.zeros_like(v)
        for first in range(0, heads, step):
            mine = slice(first, first + step)
            kv = (
                slice(first // group, first // group + 1)
                if group > 1
                else mine
            )
            out, dq, dk_part, dv_part = fn(
                q[:, :, mine], k[:, :, kv], v[:, :, kv], w[:, :, mine]
            )
            outs.append(out)
            dqs.append(dq)
            dk = dk.at[:, :, kv].add(dk_part)
            dv = dv.at[:, :, kv].add(dv_part)
        return (
            jnp.concatenate(outs, axis=2), jnp.concatenate(dqs, axis=2), dk, dv
        )

    for shape in cfg["kernel_shapes"]:
        keys = jax.random.split(jax.random.PRNGKey(sum(shape)), 4)
        batch, seq, heads, d = shape[:4]
        d_v = shape[4] if len(shape) > 4 else d
        kv_heads = shape[5] if len(shape) > 5 else heads
        # the shape's window (None: every earlier key).  Both functions are
        # made anew a shape: ``jax.jit`` keeps a function's trace by the
        # operands' shapes, and two shapes differ in the window alone
        window = shape[6] if len(shape) > 6 else None

        def reference(q, k, v, window=window):
            with jax.default_matmul_precision("highest"):
                return mha_reference(q, k, v, causal=True, window=window)

        def flash(q, k, v, window=window):
            return flash_attention(q, k, v, causal=True, window=window)

        q, k, v = (
            jax.random.normal(key, dims, jnp.float32).astype(jnp.bfloat16)
            for key, dims in zip(
                keys[:3],
                (
                    (batch, seq, heads, d),
                    (batch, seq, kv_heads, d),
                    (batch, seq, kv_heads, d_v),
                ),
            )
        )
        w = jax.random.normal(keys[3], (batch, seq, heads, d_v), jnp.float32)
        name = "x".join(map(str, shape))
        layouts[name] = flash_layout(q, k, v)
        lowered = jax.jit(flash).lower(q, k, v)
        if cfg["platform"] == "tpu" and "tpu_custom_call" not in (
            lowered.as_text()
        ):
            failures.append(f"{shape}: no compiled Mosaic call in the HLO")
        got = (
            lowered.compile()(q, k, v),
            *with_gradients(flash)(q, k, v, w)[1:],
        )
        want = reference_by_heads(reference, q, k, v, w)
        errs = {}
        for part, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            a = jnp.asarray(a, jnp.float32)
            b = jnp.asarray(b, jnp.float32)
            err = float(jnp.max(jnp.abs(a - b)))
            scale = max(1.0, float(jnp.max(jnp.abs(b))))
            errs[part] = round(err / scale, 5)
            if not math.isfinite(err) or err > KERNEL_TOL * scale:
                failures.append(
                    f"{shape} {part}: max|flash-ref| = {err:.4g} > "
                    f"{KERNEL_TOL} * {scale:.3g}"
                )
        shapes[name] = errs
    if {"lanes", "folded"} - set(layouts.values()):
        failures.append(f"a way of addressing heads was not run: {layouts}")
    shapes["sparse_" + "x".join(map(str, cfg["sparse_shape"]))] = (
        _check_sparse_kernels(cfg["sparse_shape"], failures)
    )
    shapes["short_conv_" + "x".join(map(str, cfg["short_conv_shape"]))] = (
        _check_short_conv_kernels(cfg["short_conv_shape"], failures)
    )
    shapes["expert_rows_" + "x".join(map(str, cfg["expert_rows_shape"]))] = (
        _check_expert_rows_sum(cfg["expert_rows_shape"], failures)
    )
    from elasticdl_tpu.parallel.elastic import describe_devices

    report = _common_report(run, cfg, describe_devices(devices), failures)
    report.update(
        peak_bytes_in_use=_peak_bytes(devices),
        tolerance=KERNEL_TOL,
        flash_layout=layouts,
        scaled_max_abs_err=shapes,
    )
    return report


def _check_short_conv_kernels(shape, failures) -> dict:
    """The compiled ``short_conv_fwd`` / ``short_conv_bwd`` (the layer's own
    entry, so through ``ops/on_mesh.py`` as the model calls them) against
    the plain form of ``layers/short_conv.py``: the output and all four
    gradients, bfloat16 streams, several sequences a batch."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.layers import short_conv

    batch, steps, channels, taps = shape
    keys = jax.random.split(jax.random.PRNGKey(48), 5)
    b, c, x, w = (
        jax.random.normal(key, (batch, steps, channels), jnp.bfloat16)
        for key in keys[:4]
    )
    kernel = 0.5 * jax.random.normal(keys[4], (taps, channels), jnp.float32)

    def with_gradients(fn):
        def run(b, c, x, kernel):
            out, vjp = jax.vjp(fn, b, c, x, kernel)
            return (out, *vjp(w))
        return jax.jit(run)

    lowered = with_gradients(short_conv.short_conv).lower(b, c, x, kernel)
    if jax.default_backend() == "tpu" and "tpu_custom_call" not in (
        lowered.as_text()
    ):
        failures.append(f"short_conv {shape}: no compiled Mosaic call in the HLO")
    got = lowered.compile()(b, c, x, kernel)
    want = with_gradients(short_conv.gated_short_conv)(b, c, x, kernel)
    errs = {}
    for part, a, r in zip(("out", "db", "dc", "dx", "dkernel"), got, want):
        a, r = jnp.asarray(a, jnp.float32), jnp.asarray(r, jnp.float32)
        err = float(jnp.max(jnp.abs(a - r)))
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        errs[part] = round(err / scale, 5)
        if not math.isfinite(err) or err > KERNEL_TOL * scale:
            failures.append(
                f"short_conv {shape} {part}: max|kernel-plain| = {err:.4g} > "
                f"{KERNEL_TOL} * {scale:.3g}"
            )
    return errs


def _check_expert_rows_sum(shape, failures) -> dict:
    """The compiled ``expert_rows_sum`` (``ops/grouped_matmul.py``'s
    ``sum_by_token``) against the plain float32 scatter-add it replaced, on
    a uniform routing laid out by the layer's own ``group_layout``:
    bfloat16 rows with float32 weights (the combine) and with weight 1 (the
    dispatch's transpose).  Both sides sum float32 products and round once,
    so they differ by a bfloat16 rounding where the order of a token's terms
    moved its sum."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.layers import moe
    from elasticdl_tpu.ops import grouped_matmul as gmm_ops

    rows, tokens, width, slots, held, routed = shape
    keys = jax.random.split(jax.random.PRNGKey(49), 3)
    _, top = jax.lax.top_k(jax.random.uniform(keys[0], (tokens, routed)), slots)
    group_ids = jnp.where(top < held, top, held).reshape(-1).astype(jnp.int32)
    order = gmm_ops.group_order(group_ids, held)
    layout = gmm_ops.group_layout(
        group_ids, held, gmm_ops.TILE_ROWS, rows, order, True
    )
    spans = gmm_ops.token_spans(
        group_ids, order.sizes, tokens, gmm_ops.TILE_ROWS
    )
    weights = jax.random.uniform(keys[1], (tokens, slots), jnp.float32, 0.05, 1.0)
    row_weight, row_token = moe._rows_of(weights, layout.row_pair)
    values = jax.random.normal(keys[2], (rows, width), jnp.bfloat16)
    if int(jnp.sum(row_token < tokens)) != int(jnp.sum(group_ids < held)):
        failures.append(f"expert_rows {shape}: the rung does not hold the draw")

    def plain(values, row_token, row_weight):
        products = values.astype(jnp.float32)
        if row_weight is not None:
            products = products * row_weight[:, None]
        return jnp.zeros((tokens, width), jnp.float32).at[row_token].add(
            products, mode="drop"
        ).astype(values.dtype)

    errs = {}
    for part, weight in (("combine", row_weight), ("dispatch_transpose", None)):
        lowered = jax.jit(
            lambda v, t, w: gmm_ops.sum_by_token(v, t, tokens, spans, w)
        ).lower(values, row_token, weight)
        if jax.default_backend() == "tpu" and "tpu_custom_call" not in (
            lowered.as_text()
        ):
            failures.append(f"expert_rows {shape}: no compiled Mosaic call in the HLO")
        a = jnp.asarray(lowered.compile()(values, row_token, weight), jnp.float32)
        r = jnp.asarray(jax.jit(plain)(values, row_token, weight), jnp.float32)
        err = float(jnp.max(jnp.abs(a - r)))
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        errs[part] = round(err / scale, 5)
        # (one bfloat16 rounding apart at most: 2^-8 of the value)
        if not math.isfinite(err) or err > 2.0**-7 * scale:
            failures.append(
                f"expert_rows {shape} {part}: max|kernel-plain| = {err:.4g} > "
                f"2^-7 * {scale:.3g}"
            )
    return errs


def _check_sparse_kernels(shape, failures, rows=256) -> dict:
    """The five sparse-attention kernels (``ops/sparse_attention.py``; the
    flash kernels over a selected set) against the materialised form, whole
    at ``shape`` with the float32 reference taken ``rows`` queries at a time
    (a block holds (heads, rows, tokens) float32 scores): the selection
    against ``lax.top_k`` pair by pair, then, over the KERNEL's own set,
    the attention's output and three gradients and the indexer's loss and
    its three gradients.  The selection is made twice, searched
    (``dsa_index``) and then checked from the search's threshold on the same
    operands (``dsa_index_hinted``): every pair, ``lse`` and the counters
    equal, every block's hint held, the hinted mask's own count of keys a
    query, and the two calls' times side by side."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import sparse_attention as sparse_ops
    from elasticdl_tpu.ops.attention import selected_flash_attention

    batch, seq, heads, d, kv_heads, index_heads, index_width, topk = shape
    rows = min(rows, seq)
    keys = jax.random.split(jax.random.PRNGKey(sum(shape)), 7)
    q, k, v, qi, ki = (
        jax.random.normal(key, dims, jnp.float32).astype(jnp.bfloat16)
        for key, dims in zip(
            keys,
            (
                (batch, seq, heads, d), (batch, seq, kv_heads, d),
                (batch, seq, kv_heads, d),
                (batch, seq, index_heads, index_width),
                (batch, seq, index_width),
            ),
        )
    )
    w = jax.random.normal(keys[5], (batch, seq, index_heads)) * (
        index_heads * index_width
    ) ** -0.5
    weight = jax.random.normal(keys[6], (batch, seq, heads, d), jnp.float32)

    @jax.jit
    def kernels(q, k, v, qi, ki, w, weight):
        mask, lse_i, kept, ties, searched, threshold = (
            sparse_ops.index_select_threshold(qi, ki, w, topk)
        )
        again = sparse_ops.index_select_hinted(qi, ki, w, threshold, topk)
        hinted = {
            "unequal": sum(
                jnp.sum(a != b)
                for a, b in zip(again, (mask, lse_i, kept, ties))
            ),
            "hint_held": jnp.mean(again[4]),
            "tie_search_blocks": jnp.mean(searched),
            "keys_a_query": jnp.sum(again[0].astype(jnp.int32)) / (batch * seq),
        }
        mask_t = sparse_ops.transpose_mask(mask)

        def attend(q, k, v):
            out, lse = selected_flash_attention(q, k, v, mask, mask_t)
            return jnp.sum(out.astype(jnp.float32) * weight), (out, lse)

        (_, (out, lse)), grads = jax.value_and_grad(
            attend, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        kl, kl_grads = jax.value_and_grad(
            lambda qi, ki, w: sparse_ops.indexer_kl(
                q, k, lse, mask, qi, ki, w, lse_i
            ),
            argnums=(0, 1, 2),
        )(qi, ki, w)
        return mask, kept, (out, *grads), (kl, *kl_grads), hinted, threshold

    mask, kept, got, got_kl, hinted, threshold = kernels(
        q, k, v, qi, ki, w, weight
    )
    f32 = [x.astype(jnp.float32) for x in (q, k, v, qi, ki)]

    @jax.jit
    def block(start, chosen, q, k, v, qi, ki, w, weight):
        """Rows ``[start, start + rows)``: the reference's own selection,
        and over ``chosen`` (the kernel's rows of the mask) the attention and
        the KL with every gradient (k, v and ki whole: summed outside)."""
        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1)

        with jax.default_matmul_precision("highest"):
            scores = sparse_ops.index_scores_reference(cut(qi), ki, cut(w))
            seen = (start + jnp.arange(rows))[:, None] >= jnp.arange(seq)
            _, index = jax.lax.top_k(
                jnp.where(seen, scores, -jnp.inf), min(topk, seq)
            )
            own = jax.vmap(jax.vmap(
                lambda ix: jnp.zeros((seq,), bool).at[ix].set(True)
            ))(index) & seen

            def attend(qb, k, v):
                out, probs = sparse_ops.selected_reference(qb, k, v, chosen)
                return jnp.sum(out * cut(weight)), (out, probs)

            (_, (out, probs)), grads = jax.value_and_grad(
                attend, argnums=(0, 1, 2), has_aux=True
            )(cut(q), k, v)
            kl, kl_grads = jax.value_and_grad(
                lambda qib, ki, wb: sparse_ops.indexer_kl_reference(
                    probs, sparse_ops.index_scores_reference(qib, ki, wb),
                    chosen,
                ),
                argnums=(0, 1, 2),
            )(cut(qi), ki, cut(w))
        agree = jnp.sum(own & chosen), jnp.sum(own)
        return agree, (out, *grads), (kl, *kl_grads)

    dense = sparse_ops.dense_mask(mask)
    parts = {name: [] for name in ("out", "dq", "dqi", "dw")}
    sums = {
        "dk": jnp.zeros(k.shape, jnp.float32), "dv": jnp.zeros(v.shape, jnp.float32),
        "dki": jnp.zeros(ki.shape, jnp.float32), "kl": 0.0,
    }
    agreed = pairs = 0
    for start in range(0, seq, rows):
        (same, all_), (out, dq, dk, dv), (kl, dqi, dki, dw) = block(
            start, dense[:, start:start + rows], *f32, w, weight
        )
        agreed, pairs = agreed + int(same), pairs + int(all_)
        for name, value in (("out", out), ("dq", dq), ("dqi", dqi), ("dw", dw)):
            parts[name].append(value)
        for name, value in (("dk", dk), ("dv", dv), ("dki", dki), ("kl", kl)):
            sums[name] = sums[name] + value
    want = {
        **{name: jnp.concatenate(value, axis=1) for name, value in parts.items()},
        **sums,
    }
    have = dict(
        zip(("out", "dq", "dk", "dv", "kl", "dqi", "dki", "dw"), got + got_kl)
    )
    errs = {"selected_pairs_agreeing": round(agreed / pairs, 6)}
    errs.update({name: float(value) for name, value in hinted.items()})
    if errs["unequal"] or errs["hint_held"] != 1.0:
        failures.append(
            f"sparse {shape}: the hinted selection on the search's own "
            f"operands: {errs['unequal']} values differ, the hint held in "
            f"{errs['hint_held']} of the blocks"
        )

    def seconds(call, *operands, calls=3):
        jax.block_until_ready(call(*operands))  # compiles
        start = time.perf_counter()
        for _ in range(calls):
            made = call(*operands)
        jax.block_until_ready(made)
        return (time.perf_counter() - start) / calls

    errs["select_ms"] = round(1e3 * seconds(
        jax.jit(lambda *x: sparse_ops.index_select_threshold(*x, topk)),
        qi, ki, w,
    ), 3)
    errs["hinted_ms"] = round(1e3 * seconds(
        jax.jit(lambda *x: sparse_ops.index_select_hinted(*x, topk)),
        qi, ki, w, threshold,
    ), 3)
    if agreed < 0.999 * pairs:
        failures.append(
            f"sparse {shape}: the selection agrees with lax.top_k on "
            f"{agreed} of {pairs} pairs"
        )
    expected = sum(min(t + 1, topk) for t in range(seq)) / seq
    for counted in (float(jnp.mean(kept)), errs["keys_a_query"]):
        if counted != expected:
            failures.append(
                f"sparse {shape}: {counted} keys a query, not {expected}"
            )
    for part, b in want.items():
        a = jnp.asarray(have[part], jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)))
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        errs[part] = round(err / scale, 5)
        if not math.isfinite(err) or err > KERNEL_TOL * scale:
            failures.append(
                f"sparse {shape} {part}: max|kernel-ref| = {err:.4g} > "
                f"{KERNEL_TOL} * {scale:.3g}"
            )
    return errs


def _child_workers(run, cfg, workdir):
    """Runs 5 (workers4) and the kill: master, task dispatch, four
    one-chip lockstep workers in one jax.distributed world.  THIS
    process is the master: it must never initialize a backend."""
    from elasticdl_tpu import client

    failures = []
    workers = 4
    batch = cfg["per_chip_batch"] * workers
    steps = cfg["kill_run_steps" if run == "kill" else "four_chip_steps"]
    records = batch * steps
    tdir = os.path.join(workdir, f"{run}_telemetry")
    export = os.path.join(workdir, f"{run}_export")
    shutil.rmtree(tdir, ignore_errors=True)
    shutil.rmtree(export, ignore_errors=True)
    argv = [
        "train",
        "--model_def",
        MODEL_DEF,
        "--model_params",
        cfg["model_params"],
        "--training_data",
        _ensure_data(workdir, cfg, "train", records, seed=0),
        # the final SAVE_MODEL task gathers the state off the devices: the
        # exported model_version is the number of steps the world really
        # EXECUTED (hosts enqueue ahead, and the chief reports a task
        # when its steps are enqueued)
        "--output",
        export,
        "--minibatch_size",
        str(batch),
        "--records_per_task",
        str(batch * STEPS_PER_TASK),
        "--num_epochs",
        "1",
        "--distribution_strategy",
        "AllreduceStrategy",
        "--num_workers",
        str(workers),
        "--jax_platform",
        cfg["platform"],
        "--port",
        "0",
        "--telemetry_dir",
        tdir,
        "--trace_sample_rate",
        "1.0",
    ]
    if run == "kill":
        plan = os.path.join(workdir, "kill_plan.json")
        with open(plan, "w") as f:
            json.dump(
                {
                    "name": "chip_smoke_kill",
                    "faults": [
                        {
                            "kind": "preempt_worker",
                            "fault_id": "kill_last_worker",
                            "at_step": cfg["kill_step"],
                            "process_id": workers - 1,
                            "cluster_version": 0,
                        }
                    ],
                },
                f,
            )
        argv += [
            "--envs",
            f"ELASTICDL_TPU_CHAOS_PLAN={plan},"
            f"ELASTICDL_TPU_CHAOS_EVENTS="
            f"{os.path.join(workdir, 'kill_events.jsonl')}",
        ]
    try:
        result = client.run(argv)
    except RuntimeError as ex:
        if run != "kill":
            raise
        # the kill is an experiment, not a gate: what the telemetry saw
        # of a job that did not survive it is the finding
        failures.append(f"the job did not complete: {ex}")
        result = {}

    from jax._src import xla_bridge

    master_touched_jax = xla_bridge.backends_are_initialized()
    if master_touched_jax:
        failures.append("the master process initialized a JAX backend")
    training = result.get("training", {})
    if (
        training.get("total_records") != records
        or training.get("failed_records")
    ):
        failures.append(
            f"records not accounted exactly once: {training}, "
            f"expected {records}"
        )
    events, spans = _telemetry(tdir)
    joins = {}
    for span in spans:
        if span["span"] == "world_join":
            joins.setdefault(span.get("generation", 0), {})[
                span["process_id"]
            ] = {
                k: span.get(k)
                for k in ("platform", "kind", "count", "local_devices")
            }
    first = joins.get(0, {})
    expected = {
        "platform": cfg["platform"],
        "count": workers,
        "local_devices": 1,
    }
    if sorted(first) != list(range(workers)) or any(
        join[key] != value
        for join in first.values()
        for key, value in expected.items()
    ):
        failures.append(
            f"generation 0 is not {workers} one-device processes of one "
            f"{workers}-device world: {first}"
        )
    from elasticdl_tpu.utils.export_utils import read_manifest

    # (a kill the job did not survive leaves no export)
    executed = (
        read_manifest(export)["model_version"] if os.path.isdir(export) else None
    )
    if run == "workers4" and executed != steps:
        failures.append(
            f"the exported model is at step {executed}, expected {steps}"
        )
    steady = None
    if run == "workers4":
        steady = _steady_window_compiles(
            events, spans, warm_step=3, until_last_step=True
        )
        if steady:
            failures.append(f"{steady} compile(s) inside the steady window")
    # the device as the WORKERS saw it (their world_join spans): this
    # process is the master and stays off JAX to the end
    chief = first.get(0, {})
    report = _common_report(
        run,
        cfg,
        {key: chief.get(key) for key in ("platform", "kind", "count")},
        failures,
    )
    report.update(
        steps=executed,
        training=training,
        world_joins={str(g): joins[g] for g in sorted(joins)},
        world_sizes={str(g): len(joins[g]) for g in sorted(joins)},
        reforms=result.get("reforms", []),
        steady_window_compiles=steady,
        master_initialized_backend=master_touched_jax,
    )
    return report


_BIND_PROBE = """
import json, os, sys
mode, i, n, coordinator, platform = sys.argv[1:6]
i, n = int(i), int(n)
if mode == "standby":
    # what a warm standby has done before its assignment arrives
    import jax
    from elasticdl_tpu.parallel.elastic import chip_binding_env
    os.environ.update(chip_binding_env(i, n))
from elasticdl_tpu.parallel import elastic
elastic.initialize_world(coordinator, n, i, platform=platform, timeout_secs=90)
import jax
import jax.numpy as jnp
from jax.experimental import multihost_utils
seen = multihost_utils.process_allgather(jnp.asarray([i]))
print("BIND " + json.dumps(dict(
    elastic.describe_devices(),
    process=i,
    local_devices=jax.local_device_count(),
    local_ids=[d.id for d in jax.local_devices()],
    allgather=[int(x) for x in seen.ravel()],
)), flush=True)
elastic.shutdown_world()
"""


def _child_bind(run, cfg, workdir):
    """Four one-chip processes in one jax.distributed world, without the
    master: does the binding ``LocalInstanceManager`` hands its workers
    give each process ONE chip of a four-chip world — set at spawn (a
    cold worker) and set in-process after ``import jax`` (an activated
    standby)?"""
    from elasticdl_tpu.parallel.elastic import (
        chip_binding_env,
        pick_coordinator_port,
    )

    failures = []
    workers = 4
    variants = {}
    for mode in ("spawn", "standby"):
        if variants:
            time.sleep(5)  # the previous world's chips are being released
        coordinator = f"localhost:{pick_coordinator_port()}"
        procs = []
        for i in range(workers):
            env = dict(os.environ)
            if mode == "spawn":
                env.update(chip_binding_env(i, workers))
            log = open(os.path.join(workdir, f"bind_{mode}_{i}.log"), "w+")
            procs.append(
                (
                    subprocess.Popen(
                        [
                            sys.executable,
                            "-c",
                            _BIND_PROBE,
                            mode,
                            str(i),
                            str(workers),
                            coordinator,
                            cfg["platform"],
                        ],
                        env=env,
                        stdout=log,
                        stderr=subprocess.STDOUT,
                    ),
                    log,
                )
            )
        deadline = time.monotonic() + 150
        seen = []
        for proc, log in procs:
            try:
                rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                rc = "timeout"
            log.seek(0)
            lines = log.read().splitlines()
            log.close()
            found = [
                json.loads(ln[5:]) for ln in lines if ln.startswith("BIND ")
            ]
            seen.append(
                found[0]
                if rc == 0 and found
                else {"rc": rc, "tail": [ln[:300] for ln in lines[-8:]]}
            )
        variants[mode] = seen
        good = all(
            p.get("local_devices") == 1
            and p.get("count") == workers
            and p.get("platform") == cfg["platform"]
            # (gathered in DEVICE order, which the chip does not tie to
            # the process index)
            and sorted(p.get("allgather", ())) == list(range(workers))
            for p in seen
        )
        if not good:
            failures.append(
                f"{mode}: not {workers} one-device processes of one "
                f"{workers}-device world"
            )
            break  # the next variant could only time out the same way
    chief = variants["spawn"][0]
    report = _common_report(
        run,
        cfg,
        {key: chief.get(key) for key in ("platform", "kind", "count")},
        failures,
    )
    report["bindings"] = variants
    return report


CHILDREN = {
    "bind": _child_bind,
    "train": _child_train,
    "cache": _child_train,
    "dp4": _child_train,
    "kernel": _child_kernel,
    "workers4": _child_workers,
    "kill": _child_workers,
}


def _child(args) -> int:
    os.makedirs(args.workdir, exist_ok=True)
    report = CHILDREN[args.child](args.child, SIZES[args.size], args.workdir)
    report["size"] = args.size
    with open(args.report, "w") as f:
        json.dump(report, f)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--size",
        choices=sorted(SIZES),
        default="full",
        help="full: the chip run; tiny: a CPU rehearsal of the control flow",
    )
    parser.add_argument(
        "--runs",
        default=",".join(DEFAULT_RUNS),
        help=f"comma-separated, from: {', '.join(ALL_RUNS)}",
    )
    parser.add_argument(
        "--keep",
        default="",
        help="copy the runs' logs, reports and telemetry into this directory",
    )
    parser.add_argument("--workdir", help=argparse.SUPPRESS)
    parser.add_argument(
        "--keep_going",
        action="store_true",
        help="run every listed run even after one failed (still exits 1)",
    )
    parser.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    parser.add_argument("--report", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        return _child(args)
    return _parent(args)


if __name__ == "__main__":
    sys.exit(main())
