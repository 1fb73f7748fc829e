"""The benchmark's command:

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which is the one that holds the chip (``chip_smoke.py``'s rule:
the platform is pinned, no accelerator is a failure and never a fall-back).
The last line of stdout is the result; a rate in it is all the work over
all the time of the window, and the line before it carries the interval
readings' count, median and quartiles beside that total.
``--rehearse-cpu`` walks the same control flow on the CPU backend at a tiny
size and reports counts, never a device metric."""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXIT_FAILED = 1
EXIT_NO_PROGRAM = 2
EXIT_NO_DEVICE = 3

# a traced rate under this share of the untraced one means the tracing
# changed the program it was meant to explain
TRACED_RATE_FLOOR = 0.9


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=None, help="default: BENCHMARK.json")
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument(
        "--control", action="store_true",
        help="with --trace 1: the plain reference with float8 weights in the "
        "program's place in the comparison; `correct` has to read false",
    )
    parser.add_argument(
        "--keep-trace", default=None,
        help="directory to copy the reduced events of the trace into",
    )
    return parser.parse_args(argv)


def fail(code: int, message: str):
    print(f"perf/run.py: {message}", file=sys.stderr)
    sys.exit(code)


def open_devices(cell, platform: str):
    """The platform is pinned before JAX starts; no accelerator, or another
    number of chips than the cell asks for, is a failure and never a
    fall-back."""
    os.environ["JAX_PLATFORMS"] = platform
    try:
        import elasticdl_tpu  # noqa: F401
    except ImportError as ex:
        fail(EXIT_NO_PROGRAM, f"the program is not beside the benchmark: {ex}")
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as ex:
        fail(EXIT_NO_DEVICE, f"JAX finds no {platform} device: {ex}")
    if devices[0].platform != platform or len(devices) != cell.chips:
        fail(
            EXIT_NO_DEVICE,
            f"cell {cell.name} needs {cell.chips} {platform} device(s); JAX "
            f"has {len(devices)} of platform {devices[0].platform}",
        )
    return devices


def describe_device(devices) -> dict:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        # the allocator's live arrays plus what it reserved for programs'
        # temporaries: `peak_bytes_in_use` alone leaves the step's 4-8 GB of
        # temporaries out (PERF.md, "peak memory")
        "memory_peak_bytes": max(
            s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
            for s in stats
        ),
    }


def traced_report(args, cell, probe, trace_dir, peaks, rates, info, checks, device):
    """Reduce the trace, hold it to the untraced readings of the same
    process, and read the cell's per-layer metrics.  Fills ``info``,
    ``checks`` and ``device``; returns the metrics and the breakdown."""
    from perf import trace_reduce

    untraced, traced = rates
    info["untraced_rate"] = untraced["total_over_window"]
    info["traced_rate"] = traced["total_over_window"]
    info["traced_rates"] = traced["rates"]
    info["host_traced"] = probe.host["trace"].as_dict()
    info["traced_over_untraced"] = info["traced_rate"] / info["untraced_rate"]
    info["tracing_left_the_rate_alone"] = (
        info["traced_over_untraced"] >= TRACED_RATE_FLOOR
    )
    reduced = breakdown = None
    xplane = trace_reduce.find_xplane(trace_dir)
    if xplane is not None:
        events = trace_reduce.align_host_spans(
            trace_reduce.load(xplane), probe.host_spans
        )
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            trace_reduce.save_events(
                events, os.path.join(args.keep_trace, cell.name + ".json.gz")
            )
        if events["devices"]:
            reduced = trace_reduce.reduce(events)
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = trace_reduce.breakdown(reduced)
        # a device cannot be busy for longer than a step takes
        measured = probe.host["measure"]
        wall_ms = 1e3 * measured.wall_s / measured.steps
        busy_ms = 1e3 * reduced["busy_s"] / max(1, probe.host["trace"].steps)
        idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
        info["wall_ms_per_step_untraced"] = wall_ms
        info["device_ms_per_step_traced"] = busy_ms
        info["idle_share"] = idle
        checks["device_busy_within_step_time"] = busy_ms <= wall_ms * 1.02
        checks["device_ran"] = reduced["busy_s"] > 0
        checks["idle_gaps_named"] = idle < 0.03 or bool(breakdown["idle_gaps"])
    if args.rehearse_cpu:
        return {}, breakdown
    batch = cell.traffic["batch_per_chip"]
    flops = {k: batch * v for k, v in cell.flops_per_record().items()}
    run = {
        "cell": cell,
        "host": probe.host["measure"].as_dict(),
        "host_traced": probe.host["trace"].as_dict(),
        "trace": reduced,
        "traced_steps": probe.host["trace"].steps,
        # model FLOPs of a step on one chip: "train" for the whole step,
        # further keys for the parts a kernel's roofline share is taken of
        "flops_per_step_chip": flops,
        "peaks": peaks,
        "untraced": untraced,
        "traced": traced,
    }
    metrics = {}
    for entry in cell.metrics("per_layer"):
        value = cell.reader(entry["name"])(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics, breakdown


def main(argv=None) -> int:
    args = parse_args(argv)
    from perf import manifest, meter, trafficgen

    cell = manifest.Cell(manifest.load_manifest(args.manifest), args.workload)
    platform = "cpu" if args.rehearse_cpu else "tpu"
    devices = open_devices(cell, platform)
    peaks = None
    if not args.rehearse_cpu:
        from perf.peaks import peaks_for

        peaks = peaks_for(devices[0].device_kind)

    import jax
    from elasticdl_tpu.parallel.elastic import configure_compilation_cache
    from elasticdl_tpu.telemetry import compile_tracker
    from elasticdl_tpu.utils.args import parse_master_args

    from perf import executor as executor_mod

    configure_compilation_cache("")
    marks = {"imports_s": time.perf_counter()}
    work_dir = os.path.join(ROOT, "perf", ".data", cell.name)
    driver = cell.driver()
    counts = driver.prepare(cell, args.seed, work_dir)
    marks["data_s"] = time.perf_counter()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(work_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    probe = executor_mod.Probe(cell, counts, args.seconds, trace_dir)
    executor = executor_mod.MeasuredExecutor(
        parse_master_args(
            executor_mod.build_argv(cell, counts, args.seed, platform)
        ),
        probe,
    )
    marks["build_s"] = time.perf_counter()
    driven = driver.run(executor, probe, counts, cell.traffic)
    # set-up, split: imports and the runtime's start; data made, written and
    # read once; executor built (codec, reader, mesh); the warm-up tasks
    # (trainer init, trace/lower, compile or cache load, first steps); the
    # dropped intervals while the host pipeline fills
    marks["warmup_s"] = probe.warmup_end
    marks["fill_s"] = probe.window_start
    setup_parts, previous = {}, PROCESS_START
    for name, at in marks.items():
        setup_parts[name] = at - previous
        previous = at
    # ``setup_s`` is the set-up this repo's code does, the four parts after
    # ``imports_s``: no PR's code runs in the imports and the TPU runtime's
    # start but its import graph, and their spread is the machine's (PR 64)
    setup_s = probe.window_start - marks["imports_s"]

    unit = cell.config["work"]["unit"]
    per_record = trafficgen.units_per_record(
        cell.record_kind(), cell.traffic, unit
    )

    def summarize(readings):
        return meter.summarize(
            [(records * per_record, s) for records, s in readings], cell.chips
        )

    untraced = summarize(probe.readings)
    first_loss = float(jax.device_get(probe.first_loss))
    last_loss = float(jax.device_get(probe.last_loss))
    compiles = compile_tracker.compile_count() - probe.compiles_at_window_start
    checks = {
        "no_compile_in_window": compiles == 0,
        "loss_finite": math.isfinite(first_loss) and math.isfinite(last_loss),
        "loss_below_initial": last_loss < first_loss,
        # what the way of driving the load can hold its records to
        **driven["checks"],
    }
    device = describe_device(devices)
    info = {
        "workload": cell.name,
        "seed": args.seed,
        "unit": f"{unit}/s/chip",
        **untraced,
        "steps": probe.steps,
        "first_loss": first_loss,
        "last_loss": last_loss,
        "compiles_in_window": compiles,
        "compile_secs_total": compile_tracker.compile_secs_total(),
        "data": {
            k: counts.get(k) for k in ("num_records", "bytes", "generated")
        },
        "host": probe.host["measure"].as_dict(),
        "setup_s": setup_s,
        "setup_parts": setup_parts,
        # what ``setup_s`` was up to PR 63: ``setup_s`` and ``imports_s``
        "since_process_start_s": probe.window_start - PROCESS_START,
        "checks": checks,
        "reference": "traced run only",
    }
    # each number `correct` compares, beside its limit: the last lines of
    # stderr and the last key of the result line, which is what the
    # driver's record keeps of a run that is not correct
    numbers = {
        "compiles_in_window": {"value": compiles, "limit": 0},
        # below the loss of the seeded initial parameters
        "last_loss": {"value": last_loss, "limit": first_loss},
    }
    metrics, breakdown = {}, None
    if args.trace:
        metrics, breakdown = traced_report(
            args, cell, probe, trace_dir, peaks,
            (untraced, summarize(probe.traced_readings)), info, checks, device,
        )
        # after the window and after every per-layer metric has been read:
        # the comparison's compile and memory are in no metric
        from perf import reference

        compared = reference.compare(cell, executor, args.seed, args.control)
        info["reference"] = compared or "none"
        if compared:
            checks["reference_agrees"] = compared["agrees"]
            for name, limit in compared["tolerance"].items():
                numbers[name + "_err"] = {
                    "value": compared[name + "_err"], "limit": limit
                }
    elif not args.rehearse_cpu:
        values = {
            # all the work over all the time of the window
            cell.config["work"]["rate_metric"]: untraced["total_over_window"],
            "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
            "setup_s": setup_s,
        }
        for entry in cell.metrics("end_to_end"):
            metrics[entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"]
            }
    print(json.dumps(info), flush=True)
    result = {
        "correct": all(checks.values()),
        "attempted": sum(r for r, _ in probe.readings + probe.traced_readings),
        "failed": driven["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = numbers
    for name, number in numbers.items():
        print(
            f"perf/run.py: compared {name} {number['value']} limit {number['limit']}",
            file=sys.stderr, flush=True,
        )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
