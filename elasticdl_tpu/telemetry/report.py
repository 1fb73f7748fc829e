"""Run-report CLI: join the telemetry event log with chaos artifacts.

::

    python -m elasticdl_tpu.telemetry.report <run_dir> [--json] [--output f]

``<run_dir>`` is any directory tree containing telemetry ``events.jsonl``
files (e.g. a chaos runner ``--workdir``, which holds one run under
``chaos/telemetry/`` and one under ``baseline/telemetry/``).  For each
run the report computes, per world generation:

- step count and p50/p95/p99 step time (from worker ``step`` samples);
- reform downtime — last ``step`` of generation N to first ``step`` of
  generation N+1 — annotated with the chaos fault that caused it (from
  ``chaos_events.jsonl`` / mirrored ``fault_injected`` events) and the
  tasks recovered inside the gap;
- per-worker records/sec (lockstep note: every process steps through the
  full global batch, so per-worker rates describe step cadence, not
  disjoint data slices);
- worker wall-clock bucket totals (``time_<bucket>_ms``) summed from
  ``task_done`` events.

``chaos_result.json`` (written by ``python -m elasticdl_tpu.chaos.runner``)
is surfaced verbatim so CI reads verdicts and numbers from one place.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from collections import defaultdict

from elasticdl_tpu.telemetry.events import EVENTS_FILENAME, read_events

# a fault can fire slightly before the victim's last recorded step lands
# in the log (the event is written at step START); allow this much skew
# when attributing a downtime gap to a fault
_FAULT_ATTRIBUTION_SLACK_SECS = 5.0


def percentile(samples: list[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) — exact over raw samples,
    no interpolation surprises in tiny runs."""
    if not samples:
        raise ValueError("no samples")
    ordered = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def _find_files(run_dir: str, filename: str) -> list[str]:
    found = []
    for root, _dirs, files in os.walk(run_dir):
        if filename in files:
            found.append(os.path.join(root, filename))
    return sorted(found)


def _load_fault_events(run_dir: str) -> list[dict]:
    """Fault firings from every chaos event log under the run dir plus
    any mirrored ``fault_injected`` telemetry events (deduplicated by
    fault id + firing time)."""
    faults = []
    for path in _find_files(run_dir, "chaos_events.jsonl"):
        for event in read_events(path):
            if "observation" not in event:
                faults.append(event)
    seen = {(f.get("fault_id"), round(f.get("monotonic", 0), 3)) for f in faults}
    for path in _find_files(run_dir, EVENTS_FILENAME):
        for event in read_events(path):
            if event.get("event") != "fault_injected":
                continue
            key = (event.get("fault_id"), round(event.get("monotonic", 0), 3))
            if key not in seen:
                seen.add(key)
                faults.append(event)
    return sorted(faults, key=lambda f: f.get("monotonic", 0.0))


def _generation_stats(steps: list[dict]) -> dict:
    samples = [
        e["duration_secs"] for e in steps if e.get("duration_secs") is not None
    ]
    workers = sorted({e.get("worker_id", 0) for e in steps})
    stats = {
        "steps": len(steps),
        "workers": workers,
        "records": sum(e.get("records", 0) for e in steps),
        "first_step_at": steps[0]["monotonic"],
        "last_step_at": steps[-1]["monotonic"],
    }
    if samples:
        stats.update(
            {
                "step_time_p50_ms": percentile(samples, 50) * 1000.0,
                "step_time_p95_ms": percentile(samples, 95) * 1000.0,
                "step_time_p99_ms": percentile(samples, 99) * 1000.0,
                "step_time_mean_ms": sum(samples) / len(samples) * 1000.0,
            }
        )
    return stats


def _worker_throughput(steps: list[dict]) -> dict[int, float]:
    """records/sec per worker, summed over the spans the worker was
    actually stepping (gaps between generations excluded because each
    generation's span is measured independently)."""
    spans: dict[int, float] = defaultdict(float)
    records: dict[int, float] = defaultdict(float)
    by_worker_gen: dict[tuple, list[dict]] = defaultdict(list)
    for event in steps:
        key = (event.get("worker_id", 0), event.get("generation", 0))
        by_worker_gen[key].append(event)
    for (worker_id, _gen), events in by_worker_gen.items():
        span = events[-1]["monotonic"] - events[0]["monotonic"]
        if span > 0:
            spans[worker_id] += span
            records[worker_id] += sum(e.get("records", 0) for e in events)
    return {
        w: records[w] / spans[w] for w in sorted(spans) if spans[w] > 0
    }


def _attribute_fault(faults: list[dict], gap_start: float, gap_end: float):
    candidates = [
        f
        for f in faults
        if gap_start - _FAULT_ATTRIBUTION_SLACK_SECS
        <= f.get("monotonic", 0.0)
        <= gap_end
    ]
    return candidates[-1] if candidates else None


def analyze_events(events: list[dict], faults: list[dict]) -> dict:
    """Summarize one run's telemetry event stream (pure function — the
    unit tests drive it with canned logs)."""
    steps = sorted(
        (e for e in events if e.get("event") == "step"),
        key=lambda e: e.get("monotonic", 0.0),
    )
    by_gen: dict[int, list[dict]] = defaultdict(list)
    for event in steps:
        by_gen[event.get("generation", 0)].append(event)

    generations = {
        gen: _generation_stats(by_gen[gen]) for gen in sorted(by_gen)
    }

    recovered = [e for e in events if e.get("event") == "task_recovered"]
    reform_events = [
        e
        for e in events
        if e.get("event") in ("reform_start", "reform_complete", "reform_latency")
    ]

    downtimes = []
    ordered_gens = sorted(by_gen)
    for prev, nxt in zip(ordered_gens, ordered_gens[1:]):
        gap_start = generations[prev]["last_step_at"]
        gap_end = generations[nxt]["first_step_at"]
        downtime = {
            "from_generation": prev,
            "to_generation": nxt,
            "downtime_secs": max(0.0, gap_end - gap_start),
            "tasks_recovered": sum(
                1
                for e in recovered
                if gap_start <= e.get("monotonic", 0.0) <= gap_end
            ),
        }
        fault = _attribute_fault(faults, gap_start, gap_end)
        if fault is not None:
            downtime["cause"] = {
                "fault_id": fault.get("fault_id"),
                "kind": fault.get("kind"),
                "process_id": fault.get("process_id"),
                "at_step": fault.get("step"),
            }
        downtimes.append(downtime)

    # task_done carries per-task DELTAS (lockstep exec counters);
    # worker_timing carries a runtime's cumulative TOTALS (local
    # executor) — sum the former, take max-per-worker of the latter
    time_buckets: dict[str, float] = defaultdict(float)
    cumulative: dict[tuple, float] = {}
    for event in events:
        if event.get("event") == "task_done":
            for key, value in event.items():
                if key.startswith("time_") and key.endswith("_ms"):
                    time_buckets[key[len("time_") : -len("_ms")]] += value
        elif event.get("event") == "worker_timing":
            for key, value in event.items():
                if key.startswith("time_") and key.endswith("_ms"):
                    wk = (event.get("worker_id", 0), key)
                    cumulative[wk] = max(cumulative.get(wk, 0.0), value)
    for (_worker, key), value in cumulative.items():
        time_buckets[key[len("time_") : -len("_ms")]] += value

    out = {
        "generations": generations,
        "reform_downtime": downtimes,
        "records_per_sec_by_worker": _worker_throughput(steps),
        "tasks_recovered_total": len(recovered),
        "reform_event_count": len(reform_events),
        "worker_time_ms": dict(time_buckets),
        "events_total": len(events),
    }
    if not events:
        # explicit "no data" marker: a run dir that exists but has not
        # produced events yet (job starting, rotated-away shards) must
        # report cleanly, never traceback
        out["no_data"] = "event log present but empty — no samples yet"
    goodput = goodput_section(events)
    if goodput is not None:
        out["goodput"] = goodput
    replication = replication_section(events)
    if replication is not None:
        out["replication"] = replication
    multislice = multislice_section(events)
    if multislice is not None:
        out["multislice"] = multislice
    master_ha = master_ha_section(events)
    if master_ha is not None:
        out["master_ha"] = master_ha
    serving = serving_section(events)
    if serving is not None:
        out["serving"] = serving
    memory = memory_section(events)
    if memory is not None:
        out["memory"] = memory
    slo = slo_section(events)
    if slo is not None:
        out["slo"] = slo
    streaming = streaming_section(events)
    if streaming is not None:
        out["streaming"] = streaming
    return out


# step-anatomy goodput: the phase taxonomy the events carry (one
# definition site: telemetry/anatomy.py); device-path = everything the
# dispatch spends on the device side of the pipeline
_GOODPUT_DEVICE_PATH = ("assemble", "h2d_transfer", "device_compute")
_GOODPUT_STRAGGLER_FACTOR = 1.5


def _phase_samples(anat_events: list[dict]) -> dict[str, list[float]]:
    from elasticdl_tpu.telemetry.anatomy import ALL_PHASES

    samples: dict[str, list[float]] = {}
    for event in anat_events:
        for phase in ALL_PHASES:
            value = event.get(f"{phase}_ms")
            if value is not None:
                samples.setdefault(phase, []).append(float(value))
    return samples


def _goodput_generation(anat_events: list[dict]) -> dict:
    """Goodput stats for ONE generation's ``step_anatomy`` events."""
    samples = _phase_samples(anat_events)
    wall_ms = sum(float(e.get("wall_ms", 0.0)) for e in anat_events)
    records = sum(int(e.get("records", 0)) for e in anat_events)
    steps = sum(int(e.get("steps", 0)) for e in anat_events)
    phases = {}
    for phase, values in samples.items():
        total = sum(values)
        phases[phase] = {
            "total_ms": round(total, 3),
            "share": round(total / wall_ms, 4) if wall_ms else None,
            "p50_ms": round(percentile(values, 50), 3),
            "p95_ms": round(percentile(values, 95), 3),
            "p99_ms": round(percentile(values, 99), 3),
        }
    # the sum-exact contract, verified not assumed: the per-event
    # residual between wall and the phase sum (incl. untracked) is
    # float noise only
    residual = max(
        (
            abs(
                float(e.get("wall_ms", 0.0))
                - sum(
                    float(e.get(f"{p}_ms", 0.0))
                    for p in samples
                )
            )
            for e in anat_events
        ),
        default=0.0,
    )
    host_ms = sum(samples.get("host_fetch", []))
    device_path_ms = sum(
        sum(samples.get(p, [])) for p in _GOODPUT_DEVICE_PATH
    )
    untracked_ms = sum(samples.get("untracked", []))
    out = {
        "dispatches": len(anat_events),
        "steps": steps,
        "records": records,
        "wall_ms_total": round(wall_ms, 3),
        "phases": phases,
        "max_sum_residual_ms": round(residual, 6),
        "untracked_share": round(untracked_ms / wall_ms, 4)
        if wall_ms
        else None,
        # live e2e-vs-roofline: the binding path's busy time (host
        # fetch wait vs the device path) over end-to-end wall — 1.0
        # means zero overlap slack, MEASURED per dispatch rather than
        # inferred from separate ceiling runs
        "e2e_vs_roofline": round(
            max(host_ms, device_path_ms) / wall_ms, 4
        )
        if wall_ms
        else None,
        "binding": (
            "host_fetch" if host_ms > device_path_ms else "device_path"
        ),
    }
    # async-dispatch overlap visibility: how much of device_compute was
    # the enqueue call vs waiting for results
    enqueue_ms = sum(float(e.get("enqueue_ms", 0.0)) for e in anat_events)
    ready_ms = sum(float(e.get("ready_wait_ms", 0.0)) for e in anat_events)
    if enqueue_ms or ready_ms:
        out["device_compute_split_ms"] = {
            "enqueue": round(enqueue_ms, 3),
            "ready_wait": round(ready_ms, 3),
        }
    # model-FLOPs MFU, when the model cost and the device peak are known
    flops = next(
        (
            e["flops_per_record"]
            for e in anat_events
            if e.get("flops_per_record")
        ),
        None,
    )
    peak = next(
        (
            e["peak_flops_per_chip"]
            for e in anat_events
            if e.get("peak_flops_per_chip")
        ),
        None,
    )
    n_chips = next(
        (e["n_chips"] for e in anat_events if e.get("n_chips")), 1
    )
    device_secs = sum(samples.get("device_compute", [])) / 1000.0
    if flops is None:
        out["mfu"] = None
        out["mfu_reason"] = "model FLOPs unknown (not in the zoo cost table)"
    elif peak is None:
        out["mfu"] = None
        out["mfu_reason"] = (
            "device peak FLOPs unknown "
            "(set ELASTICDL_TPU_PEAK_FLOPS_PER_CHIP)"
        )
    elif device_secs <= 0:
        out["mfu"] = None
        out["mfu_reason"] = "no device_compute time measured"
    else:
        out["mfu"] = round(
            flops * records / (device_secs * peak * n_chips), 4
        )
    # per-host straggler attribution: whose device_compute vs
    # host_fetch lags the fleet — the "which worker, which phase"
    # answer the barrier-wait split alone can't give
    by_worker: dict = defaultdict(list)
    for event in anat_events:
        by_worker[event.get("worker_id", 0)].append(event)
    if len(by_worker) > 1:
        # a straggler is a worker whose dispatch WALL lags the fleet
        # (each phase alone can be bimodal across a healthy fleet);
        # the lagging phase then names WHY — compute-bound vs
        # input-bound — which is the actionable half of the answer
        gen_wall = percentile(
            [float(e.get("wall_ms", 0.0)) for e in anat_events], 50
        )
        gen_compute = percentile(
            samples.get("device_compute", [0.0]), 50
        )
        gen_fetch = percentile(samples.get("host_fetch", [0.0]), 50)
        workers = {}
        for worker_id, worker_events in sorted(by_worker.items()):
            worker_samples = _phase_samples(worker_events)
            wall_p50 = percentile(
                [float(e.get("wall_ms", 0.0)) for e in worker_events], 50
            )
            compute_p50 = percentile(
                worker_samples.get("device_compute", [0.0]), 50
            )
            fetch_p50 = percentile(
                worker_samples.get("host_fetch", [0.0]), 50
            )
            entry = {
                "wall_p50_ms": round(wall_p50, 3),
                "device_compute_p50_ms": round(compute_p50, 3),
                "host_fetch_p50_ms": round(fetch_p50, 3),
                "straggler": bool(
                    gen_wall
                    and wall_p50 > _GOODPUT_STRAGGLER_FACTOR * gen_wall
                ),
            }
            if entry["straggler"]:
                compute_lag = (
                    compute_p50 / gen_compute if gen_compute else 0.0
                )
                fetch_lag = fetch_p50 / gen_fetch if gen_fetch else 0.0
                entry["lagging_phase"] = (
                    "device_compute"
                    if compute_lag >= fetch_lag
                    else "host_fetch"
                )
            workers[worker_id] = entry
        out["workers"] = workers
    return out


def goodput_section(events: list[dict]) -> dict | None:
    """Live goodput ledger from per-dispatch ``step_anatomy`` events
    (telemetry/anatomy.py): per-generation phase percentiles, the
    sum-exact residual check, a MEASURED ``e2e_vs_roofline``, MFU for
    zoo models with known costs, and per-host straggler attribution.
    None (key absent) when the run never recorded anatomy, so
    anatomy-less reports are unchanged."""
    anat = [e for e in events if e.get("event") == "step_anatomy"]
    if not anat:
        return None
    by_gen: dict[int, list[dict]] = defaultdict(list)
    for event in anat:
        by_gen[event.get("generation", 0)].append(event)
    generations = {
        gen: _goodput_generation(by_gen[gen]) for gen in sorted(by_gen)
    }
    overall = _goodput_generation(anat)
    return {"generations": generations, "overall": overall}


def multislice_section(events: list[dict]) -> dict | None:
    """Slice-topology timeline (slice-granular elasticity): every
    whole-slice loss, hybrid-mesh resize and autoscale decision, plus
    per-slice replica-push counts (the cross-slice ring's observable).
    None (key absent) when the run never touched slice machinery, so
    single-slice reports are unchanged."""
    losses = []
    resizes = []
    decisions = []
    pushes_by_slice: dict[str, int] = defaultdict(int)
    for event in events:
        kind = event.get("event")
        if kind == "slice_loss":
            losses.append(
                {
                    "generation": event.get("generation"),
                    "lost_slices": event.get("lost_slices"),
                    "dead_workers": event.get("dead_workers"),
                    "old_slices": event.get("old_slices"),
                    "new_slices": event.get("new_slices"),
                    "parked": event.get("parked"),
                }
            )
        elif kind == "mesh_resize":
            resizes.append(
                {
                    "generation": event.get("generation"),
                    "old_world_size": event.get("old_world_size"),
                    "new_world_size": event.get("new_world_size"),
                    "old_slices": event.get("old_slices"),
                    "new_slices": event.get("new_slices"),
                    "dcn": event.get("dcn"),
                }
            )
        elif kind == "autoscale_decision":
            decisions.append(
                {
                    "generation": event.get("generation"),
                    "action": event.get("action"),
                    "from_slices": event.get("from_slices"),
                    "to_slices": event.get("to_slices"),
                    "reason": event.get("reason"),
                }
            )
        elif (
            kind == "replica_push"
            and int(event.get("num_slices", 1) or 1) > 1
        ):
            pushes_by_slice[str(event.get("source_slice"))] += 1
    if not (losses or resizes or decisions or pushes_by_slice):
        return None
    return {
        "slice_losses": losses,
        "mesh_resizes": resizes,
        "autoscale_decisions": decisions,
        "replica_pushes_by_source_slice": dict(pushes_by_slice),
    }


def master_ha_section(events: list[dict]) -> dict | None:
    """Master-downtime stats (master high availability): one entry per
    ``master_restart`` event — the measured step gap the outage caused
    (last worker ``step`` before the restore began to the first after
    the master served again, mirroring the reform-downtime definition),
    the journal-replay cost, and the lease-reconciliation outcome of
    every ``worker_rehome``.  None (key absent) when the run never
    restarted a master, so HA-less reports are unchanged."""
    restarts = sorted(
        (
            e
            for e in events
            if e.get("event") == "master_restart"
            and e.get("monotonic") is not None
        ),
        key=lambda e: e["monotonic"],
    )
    if not restarts:
        return None
    steps = [
        e["monotonic"]
        for e in events
        if e.get("event") == "step" and e.get("monotonic") is not None
    ]
    replays = sorted(
        (e for e in events if e.get("event") == "journal_replay"),
        key=lambda e: e.get("monotonic", 0.0),
    )
    rehomes = sorted(
        (e for e in events if e.get("event") == "worker_rehome"),
        key=lambda e: e.get("monotonic", 0.0),
    )
    entries = []
    bounds = [r["monotonic"] for r in restarts[1:]] + [float("inf")]
    for restart, until in zip(restarts, bounds):
        at = restart["monotonic"]
        last_before = max((t for t in steps if t <= at), default=None)
        first_after = min((t for t in steps if t >= at), default=None)
        replay = next(
            (e for e in replays if at <= e.get("monotonic", 0.0) < until),
            None,
        )
        mine = [
            e for e in rehomes if at <= e.get("monotonic", 0.0) < until
        ]
        entries.append(
            {
                "generation": restart.get("generation"),
                "downtime_secs": round(first_after - last_before, 6)
                if last_before is not None and first_after is not None
                else None,
                "journal_replay_secs": replay.get("duration_secs")
                if replay
                else None,
                "pending_tasks_restored": replay.get("pending")
                if replay
                else None,
                "active_leases_restored": replay.get("active")
                if replay
                else None,
                "workers_rehomed": sorted(
                    e.get("worker_id") for e in mine
                ),
                "leases_kept": sum(e.get("kept", 0) for e in mine),
                "leases_requeued": sum(e.get("requeued", 0) for e in mine),
            }
        )
    measured = [
        e["downtime_secs"]
        for e in entries
        if e["downtime_secs"] is not None
    ]
    return {
        "restarts": entries,
        "total_downtime_secs": round(sum(measured), 6) if measured else None,
    }


def replication_section(events: list[dict]) -> dict | None:
    """Replica-coverage stats (peer state replication): pushes and hosts
    covered per generation, the freshest shard versions, harvest
    outcomes, and restores served from peer RAM.  None (key absent) when
    the run never replicated, so replication-less reports are unchanged."""
    pushes: dict[int, int] = defaultdict(int)
    hosts: dict[int, set] = defaultdict(set)
    versions: dict[int, int] = {}
    restores = []
    harvests = []
    for event in events:
        kind = event.get("event")
        gen = event.get("generation", 0)
        if kind == "replica_push":
            pushes[gen] += 1
            if event.get("source") is not None:
                hosts[gen].add(event["source"])
            versions[gen] = max(
                versions.get(gen, -1), event.get("step", -1)
            )
        elif kind == "replica_restore":
            restores.append(
                {"generation": gen, "step": event.get("step")}
            )
        elif kind == "replica_harvest":
            harvests.append(
                {
                    "generation": gen,
                    "complete": event.get("complete"),
                    "version": event.get("version"),
                }
            )
    if not (pushes or restores or harvests):
        return None
    return {
        "pushes_by_generation": dict(pushes),
        "hosts_covered_by_generation": {
            g: sorted(h) for g, h in hosts.items()
        },
        "shard_versions_by_generation": versions,
        "restores": restores,
        "harvests": harvests,
    }


def serving_section(events: list[dict]) -> dict | None:
    """Serving-plane aggregate from ``serving_request`` events — the
    way goodput aggregates ``step_anatomy``: per-phase p50/p95/p99 over
    completed requests, shed/error counts (the batcher's overload
    rejections ride the same event stream with ``error`` set), and the
    ``model_swap`` timeline.  None (key absent) when the run never
    served, so training-only reports are unchanged."""
    requests = [e for e in events if e.get("event") == "serving_request"]
    swaps = sorted(
        (e for e in events if e.get("event") == "model_swap"),
        key=lambda e: e.get("monotonic", 0.0),
    )
    if not requests and not swaps:
        return None
    ok = [e for e in requests if not e.get("error")]
    failed = [e for e in requests if e.get("error")]
    sheds = sum(1 for e in failed if e.get("shed"))
    errors_by_kind: dict[str, int] = defaultdict(int)
    for event in failed:
        errors_by_kind[str(event.get("error"))] += 1
    from elasticdl_tpu.telemetry.anatomy import SERVING_REQUEST_PHASES

    phases = {}
    for phase in SERVING_REQUEST_PHASES + ("untracked",):
        values = [
            float(e[f"{phase}_ms"]) for e in ok if f"{phase}_ms" in e
        ]
        if values:
            phases[phase] = {
                "total_ms": round(sum(values), 3),
                "p50_ms": round(percentile(values, 50), 3),
                "p95_ms": round(percentile(values, 95), 3),
                "p99_ms": round(percentile(values, 99), 3),
            }
    totals = [float(e["total_ms"]) for e in ok if "total_ms" in e]
    out = {
        "requests": len(ok),
        "rows": sum(int(e.get("rows", 0)) for e in ok),
        "dispatches": sum(int(e.get("dispatches", 0)) for e in ok),
        "sheds": sheds,
        "errors": len(failed) - sheds,
        "errors_by_kind": dict(errors_by_kind),
        "phases": phases,
        "swaps": [
            {
                "old_version": s.get("old_version"),
                "model_version": s.get("model_version"),
                "replica_id": s.get("replica_id"),
                "source": s.get("source"),
                "swap_ms": s.get("swap_ms"),
                "monotonic": s.get("monotonic"),
            }
            for s in swaps
        ],
    }
    if totals:
        out["latency_p50_ms"] = round(percentile(totals, 50), 3)
        out["latency_p95_ms"] = round(percentile(totals, 95), 3)
        out["latency_p99_ms"] = round(percentile(totals, 99), 3)
    return out


def streaming_section(events: list[dict]) -> dict | None:
    """Streaming-mode aggregate: watermark progression from
    ``stream_watermark``/``stream_lag`` ticks (final watermarks, lag
    percentiles, max lag — the bounded-lag evidence) and the freshness
    ledger from ``live_push`` events — one row per live train->serve
    push with the trained-watermark-at-swap vs source-watermark pair
    (``staleness`` = how many records behind the source the SERVED
    model was the moment it went live).  None (key absent) when the
    run never streamed, so epoch-mode reports are unchanged."""
    ticks = sorted(
        (e for e in events if e.get("event") == "stream_watermark"),
        key=lambda e: e.get("monotonic", 0.0),
    )
    lags = [
        float(e["lag_records"])
        for e in events
        if e.get("event") == "stream_lag" and "lag_records" in e
    ]
    pushes = sorted(
        (e for e in events if e.get("event") == "live_push"),
        key=lambda e: e.get("monotonic", 0.0),
    )
    if not ticks and not lags and not pushes:
        return None
    out: dict = {"watermark_ticks": len(ticks)}
    if ticks:
        last = ticks[-1]
        out["source_watermark"] = int(last.get("source_watermark", 0))
        out["trained_watermark"] = int(last.get("trained_watermark", 0))
        out["closed"] = bool(last.get("closed", False))
    if lags:
        out["lag_records"] = {
            "max": int(max(lags)),
            "p50": round(percentile(lags, 50), 1),
            "p95": round(percentile(lags, 95), 1),
            "last": int(lags[-1]),
        }
    if pushes:
        accepted = [e for e in pushes if e.get("accepted")]
        staleness = [
            int(e.get("staleness", 0)) for e in accepted
        ]
        out["freshness"] = {
            "pushes": len(pushes),
            "accepted": len(accepted),
            "refused": len(pushes) - len(accepted),
            "max_staleness_records": max(staleness) if staleness else None,
            "ledger": [
                {
                    "model_version": e.get("model_version"),
                    "trained_watermark": e.get("trained_watermark"),
                    "source_watermark": e.get("source_watermark"),
                    "staleness": e.get("staleness"),
                    "accepted": bool(e.get("accepted")),
                    "swap_ms": e.get("swap_ms"),
                    "monotonic": e.get("monotonic"),
                }
                for e in pushes
            ],
        }
    return out


def memory_section(events: list[dict]) -> dict | None:
    """Component-level memory ledger aggregate from ``memory_sample``
    events (telemetry/memory.py): per-component last/current and peak
    bytes with shares of the tracked total, the host-RSS residual as an
    explicit ``unaccounted`` line gated against its absolute-bytes
    budget (allocators lie, so the residual is surfaced, never forced
    to zero), and the ``memory_pressure`` crossing timeline.  None
    (key absent) when the run never sampled, so ledger-less reports
    are unchanged.

    Samples are grouped by EMITTING PROCESS (``worker_id`` /
    ``process_id``, riding every worker-hooks emit; the master's own
    ledger forms its own group) and only ordered WITHIN a group —
    ``monotonic`` restarts per process, so a cross-process sort would
    interleave incomparable clocks and make "last sample" one
    arbitrary worker's reading.  Per-process lasts and peaks then SUM
    across groups: currents are the fleet's newest per-process bytes
    (the wire's last-writer-wins, re-derived from the log), peaks the
    sum of per-process watermarks, RSS and the unaccounted residual
    the sums of per-process values."""
    by_process: dict[tuple, list[dict]] = {}
    for event in events:
        if event.get("event") == "memory_sample":
            key = (event.get("worker_id"), event.get("process_id"))
            by_process.setdefault(key, []).append(event)
    pressures = [
        e for e in events if e.get("event") == "memory_pressure"
    ]
    if not by_process and not pressures:
        return None
    components: dict[str, dict] = {}
    n_samples = 0
    last_rss = None
    peak_rss = 0
    device_peak = 0
    for group in by_process.values():
        group.sort(key=lambda e: e.get("monotonic", 0.0))
        n_samples += len(group)
        group_current: dict[str, int] = {}
        group_peak: dict[str, int] = {}
        group_rss = None
        group_rss_peak = 0
        group_device_peak = 0
        for event in group:
            comp = event.get("components")
            if isinstance(comp, dict):
                group_current = {}
                for name, value in comp.items():
                    try:
                        value = int(value)
                    except (TypeError, ValueError):
                        continue
                    group_current[name] = value  # last sample wins
                    if value > group_peak.get(name, 0):
                        group_peak[name] = value
            rss = event.get("host_rss_bytes")
            if isinstance(rss, (int, float)):
                group_rss = int(rss)
                if rss > group_rss_peak:
                    group_rss_peak = int(rss)
            dev = event.get("device_peak_bytes_in_use")
            if isinstance(dev, (int, float)) and dev > group_device_peak:
                group_device_peak = int(dev)
        for name, value in group_current.items():
            slot = components.setdefault(
                name, {"current_bytes": 0, "peak_bytes": 0}
            )
            slot["current_bytes"] += value
        for name, value in group_peak.items():
            slot = components.setdefault(
                name, {"current_bytes": 0, "peak_bytes": 0}
            )
            slot["peak_bytes"] += value
        if group_rss is not None:
            last_rss = (last_rss or 0) + group_rss
            peak_rss += group_rss_peak
        device_peak += group_device_peak
    tracked = sum(c["current_bytes"] for c in components.values())
    for slot in components.values():
        slot["share_of_tracked"] = (
            round(slot["current_bytes"] / tracked, 4) if tracked else None
        )
    from elasticdl_tpu.telemetry.memory import untracked_budget_bytes

    budget = untracked_budget_bytes()
    unaccounted = (
        max(0, last_rss - tracked) if last_rss is not None else None
    )
    out = {
        "samples": n_samples,
        "components": components,
        "tracked_bytes": tracked,
        "host_rss_bytes": last_rss,
        "host_rss_peak_bytes": peak_rss or None,
        "unaccounted_bytes": unaccounted,
        "unaccounted_share_of_rss": round(unaccounted / last_rss, 4)
        if unaccounted is not None and last_rss
        else None,
        "unaccounted_budget_bytes": budget,
        "unaccounted_over_budget": bool(
            unaccounted is not None and unaccounted > budget
        ),
        "pressure_events": [
            {
                "entered": e.get("entered"),
                "host_available_bytes": e.get("host_available_bytes"),
                "monotonic": e.get("monotonic"),
            }
            for e in pressures
        ],
    }
    if device_peak:
        out["device_peak_bytes_in_use"] = device_peak
    if not n_samples:
        # pressure events without samples (a partial log): still a
        # valid report, flagged explicitly — the no_data discipline
        out["no_data"] = "memory_pressure events but no memory samples"
    return out


def slo_section(events: list[dict]) -> dict | None:
    """SLO transition timeline from ``slo_violation`` /
    ``slo_recovered`` events (telemetry/slo.py): every burn-rate
    firing with the measured value vs its threshold, recovery count,
    and which objectives were still firing when the log ended.  None
    (key absent) when the run never fired, so watchdog-less reports
    are unchanged."""
    transitions = sorted(
        (
            e
            for e in events
            if e.get("event") in ("slo_violation", "slo_recovered")
        ),
        key=lambda e: e.get("monotonic", 0.0),
    )
    if not transitions:
        return None
    firing: dict[str, dict] = {}
    violations = []
    recoveries = 0
    for event in transitions:
        objective = str(event.get("objective"))
        if event.get("event") == "slo_violation":
            entry = {
                "objective": objective,
                "signal": event.get("signal"),
                "value": event.get("value"),
                "threshold": event.get("threshold"),
                "burn_fast": event.get("burn_fast"),
                "burn_slow": event.get("burn_slow"),
                "monotonic": event.get("monotonic"),
            }
            violations.append(entry)
            firing[objective] = entry
        else:
            recoveries += 1
            firing.pop(objective, None)
    return {
        "violations": violations,
        "recoveries": recoveries,
        "still_firing": sorted(firing),
    }


def incidents_section(run_dir: str) -> dict | None:
    """Postmortem digest from every ``incidents/incident_<n>.json``
    under the run dir (telemetry/incident.py writes them at close).
    The artifacts are the full causal record; this section carries the
    operator's first-page view — cause, duration, objectives, where
    the profiler captured — plus any incident the event log says is
    STILL open (an ``incident_open`` without a matching close writes
    no artifact).  None (key absent) when the run had no incidents."""
    from elasticdl_tpu.telemetry.incident import read_incidents

    incidents = read_incidents(run_dir)
    entries = [
        {
            "incident": record.get("incident"),
            "suspected_cause": record.get("suspected_cause"),
            "rationale": record.get("rationale"),
            "duration_secs": record.get("duration_secs"),
            "objectives": record.get("objectives", []),
            "violations": len(record.get("violations", [])),
            "profile_windows": [
                w.get("window_id")
                for w in record.get("profile_windows", [])
            ],
            "timeline_entries": len(record.get("timeline", [])),
            "artifact": record.get("_path"),
        }
        for record in incidents
    ]
    # still-open incidents never wrote an artifact — recover them from
    # the event logs (open without close = the run ended unhealthy)
    open_incidents = []
    for path in _find_files(run_dir, EVENTS_FILENAME):
        opens: dict = {}
        for event in read_events(path):
            if event.get("event") == "incident_open":
                opens[event.get("incident")] = event
            elif event.get("event") == "incident_close":
                opens.pop(event.get("incident"), None)
        for number, event in sorted(opens.items(), key=lambda x: str(x[0])):
            open_incidents.append(
                {
                    "incident": number,
                    "objective": event.get("objective"),
                    "signal": event.get("signal"),
                    "log": os.path.relpath(path, run_dir),
                }
            )
    if not entries and not open_incidents:
        return None
    return {
        "total": len(entries) + len(open_incidents),
        "closed": entries,
        "open": open_incidents,
        "causes": {
            cause: sum(
                1 for e in entries if e["suspected_cause"] == cause
            )
            for cause in sorted(
                {e["suspected_cause"] for e in entries if e["suspected_cause"]}
            )
        },
    }


def control_plane_section(run_dir: str) -> dict | None:
    """Control-plane scale: heartbeat fan-in shape, per-event master
    CPU, sweep/fence latency and scrape cost vs world size — read from
    every ``fleetsim_result.json`` under the run dir.  The simulator
    mirrors its ``scale`` section into that artifact, so this section
    and the artifact stay one schema (the chaos_result discipline)."""
    runs = []
    for path in _find_files(run_dir, "fleetsim_result.json"):
        try:
            with open(path, encoding="utf-8") as f:
                result = json.load(f)
        except (OSError, ValueError):
            continue
        runs.append(
            {
                "plan": result.get("plan"),
                "seed": result.get("seed"),
                "world_size": result.get("world_size"),
                "invariants_ok": result.get("invariants_ok"),
                "budgets": result.get("budgets", {}),
                "scale": result.get("scale", {}),
            }
        )
    return {"runs": runs} if runs else None


def build_report(run_dir: str) -> dict:
    from elasticdl_tpu.telemetry.tracing import SPANS_FILENAME
    from elasticdl_tpu.telemetry.trace import analyze_telemetry_dir

    faults = _load_fault_events(run_dir)
    runs = {}
    for path in _find_files(run_dir, EVENTS_FILENAME):
        rel = os.path.relpath(path, run_dir)
        runs[rel] = analyze_events(read_events(path), faults)
        # causal-trace view (reform critical path, stragglers) when the
        # run also wrote a span log
        telemetry_dir = os.path.dirname(path)
        if os.path.exists(os.path.join(telemetry_dir, SPANS_FILENAME)):
            runs[rel]["trace"] = analyze_telemetry_dir(telemetry_dir)
    report = {"run_dir": run_dir, "runs": runs, "faults": faults}
    for path in _find_files(run_dir, "chaos_result.json"):
        try:
            with open(path, encoding="utf-8") as f:
                report["chaos_result"] = json.load(f)
            break
        except (OSError, ValueError):
            continue
    control_plane = control_plane_section(run_dir)
    if control_plane is not None:
        report["control_plane"] = control_plane
    incidents = incidents_section(run_dir)
    if incidents is not None:
        report["incidents"] = incidents
    return report


def _format_text(report: dict) -> str:
    lines = [f"Run report: {report['run_dir']}"]
    chaos = report.get("chaos_result")
    if chaos:
        verdicts = " ".join(
            f"{i['name']}={i['status']}" for i in chaos.get("invariants", [])
        )
        lines.append(
            f"chaos: plan={chaos.get('plan')} seed={chaos.get('seed')} "
            f"ok={chaos.get('invariants_ok')}"
        )
        if verdicts:
            lines.append(f"  invariants: {verdicts}")
    control_plane = report.get("control_plane")
    if control_plane:
        for sim in control_plane["runs"]:
            scale = sim.get("scale", {})
            hb = scale.get("heartbeats", {})
            sweep = scale.get("sweep_ms", {})
            fence = scale.get("fence_ms", {})
            scrape = scale.get("scrape", {})
            lines.append(
                "control plane (fleetsim {}): {} workers  ok={}".format(
                    sim.get("plan"),
                    sim.get("world_size"),
                    sim.get("invariants_ok"),
                )
            )
            lines.append(
                "  heartbeats: {} in {} batches (mean {} max {})  "
                "cpu/call {}ms".format(
                    hb.get("total"),
                    hb.get("batches"),
                    hb.get("mean_batch"),
                    hb.get("max_batch"),
                    hb.get("cpu_ms_per_call"),
                )
            )
            if sweep:
                lines.append(
                    "  sweep: p50={}ms p95={}ms p99={}ms max={}ms  "
                    "fence max={}ms  dead={}".format(
                        sweep.get("p50"),
                        sweep.get("p95"),
                        sweep.get("p99"),
                        sweep.get("max"),
                        fence.get("max"),
                        scale.get("dead_detected"),
                    )
                )
            if scrape:
                lines.append(
                    "  scrape: {}ms, {} bytes, {} worker series".format(
                        scrape.get("ms"),
                        scrape.get("bytes"),
                        scrape.get("worker_series"),
                    )
                )
            for name, budget in sorted(sim.get("budgets", {}).items()):
                lines.append(
                    "  budget {:<24s} {} / {}  [{}]".format(
                        name,
                        budget.get("value"),
                        budget.get("budget"),
                        "ok" if budget.get("ok") else "EXCEEDED",
                    )
                )
    if not report["runs"]:
        lines.append(
            "no telemetry event logs found (run the master with "
            "--telemetry_dir, or the chaos runner with --workdir)"
        )
    for rel, run in report["runs"].items():
        lines.append(f"== {rel} ==")
        if run.get("no_data"):
            lines.append(f"no data: {run['no_data']}")
        for gen, stats in run["generations"].items():
            pct = (
                "  p50={:.1f}ms p95={:.1f}ms p99={:.1f}ms".format(
                    stats["step_time_p50_ms"],
                    stats["step_time_p95_ms"],
                    stats["step_time_p99_ms"],
                )
                if "step_time_p50_ms" in stats
                else ""
            )
            lines.append(
                f"generation {gen}: {stats['steps']} steps{pct}  "
                f"records={stats['records']} workers={stats['workers']}"
            )
        for gap in run["reform_downtime"]:
            cause = gap.get("cause")
            caused_by = (
                "  cause: {} ({}, process {}, step {})".format(
                    cause.get("fault_id"),
                    cause.get("kind"),
                    cause.get("process_id"),
                    cause.get("at_step"),
                )
                if cause
                else "  cause: unattributed"
            )
            lines.append(
                "reform gen{}->gen{}: downtime {:.2f}s  "
                "tasks recovered: {}{}".format(
                    gap["from_generation"],
                    gap["to_generation"],
                    gap["downtime_secs"],
                    gap["tasks_recovered"],
                    caused_by,
                )
            )
        trace = run.get("trace") or {}
        for gap in trace.get("reform_downtime", []):
            for phase, secs in gap.get("phases_secs", {}).items():
                lines.append(
                    "  phase {:<20s} {:8.3f}s  (gen{}->gen{})".format(
                        phase,
                        secs,
                        gap["from_generation"],
                        gap["to_generation"],
                    )
                )
        for gen, stats in (trace.get("stragglers") or {}).items():
            for worker, w in stats.get("workers", {}).items():
                if w.get("straggler"):
                    lines.append(
                        f"straggler: gen {gen} worker {worker}: median "
                        f"{w['median_step_ms']:.1f}ms "
                        f"({w['vs_generation_median']}x gen median)"
                    )
        goodput = run.get("goodput")
        if goodput:
            for gen, g in goodput["generations"].items():
                roofline = g.get("e2e_vs_roofline")
                mfu = g.get("mfu")
                lines.append(
                    "goodput gen {}: e2e_vs_roofline {} (binding: {})  "
                    "untracked {}  mfu {}".format(
                        gen,
                        f"{roofline:.3f}" if roofline is not None else "n/a",
                        g.get("binding"),
                        f"{g['untracked_share'] * 100:.1f}%"
                        if g.get("untracked_share") is not None
                        else "n/a",
                        f"{mfu:.3f}"
                        if mfu is not None
                        else f"n/a ({g.get('mfu_reason')})",
                    )
                )
                for phase, stats in sorted(g["phases"].items()):
                    lines.append(
                        "  phase {:<17s} {:9.1f}ms ({:5.1f}%)  "
                        "p50={:.2f}ms p95={:.2f}ms p99={:.2f}ms".format(
                            phase,
                            stats["total_ms"],
                            (stats["share"] or 0.0) * 100.0,
                            stats["p50_ms"],
                            stats["p95_ms"],
                            stats["p99_ms"],
                        )
                    )
                for worker, w in (g.get("workers") or {}).items():
                    if w.get("straggler"):
                        lines.append(
                            "  straggler: worker {} lags on {} "
                            "(device_compute p50 {:.1f}ms, host_fetch "
                            "p50 {:.1f}ms)".format(
                                worker,
                                w["lagging_phase"],
                                w["device_compute_p50_ms"],
                                w["host_fetch_p50_ms"],
                            )
                        )
        master_ha = run.get("master_ha")
        if master_ha:
            for restart in master_ha["restarts"]:
                downtime = restart["downtime_secs"]
                replay = restart["journal_replay_secs"]
                lines.append(
                    "master restart (gen {}): downtime {}  journal "
                    "replay {}  re-homed workers {}  leases kept {} / "
                    "requeued {}".format(
                        restart["generation"],
                        f"{downtime:.2f}s" if downtime is not None else "n/a",
                        f"{replay * 1000:.0f}ms"
                        if replay is not None
                        else "n/a",
                        restart["workers_rehomed"],
                        restart["leases_kept"],
                        restart["leases_requeued"],
                    )
                )
        replication = run.get("replication")
        if replication:
            for gen, n in sorted(replication["pushes_by_generation"].items()):
                hosts = replication["hosts_covered_by_generation"].get(
                    gen, []
                )
                version = replication["shard_versions_by_generation"].get(
                    gen
                )
                lines.append(
                    f"replication gen {gen}: {n} pushes, hosts {hosts}, "
                    f"freshest shard version {version}"
                )
            for restore in replication["restores"]:
                lines.append(
                    "replica restore: gen {} resumed at step {} "
                    "(peer RAM, no disk read)".format(
                        restore["generation"], restore["step"]
                    )
                )
        multislice = run.get("multislice")
        if multislice:
            for loss in multislice["slice_losses"]:
                lines.append(
                    "slice loss (gen {}): slices {} dead -> {} of {} "
                    "slice(s) survive{}".format(
                        loss["generation"],
                        loss["lost_slices"],
                        loss["new_slices"],
                        loss["old_slices"],
                        "  [PARKED below --min_slices]"
                        if loss.get("parked")
                        else "",
                    )
                )
            for resize in multislice["mesh_resizes"]:
                lines.append(
                    "mesh resize (gen {}): {} procs / {} slice(s) -> "
                    "{} procs / {} slice(s)  dcn={}".format(
                        resize["generation"],
                        resize["old_world_size"],
                        resize["old_slices"],
                        resize["new_world_size"],
                        resize["new_slices"],
                        resize["dcn"],
                    )
                )
            for decision in multislice["autoscale_decisions"]:
                lines.append(
                    "autoscale {} (gen {}): {} -> {} slice(s)  "
                    "({})".format(
                        decision["action"],
                        decision["generation"],
                        decision["from_slices"],
                        decision["to_slices"],
                        decision["reason"],
                    )
                )
            pushes = multislice["replica_pushes_by_source_slice"]
            if pushes:
                per_slice = " ".join(
                    f"slice{s}={n}" for s, n in sorted(pushes.items())
                )
                lines.append(f"cross-slice replica pushes: {per_slice}")
        serving = run.get("serving")
        if serving:
            lines.append(
                "serving: {} requests / {} rows in {} dispatches  "
                "sheds={} errors={}{}".format(
                    serving["requests"],
                    serving["rows"],
                    serving["dispatches"],
                    serving["sheds"],
                    serving["errors"],
                    "  p50={}ms p95={}ms p99={}ms".format(
                        serving["latency_p50_ms"],
                        serving["latency_p95_ms"],
                        serving["latency_p99_ms"],
                    )
                    if "latency_p50_ms" in serving
                    else "",
                )
            )
            for phase, stats in sorted(serving["phases"].items()):
                lines.append(
                    "  phase {:<15s} p50={:.3f}ms p95={:.3f}ms "
                    "p99={:.3f}ms".format(
                        phase,
                        stats["p50_ms"],
                        stats["p95_ms"],
                        stats["p99_ms"],
                    )
                )
            for swap in serving["swaps"]:
                lines.append(
                    "  swap: v{} -> v{} ({}, {:.1f}ms)".format(
                        swap.get("old_version"),
                        swap.get("model_version"),
                        swap.get("source"),
                        float(swap.get("swap_ms") or 0.0),
                    )
                )
        memory = run.get("memory")
        if memory:
            if memory.get("no_data"):
                lines.append(f"memory: no data: {memory['no_data']}")
            rss = memory.get("host_rss_bytes")
            unaccounted = memory.get("unaccounted_bytes")
            lines.append(
                "memory: tracked {:.1f} MB over {} components  "
                "rss {}  unaccounted {}{}".format(
                    memory["tracked_bytes"] / 1e6,
                    len(memory["components"]),
                    f"{rss / 1e6:.1f} MB" if rss is not None else "n/a",
                    f"{unaccounted / 1e6:.1f} MB"
                    if unaccounted is not None
                    else "n/a",
                    "  [OVER BUDGET]"
                    if memory.get("unaccounted_over_budget")
                    else "",
                )
            )
            for name, slot in sorted(memory["components"].items()):
                lines.append(
                    "  component {:<16s} current {:>12.0f} B  "
                    "peak {:>12.0f} B{}".format(
                        name,
                        slot["current_bytes"],
                        slot["peak_bytes"],
                        "  ({:.1f}% of tracked)".format(
                            slot["share_of_tracked"] * 100.0
                        )
                        if slot.get("share_of_tracked") is not None
                        else "",
                    )
                )
            for pressure in memory["pressure_events"]:
                lines.append(
                    "  pressure {}: MemAvailable {}".format(
                        "ENTERED" if pressure.get("entered") else "cleared",
                        pressure.get("host_available_bytes"),
                    )
                )
        slo = run.get("slo")
        if slo:
            lines.append(
                "slo: {} violation(s), {} recovery(ies){}".format(
                    len(slo["violations"]),
                    slo["recoveries"],
                    "  STILL FIRING: " + ", ".join(slo["still_firing"])
                    if slo["still_firing"]
                    else "",
                )
            )
            for violation in slo["violations"]:
                lines.append(
                    "  violated {}: {} = {} (threshold {})".format(
                        violation["objective"],
                        violation["signal"],
                        violation["value"],
                        violation["threshold"],
                    )
                )
        streaming = run.get("streaming")
        if streaming:
            lines.append(
                "streaming: trained watermark {} / source {}{}".format(
                    streaming.get("trained_watermark", "?"),
                    streaming.get("source_watermark", "?"),
                    " (source closed)"
                    if streaming.get("closed")
                    else "",
                )
            )
            lag = streaming.get("lag_records")
            if lag:
                lines.append(
                    "  lag: max {} p50 {} p95 {} last {} record(s)".format(
                        lag["max"], lag["p50"], lag["p95"], lag["last"]
                    )
                )
            fresh = streaming.get("freshness")
            if fresh:
                lines.append(
                    "  freshness: {} push(es), {} accepted, {} refused, "
                    "max staleness {} record(s)".format(
                        fresh["pushes"],
                        fresh["accepted"],
                        fresh["refused"],
                        fresh["max_staleness_records"],
                    )
                )
                for row in fresh["ledger"]:
                    lines.append(
                        "    push v{}: trained {} / source {} "
                        "(staleness {}){}".format(
                            row["model_version"],
                            row["trained_watermark"],
                            row["source_watermark"],
                            row["staleness"],
                            "" if row["accepted"] else "  REFUSED",
                        )
                    )
        for worker, rate in run["records_per_sec_by_worker"].items():
            lines.append(f"throughput: worker {worker}: {rate:.1f} records/s")
        if run["worker_time_ms"]:
            buckets = " ".join(
                f"{name}={total:.0f}ms"
                for name, total in sorted(run["worker_time_ms"].items())
            )
            lines.append(f"worker time buckets: {buckets}")
    incidents = report.get("incidents")
    if incidents:
        lines.append(
            "incidents: {} total ({} closed, {} still open)".format(
                incidents["total"],
                len(incidents["closed"]),
                len(incidents["open"]),
            )
        )
        for entry in incidents["closed"]:
            windows = entry["profile_windows"]
            lines.append(
                "  incident {}: {} for {:.1f}s  objectives: {}  "
                "profile windows: {}  [{}]".format(
                    entry["incident"],
                    entry["suspected_cause"],
                    float(entry["duration_secs"] or 0.0),
                    ", ".join(entry["objectives"]) or "n/a",
                    ", ".join(str(w) for w in windows) if windows else "none",
                    entry["artifact"],
                )
            )
            lines.append(f"    rationale: {entry['rationale']}")
        for entry in incidents["open"]:
            lines.append(
                "  incident {}: STILL OPEN (opened on {}, log {})".format(
                    entry["incident"],
                    entry["objective"],
                    entry["log"],
                )
            )
    return "\n".join(lines)


def summarize_report(report: dict) -> dict:
    """Machine-readable digest of a full report (``--summary-json``):
    a top-level ``verdict`` plus the counts CI actually branches on.
    Pure over the report dict so tests drive it with canned reports.

    Verdict ladder (worst wins): ``fail`` when any chaos/fleetsim
    invariant failed, an incident is still open, or an SLO objective
    was still firing at log end; ``degraded`` when incidents or SLO
    violations occurred but everything recovered; ``no_data`` when
    nothing produced a single event or artifact; ``ok`` otherwise."""
    reasons = []
    slo_violations = 0
    slo_recoveries = 0
    still_firing: list[str] = []
    events_total = 0
    serving_runs = 0
    serving_totals = {"requests": 0, "rows": 0, "sheds": 0, "errors": 0}
    for rel, run in report.get("runs", {}).items():
        events_total += run.get("events_total", 0)
        slo = run.get("slo")
        if slo:
            slo_violations += len(slo["violations"])
            slo_recoveries += slo["recoveries"]
            for objective in slo["still_firing"]:
                still_firing.append(objective)
                reasons.append(
                    f"slo objective {objective} still firing ({rel})"
                )
        serving = run.get("serving")
        if serving:
            serving_runs += 1
            for key in serving_totals:
                serving_totals[key] += int(serving.get(key, 0))
    chaos = report.get("chaos_result")
    if chaos is not None and not chaos.get("invariants_ok", True):
        reasons.append("chaos invariants failed")
    fleetsim_runs = (report.get("control_plane") or {}).get("runs", [])
    for sim in fleetsim_runs:
        if not sim.get("invariants_ok", True):
            reasons.append(
                f"fleetsim invariants failed ({sim.get('plan')})"
            )
    incidents = report.get("incidents") or {}
    for entry in incidents.get("open", []):
        reasons.append(f"incident {entry['incident']} still open")
    if reasons:
        verdict = "fail"
    elif incidents.get("total") or slo_violations:
        verdict = "degraded"
        reasons.append(
            "incidents/slo violations occurred but all recovered"
        )
    elif not report.get("runs") and chaos is None and not fleetsim_runs:
        verdict = "no_data"
        reasons.append("no telemetry, chaos, or fleetsim artifacts found")
    else:
        verdict = "ok"
    return {
        "verdict": verdict,
        "reasons": reasons,
        "run_dir": report.get("run_dir"),
        "runs": len(report.get("runs", {})),
        "events_total": events_total,
        "slo": {
            "violations": slo_violations,
            "recoveries": slo_recoveries,
            "still_firing": sorted(set(still_firing)),
        },
        "incidents": {
            "total": incidents.get("total", 0),
            "open": len(incidents.get("open", [])),
            "causes": incidents.get("causes", {}),
        },
        # serving runs ride the same verdict ladder (their incidents
        # and SLO blocks land via the shared paths above); the digest
        # adds the traffic counts CI asserts on, None when no run served
        "serving": {"runs": serving_runs, **serving_totals}
        if serving_runs
        else None,
        "chaos": {
            "plan": chaos.get("plan"),
            "invariants_ok": chaos.get("invariants_ok"),
        }
        if chaos is not None
        else None,
        "fleetsim": [
            {
                "plan": sim.get("plan"),
                "world_size": sim.get("world_size"),
                "invariants_ok": sim.get("invariants_ok"),
            }
            for sim in fleetsim_runs
        ],
    }


def build_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m elasticdl_tpu.telemetry.report",
        description="Summarize a run's telemetry event logs",
    )
    parser.add_argument("run_dir", help="Directory tree holding events.jsonl")
    parser.add_argument(
        "--json", action="store_true", help="Emit the full report as JSON"
    )
    parser.add_argument(
        "--output", default="", help="Also write the JSON report here"
    )
    parser.add_argument(
        "--summary-json",
        default="",
        dest="summary_json",
        help="Write a machine-readable digest (top-level verdict + the "
        "counts CI branches on) to this path",
    )
    return parser


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)
    if not os.path.isdir(args.run_dir):
        print(f"not a directory: {args.run_dir}", file=sys.stderr)
        return 2
    report = build_report(args.run_dir)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(_format_text(report))
    if args.output:
        with open(args.output, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2, default=str)
            f.write("\n")
    if args.summary_json:
        with open(args.summary_json, "w", encoding="utf-8") as f:
            json.dump(summarize_report(report), f, indent=2, default=str)
            f.write("\n")
    # a run dir with no telemetry yet is a VALID state (job starting,
    # telemetry disabled), reported explicitly above — not an error.
    # Only a non-directory argument (rc 2, earlier) is caller misuse.
    # The summary artifact carries the VERDICT; the process rc stays
    # "did the report build", so watch pipelines can read severity
    # without conflating it with tool failure.
    return 0


if __name__ == "__main__":
    sys.exit(main())
