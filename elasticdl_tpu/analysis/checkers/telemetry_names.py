"""telemetry-names: the naming lint, absorbed from scripts/.

Same contracts ``scripts/check_telemetry_names.py`` enforced since PR 2
(that script is now a thin shim over this checker):

1. every metric/event/span name passed literally to a registration call
   is snake_case;
2. each such name has exactly ONE registration site (multi-module names
   live in a shared constant: the ``EVENT_*`` vocabulary in
   ``telemetry/events.py``, ``SPAN_*`` in ``telemetry/tracing.py``,
   ``PHASE_*`` in ``telemetry/anatomy.py``);
3. the constant vocabularies are snake_case, defined once, and contain
   the REQUIRED names downstream tooling scrapes (smokes, report
   sections, /metrics gates);
4. required metric families are registered somewhere.

The bare-print rule the script also carried lives in the ``hot-path``
checker now (AST-based, so it catches indented prints too).

Regex-over-text like the original — registration calls wrap across
lines, and names are string literals, so regex is the right tool; the
required-vocabulary rules only engage when the canonical telemetry
modules are in the scanned set (fixture trees can carry miniatures).
"""

from __future__ import annotations

import re

from elasticdl_tpu.analysis.core import Finding, register

CHECKER = "telemetry-names"

SNAKE_CASE = re.compile(r"^[a-z][a-z0-9_]*$")
METRIC_CALL = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*[\"']([^\"']+)[\"']", re.S
)
EMIT_CALL = re.compile(r"(?:\.emit|emit_event)\(\s*[\"']([^\"']+)[\"']", re.S)
SPAN_CALL = re.compile(
    r"(?:\.start_span|\.record_span|trace_span)\(\s*[\"']([^\"']+)[\"']",
    re.S,
)
EVENT_CONST = re.compile(r"^EVENT_\w+\s*=\s*[\"']([^\"']+)[\"']", re.M)
SPAN_CONST = re.compile(r"^SPAN_\w+\s*=\s*[\"']([^\"']+)[\"']", re.M)
PHASE_CONST = re.compile(r"^PHASE_\w+\s*=\s*[\"']([^\"']+)[\"']", re.M)

REQUIRED_EVENT_NAMES = frozenset(
    {
        "replica_push",
        "replica_restore",
        "replica_harvest",
        "master_restart",
        "journal_replay",
        "worker_rehome",
        "slice_loss",
        "mesh_resize",
        "autoscale_decision",
        "rpc_fault_injected",
        "step_anatomy",
        "serving_request",
        "model_swap",
        "fleet_fault",
        # memory observability plane (telemetry/memory.py) + the
        # on-demand profiler round trip (utils/profiling.py)
        "memory_sample",
        "memory_pressure",
        "profile_window_open",
        "profile_window_close",
        # sharded embedding subsystem (elasticdl_tpu/embeddings): the
        # host-tier pull into the device minitable and the admission
        # fault when neither tier has headroom
        "embedding_gather",
        "embedding_spill_fault",
        # SLO watchdog plane (telemetry/slo.py + telemetry/incident.py):
        # detector fire/clear transitions and the incident lifecycle
        "slo_violation",
        "slo_recovered",
        "incident_open",
        "incident_close",
        # streaming subsystem (elasticdl_tpu/streaming): the watermark/
        # lag tick pair and the live train->serve push (freshness ledger)
        "stream_watermark",
        "stream_lag",
        "live_push",
    }
)
REQUIRED_SPAN_NAMES = frozenset(
    {
        "replica_push",
        "replica_restore",
        "replica_harvest",
        "compile",
        "master_restart",
        "journal_replay",
        "worker_rehome",
        "slice_loss",
        "mesh_resize",
        "autoscale_decision",
        "rpc_degraded",
        "step_anatomy",
        "serving_request",
        "model_swap",
        "fleet_fault",
        # the XLA profiler capture window (flag-armed or on-demand)
        "profile_window",
        # the SLO watchdog burn window: first bad evaluation -> fire
        "slo_watch",
        # serving fleet tracing: one trace per request — client root,
        # router (re)route children, replica queue/engine split, and
        # the batched dispatch group LINKED to its member traces
        "predict_request",
        "route",
        "reroute",
        "queue",
        "engine",
        "serving_dispatch",
        # streaming: one span per live train->serve push (harvest ->
        # swap accepted)
        "live_push",
    }
)
REQUIRED_PHASE_NAMES = frozenset(
    {
        "host_fetch",
        "assemble",
        "h2d_transfer",
        "device_compute",
        "step_bookkeeping",
        "untracked",
        "queue_wait",
        "d2h_transfer",
        "boundary_stall",
        # the always-on timeline's span names (read by name by the
        # benchmark's readers, perf/program_spans.py, and by the profile
        # window's host_spans.json consumers)
        "enqueue",
        "ready_wait",
        "sync",
        "produce_next_task",
        "produce_batch",
        "produce_blocked",
    }
)
REQUIRED_METRIC_NAMES = frozenset(
    {
        "elasticdl_compile_total",
        "elasticdl_rpc_deadline_exceeded_total",
        "elasticdl_rpc_latency_seconds",
        "elasticdl_step_phase_ms_total",
        "elasticdl_step_phase_seconds",
        "elasticdl_device_prefetch_groups_total",
        "elasticdl_device_prefetch_stall_ms_total",
        "elasticdl_device_prefetch_stage_ms_total",
        "elasticdl_boundary_stall_ms_total",
        "elasticdl_serving_latency_seconds",
        "elasticdl_serving_requests_total",
        "elasticdl_serving_swaps_total",
        # thousand-worker control plane (coalesced heartbeat fan-in,
        # incremental dead-worker sweep, cardinality-bounded per-worker
        # series) — the fleetsim scale budgets scrape these
        "elasticdl_heartbeats_total",
        "elasticdl_heartbeat_batches_total",
        "elasticdl_dead_worker_sweep_ms_total",
        "elasticdl_worker_heartbeat_age_secs",
        # memory observability plane: the component-level byte ledger
        # (component= / kind=current|peak gauge family)
        "elasticdl_memory_bytes",
        # sharded embedding subsystem: per-table resident bytes by tier
        # (table= / tier=device|spill)
        "elasticdl_embedding_bytes",
        # SLO watchdog plane: per-objective detector state (objective= /
        # window=fast|slow) and the incident counter — registered at one
        # site each inside SLOEngine.mirror_metrics
        "elasticdl_slo_violations_total",
        "elasticdl_slo_objective_ok",
        "elasticdl_slo_burn_rate",
        "elasticdl_slo_incidents_total",
        # serving fleet fan-in: router-side per-replica families over
        # the probe-beat merge (replica= label under the PR-13
        # cardinality cap) — registered at one site each inside
        # serving/metrics.py FleetMetrics._collect
        "elasticdl_serving_replica_queue_rows",
        "elasticdl_serving_replica_outstanding",
        "elasticdl_serving_replica_probe_age_secs",
        "elasticdl_serving_replica_shed_total",
        "elasticdl_serving_replica_errors_total",
        "elasticdl_serving_replica_phase_ms_total",
        # streaming subsystem: the backlog signal pair (lag in records,
        # source/trained watermark by role=) and the live-push counter —
        # registered at one site each inside MasterTelemetry's collect
        "elasticdl_stream_lag_records",
        "elasticdl_stream_watermark",
        "elasticdl_stream_live_push_total",
    }
)

# (path suffix of the canonical vocabulary module, const pattern, label,
# required set)
_VOCABULARIES = (
    ("telemetry/events.py", EVENT_CONST, "event", REQUIRED_EVENT_NAMES),
    ("telemetry/tracing.py", SPAN_CONST, "span", REQUIRED_SPAN_NAMES),
    ("telemetry/anatomy.py", PHASE_CONST, "phase", REQUIRED_PHASE_NAMES),
)


def _line_of(text: str, offset: int) -> int:
    return text.count("\n", 0, offset) + 1


@register(CHECKER)
def check(sources) -> list[Finding]:
    findings: list[Finding] = []
    metric_sites: dict[str, list[tuple[str, int]]] = {}
    event_sites: dict[str, list[tuple[str, int]]] = {}
    span_sites: dict[str, list[tuple[str, int]]] = {}

    scanned = [s for s in sources if "/analysis/" not in f"/{s.path}"]
    for source in scanned:
        for pattern, sites in (
            (METRIC_CALL, metric_sites),
            (EMIT_CALL, event_sites),
            (SPAN_CALL, span_sites),
        ):
            for match in pattern.finditer(source.text):
                sites.setdefault(match.group(1), []).append(
                    (source.path, _line_of(source.text, match.start()))
                )

    for kind, sites in (
        ("metric", metric_sites),
        ("event", event_sites),
        ("span", span_sites),
    ):
        for name, where in sorted(sites.items()):
            path, line = where[0]
            if not SNAKE_CASE.match(name):
                findings.append(
                    Finding(
                        CHECKER,
                        path,
                        f"{kind}:{name}",
                        f"{kind} name {name!r} is not snake_case",
                        line=line,
                    )
                )
            if len(where) > 1:
                rendered = ", ".join(f"{p}:{ln}" for p, ln in where)
                findings.append(
                    Finding(
                        CHECKER,
                        path,
                        f"multisite:{kind}:{name}",
                        f"{kind} name {name!r} registered at "
                        f"{len(where)} sites ({rendered}); hoist it into "
                        "a shared constant with one definition site",
                        line=line,
                    )
                )

    have_canonical = any(
        s.path.endswith(_VOCABULARIES[0][0]) for s in scanned
    )
    if have_canonical:
        for name in sorted(REQUIRED_METRIC_NAMES - set(metric_sites)):
            findings.append(
                Finding(
                    CHECKER,
                    "elasticdl_tpu/telemetry",
                    f"required:metric:{name}",
                    f"required metric {name!r} is not registered anywhere "
                    "(smoke/report scrape contract)",
                )
            )

    for suffix, pattern, label, required in _VOCABULARIES:
        source = next((s for s in scanned if s.path.endswith(suffix)), None)
        if source is None:
            continue
        values = pattern.findall(source.text)
        for value in values:
            if not SNAKE_CASE.match(value):
                findings.append(
                    Finding(
                        CHECKER,
                        source.path,
                        f"const:{label}:{value}",
                        f"{label} constant value {value!r} is not "
                        "snake_case",
                    )
                )
        for value in sorted({v for v in values if values.count(v) > 1}):
            findings.append(
                Finding(
                    CHECKER,
                    source.path,
                    f"const:{label}:{value}",
                    f"{label} name {value!r} defined more than once",
                )
            )
        for value in sorted(required - set(values)):
            findings.append(
                Finding(
                    CHECKER,
                    source.path,
                    f"required:{label}:{value}",
                    f"required {label} name {value!r} missing from the "
                    "shared vocabulary (downstream tooling contract)",
                )
            )
    return findings
