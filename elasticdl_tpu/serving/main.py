"""``python -m elasticdl_tpu.serving.main`` — the prediction service.

Two roles, one binary (the master/worker spawn pattern):

- **frontend** (default): binds the router — the serving master — on
  ``--port``, spawns ``--num_replicas`` replica subprocesses (each its
  own JAX process over the local devices), registers them as their port
  files land, runs the liveness probe beat, and serves ``/metrics`` +
  ``/healthz`` for scrapes.  ``--addr_file`` publishes the bound
  address atomically (the master-addr-file idiom) so smokes/benches
  discover an ephemeral port without parsing logs.
- **replica** (spawned): engine + micro-batcher + dispatch thread
  behind its own gRPC port, written to ``--port_file``.

Every flag defaults to a served-locally-sane value; the serving CLI is
its OWN argparse surface (it shares no parser with the training
master, so the worker-argv byte-identity contract is untouched).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

from elasticdl_tpu.utils.log_utils import default_logger as logger

DEFAULT_MAX_WAIT_MS = 2.0
DEFAULT_DEADLINE_SECS = 5.0
PORT_FILE_WAIT_SECS = 120.0


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="elasticdl_tpu.serving", description="ElasticDL-TPU serving"
    )
    parser.add_argument(
        "--model_dir",
        required=True,
        help="Exported model directory (manifest.json + params.npz)",
    )
    parser.add_argument(
        "--port",
        type=int,
        default=0,
        help="Front-door port (0 = ephemeral; see --addr_file)",
    )
    parser.add_argument(
        "--num_replicas",
        type=int,
        default=1,
        help="Serving worker subprocesses behind the router",
    )
    parser.add_argument(
        "--minibatch_size",
        type=int,
        default=64,
        help=(
            "Basis of the canonical batch shape (rounded up to the "
            "local mesh's batch divisor, exactly like training)"
        ),
    )
    parser.add_argument(
        "--max_wait_ms",
        type=float,
        default=DEFAULT_MAX_WAIT_MS,
        help=(
            "Micro-batch coalescing window: how long the oldest queued "
            "row may wait for the batch to fill (0 = dispatch "
            "immediately)"
        ),
    )
    parser.add_argument(
        "--max_queue_rows",
        type=int,
        default=0,
        help=(
            "Bounded-queue row cap per replica; beyond it requests are "
            "shed with a retryable overload error (0 = 32 batches)"
        ),
    )
    parser.add_argument(
        "--rpc_deadline_secs",
        type=float,
        default=DEFAULT_DEADLINE_SECS,
        help="Per-call deadline router->replica (liveness floor)",
    )
    parser.add_argument(
        "--evict_after_secs",
        type=float,
        default=10.0,
        help="Evict a replica from rotation after this much probe silence",
    )
    parser.add_argument(
        "--watch_model",
        action="store_true",
        help=(
            "Poll --model_dir's manifest and hot-swap when a newer "
            "model_version lands (the train->serve loop)"
        ),
    )
    parser.add_argument(
        "--metrics_port",
        type=int,
        default=-1,
        help="/metrics + /healthz port (0 = ephemeral, negative = off)",
    )
    parser.add_argument(
        "--slo_config",
        default="",
        help=(
            "Router-side SLO watchdog over the probe-beat fan-in: "
            "'default', inline JSON, or a file path (empty = off; "
            "frontend-only — replica argv never carries it)"
        ),
    )
    parser.add_argument("--telemetry_dir", default="")
    parser.add_argument("--addr_file", default="")
    parser.add_argument(
        "--metrics_addr_file",
        default="",
        help=(
            "Publish the bound /metrics address (the addr-file idiom "
            "for an ephemeral --metrics_port 0; frontend-only)"
        ),
    )
    # spawned-replica internals
    parser.add_argument("--role", default="frontend", choices=["frontend", "replica"])
    parser.add_argument("--replica_id", type=int, default=0)
    parser.add_argument("--port_file", default="")
    return parser


def _write_atomic(path: str, text: str):
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(text)
    os.replace(tmp, path)


def _canonical_rows(minibatch_size: int) -> int:
    from elasticdl_tpu.parallel.mesh import MeshConfig, batch_divisor
    from elasticdl_tpu.trainer.stacking import canonical_batch_rows

    mesh = MeshConfig.from_string("").create()
    return canonical_batch_rows(minibatch_size, batch_divisor(mesh))


def _install_telemetry(args):
    from elasticdl_tpu.telemetry import (
        compile_tracker,
        memory,
        tracing,
        worker_hooks,
    )

    telemetry_dir = args.telemetry_dir or os.environ.get(
        worker_hooks.TELEMETRY_DIR_ENV, ""
    )
    worker_hooks.install(telemetry_dir)
    # spans carry the serving role so the trace export lays out one
    # track per replica and one for the router (trace.py's serving
    # track rule) instead of piling every process onto "worker 0"
    tracing.install(
        telemetry_dir,
        role="replica" if getattr(args, "role", "") == "replica" else "router",
        worker_id=getattr(args, "replica_id", 0),
    )
    compile_tracker.install()
    # the serving plane's byte owners (batcher queue, served leaves incl.
    # the swap's double residency) register against THIS process's
    # ledger; without it every engine/batcher sample site is a no-op
    memory.install_if_enabled(telemetry_dir)
    return telemetry_dir


# ---- replica role ------------------------------------------------------------


def run_replica(args) -> int:
    from elasticdl_tpu.parallel.elastic import configure_compilation_cache
    from elasticdl_tpu.serving.engine import ExportDirWatcher
    from elasticdl_tpu.serving.replica import ServingReplica

    configure_compilation_cache()
    _install_telemetry(args)
    replica = ServingReplica(
        args.model_dir,
        _canonical_rows(args.minibatch_size),
        max_wait_secs=args.max_wait_ms / 1000.0,
        max_queue_rows=args.max_queue_rows or None,
        replica_id=args.replica_id,
        port=args.port,
    ).start()
    if args.port_file:
        _write_atomic(args.port_file, str(replica.port))
    watcher = None
    if args.watch_model:
        watcher = ExportDirWatcher(replica.engine, args.model_dir)
        watcher.start()
    metrics_server = None
    if args.metrics_port >= 0:
        from elasticdl_tpu.telemetry.httpd import TelemetryHTTPServer

        metrics_server = TelemetryHTTPServer(
            replica.engine.metrics.registry,
            health_fn=lambda: {
                "role": "replica",
                "replica_id": args.replica_id,
                "model_version": replica.engine.version,
                "queue_rows": replica.batcher.queue_rows(),
            },
            port=args.metrics_port,
        )
        metrics_server.start()
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_a: stop.set())
    try:
        while not stop.wait(0.5):
            pass
    finally:
        if watcher is not None:
            watcher.close()
        replica.close()
        if metrics_server is not None:
            metrics_server.stop()
        # the replica buffers spans (queue/engine/dispatch); a graceful
        # SIGTERM must not strand the tail of the request traces
        from elasticdl_tpu.telemetry import tracing

        tracing.flush()
    return 0


# ---- frontend role -----------------------------------------------------------


def _replica_argv(args, i: int, workdir: str) -> list[str]:
    """A spawned replica's exact command line — pure so the argv
    byte-identity test can pin it: observability settings (telemetry
    dir, SLO config, sample rate) travel by ENV, never argv, so this
    list is byte-identical whether the watchdog/tracing flags are on
    or off (the worker-argv contract, applied to serving)."""
    argv = [
        sys.executable,
        "-m",
        "elasticdl_tpu.serving.main",
        "--role",
        "replica",
        "--replica_id",
        str(i),
        "--model_dir",
        args.model_dir,
        "--port",
        "0",
        "--port_file",
        os.path.join(workdir, f"replica_{i}.port"),
        "--minibatch_size",
        str(args.minibatch_size),
        "--max_wait_ms",
        str(args.max_wait_ms),
        "--max_queue_rows",
        str(args.max_queue_rows),
        "--metrics_port",
        "-1",
    ]
    if args.watch_model:
        argv.append("--watch_model")
    return argv


def _spawn_replicas(args, workdir: str) -> list[subprocess.Popen]:
    procs = []
    for i in range(args.num_replicas):
        argv = _replica_argv(args, i, workdir)
        env = dict(os.environ)
        if args.telemetry_dir:
            from elasticdl_tpu.telemetry.worker_hooks import TELEMETRY_DIR_ENV

            env[TELEMETRY_DIR_ENV] = args.telemetry_dir
        procs.append(subprocess.Popen(argv, env=env))
    return procs


def _await_ports(workdir: str, n: int, procs) -> list[int]:
    deadline = time.monotonic() + PORT_FILE_WAIT_SECS
    ports: list[int | None] = [None] * n
    while time.monotonic() < deadline:
        for i in range(n):
            if ports[i] is not None:
                continue
            path = os.path.join(workdir, f"replica_{i}.port")
            try:
                with open(path, encoding="utf-8") as f:
                    ports[i] = int(f.read().strip())
            except (OSError, ValueError):
                pass
        if all(p is not None for p in ports):
            return ports  # type: ignore[return-value]
        for i, proc in enumerate(procs):
            if proc.poll() is not None and ports[i] is None:
                raise RuntimeError(
                    f"serving replica {i} exited rc={proc.returncode} "
                    "before binding its port"
                )
        time.sleep(0.1)
    raise RuntimeError(f"serving replicas not up after {PORT_FILE_WAIT_SECS}s")


def run_frontend(args) -> int:
    from elasticdl_tpu.rpc.deadline import DeadlinePolicy
    from elasticdl_tpu.rpc.service import create_server
    from elasticdl_tpu.serving.replica import (
        SERVING_METHODS,
        SERVING_SERVICE_NAME,
    )
    from elasticdl_tpu.serving.router import ServingRouter

    telemetry_dir = _install_telemetry(args)
    deadlines = (
        DeadlinePolicy.from_secs(args.rpc_deadline_secs)
        if args.rpc_deadline_secs
        else None
    )
    router = ServingRouter(
        deadlines=deadlines, evict_after_secs=args.evict_after_secs
    )
    if args.slo_config:
        # parse BEFORE spawning: a bad config must fail the frontend,
        # not orphan replica subprocesses
        from elasticdl_tpu.serving.watchdog import (
            ServingWatchdog,
            parse_serving_slo_config,
        )
        from elasticdl_tpu.telemetry import tracing, worker_hooks

        slo_config = parse_serving_slo_config(args.slo_config)
        if slo_config is not None:
            router.watchdog = ServingWatchdog(
                router,
                slo_config,
                telemetry_dir=telemetry_dir,
                emit=worker_hooks.emit_event,
                tracer=tracing.get_tracer(),
            )
    workdir = tempfile.mkdtemp(prefix="edl_serving_")
    procs = _spawn_replicas(args, workdir)
    try:
        # EVERY startup step sits inside this try: a bind failure (port
        # taken), a router error, anything — the spawned replica
        # subprocesses must never outlive a frontend that dies before
        # installing its signal-driven shutdown loop
        ports = _await_ports(workdir, args.num_replicas, procs)
        for port in ports:
            router.add_replica(f"localhost:{port}")
        router.probe_once()  # seed liveness before the first request
        router.start()
        server = create_server(
            router,
            args.port,
            methods=SERVING_METHODS,
            service_name=SERVING_SERVICE_NAME,
        )
        server.start()
        bound = server._edl_bound_port
        if args.addr_file:
            _write_atomic(args.addr_file, f"localhost:{bound}")
    except Exception:
        router.close()
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        raise
    logger.info(
        "Serving frontend up on port %d (%d replicas: %s)",
        bound,
        len(ports),
        ports,
    )
    metrics_server = None
    if args.metrics_port >= 0:
        from elasticdl_tpu.rpc import messages as msg
        from elasticdl_tpu.telemetry.registry import MetricsRegistry

        registry = MetricsRegistry()
        live_gauge = registry.gauge(
            "elasticdl_serving_live_replicas",
            "Replicas currently in routing rotation",
        )
        registry.add_collect_callback(
            lambda _r: live_gauge.set(len(router.live_replicas()))
        )
        # per-replica fleet families over the probe-beat fan-in
        # (cardinality-capped, pruned with the registry)
        from elasticdl_tpu.serving.metrics import FleetMetrics

        FleetMetrics(router, registry)
        if router.watchdog is not None:
            registry.add_collect_callback(
                lambda _r: router.watchdog.mirror_metrics(registry)
            )

        def health():
            status = router.serving_status(msg.ServingStatusRequest())
            snap = router.fleet_snapshot()
            block = {
                "role": "frontend",
                "live_replicas": len(snap["live"]),
                "model_version": status.model_version,
                "queue_rows": status.queue_rows,
                "replicas": {
                    str(rid): {
                        "last_probe_age_secs": round(
                            r["last_probe_age_secs"], 3
                        ),
                        "outstanding": r["outstanding"],
                        "evict_in_secs": round(r["evict_in_secs"], 3),
                        "live": r["live"],
                    }
                    for rid, r in snap["replicas"].items()
                },
            }
            if router.watchdog is not None:
                block["slo"] = router.watchdog.health_block()
            return block

        from elasticdl_tpu.telemetry.httpd import TelemetryHTTPServer

        metrics_server = TelemetryHTTPServer(
            registry, health_fn=health, port=args.metrics_port
        )
        metrics_server.start()
        if args.metrics_addr_file:
            _write_atomic(
                args.metrics_addr_file, f"localhost:{metrics_server.port}"
            )
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_a: stop.set())
    try:
        while not stop.wait(0.5):
            for proc in procs:
                if proc.poll() is not None:
                    logger.warning(
                        "Serving replica exited rc=%d (router will "
                        "evict it; remaining replicas keep serving)",
                        proc.returncode,
                    )
                    procs = [p for p in procs if p.poll() is None]
                    break
    finally:
        server.stop(1.0).wait(1.0)
        router.close()
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        if metrics_server is not None:
            metrics_server.stop()
        # same contract as the replica: the router's (re)route spans
        # must survive a graceful shutdown
        from elasticdl_tpu.telemetry import tracing

        tracing.flush()
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.role == "replica":
        return run_replica(args)
    return run_frontend(args)


if __name__ == "__main__":
    sys.exit(main())
