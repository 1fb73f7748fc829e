"""What the program records about itself, read by the benchmark: the host
spans of ``elasticdl_tpu.telemetry.anatomy``'s always-on timeline, and the
compile listener's totals.  The arithmetic shared by the ``program_span`` and
``program_counter`` readers under ``layer_metrics/``.

The timeline is a ring in the measured process's memory; ``anatomy.snapshot()``
after the run returns its spans oldest first, each ``(name, thread, start_ns,
duration_ns, cpu_ns, ordinal, count)`` on ``time.perf_counter_ns``.  A program
that has no timeline or no such counter (the parent of the PR that added
them) gives every reader here None, and the line leaves the metric out.

Which spans a reader takes.  Every ``enqueue`` span carries its thread's
dispatch ordinal, and the spans that led up to it on that thread carry the
same one.  A traced process first measures untraced, then traces, and no
dispatch happens after the trace closes: so the traced intervals' dispatches
are the newest ``run["host_traced"]["batches"]`` ordinals of the dispatching
thread, and the untraced measured intervals' are the ``run["host"]["batches"]``
before those (one dispatch a batch in every cell there is).  The readers
take the untraced ones: the program as the end-to-end metrics see it.
``host_fetch`` and the producer thread's spans carry batch ordinals, not
dispatch ordinals, and are taken by time: from the first selected span's
start to the last selected ``enqueue``'s end."""

from __future__ import annotations

import json
import os
import statistics
import sys

ENQUEUE = "enqueue"
HOST_FETCH = "host_fetch"
SYNC = "sync"
PRODUCER_SPANS = ("produce_next_task", "produce_batch", "produce_blocked")
PRODUCE_BATCH = "produce_batch"
PRODUCE_BLOCKED = "produce_blocked"
_KEY = "_program_spans"
# utils/profiling.py::HOST_SPANS_FILE, spelled here too: the benchmark's
# files import nothing of the program at module level
HOST_SPANS_FILE = "host_spans.json"


def snapshot_of(run) -> list | None:
    """The program's timeline, taken once per ``run`` and kept in it."""
    if _KEY not in run:
        try:
            from elasticdl_tpu.telemetry import anatomy
        except ImportError:
            anatomy = None
        take = getattr(anatomy, "snapshot", None)
        run[_KEY] = list(take()) if take is not None else None
    return run[_KEY]


def select_dispatches(spans, untraced: int, traced: int) -> dict | None:
    """The untraced measured dispatches: ``untraced`` ordinals of the
    dispatching thread before its newest ``traced``.  Returns the thread,
    the ordinal range, the ``enqueue`` spans found in it (the ring may have
    dropped the oldest) and the range in time."""
    enqueues = [s for s in spans if s.name == ENQUEUE]
    if not enqueues or untraced <= 0:
        return None
    thread = enqueues[-1].thread
    newest = max(s.ordinal for s in enqueues if s.thread == thread)
    hi = newest - traced
    lo = hi - untraced + 1
    mine = [
        s for s in spans if s.thread == thread and lo <= s.ordinal <= hi
        and s.name != HOST_FETCH
    ]
    found = [s for s in mine if s.name == ENQUEUE]
    if not found:
        return None
    return {
        "thread": thread,
        "lo": lo,
        "hi": hi,
        "dispatches": len(found),
        "spans": mine,
        "start_ns": min(s.start_ns for s in mine),
        "end_ns": max(s.start_ns + s.duration_ns for s in found),
    }


def selected(run) -> dict | None:
    spans = snapshot_of(run)
    if not spans:
        return None
    return select_dispatches(
        spans, int(run["host"]["batches"]), int(run["host_traced"]["batches"])
    )


def in_time(spans, window: dict, names, thread=None) -> list:
    """Spans named in ``names`` that start inside the window's time."""
    return [
        s for s in spans
        if s.name in names
        and window["start_ns"] <= s.start_ns <= window["end_ns"]
        and (thread is None or s.thread == thread)
    ]


def mean_ms_per_dispatch(run, name: str) -> float | None:
    """Summed time of the dispatching thread's ``name`` spans over the
    untraced measured dispatches, per dispatch."""
    window = selected(run)
    if window is None:
        return None
    total = sum(s.duration_ns for s in window["spans"] if s.name == name)
    return total / 1e6 / window["dispatches"]


def fetch_wait_ms(run) -> float | None:
    """Time the dispatching thread spent inside ``next()`` of its batch
    stream per dispatch (the waits that ended a task's stream too)."""
    window = selected(run)
    if window is None:
        return None
    fetches = in_time(
        snapshot_of(run), window, (HOST_FETCH,), thread=window["thread"]
    )
    if not fetches:
        return None  # no batch stream in this traffic mode
    return sum(s.duration_ns for s in fetches) / 1e6 / window["dispatches"]


def producer_batch_ms(run) -> float | None:
    """Median wall time of one ``produce_batch``: read, decode, shuffle,
    stack, on the prefetcher's producer thread."""
    window = selected(run)
    if window is None:
        return None
    made = in_time(snapshot_of(run), window, (PRODUCE_BATCH,))
    if not made:
        return None
    return statistics.median(s.duration_ns for s in made) / 1e6


def producer_busy_share(run) -> float | None:
    """1 - (time the producer thread waited for a buffer budget) over the
    window's time: under 100% it has headroom, near 100% it sets the pace."""
    window = selected(run)
    if window is None:
        return None
    spans = snapshot_of(run)
    if not in_time(spans, window, PRODUCER_SPANS):
        return None
    wall = window["end_ns"] - window["start_ns"]
    blocked = 0
    for s in spans:
        if s.name == PRODUCE_BLOCKED:
            start = max(s.start_ns, window["start_ns"])
            end = min(s.start_ns + s.duration_ns, window["end_ns"])
            blocked += max(0, end - start)
    return 100.0 * (1.0 - blocked / wall)


# ---- the compile listener's totals -------------------------------------------

_COUNTERS = {
    "trace": "trace_secs_total",
    "lower": "lower_secs_total",
    "compile": "compile_secs_total",
}


def setup_seconds(run, stage: str) -> float | None:
    """The process's total seconds in one stage of making its programs —
    tracing to jaxprs, lowering to MLIR, the backend's compile or cache
    load — which are set-up's: ``correct`` forbids a compile in the window."""
    try:
        from elasticdl_tpu.telemetry import compile_tracker
    except ImportError:
        return None
    read = getattr(compile_tracker, _COUNTERS[stage], None)
    return float(read()) if read is not None else None


# ---- a profile window's host spans, on the device trace's clock --------------


def load_host_spans(path: str) -> list[list]:
    """``host_spans.json`` (written by the program's profile window beside
    its ``.xplane.pb``) as ``trace_reduce``'s ``[name, start_ns,
    duration_ns]`` host spans: those of the thread that closed the window
    (the dispatching thread: what the device was waiting for), plus one
    ``perf:interval`` span that ends where the window's ``sync`` span ends
    — the instant the device had finished the last dispatched step, which
    is the anchor ``trace_reduce.align_host_spans`` puts the two clocks
    together with."""
    from perf.trace_reduce import SPAN_INTERVAL

    with open(path) as f:
        dumped = json.load(f)
    at = {name: i for i, name in enumerate(dumped["fields"])}
    syncs = [s for s in dumped["spans"] if s[at["name"]] == SYNC]
    threads = {s[at["thread"]] for s in syncs}
    spans = [
        [s[at["name"]], s[at["start_ns"]], s[at["duration_ns"]]]
        for s in dumped["spans"]
        if not threads or s[at["thread"]] in threads
    ]
    if syncs:
        start = min(s[1] for s in spans)
        end = max(s[at["start_ns"]] + s[at["duration_ns"]] for s in syncs)
        spans.append([SPAN_INTERVAL, start, end - start])
    return spans


def align_profile_window(profile_dir: str) -> dict:
    """A profile window's directory (``--profile_dir`` or the
    ``request_profile`` RPC) as ``trace_reduce`` events: the device planes
    of its ``.xplane.pb`` and the program's host spans on the same clock."""
    from perf import trace_reduce

    xplane = trace_reduce.find_xplane(profile_dir)
    if xplane is None:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    host = load_host_spans(os.path.join(os.path.dirname(xplane), HOST_SPANS_FILE))
    return trace_reduce.align_host_spans(trace_reduce.load(xplane), host)


def main(argv=None) -> int:
    """``python3 perf/program_spans.py <profile_dir>``: the window's device
    busy and idle time, and every idle gap of 20 us or more put down to the
    program span that covers it (``perf:bookkeeping``: none does)."""
    from perf import trace_reduce

    (profile_dir,) = argv if argv is not None else sys.argv[1:]
    reduced = trace_reduce.reduce(align_profile_window(profile_dir))
    print(
        json.dumps(
            {
                "window_s": reduced["window_s"],
                "busy_s": reduced["busy_s"],
                "idle_share": 1.0 - reduced["busy_s"] / reduced["window_s"],
                "idle_gaps_s": reduced["idle_gaps_s"],
                "top_ops": trace_reduce.breakdown(reduced)["device_ops"],
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
