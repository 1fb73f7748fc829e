"""From the profiler's ``.xplane.pb`` to numbers, with JAX alone
(``jax.profiler.ProfileData``; no tensorflow import).

Two steps, so the second can be checked on a small recorded trace:

- :func:`load` reads the file into plain lists: per device plane the events
  of its op line as ``[name, start_ns, duration_ns]`` and one detail string
  per distinct op name.  :func:`align_host_spans` adds the harness's own
  host spans (``perf:*``, taken on the host's monotonic clock) on the
  trace's clock.
- :func:`reduce` turns those into device-busy time (the union of op
  intervals), per-op self time, the time a collective runs and no compute
  op does, and each idle gap attributed to what the host was doing in it.

All times inside are nanoseconds; results are seconds."""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
# collectives that run beside the core's ops are drawn here, start to done
ASYNC_LINE = "Async XLA Ops"
SPAN_INTERVAL = "perf:interval"
# the dispatching thread outside its spans: task report, counters, hooks
UNSPANNED = "perf:bookkeeping"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast",
)
# gaps shorter than this are the device's own turn-around between ops
MIN_GAP_NS = 20_000
_DETAIL_STATS = ("hlo_category", "tf_op", "long_name", "name")
_DETAIL_CHARS = 400
# kept from the HLO text even where it lies beyond _DETAIL_CHARS
_DETAIL_MARKS = ("tpu_custom_call",)


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return found[-1] if found else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    async_devices: dict[str, list] = {}
    details: dict[str, str] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    events = devices.setdefault(plane.name, [])
                elif line.name == ASYNC_LINE:
                    events = async_devices.setdefault(plane.name, [])
                else:
                    continue
                for event in line.events:
                    # the op line names an event by its whole HLO text,
                    # "%fusion.3 = f32[...] fusion(...)": the name is the
                    # part before " = ", the rest is its detail
                    name, _, text = event.name.partition(" = ")
                    name = name.lstrip("%")
                    events.append(
                        [name, int(event.start_ns), int(event.duration_ns)]
                    )
                    if name not in details:
                        stats = dict(event.stats)
                        details[name] = " ".join(
                            [text[:_DETAIL_CHARS]]
                            + [m for m in _DETAIL_MARKS if m in text]
                            + [str(stats[k]) for k in _DETAIL_STATS if k in stats]
                        )
    return {
        "devices": devices,
        "async": async_devices,
        "details": details,
        "host": [],
    }


def align_host_spans(events: dict, host_spans: list) -> dict:
    """Put the harness's host spans on the trace's clock.

    Every ``perf:interval`` ends when its readback returns, which is when
    the device has finished the interval's last op plus the way back: so
    ``interval end (host clock) - last device op end before it (trace
    clock)`` is the clocks' offset plus that latency, and the smallest of
    them over the intervals is the offset to within the shortest latency
    seen (some 0.1 ms).  The whole trace's last op end against the last
    interval's end gives a first guess good enough to tell the intervals'
    last ops apart."""
    ends = sorted(s + d for n, s, d in host_spans if n == SPAN_INTERVAL)
    op_ends = sorted(
        s + d for evs in events["devices"].values() for _, s, d in evs
    )
    if not ends or not op_ends:
        return {**events, "host": []}
    guess = ends[-1] - op_ends[-1]
    slack = 200_000
    offsets = []
    for end in ends:
        before = bisect.bisect_right(op_ends, end - guess + slack)
        if before:
            offsets.append(end - op_ends[before - 1])
    offset = min(offsets)
    aligned = sorted(
        ([n, s - offset, d] for n, s, d in host_spans), key=lambda e: e[1]
    )
    return {**events, "host": aligned, "clock_offset_ns": offset}


def save_events(events: dict, path: str, start_ns=None, end_ns=None):
    """Write loaded (and aligned) events as gzipped JSON; with ``start_ns``
    and ``end_ns``, only what lies wholly inside, and one ``perf:interval``
    span for the slice itself.  The recorded traces under ``perf/testdata``
    are made with this."""
    sliced = start_ns is not None and end_ns is not None

    def inside(event):
        return not sliced or (
            event[1] >= start_ns and event[1] + event[2] <= end_ns
        )

    kept = {
        group: {
            plane: [e for e in evs if inside(e)]
            for plane, evs in events.get(group, {}).items()
        }
        for group in ("devices", "async")
    }
    kept["host"] = [
        e for e in events["host"]
        if inside(e) and not (sliced and e[0] == SPAN_INTERVAL)
    ]
    if sliced:
        kept["host"].insert(0, [SPAN_INTERVAL, start_ns, end_ns - start_ns])
    names = {e[0] for evs in kept["devices"].values() for e in evs}
    kept["details"] = {n: events["details"].get(n, "") for n in sorted(names)}
    with gzip.open(path, "wt") as f:
        json.dump(kept, f, separators=(",", ":"))


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---- interval arithmetic -----------------------------------------------------


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out = []
    j = 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def clip(events, start_ns, end_ns):
    """Events cut to the window, as ``(name, start, end)``."""
    out = []
    for name, start, duration in events:
        s, e = max(start, start_ns), min(start + duration, end_ns)
        if e > s:
            out.append((name, s, e))
    return out


def self_times(clipped) -> dict[str, int]:
    """Per-name time with nested events' time taken out of their parent
    (a ``while`` or a ``call`` encloses its body's ops on the op line)."""
    result: dict[str, int] = {}
    stack: list[list] = []  # [name, end, self]
    ordered = sorted(clipped, key=lambda e: (e[1], -e[2]))

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _end, own = stack.pop()
            result[name] = result.get(name, 0) + own

    for name, start, end in ordered:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return result


# ---- the reduction ------------------------------------------------------------


def window_of(events: dict) -> tuple[int, int]:
    """The traced window: from the first ``perf:interval`` span's start to
    the last one's end (each ends with its readback), else the extent of
    the device events."""
    spans = [e for e in events["host"] if e[0] == SPAN_INTERVAL]
    if spans:
        return min(s for _, s, _ in spans), max(s + d for _, s, d in spans)
    every = [e for evs in events["devices"].values() for e in evs]
    if not every:
        raise ValueError("the trace holds no device event")
    return min(s for _, s, _ in every), max(s + d for _, s, d in every)


def attribute_gaps(gaps, host_spans, min_gap_ns=MIN_GAP_NS) -> dict[str, int]:
    """Each idle gap of at least ``min_gap_ns`` goes, nanosecond by
    nanosecond, to the host span that covers it; what no span covers is
    the dispatching thread's bookkeeping.  ``perf:interval`` encloses the
    others and is not a candidate."""
    by_name: dict[str, list] = {}
    for name, start, duration in host_spans:
        if name != SPAN_INTERVAL:
            by_name.setdefault(name, []).append((start, start + duration))
    merged = {name: merge(iv) for name, iv in by_name.items()}
    long_gaps = [(s, e) for s, e in gaps if e - s >= min_gap_ns]
    out: dict[str, int] = {}
    rest = long_gaps
    for name, intervals in merged.items():
        covered = total(long_gaps) - total(subtract(long_gaps, intervals))
        if covered:
            out[name] = covered
        rest = subtract(rest, intervals)
    if total(rest):
        out[UNSPANNED] = total(rest)
    return out


def reduce(events: dict) -> dict:
    start_ns, end_ns = window_of(events)
    window_ns = end_ns - start_ns
    planes = sorted(events["devices"])
    if not planes:
        raise ValueError("the trace holds no device plane with an op line")
    busy, exposed, gaps_by_what, op_self = [], [], {}, {}
    for plane in planes:
        clipped = clip(events["devices"][plane], start_ns, end_ns)
        every = merge((s, e) for _, s, e in clipped)
        busy.append(total(every))
        drawn_async = clip(
            events.get("async", {}).get(plane, []), start_ns, end_ns
        )
        collectives = merge(
            (s, e) for n, s, e in clipped + drawn_async if COLLECTIVE.search(n)
        )
        compute = merge(
            (s, e) for n, s, e in clipped if not COLLECTIVE.search(n)
        )
        exposed.append(total(subtract(collectives, compute)))
        gaps = subtract([(start_ns, end_ns)], every)
        for what, ns in attribute_gaps(gaps, events["host"]).items():
            gaps_by_what[what] = gaps_by_what.get(what, 0) + ns
        for name, ns in self_times(clipped).items():
            op_self[name] = op_self.get(name, 0) + ns
    n = len(planes)
    return {
        "devices": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "collective_exposed_s": sum(exposed) / n / 1e9,
        "op_self_s": {k: v / n / 1e9 for k, v in op_self.items()},
        "details": events.get("details", {}),
        "idle_gaps_s": {k: v / n / 1e9 for k, v in gaps_by_what.items()},
    }


def matching_seconds(reduced: dict, pattern: str) -> float:
    """Summed self time of the ops whose name or detail matches."""
    rx = re.compile(pattern)
    return sum(
        seconds
        for name, seconds in reduced["op_self_s"].items()
        if rx.search(name) or rx.search(reduced["details"].get(name, ""))
    )


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["op_self_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_gaps_s"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in gaps],
    }
