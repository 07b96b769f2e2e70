"""From a profiler trace (xplane) to device busy time, idle gaps and names.

`reduce_trace` takes planes as plain data, {plane name: {line name:
[(name, start_ns, duration_ns), ...]}}, which `load_xplane` reads from the
file jax.profiler writes. Busy time is the union of the intervals in which
an operation ran on a device, inside the window that the benchmark's own
`bench.pass` annotations span; an idle gap is named by the innermost host
event that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"   # one event a compiled program the device ran
PASS_ANNOTATION = "bench.pass"
#: gaps shorter than this lie between two back-to-back operations; no host
#: event is looked up for them
SHORT_GAP_NS = 2_000


def load_xplane(trace_dir: str) -> dict:
    """The newest .xplane.pb under `trace_dir` as plain data. Lines of one
    name in one plane are joined: the host plane has a line for every
    thread, named by the thread, and all Python threads are "python3"."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.duration_ns) for e in line.events)
    return planes


def describe(planes: dict) -> dict:
    """What a trace holds, short enough for a log: for each plane and line
    the number of events and the nanoseconds they span. Written out where
    `reduce_trace` finds nothing to read, so that the cause can be seen."""
    return {plane: {line: [len(events),
                           min((s for _, s, _ in events), default=0),
                           max((s + d for _, s, d in events), default=0)]
                    for line, events in lines.items() if events}
            for plane, lines in planes.items()}


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z]+[0-9]*\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """An operation's name without its operands: the device line names an
    op by its whole HLO text ('%fusion.3 = u8[1024]{0:T(1024)} fusion(...)')."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def _self_times(events: list) -> dict:
    """Seconds by name, each event counted without the events nested in it
    (a while loop's span holds its body's operations); events are
    [(name, start, end)]."""
    out, stack = defaultdict(float), []   # stack of [name, end, child_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, start, child = stack.pop()
            out[name] += (end - start - child) / 1e9
            if stack:
                stack[-1][3] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, end, start, 0])
    close(float("inf"))
    return out


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _name_gaps(gaps: list, host_events: list) -> dict:
    """Seconds of idle gap by the innermost host event over each gap's
    middle; `host_events` is [(name, start, end)]."""
    by_name = defaultdict(float)
    events = sorted(host_events, key=lambda e: e[1])
    active, nxt = [], 0
    for start, end in sorted(gaps, key=lambda g: g[0] + g[1]):
        if end - start < SHORT_GAP_NS:
            by_name["between_ops"] += (end - start) / 1e9
            continue
        mid = (start + end) / 2
        while nxt < len(events) and events[nxt][1] <= mid:
            active.append(events[nxt])
            nxt += 1
        active = [e for e in active if e[2] >= mid]
        name = min(active, key=lambda e: e[2] - e[1])[0] if active \
            else "unlabelled"
        by_name[name] += (end - start) / 1e9
    return by_name


def reduce_trace(planes: dict, top: int = 10) -> dict | None:
    """{"busy_s", "window_s", "passes", "programs", "device_ops",
    "idle_gaps"} over the annotated window, or None where the trace has no
    device operation or no annotated pass. busy_s and programs (compiled
    programs started in the window) are means over the devices that ran
    anything."""
    host_events, passes = [], []
    for plane, lines in planes.items():
        if plane.startswith("/device:"):
            continue
        for events in lines.values():
            for name, start, dur in events:
                if name == PASS_ANNOTATION:
                    passes.append((start, start + dur))
                elif dur > 0:
                    host_events.append((name, start, start + dur))
    if not passes:
        return None
    lo = min(s for s, _ in passes)
    hi = max(e for _, e in passes)

    busy_s, op_s, gaps, programs = [], defaultdict(float), [], 0
    for plane, lines in planes.items():
        if not plane.startswith(DEVICE_PREFIX) or OPS_LINE not in lines:
            continue
        ops = [(short_name(name), s, e) for name, start, dur in lines[OPS_LINE]
               for s, e in _clip([(start, start + dur)], lo, hi)]
        if not ops:
            continue
        programs += sum(lo <= start < hi
                        for _, start, _ in lines.get(MODULES_LINE, ()))
        for name, seconds in _self_times(ops).items():
            op_s[name] += seconds
        merged = _union([(s, e) for _, s, e in ops])
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_s:
        return None
    n_dev = len(busy_s)
    by_gap = _name_gaps(gaps, [e for e in host_events
                               if e[2] > lo and e[1] < hi])

    def ranked(d):
        return [[k, v / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(busy_s) / n_dev, "window_s": (hi - lo) / 1e9,
            "passes": len(passes), "programs": programs / n_dev,
            "device_ops": ranked(op_s),
            "idle_gaps": ranked(by_gap)}
