"""From a profiler trace to numbers: busy and idle time, operations by name
pattern, compiled programs by name pattern, and the longest idle gaps by
what the host was doing.

The trace is read into a plain form first (`Trace`: per device plane its
lines of `(name, start_ns, duration_ns)` events, and the host's events), so
that the reduction can be checked on a small recorded trace kept beside
this file (`data/small_trace.json`) and never depends on the profiler's
file format beyond `load_xplane`.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = tuple  # (name, start_ns, duration_ns)

# An operation that only holds others (a scan's loop, a branch): its time is
# its children's, so a list of the operations that took most time skips it.
CONTAINER = re.compile(r"^(while|conditional|call)([._]|$)")


def op_name(text: str) -> str:
    """The profiler names a device operation by its whole instruction
    (`%name.7 = type opcode(operands...)`): the operands' names are in the
    text, so a pattern is matched against the operation's own name only."""
    if text.startswith("%") and " = " in text:
        return text[1 : text.index(" = ")]
    return text


@dataclass
class Trace:
    # device plane name -> line name -> events sorted by start
    devices: dict[str, dict[str, list[Event]]] = field(default_factory=dict)
    # host events of every host thread: (thread, name, start_ns, duration_ns),
    # thread = "<line index>/<line name>"
    host: list[tuple] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host}

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        devices = {
            p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
            for p, lines in obj["devices"].items()
        }
        return Trace(devices=devices, host=[tuple(e) for e in obj["host"]])


def find_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                evs = [
                    (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                ]
                evs.sort(key=lambda e: e[1])
                lines[line.name] = evs
            trace.devices[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            # several threads share a line name: the line's index tells them apart
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.duration_ns > 0:
                        trace.host.append(
                            (f"{i}/{line.name}", e.name, float(e.start_ns), float(e.duration_ns))
                        )
    trace.host.sort(key=lambda e: e[2])
    return trace


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))


# -- busy and idle ---------------------------------------------------------


def union(intervals: list[tuple]) -> list[tuple]:
    """Sorted, merged `(start, end)` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_intervals(trace: Trace, plane: str, line: str = OPS_LINE) -> list[tuple]:
    evs = trace.devices.get(plane, {}).get(line, [])
    return union([(s, s + d) for _, s, d in evs if d > 0])


def window_of(trace: Trace, line: str = OPS_LINE) -> tuple | None:
    """First start to last end of any device operation: the traced steady
    window as the device saw it."""
    spans = [
        (evs[0][1], max(s + d for _, s, d in evs))
        for lines in trace.devices.values()
        for ln, evs in lines.items()
        if ln == line and evs
    ]
    if not spans:
        return None
    return min(a for a, _ in spans), max(b for _, b in spans)


def busy_seconds(trace: Trace, line: str = OPS_LINE) -> float | None:
    """Seconds in which an operation ran, averaged over the device planes."""
    per_plane = [
        sum(b - a for a, b in busy_intervals(trace, p, line)) / 1e9
        for p in trace.devices
    ]
    per_plane = [x for x in per_plane if x > 0]
    return sum(per_plane) / len(per_plane) if per_plane else None


def idle_share(trace: Trace, window_s: float | None = None) -> float | None:
    """1 - busy / window, in percent. `window_s` defaults to the device's
    own first-to-last span."""
    busy = busy_seconds(trace)
    if busy is None:
        return None
    if window_s is None:
        w = window_of(trace)
        window_s = (w[1] - w[0]) / 1e9
    if window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / window_s)


# -- operations and programs by name ---------------------------------------


def _within(evs: list[Event], spans: list[tuple]) -> list[Event]:
    """The events that start inside one of the sorted, disjoint spans."""
    starts = [a for a, _ in spans]
    out = []
    for e in evs:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < spans[i][1]:
            out.append(e)
    return out


def select(
    trace: Trace,
    pattern: str,
    line: str = OPS_LINE,
    within: str | None = None,
    within_line: str = MODULES_LINE,
) -> list[list[Event]]:
    """Per device plane, the events of `line` whose name matches `pattern`;
    with `within`, only those that start inside an event of `within_line`
    whose name matches `within` (an operation inside a compiled program)."""
    rx = re.compile(pattern)
    out = []
    for lines in trace.devices.values():
        evs = [e for e in lines.get(line, []) if rx.search(e[0])]
        if within is not None:
            wrx = re.compile(within)
            spans = union(
                [(s, s + d) for n, s, d in lines.get(within_line, []) if wrx.search(n)]
            )
            evs = _within(evs, spans)
        out.append(evs)
    return out


def summed_seconds(per_plane: list[list[Event]]) -> float:
    """Summed duration, averaged over the planes that have such events."""
    sums = [sum(d for _, _, d in evs) / 1e9 for evs in per_plane if evs]
    return sum(sums) / len(sums) if sums else 0.0


def count(per_plane: list[list[Event]]) -> float:
    counts = [len(evs) for evs in per_plane if evs]
    return sum(counts) / len(counts) if counts else 0.0


def top_ops(trace: Trace, n: int = 10, line: str = OPS_LINE) -> list[list]:
    """The operations that took most device time, summed by name."""
    total: dict[str, float] = {}
    for lines in trace.devices.values():
        for name, _, d in lines.get(line, []):
            if not CONTAINER.match(name):
                total[name] = total.get(name, 0.0) + d / 1e9
    k = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in ranked]


# -- idle gaps by what the host was doing ----------------------------------


# A host thread that only waits (the clients in `recv`, the event loop in
# `poll`) is not what kept the device idle.
WAITING = re.compile(r"(recv|poll|select|wait|sleep|acquire|_run_once|run_forever)\b")


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", name)[:96]


def idle_gaps(trace: Trace, n: int = 10, consider: int = 200) -> list[list]:
    """The host activity under the device's idle time.

    For each of the `consider` longest gaps between device operations (of
    the first device plane), the shortest host event that overlaps at least
    half of it (the deepest frame that was open for most of the gap) gets
    the gap's length; a thread that spent most of the gap waiting (in
    `recv`, `poll`, a lock) is left out with all its frames. Summed by that
    event's name; the `n` largest.
    """
    if not trace.devices:
        return []
    plane = sorted(trace.devices)[0]
    busy = busy_intervals(trace, plane)
    gaps = sorted(
        ((b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:]) if a1 > b0),
        key=lambda g: g[0] - g[1],
    )[:consider]
    if not gaps or not trace.host:
        return []
    import numpy as np

    threads = {t: i for i, t in enumerate(sorted({e[0] for e in trace.host}))}
    thread = np.array([threads[e[0]] for e in trace.host])
    waiting = np.array([bool(WAITING.search(e[1])) for e in trace.host])
    starts = np.array([e[2] for e in trace.host])
    ends = starts + np.array([e[3] for e in trace.host])
    durs = ends - starts
    by_name: dict[str, float] = {}
    for a, b in gaps:
        covers = (np.minimum(ends, b) - np.maximum(starts, a)) >= 0.5 * (b - a)
        idle_threads = np.unique(thread[covers & waiting])
        cand = np.flatnonzero(covers & ~waiting & ~np.isin(thread, idle_threads))
        if cand.size == 0:
            name = "no_host_event"
        else:
            i = cand[np.argmin(durs[cand])]
            who, ev = trace.host[i][0], trace.host[i][1]
            name = _clean(f"{who.split('/')[-1]}:{ev}")
        by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def trim(trace: Trace, t0: float, t1: float) -> Trace:
    """The part of a trace inside [t0, t1) ns: how the small recorded trace
    kept with the benchmark was cut from a real one."""
    out = Trace()
    for p, lines in trace.devices.items():
        out.devices[p] = {
            ln: [e for e in evs if t0 <= e[1] < t1] for ln, evs in lines.items()
        }
    out.host = [e for e in trace.host if e[2] < t1 and e[2] + e[3] > t0]
    return out
