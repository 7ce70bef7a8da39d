"""The device's idle time, booked to the program's own spans.

The program names what its host is doing with `obs.phase(name)`: one
`advspec.<name>` annotation on the calling thread's line of a profile,
on the device's clock (docs/observability.md "Phases"). Every stretch in
which the device ran no operation is booked here to the innermost such
span open over it, and to `NO_SPAN` where none was. `tools/trace_view.py
--xplane` prints the table and the four shares of `SHARES`, which split
`device.idle_share` by what the host was doing.

`trace_idle_by_span` is that share as a per-layer metric's reader. The
harness finds a reader only in `reducers.REDUCERS`, a file a `tracing` PR
may not edit, so no metric reads it yet (PERF.md section 7).
"""

from __future__ import annotations

import json
import re

from perfbench import reduce as rd

PREFIX = "advspec."
NO_SPAN = "(no span)"
IDLE_PARTS = ("head", "between", "tail", "whole")

# What the device waited on, by the innermost span open over its idle
# time: the names after `advspec.` (obs.PHASES, obs.PHASE_PARENT) and
# NO_SPAN. The four patterns split every name, so the shares add up to
# the idle share.
SHARES = {
    # an admission: lookup, reservation and eviction, promotion, a
    # standalone chunk, the handoff (Speed queue (2))
    "idle.admission_share": r"^(drive\.(admit|prefill)|(admit|prefill|handoff)\.\w+)$",
    # a completion: the row's tokens, its result, the slot's release
    "idle.collect_share": r"^(drive\.collect|collect\.\w+)$",
    # a verify step's own host work, and the iteration's remainder
    "idle.step_share": r"^drive\.(iteration|prepare|dispatch|fetch|apply|stream)$",
    # between dispatches: engine.*, serve.*, no span
    "idle.outside_share": r"^(?!(drive|admit|prefill|handoff|collect)\.)",
}


def host_spans(host: list[tuple]) -> list[tuple]:
    """`(name, start, end)` of every `advspec.*` event of a trace's host
    events (`(thread, name, start_ns, duration_ns)`), the prefix cut."""
    return [
        (name[len(PREFIX):], start, start + dur)
        for _, name, start, dur in host
        if name.startswith(PREFIX)
    ]


def innermost_segments(phases: list[tuple]) -> list[tuple]:
    """Flatten nested ``(name, start, end)`` phase events into disjoint,
    sorted ``(start, end, name)`` segments, each named by the innermost
    phase open over it (phases of one thread nest; across threads the
    later-opened one wins, which is still the most specific answer)."""
    cuts = sorted({t for _, a, b in phases for t in (a, b)})
    events = sorted(phases, key=lambda p: (p[1], -p[2]))
    out: list[tuple] = []
    stack: list[tuple] = []  # open phases, outermost first
    i = 0
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(events) and events[i][1] <= lo:
            stack.append(events[i])
            i += 1
        stack = [p for p in stack if p[2] > lo]
        if stack:
            name = stack[-1][0]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def idle_by_phase(
    busy: list[tuple], phases: list[tuple], window: tuple | None = None
) -> dict:
    """Seconds of device idle time (the gaps between the sorted,
    disjoint ``busy`` intervals, in ns) under each innermost host phase
    and under no phase at all, split by where in the phase's stretch the
    gap lies: at its ``head`` (the device had nothing yet: the host was
    still enqueueing, or launch latency), ``between`` two operations
    inside it (eager dispatches), at its ``tail`` (the device was done
    and the host had not moved on: completion latency), or over the
    ``whole`` stretch (no operation ran in it). With ``window``
    (start, end), the stretches of it before the first and after the last
    busy interval are gaps too: the table then adds up to the window
    less the busy time."""
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:]) if a1 > b0]
    if window is not None and busy:
        if window[0] < busy[0][0]:
            gaps.insert(0, (window[0], busy[0][0]))
        if busy[-1][1] < window[1]:
            gaps.append((busy[-1][1], window[1]))
    segs = innermost_segments(phases)
    idle: dict[str, list[float]] = {}

    def add(name: str, part: str, ns: float) -> None:
        row = idle.setdefault(name, [0.0] * len(IDLE_PARTS))
        row[IDLE_PARTS.index(part)] += ns / 1e9

    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi, name = segs[k]
            ns = min(hi, b) - max(lo, a)
            if ns > 0:
                head, tail = a <= lo, b >= hi
                add(
                    name,
                    "whole" if head and tail
                    else "head" if head
                    else "tail" if tail
                    else "between",
                    ns,
                )
                covered += ns
            k += 1
        rest = (b - a) - covered
        if rest > 0:
            add(NO_SPAN, "whole", rest)
    return {
        name: {**dict(zip(IDLE_PARTS, row)), "idle_s": sum(row)}
        for name, row in idle.items()
    }


def idle_by_span(trace: rd.Trace) -> dict | None:
    """`idle_by_phase` of each device plane over the trace's device window
    (`reduce.window_of`), averaged over the planes as `reduce.busy_seconds`
    averages busy time: its rows add up to the window less that busy time,
    the seconds `reduce.idle_share` is a share of."""
    window = rd.window_of(trace)
    planes = [rd.busy_intervals(trace, p) for p in trace.devices]
    planes = [busy for busy in planes if busy]
    if window is None or not planes:
        return None
    phases = host_spans(trace.host)
    table: dict = {}
    for busy in planes:
        for name, row in idle_by_phase(busy, phases, window).items():
            acc = table.setdefault(name, dict.fromkeys(row, 0.0))
            for k, v in row.items():
                acc[k] += v / len(planes)
    return table


def share(table: dict, spans: str, window_s: float) -> float:
    """100 x the idle seconds of `table` whose span matches `spans`, over
    `window_s`."""
    rx = re.compile(spans)
    return 100.0 * sum(
        row["idle_s"] for name, row in table.items() if rx.search(name)
    ) / window_s


def note(table: dict) -> str:
    """One line: each span's idle seconds, split by `IDLE_PARTS`."""
    rows = sorted(table.items(), key=lambda kv: -kv[1]["idle_s"])
    return (
        "idle_by_span: seconds [" + ", ".join(IDLE_PARTS) + "] "
        + json.dumps({n: [round(r[k], 4) for k in IDLE_PARTS] for n, r in rows})
    )


def trace_idle_by_span(r, p: dict):
    """A per-layer metric's reader (`reducers.Reading`, params {"spans":
    a pattern}): `share` of the device's idle time under the spans that
    match, over the window `reducers.trace_idle_share` divides by, and
    one `NOTE idle_by_span:` line a run."""
    if r.trace is None or r.window_s <= 0:
        return None
    table = idle_by_span(r.trace)
    if table is None:
        return None
    if not any(n.startswith("idle_by_span:") for n in r.notes):
        r.notes.append(note(table))
    return share(table, p["spans"], r.window_s)
