"""The fixed set of readers a per-layer metric's file chooses from.

A metric's file (`metrics/<name>.json`) names one of these under "reducer"
and gives its parameters under "params". A reader that finds nothing to
read returns None, and the harness leaves the metric out of the line: it
never returns 0 for a share of a roofline or of a peak.

What a reader sees (`Reading`): the traced window's length, the counters
at its start and end, the client's statistics, the tokens the clients were
delivered in the window (each with the context length of its row), the
verify steps' deliveries in it (one a row a step, each with the row's
length after it: what the keys and values of decode are counted by), the
prompt spans that were prefilled in it, the configuration's sizes, its
architecture's module (`architectures/<model_type>.py`: what a "work" a
metric's file names costs in bytes and FLOPs), the chip's peaks and, in a
traced run, the trace.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import ModuleType

from perfbench import reduce as rd
from perfbench import shapes


@dataclass
class Reading:
    window_s: float
    counters_start: dict
    counters_end: dict
    client: dict  # statistic name -> value
    token_contexts: list[int]  # one per token delivered in the window
    row_step_contexts: list[int]  # one per row per verify step that delivered in it
    prefill_spans: list[tuple]  # (first, end) prompt positions prefilled in it
    rows: int  # opponents of one debate: the rows of one dispatch
    config: dict
    arch: ModuleType  # the configuration's architecture: `work(kind, reading, n_steps)`
    quant: str
    peaks: dict | None
    trace: rd.Trace | None = None
    notes: list[str] = field(default_factory=list)


def _sum(counters: dict, keys: list[str]) -> float | None:
    found = [counters[k] for k in keys if k in counters]
    return float(sum(found)) if found else None


def client_stat(r: Reading, p: dict):
    return r.client.get(p["stat"])


def counter_ratio(r: Reading, p: dict):
    """sum(num) / sum(den) * scale, of the window's increase ("at": "delta")
    or of the values at its end ("at": "end")."""
    scale = float(p.get("scale", 1.0))

    def read(keys):
        end = _sum(r.counters_end, keys)
        if end is None:
            return None
        if p.get("at", "delta") == "end":
            return end
        return end - (_sum(r.counters_start, keys) or 0.0)

    num, den = read(p["num"]), read(p["den"])
    if num is None or not den:
        return None
    return scale * num / den


def counter_delta(r: Reading, p: dict):
    end = _sum(r.counters_end, p["keys"])
    if end is None:
        return None
    return float(p.get("scale", 1.0)) * (end - (_sum(r.counters_start, p["keys"]) or 0.0))


def gauge(r: Reading, p: dict):
    """A gauge's value at the window's end."""
    v = r.counters_end.get(p["key"])
    return None if v is None else float(p.get("scale", 1.0)) * v


def histogram_mean(r: Reading, p: dict):
    """Mean of what a histogram of the obs registry took in over the window."""
    return counter_ratio(
        r,
        {"num": [p["key"] + ".sum"], "den": [p["key"] + ".count"], "scale": p.get("scale", 1.0)},
    )


def _events(r: Reading, p: dict):
    if r.trace is None:
        return None
    evs = rd.select(
        r.trace,
        p["pattern"],
        line=p.get("line", rd.OPS_LINE),
        within=p.get("within"),
        within_line=p.get("within_line", rd.MODULES_LINE),
    )
    return evs if any(evs) else None


def trace_mean_ms(r: Reading, p: dict):
    """Mean device time of the events that match, in milliseconds."""
    evs = _events(r, p)
    if evs is None:
        return None
    return 1000.0 * rd.summed_seconds(evs) / rd.count(evs)


def trace_idle_share(r: Reading, p: dict):
    if r.trace is None:
        return None
    return rd.idle_share(r.trace, r.window_s)


def least_time_share(r: Reading, p: dict):
    """The least time the chip needs for the window's useful work ("work": a
    name the architecture's module knows, an unknown one is its KeyError),
    over the window's seconds ("over": "window") or over the summed device
    time of the operations that match ("over": {...}). "steps" says how the
    trace counts the decode steps."""
    if r.trace is None or r.peaks is None:
        return None
    n_steps = None
    if "steps" in p:
        steps = _events(r, p["steps"])
        n_steps = rd.count(steps) if steps else None
    work = r.arch.work(p["work"], r, n_steps)
    if work is None:
        return None
    least, bound = shapes.least_seconds(work, r.peaks)
    if p["over"] == "window":
        spent = r.window_s
    else:
        evs = _events(r, p["over"])
        if evs is None:
            return None
        spent = rd.summed_seconds(evs)
    if spent <= 0:
        return None
    r.notes.append(f"{p['work']}: least {least:.4f} s ({bound}-bound) over {spent:.4f} s")
    return 100.0 * least / spent


REDUCERS = {
    "client_stat": client_stat,
    "counter_ratio": counter_ratio,
    "counter_delta": counter_delta,
    "gauge": gauge,
    "histogram_mean": histogram_mean,
    "trace_mean_ms": trace_mean_ms,
    "trace_idle_share": trace_idle_share,
    "least_time_share": least_time_share,
}


def read_metric(spec: dict, reading: Reading):
    fn = REDUCERS.get(spec["reducer"])
    if fn is None:
        raise KeyError(f"metric {spec['name']}: unknown reducer {spec['reducer']!r}")
    value = fn(reading, spec.get("params", {}))
    return None if value is None else float(value)
