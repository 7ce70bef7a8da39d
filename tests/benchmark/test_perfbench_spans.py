"""perfbench/spans.py: the device's idle time booked to the innermost
`advspec.*` span open over it, and the four shares of it (`SHARES`) that
split `device.idle_share` by what the host was doing."""

import re
from pathlib import Path

import pytest

from adversarial_spec_tpu import obs
from perfbench import reduce as rd
from perfbench import reducers
from perfbench import spans as sp

BENCH = Path(__file__).resolve().parents[2] / "perfbench"
PLANE = "/device:TPU:0"


def _recorded() -> rd.Trace:
    """A cut of a traced window of `mistral-7b-int8.critique` around one
    admission's handoff (my chip run, PR 40; `.perfbench_run` trace cut
    with `reduce.trim`, host events other than `advspec.*` dropped)."""
    return rd.load_json(str(BENCH / "data/small_trace_spans.json"))


def _window_s(tr: rd.Trace) -> float:
    w = rd.window_of(tr)
    return (w[1] - w[0]) / 1e9


def _shares(tr: rd.Trace) -> dict:
    table = sp.idle_by_span(tr)
    return {k: sp.share(table, v, _window_s(tr)) for k, v in sp.SHARES.items()}


def _reading(tr: rd.Trace | None, window_s: float = 1.0) -> reducers.Reading:
    return reducers.Reading(
        window_s=window_s, counters_start={}, counters_end={}, client={},
        token_contexts=[], row_step_contexts=[], prefill_spans=[], rows=4,
        config={}, arch=None, quant="int8", peaks=None, trace=tr,
    )


def _synthetic(busy: list[tuple], host: list[tuple]) -> rd.Trace:
    """A one-plane trace: device operations over `busy` (ns), and host
    spans `(name, start, end)` on one thread."""
    return rd.Trace(
        devices={PLANE: {rd.OPS_LINE: [("op", a, b - a) for a, b in busy]}},
        host=[("0/python", "advspec." + n, a, b - a) for n, a, b in host],
    )


def test_the_recorded_trace_holds_a_handoff_inside_its_admission():
    tr = _recorded()
    spans = sp.host_spans(tr.host)
    names = {n for n, _, _ in spans}
    assert {"drive.prefill", "handoff.launch", "handoff.sync",
            "handoff.insert", "handoff.books", "handoff.deliver"} <= names
    prefills = [(a, b) for n, a, b in spans if n == "drive.prefill"]
    for n, a, b in spans:
        if n.startswith("handoff."):
            assert any(a0 <= a and b <= b0 for a0, b0 in prefills), n
    assert rd.busy_seconds(tr) > 0


def test_the_four_shares_add_up_to_the_idle_share():
    tr = _recorded()
    shares = _shares(tr)
    idle = rd.idle_share(tr, _window_s(tr))
    assert 0 < idle < 100
    assert sum(shares.values()) == pytest.approx(idle, abs=0.01)
    # the cut holds an admission: its handoff is where the device waited
    assert shares["idle.admission_share"] > 0
    # the reader a metric's file would name reads the same, one note a run
    r = _reading(tr, _window_s(tr))
    for name, pattern in sp.SHARES.items():
        value = sp.trace_idle_by_span(r, {"spans": pattern})
        assert value == pytest.approx(shares[name])
    assert len(r.notes) == 1 and r.notes[0].startswith("idle_by_span:")


def test_every_span_name_falls_in_exactly_one_share():
    for name in obs.PHASES + (sp.NO_SPAN,):
        hits = [k for k, v in sp.SHARES.items() if re.search(v, name)]
        assert len(hits) == 1, (name, hits)
    # a sub-phase is booked where its parent is
    for name, parent in obs.PHASE_PARENT.items():
        family = [k for k, v in sp.SHARES.items() if re.search(v, name)]
        assert family == [
            k for k, v in sp.SHARES.items() if re.search(v, parent)
        ]


def test_a_nested_span_takes_the_idle_time_under_it():
    # device: busy 0-100 and 300-400; host: an admission 90-310 whose
    # handoff's insert holds 150-250
    tr = _synthetic(
        [(0, 100), (300, 400)],
        [("drive.iteration", 0, 400), ("drive.prefill", 90, 310),
         ("handoff.insert", 150, 250)],
    )
    table = sp.idle_by_span(tr)
    idle = {k: round(v["idle_s"] * 1e9) for k, v in table.items()}
    assert idle == {"drive.prefill": 100, "handoff.insert": 100}
    assert round(table["handoff.insert"]["whole"] * 1e9) == 100
    shares = _shares(tr)
    assert shares["idle.admission_share"] == pytest.approx(50.0)
    assert sum(shares.values()) == pytest.approx(rd.idle_share(tr))


def test_a_trace_with_no_spans_books_everything_outside():
    tr = rd.load_json(str(BENCH / "data/small_trace.json"))
    assert not sp.host_spans(tr.host)
    shares = _shares(tr)
    idle = rd.idle_share(tr, _window_s(tr))
    assert idle > 0
    assert shares["idle.outside_share"] == pytest.approx(idle)
    assert all(v == 0 for k, v in shares.items() if k != "idle.outside_share")


def test_the_windows_ends_count_as_idle():
    """Over a window wider than the busy time, the stretches before the
    first operation and after the last are idle too, so the table adds up
    to the window less the busy time."""
    table = sp.idle_by_phase(
        [(100, 200), (300, 400)], [("drive.fetch", 0, 500)], window=(0, 500)
    )
    row = table["drive.fetch"]
    assert round(row["idle_s"] * 1e9) == 300
    assert (
        round(row["head"] * 1e9),
        round(row["between"] * 1e9),
        round(row["tail"] * 1e9),
    ) == (100, 100, 100)


def test_nothing_to_read_is_nothing():
    assert sp.trace_idle_by_span(_reading(None), {"spans": "."}) is None
    assert sp.idle_by_span(rd.Trace()) is None
