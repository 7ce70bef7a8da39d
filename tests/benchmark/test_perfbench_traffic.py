"""The traffic generator does the same counted work for every seed."""

import json
from pathlib import Path

import pytest

from perfbench import traffic

BENCH = Path(__file__).resolve().parents[2] / "perfbench"
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_give_the_same_work_in_the_same_order(name):
    mix = _mix(name)
    a = traffic.plan(mix, 3)
    b = traffic.plan(mix, 2_147_483_659)  # the driver's seeds are large
    assert traffic.shape_of(a) == traffic.shape_of(b)
    assert len(a) == mix["clients"]
    # debates, requests, prompt bytes and reply budgets, one by one
    for da, db in zip(a[0], b[0]):
        assert (da.opponents, da.max_new_tokens, len(da.spec)) == (
            db.opponents, db.max_new_tokens, len(db.spec))
    # only the bytes differ
    assert [d.spec for d in a[0]] != [d.spec for d in b[0]]


@pytest.mark.parametrize("name", MIXES)
def test_the_same_seed_gives_the_same_bytes(name):
    mix = _mix(name)
    assert traffic.plan(mix, 41) == traffic.plan(mix, 41)


@pytest.mark.parametrize("name", MIXES)
def test_documents_have_exactly_the_stated_length_in_one_byte_tokens(name):
    mix = _mix(name)
    for seed in (0, 7, 99991):
        plans = traffic.plan(mix, seed, n_debates=6)
        for d in [traffic.primer(mix, seed, 4)] + [d for debates in plans for d in debates]:
            assert len(d.spec) == len(d.spec.encode()) == mix["document"]["bytes"]
            assert d.spec.isascii()


def test_fresh_documents_share_nothing_and_a_session_keeps_its_document():
    fresh = traffic.plan(_mix("fresh-doc"), 5, n_debates=4)
    specs = [d.spec for debates in fresh for d in debates]
    assert len(set(specs)) == len(specs)
    sessions = traffic.plan(_mix("critique"), 5, n_debates=4)
    for session in sessions:
        assert len({d.spec for d in session}) == 1
        # a session's rounds keep one width of round number (same prompt length)
        assert len({len(str(d.round_num)) for d in session}) == 1
        assert [d.round_num for d in session] == list(range(traffic.ROUND_BASE, traffic.ROUND_BASE + 4))


@pytest.mark.parametrize("name", MIXES)
def test_rows_that_decode_together_hold_different_sequences(name):
    """Two clients' debates fill one dispatch, and no two clients (nor the
    primer) ever send the same document: so `correct` can see a row that
    reads another row's pages."""
    from perfbench import system

    mix, rows = _mix(name), system.dispatch_rows()
    assert mix["opponents"] < rows and rows % mix["opponents"] == 0
    # two dispatches' worth of clients: one is always queued while the other runs
    assert mix["clients"] * mix["opponents"] == 2 * rows
    plans = traffic.plan(mix, 77, n_debates=5)
    first = traffic.primer(mix, 77, rows)
    assert first.opponents == rows and first.warmup
    assert first.max_new_tokens == mix["max_new_tokens"]  # or the batcher is rebuilt after it
    docs = [{d.spec for d in debates} for debates in plans] + [{first.spec}]
    for i, a in enumerate(docs):
        for b in docs[i + 1:]:
            assert not a & b


def test_warm_up_debates_come_first():
    for name in MIXES:
        mix = _mix(name)
        for debates in traffic.plan(mix, 1, n_debates=8):
            flags = [d.warmup for d in debates]
            assert flags == sorted(flags, reverse=True)
            assert sum(flags) == mix["warmup_debates"]
