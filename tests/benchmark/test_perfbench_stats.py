"""What the clients' records become: every token, gap and request of the
window, failures in `failed` and in no latency."""

import pytest

from perfbench import stats
from perfbench.loadgen import DebateRecord
from perfbench.traffic import Debate


def _debate(i, opponents=2, max_new=4):
    return Debate(client=0, index=i, tenant="t0", round_num=10 + i, spec="x", opponents=opponents,
                  max_new_tokens=max_new, warmup=False)


def _record(i, t_submit, firsts, step, n_out=4, t_result=None, error=None, per_delivery=1):
    """Opponent k streams its first delivery at firsts[k], then one every `step`."""
    d = _debate(i, opponents=len(firsts), max_new=4)
    n_deliveries = n_out // per_delivery
    rec = DebateRecord(debate=d, t_submit=t_submit)
    results = []
    for k, first in enumerate(firsts):
        rec.stream_times[k] = [first + j * step for j in range(n_deliveries)]
        results.append({"span_id": f"s{i}.{k}", "output_tokens": n_out, "input_tokens": 100,
                        "cached_tokens": 64, "error": None})
    rec.t_result = t_result if t_result is not None else max(firsts) + (n_deliveries - 1) * step
    rec.final = {"event": "result", "results": results, "error": error}
    return rec


def _tap(records, per_delivery=1, n_out=4):
    out = {}
    for rec in records:
        for r in rec.final["results"]:
            out[r["span_id"]] = {
                "span_id": r["span_id"], "prompt_ids": list(range(100)),
                "tokens": list(range(n_out)),
                "deliveries": list(range(per_delivery, n_out + 1, per_delivery)),
            }
    return out


def test_tokens_gaps_ttft_and_rounds_of_the_window_by_hand():
    a = _record(0, t_submit=10.0, firsts=[11.0, 11.5], step=0.1)  # ends 11.8
    b = _record(1, t_submit=12.0, firsts=[13.0, 13.0], step=0.2)  # ends 13.6
    ws = stats.window_stats([[a, b]], _tap([a, b]), t0=10.0, t1=20.0, chips=1)
    assert ws.counts["attempted"] == 4 and ws.counts["failed"] == 0
    assert ws.counts["tokens"] == 16 and ws.counts["gaps"] == 12
    assert ws.client["out_tokens_per_s"] == pytest.approx(1.6)
    assert ws.client["ttft_p50_ms"] == pytest.approx(1000.0)  # 1.0, 1.5, 1.0, 1.0
    assert ws.client["ttft_mean_ms"] == pytest.approx(1125.0)
    assert ws.client["round_p50_s"] == pytest.approx((1.8 + 1.6) / 2)
    assert ws.client["itl_p50_ms"] == pytest.approx(150.0)  # six gaps of 0.1, six of 0.2
    # every delivered token carries its row's context: the prompt and what came before it
    assert sorted(ws.token_contexts)[:2] == [100, 100] and max(ws.token_contexts) == 103
    # and every delivery but a reply's first (the handoff's one token) is a row's share of a
    # verify step, at the row's length after it
    assert sorted(ws.row_step_contexts) == [102] * 4 + [103] * 4 + [104] * 4
    assert ws.counts["row_steps"] == 12 and ws.counts["handoff_deliveries"] == 4
    assert ws.prefill_spans == [(64, 100)] * 4
    assert len(ws.finished) == 4 and ws.finished[0].length == 104


def test_tokens_count_where_they_land_and_requests_where_they_were_sent():
    warm = _record(0, t_submit=1.0, firsts=[2.0], step=0.1)  # over before the window
    early = _record(1, t_submit=8.0, firsts=[9.5], step=1.0)  # tokens at 9.5, 10.5, 11.5, 12.5
    late = _record(2, t_submit=10.0, firsts=[10.5], step=1.0)  # tokens at 10.5, 11.5, 12.5, 13.5
    recs = [warm, early, late]
    ws = stats.window_stats([recs], _tap(recs), t0=10.0, t1=12.0, chips=1)
    # sent in the window: `late` alone; it is waited for, so its latencies are whole
    assert ws.counts["attempted"] == 1 and ws.counts["ttft_samples"] == 1
    assert ws.counts["rounds"] == 1 and ws.client["round_p50_s"] == pytest.approx(3.5)
    assert [f.debate_key for f in ws.finished] == [(0, 2)]
    # delivered in the window: two tokens of each, whenever their debate was sent
    assert ws.counts["tokens"] == 4 and ws.counts["gaps"] == 3
    assert ws.prefill_spans == [(64, 100)]  # the one first token inside it


def test_a_failed_debate_is_in_failed_and_in_no_latency():
    ok = _record(0, t_submit=10.0, firsts=[10.5, 10.5], step=0.1)
    bad = _record(1, t_submit=11.0, firsts=[11.5, 11.5], step=0.1, error="boom")
    shed = DebateRecord(debate=_debate(2), t_submit=12.0, t_result=12.0,
                        final={"event": "shed", "reason": "backlog"})
    ws = stats.window_stats([[ok, bad, shed]], _tap([ok]), t0=10.0, t1=20.0, chips=1)
    assert ws.counts["attempted"] == 6 and ws.counts["failed"] == 4
    assert ws.counts["ttft_samples"] == 2 and ws.counts["rounds"] == 1


def test_a_delivery_of_several_tokens_counts_each_token():
    rec = _record(0, t_submit=10.0, firsts=[11.0], step=0.2, per_delivery=2)  # 2 deliveries x 2
    ws = stats.window_stats([[rec]], _tap([rec], per_delivery=2), t0=10.0, t1=20.0, chips=1)
    assert ws.counts["tokens"] == 4
    assert ws.counts["gaps"] == 2 and ws.client["itl_p50_ms"] == pytest.approx(100.0)
    # the gap between deliveries, undiluted: where a stall shows
    assert ws.client["delivery_gap_p95_ms"] == pytest.approx(200.0)
    assert ws.counts["stream_events_unmatched"] == 0


def _reply(deliveries, times, n_in=100):
    """One reply whose tap saw `deliveries` (ids so far) at `times`."""
    rec = DebateRecord(debate=_debate(0, opponents=1, max_new=deliveries[-1]), t_submit=times[0] - 1.0)
    rec.stream_times[0] = list(times)
    rec.t_result = times[-1]
    rec.final = {"event": "result", "error": None, "results": [
        {"span_id": "s", "output_tokens": deliveries[-1], "input_tokens": n_in,
         "cached_tokens": 0, "error": None}]}
    tap = {"s": {"span_id": "s", "prompt_ids": list(range(n_in)),
                 "tokens": list(range(deliveries[-1])), "deliveries": list(deliveries)}}
    return rec, tap


def test_a_rows_kv_is_counted_once_a_delivery_at_its_length_after_it():
    rec, tap = _reply([2, 4, 5], [11.0, 11.1, 11.2])
    ws = stats.window_stats([[rec]], tap, t0=10.0, t1=20.0, chips=1)
    assert ws.row_step_contexts == [102, 104, 105]
    assert ws.token_contexts == [100, 101, 102, 103, 104]
    assert ws.counts["row_steps"] == 3 and ws.counts["handoff_deliveries"] == 0
    # a delivery outside [t0, t1] is left out, with its tokens
    ws = stats.window_stats([[rec]], tap, t0=11.05, t1=11.15, chips=1)
    assert ws.row_step_contexts == [104] and ws.token_contexts == [102, 103]
    ws = stats.window_stats([[rec]], tap, t0=11.15, t1=20.0, chips=1)
    assert ws.row_step_contexts == [105] and ws.token_contexts == [104]


def test_the_handoffs_first_token_is_a_token_and_no_verify_step():
    # as the program streams: the prefill's own sample alone, then a verify step a delivery
    rec, tap = _reply([1, 3, 4, 6], [11.0, 11.1, 11.2, 11.3])
    ws = stats.window_stats([[rec]], tap, t0=10.0, t1=20.0, chips=1)
    assert ws.counts["tokens"] == 6 and len(ws.token_contexts) == 6
    assert ws.row_step_contexts == [103, 104, 106]
    assert ws.counts["row_steps"] == 3 and ws.counts["handoff_deliveries"] == 1
    # where the tap saw nothing, every stream event stands for one delivery of one token
    ws = stats.window_stats([[rec]], {}, t0=10.0, t1=20.0, chips=1)
    assert ws.counts["tokens"] == 6  # what the stream did not bring came with the result
    assert ws.row_step_contexts == [102, 103, 104, 106]


def test_a_reply_that_ends_early_or_skips_the_batcher_is_counted():
    rec = _record(0, t_submit=10.0, firsts=[11.0], step=0.1, n_out=3)
    ws = stats.window_stats([[rec]], {}, t0=10.0, t1=20.0, chips=1)
    assert ws.counts["ended_early"] == 1
    assert ws.counts["not_served_by_batcher"] == 1 and ws.finished == []
    assert ws.counts["stream_events_unmatched"] == 1


def test_percentile_is_linear_between_closest_ranks():
    assert stats.percentile([1, 2, 3, 4], 0.5) == 2.5
    assert stats.percentile([10.0], 0.95) == 10.0
    assert stats.percentile([], 0.5) is None
    assert stats.percentile(list(range(101)), 0.95) == 95


def test_the_checked_sample_holds_the_longest_and_every_client_in_turn():
    from perfbench import correct

    class F:
        def __init__(self, client, index, length):
            self.key, self.length = (client, index), length

    finished = [F(c, i, 100 + (c == 2 and i == 1)) for c in range(4) for i in range(3)]
    picked = correct.pick_debates(finished, 4, seed=7)
    assert picked[0].key == (2, 1)  # the longest, always
    # then one of each other client before any client comes twice: a client keeps
    # its place among a dispatch's rows, so every place is in the sample
    assert sorted(p.key[0] for p in picked) == [0, 1, 2, 3]
    assert picked == correct.pick_debates(finished, 4, seed=7)
    assert picked != correct.pick_debates(finished, 4, seed=8)
    assert len(correct.pick_debates(finished, 50, seed=7)) == len(finished)
    assert correct.pick_debates([], 4, seed=7) == []
