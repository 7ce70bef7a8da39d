"""From the clients' records to what a user saw in the window.

Every statistic is over all the requests, tokens or gaps of the whole
window [t0, t1], by the host's clock on the client's side of the socket.
A request that failed, was shed or timed out is in `failed` and in no
latency. A gap is taken twice: per token (a delivery of m tokens after g
ms is m gaps of g/m: what a reader of the text feels as its speed) and per
delivery (one gap of g: where a stall shows undiluted). How many tokens a stream event brought is not in the event (it
carries text, and most ids of a random model print as nothing): the tap's
count of ids at the same delivery says it, and where the two disagree in
number every event stands for one token and the run says so.

A delivery is also what the decode work is counted by: one row's share of
one verify step, which reads that row's keys and values once however many
tokens it emits. A reply's first delivery, of one token, is not one: the
prefill's last position sampled it (the handoff), no verify step.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field


def percentile(values: list[float], q: float) -> float | None:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    v = sorted(values)
    k = (len(v) - 1) * q
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


@dataclass
class Finished:
    """One finished opponent request of the window, for the comparison."""

    span_id: str
    prompt_ids: list[int]
    tokens: list[int]
    debate_key: tuple

    @property
    def length(self) -> int:
        return len(self.prompt_ids) + len(self.tokens)


@dataclass
class WindowStats:
    client: dict = field(default_factory=dict)
    counts: dict = field(default_factory=dict)
    token_contexts: list[int] = field(default_factory=list)  # one per token delivered
    row_step_contexts: list[int] = field(default_factory=list)  # one per verify step's delivery
    prefill_spans: list[tuple] = field(default_factory=list)
    finished: list[Finished] = field(default_factory=list)
    notes: list[str] = field(default_factory=list)
    cached_by_debate: list[list[int]] = field(default_factory=list)
    samples: dict = field(default_factory=dict)  # the latencies themselves, sorted


def window_stats(records, tap_requests: dict, t0: float, t1: float, chips: int) -> WindowStats:
    """`records`: per client, its DebateRecords in order, set-up's included.

    The loops run through the window's edges, so: a token or a gap counts
    where its delivery falls in [t0, t1], whenever its debate was sent; a
    request or a debate counts (in `attempted`, TTFT, round time, and for
    the comparison) where it was sent in [t0, t1], whenever it ended: the
    run waits for every one.
    """
    ws = WindowStats()
    gaps: list[float] = []
    delivery_gaps: list[float] = []
    ttft: list[float] = []
    rounds: list[float] = []
    late: list[float] = []
    tokens_in_window = 0
    attempted = failed = ended_early = unmatched = not_batched = handoffs = 0
    for client_records in records:
        for rec in client_records:
            d = rec.debate
            sent_in_window = t0 <= rec.t_submit <= t1
            if sent_in_window:
                attempted += d.opponents
                if rec.late_ms is not None:
                    late.append(rec.late_ms)
            if not rec.ok:
                if sent_in_window:
                    failed += d.opponents
                continue
            if rec.t_result < t0:
                continue
            if sent_in_window:
                rounds.append(rec.t_result - rec.t_submit)
            results = rec.final["results"]
            if sent_in_window:
                ws.cached_by_debate.append([int(r.get("cached_tokens") or 0) for r in results])
            for i, res in enumerate(results):
                n_out = int(res.get("output_tokens") or 0)
                n_in = int(res.get("input_tokens") or 0)
                times = rec.stream_times.get(i, [])
                tapped = tap_requests.get(res.get("span_id") or "")
                if tapped is not None and len(tapped["deliveries"]) == len(times):
                    cum = tapped["deliveries"]
                else:
                    cum = list(range(1, len(times) + 1))
                    unmatched += sent_in_window
                # what the stream did not bring arrives with the result
                if (cum[-1] if cum else 0) < n_out:
                    times = times + [rec.t_result]
                    cum = cum + [n_out]
                if sent_in_window:
                    if tapped is None:
                        not_batched += 1
                    else:
                        ws.finished.append(
                            Finished(
                                span_id=tapped["span_id"],
                                prompt_ids=tapped["prompt_ids"],
                                tokens=tapped["tokens"] or [],
                                debate_key=(d.client, d.index),
                            )
                        )
                    if n_out < d.max_new_tokens:
                        ended_early += 1
                    if times:
                        ttft.append(times[0] - rec.t_submit)
                if times and t0 <= times[0] <= t1:
                    ws.prefill_spans.append((int(res.get("cached_tokens") or 0), n_in))
                prev_t, prev_n = None, 0
                for t, n in zip(times, cum):
                    new = n - prev_n
                    if new <= 0:
                        continue
                    if t0 <= t <= t1:
                        tokens_in_window += new
                        ws.token_contexts.extend(n_in + prev_n + j for j in range(new))
                        if prev_n == 0 and new == 1:
                            handoffs += 1  # the prefill's own sample: no verify step
                        else:
                            ws.row_step_contexts.append(n_in + n)
                        if prev_t is not None:
                            gaps.extend([(t - prev_t) / new] * new)
                            delivery_gaps.append(t - prev_t)
                    prev_t, prev_n = t, n
    window_s = t1 - t0
    ws.client = {
        "out_tokens_per_s": tokens_in_window / window_s / chips if tokens_in_window else None,
        "itl_p50_ms": _ms(percentile(gaps, 0.50)),
        "itl_p95_ms": _ms(percentile(gaps, 0.95)),
        "delivery_gap_p95_ms": _ms(percentile(delivery_gaps, 0.95)),
        "ttft_mean_ms": _ms(sum(ttft) / len(ttft)) if ttft else None,
        "ttft_p50_ms": _ms(percentile(ttft, 0.50)),
        "ttft_p90_ms": _ms(percentile(ttft, 0.90)),
        "round_p50_s": percentile(rounds, 0.50),
        "late_p99_ms": percentile(late, 0.99),
    }
    ws.samples = {
        "ttft_ms": sorted(round(1000.0 * x) for x in ttft),
        "round_s": sorted(round(x, 2) for x in rounds),
    }
    ws.counts = {
        "attempted": attempted,
        "failed": failed,
        "ended_early": ended_early,
        "not_served_by_batcher": not_batched,
        "stream_events_unmatched": unmatched,
        "tokens": tokens_in_window,
        "row_steps": len(ws.row_step_contexts),
        "handoff_deliveries": handoffs,
        "gaps": len(gaps),
        "ttft_samples": len(ttft),
        "rounds": len(rounds),
    }
    return ws


def _ms(x):
    return None if x is None else 1000.0 * x
