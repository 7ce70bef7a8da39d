"""Streaming token API + early-convergence cancellation.

Covers the whole stack: the incremental marker scanner
(debate/parsing.StreamScanner), the mock engine's deterministic chunked
delivery, the ContinuousBatcher's mid-decode cancellation (byte parity
up to the cancel point, page/slot surgery, partial-prefix salvage,
spec-path composition), the debate core's consumer wiring, CLI flag
plumbing, and the obs/tooling render path (CancelEvent schema,
``cancelled`` span phase, trace_view decomposition).
"""

from __future__ import annotations

import json
import random

import numpy as np
import pytest

from adversarial_spec_tpu import obs
from adversarial_spec_tpu.debate import parsing
from adversarial_spec_tpu.debate.core import RoundConfig, run_round
from adversarial_spec_tpu.debate.parsing import (
    AGREE_MARKER,
    StreamScanner,
    detect_agreement,
    get_critique_summary,
)
from adversarial_spec_tpu.engine import streaming
from adversarial_spec_tpu.engine.mock import MockEngine
from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams


@pytest.fixture(autouse=True)
def _spec_off():
    """Speculation off by default in this module (suite wall budget —
    the PR 6 precedent); the spec-composition tests opt back in
    explicitly."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=True)


# -- incremental marker scanner ------------------------------------------


class TestStreamScanner:
    def test_marker_split_across_two_chunks(self):
        sc = StreamScanner()
        assert sc.feed("critique [AGR") is None
        assert sc.feed("critique [AGREE] done") == AGREE_MARKER
        assert sc.found_at == 9

    def test_marker_split_across_three_chunks(self):
        sc = StreamScanner()
        assert sc.feed("[A") is None
        assert sc.feed("[AGRE") is None
        assert sc.feed("[AGREE]") == AGREE_MARKER
        assert sc.found_at == 0

    def test_marker_inside_code_fence_counts(self):
        # Substring semantics deliberately mirror detect_agreement
        # (bare substring, reference parity): a fenced marker counts
        # for BOTH parsers, so the incremental verdict can never
        # diverge from the whole-text one.
        text = "look:\n```\n[AGREE]\n```\nnot really"
        sc = StreamScanner()
        assert sc.feed(text) == AGREE_MARKER
        assert detect_agreement(text)

    def test_marker_never_arrives(self):
        sc = StreamScanner()
        text = "a long critique with no verdict marker at all" * 8
        for end in range(0, len(text) + 1, 7):
            assert sc.feed(text[:end]) is None
        assert sc.feed(text) is None  # EOS: falls through, no verdict

    def test_verdict_sticky(self):
        sc = StreamScanner()
        sc.feed("x [AGREE]")
        at = sc.found_at
        assert sc.feed("x [AGREE] more text [AGREE]") == AGREE_MARKER
        assert sc.found_at == at  # first find wins, no rescan

    def test_custom_marker_list_earliest_wins(self):
        sc = StreamScanner(markers=("[DONE]", AGREE_MARKER))
        assert sc.feed("a [AGREE] b [DONE]") == AGREE_MARKER

    def test_fuzz_matches_whole_text_parser(self):
        rng = random.Random(7)
        pieces = ["crit ", "[AG", "REE]", "[A", "GREE", "]", "x", "[AGREE]"]
        for trial in range(200):
            n = rng.randrange(1, 7)
            text = "".join(rng.choice(pieces) for _ in range(n))
            # Random chunking of the stream.
            sc = StreamScanner()
            verdict = None
            pos = 0
            while pos < len(text):
                pos = min(pos + rng.randrange(1, 9), len(text))
                verdict = sc.feed(text[:pos])
            whole = AGREE_MARKER in text
            assert (verdict == AGREE_MARKER) == whole, (trial, text)
            if whole:
                assert sc.found_at == text.find(AGREE_MARKER), text


class TestMarkerCleanup:
    def test_summary_strips_every_cancel_marker(self, monkeypatch):
        # Regression pin for the marker-list-driven cleanup: a section
        # marker added to EARLY_CANCEL_MARKERS is stripped from
        # summaries by the SAME path as [AGREE] — no second list.
        monkeypatch.setattr(
            parsing,
            "EARLY_CANCEL_MARKERS",
            (AGREE_MARKER, "[VERDICT]"),
        )
        s = get_critique_summary("[VERDICT] [AGREE] the spec is fine")
        assert "[VERDICT]" not in s and AGREE_MARKER not in s
        assert s == "the spec is fine"

    def test_summary_still_strips_agree(self):
        assert (
            get_critique_summary("[AGREE]\nall good") == "all good"
        )


# -- mock engine streaming ------------------------------------------------


def _agree_req(tail=50, model=None):
    return ChatRequest(
        model=model or f"mock://critic?agree_after=1&agree_tail={tail}",
        system="sys",
        user="Debate round 1\n--- DOCUMENT ---\nspec text\n--- END DOCUMENT ---",
    )


class TestMockStreaming:
    def test_cancel_truncates_to_blocking_prefix(self):
        full = MockEngine().chat([_agree_req()], SamplingParams())[0]
        sc = StreamScanner()

        def consumer(row, text):
            return sc.feed(text) is None

        out = MockEngine().chat(
            [_agree_req()], SamplingParams(), consumer=consumer
        )[0]
        assert out.cancelled
        assert full.text.startswith(out.text)  # byte-identical prefix
        assert detect_agreement(out.text)
        assert len(out.text) < len(full.text)

    def test_no_consumer_is_blocking_path(self):
        a = MockEngine().chat([_agree_req()], SamplingParams())[0]
        b = MockEngine().chat([_agree_req()], SamplingParams())[0]
        assert a.text == b.text and not a.cancelled

    def test_stream_disabled_ignores_consumer(self):
        streaming.configure(enabled=False)
        calls = []
        out = MockEngine().chat(
            [_agree_req()],
            SamplingParams(),
            consumer=lambda r, t: calls.append(t) or False,
        )[0]
        assert not out.cancelled and not calls

    def test_saved_tokens_accounted(self):
        streaming.reset_stats()
        sc = StreamScanner()
        MockEngine().chat(
            [_agree_req(tail=100)],
            SamplingParams(),
            consumer=lambda r, t: sc.feed(t) is None,
        )
        snap = streaming.snapshot()
        assert snap["cancels"] == 1
        assert snap["tokens_saved"] > 0
        assert 0.0 < snap["saved_fraction"] <= 1.0

    def test_raising_consumer_degrades_to_blocking(self):
        def bad(row, text):
            raise RuntimeError("boom")

        out = MockEngine().chat(
            [_agree_req()], SamplingParams(), consumer=bad
        )[0]
        full = MockEngine().chat([_agree_req()], SamplingParams())[0]
        assert out.text == full.text and not out.cancelled

    def test_cancel_emits_schema(self, tmp_path):
        obs.reset_stats()
        sc = StreamScanner()
        MockEngine().chat(
            [_agree_req()],
            SamplingParams(),
            consumer=lambda r, t: sc.feed(t) is None,
        )
        events = obs.recorder.events()
        cancels = [e for e in events if e["type"] == "cancel"]
        assert len(cancels) == 1
        assert cancels[0]["reason"] == "early_converge"
        assert cancels[0]["tokens_saved"] > 0
        for e in events:
            assert obs.validate_event(e) == [], e
        states = [
            e["state"] for e in events if e["type"] == "request"
        ]
        assert states[-1] == "cancelled"
        req_span = [
            e
            for e in events
            if e["type"] == "span" and e["name"] == "request"
        ]
        assert req_span[-1]["phase"] == "cancelled"
        snap = obs.metrics.snapshot()
        assert (
            snap['advspec_cancelled_total{reason="early_converge"}'] == 1
        )


# -- debate core wiring ---------------------------------------------------


class TestRoundIntegration:
    def test_round_cancels_agree_and_keeps_critics(self):
        streaming.reset_stats()
        r = run_round(
            "spec body",
            [
                "mock://critic?agree_after=1&agree_tail=80",
                "mock://critic",
            ],
            round_num=1,
        )
        agree, critic = r.responses
        assert agree.agreed and detect_agreement(agree.critique)
        assert not critic.agreed and "[SPEC]" in critic.critique
        assert streaming.stats.cancels == 1

    def test_early_cancel_off_streams_nothing(self):
        streaming.configure(early_cancel=False)
        streaming.reset_stats()
        r = run_round(
            "spec body",
            ["mock://critic?agree_after=1&agree_tail=80"],
            round_num=1,
        )
        assert streaming.stats.cancels == 0
        assert "remark 80" in r.responses[0].critique  # full tail decoded

    def test_two_arg_engine_fake_still_works(self):
        # An engine without the consumer seam (the pre-streaming
        # 2-argument chat) must serve the blocking path unmodified.
        class OldEngine:
            def chat(self, requests, params):
                from adversarial_spec_tpu.engine.types import Completion

                return [Completion(text="[AGREE] ok") for _ in requests]

            def validate(self, model):
                return None

        from adversarial_spec_tpu.engine import dispatch

        eng = OldEngine()
        assert not streaming.consumer_supported(eng)
        dispatch._ENGINE_CACHE["mock"] = eng
        r = run_round("spec", ["mock://whatever"], round_num=1)
        assert r.responses[0].critique == "[AGREE] ok"

    def test_round_transcripts_prefix_of_blocking(self):
        models = ["mock://critic?agree_after=1&agree_tail=40"]
        streaming.configure(enabled=False)
        blocking = run_round("spec", models, round_num=1)
        streaming.configure(enabled=True, early_cancel=True)
        streamed = run_round("spec", models, round_num=1)
        full = blocking.responses[0].critique
        part = streamed.responses[0].critique
        assert full.startswith(part) and len(part) < len(full)


# -- continuous batcher ---------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg, jnp.float32)
    return params, cfg


def _mk_batcher(tiny_model, **kw):
    from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher

    params, cfg = tiny_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_new_cap", 48)
    kw.setdefault("page_size", 64)
    kw.setdefault("capacity_tokens", 8192)
    kw.setdefault("greedy", True)
    return ContinuousBatcher(params, cfg, **kw)


def _drain(b, prompts, budget=48, cancel_after=None, cancel_rows=()):
    from adversarial_spec_tpu.engine.scheduler import SchedRequest

    delivered: dict[int, list[int]] = {}
    for i, p in enumerate(prompts):
        cb = None
        if i in cancel_rows:
            def cb(toks, _i=i):
                delivered[_i] = [int(t) for t in toks]
                return not (
                    cancel_after is not None and len(toks) >= cancel_after
                )
        b.submit(
            SchedRequest(
                req_id=i, prompt_ids=p, max_new_tokens=budget, on_tokens=cb
            )
        )
    res = b.run_all()
    b.allocator.check_invariants()
    return res, delivered


PROMPTS = [[5, 6, 7, 8] * 20, [9, 10, 11, 12] * 20]


class TestBatcherCancel:
    @pytest.mark.parametrize(
        "kw",
        [
            # The drive loop's two retirements (a plain step's double
            # buffer: this module's default; a verify step's counts
            # fetch) x the two layouts (prefix cache on; padded).
            {},
            {"speculative": True, "gamma": 4},
            {"prefix_cache": False},
            {"speculative": True, "gamma": 4, "prefix_cache": False},
        ],
        ids=["plain", "speculative", "no-prefix-cache",
             "speculative-no-prefix-cache"],
    )
    def test_cancel_prefix_parity_and_readmission(self, tiny_model, kw):
        ref, _ = _drain(_mk_batcher(tiny_model, **kw), PROMPTS)
        res, delivered = _drain(
            _mk_batcher(tiny_model, **kw),
            PROMPTS,
            cancel_after=8,
            cancel_rows={0},
        )
        r0 = next(r for r in res if r.req_id == 0)
        r1 = next(r for r in res if r.req_id == 1)
        ref0 = next(r for r in ref if r.req_id == 0)
        assert r0.cancelled and r0.error is None
        # Byte-identical up to the cancellation point (greedy).
        assert (
            r0.tokens.tolist()
            == ref0.tokens.tolist()[: r0.n_generated]
        )
        assert r0.n_generated >= 8
        assert r0.tokens_saved == 48 - r0.n_generated
        # The consumer saw exactly the transcript prefix.
        assert delivered[0] == r0.tokens.tolist()
        # Co-resident unaffected.
        assert not r1.cancelled and r1.n_generated == 48

    def test_cancel_with_speculation_mid_span(self, tiny_model):
        # Mid-spec-span cancel: the per-step counts fetch rolled draft
        # pages back (PageAllocator.truncate) before the cancel runs;
        # invariants must hold after every cancel.
        from adversarial_spec_tpu.engine import spec as spec_mod

        spec_mod.configure(enabled=True, gamma=4)
        try:
            b = _mk_batcher(tiny_model, speculative=True, gamma=4)
            res, _ = _drain(b, PROMPTS, cancel_after=6, cancel_rows={0})
            r0 = next(r for r in res if r.req_id == 0)
            assert r0.cancelled and r0.spec_steps > 0
            ref, _ = _drain(
                _mk_batcher(tiny_model, speculative=True, gamma=4), PROMPTS
            )
            ref0 = next(r for r in ref if r.req_id == 0)
            assert (
                r0.tokens.tolist()
                == ref0.tokens.tolist()[: r0.n_generated]
            )
        finally:
            spec_mod.configure(enabled=False)

    def test_freed_slot_readmits_queued_request(self, tiny_model):
        # max_batch=1: the queued request can only start once the
        # cancelled one releases the slot — and it must start well
        # before the cancelled request's old budget would have elapsed.
        obs.reset_stats()
        b = _mk_batcher(tiny_model, max_batch=1, max_new_cap=256)
        res, _ = _drain(
            b,
            PROMPTS,
            budget=256,
            cancel_after=8,
            cancel_rows={0},
        )
        assert next(r for r in res if r.req_id == 0).cancelled
        assert next(r for r in res if r.req_id == 1).n_generated == 256
        steps = [
            e
            for e in obs.recorder.events()
            if e["type"] == "step" and e["kind"] != "prefill"
        ]
        # Without the cancel, req0 alone needs ~256/chunk decode steps
        # BEFORE req1 could even start; with it, the whole drain fits
        # in roughly req1's own budget of steps.
        assert len(steps) < (256 // b.chunk) + 4

    def test_cancelled_pages_freed_and_partial_prefix_cached(
        self, tiny_model
    ):
        b = _mk_batcher(tiny_model, max_batch=1, max_new_cap=96)
        prompt = [5, 6, 7, 8] * 40  # 160 tokens
        res, _ = _drain(
            b, [prompt], budget=96, cancel_after=40, cancel_rows={0}
        )
        r0 = res[0]
        assert r0.cancelled and r0.n_generated >= 40
        # All sequence refs dropped; only cache refs remain.
        assert b.allocator.free_pages > 0
        # Replay with the salvaged prefix: the adopted prefix must
        # extend PAST the prompt into the cancelled decode's tokens
        # (160 prompt tokens + the salvaged tail pages).
        res2, _ = _drain(
            b, [prompt + r0.tokens.tolist()], budget=16
        )
        covered = len(prompt) + r0.n_generated - 1
        expect = (covered // b.page_size) * b.page_size
        assert res2[0].cached_tokens >= min(expect, 192) > len(prompt)

    def test_cancel_obs_schema_and_no_recompiles(self, tiny_model):
        obs.reset_stats()
        obs.retrace.clear()
        b = _mk_batcher(tiny_model)
        _drain(b, PROMPTS, cancel_after=8, cancel_rows={0})
        events = obs.recorder.events()
        for e in events:
            assert obs.validate_event(e) == [], e
        cancels = [e for e in events if e["type"] == "cancel"]
        assert len(cancels) == 1
        assert cancels[0]["tokens_emitted"] >= 8
        spans = [
            e
            for e in events
            if e["type"] == "span"
            and e["name"] == "request"
            and e["phase"] == "cancelled"
        ]
        assert len(spans) == 1
        # Decomposition: cancelled envelope == prefill + decode spans.
        assert obs.snapshot()["retrace"]["unexpected_recompiles"] == 0

    def test_round_slo_judged_on_cancel(self, tiny_model):
        # A cancelled request still consumed service: a round-SLO
        # breach that happens to end in a cancel must count (and
        # self-capture) exactly as _finish_slot's does — regression
        # pin for the real-batcher slo_check on the cancel path.
        from adversarial_spec_tpu.engine.scheduler import SchedRequest

        obs.reset_stats()
        obs.configure(slo_round_s=1e-9)
        try:
            b = _mk_batcher(tiny_model)
            b.submit(
                SchedRequest(
                    req_id=0,
                    prompt_ids=PROMPTS[0],
                    max_new_tokens=48,
                    span_id="tr-001-00/s00",
                    on_tokens=lambda toks: len(toks) < 8,
                )
            )
            res = b.run_all()
            assert res[0].cancelled
            assert obs.slo_breaches().get("round") == 1
        finally:
            obs.configure(slo_round_s=0.0)

    def test_finished_row_not_cancelled(self, tiny_model):
        # A consumer that asks for cancellation AFTER its row already
        # finished (EOS/budget) must be a no-op: the row resolves as
        # finished, nothing to save.
        b = _mk_batcher(tiny_model, max_new_cap=4)
        res, delivered = _drain(
            b, PROMPTS, budget=4, cancel_after=1, cancel_rows={0}
        )
        r0 = next(r for r in res if r.req_id == 0)
        # Cancelled exactly at the first delivery point that found it
        # still active — or finished clean if it was already done.
        assert r0.n_generated >= 1
        b.allocator.check_invariants()


# -- tools render path ----------------------------------------------------


class TestToolsRender:
    def _dump_cancel_round(self, tmp_path):
        import dataclasses

        obs.reset_stats()
        sc = StreamScanner()
        # Stamp trace/span ids the way the debate layer does — the
        # per-request waterfall groups by span_id.
        req = dataclasses.replace(
            _agree_req(), trace_id="tr-001-00", span_id="tr-001-00/s00"
        )
        MockEngine().chat(
            [req],
            SamplingParams(),
            consumer=lambda r, t: sc.feed(t) is None,
        )
        path = tmp_path / "ev.jsonl"
        obs.dump_events(str(path))
        return path

    def test_obs_dump_renders_cancelled_request(self, tmp_path, capsys):
        from tools import obs_dump

        path = self._dump_cancel_round(tmp_path)
        rc = obs_dump.main([str(path), "--timeline", "--requests"])
        out = capsys.readouterr().out
        assert rc == 0  # every line schema-valid
        assert "early cancellation" in out
        assert "cancelled" in out

    def test_trace_view_decomposition_passes_on_cancel(
        self, tmp_path, capsys
    ):
        from tools import trace_view

        path = self._dump_cancel_round(tmp_path)
        rc = trace_view.main([str(path)])
        out = capsys.readouterr().out
        assert rc == 0  # decomposition check PASSES on the truncated set
        assert "CANCELLED" in out

    def test_bench_cancel_file_validates(self):
        from pathlib import Path

        from tools.bench_trend import validate_bench_file

        path = Path(__file__).resolve().parent.parent / "BENCH_cancel.json"
        if not path.exists():
            pytest.skip("BENCH_cancel.json not generated yet")
        row, problems = validate_bench_file(path)
        assert problems == [] and row is not None
        assert row["mode"] == "cancel"


# -- CLI plumbing ---------------------------------------------------------

SPEC = "# Spec\nA thing.\n"


class TestCliFlags:
    def _run(self, argv, stdin=SPEC):
        import io
        import sys as _sys

        from adversarial_spec_tpu import cli

        old = _sys.stdin
        _sys.stdin = io.StringIO(stdin)
        try:
            return cli.main(argv)
        finally:
            _sys.stdin = old

    def test_perf_stream_block_and_cancel(self, capsys):
        rc = self._run(
            [
                "critique",
                "-m",
                "mock://critic?agree_after=1&agree_tail=60",
                "--json",
            ]
        )
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        stream = out["perf"]["stream"]
        assert stream["enabled"] and stream["early_cancel"]
        assert stream["cancels"] == 1
        assert stream["tokens_saved"] > 0

    def test_no_stream_flag(self, capsys):
        rc = self._run(
            [
                "critique",
                "-m",
                "mock://critic?agree_after=1&agree_tail=60",
                "--no-stream",
                "--json",
            ]
        )
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        stream = out["perf"]["stream"]
        assert not stream["enabled"] and stream["cancels"] == 0
        # Full tail decoded: blocking path end to end.
        assert "remark 60" in out["results"][0]["response"]

    def test_no_early_cancel_flag(self, capsys):
        rc = self._run(
            [
                "critique",
                "-m",
                "mock://critic?agree_after=1&agree_tail=60",
                "--no-early-cancel",
                "--json",
            ]
        )
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["perf"]["stream"]["cancels"] == 0

    def test_env_default_and_no_leak(self, capsys, monkeypatch):
        monkeypatch.setenv("ADVSPEC_EARLY_CANCEL", "0")
        rc = self._run(
            [
                "critique",
                "-m",
                "mock://critic?agree_after=1&agree_tail=60",
                "--json",
            ]
        )
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert not out["perf"]["stream"]["early_cancel"]
        # Flag beats env; and the next invocation re-resolves (no leak).
        monkeypatch.delenv("ADVSPEC_EARLY_CANCEL")
        rc = self._run(
            [
                "critique",
                "-m",
                "mock://critic?agree_after=1&agree_tail=60",
                "--json",
            ]
        )
        out = json.loads(capsys.readouterr().out)
        assert out["perf"]["stream"]["early_cancel"]
        assert out["perf"]["stream"]["cancels"] == 1
