"""Causal request tracing: id minting, propagation parity (mock vs real
batcher), the checked waterfall decomposition, chaos-dump trace
resolution, SLO-triggered capture, and atomic obs file writes.

The load-bearing pins: (1) ids minted by the debate layer arrive
byte-identical at the event stream on BOTH serving paths, (2) a
request's stage walls sum EXACTLY to its reported prefill+decode
timings (SchedResult fields — the decomposition is checked, not
decorative), (3) a chaos fault's auto-dump resolves to the injured
request's trace, (4) an SLO capture fires exactly once per breaching
request, and (5) no trace state leaks across CLI invocations.
"""

import io
import json

import pytest

from adversarial_spec_tpu import cli, obs
from adversarial_spec_tpu.obs import trace as trace_mod


@pytest.fixture(autouse=True)
def _spec_off_module(monkeypatch):
    """Speculation multiplies the jit programs every batcher here
    compiles and its subject is orthogonal (the PR 6 tier-1 budget
    precedent); spec-on trace coverage rides test_spec_batcher.py's
    SpecEvent assertions."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    monkeypatch.setenv("ADVSPEC_SPECULATIVE", "0")
    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)


class TestMinting:
    def test_counter_minting_is_deterministic_and_resets(self):
        trace_mod.reset()
        assert trace_mod.mint_trace(1) == "tr-001-01"
        assert trace_mod.mint_trace(2) == "tr-002-02"
        trace_mod.reset()
        assert trace_mod.mint_trace(1) == "tr-001-01"

    def test_span_embeds_trace(self):
        sid = trace_mod.mint_span("tr-003-01", 2)
        assert sid == "tr-003-01/s02"
        assert trace_mod.trace_of(sid) == "tr-003-01"
        assert trace_mod.trace_of("") == ""

    def test_seeded_minting_is_stable(self):
        trace_mod.reset()
        a = trace_mod.mint_trace(1, seed=42)
        trace_mod.reset()
        b = trace_mod.mint_trace(1, seed=42)
        assert a == b and a.startswith("tr-001-01-")
        trace_mod.reset()
        assert trace_mod.mint_trace(1, seed=43) != a

    def test_scope_restores_even_through_exceptions(self):
        trace_mod.set_ambient("outer-t", "outer-s")
        with pytest.raises(RuntimeError):
            with trace_mod.scope("t", "s"):
                assert trace_mod.get_ambient() == ("t", "s")
                raise RuntimeError("boom")
        assert trace_mod.get_ambient() == ("outer-t", "outer-s")
        trace_mod.reset()
        assert trace_mod.get_ambient() == ("", "")

    def test_emit_stamps_empty_fields_only(self):
        obs.reset_stats()
        with trace_mod.scope("amb-t", "amb-s"):
            obs.emit(obs.StepEvent(kind="decode"))
            obs.emit(
                obs.FaultEvent(seam="x", trace_id="own-t", span_id="own-s")
            )
        evs = obs.recorder.events()
        assert (evs[0]["trace_id"], evs[0]["span_id"]) == ("amb-t", "amb-s")
        # Explicit stamping wins over ambient (fault victim vs the
        # co-resident admission whose scope was active).
        assert (evs[1]["trace_id"], evs[1]["span_id"]) == ("own-t", "own-s")


class TestMockPropagation:
    def _round(self, round_num=1):
        from adversarial_spec_tpu.debate.core import run_round

        return run_round(
            "# Spec body\n\nA paragraph.",
            ["mock://critic", "mock://agree"],
            round_num=round_num,
        )

    def test_every_event_resolves_to_one_round_and_opponent(self):
        obs.reset_stats()
        result = self._round(round_num=2)
        assert result.trace_id == "tr-002-01"
        assert [r.span_id for r in result.responses] == [
            "tr-002-01/s00",
            "tr-002-01/s01",
        ]
        evs = obs.recorder.events()
        assert evs, "round emitted nothing"
        for e in evs:
            assert e["trace_id"] == "tr-002-01", e
            if e["span_id"]:
                assert e["span_id"] in (
                    "tr-002-01/s00",
                    "tr-002-01/s01",
                ), e
        # Request-scoped events carry their exact span.
        req_spans = {
            e["req_id"]: e["span_id"]
            for e in evs
            if e["type"] == "request"
        }
        assert req_spans == {0: "tr-002-01/s00", 1: "tr-002-01/s01"}

    def test_mock_waterfall_decomposition_is_exact(self):
        """Synthetic walls are exact binary fractions; the only slack
        is the dump-time 6-decimal rounding of each float (each half
        rounds independently), so the sum holds to 2 ulp of that."""
        obs.reset_stats()
        self._round()
        spans = [
            e for e in obs.recorder.events() if e["type"] == "span"
        ]
        for sid in ("tr-001-01/s00", "tr-001-01/s01"):
            # ``cancelled`` closes an early-cancelled request envelope
            # exactly like ``end`` (the agree opponent cancels under
            # the streaming default) — the decomposition must hold for
            # the truncated span set too.
            ends = {
                e["name"]: e["wall_s"]
                for e in spans
                if e["span_id"] == sid
                and e["phase"] in ("end", "cancelled")
            }
            assert (
                abs(ends["request"] - (ends["prefill"] + ends["decode"]))
                <= 2e-6
            )

    def test_ambient_clears_after_round(self):
        obs.reset_stats()
        self._round()
        assert trace_mod.get_ambient() == ("", "")

    def test_breaker_degraded_opponent_span_is_balanced(self):
        """A breaker-open opponent resolves with zero engine calls —
        its 'opponent' span must still close (begin without end would
        read as a forever-in-flight request)."""
        from adversarial_spec_tpu.debate.core import RoundConfig, run_round
        from adversarial_spec_tpu.resilience.breaker import BreakerRegistry
        from adversarial_spec_tpu.resilience.faults import FaultKind

        breakers = BreakerRegistry(
            threshold=1, cooldown_s=3600.0, clock=lambda: 0.0
        )
        breakers.record("mock://critic", ok=False, kind=FaultKind.OOM)
        obs.reset_stats()
        result = run_round(
            "# Spec",
            ["mock://critic", "mock://agree"],
            cfg=RoundConfig(breakers=breakers),
        )
        degraded = result.responses[0]
        assert degraded.error and "circuit open" in degraded.error
        phases = [
            e["phase"]
            for e in obs.recorder.events()
            if e["type"] == "span"
            and e["name"] == "opponent"
            and e["span_id"] == degraded.span_id
        ]
        assert phases == ["begin", "end"]

    def test_trace_view_checks_pass_and_catch_corruption(self, tmp_path):
        from tools.trace_view import main as trace_view_main

        obs.reset_stats()
        self._round()
        path = tmp_path / "ev.jsonl"
        obs.dump_events(str(path))
        assert trace_view_main([str(path)]) == 0
        # Corrupt one request envelope's wall: the checked
        # decomposition must fail loudly (exit 1), not render anyway.
        lines = path.read_text().splitlines()
        out = []
        for line in lines:
            e = json.loads(line)
            if (
                e["type"] == "span"
                and e["name"] == "request"
                and e["phase"] == "end"
            ):
                e["wall_s"] += 1.0
            out.append(json.dumps(e, separators=(",", ":")))
        path.write_text("\n".join(out) + "\n")
        assert trace_view_main([str(path)]) == 1
        assert trace_view_main([str(path), "--no-check"]) == 0


class TestCliNoLeak:
    def _run(self, monkeypatch, capsys, *extra):
        monkeypatch.setattr("sys.stdin", io.StringIO("# Spec"))
        code = cli.main(
            ["critique", "--models", "mock://critic", "--json", *extra]
        )
        out, _ = capsys.readouterr()
        return code, json.loads(out)

    def test_trace_ids_restart_every_invocation(self, monkeypatch, capsys):
        """One CLI invocation = one round: the trace counter resets, so
        two invocations mint the SAME ids (byte-determinism of the
        events JSONL depends on it) and the ambient context never
        leaks."""
        code1, data1 = self._run(monkeypatch, capsys)
        assert code1 == 0
        code2, data2 = self._run(monkeypatch, capsys)
        assert code2 == 0
        assert data1["trace_id"] == data2["trace_id"] == "tr-001-01"
        assert trace_mod.get_ambient() == ("", "")

    def test_slo_flags_do_not_leak(self, monkeypatch, capsys):
        code, data = self._run(
            monkeypatch, capsys, "--slo-ttft-ms", "0.001"
        )
        assert code == 0
        assert data["perf"]["obs"]["slo"]["ttft_ms"] == 0.001
        assert data["perf"]["obs"]["slo"]["breaches"].get("ttft") == 1
        code, data = self._run(monkeypatch, capsys)
        assert code == 0
        assert data["perf"]["obs"]["slo"] == {
            "ttft_ms": 0.0,
            "round_s": 0.0,
            "breaches": {},
        }


class TestSloCapture:
    def test_fires_exactly_once_per_breaching_request(self, tmp_path):
        obs.configure(
            events_out=str(tmp_path / "ev.jsonl"), slo_ttft_ms=1.0
        )
        obs.reset_stats()
        with trace_mod.scope("tr-001-01", ""):
            obs.emit(obs.StepEvent(kind="decode"))
        path = obs.slo_check("ttft", "tr-001-01/s00", 0.5)
        assert path == str(tmp_path / "ev.slo_ttft.jsonl")
        # Same request again: no second capture, count stays 1.
        assert obs.slo_check("ttft", "tr-001-01/s00", 0.9) is None
        # A different request captures independently.
        assert obs.slo_check("ttft", "tr-001-01/s01", 0.5) is not None
        snap = obs.metrics.snapshot()
        assert snap['advspec_slo_breaches_total{kind="ttft"}'] == 2
        assert obs.slo_breaches() == {"ttft": 2}

    def test_capture_is_scoped_to_the_breaching_trace(self, tmp_path):
        obs.configure(
            events_out=str(tmp_path / "ev.jsonl"), slo_round_s=0.001
        )
        obs.reset_stats()
        with trace_mod.scope("tr-001-01", ""):
            obs.emit(obs.StepEvent(kind="decode"))
        with trace_mod.scope("tr-002-02", ""):
            obs.emit(obs.StepEvent(kind="decode"))
        assert obs.slo_check("round", "tr-002-02/s00", 0.5) is not None
        dumped = [
            json.loads(line)
            for line in (tmp_path / "ev.slo_round.jsonl")
            .read_text()
            .splitlines()
        ]
        assert dumped, "SLO capture wrote nothing"
        assert all(e["trace_id"] == "tr-002-02" for e in dumped)

    def test_disabled_budgets_never_fire(self):
        obs.configure(slo_ttft_ms=0.0, slo_round_s=0.0)
        obs.reset_stats()
        assert obs.slo_check("ttft", "s", 1e9) is None
        assert obs.slo_check("round", "s", 1e9) is None
        assert obs.slo_breaches() == {}

    def test_mock_round_breaches_and_captures(self, tmp_path):
        """End-to-end on the mock: synthetic prefill walls (~0.29s)
        breach a 1ms TTFT budget — one capture per opponent request,
        scoped to the round's trace."""
        from adversarial_spec_tpu.debate.core import run_round

        obs.configure(
            events_out=str(tmp_path / "ev.jsonl"), slo_ttft_ms=1.0
        )
        obs.reset_stats()
        result = run_round(
            "# Spec body", ["mock://critic", "mock://agree"], round_num=1
        )
        assert obs.slo_breaches() == {"ttft": 2}
        cap = tmp_path / "ev.slo_ttft.jsonl"
        assert cap.exists()
        dumped = [
            json.loads(line) for line in cap.read_text().splitlines()
        ]
        assert all(e["trace_id"] == result.trace_id for e in dumped)


class TestAtomicWrites:
    def test_write_metrics_crash_window_leaves_old_file_intact(
        self, tmp_path, monkeypatch
    ):
        """The scraper contract: a writer dying anywhere before the
        rename leaves the PREVIOUS complete exposition in place and no
        half-written target — tmp+rename, DiskStore's discipline."""
        import os as os_mod

        target = tmp_path / "metrics.prom"
        target.write_text("previous complete exposition\n")
        obs.reset_stats()
        obs.metrics.counter("advspec_x_total").inc()

        def boom(src, dst):
            raise OSError("crash inside the rename window")

        monkeypatch.setattr(os_mod, "replace", boom)
        with pytest.raises(OSError):
            obs.write_metrics(str(target))
        monkeypatch.undo()
        assert target.read_text() == "previous complete exposition\n"
        # The failed attempt's temp file is cleaned up, not orphaned
        # as a live path a scraper could mistake for the exposition.
        assert list(tmp_path.iterdir()) == [target]
        # And a healthy write lands atomically with the new content.
        obs.write_metrics(str(target))
        assert "advspec_x_total 1" in target.read_text()
        assert list(tmp_path.iterdir()) == [target]

    def test_dump_events_crash_window(self, tmp_path, monkeypatch):
        import os as os_mod

        target = tmp_path / "ev.jsonl"
        target.write_text('{"seq":1,"type":"old"}\n')
        obs.reset_stats()
        obs.emit(obs.StepEvent(kind="decode"))

        def boom(src, dst):
            raise OSError("crash inside the rename window")

        monkeypatch.setattr(os_mod, "replace", boom)
        with pytest.raises(OSError):
            obs.dump_events(str(target))
        monkeypatch.undo()
        assert target.read_text() == '{"seq":1,"type":"old"}\n'
        assert list(tmp_path.iterdir()) == [target]
        assert obs.dump_events(str(target)) == 1


class TestBatcherPropagation:
    @pytest.fixture(scope="class")
    def tiny_model(self):
        import jax
        import jax.numpy as jnp

        from adversarial_spec_tpu.models import transformer as T
        from adversarial_spec_tpu.models.config import get_config

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        return params, cfg

    def _batcher(self, params, cfg, **kw):
        from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher

        return ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=8, chunk=4, **kw
        )

    def _submit_two(self, b):
        from adversarial_spec_tpu.engine.scheduler import SchedRequest

        b.submit(
            SchedRequest(
                req_id=0,
                prompt_ids=[1, 5, 9],
                max_new_tokens=6,
                trace_id="tr-001-01",
                span_id="tr-001-01/s00",
            )
        )
        b.submit(
            SchedRequest(
                req_id=1,
                prompt_ids=[2, 6],
                max_new_tokens=6,
                trace_id="tr-001-01",
                span_id="tr-001-01/s01",
            )
        )

    def test_ids_propagate_verbatim_to_every_request_event(
        self, tiny_model
    ):
        """Parity with the mock path: the ids minted above the engine
        arrive byte-identical in the real batcher's event stream and on
        its SchedResults."""
        params, cfg = tiny_model
        obs.reset_stats()
        b = self._batcher(params, cfg)
        self._submit_two(b)
        results = b.run_all()
        assert [(r.trace_id, r.span_id) for r in results] == [
            ("tr-001-01", "tr-001-01/s00"),
            ("tr-001-01", "tr-001-01/s01"),
        ]
        evs = obs.recorder.events()
        by_req = {}
        for e in evs:
            if e["type"] in ("request", "spec", "fault") and e.get(
                "req_id", -1
            ) >= 0:
                by_req.setdefault(e["req_id"], set()).add(e["span_id"])
        assert by_req[0] == {"tr-001-01/s00"}
        assert by_req[1] == {"tr-001-01/s01"}
        # Cache events (ambient-stamped) resolve to an admission, and
        # every stamped event resolves to the one round.
        for e in evs:
            if e["trace_id"]:
                assert e["trace_id"] == "tr-001-01", e
            if e["type"] == "cache":
                assert e["span_id"] in (
                    "tr-001-01/s00",
                    "tr-001-01/s01",
                ), e

    def test_decomposition_matches_sched_result_exactly(
        self, tiny_model, tmp_path
    ):
        """The acceptance pin: waterfall stage walls sum to the
        request's REPORTED prefill+decode timings (SchedResult fields),
        and the slot decode sums reproduce the batcher's decode
        counter."""
        from tools.trace_view import (
            check_decomposition,
            collect_requests,
            main as trace_view_main,
        )

        params, cfg = tiny_model
        obs.reset_stats()
        b = self._batcher(params, cfg)
        self._submit_two(b)
        results = b.run_all()
        assert abs(
            sum(r.decode_time_s for r in results) - b.decode_time_s
        ) < 1e-9
        evs = obs.recorder.events()
        reqs = collect_requests(evs)
        assert set(reqs) == {"tr-001-01/s00", "tr-001-01/s01"}
        for r in results:
            rec = reqs[r.span_id]
            assert rec["stages"]["prefill"] == round(r.prefill_time_s, 6)
            assert rec["stages"]["decode"] == round(r.decode_time_s, 6)
            assert rec["request_wall"] == round(
                r.prefill_time_s + r.decode_time_s, 6
            )
        assert check_decomposition(reqs) == []
        path = tmp_path / "ev.jsonl"
        obs.dump_events(str(path))
        assert trace_view_main([str(path)]) == 0

    @pytest.mark.parametrize("speculative", [False, True])
    def test_decomposition_holds_in_both_branches(
        self, tiny_model, speculative
    ):
        """The step's accounting is written once: the slot shares
        reproduce the decode counter whether a step is retired through
        the double buffer or by the verify step's counts fetch."""
        from tools.trace_view import check_decomposition, collect_requests

        params, cfg = tiny_model
        obs.reset_stats()
        b = self._batcher(params, cfg, speculative=speculative)
        self._submit_two(b)
        results = b.run_all()
        steps = [
            e
            for e in obs.recorder.events()
            if e["type"] == "step" and e["kind"] != "prefill"
        ]
        assert steps
        want = {"spec_counts"} if speculative else {"", "depth_fetch"}
        assert {e["sync_reason"] for e in steps} <= want
        assert abs(
            sum(r.decode_time_s for r in results) - b.decode_time_s
        ) < 1e-9
        reqs = collect_requests(obs.recorder.events())
        assert check_decomposition(reqs) == []
        assert {r.span_id for r in results} == set(reqs)

    def test_slo_round_breach_captures_on_real_batcher(
        self, tiny_model, tmp_path
    ):
        params, cfg = tiny_model
        obs.configure(
            events_out=str(tmp_path / "ev.jsonl"), slo_round_s=1e-9
        )
        obs.reset_stats()
        b = self._batcher(params, cfg)
        self._submit_two(b)
        b.run_all()
        assert obs.slo_breaches()["round"] == 2
        cap = tmp_path / "ev.slo_round.jsonl"
        assert cap.exists()
        dumped = [
            json.loads(line) for line in cap.read_text().splitlines()
        ]
        assert dumped and all(
            e["trace_id"] == "tr-001-01" for e in dumped
        )

    def test_chaos_kv_alloc_dump_resolves_to_injured_trace(
        self, tiny_model, tmp_path
    ):
        """Acceptance: the chaos fault's auto-dump JSONL resolves to
        the INJURED request's trace/span — the FaultEvent and the
        evicted lifecycle row both carry them."""
        from adversarial_spec_tpu.resilience import injector as injector_mod
        from adversarial_spec_tpu.resilience.injector import (
            FaultInjector,
            parse_chaos_spec,
        )

        params, cfg = tiny_model
        obs.configure(events_out=str(tmp_path / "flight.jsonl"))
        obs.reset_stats()
        try:
            injector_mod.install(
                FaultInjector(parse_chaos_spec("bug@kv_alloc:times=1"))
            )
            b = self._batcher(params, cfg)
            self._submit_two(b)
            results = b.run_all()
        finally:
            injector_mod.reset()
            obs.configure(events_out="")
        assert results[0].fault_kind == "bug"
        assert results[0].span_id == "tr-001-01/s00"
        dump = tmp_path / "flight.fault.jsonl"
        assert dump.exists()
        events = [
            json.loads(line) for line in dump.read_text().splitlines()
        ]
        for e in events:
            assert obs.validate_event(e) == [], e
        fe = [e for e in events if e["type"] == "fault"][-1]
        assert fe["seam"] == "kv_alloc"
        assert fe["trace_id"] == "tr-001-01"
        assert fe["span_id"] == "tr-001-01/s00"
        evicted = [
            e
            for e in events
            if e["type"] == "request" and e["state"] == "evicted"
        ][-1]
        assert evicted["span_id"] == "tr-001-01/s00"

    def test_chaos_scheduler_chunk_dump_resolves_to_victim_trace(
        self, tiny_model, tmp_path
    ):
        """A decode-side fault evicts a victim chosen at fault time —
        its FaultEvent must stamp the VICTIM's span, not whatever
        admission scope was ambient."""
        from adversarial_spec_tpu.resilience import injector as injector_mod
        from adversarial_spec_tpu.resilience.injector import (
            FaultInjector,
            parse_chaos_spec,
        )

        params, cfg = tiny_model
        obs.configure(events_out=str(tmp_path / "flight.jsonl"))
        obs.reset_stats()
        try:
            injector_mod.install(
                FaultInjector(
                    parse_chaos_spec("bug@scheduler_chunk:after=1:times=1")
                )
            )
            b = self._batcher(params, cfg)
            self._submit_two(b)
            results = b.run_all()
        finally:
            injector_mod.reset()
            obs.configure(events_out="")
        victims = [r for r in results if r.fault_kind is not None]
        assert victims, "chaos fault did not evict anyone"
        dump = tmp_path / "flight.fault.jsonl"
        assert dump.exists()
        events = [
            json.loads(line) for line in dump.read_text().splitlines()
        ]
        fe = [e for e in events if e["type"] == "fault"][-1]
        assert fe["span_id"] == victims[0].span_id
        assert fe["trace_id"] == victims[0].trace_id == "tr-001-01"
