"""Observability subsystem: metrics registry, flight recorder, retrace
watch, scheduler/mock instrumentation, and the CLI's --metrics-out /
--events-out / perf.obs surfaces.

The load-bearing pins: (1) a mock round's Prometheus text and events
JSONL are BYTE-identical across two runs (the schema the acceptance
criteria fix), (2) the recorder ring never grows past its bound, (3)
the real scheduler emits the same event vocabulary the mock does.
"""

import io
import json

import pytest

from adversarial_spec_tpu import cli, obs
from adversarial_spec_tpu.obs import (
    BreakerEvent,
    CacheEvent,
    CompileEvent,
    FaultEvent,
    FlightRecorder,
    MetricsRegistry,
    RequestEvent,
    StepEvent,
    validate_event,
)
from adversarial_spec_tpu.obs.retrace import RetraceWatch


@pytest.fixture(autouse=True)
def _spec_off_module(monkeypatch):
    """Speculation is default-on and only multiplies the jit programs
    every batcher/engine this module compiles; its subject is
    orthogonal. Spec-on coverage (incl. SpecEvents, spec chaos fuzz,
    and the obs families) lives in tests/test_spec_batcher.py."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    monkeypatch.setenv("ADVSPEC_SPECULATIVE", "0")
    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)



@pytest.fixture(autouse=True)
def _reset_obs():
    obs.configure(
        enabled=True,
        recorder_size=obs.DEFAULT_RECORDER_SIZE,
        events_out="",
        dump_on_fault=True,
    )
    obs.reset_stats()
    yield
    obs.configure(
        enabled=True,
        recorder_size=obs.DEFAULT_RECORDER_SIZE,
        events_out="",
        dump_on_fault=True,
    )
    obs.reset_stats()


class TestMetricsRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("advspec_x_total", seam="a").inc()
        reg.counter("advspec_x_total", seam="a").inc(2)
        reg.counter("advspec_x_total", seam="b").inc()
        reg.gauge("advspec_util").set(0.5)
        h = reg.histogram("advspec_lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(99.0)
        snap = reg.snapshot()
        assert snap['advspec_x_total{seam="a"}'] == 3
        assert snap['advspec_x_total{seam="b"}'] == 1
        assert snap["advspec_util"] == 0.5
        assert snap["advspec_lat_seconds"] == {
            "count": 3,
            "sum": 99.55,
            # Bucket-estimated quantiles: p50 interpolates inside the
            # (0.1, 1.0] bucket; the tail quantiles clamp to the last
            # bound (the overflow observation is past what fixed
            # buckets can resolve).
            "p50": 0.55,
            "p95": 1.0,
            "p99": 1.0,
        }

    def test_handles_are_stable_and_reset_in_place(self):
        """The resilience/interleave reset contract: an engine holding a
        metric handle keeps recording into the same object."""
        reg = MetricsRegistry()
        c = reg.counter("advspec_n_total")
        c.inc(5)
        reg.reset()
        assert reg.counter("advspec_n_total") is c
        assert c.value == 0
        c.inc()
        assert reg.snapshot()["advspec_n_total"] == 1

    def test_hot_handles_alias_registry_series(self):
        """obs.hot caches handles ONCE at import; they must be the very
        objects the registry returns for the same name+labels, and must
        survive reset() live (reset-in-place contract) — otherwise the
        hot emit sites would record into orphaned series."""
        assert obs.hot.prefill_wall is obs.metrics.histogram(
            "advspec_prefill_wall_seconds"
        )
        assert obs.hot.req_finished is obs.metrics.counter(
            "advspec_requests_total", outcome="finished"
        )
        obs.metrics.reset()
        obs.hot.req_finished.inc()
        assert (
            obs.metrics.snapshot()['advspec_requests_total{outcome="finished"}']
            == 1
        )
        # Label-dynamic families cache per label, same aliasing rule.
        assert obs.hot.sync("fault") is obs.metrics.counter(
            "advspec_host_syncs_total", reason="fault"
        )
        assert obs.hot.sync("fault") is obs.hot.sync("fault")

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("advspec_n_total")
        with pytest.raises(ValueError):
            reg.gauge("advspec_n_total")

    def test_prometheus_exposition_schema(self):
        """Schema pin: TYPE lines, labeled series, cumulative histogram
        buckets ending at +Inf, _sum/_count — and integral floats render
        as integers (byte-stable formatting)."""
        reg = MetricsRegistry()
        reg.counter("advspec_x_total", help="things", seam="a").inc(3)
        reg.histogram("advspec_lat_seconds", buckets=(0.5, 1.0)).observe(0.7)
        text = reg.render_prometheus()
        assert "# HELP advspec_x_total things\n" in text
        assert "# TYPE advspec_x_total counter\n" in text
        assert 'advspec_x_total{seam="a"} 3\n' in text
        assert "# TYPE advspec_lat_seconds histogram\n" in text
        assert 'advspec_lat_seconds_bucket{le="0.5"} 0\n' in text
        assert 'advspec_lat_seconds_bucket{le="1"} 1\n' in text
        assert 'advspec_lat_seconds_bucket{le="+Inf"} 1\n' in text
        assert "advspec_lat_seconds_sum 0.7\n" in text
        assert "advspec_lat_seconds_count 1\n" in text
        # Quantile estimate lines ride along after _count — ONE
        # implementation (Histogram.quantile) feeds snapshot(),
        # render_prometheus(), and every harness percentile.
        assert "advspec_lat_seconds_p50 0.75\n" in text
        assert "advspec_lat_seconds_p95 0.975\n" in text
        assert "advspec_lat_seconds_p99 0.995\n" in text
        # Deterministic: same registry renders the same bytes.
        assert text == reg.render_prometheus()

    def test_percentile_exact_nearest_rank(self):
        """The shared sample-percentile (obs.metrics.percentile): exact
        nearest-rank pins on a known sample — the SLO gate, bench.py,
        and load_replay all report through this one implementation."""
        from adversarial_spec_tpu.obs.metrics import percentile

        xs = list(range(1, 101))  # 1..100
        assert percentile(xs, 0.50) == 50
        assert percentile(xs, 0.95) == 95
        assert percentile(xs, 0.99) == 99
        assert percentile(xs, 1.0) == 100
        assert percentile(xs, 0.0) == 1
        assert percentile([7.5], 0.99) == 7.5
        assert percentile([], 0.99) == 0.0
        # Unsorted input: percentile sorts a copy, never mutates.
        ys = [3.0, 1.0, 2.0]
        assert percentile(ys, 0.5) == 2.0
        assert ys == [3.0, 1.0, 2.0]

    def test_histogram_quantile_vs_exact_percentiles(self):
        """Unit pin: bucket-estimated quantiles track exact percentiles
        on a known sample to within one bucket width (the resolution a
        fixed-bucket histogram can promise) and clamp to the last bound
        beyond it."""
        from adversarial_spec_tpu.obs.metrics import (
            Histogram,
            percentile,
        )

        buckets = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0)
        h = Histogram(buckets=buckets)
        samples = [0.001 * i for i in range(1, 200)]  # 1ms..199ms
        for v in samples:
            h.observe(v)
        for q in (0.5, 0.95, 0.99):
            exact = percentile(samples, q)
            est = h.quantile(q)
            # The estimate lands in the same bucket as the exact value.
            width = max(
                b - a for a, b in zip((0.0,) + buckets, buckets)
            )
            assert abs(est - exact) <= width
        assert Histogram(buckets=buckets).quantile(0.99) == 0.0
        h2 = Histogram(buckets=(1.0, 2.0))
        h2.observe(50.0)  # beyond the last bound: clamps, never lies up
        assert h2.quantile(0.99) == 2.0


class TestFlightRecorder:
    def test_ring_is_bounded_and_counts_drops(self):
        r = FlightRecorder(size=4)
        for i in range(10):
            r.append(RequestEvent(req_id=i, state="queued"))
        assert len(r) == 4
        assert r.seq == 10
        assert r.dropped == 6
        # The LAST 4 events survive, in order.
        assert [e["req_id"] for e in r.events()] == [6, 7, 8, 9]
        assert [e["seq"] for e in r.events()] == [7, 8, 9, 10]

    def test_every_event_type_validates(self):
        r = FlightRecorder(size=16)
        for ev in (
            StepEvent(kind="fused", n_live=2, sync_reason="depth_fetch"),
            RequestEvent(req_id=1, state="finished", tokens=3),
            FaultEvent(seam="kv_alloc", kind="oom", slot=1),
            BreakerEvent(model="m", frm="closed", to="open"),
            CacheEvent(op="lookup", matched_tokens=64, hit=True),
            CompileEvent(program="decode", key="(4,)", n_compiles=1),
        ):
            r.append(ev)
        for line in r.to_jsonl().splitlines():
            assert validate_event(json.loads(line)) == []

    def test_validate_rejects_bad_lines(self):
        assert validate_event({"type": "nope"})  # unknown type
        assert validate_event(
            {"seq": 1, "type": "request", "req_id": "x"}
        )  # wrong type + missing fields
        good = {
            "seq": 1,
            "type": "request",
            "req_id": 0,
            "state": "queued",
            "slot": -1,
            "tokens": 0,
            "cached_tokens": 0,
            "arrival_s": 0.0,
            "trace_id": "",
            "span_id": "",
        }
        assert validate_event(good) == []
        assert validate_event({**good, "state": "exploded"})  # bad state
        assert validate_event({**good, "extra": 1})  # unknown field
        # arrival_s is a schema field like any other: int is an
        # acceptable float, a string is not.
        assert validate_event({**good, "arrival_s": 2}) == []
        assert validate_event({**good, "arrival_s": "soon"})
        # Trace ids are schema fields like any other: wrong type and
        # missing both reject.
        assert validate_event({**good, "trace_id": 7})
        missing = dict(good)
        del missing["span_id"]
        assert validate_event(missing)

    def test_dump_jsonl_atomic_write(self, tmp_path):
        r = FlightRecorder(size=4)
        r.append(StepEvent())
        out = tmp_path / "ev.jsonl"
        assert r.dump_jsonl(str(out)) == 1
        assert out.read_text().count("\n") == 1
        assert not (tmp_path / "ev.jsonl.tmp").exists()

    def test_shrink_resize_counts_aged_out_events_as_dropped(self):
        """buffered + dropped == recorded must survive a shrink: the
        events a smaller ring ages out are drops like any other."""
        r = FlightRecorder(size=8)
        for i in range(6):
            r.append(RequestEvent(req_id=i, state="queued"))
        r.resize(2)
        assert len(r) == 2
        assert r.dropped == 4
        assert len(r) + r.dropped == r.seq
        assert [e["req_id"] for e in r.events()] == [4, 5]

    def test_disabled_recorder_is_inert(self):
        r = FlightRecorder(size=4, enabled=False)
        r.append(StepEvent())
        assert len(r) == 0 and r.seq == 0


class TestRetraceWatch:
    def test_new_key_is_an_expected_compile(self):
        events = []
        w = RetraceWatch(emit=events.append)
        assert w.observe("decode", (4, True)) is True
        assert w.observe("decode", (4, True)) is False  # seen: no compile
        assert w.observe("decode", (8, True)) is True  # new shape
        snap = w.snapshot()
        assert snap["programs"]["decode"]["compiles"] == 2
        assert snap["programs"]["decode"]["distinct_keys"] == 2
        assert snap["programs"]["decode"]["dispatches"] == 3
        assert snap["unexpected_recompiles"] == 0
        assert all(not e.unexpected for e in events)

    def test_cache_size_growth_on_seen_key_is_unexpected(self):
        """The silent-100x-slowdown case: the host key says 'compiled
        already' but the trace cache grew — flagged, not swallowed."""

        class FakeJitted:
            sizes = iter([1, 2])

            def _cache_size(self):
                return next(self.sizes)

        fn = FakeJitted()
        events = []
        w = RetraceWatch(emit=events.append)
        assert w.observe("decode", (4,), fn=fn) is True  # first compile
        assert w.observe("decode", (4,), fn=fn) is True  # cache grew!
        snap = w.snapshot()
        assert snap["programs"]["decode"]["unexpected_recompiles"] == 1
        assert snap["unexpected_recompiles"] == 1
        assert [e.unexpected for e in events] == [False, True]

    def test_reset_keeps_baselines_clear_forgets_them(self):
        """Per-invocation reset() zeroes COUNTS but keeps seen keys and
        the cache-size baseline: the jit caches live for the process, so
        round 2's first warm dispatch must not report a fresh compile.
        clear() is the cold-start variant (test isolation)."""
        w = RetraceWatch()
        assert w.observe("decode", (4,)) is True
        w.reset()
        assert w.observe("decode", (4,)) is False  # warm: same key
        snap = w.snapshot()
        assert snap["programs"]["decode"]["compiles"] == 0
        assert snap["programs"]["decode"]["dispatches"] == 1
        w.clear()
        assert w.observe("decode", (4,)) is True  # cold start again

    def test_cache_size_steady_suppresses_false_positive(self):
        """A repeated key with a steady cache size is NOT a compile even
        though the probe is available."""

        class FakeJitted:
            def _cache_size(self):
                return 1

        w = RetraceWatch()
        assert w.observe("decode", (4,), fn=FakeJitted()) is True
        assert w.observe("decode", (4,), fn=FakeJitted()) is False


class TestSchedulerInstrumentation:
    @pytest.fixture(scope="class")
    def tiny_model(self):
        import jax
        import jax.numpy as jnp

        from adversarial_spec_tpu.models import transformer as T
        from adversarial_spec_tpu.models.config import get_config

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        return params, cfg

    def _drain(self, params, cfg, **kw):
        from adversarial_spec_tpu.engine.scheduler import (
            ContinuousBatcher,
            SchedRequest,
        )

        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=8, chunk=4, **kw
        )
        b.submit(SchedRequest(req_id=0, prompt_ids=[1, 5, 9], max_new_tokens=6))
        b.submit(SchedRequest(req_id=1, prompt_ids=[2, 6], max_new_tokens=6))
        return b.run_all()

    def test_drain_emits_full_lifecycle_and_steps(self, tiny_model):
        params, cfg = tiny_model
        obs.reset_stats()
        results = self._drain(params, cfg)
        assert len(results) == 2
        events = obs.recorder.events()
        for line in obs.recorder.to_jsonl().splitlines():
            assert validate_event(json.loads(line)) == []
        reqs = [e for e in events if e["type"] == "request"]
        for rid in (0, 1):
            states = [e["state"] for e in reqs if e["req_id"] == rid]
            # queued → admitted → ... → decode → finished, in order.
            assert states[0] == "queued"
            assert "admitted" in states and "decode" in states
            assert states[-1] == "finished"
            assert states.index("admitted") < states.index("decode")
        steps = [e for e in events if e["type"] == "step"]
        assert steps, "drive loop emitted no StepEvents"
        # Metrics: prefill wall observed once per admission, steps timed, pool
        # utilization gauge live, sanctioned syncs labeled.
        snap = obs.metrics.snapshot()
        assert snap["advspec_prefill_wall_seconds"]["count"] == 2
        assert snap["advspec_step_wall_seconds"]["count"] >= 1
        assert "advspec_page_pool_utilization" in snap
        assert (
            snap['advspec_requests_total{outcome="finished"}'] == 2
        )
        assert any(
            k.startswith("advspec_host_syncs_total") for k in snap
        )

    @pytest.mark.parametrize(
        "quantized,fused,want", [(True, True, 7), (True, False, 0), (False, True, 0)]
    )
    def test_indexed_stacks_gauge_is_set_where_the_batcher_is_built(
        self, tiny_model, quantized, fused, want
    ):
        """`advspec_qmm_indexed_stacks`: the quantized stacks the decode
        step reads by layer index: a dense family's seven with the fused
        dequant-matmul on, none with it off or with nothing quantized."""
        from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher
        from adversarial_spec_tpu.ops import quant

        params, cfg = tiny_model
        if quantized:
            params = quant.quantize_params(params)
        obs.reset_stats()
        ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=8, chunk=4,
            use_pallas_matmul=fused,
        )
        assert obs.metrics.snapshot()["advspec_qmm_indexed_stacks"] == want

    def test_retrace_watch_sees_scheduler_programs(self, tiny_model):
        params, cfg = tiny_model
        obs.reset_stats()
        self._drain(params, cfg)
        snap = obs.retrace.snapshot()
        assert "prefill_chunk" in snap["programs"]
        assert snap["programs"]["prefill_chunk"]["compiles"] >= 1
        # Pow2 chunking bounds the shapes: nothing unexpected.
        assert snap["unexpected_recompiles"] == 0

    @pytest.mark.parametrize("speculative", [False, True])
    def test_both_branches_emit_the_same_schema(
        self, tiny_model, speculative
    ):
        """A plain step (double buffer) and a verify step (counts
        fetch) emit the same event schema; each names its own sync."""
        params, cfg = tiny_model
        obs.reset_stats()
        self._drain(params, cfg, speculative=speculative)
        events = obs.recorder.events()
        for e in events:
            assert validate_event(e) == []
        kinds = {e["type"] for e in events}
        assert {"request", "step"} <= kinds
        steps = [
            e for e in events
            if e["type"] == "step" and e["kind"] != "prefill"
        ]
        syncs = obs.snapshot()["host_syncs"]
        # tools/obs_dump.py renders depth and sync from the one
        # StepEvent constructor.
        from tools.obs_dump import occupancy_timeline

        text = occupancy_timeline(events)
        if speculative:
            assert "depth=1 sync=spec_counts" in text
            assert {e["sync_reason"] for e in steps} == {"spec_counts"}
            assert {e["pipeline_depth"] for e in steps} == {1}
            assert syncs["spec_counts"] == len(steps)
        else:
            # A blocking fetch only at the depth bound, and counted.
            assert {e["sync_reason"] for e in steps} <= {"", "depth_fetch"}
            assert {e["pipeline_depth"] for e in steps} <= {1, 2}
            assert "depth=1" in text or "depth=2" in text
            assert syncs.get("depth_fetch", 0) == sum(
                e["sync_reason"] == "depth_fetch" for e in steps
            )
            assert not syncs.get("spec_counts")

    def test_disabled_obs_records_nothing(self, tiny_model):
        params, cfg = tiny_model
        obs.configure(enabled=False)
        obs.reset_stats()
        results = self._drain(params, cfg)
        assert len(results) == 2
        assert len(obs.recorder) == 0
        # Families registered by earlier (enabled) drains survive reset
        # as zeroed series; disabled means no NEW observations land.
        for key, value in obs.metrics.snapshot().items():
            if isinstance(value, dict):
                assert value["count"] == 0, key
            else:
                assert value == 0, key


class TestCliObs:
    def _run(self, tmp_path, tag):
        from adversarial_spec_tpu.engine.dispatch import _ENGINE_CACHE

        _ENGINE_CACHE.pop("mock", None)  # fresh engine: fresh mock cache
        m = tmp_path / f"metrics-{tag}.prom"
        e = tmp_path / f"events-{tag}.jsonl"
        import sys

        stdin0 = sys.stdin
        sys.stdin = io.StringIO("# Spec body\n\nA paragraph.")
        try:
            code = cli.main(
                [
                    "critique",
                    "--models",
                    "mock://critic,mock://agree",
                    "--json",
                    "--metrics-out",
                    str(m),
                    "--events-out",
                    str(e),
                ]
            )
        finally:
            sys.stdin = stdin0
        assert code == 0
        return m.read_bytes(), e.read_bytes()

    def test_mock_round_outputs_are_byte_deterministic(
        self, tmp_path, capsys
    ):
        """Acceptance pin: a mock debate round with --metrics-out /
        --events-out produces a Prometheus file and a JSONL stream that
        are byte-identical across two runs on CPU."""
        m1, e1 = self._run(tmp_path, "a")
        capsys.readouterr()
        m2, e2 = self._run(tmp_path, "b")

        def _drop_wallclock(blob: bytes) -> bytes:
            # The lockdep sanitizer's hold/wait histograms (armed
            # suite-wide by conftest) measure real wall time on real
            # lock acquisitions — the one telemetry family that is
            # wall-clock by definition and cannot be byte-reproducible.
            # Everything else in the file stays pinned byte-for-byte.
            return b"\n".join(
                ln
                for ln in blob.splitlines()
                if b"advspec_lock_hold_seconds" not in ln
                and b"advspec_lock_wait_seconds" not in ln
            )

        assert _drop_wallclock(m1) == _drop_wallclock(m2)
        assert e1 == e2
        # Schema-pinned content, not just determinism:
        text = m1.decode()
        for family in (
            "advspec_engine_chat_requests_total",
            "advspec_prefill_wall_seconds_bucket",
            "advspec_prefill_chunk_wall_seconds_sum",
            "advspec_requests_total",
        ):
            assert family in text, family
        for line in e1.decode().splitlines():
            assert validate_event(json.loads(line)) == []

    def test_perf_obs_block_and_flag_plumbing(self, monkeypatch, capsys):
        monkeypatch.setattr("sys.stdin", io.StringIO("# Spec"))
        code = cli.main(
            [
                "critique",
                "--models",
                "mock://critic",
                "--json",
                "--flight-recorder-size",
                "64",
            ]
        )
        out, _ = capsys.readouterr()
        assert code == 0
        perf = json.loads(out)["perf"]
        assert perf["obs"]["enabled"] is True
        assert perf["obs"]["recorder"]["size"] == 64
        assert perf["obs"]["events_by_type"]["request"] >= 5
        assert perf["obs"]["retrace"]["unexpected_recompiles"] == 0
        # The merged debate-layer spans ride the same report.
        assert "debate/engine_chat" in perf["spans"]
        assert perf["span_tree"]["debate"]["count"] >= 1

    def test_obs_flags_do_not_leak_across_invocations(
        self, monkeypatch, capsys
    ):
        """One invocation = one round: a --no-obs (or shrunken ring)
        round must not bleed into the next flagless invocation — every
        knob re-resolves to flag-else-env-default."""
        monkeypatch.setattr("sys.stdin", io.StringIO("# Spec"))
        assert (
            cli.main(
                [
                    "critique", "--models", "mock://critic", "--json",
                    "--no-obs", "--flight-recorder-size", "16",
                ]
            )
            == 0
        )
        capsys.readouterr()
        monkeypatch.setattr("sys.stdin", io.StringIO("# Spec"))
        assert (
            cli.main(["critique", "--models", "mock://critic", "--json"])
            == 0
        )
        out, _ = capsys.readouterr()
        perf = json.loads(out)["perf"]
        assert perf["obs"]["enabled"] is True
        assert perf["obs"]["recorder"]["size"] == obs.DEFAULT_RECORDER_SIZE
        assert perf["obs"]["recorder"]["recorded"] > 0

    def test_fault_autodump_goes_to_trigger_sibling(self, tmp_path):
        """autodump writes <stem>.<trigger>.jsonl next to events_out so
        the end-of-round dump can never clobber the fault snapshot."""
        obs.configure(events_out=str(tmp_path / "ev.jsonl"))
        obs.emit(StepEvent(kind="decode"))
        path = obs.autodump("fault")
        assert path == str(tmp_path / "ev.fault.jsonl")
        assert (tmp_path / "ev.fault.jsonl").exists()
        assert obs.autodump_path("timeout") == str(
            tmp_path / "ev.timeout.jsonl"
        )
        # Unarmed: no dump.
        obs.configure(events_out="")
        assert obs.autodump("fault") is None

    def test_no_obs_disables_everything(self, monkeypatch, capsys):
        monkeypatch.setattr("sys.stdin", io.StringIO("# Spec"))
        code = cli.main(
            ["critique", "--models", "mock://critic", "--json", "--no-obs"]
        )
        out, _ = capsys.readouterr()
        assert code == 0
        perf = json.loads(out)["perf"]
        assert perf["obs"]["enabled"] is False
        assert perf["obs"]["recorder"]["recorded"] == 0
        assert perf["obs"]["events_by_type"] == {}


class TestBreakerEvents:
    def test_transitions_emit_events_and_metrics(self):
        from adversarial_spec_tpu.resilience.breaker import (
            OPEN,
            BreakerRegistry,
        )
        from adversarial_spec_tpu.resilience.faults import FaultKind

        obs.reset_stats()
        clock = [0.0]
        reg = BreakerRegistry(
            threshold=1, cooldown_s=5.0, clock=lambda: clock[0]
        )
        reg.record("tpu://m", ok=False, kind=FaultKind.OOM)
        assert reg.breaker("tpu://m").state == OPEN
        clock[0] = 5.0
        assert reg.allow("tpu://m")  # half-open probe
        reg.record("tpu://m", ok=True)  # closes
        transitions = [
            (e["frm"], e["to"])
            for e in obs.recorder.events()
            if e["type"] == "breaker" and e["model"] == "tpu://m"
        ]
        assert transitions == [
            ("closed", "open"),
            ("open", "half_open"),
            ("half_open", "closed"),
        ]
        snap = obs.metrics.snapshot()
        assert snap['advspec_breaker_transitions_total{to="open"}'] == 1
        assert snap['advspec_breaker_transitions_total{to="closed"}'] == 1


class TestHandoffTelemetry:
    """Disaggregation telemetry (fleet/handoff.py): ship/prefetch
    SwapEvents validate against the schema and the handoff ledger's
    surgery updates the counter + latency histogram exactly once."""

    def test_ship_and_prefetch_swap_events_validate(self):
        from adversarial_spec_tpu.obs.events import SwapEvent

        r = FlightRecorder(size=8)
        r.append(SwapEvent(op="ship", tier="disk", blocks=4, slot=0))
        r.append(SwapEvent(op="prefetch", tier="disk", blocks=4))
        for line in r.to_jsonl().splitlines():
            assert validate_event(json.loads(line)) == []
        bad = json.loads(r.to_jsonl().splitlines()[0])
        bad["op"] = "teleport"
        assert validate_event(bad)  # unknown swap op rejects

    def test_surgery_updates_counter_and_histogram_once(self):
        from adversarial_spec_tpu import fleet as fleet_mod
        from adversarial_spec_tpu import obs as obs_mod
        from adversarial_spec_tpu.fleet.handoff import HandoffLedger

        obs_mod.configure(enabled=True)
        fleet_mod.reset_stats()
        led = HandoffLedger(stats=fleet_mod.stats)
        led.begin("k", "r0", "r1")
        led.note_published("k", ["c1"], blocks=1)
        led._finish_adopt("k")
        led._finish_adopt("k")  # idempotent: no double count
        led.begin("k2", "r0", "r1")
        led._degrade("k2", "store_miss")
        snap = obs_mod.metrics.snapshot()
        assert snap['advspec_kv_handoff_total{outcome="adopted"}'] == 1
        assert snap['advspec_kv_handoff_total{outcome="degraded"}'] == 1
        assert snap["advspec_kv_handoff_seconds"]["count"] == 2
