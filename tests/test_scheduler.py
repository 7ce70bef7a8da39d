"""Continuous batching scheduler tests.

Correctness bar: every request's greedy output through the scheduler must
equal its output through plain generate() — admission order, slot reuse,
and co-residency with other sequences must never change tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine.generate import generate
from adversarial_spec_tpu.engine.scheduler import (
    ContinuousBatcher,
    SchedRequest,
)
from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    return params, cfg


@pytest.fixture(autouse=True)
def _spec_off(monkeypatch):
    """This module pins admission/interleave/slot semantics; speculation
    is default-on and would only multiply the jit programs every batcher
    here compiles (each distinct (B, cap) pair adds a γ-wide verify
    program). Spec-on coverage of these same paths — parity, slot
    churn, tp=2 — lives in tests/test_spec_batcher.py."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    monkeypatch.setenv("ADVSPEC_SPECULATIVE", "0")
    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)


def _reference(params, cfg, prompt, max_new):
    out = generate(
        params,
        cfg,
        [prompt],
        max_new_tokens=max_new,
        eos_ids=[],
        greedy=True,
        speculative=False,
    )
    return out.tokens[0, : out.n_generated[0]]


class TestContinuousBatcher:
    def test_single_request_matches_generate(self, tiny_model):
        params, cfg = tiny_model
        b = ContinuousBatcher(params, cfg, max_batch=2, max_new_cap=16)
        b.submit(SchedRequest(req_id=0, prompt_ids=[1, 5, 9], max_new_tokens=8))
        results = b.run_all()
        assert len(results) == 1
        ref = _reference(params, cfg, [1, 5, 9], 8)
        np.testing.assert_array_equal(results[0].tokens, np.asarray(ref))

    def test_more_requests_than_slots(self, tiny_model):
        """5 requests through 2 slots: queueing + slot reuse + co-residency
        must leave every output identical to its solo reference."""
        params, cfg = tiny_model
        prompts = [
            [1, 5, 9],
            [2, 6],
            [8, 8, 8, 4],
            [3],
            [7, 1, 4, 1, 5],
        ]
        budgets = [8, 5, 9, 4, 7]
        b = ContinuousBatcher(params, cfg, max_batch=2, max_new_cap=16)
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            b.submit(SchedRequest(req_id=i, prompt_ids=p, max_new_tokens=n))
        results = b.run_all()
        assert [r.req_id for r in results] == [0, 1, 2, 3, 4]
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            ref = _reference(params, cfg, p, n)
            np.testing.assert_array_equal(
                results[i].tokens, np.asarray(ref), err_msg=f"req {i}"
            )

    def test_different_budgets_finish_independently(self, tiny_model):
        params, cfg = tiny_model
        b = ContinuousBatcher(params, cfg, max_batch=3, max_new_cap=32)
        b.submit(SchedRequest(req_id=0, prompt_ids=[1, 2], max_new_tokens=2))
        b.submit(SchedRequest(req_id=1, prompt_ids=[3, 4], max_new_tokens=20))
        results = b.run_all()
        assert results[0].n_generated == 2
        assert results[1].n_generated == 20

    def test_eos_stops_row(self, tiny_model):
        params, cfg = tiny_model
        probe = _reference(params, cfg, [1, 2], 4)
        eos = int(probe[0])
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=32, eos_ids=[eos]
        )
        b.submit(SchedRequest(req_id=0, prompt_ids=[1, 2], max_new_tokens=30))
        results = b.run_all()
        n = results[0].n_generated
        assert n < 30
        assert int(results[0].tokens[n - 1]) == eos

    def test_pages_recycled_across_requests(self, tiny_model):
        """Sequential requests through one slot must free and reuse pages
        (allocator returns to full free count at drain)."""
        params, cfg = tiny_model
        b = ContinuousBatcher(
            params, cfg, max_batch=1, max_new_cap=8, capacity_tokens=512
        )
        total_pages = b.allocator.free_pages
        for i in range(4):
            b.submit(
                SchedRequest(req_id=i, prompt_ids=[1 + i], max_new_tokens=4)
            )
        results = b.run_all()
        assert len(results) == 4
        assert b.allocator.free_pages == total_pages

    def test_cap_violation_rejected(self, tiny_model):
        params, cfg = tiny_model
        b = ContinuousBatcher(params, cfg, max_batch=1, max_new_cap=8)
        with pytest.raises(ValueError, match="exceeds scheduler"):
            b.submit(
                SchedRequest(req_id=0, prompt_ids=[1], max_new_tokens=99)
            )

    def test_oversized_request_rejected_at_submit(self, tiny_model):
        params, cfg = tiny_model
        b = ContinuousBatcher(
            params, cfg, max_batch=1, max_new_cap=64, capacity_tokens=128
        )
        with pytest.raises(ValueError, match="pool holds only"):
            b.submit(
                SchedRequest(
                    req_id=0, prompt_ids=[1] * 100, max_new_tokens=64
                )
            )

    def test_full_pool_defers_admission(self, tiny_model):
        """Two slots, pool sized for ~one resident: the second request
        must wait for the first to finish (deferred, not crashed) and
        still produce its exact reference output."""
        params, cfg = tiny_model
        # Prompt buckets to 128; 128+8=136 tokens → 3 pages of 64. Pool of
        # 4 pages fits one resident but not two.
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=2,
            max_new_cap=8,
            page_size=64,
            capacity_tokens=256,
        )
        b.submit(SchedRequest(req_id=0, prompt_ids=[1, 5], max_new_tokens=8))
        b.submit(SchedRequest(req_id=1, prompt_ids=[2, 6], max_new_tokens=8))
        results = b.run_all()
        assert len(results) == 2
        for i, p in enumerate([[1, 5], [2, 6]]):
            ref = _reference(params, cfg, p, 8)
            np.testing.assert_array_equal(results[i].tokens, np.asarray(ref))


class TestPagedUnderDp:
    """Paged decode over a dp-sharded mesh: per-device page pools,
    device-local tables, zero cross-device page traffic (paged no
    longer excludes multi-device)."""

    @pytest.fixture(autouse=True)
    def _needs_8_devices(self):
        if len(jax.devices()) < 8:
            pytest.skip("requires 8 virtual devices")

    @pytest.mark.parametrize("n_prompts", [4, 3])
    def test_paged_dp_matches_single_device(self, n_prompts):
        """Greedy paged decode on dp=4 (with dp-padding for 3 prompts)
        must reproduce single-device paged tokens exactly."""
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1 + i, 5, 9, 3 + i] for i in range(n_prompts)]
        kw = dict(
            max_new_tokens=6, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({})  # all 8 devices on dp
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(sharded, cfg, prompts, mesh=mesh, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)
        np.testing.assert_array_equal(ref.n_generated, out.n_generated)


def _spy_dispatches(sched_mod, calls):
    """Wrap the dispatch entry points with call-order spies; returns the
    originals for restoration. The speculative siblings map onto the
    same letters — "D" is a decode-side program (token-at-a-time or
    draft+verify), "F" is a fused ride (either flavor) — so the
    interleave properties hold under whatever the speculation default
    is."""
    real_prefill = sched_mod.prefill_chunk
    real_decode = sched_mod.scheduler_decode_chunk
    real_fused = sched_mod.fused_prefill_decode_chunk
    real_spec = sched_mod.scheduler_spec_chunk
    real_fused_spec = sched_mod.fused_prefill_spec_chunk

    def spy_prefill(*a, **kw):
        calls.append("P")
        return real_prefill(*a, **kw)

    def spy_decode(*a, **kw):
        calls.append("D")
        return real_decode(*a, **kw)

    def spy_fused(*a, **kw):
        calls.append("F")
        return real_fused(*a, **kw)

    def spy_spec(*a, **kw):
        calls.append("D")
        return real_spec(*a, **kw)

    def spy_fused_spec(*a, **kw):
        calls.append("F")
        return real_fused_spec(*a, **kw)

    sched_mod.prefill_chunk = spy_prefill
    sched_mod.scheduler_decode_chunk = spy_decode
    sched_mod.fused_prefill_decode_chunk = spy_fused
    sched_mod.scheduler_spec_chunk = spy_spec
    sched_mod.fused_prefill_spec_chunk = spy_fused_spec
    return (
        real_prefill,
        real_decode,
        real_fused,
        real_spec,
        real_fused_spec,
    )


class TestChunkedPrefillInterleave:
    """Admission prefill does not pause decode: a multi-chunk prompt's
    chunks ride INSIDE the residents' decode program (the fused step)."""

    def _workload(self, params, cfg, **kw):
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=64, chunk=8, **kw
        )
        long_prompt = [((i * 11) % 500) + 3 for i in range(600)]
        b.submit(
            SchedRequest(req_id=0, prompt_ids=[1, 5, 9],
                         max_new_tokens=64)
        )
        b.submit(
            SchedRequest(req_id=1, prompt_ids=long_prompt,
                         max_new_tokens=8)
        )
        return b, long_prompt

    def test_admission_chunks_ride_fused_with_decode(self, tiny_model):
        import adversarial_spec_tpu.engine.scheduler as sched_mod

        params, cfg = tiny_model
        calls = []
        real = _spy_dispatches(sched_mod, calls)
        try:
            b, long_prompt = self._workload(params, cfg)
            results = b.run_all()
        finally:
            (
                sched_mod.prefill_chunk,
                sched_mod.scheduler_decode_chunk,
                sched_mod.fused_prefill_decode_chunk,
                sched_mod.scheduler_spec_chunk,
                sched_mod.fused_prefill_spec_chunk,
            ) = real

        assert len(results) == 2
        s = "".join(calls)
        # The 600-token prompt's multi-chunk prefill must ride the
        # resident row's decode program — fused dispatches, not
        # standalone prefills between decode chunks.
        assert "F" in s, f"no fused prefill+decode step: {s}"
        # The fused steps carry the admission: no standalone decode may
        # run between two standalone prefills while it is in flight.
        assert "PDP" not in s, f"admission stalled decode: {s}"
        # Fusion must not change tokens (row independence).
        ref0 = _reference(params, cfg, [1, 5, 9], 64)
        ref1 = _reference(params, cfg, long_prompt, 8)
        np.testing.assert_array_equal(results[0].tokens, np.asarray(ref0))
        np.testing.assert_array_equal(results[1].tokens, np.asarray(ref1))
        # Telemetry: the ride-along chunks were accounted as overlapped.
        assert b.overlapped_prefill_s > 0
        assert b.prefill_time_s == (
            b.stalled_prefill_s + b.overlapped_prefill_s
        )

    def test_slot_reuse_mid_flight_does_not_truncate(self, tiny_model):
        """Regression: a step dispatched while slot s ran request A,
        fetched AFTER s was freed and re-admitted to request B, must not
        apply A's completion flag to B (the per-slot generation guard).
        Mixed lengths/budgets force exactly that slot churn; every row
        must still emit its full reference output."""
        params, cfg = tiny_model
        prompts = [
            [((i * 13 + j * 7) % 500) + 3 for j in range(296 if i % 2 == 0 else 5)]
            for i in range(6)
        ]
        budgets = [8 if i % 2 == 0 else 24 for i in range(6)]
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=32, chunk=8,
            prefix_cache=False,
        )
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            b.submit(SchedRequest(req_id=i, prompt_ids=p, max_new_tokens=n))
        results = b.run_all()
        assert [r.req_id for r in results] == list(range(6))
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            ref = _reference(params, cfg, p, n)
            assert results[i].n_generated == len(ref), f"req {i} truncated"
            np.testing.assert_array_equal(
                results[i].tokens, np.asarray(ref), err_msg=f"req {i}"
            )

    def test_prefill_time_telemetry_accumulates(self, tiny_model):
        params, cfg = tiny_model
        b = ContinuousBatcher(params, cfg, max_batch=1, max_new_cap=8)
        b.submit(SchedRequest(req_id=0, prompt_ids=[2, 4, 6],
                              max_new_tokens=4))
        b.run_all()
        assert b.prefill_time_s > 0
        assert b.decode_time_s > 0
        assert b.prefill_time_s == (
            b.stalled_prefill_s + b.overlapped_prefill_s
        )

    @pytest.mark.parametrize(
        "kind", ["decode", "fused", "spec", "fused_spec"]
    )
    def test_account_step_contract(self, tiny_model, kind):
        """The one place a retired step's wall clock is booked: the
        fused share goes to the overlapped-prefill bucket and to the
        riding admission, the rest to ``decode_time_s`` in even per-slot
        shares, and the StepEvent of that kind carries the riding
        admission's slot and span, or none."""
        from types import SimpleNamespace

        from adversarial_spec_tpu import obs

        params, cfg = tiny_model
        spec, fused = kind.endswith("spec"), kind.startswith("fused")
        b = ContinuousBatcher(
            params, cfg, max_batch=4, max_new_cap=16, chunk=8,
            speculative=spec, gamma=3,
        )
        adm = SimpleNamespace(
            slot=3,
            prefill_s=0.0,
            req=SimpleNamespace(span_id="span-7", trace_id="trace-7"),
        )
        width = 4 if spec else 8
        live = [0, 2]
        share = 64 / (64 + len(live) * width) if fused else 0.0
        obs.reset_stats()
        b._account_step(
            0.5,
            live,
            width,
            adm if fused else None,
            64,  # sized for a ride every iteration; counted only on one
            1,
            "spec_counts" if spec else "",
        )
        assert b.overlapped_prefill_s == pytest.approx(0.5 * share)
        assert adm.prefill_s == pytest.approx(0.5 * share)
        assert b.stalled_prefill_s == 0.0
        assert b.decode_time_s == pytest.approx(0.5 * (1.0 - share))
        assert b._slot_decode_s[0] == b._slot_decode_s[2] > 0.0
        assert sum(b._slot_decode_s) == pytest.approx(b.decode_time_s)
        assert b._slot_decode_s[1] == b._slot_decode_s[3] == 0.0
        snap = obs.metrics.snapshot()["advspec_step_wall_seconds"]
        assert snap["count"] == 1 and snap["sum"] == pytest.approx(0.5)
        (ev,) = [e for e in obs.recorder.events() if e["type"] == "step"]
        assert ev["kind"] == kind
        assert ev["n_live"] == 2 and ev["decode_chunk"] == width
        assert ev["pipeline_depth"] == 1
        assert ev["sync_reason"] == ("spec_counts" if spec else "")
        assert ev["admission_slot"] == (3 if fused else -1)
        assert ev["prefill_tokens"] == (64 if fused else 0)
        assert ev["span_id"] == ("span-7" if fused else "")

    @pytest.mark.parametrize("depth", [1, 2])
    def test_a_verify_steps_wall_clock_is_booked_once(
        self, tiny_model, monkeypatch, depth
    ):
        """One deep and two deep: the retired steps' walls do not
        overlap (they sum to under the drain's wall clock with the
        prefill beside them), every second booked to decode is on some
        request's result, and a step that was enqueued behind another
        says so in its StepEvent (``pipeline_depth`` 2), as many as the
        counter ``spec.pipelined_steps`` has rows of."""
        import time

        import adversarial_spec_tpu.engine.scheduler as sched_mod
        from adversarial_spec_tpu import obs
        from adversarial_spec_tpu.engine import spec as spec_mod

        monkeypatch.setattr(sched_mod, "_PIPELINE_DEPTH", depth)
        params, cfg = tiny_model
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=24, chunk=8,
            speculative=True, gamma=3,
        )
        prompts = [[5 + i % 7 for i in range(30)], [3 + i % 5 for i in range(600)]]
        for i, p in enumerate(prompts):
            b.submit(SchedRequest(req_id=i, prompt_ids=p, max_new_tokens=24))
        obs.reset_stats()
        spec_mod.reset_stats()
        t0 = time.monotonic()
        results = b.run_all()
        wall = time.monotonic() - t0
        assert sum(r.decode_time_s for r in results) == pytest.approx(
            b.decode_time_s
        )
        assert 0 < b.decode_time_s + b.prefill_time_s <= wall
        steps = [e for e in obs.recorder.events() if e["type"] == "step"
                 and e["kind"] in ("spec", "fused_spec")]
        assert {e["kind"] for e in steps} == {"spec", "fused_spec"}
        snap = obs.metrics.snapshot()["advspec_step_wall_seconds"]
        assert snap["count"] == len(steps)
        # (the snapshot rounds a sum to the microsecond)
        assert snap["sum"] == pytest.approx(
            b.decode_time_s + b.overlapped_prefill_s, abs=1e-6
        )
        deep = [e for e in steps if e["pipeline_depth"] == 2]
        assert {e["pipeline_depth"] for e in steps} <= {1, 2}
        assert bool(deep) == (depth == 2)
        pipelined = spec_mod.stats.pipelined_steps
        assert bool(pipelined) == (depth == 2)
        assert pipelined <= sum(e["n_live"] for e in deep)

    def test_a_deadline_keeps_what_the_steps_in_flight_emitted(
        self, tiny_model
    ):
        """At the drain's deadline the verify steps in flight are
        retired before the rows resolve: a result holds every token its
        consumer was shown and is a prefix of the undisturbed reply."""
        import time

        params, cfg = tiny_model
        prompts = [[5 + i % 7 for i in range(30)], [4 + i % 6 for i in range(41)]]
        seen = {0: [], 1: []}

        def serve(timeout_s, pause):
            b = ContinuousBatcher(
                params, cfg, max_batch=2, max_new_cap=48,
                speculative=True, gamma=3,
            )

            def consumer(i):
                def on_tokens(tokens):
                    seen[i] = tokens.tolist()
                    time.sleep(pause)
                    return True

                return on_tokens

            for i, p in enumerate(prompts):
                b.submit(
                    SchedRequest(
                        req_id=i, prompt_ids=p, max_new_tokens=48,
                        on_tokens=consumer(i),
                    )
                )
            return {r.req_id: r for r in b.run_all(timeout_s)}

        whole = serve(0.0, 0.0)  # also compiles every program
        assert all(r.n_generated == 48 for r in whole.values())
        cut = serve(0.6, 0.05)
        for i, r in cut.items():
            got = r.tokens[: r.n_generated].tolist()
            assert 0 < r.n_generated < 48 and r.error is None
            assert got == whole[i].tokens[: r.n_generated].tolist()
            assert got == seen[i]

    @pytest.mark.parametrize(
        "knob", [{"interleave": False}, {"pipeline_depth": 1}]
    )
    def test_drive_loop_knobs_are_gone(self, tiny_model, knob):
        """One drive loop: the constructor takes no switch for it."""
        params, cfg = tiny_model
        with pytest.raises(TypeError):
            ContinuousBatcher(params, cfg, max_batch=2, **knob)


class TestBatcherInt8Pool:
    def test_int8_pool_matches_int8_reference(self, tiny_model):
        """ContinuousBatcher with kv_dtype=int8: output must match the
        round-synchronous int8 dense-cache generate() for each request."""
        params, cfg = tiny_model
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=16, kv_dtype="int8"
        )
        assert "ks" in b.pool
        b.submit(SchedRequest(req_id=0, prompt_ids=[1, 5, 9], max_new_tokens=8))
        results = b.run_all()
        ref = generate(
            params,
            cfg,
            [[1, 5, 9]],
            max_new_tokens=8,
            eos_ids=[],
            greedy=True,
            speculative=False,
            kv_dtype="int8",
        )
        np.testing.assert_array_equal(
            results[0].tokens,
            np.asarray(ref.tokens[0, : ref.n_generated[0]]),
        )


class TestPagedUnderTp:
    def test_paged_tp_matches_single_device(self, tiny_model):
        """Paged decode on a tp-only mesh (head-sharded global pool, the
        fused kernel under shard_map in interpret mode) must reproduce
        single-device paged tokens."""
        if len(jax.devices()) < 2:
            pytest.skip("requires 2 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model  # n_kv_heads=2 → tp=2 divides
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            # Exercise the shard_mapped KERNEL (interpret on CPU), not
            # just the GSPMD gather path.
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)
        # And the gather path for completeness.
        with mesh:
            out2 = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=False, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out2.tokens)

    def test_paged_tp_int8_pool(self, tiny_model):
        """int8 pages compose with the tp-sharded pool."""
        if len(jax.devices()) < 2:
            pytest.skip("requires 2 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8]]
        kw = dict(
            max_new_tokens=6, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False, kv_dtype="int8",
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_paged_mixed_dp_tp_matches_single_device(self, tiny_model):
        """Paged decode on a MIXED dp=2×tp=2 mesh (per-dp-slice pool
        layout, GSPMD chunk loop, kernel under the dp×tp shard_map with
        global→local id shift) must reproduce single-device paged
        tokens — on both the kernel and gather paths."""
        if len(jax.devices()) < 4:
            pytest.skip("requires 4 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8], [6, 1, 1, 2], [9, 9]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"dp": 2, "tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)
        with mesh:
            out2 = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=False, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out2.tokens)

    def test_paged_mixed_dp_tp_int8_pool(self, tiny_model):
        """int8 pages compose with the mixed dp×tp pool."""
        if len(jax.devices()) < 4:
            pytest.skip("requires 4 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8], [6, 1, 1, 2], [9, 9]]
        kw = dict(
            max_new_tokens=6, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False, kv_dtype="int8",
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"dp": 2, "tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_paged_sp_only_matches_single_device(self, tiny_model):
        """Paged decode on an sp-only mesh: sp is a prefill axis — during
        decode it idles/replicates (pool replicated, same semantics as the
        dense decode path after reshard_cache_for_decode) — so paged
        tokens must reproduce single-device paged tokens. Exercises the
        sp_prefill → reshard → page-migration handoff (the 16k-context
        config's paged decode)."""
        if len(jax.devices()) < 2:
            pytest.skip("requires 2 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8], [6, 1, 1, 2], [9, 9]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"sp": 2, "tp": 1}, devices=jax.devices()[:2])
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)
        with mesh:
            out2 = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=False, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out2.tokens)

    def test_paged_sp_tp_int8_pool(self, tiny_model):
        """Paged + int8 pages on an sp×tp mesh (heads over tp, pool
        replicated over sp; int8 quantization happens at the sp→decode
        reshard boundary before page migration)."""
        if len(jax.devices()) < 4:
            pytest.skip("requires 4 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model  # n_kv_heads=2 → tp=2 divides
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8]]
        kw = dict(
            max_new_tokens=6, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False, kv_dtype="int8",
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"sp": 2, "tp": 2}, devices=jax.devices()[:4])
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_paged_dp_sp_mixed_matches_single_device(self, tiny_model):
        """Paged decode on a dp×sp mesh reuses the per-dp-slice mixed
        layout (rows + page slabs over dp, sp replicated during decode)."""
        if len(jax.devices()) < 4:
            pytest.skip("requires 4 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8], [6, 1, 1, 2], [9, 9]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"dp": 2, "sp": 2, "tp": 1})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)
        with mesh:
            out2 = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=False, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out2.tokens)

    def test_paged_tp_not_dividing_heads_falls_back_dense(
        self, tiny_model, capsys
    ):
        """tp ∤ n_kv_heads warns + refuses paged BEFORE touching pool
        layout. The dense fallback then hits the same divisibility wall
        in its own cache sharding (dense KV heads shard over tp too), so
        pin that SPECIFIC ValueError — a blanket except would also pass
        if the fallback path crashed some new way after the warning
        (ADVICE r5)."""
        if len(jax.devices()) < 8:
            pytest.skip("requires 8 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh

        params, cfg = tiny_model  # n_kv_heads=2; tp=8 does not divide
        mesh = make_mesh({"dp": 1, "sp": 1, "tp": 8})
        from adversarial_spec_tpu.engine import generate as G

        prompts = [[1, 5, 9], [2, 6]]
        with pytest.raises(ValueError, match="partitioned"):
            with mesh:
                G.generate(
                    params, cfg, prompts, mesh=mesh,
                    max_new_tokens=2, eos_ids=[], greedy=True,
                    paged=True, speculative=False,
                )
        # The paged eligibility check rejected (and warned) before any
        # pool layout work; the error above came from the dense cache.
        assert "falling back to the dense cache" in capsys.readouterr().err

    @pytest.mark.slow
    def test_paged_sp_long_prompt_multi_page(self, tiny_model):
        """sp paged at a ~1.5k-token prompt: the page table spans ~100
        pages per row and the sp_prefill → reshard → migration handoff
        moves every prompt slot (gather path keeps CPU cost sane; the
        kernel path is pinned at small scale above)."""
        if len(jax.devices()) < 2:
            pytest.skip("requires 2 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(3, cfg.vocab_size, 1500).tolist(),
            rng.integers(3, cfg.vocab_size, 900).tolist(),
        ]
        kw = dict(
            max_new_tokens=4, eos_ids=[], greedy=True,
            paged=True, page_size=16, speculative=False,
            share_prefix=False, use_pallas_decode=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"sp": 2, "tp": 1}, devices=jax.devices()[:2])
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(sharded, cfg, prompts, mesh=mesh, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)
