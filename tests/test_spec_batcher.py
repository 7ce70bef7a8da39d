"""Per-slot prompt-lookup speculation on the paged serving path.

Correctness contracts (ISSUE 6):
- greedy output through the ContinuousBatcher is BYTE-IDENTICAL spec-on
  vs spec-off — across tp=1 and tp=2 meshes, prefix cache on and off,
  and every draft width γ (acceptance only changes how many tokens emerge per device program,
  never which tokens);
- the page pool survives rollback: ``check_invariants`` holds after
  EVERY speculative step, rejected draft pages return to the pool, and
  pages shared with the prefix cache only lose the speculating row's
  reference;
- a fault mid-verify evicts ONLY the speculating slot, frees both its
  committed and in-flight draft pages, and the auto-dumped flight
  recorder JSONL reconstructs the eviction;
- the γ knob lives in engine/spec.py (process config, CLI ``--gamma``),
  reconfigurable without a reimport, validated at the knob.
"""

import functools
import io
import json
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine import spec as spec_mod
from adversarial_spec_tpu.engine.generate import generate
from adversarial_spec_tpu.engine.kvcache import PageAllocator
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
def _spec_defaults():
    """Every test starts from the process defaults and leaves them."""
    spec_mod.configure(enabled=True, gamma=spec_mod.DEFAULT_GAMMA)
    spec_mod.reset_stats()
    yield
    spec_mod.configure(enabled=True, gamma=spec_mod.DEFAULT_GAMMA)
    spec_mod.reset_stats()


def _repetitive_prompt(n, period=7, lo=5):
    """Tiled token pattern: recurring bigrams for prompt-lookup to
    draft from (the [SPEC] revision shape — near-copies of earlier
    context)."""
    return [lo + (i % period) for i in range(n)]


def _drain(params, cfg, prompts, budgets, *, eos=(), **kw):
    timeout_s = kw.pop("timeout_s", 0.0)
    b = ContinuousBatcher(
        params,
        cfg,
        max_batch=kw.pop("max_batch", 2),
        max_new_cap=max(budgets),
        eos_ids=list(eos),
        **kw,
    )
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        b.submit(
            SchedRequest(req_id=i, prompt_ids=list(p), max_new_tokens=n)
        )
    results = b.run_all(timeout_s)
    return b, {r.req_id: r.tokens.tolist() for r in results}, results


class TestSpecConfig:
    def test_gamma_validated_at_the_knob(self):
        with pytest.raises(ValueError, match="ADVSPEC_GAMMA must be >= 1"):
            spec_mod.configure(gamma=0)

    def test_configure_retunes_without_reimport(self):
        spec_mod.configure(gamma=3, enabled=False)
        assert spec_mod.config().gamma == 3
        assert spec_mod.config().enabled is False
        snap = spec_mod.snapshot()
        assert snap["gamma"] == 3 and snap["enabled"] is False

    def test_env_gamma_validated(self, monkeypatch):
        monkeypatch.setenv("ADVSPEC_GAMMA", "0")
        with pytest.raises(ValueError, match="ADVSPEC_GAMMA must be >= 1"):
            spec_mod.env_gamma()

    def test_speculative_module_snapshot_constant(self):
        # The dense path's import-time GAMMA snapshot still validates
        # (it IS env_gamma at import) and stays an int ≥ 1.
        from adversarial_spec_tpu.engine.speculative import GAMMA

        assert GAMMA >= 1

    def test_reenable_reclamps_gamma_vs_cap(self, tiny_model):
        """Review regression: reconfigure_speculative(enabled=True) on a
        batcher the constructor degraded to plain decode (cap <= 1
        leaves γ unclamped) must re-walk the γ-vs-cap clamp instead of
        re-arming speculation with a span wider than the output
        buffer."""
        params, cfg = tiny_model
        b = ContinuousBatcher(
            params, cfg, max_batch=1, max_new_cap=1,
            speculative=True, gamma=8,
        )
        assert b.speculative is False
        b.reconfigure_speculative(enabled=True)
        assert b.speculative is False, "1-token cap cannot fit a span"

    def test_dense_generate_follows_process_config(
        self, tiny_model, monkeypatch
    ):
        """Review regression: dense generate() used to read
        ADVSPEC_SPECULATIVE from the env directly and freeze γ at
        import, so CLI --no-speculative/--gamma (which only call
        spec.configure()) never reached the dense fallback path."""
        import adversarial_spec_tpu.engine.speculative as sp_mod

        params, cfg = tiny_model
        real = sp_mod.speculative_decode_steps
        seen_gammas = []

        def spy(*a, **k):
            seen_gammas.append(k.get("gamma"))
            return real(*a, **k)

        monkeypatch.setattr(sp_mod, "speculative_decode_steps", spy)
        prompt = _repetitive_prompt(24)
        kw = dict(max_new_tokens=16, eos_ids=[], greedy=True)
        spec_mod.configure(enabled=False)
        off = generate(params, cfg, [prompt], **kw)
        assert not seen_gammas, "configure(enabled=False) must reach it"
        spec_mod.configure(enabled=True, gamma=4)
        on = generate(params, cfg, [prompt], **kw)
        assert seen_gammas == [4], "configure(gamma=) must reach it"
        np.testing.assert_array_equal(on.tokens, off.tokens)

    def test_reconfigure_refuses_resident_rows(self, tiny_model):
        params, cfg = tiny_model
        b = ContinuousBatcher(params, cfg, max_batch=1, max_new_cap=4)
        b._slot_req[0] = SchedRequest(
            req_id=0, prompt_ids=[1], max_new_tokens=1
        )
        with pytest.raises(RuntimeError, match="resident rows"):
            b.reconfigure_speculative(enabled=False)

    def test_reconfigure_between_drains(self, tiny_model):
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(40)]
        b, toks1, _ = _drain(
            params, cfg, prompts, [16], max_batch=1, speculative=True,
            gamma=4,
        )
        b.reconfigure_speculative(enabled=False)
        for i, p in enumerate(prompts):
            b.submit(
                SchedRequest(req_id=i, prompt_ids=p, max_new_tokens=16)
            )
        results2 = b.run_all()
        toks2 = {r.req_id: r.tokens.tolist() for r in results2}
        assert toks1 == toks2  # greedy parity across the flip
        # Review regression: the handoff must reset the slot's spec
        # telemetry even with speculation now OFF — round 2's results
        # must not inherit round 1's counts ('all zero with
        # --no-speculative').
        assert all(r.spec_steps == 0 for r in results2)
        assert all(r.spec_drafted == 0 for r in results2)


class TestBatcherSpecParity:
    def test_spec_on_off_greedy_parity_with_acceptance(self, tiny_model):
        # max_batch=2 with 4 requests: co-residency AND queue churn,
        # on the (B=2, cap=16, γ=4) program shape every parity test in
        # this class shares (cap/B are static args — each distinct pair
        # compiles a fresh verify program).
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(60 + i) for i in range(4)]
        budgets = [16] * 4
        spec_mod.reset_stats()
        _, on, _ = _drain(
            params, cfg, prompts, budgets, max_batch=2,
            speculative=True, gamma=4,
        )
        stats = spec_mod.stats
        assert stats.spec_steps > 0
        assert stats.accepted_tokens > 0, "workload must exercise accepts"
        assert stats.emitted_tokens > stats.spec_steps  # >1 token/step
        _, off, _ = _drain(
            params, cfg, prompts, budgets, max_batch=2, speculative=False,
        )
        assert on == off

    @pytest.mark.slow  # batcher-vs-dense is also pinned (cheaper) by
    # test_gamma_clamps_to_output_cap and the slot-churn test
    def test_matches_dense_generate_reference(self, tiny_model):
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(48), _repetitive_prompt(31)]
        _, on, _ = _drain(
            params, cfg, prompts, [16, 16], speculative=True, gamma=4,
        )
        for i, p in enumerate(prompts):
            ref = generate(
                params, cfg, [p], max_new_tokens=16, eos_ids=[],
                greedy=True, speculative=False,
            )
            np.testing.assert_array_equal(
                on[i], ref.tokens[0, : ref.n_generated[0]],
                err_msg=f"req {i}",
            )

    def test_parity_with_prefix_cache(self, tiny_model):
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(80)] * 2  # identical → shared blocks
        kw = dict(speculative=True, gamma=4, page_size=16)
        _, cached, r1 = _drain(
            params, cfg, prompts, [16, 16], prefix_cache=True, **kw
        )
        _, plain, _ = _drain(
            params, cfg, prompts, [16, 16], prefix_cache=False, **kw
        )
        assert cached == plain
        assert r1[1].cached_tokens > 0  # the cache actually engaged

    @pytest.mark.slow  # full sharded-program compile set; the cheaper
    # dp:1 mesh pin below keeps the on-mesh jit-signature class in
    # tier-1
    def test_tp2_mesh_parity(self, tiny_model):
        if len(jax.devices()) < 2:
            pytest.skip("requires 2 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        prompts = [_repetitive_prompt(50), _repetitive_prompt(50 + 1)]
        _, ref, _ = _drain(
            params, cfg, prompts, [16, 16], speculative=False,
        )
        mesh = make_mesh({"tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            _, out, _ = _drain(
                sharded, cfg, prompts, [16, 16], speculative=True, gamma=4,
            )
        assert ref == out

    def test_verify_program_compiles_once_on_mesh(self, tiny_model):
        """Verify-drive regression: with mesh-committed params, the
        batcher's fresh (uncommitted) row-state arrays and step 1's
        mesh-committed donated outputs used to present two jit
        signatures for the same verify program — XLA compiled
        scheduler_spec_chunk twice on the engine's first paged spec
        drive (ctx_len/prev_tok/cur_len/n_emitted/active flipped
        UnspecifiedValue → NamedSharding between steps). Row state is
        now committed at creation; the retrace watch must see no
        seen-key recompile."""
        from adversarial_spec_tpu import obs
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        params, cfg = tiny_model
        mesh = make_mesh({"dp": 1})
        sharded = shard_params(mesh, params)
        was_enabled = obs.config().enabled
        obs.configure(enabled=True)
        obs.retrace.clear()
        try:
            with mesh:
                # Minimal shapes: the pin is about jit SIGNATURES
                # (≥2 spec steps on mesh-sharded params), not workload.
                _drain(
                    sharded, cfg, [_repetitive_prompt(24)], [8],
                    max_batch=1, speculative=True, gamma=4,
                )
        finally:
            snap = obs.retrace.snapshot()
            obs.retrace.clear()
            obs.configure(enabled=was_enabled)
        spec_progs = {
            k: v for k, v in snap["programs"].items() if "spec" in k
        }
        assert spec_progs, "no speculative program dispatched"
        assert snap["unexpected_recompiles"] == 0, snap

    def test_gamma_sweep_parity(self, tiny_model):
        """Every draft width compiles its own verify program; none may
        change greedy tokens."""
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(44)]
        outs = {}
        for gamma in (1, 3, 8):
            _, outs[gamma], _ = _drain(
                params, cfg, prompts, [16], max_batch=1,
                speculative=True, gamma=gamma,
            )
        assert outs[1] == outs[3] == outs[8]

    def test_eos_parity_inside_span(self, tiny_model):
        """An EOS landing inside an accepted span must stop the row at
        the same token plain decode stops at."""
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(40)]
        _, probe, _ = _drain(
            params, cfg, prompts, [16], max_batch=1, speculative=False,
        )
        out = probe[0]
        if len(out) < 4:
            pytest.skip("probe output too short to pick a mid-run EOS")
        eos = out[len(out) // 2]
        kw = dict(max_batch=1, eos=[eos])
        _, on, _ = _drain(
            params, cfg, prompts, [16], speculative=True, gamma=4, **kw
        )
        _, off, _ = _drain(
            params, cfg, prompts, [16], speculative=False, **kw
        )
        assert on == off
        assert on[0][-1] == eos  # EOS kept, nothing after

    def test_gamma_clamps_to_output_cap(self, tiny_model):
        """Regression: max_new_cap smaller than γ+1 used to push the
        spec chunk's masked append window start negative, smashing the
        row's first tokens (found by the prefix-cache replay test's
        max_new_cap=8 batcher under the default γ=8). γ must clamp so
        the span fits the buffer; a 1-token cap degrades to plain
        decode."""
        params, cfg = tiny_model
        prompt = [((i * 7) % 400) + 3 for i in range(96)]
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=8,
            speculative=True, gamma=8,
        )
        assert b.gamma == 7
        b.submit(
            SchedRequest(req_id=0, prompt_ids=list(prompt),
                         max_new_tokens=8)
        )
        [res] = b.run_all()
        ref = generate(
            params, cfg, [prompt], max_new_tokens=8, eos_ids=[],
            greedy=True, speculative=False,
        )
        np.testing.assert_array_equal(
            res.tokens, ref.tokens[0, : ref.n_generated[0]]
        )
        tiny = ContinuousBatcher(
            params, cfg, max_batch=1, max_new_cap=1,
            speculative=True, gamma=8,
        )
        assert tiny.speculative is False

    def test_sched_result_carries_spec_counts(self, tiny_model):
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(48)]
        _, _, results = _drain(
            params, cfg, prompts, [16], max_batch=1,
            speculative=True, gamma=4,
        )
        r = results[0]
        assert r.spec_steps > 0
        assert r.spec_drafted >= r.spec_accepted >= 0
        _, _, results = _drain(
            params, cfg, prompts, [16], max_batch=1, speculative=False,
        )
        assert results[0].spec_steps == 0
        assert results[0].spec_drafted == 0


class TestSpecRollback:
    def test_truncate_releases_tail_pages(self):
        a = PageAllocator(8, 4)
        a.new_sequence(0)
        a.extend(0, 10)  # 3 pages
        assert a.free_pages == 5
        released = a.truncate(0, 5)  # keep 2 pages
        assert len(released) == 1
        assert a.length(0) == 5
        assert a.covered_tokens(0) == 8
        assert a.free_pages == 6
        a.check_invariants()

    def test_truncate_validates_bounds(self):
        a = PageAllocator(8, 4)
        a.new_sequence(0)
        a.extend(0, 6)
        with pytest.raises(ValueError):
            a.truncate(0, 7)
        with pytest.raises(ValueError):
            a.truncate(0, -1)

    def test_truncate_shared_page_keeps_cache_ref(self):
        """A draft tail page shared with the prefix cache loses only the
        sequence's hold — the copy-on-append boundary."""
        a = PageAllocator(8, 4)
        a.new_sequence(0)
        a.extend(0, 8)  # 2 pages
        tail = a.table(0)[1]
        a.cache_ref(tail)  # the cache holds the tail block too
        released = a.truncate(0, 4)
        assert released == [tail]
        assert a.refcount(tail) == 1  # cache hold survives
        assert a.free_pages == 6  # NOT back on the free list
        a.check_invariants()
        a.cache_unref(tail)
        assert a.free_pages == 7

    def test_rollback_happens_with_small_pages(self, tiny_model):
        """γ spanning multiple small pages: rejected drafts must release
        pages (rolled_back_pages > 0) and the pool must stay clean."""
        params, cfg = tiny_model
        spec_mod.reset_stats()
        # Same (B=2, cap=16, γ=7, page=4) shape as the fuzz's third
        # trial, so the verify program compiles once for both tests.
        b, _, results = _drain(
            params, cfg, [_repetitive_prompt(41)], [16], max_batch=2,
            speculative=True, gamma=7, page_size=4, prefix_cache=False,
            capacity_tokens=512,
        )
        assert all(r.error is None for r in results)
        assert spec_mod.stats.rolled_back_pages > 0
        b.allocator.check_invariants()
        assert b.allocator.free_pages == b.allocator.n_pages

    def test_invariants_after_every_spec_step_fuzz(
        self, tiny_model, monkeypatch
    ):
        """THE acceptance pin: check_invariants after EVERY speculative
        step (the instant the rollback ran), over a randomized workload
        with small pages, pool pressure, and the prefix cache engaged."""
        params, cfg = tiny_model
        checked = {"n": 0}
        orig = ContinuousBatcher._apply_spec_counts

        def checked_apply(self, counts_np, live_slots):
            orig(self, counts_np, live_slots)
            self.allocator.check_invariants()
            checked["n"] += 1

        monkeypatch.setattr(
            ContinuousBatcher, "_apply_spec_counts", checked_apply
        )
        rng = random.Random(0xD1CE)
        for trial in range(3):
            prompts = [
                _repetitive_prompt(
                    rng.randrange(20, 70), period=rng.randrange(3, 9)
                )
                for _ in range(4)
            ]
            # cap = max(budgets) is a STATIC jit arg — pin it to 16 so
            # the three trials recompile only per γ, not per trial.
            budgets = [rng.randrange(6, 17) for _ in prompts]
            budgets[0] = 16
            b, _, results = _drain(
                params, cfg, prompts, budgets, max_batch=2,
                speculative=True, gamma=[2, 5, 7][trial],
                page_size=4, capacity_tokens=512,
                prefix_cache=bool(trial % 2),
            )
            assert {r.req_id for r in results} == set(range(len(prompts)))
            if b.prefix_cache is not None:
                b.prefix_cache.clear()
            assert b.allocator.free_pages == b.allocator.n_pages
        assert checked["n"] > 0, "fuzz never exercised a speculative step"


class TestSpecChaos:
    def _arm(self, spec):
        from adversarial_spec_tpu.resilience import injector

        injector.install(
            injector.FaultInjector(injector.parse_chaos_spec(spec))
        )
        return injector

    def test_mid_verify_fault_evicts_only_speculating_slot(
        self, tiny_model, tmp_path
    ):
        """An injected fault on the spec dispatch seam: the named slot is
        evicted with its committed AND draft pages freed, the
        co-resident finishes with byte-identical tokens, and the
        auto-dumped JSONL reconstructs the eviction."""
        from adversarial_spec_tpu import obs

        params, cfg = tiny_model
        prompts = [_repetitive_prompt(40), _repetitive_prompt(41)]
        _, ref, _ = _drain(
            params, cfg, prompts, [16, 16], speculative=False,
        )
        obs.configure(enabled=True, events_out=str(tmp_path / "ev.jsonl"))
        obs.reset_stats()
        # after=4 skips the admission-phase scheduler_chunk hits so the
        # fault lands on a speculative dispatch with both rows resident.
        inj = self._arm("bug@scheduler_chunk:after=4:times=1:slot=0")
        try:
            b, out, results = _drain(
                params, cfg, prompts, [16, 16],
                speculative=True, gamma=4, page_size=4,
                prefix_cache=False,
            )
        finally:
            inj.reset()
        by_id = {r.req_id: r for r in results}
        assert by_id[0].error is not None
        assert by_id[0].fault_kind is not None
        assert by_id[1].error is None
        assert out[1] == ref[1], "co-resident tokens perturbed"
        b.allocator.check_invariants()
        assert b.allocator.free_pages == b.allocator.n_pages
        # The flight recorder dumped at the moment of eviction.
        dump = tmp_path / "ev.fault.jsonl"
        assert dump.exists()
        events = [json.loads(ln) for ln in dump.read_text().splitlines()]
        faults = [e for e in events if e["type"] == "fault"]
        assert faults, "no FaultEvent in the auto-dump"
        last = faults[-1]
        assert last["slot"] == 0
        assert last["pages_freed"] > 0
        assert last["kind"]
        assert any(e["type"] == "spec" for e in events), (
            "SpecEvents missing from the reconstruction"
        )

    def test_kv_alloc_fault_during_spec_prepare_contained(self, tiny_model):
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(40), _repetitive_prompt(41)]
        _, ref, _ = _drain(
            params, cfg, prompts, [16, 16], speculative=False,
        )
        # Skip the admission-time kv_alloc hits; fire on the per-step
        # coverage extension inside _prepare_spec_step.
        inj = self._arm("bug@kv_alloc:after=2:times=1:slot=0")
        try:
            b, out, results = _drain(
                params, cfg, prompts, [16, 16],
                speculative=True, gamma=4, page_size=4,
                prefix_cache=False,
            )
        finally:
            inj.reset()
        by_id = {r.req_id: r for r in results}
        assert by_id[0].error is not None
        assert by_id[1].error is None
        assert out[1] == ref[1]
        b.allocator.check_invariants()
        assert b.allocator.free_pages == b.allocator.n_pages

    def test_chaos_fuzz_no_request_lost_with_spec(self, tiny_model):
        """The resilience fuzz invariant, speculation enabled: every
        req_id resolves exactly once, pool invariants hold, and all
        pages return — under random kv_alloc/scheduler_chunk faults."""
        from adversarial_spec_tpu.resilience import injector as inj_mod
        from adversarial_spec_tpu.resilience.faults import FaultKind
        from adversarial_spec_tpu.resilience.injector import (
            FaultInjector,
            FaultRule,
        )

        params, cfg = tiny_model
        kinds = list(FaultKind)
        seams = ["scheduler_chunk", "kv_alloc"]
        for seed in (0, 1, 2):
            rng = random.Random(seed)
            rules = [
                FaultRule(
                    kind=rng.choice(kinds),
                    seam=rng.choice(seams),
                    p=0.25,
                    slot=rng.choice([None, 0, 1]),
                )
                for _ in range(rng.randrange(1, 3))
            ]
            inj_mod.install(FaultInjector(rules, seed=seed))
            try:
                n_req = rng.randrange(3, 6)
                prompts = [
                    _repetitive_prompt(10 + (i * 13) % 40)
                    for i in range(n_req)
                ]
                budgets = [4 + (i * 3) % 12 for i in range(n_req)]
                b, _, results = _drain(
                    params, cfg, prompts, budgets, max_batch=2,
                    speculative=True, gamma=3, page_size=4,
                    prefix_cache=False, timeout_s=60.0,
                )
            finally:
                inj_mod.reset()
            assert sorted(r.req_id for r in results) == list(range(n_req))
            b.allocator.check_invariants()
            assert b.allocator.free_pages == b.allocator.n_pages


class TestSlotReuseWithSpec:
    def test_multi_token_steps_respect_generation_guard(self, tiny_model):
        """The multi-token analog of the slot-reuse regression: steps
        emitting 1..γ+1 tokens per row, slots churning through mixed
        budgets — a freed-and-readmitted slot must not inherit the old
        owner's counts or flags. Every request must equal its solo
        dense reference."""
        params, cfg = tiny_model
        prompts = [
            _repetitive_prompt(
                120 if i % 2 == 0 else 17, period=5 + i % 3
            )
            for i in range(6)
        ]
        budgets = [8 if i % 2 == 0 else 16 for i in range(6)]
        _, out, results = _drain(
            params, cfg, prompts, budgets, max_batch=2, chunk=8,
            speculative=True, gamma=4,
        )
        assert [r.req_id for r in results] == list(range(6))
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            ref = generate(
                params, cfg, [p], max_new_tokens=n, eos_ids=[],
                greedy=True, speculative=False,
            )
            np.testing.assert_array_equal(
                out[i], ref.tokens[0, : ref.n_generated[0]],
                err_msg=f"req {i} (slot churn corrupted a row)",
            )


class TestGenerateSeamWarning:
    def test_paged_speculative_warns_once(self, tiny_model, capsys):
        """satellite: ``speculative and not paged`` used to silently
        disable speculation for paged generate() calls — now the flag
        interaction is named ONCE on stderr, and tokens are unchanged."""
        import adversarial_spec_tpu.engine.generate as gen_mod

        params, cfg = tiny_model
        prompt = _repetitive_prompt(24)
        kw = dict(
            max_new_tokens=16, eos_ids=[], greedy=True,
            paged=True, page_size=16, share_prefix=False,
        )
        gen_mod._PAGED_SPEC_WARNED = False
        try:
            out = generate(params, cfg, [prompt], speculative=True, **kw)
            err = capsys.readouterr().err
            assert "speculative=True is ignored when paged=True" in err
            assert "ContinuousBatcher" in err
            generate(params, cfg, [prompt], speculative=True, **kw)
            assert (
                "speculative=True is ignored"
                not in capsys.readouterr().err
            ), "warning must fire once per process"
        finally:
            gen_mod._PAGED_SPEC_WARNED = False
        ref = generate(params, cfg, [prompt], speculative=False, **kw)
        np.testing.assert_array_equal(out.tokens, ref.tokens)

    def test_paged_inherited_default_does_not_warn(
        self, tiny_model, capsys
    ):
        """Review regression: a paged generate() that merely INHERITED
        the default-on process config (the engine's dense fallback
        passes speculative=None) asked for nothing — warning it to
        'pass speculative=False' would be spurious noise once per
        process."""
        import adversarial_spec_tpu.engine.generate as gen_mod

        params, cfg = tiny_model
        gen_mod._PAGED_SPEC_WARNED = False
        spec_mod.configure(enabled=True)
        generate(
            params, cfg, [_repetitive_prompt(24)], max_new_tokens=16,
            eos_ids=[], greedy=True, paged=True, page_size=16,
            share_prefix=False,
        )
        assert "speculative=True is ignored" not in capsys.readouterr().err
        assert gen_mod._PAGED_SPEC_WARNED is False

    def test_dense_speculative_does_not_warn(self, tiny_model, capsys):
        import adversarial_spec_tpu.engine.generate as gen_mod

        params, cfg = tiny_model
        gen_mod._PAGED_SPEC_WARNED = False
        generate(
            params, cfg, [_repetitive_prompt(24)], max_new_tokens=16,
            eos_ids=[], greedy=True, speculative=True,
        )
        assert "speculative=True is ignored" not in capsys.readouterr().err


class TestCliSpecFlags:
    SPEC = "# Title\n" + "The allocator SHALL bound reuse. " * 30

    def _run(self, argv, monkeypatch, capsys):
        from adversarial_spec_tpu import cli

        monkeypatch.setattr("sys.stdin", io.StringIO(self.SPEC))
        code = cli.main(argv)
        out, err = capsys.readouterr()
        return code, json.loads(out), err

    def test_json_carries_spec_section_with_acceptance(
        self, monkeypatch, capsys
    ):
        """A mock critique round: the [SPEC] revision is a near-copy of
        the document, so the deterministic acceptance model records
        real accepts and ``perf.spec`` reports them."""
        code, data, _ = self._run(
            ["critique", "--models", "mock://critic", "--json"],
            monkeypatch, capsys,
        )
        assert code == 0
        snap = data["perf"]["spec"]
        assert snap["enabled"] is True
        assert snap["gamma"] == spec_mod.DEFAULT_GAMMA
        assert snap["spec_steps"] > 0
        assert snap["acceptance_rate"] > 0
        assert snap["tokens_per_step"] > 1.0
        assert snap["emitted_tokens"] >= snap["accepted_tokens"]

    def test_no_speculative_escape_hatch(self, monkeypatch, capsys):
        code, data, _ = self._run(
            [
                "critique", "--models", "mock://critic", "--json",
                "--no-speculative",
            ],
            monkeypatch, capsys,
        )
        assert code == 0
        snap = data["perf"]["spec"]
        assert snap["enabled"] is False
        assert snap["spec_steps"] == 0

    def test_gamma_flag_reaches_config(self, monkeypatch, capsys):
        code, data, _ = self._run(
            [
                "critique", "--models", "mock://critic", "--json",
                "--gamma", "4",
            ],
            monkeypatch, capsys,
        )
        assert code == 0
        assert data["perf"]["spec"]["gamma"] == 4

    def test_flags_do_not_leak_across_invocations(
        self, monkeypatch, capsys
    ):
        """One round's --no-speculative/--gamma must not leak into the
        next (flag-else-env-default per invocation, like obs)."""
        self._run(
            [
                "critique", "--models", "mock://critic", "--json",
                "--no-speculative", "--gamma", "2",
            ],
            monkeypatch, capsys,
        )
        code, data, _ = self._run(
            ["critique", "--models", "mock://critic", "--json"],
            monkeypatch, capsys,
        )
        assert code == 0
        snap = data["perf"]["spec"]
        assert snap["enabled"] is True
        assert snap["gamma"] == spec_mod.DEFAULT_GAMMA


class TestMockAcceptanceModel:
    def _chat(self, doc, rnd=1, n=1):
        from adversarial_spec_tpu.engine.mock import MockEngine
        from adversarial_spec_tpu.engine.types import (
            ChatRequest,
            SamplingParams,
        )

        eng = MockEngine()
        reqs = [
            ChatRequest(
                model="mock://critic",
                system="You are a critic.",
                user=(
                    f"Debate round {rnd}\n--- DOCUMENT ---\n{doc}"
                    "\n--- END DOCUMENT ---"
                ),
            )
            for _ in range(n)
        ]
        return eng.chat(reqs, SamplingParams())

    def test_deterministic_and_high_on_near_copy(self):
        doc = "All pages SHALL be refcounted and bounded. " * 30
        spec_mod.configure(enabled=True, gamma=8)
        spec_mod.reset_stats()
        self._chat(doc)
        s1 = spec_mod.stats.snapshot()
        assert s1["acceptance_rate"] > 0.3, "near-copy must accept"
        assert s1["tokens_per_step"] >= 2.0
        spec_mod.reset_stats()
        self._chat(doc)
        assert spec_mod.stats.snapshot() == s1  # byte-deterministic

    def test_replies_independent_of_spec_config(self):
        doc = "All pages SHALL be refcounted. " * 20
        on = [c.text for c in self._chat(doc)]
        spec_mod.configure(enabled=False)
        off = [c.text for c in self._chat(doc)]
        assert on == off

    def test_disabled_records_nothing(self):
        spec_mod.configure(enabled=False)
        spec_mod.reset_stats()
        self._chat("Words repeat here. " * 20)
        assert spec_mod.stats.spec_steps == 0


class TestBatcherSpecPallasVerify:
    """The γ-span verify routed through the multi-position paged Pallas
    kernel (``paged_decode_attention_mq``, interpret on CPU) must not
    change a single greedy token vs the XLA gather verify or plain
    dense decode — three arms, every draft width."""

    def _drain_kernel(self, params, cfg, prompts, budgets, *, eos=(), **kw):
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=kw.pop("max_batch", 2),
            max_new_cap=max(budgets),
            eos_ids=list(eos),
            **kw,
        )
        # Route attention through the Pallas kernels in interpret mode
        # (the batcher auto-enables them on TPU only).
        b._use_pallas = True
        b._pallas_interpret = True
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            b.submit(
                SchedRequest(req_id=i, prompt_ids=list(p), max_new_tokens=n)
            )
        results = b.run_all()
        return {r.req_id: r.tokens.tolist() for r in results}

    # Interpret-mode drains are wall-heavy, so the budgets stay small —
    # 8 tokens still crosses several verify spans at every γ here.
    @pytest.mark.parametrize("gamma", [2, 4, 8])
    def test_three_arm_parity(self, tiny_model, gamma):
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(44), _repetitive_prompt(52, period=5)]
        budgets = [8, 8]
        _, xla, _ = _drain(
            params, cfg, prompts, budgets, speculative=True, gamma=gamma
        )
        kern = self._drain_kernel(
            params, cfg, prompts, budgets, speculative=True, gamma=gamma
        )
        assert xla == kern, f"gamma={gamma}: kernel verify changed tokens"
        for i, p in enumerate(prompts):
            ref = generate(
                params, cfg, [p], max_new_tokens=budgets[i], eos_ids=[],
                greedy=True, speculative=False,
            )
            np.testing.assert_array_equal(
                kern[i], ref.tokens[0, : ref.n_generated[0]],
                err_msg=f"gamma={gamma} req {i} vs dense reference",
            )

    def test_walk_kernel_parity_at_head_dim_128(self):
        """The tiny presets have head dim 64, which the span kernel's
        (B, P) grid form takes (Mosaic cuts pages out of a pool by hand
        only in whole lanes). At the head dim of the 7B models the
        batcher's verify goes through the walk over the live pages: the
        same greedy tokens as the XLA gather verify."""
        import dataclasses

        from adversarial_spec_tpu.ops import pallas_paged

        cfg = dataclasses.replace(get_config("llama", "tiny"), head_dim=128)
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        assert pallas_paged._sliceable(
            [jnp.zeros((1, 1, cfg.n_kv_heads, 64, cfg.head_dim))]
        )
        prompts = [_repetitive_prompt(150), _repetitive_prompt(52, period=5)]
        budgets = [6, 6]
        _, xla, _ = _drain(
            params, cfg, prompts, budgets, speculative=True, gamma=4
        )
        kern = self._drain_kernel(
            params, cfg, prompts, budgets, speculative=True, gamma=4
        )
        assert xla == kern

    def test_eos_inside_span_kernel_verify(self, tiny_model):
        """An EOS accepted mid-span through the kernel verify must stop
        the row exactly where the XLA verify (and plain decode) stops."""
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(40)]
        _, probe, _ = _drain(
            params, cfg, prompts, [16], max_batch=1, speculative=False,
        )
        out = probe[0]
        if len(out) < 4:
            pytest.skip("probe output too short to pick a mid-run EOS")
        eos = out[len(out) // 2]
        _, off, _ = _drain(
            params, cfg, prompts, [16], max_batch=1, eos=[eos],
            speculative=False,
        )
        kern = self._drain_kernel(
            params, cfg, prompts, [16], max_batch=1, eos=[eos],
            speculative=True, gamma=4,
        )
        assert kern == off
        assert kern[0][-1] == eos  # EOS kept, nothing after


# -- the drive loop two steps deep (PR 37) -----------------------------------


@functools.lru_cache(maxsize=None)
def _preset(name):
    """(params, cfg) of a tiny preset: the dense one of this module, the
    routed latent one and the hybrid one as their own test modules build
    them (float32 where the program keeps floating point)."""
    if name == "dense":
        cfg = get_config("llama", "tiny")
        return T.init_params(jax.random.key(0), cfg, dtype=jnp.float32), cfg
    if name == "routed-latent":
        from tests.test_moe import program_params

        cfg = get_config(
            "mistral4", "tiny", experts_held=[2, 4], vocab_rows=384
        )
        return program_params(cfg), cfg
    cfg = get_config("granitemoehybrid", "tiny")
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        T.init_params(jax.random.key(0), cfg, jnp.bfloat16),
    )
    return params, cfg


# Five requests over two slots: the third to fifth join mid-run, as a
# finishing row frees its slot; a 150-token prompt prefills in chunks that
# ride the resident's verify steps (chunk=8 keeps the step-token budget
# small enough to split it).
_DEPTH_PROMPTS = [
    _repetitive_prompt(40),
    _repetitive_prompt(23, period=5),
    _repetitive_prompt(150, period=9),
    _repetitive_prompt(31, period=4),
    _repetitive_prompt(18, period=6),
]
_DEPTH_BUDGETS = [24, 13, 9, 24, 17]
_SAMPLED = dict(greedy=False, temperature=0.9, top_k=12, seed=5)

_DEPTH_CASES = {
    # preset, batcher keywords, chaos rule, cancel a stream at n tokens
    "dense-greedy-admissions": ("dense", {}, "", 0),
    "dense-sampled-admissions": ("dense", _SAMPLED, "", 0),
    "routed-latent-greedy": ("routed-latent", {}, "", 0),
    "routed-latent-sampled": ("routed-latent", _SAMPLED, "", 0),
    "hybrid-greedy": ("hybrid", {}, "", 0),
    "hybrid-sampled": ("hybrid", _SAMPLED, "", 0),
    "dense-greedy-stream-cancel": ("dense", {}, "", 7),
    # Sampled, the two requests that fit the slots: what a consumer will
    # say is not the batcher's to know, so the step after a cancelled
    # row's last is already enqueued, and a request that waited for the
    # slot would join a step (and a key) later than one deep.
    "dense-sampled-stream-cancel": ("dense", dict(n=2, **_SAMPLED), "", 7),
    "dense-kv_alloc-fault": (
        "dense", {}, "bug@kv_alloc:after=9:times=1:slot=1", 0,
    ),
    "dense-scheduler_chunk-fault": (
        "dense", _SAMPLED, "bug@scheduler_chunk:after=6:times=1:slot=0", 0,
    ),
    # 73 or 74 pages of 4 tokens under two rows of a 128-token bucket and
    # up to 24 tokens each: every first span fits, not every second.
    "dense-greedy-small-pool": (
        "dense", dict(capacity_tokens=292, prefix_cache=False), "", 0,
    ),
    "dense-sampled-small-pool": (
        "dense",
        dict(capacity_tokens=296, prefix_cache=False, **_SAMPLED),
        "",
        0,
    ),
}


def _serve_at_depth(monkeypatch, depth, preset, kw, chaos, cancel_at):
    """One drain of the five requests at a pipeline depth: what every
    request got (tokens, counts, fault, cancellation, every delivery its
    consumer saw) and the process's speculation counters."""
    import adversarial_spec_tpu.engine.scheduler as sched_mod
    from adversarial_spec_tpu.resilience import injector

    monkeypatch.setattr(sched_mod, "_PIPELINE_DEPTH", depth)
    params, cfg = _preset(preset)
    kw = dict(kw)
    n = kw.pop("n", len(_DEPTH_PROMPTS))
    prompts = [p[:] for p in _DEPTH_PROMPTS[:n]]
    budgets = list(_DEPTH_BUDGETS[:n])
    if "capacity_tokens" in kw:
        # without the 150-token prompt, whose bucket alone is the pool
        prompts, budgets = prompts[:2] + prompts[3:], budgets[:2] + budgets[3:]
    spec_mod.reset_stats()
    b = ContinuousBatcher(
        params, cfg,
        **{
            "max_batch": 2, "max_new_cap": max(budgets), "eos_ids": [],
            "speculative": True, "gamma": 3, "page_size": 4, "chunk": 8,
            "capacity_tokens": 2048, **kw,
        },
    )
    seen = {i: [] for i in range(len(prompts))}

    def consumer(i):
        def on_tokens(tokens):
            seen[i].append(tokens.tolist())
            return not (cancel_at and i == 1 and len(tokens) >= cancel_at)

        return on_tokens

    for i, (p, n) in enumerate(zip(prompts, budgets)):
        b.submit(
            SchedRequest(
                req_id=i, prompt_ids=p, max_new_tokens=n,
                on_tokens=consumer(i) if cancel_at else None,
            )
        )
    if chaos:
        injector.install(
            injector.FaultInjector(injector.parse_chaos_spec(chaos))
        )
    try:
        results = b.run_all()
    finally:
        injector.reset()
    b.check_invariants()
    assert b.allocator.free_pages + (
        b.prefix_cache.cached_pages if b.prefix_cache is not None else 0
    ) == b.allocator.n_pages
    served = {
        r.req_id: (
            r.tokens.tolist(), r.n_generated, r.fault_kind, r.cancelled,
            r.spec_steps, r.spec_drafted, r.spec_accepted, seen[r.req_id],
        )
        for r in results
    }
    return served, spec_mod.stats.as_dict()


class TestTwoDeepDrive:
    @pytest.mark.parametrize("case", list(_DEPTH_CASES))
    def test_depth_1_and_2_serve_the_same(self, case, monkeypatch):
        """The two-deep loop enqueues the programs the one-deep loop
        enqueues, in its order and with its keys: every request's tokens,
        its per-step counts, the victim of an injected fault and what it
        keeps, a cancelled stream and every delivery before it are the
        same at ``_PIPELINE_DEPTH`` 1 and 2, greedy and sampled."""
        preset, kw, chaos, cancel_at = _DEPTH_CASES[case]
        one, stats1 = _serve_at_depth(
            monkeypatch, 1, preset, kw, chaos, cancel_at
        )
        two, stats2 = _serve_at_depth(
            monkeypatch, 2, preset, kw, chaos, cancel_at
        )
        assert sorted(one) == sorted(two) == list(range(len(one)))
        for rid in one:
            assert one[rid] == two[rid], f"request {rid}"
        if chaos:
            assert sum(1 for r in two.values() if r[2]) == 1
        if cancel_at:
            assert two[1][3] and two[1][1] >= cancel_at
        # The same steps were booked; only the second run rode any of
        # them under its predecessor.
        pipelined = stats2.pop("pipelined_steps")
        assert stats1.pop("pipelined_steps") == 0
        # (pages go back later while a successor is in flight, so fewer
        # cross a page's edge twice)
        assert stats1.pop("rolled_back_pages") > 0
        assert stats2.pop("rolled_back_pages") > 0
        assert stats1 == stats2
        assert 0 < pipelined < stats2["spec_steps"]
        if "capacity_tokens" in kw:
            # Counted: with room for every second span the same requests
            # ride more of their steps.
            roomy = dict(kw, capacity_tokens=2048)
            _, stats = _serve_at_depth(monkeypatch, 2, preset, roomy, "", 0)
            assert stats["spec_steps"] == stats2["spec_steps"]
            assert pipelined < stats["pipelined_steps"]

    def test_no_page_leaves_a_row_while_a_step_can_commit_on_it(
        self, tiny_model, monkeypatch
    ):
        """The rollback lags with the view. Every token a verify step
        commits lands on the page its table named when the step was
        ENQUEUED: that page is still the row's at the step's retirement
        and was released by nobody in between (so it went to no other
        row and not to the prefix cache), over a run whose every step
        rejects drafts, with pages of four tokens, the prefix cache on
        and requests queueing for two slots."""
        params, cfg = tiny_model
        released = []  # every page any sequence let go of, in order
        tables = {}  # id(step) -> (the table as pushed, len(released))
        checked = {"tokens": 0, "ahead": 0}

        real_truncate = PageAllocator.truncate
        real_free = PageAllocator.free_sequence

        def truncate(self, seq_id, n_tokens):
            pages = real_truncate(self, seq_id, n_tokens)
            released.extend(pages)
            return pages

        def free_sequence(self, seq_id):
            released.extend(self.table(seq_id))
            real_free(self, seq_id)

        monkeypatch.setattr(PageAllocator, "truncate", truncate)
        monkeypatch.setattr(PageAllocator, "free_sequence", free_sequence)

        real_dispatch = ContinuousBatcher._dispatch_step
        real_apply = ContinuousBatcher._apply_spec_counts

        def dispatch(self, live, alloc_len, adm, chunk_len):
            real_dispatch(self, live, alloc_len, adm, chunk_len)
            tables[id(self._pipe[-1])] = (
                np.asarray(self.page_table) - 1, len(released)
            )

        def apply(self, counts_np, step):
            pushed, mark = tables.pop(id(step))
            since = set(released[mark:])
            for slot, gen in step.slots:
                if gen != self._slot_gen[slot] or not counts_np[2, slot]:
                    continue
                now = self.allocator.table(self._slot_seq[slot])
                # K/V slots the step committed: its first token's and its
                # accepted drafts', then the slot held for its last.
                first = int(self._cur_len_np[slot]) - 1
                for pos in range(first, int(counts_np[4, slot])):
                    page = int(pushed[slot, pos // self.page_size])
                    assert page >= 0, "committed onto the trash page"
                    assert page == now[pos // self.page_size]
                    assert page not in since
                    checked["tokens"] += 1
                checked["ahead"] += step.ahead
            real_apply(self, counts_np, step)
            self.allocator.check_invariants()

        monkeypatch.setattr(ContinuousBatcher, "_dispatch_step", dispatch)
        monkeypatch.setattr(ContinuousBatcher, "_apply_spec_counts", apply)
        prompts = [
            _repetitive_prompt(30 + 7 * i, period=3 + i % 4) for i in range(6)
        ]
        b, out, results = _drain(
            params, cfg, prompts, [32, 20, 32, 9, 27, 32], max_batch=2,
            speculative=True, gamma=7, page_size=4, capacity_tokens=512,
        )
        assert all(r.error is None for r in results)
        s = spec_mod.stats
        assert s.accepted_tokens < s.drafted_tokens  # drafts were rejected
        assert s.accepted_tokens > 0  # and some crossed a page's edge
        assert s.rolled_back_pages > 0
        assert checked["ahead"] == s.pipelined_steps > 0
        assert checked["tokens"] >= s.emitted_tokens
        b.check_invariants()

    def test_no_step_is_enqueued_for_rows_that_are_all_finished(
        self, tiny_model, monkeypatch
    ):
        """Rule 4: a verify step is enqueued ahead only if some row is
        certain to outlive the step in flight, so every program enqueued
        finds a row to emit for, and a drained dispatch never pays a step
        for nothing. ``spec.pipelined_steps`` counts, in the unit of
        ``spec.spec_steps``, the rows of the programs that were."""
        params, cfg = tiny_model
        enqueued = []  # one entry a program: [ahead, rows that emitted]
        real_dispatch = ContinuousBatcher._dispatch_step
        real_apply = ContinuousBatcher._apply_spec_counts

        def dispatch(self, live, alloc_len, adm, chunk_len):
            span = self.gamma + 1
            left = [
                int(self._max_new_np[s])
                - int(self._cur_len_np[s] - self._row_len_np[s])
                - self._steps_ahead(s) * span
                for s in live
            ]
            real_dispatch(self, live, alloc_len, adm, chunk_len)
            step = self._pipe[-1]
            assert step.ahead == (len(self._pipe) == 2)
            assert max(left) > 0, "every row certain to be finished"
            enqueued.append([step, 0])

        def apply(self, counts_np, step):
            (entry,) = [e for e in enqueued if e[0] is step]
            entry[1] = int((counts_np[2] > 0).sum())
            real_apply(self, counts_np, step)

        monkeypatch.setattr(ContinuousBatcher, "_dispatch_step", dispatch)
        monkeypatch.setattr(ContinuousBatcher, "_apply_spec_counts", apply)
        # Two rows that start together and end four steps apart, then a
        # third alone: with random drafts a step emits one token a row.
        rng = random.Random(3)
        prompts = [[rng.randrange(3, 500) for _ in range(20)] for _ in range(3)]
        b, out, results = _drain(
            params, cfg, prompts, [14, 18, 11], max_batch=2,
            speculative=True, gamma=3, page_size=4, prefix_cache=False,
        )
        assert [len(out[i]) for i in range(3)] == [14, 18, 11]
        assert all(rows > 0 for _, rows in enqueued), "a step for nothing"
        s = spec_mod.stats
        assert sum(rows for _, rows in enqueued) == s.spec_steps
        assert s.pipelined_steps == sum(
            rows for step, rows in enqueued if step.ahead
        )
        # One deep: a dispatch's first step, and from where no row has
        # more than a span (4) of budget left at the trailing view.
        assert 0 < s.pipelined_steps <= s.spec_steps - 2 * (1 + 2)
        assert s.pipelined_steps >= s.spec_steps // 2
        assert not b._pipe

    def test_the_share_metric_reads_the_two_counters(self):
        """`batcher.pipelined_step_share` is `spec.pipelined_steps` over
        `spec.spec_steps` through the standing `counter_ratio` reader:
        both are fields of `perf.spec`, which the benchmark's counters
        flatten; the entry (found by its name) is for the two dense
        critique cells, and a program without the counter (the parent)
        reads nothing and raises nothing."""
        from pathlib import Path

        from perfbench import reducers

        root = Path(__file__).resolve().parents[1]
        spec = json.loads(
            (root / "perfbench/metrics/batcher.pipelined_step_share.json")
            .read_text()
        )
        assert spec["reducer"] == "counter_ratio"
        fields = spec_mod.snapshot()
        for key in spec["params"]["num"] + spec["params"]["den"]:
            assert key.startswith("spec.") and key[len("spec."):] in fields
        [entry] = [
            m
            for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]
            if m["name"] == "batcher.pipelined_step_share"
        ]
        assert entry == {
            "name": "batcher.pipelined_step_share", "unit": "%",
            "better": "higher", "source": "program_counter",
            "layer": "batcher", "moves": "out_tokens_per_s",
            "workloads": [
                "mistral-7b-int8.critique", "qwen2-7b-int8.critique"
            ],
        }
        from types import SimpleNamespace

        read = lambda start, end: reducers.counter_ratio(  # noqa: E731
            SimpleNamespace(counters_start=start, counters_end=end),
            spec["params"],
        )
        assert read(
            {"spec.spec_steps": 100, "spec.pipelined_steps": 80},
            {"spec.spec_steps": 300, "spec.pipelined_steps": 260},
        ) == pytest.approx(90.0)
        assert read({"spec.spec_steps": 1}, {"spec.spec_steps": 9}) is None

    @pytest.mark.parametrize("depth", [1, 2])
    def test_a_fault_at_the_fetch_clears_the_pipe(
        self, tiny_model, monkeypatch, depth
    ):
        """A step's counts cannot be fetched (the device state
        survives): the steps in flight are dropped with it, one row is
        evicted with what the device had made for it, and the host's
        views are read back from the device, so the co-resident goes on
        to the tokens it would have served undisturbed."""
        import adversarial_spec_tpu.engine.scheduler as sched_mod

        class Unfetchable:
            def __init__(self, counts):
                self.counts = counts

            def copy_to_host_async(self):
                pass

            def __array__(self, *args, **kwargs):
                raise RuntimeError("the counts were lost")

        monkeypatch.setattr(sched_mod, "_PIPELINE_DEPTH", depth)
        params, cfg = tiny_model
        prompts = [_repetitive_prompt(40), _repetitive_prompt(33, period=5)]
        _, ref, _ = _drain(params, cfg, prompts, [24, 24], speculative=False)
        real = ContinuousBatcher._dispatch_spec
        calls = {"n": 0}

        def dispatch(self, *args, **kwargs):
            counts = real(self, *args, **kwargs)
            calls["n"] += 1
            return Unfetchable(counts) if calls["n"] == 4 else counts

        monkeypatch.setattr(ContinuousBatcher, "_dispatch_spec", dispatch)
        b, out, results = _drain(
            params, cfg, prompts, [24, 24],
            speculative=True, gamma=3, page_size=4, prefix_cache=False,
        )
        (gone,) = [r.req_id for r in results if r.error is not None]
        # the victim leaves with a prefix of its undisturbed reply
        n = results[gone].n_generated
        assert 4 <= n < 24 and out[gone][:n] == ref[gone][:n]
        assert out[1 - gone] == ref[1 - gone]
        assert not b._pipe
        b.allocator.check_invariants()
        assert b.allocator.free_pages == b.allocator.n_pages
