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
