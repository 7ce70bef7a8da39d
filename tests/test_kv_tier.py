"""Tiered KV cache tests (engine/kvtier.py + the serving-path wiring).

Covers the tier state machine bottom-up:
- the procconfig hoist (the shared config/stats mechanics the four
  process-wide modules now ride on);
- chain hashing (cross-process content identity of radix blocks);
- HostTier LRU + the demote conservation invariant;
- DiskStore format hardening: atomic writes, fingerprint/token/sha
  verification, corrupt-entry quarantine, and a write/rehydrate/corrupt
  fuzz against an oracle;
- PageAllocator swap pins (a promotion's in-flight write target can
  never free under it);
- the mock engine's deterministic tier accounting (pressure promotion,
  restart rehydration through a shared store dir);
- the real batcher: demote/promote under a page cap and restart
  rehydration through the store, both byte-identical to tier-off, with
  allocator + tier invariants clean and zero unexpected recompiles;
- chaos: ``kv_swap`` injected mid-promotion evicts only the waiting
  request, leaves both tiers invariant-clean, and the auto-dumped JSONL
  reconstructs the swap + fault;
- CLI plumbing: flags/env reach the process config and ``perf.kv_tier``.
"""

import json
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine import kvtier
from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
from adversarial_spec_tpu.engine.kvcache import PageAllocator
from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config


@pytest.fixture(autouse=True)
def _spec_off(monkeypatch):
    """This module pins tier demote/promote semantics; speculation only
    multiplies the jit programs each batcher compiles (the spec × tier
    interaction rides the same extend_evicting path test_spec_batcher
    covers)."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    monkeypatch.setenv("ADVSPEC_SPECULATIVE", "0")
    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    return params, cfg


class TestProcConfig:
    def test_unknown_knob_fails_loudly(self):
        from adversarial_spec_tpu.engine import procconfig

        from dataclasses import dataclass

        @dataclass
        class C:
            enabled: bool = True

        @dataclass
        class S(procconfig.StatsBase):
            n: int = 0

        state = procconfig.ProcState(C(), S())
        with pytest.raises(AttributeError, match="no knob"):
            state.configure(typo=1)

    def test_ported_modules_keep_their_payload_keys(self):
        """The hoist is internal: every perf payload keeps its exact
        key set (CLI consumers and docs depend on them)."""
        from adversarial_spec_tpu.engine import interleave, spec

        il = interleave.snapshot()
        assert {"fused_steps", "prefill_time_s", "sync_points"} <= set(il)
        # Counters only: the drive loop has no switch to report.
        assert not {"enabled", "pipeline_depth"} & set(il)
        assert il["prefill_time_s"] == (
            il["stalled_prefill_s"] + il["overlapped_prefill_s"]
        )
        sp = spec.snapshot()
        assert {"acceptance_rate", "tokens_per_step", "enabled",
                "gamma"} <= set(sp)
        pc = prefix_mod.snapshot()
        assert "hit_rate" in pc and "enabled" in pc
        assert "max_pages" not in pc  # config-only knob stays out
        kt = kvtier.snapshot()
        assert {"host_hit_rate", "disk_hit_rate", "enabled", "host_mb",
                "store_dir"} <= set(kt)

    def test_gamma_validation_survives_the_port(self):
        from adversarial_spec_tpu.engine import spec

        with pytest.raises(ValueError, match="ADVSPEC_GAMMA"):
            spec.configure(gamma=0)

    def test_stats_reset_in_place(self):
        kvtier.stats.demoted_blocks = 7
        ref = kvtier.stats
        kvtier.reset_stats()
        assert ref.demoted_blocks == 0 and kvtier.stats is ref


class TestChainHash:
    def test_deterministic_and_parent_sensitive(self):
        a = kvtier.chain_hash("", (1, 2, 3))
        assert a == kvtier.chain_hash("", (1, 2, 3))
        assert a != kvtier.chain_hash("", (1, 2, 4))
        assert kvtier.chain_hash(a, (9,)) != kvtier.chain_hash("", (9,))

    def test_string_tokens_hash(self):
        # The mock's 4-char-chunk tokens must address the same way.
        assert kvtier.chain_hash("", ("abcd", "efgh")) == kvtier.chain_hash(
            "", ("abcd", "efgh")
        )


class TestHostTier:
    def test_lru_eviction_and_conservation(self):
        h = kvtier.HostTier(capacity_bytes=300, block_bytes=100)
        assert h.put("a", (1,), None) == []
        assert h.put("b", (2,), None) == []
        h.get("a")  # refresh: b becomes LRU
        assert h.put("c", (3,), None) == []
        evicted = h.put("d", (4,), None)
        assert [b.chain for b in evicted] == ["b"]
        h.note_freed(len(evicted))
        h.check_invariants()

    def test_take_promoted_is_terminal(self):
        h = kvtier.HostTier(capacity_bytes=1000, block_bytes=100)
        h.put("a", (1, 2), None)
        assert h.take_promoted("a").chain == "a"
        assert h.get("a") is None
        assert h.take_promoted("a") is None  # idempotent miss
        h.check_invariants()

    def test_conservation_violation_detected(self):
        h = kvtier.HostTier(capacity_bytes=1000, block_bytes=100)
        h.put("a", (1,), None)
        del h._blocks["a"]  # corrupt: vanished without a terminal state
        with pytest.raises(RuntimeError, match="conservation"):
            h.check_invariants()

    def test_single_block_over_budget_demotes_without_crash(self):
        """A block bigger than the whole host budget is evicted by
        put() itself (clear branch) — demote must treat it as an LRU
        victim (spill/free), not index the vanished entry."""
        kvtier.reset_stats()
        tiers = kvtier.TieredStore(
            kvtier.HostTier(capacity_bytes=10, block_bytes=100), None
        )
        calls = []

        def lazy():
            calls.append(1)
            return {"k": np.zeros(2)}

        tiers.demote("a", (1, 2), lazy)  # must not raise
        assert tiers.host_resident == 0
        assert kvtier.stats.host_freed_blocks == 1
        tiers.check_invariants()

    def test_lazy_payload_materializes_once(self):
        calls = []

        def lazy():
            calls.append(1)
            return {"k": np.zeros(2)}

        h = kvtier.HostTier(capacity_bytes=1000, block_bytes=100)
        h.put("a", (1,), lazy)
        b = h.get("a")
        p1 = kvtier.HostTier.materialize(b)
        p2 = kvtier.HostTier.materialize(b)
        assert p1 is p2 and calls == [1]


class TestDiskStore:
    def _store(self, tmp_path, fp="fp-a"):
        return kvtier.DiskStore(str(tmp_path / "store"), fp)

    def test_roundtrip_preserves_dtype_and_shape(self, tmp_path):
        s = self._store(tmp_path)
        payload = {
            "k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
            "v": np.ones((2, 2), np.int8),
        }
        chain = kvtier.chain_hash("", (5, 6))
        assert s.put(chain, (5, 6), payload)
        assert not s.put(chain, (5, 6), payload)  # idempotent
        toks, got = s.get(chain, (5, 6))
        assert toks == (5, 6)
        assert got["k"].dtype == np.float32 and got["k"].shape == (2, 3, 4)
        assert np.array_equal(got["k"], payload["k"])
        assert got["v"].dtype == np.int8

    def test_no_tmp_orphan_after_put(self, tmp_path):
        s = self._store(tmp_path)
        s.put(kvtier.chain_hash("", (1,)), (1,), None)
        leftovers = [
            p for p in (tmp_path / "store").rglob("*") if ".tmp" in p.name
        ]
        assert leftovers == []

    def test_fingerprint_namespaces(self, tmp_path):
        a = self._store(tmp_path, "fp-a")
        chain = kvtier.chain_hash("", (1,))
        a.put(chain, (1,), None)
        b = kvtier.DiskStore(str(tmp_path / "store"), "fp-b")
        assert not b.has(chain)  # different namespace directory

    def test_token_mismatch_quarantines(self, tmp_path):
        s = self._store(tmp_path)
        chain = kvtier.chain_hash("", (1, 2))
        s.put(chain, (1, 2), None)
        kvtier.reset_stats()
        assert s.get(chain, (9, 9)) is None
        assert kvtier.stats.store_corrupt == 1
        assert s.resident_entries == 0
        assert not s.has(chain)
        # The evidence moved aside rather than vanishing.
        assert list((tmp_path / "store").rglob("quarantine/*.kvb"))

    def test_corrupt_payload_quarantines_and_store_survives(self, tmp_path):
        s = self._store(tmp_path)
        c1 = kvtier.chain_hash("", (1,))
        c2 = kvtier.chain_hash("", (2,))
        s.put(c1, (1,), {"k": np.arange(8, dtype=np.float32)})
        s.put(c2, (2,), {"k": np.arange(8, dtype=np.float32)})
        path = s._path(c1)
        raw = bytearray(open(path, "rb").read())
        raw[-3] ^= 0xFF  # flip a payload byte: sha must catch it
        open(path, "wb").write(bytes(raw))
        kvtier.reset_stats()
        assert s.get(c1, (1,)) is None
        assert kvtier.stats.store_corrupt == 1
        # The sibling entry still serves.
        assert s.get(c2, (2,)) is not None
        assert s.resident_entries == 1

    def test_restart_rescan_counts_entries(self, tmp_path):
        s = self._store(tmp_path)
        for i in range(3):
            s.put(kvtier.chain_hash("", (i,)), (i,), None)
        reopened = kvtier.DiskStore(str(tmp_path / "store"), "fp-a")
        assert reopened.resident_entries == 3


class TestDiskStoreConcurrentWriters:
    """The property the SHARED fleet store depends on (docs/fleet.md):
    N writers racing the same content-addressed chain — two threads of
    one engine, or two replica processes writing through one store dir
    — must end with EXACTLY ONE valid entry, no quarantine, and
    consistent resident accounting. The tmp+rename discipline makes
    the race harmless: every writer lands a complete identical entry
    under a unique temp name and the replaces are atomic."""

    def _race(self, tmp_path, stores, n_threads, payload):
        """Hammer one chain from n_threads across the given store
        instances, all released together by a barrier."""
        import threading

        chain = kvtier.chain_hash("", (7, 8, 9))
        barrier = threading.Barrier(n_threads)
        errors: list[BaseException] = []

        def write(store):
            try:
                barrier.wait(timeout=10)
                for _ in range(5):
                    store.put(chain, (7, 8, 9), payload)
            except BaseException as e:  # pragma: no cover - fail loudly
                errors.append(e)

        threads = [
            threading.Thread(target=write, args=(stores[i % len(stores)],))
            for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert errors == []
        return chain

    def _check_one_valid_entry(self, tmp_path, stores, chain, payload):
        kvtier.reset_stats()
        root = tmp_path / "store"
        entries = [
            p
            for p in root.rglob("*.kvb")
            if "quarantine" not in p.parts
        ]
        assert len(entries) == 1  # exactly one on-disk entry
        assert not list(root.rglob("quarantine/*")), "nothing quarantined"
        assert not [p for p in root.rglob("*") if ".tmp" in p.name]
        for s in stores:
            toks, got = s.get(chain, (7, 8, 9))  # fully verifies
            assert toks == (7, 8, 9)
            if payload is not None:
                assert np.array_equal(got["k"], payload["k"])
            # No writer double-counted: each instance tracks at most
            # the single entry that exists (check_invariants' one-sided
            # shared-store rule).
            assert s.resident_entries <= s._scan() == 1
        assert kvtier.stats.store_corrupt == 0

    def test_threads_sharing_one_instance(self, tmp_path):
        payload = {"k": np.arange(64, dtype=np.float32)}
        store = kvtier.DiskStore(str(tmp_path / "store"), "fp-a")
        chain = self._race(tmp_path, [store], n_threads=8, payload=payload)
        self._check_one_valid_entry(tmp_path, [store], chain, payload)
        assert store.resident_entries == 1  # counted exactly once

    def test_two_instances_same_dir_like_two_processes(self, tmp_path):
        """Two DiskStore instances over one dir — each fleet replica
        process holds its own instance; same-pid here makes the temp
        name collision HARDER than the cross-process case."""
        payload = {"k": np.arange(64, dtype=np.float32)}
        stores = [
            kvtier.DiskStore(str(tmp_path / "store"), "fp-a")
            for _ in range(2)
        ]
        chain = self._race(tmp_path, stores, n_threads=8, payload=payload)
        self._check_one_valid_entry(tmp_path, stores, chain, payload)

    def test_two_real_processes(self, tmp_path):
        """The literal fleet shape: two PROCESSES write-through the
        same chain simultaneously (rendezvous via a spin on a marker
        file), then the parent verifies the single valid entry."""
        import subprocess
        import sys

        script = r"""
import sys, os, time
sys.path.insert(0, sys.argv[1])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from adversarial_spec_tpu.engine import kvtier

root, ready, go = sys.argv[2], sys.argv[3], sys.argv[4]
store = kvtier.DiskStore(root, "fp-a")
chain = kvtier.chain_hash("", (7, 8, 9))
open(ready, "w").close()
deadline = time.time() + 20
while not os.path.exists(go):
    if time.time() > deadline:
        sys.exit(3)
    time.sleep(0.001)
for _ in range(5):
    store.put(chain, (7, 8, 9), {"k": np.arange(64, dtype=np.float32)})
print(store.resident_entries)
"""
        import os

        repo = os.path.dirname(
            os.path.dirname(
                os.path.dirname(os.path.abspath(kvtier.__file__))
            )
        )
        root = str(tmp_path / "store")
        go = tmp_path / "go"
        procs = []
        readies = []
        for i in range(2):
            ready = tmp_path / f"ready-{i}"
            readies.append(ready)
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-c", script, repo, root,
                        str(ready), str(go),
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        import time

        deadline = time.time() + 20
        while not all(r.exists() for r in readies):
            assert time.time() < deadline, "children never reached rendezvous"
            time.sleep(0.005)
        go.touch()  # both children race from here
        outs = [p.communicate(timeout=30) for p in procs]
        assert all(p.returncode == 0 for p in procs), outs
        chain = kvtier.chain_hash("", (7, 8, 9))
        verifier = kvtier.DiskStore(root, "fp-a")
        payload = {"k": np.arange(64, dtype=np.float32)}
        self._check_one_valid_entry(
            tmp_path, [verifier], chain, payload
        )


class TestDiskFuzz:
    def test_write_rehydrate_corrupt_against_oracle(self, tmp_path):
        """Random block sets through write/rehydrate/quarantine must
        agree with an oracle dict at every step: a corrupted entry
        reads as a miss exactly once (then quarantined), never as wrong
        data."""
        rng = random.Random(0)
        s = kvtier.DiskStore(str(tmp_path / "store"), "fuzz")
        oracle: dict[str, tuple] = {}
        kvtier.reset_stats()
        for step in range(200):
            op = rng.random()
            if op < 0.5 or not oracle:
                toks = tuple(rng.randrange(100) for _ in range(4))
                chain = kvtier.chain_hash("", toks + (step,))
                payload = {
                    "k": np.full((2, 2), step, np.float32)
                } if rng.random() < 0.5 else None
                s.put(chain, toks, payload)
                oracle[chain] = (
                    toks,
                    None if payload is None else payload["k"].copy(),
                )
            elif op < 0.85:
                chain = rng.choice(list(oracle))
                toks, want = oracle[chain]
                got = s.get(chain, toks)
                assert got is not None, "oracle says resident"
                assert got[0] == toks
                if want is None:
                    assert got[1] is None
                else:
                    assert np.array_equal(got[1]["k"], want)
            else:
                chain = rng.choice(list(oracle))
                path = s._path(chain)
                raw = bytearray(open(path, "rb").read())
                raw[rng.randrange(len(raw))] ^= 0xFF
                open(path, "wb").write(bytes(raw))
                del oracle[chain]
                # Corruption reads as a miss (quarantine), never data.
                assert s.get(chain, None) is None
            assert s.resident_entries == len(oracle)
        assert kvtier.stats.store_corrupt > 0


class TestAllocatorSwapPins:
    def test_pin_requires_allocated_page(self):
        a = PageAllocator(4, 4)
        with pytest.raises(ValueError, match="unallocated"):
            a.swap_pin(0)

    def test_free_under_pin_is_corruption(self):
        a = PageAllocator(4, 4)
        a.new_sequence(0)
        [p] = a.extend(0, 4)
        a.swap_pin(p)
        with pytest.raises(RuntimeError, match="swap in flight"):
            a.free_sequence(0)
        a.swap_unpin(p)
        a.check_invariants()

    def test_unpin_without_pin_raises(self):
        a = PageAllocator(4, 4)
        a.new_sequence(0)
        [p] = a.extend(0, 4)
        with pytest.raises(RuntimeError, match="without pin"):
            a.swap_unpin(p)

    def test_invariants_catch_pin_on_freed_page(self):
        a = PageAllocator(4, 4)
        a.new_sequence(0)
        [p] = a.extend(0, 4)
        a._swap_pins[p] = 1
        # Corrupt: the page freed (refs + table dropped) while a swap
        # pin is outstanding — an in-flight write against a freed page.
        a._tables[0] = []
        a._lengths[0] = 0
        a._refs.pop(p)
        a._free.append(p)
        with pytest.raises(RuntimeError, match="swap-pinned"):
            a.check_invariants()


def _mock_round(eng, doc, rnd, n_opp=2):
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams

    reqs = [
        ChatRequest(
            model="mock://critic",
            system="You are an adversarial spec critic.",
            # Prefix-stable ordering: document first, round header last.
            user=(
                f"--- DOCUMENT ---\n{doc}\n--- END DOCUMENT ---\n"
                f"Debate round {rnd}"
            ),
        )
        for _ in range(n_opp)
    ]
    return eng.chat(reqs, SamplingParams())


class TestMockTier:
    DOC = (
        "The allocator SHALL bound page reuse by refcount. "
        "Demoted blocks MUST reach exactly one terminal state. "
    ) * 40  # ~4 KB -> well past a small radix cap

    def test_pressure_promotes_from_host(self):
        from adversarial_spec_tpu.engine.mock import MockEngine

        kvtier.configure(enabled=True, host_mb=16, store_dir="")
        prefix_mod.configure(enabled=True, max_pages=16)
        prefix_mod.reset_stats()
        kvtier.reset_stats()
        eng = MockEngine()
        _mock_round(eng, self.DOC, 1)
        snap = kvtier.snapshot()
        assert snap["demoted_blocks"] > 0  # cap eviction demoted the tail
        assert snap["promoted_tokens"] > 0  # opponent 2 promoted it back
        assert snap["host_hit_rate"] > 0
        eng._prefix.tiers.check_invariants()
        eng._allocator.check_invariants()

    def test_restart_rehydrates_from_store(self, tmp_path):
        from adversarial_spec_tpu.engine.mock import MockEngine

        kvtier.configure(
            enabled=True, host_mb=16, store_dir=str(tmp_path / "kv")
        )
        prefix_mod.configure(enabled=True, max_pages=0)
        prefix_mod.reset_stats()
        kvtier.reset_stats()
        eng_a = MockEngine()
        _mock_round(eng_a, self.DOC, 1)
        assert kvtier.stats.store_writes > 0
        # The restart: a FRESH engine (empty radix, empty host tier)
        # sharing only the store directory.
        before = prefix_mod.stats.prefilled_tokens
        eng_b = MockEngine()
        out = _mock_round(eng_b, self.DOC, 1)
        rehydration_prefill = prefix_mod.stats.prefilled_tokens - before
        snap = kvtier.snapshot()
        assert snap["rehydrated_tokens"] > 0
        assert out[0].usage.cached_tokens >= snap["rehydrated_tokens"] // 2
        # The restarted engine prefilled only the unaligned tail.
        assert rehydration_prefill < len(self.DOC) // 4 // 4
        eng_b._prefix.tiers.check_invariants()

    def test_transcripts_identical_tier_on_off(self, tmp_path):
        from adversarial_spec_tpu.engine.mock import MockEngine

        texts = {}
        for on in (True, False):
            kvtier.configure(
                enabled=on,
                host_mb=16,
                store_dir=str(tmp_path / "kv") if on else "",
            )
            prefix_mod.configure(enabled=True, max_pages=16)
            prefix_mod.reset_stats()
            kvtier.reset_stats()
            eng = MockEngine()
            texts[on] = [
                [c.text for c in _mock_round(eng, self.DOC, rnd)]
                for rnd in (1, 2)
            ]
        assert texts[True] == texts[False]

    def test_deterministic_stats_across_runs(self, tmp_path):
        from adversarial_spec_tpu.engine.mock import MockEngine

        snaps = []
        for rep in range(2):
            kvtier.configure(
                enabled=True,
                host_mb=16,
                store_dir=str(tmp_path / f"kv{rep}"),
            )
            prefix_mod.configure(enabled=True, max_pages=16)
            prefix_mod.reset_stats()
            kvtier.reset_stats()
            eng = MockEngine()
            for rnd in (1, 2):
                _mock_round(eng, self.DOC, rnd)
            snap = kvtier.stats.snapshot()
            snap.pop("swap_in_s")
            snap.pop("swap_out_s")
            snaps.append(snap)
        assert snaps[0] == snaps[1]


def _drain_rounds(params, cfg, *, rounds, prompt, cap_pages, max_new=8):
    """Drive a growing-prompt workload through a fresh batcher; returns
    (per-round token lists, per-round prefilled, batcher)."""
    from adversarial_spec_tpu.engine.scheduler import (
        ContinuousBatcher,
        SchedRequest,
    )

    prefix_mod.configure(enabled=True, max_pages=cap_pages)
    b = ContinuousBatcher(
        params, cfg, max_batch=2, max_new_cap=max_new, page_size=16,
        prefix_cache=True,
    )
    doc = list(prompt)
    toks, prefilled = [], []
    for r in range(rounds):
        before = prefix_mod.stats.prefilled_tokens
        for i in range(2):
            b.submit(
                SchedRequest(
                    req_id=i, prompt_ids=list(doc), max_new_tokens=max_new
                )
            )
        results = b.run_all()
        toks.append([x.tokens.tolist() for x in results])
        prefilled.append(prefix_mod.stats.prefilled_tokens - before)
        doc = doc + [((r * 13 + k) % 400) + 3 for k in range(16)]
        b.allocator.check_invariants()
        if b.tiers is not None:
            b.tiers.check_invariants()
    return toks, prefilled, b


class TestBatcherTier:
    PROMPT = [((i * 7) % 400) + 3 for i in range(96)]

    def test_pressure_parity_and_promotion(self, tiny_model):
        """Page-cap pressure: tier-off re-prefills the evicted tail,
        tier-on promotes it from host RAM — byte-identical greedy
        tokens, clean invariants, zero unexpected recompiles."""
        from adversarial_spec_tpu import obs

        params, cfg = tiny_model
        kvtier.configure(enabled=True, host_mb=16, store_dir="")
        prefix_mod.reset_stats()
        kvtier.reset_stats()
        obs.reset_stats()
        on_toks, on_pre, b = _drain_rounds(
            params, cfg, rounds=2, prompt=self.PROMPT, cap_pages=3
        )
        snap = kvtier.snapshot()
        assert snap["demoted_blocks"] > 0
        assert snap["promoted_tokens"] > 0
        assert obs.snapshot()["retrace"]["unexpected_recompiles"] == 0
        kvtier.configure(enabled=False)
        off_toks, off_pre, _ = _drain_rounds(
            params, cfg, rounds=2, prompt=self.PROMPT, cap_pages=3
        )
        assert on_toks == off_toks
        # The host tier strictly reduces re-prefill under pressure.
        assert sum(on_pre) < sum(off_pre)

    def test_the_first_demotion_compiles_nothing(self, tiny_model):
        """The one-page read an eviction demotes a block through is
        compiled at a batcher's first handoff (tiers armed), on the pool
        as the programs return it (with weights committed to a device, as
        a served model's are), so the first eviction, which comes
        whenever the pool first fills, compiles nothing in the middle of
        serving."""
        from adversarial_spec_tpu.engine.kvcache import _pool_jits
        from adversarial_spec_tpu.engine.scheduler import (
            ContinuousBatcher,
            SchedRequest,
        )

        params, cfg = tiny_model
        params = jax.device_put(params, jax.devices()[0])
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, seconds, **kw: compiles.append(event)
            if event.endswith("backend_compile_duration")
            else None
        )
        kvtier.configure(enabled=True, host_mb=16, store_dir="")
        kvtier.reset_stats()
        read = _pool_jits()[1]
        read.clear_cache()
        during = []
        try:
            # A page size no other test's batcher reads a page of.
            prefix_mod.configure(enabled=True, max_pages=3)
            b = ContinuousBatcher(
                params, cfg, max_batch=2, max_new_cap=8, page_size=32,
                prefix_cache=True,
            )
            fetch = b._fetch_page_kv

            def counted(page, n_tokens):
                before = len(compiles)
                out = fetch(page, n_tokens)
                during.append(len(compiles) - before)
                return out

            b.prefix_cache._kv_fetch = b._fetch_page_kv = counted
            doc = list(self.PROMPT) * 2
            for r in range(3):
                b.submit(
                    SchedRequest(
                        req_id=r,
                        prompt_ids=doc[: 128 + 32 * (r % 2)] + [7 + r],
                        max_new_tokens=8,
                    )
                )
                b.run_all()
            assert kvtier.snapshot()["demoted_blocks"] > 0
            # the first call is the handoff's; every eviction's after it
            assert len(during) > 1 and during[0] == 1
            assert not any(during[1:]), during
        finally:
            kvtier.configure(enabled=False)

    def test_restart_rehydrates_byte_identical(self, tiny_model, tmp_path):
        params, cfg = tiny_model
        store = str(tmp_path / "kv")
        kvtier.configure(enabled=True, host_mb=16, store_dir=store)
        prefix_mod.reset_stats()
        kvtier.reset_stats()
        _drain_rounds(params, cfg, rounds=1, prompt=self.PROMPT, cap_pages=0)
        # Restart: a fresh batcher (new pool + radix) over the same store.
        kvtier.reset_stats()
        warm_toks, warm_pre, b = _drain_rounds(
            params, cfg, rounds=1, prompt=self.PROMPT, cap_pages=0
        )
        snap = kvtier.snapshot()
        assert snap["rehydrated_tokens"] > 0
        kvtier.configure(enabled=False)
        cold_toks, cold_pre, _ = _drain_rounds(
            params, cfg, rounds=1, prompt=self.PROMPT, cap_pages=0
        )
        assert warm_toks == cold_toks  # rehydrated KV == recomputed KV
        assert sum(warm_pre) < sum(cold_pre)

    def test_lost_race_degrades_to_prefill(self, tiny_model):
        """A host entry evicted between lookup and promotion must fall
        back to prefill (recomputed_blocks counts it) with identical
        output — the correctness escape hatch."""
        params, cfg = tiny_model
        from adversarial_spec_tpu.engine.scheduler import (
            ContinuousBatcher,
            SchedRequest,
        )

        kvtier.configure(enabled=True, host_mb=16, store_dir="")
        prefix_mod.configure(enabled=True, max_pages=3)
        kvtier.reset_stats()
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=8, page_size=16,
            prefix_cache=True,
        )
        b.submit(
            SchedRequest(
                req_id=0, prompt_ids=list(self.PROMPT), max_new_tokens=8
            )
        )
        ref = b.run_all()
        assert b.tiers.host_resident > 0
        # Sabotage the race: empty the host tier after lookups would
        # have seen it. materialize() must report the loss.
        b.tiers.host.clear()
        b.submit(
            SchedRequest(
                req_id=0, prompt_ids=list(self.PROMPT), max_new_tokens=8
            )
        )
        out = b.run_all()
        assert out[0].tokens.tolist() == ref[0].tokens.tolist()
        b.allocator.check_invariants()
        b.tiers.check_invariants()

    def test_chaos_kv_swap_evicts_only_waiting_slot(
        self, tiny_model, tmp_path
    ):
        """``kv_swap`` injected mid-promotion: the co-resident request
        finishes untouched, the faulted request reports the injected
        kind at the kv_swap seam, both tiers stay invariant-clean, and
        the auto-dumped JSONL reconstructs the swap + fault."""
        from adversarial_spec_tpu import obs
        from adversarial_spec_tpu.engine.scheduler import (
            ContinuousBatcher,
            SchedRequest,
        )
        from adversarial_spec_tpu.resilience import injector

        params, cfg = tiny_model
        events_out = tmp_path / "ev.jsonl"
        obs.configure(events_out=str(events_out))
        kvtier.configure(enabled=True, host_mb=16, store_dir="")
        prefix_mod.configure(enabled=True, max_pages=3)
        kvtier.reset_stats()
        b = ContinuousBatcher(
            params, cfg, max_batch=2, max_new_cap=8, page_size=16,
            prefix_cache=True,
        )
        # Round 1 populates the host tier (cap eviction demotes).
        b.submit(
            SchedRequest(
                req_id=0, prompt_ids=list(self.PROMPT), max_new_tokens=8
            )
        )
        b.run_all()
        assert b.tiers.host_resident > 0
        # Round 2: the second promotion attempt faults (after=1 lets
        # block 1 promote first, so an in-flight swap is genuinely
        # abandoned mid-run).
        injector.install(
            injector.FaultInjector(
                injector.parse_chaos_spec("bug@kv_swap:after=1:times=1")
            )
        )
        try:
            for i in range(2):
                b.submit(
                    SchedRequest(
                        req_id=i,
                        prompt_ids=list(self.PROMPT),
                        max_new_tokens=8,
                    )
                )
            results = b.run_all()
        finally:
            injector.install(None)
        by_id = {r.req_id: r for r in results}
        # Exactly one request faulted (bug = permanent, no requeue) and
        # the co-resident finished with real tokens.
        faulted = [r for r in results if r.error]
        clean = [r for r in results if not r.error]
        assert len(faulted) == 1 and len(clean) == 1
        assert faulted[0].fault_kind == "bug"
        assert clean[0].n_generated > 0
        assert len(by_id) == 2
        b.allocator.check_invariants()
        b.tiers.check_invariants()
        b.prefix_cache.allocator.check_invariants()
        # The fault auto-dump reconstructs the story: SwapEvents for the
        # demotions/promotions and a FaultEvent at the kv_swap seam.
        dump = tmp_path / "ev.fault.jsonl"
        assert dump.exists()
        events = [json.loads(l) for l in dump.read_text().splitlines()]
        from adversarial_spec_tpu.obs.events import validate_event

        assert all(validate_event(e) == [] for e in events)
        assert any(e["type"] == "swap" for e in events)
        faults = [e for e in events if e["type"] == "fault"]
        assert any(e["seam"] == "kv_swap" for e in faults)


class TestCliPlumbing:
    def _run(self, argv, monkeypatch, capsys, stdin="# Spec\nbody\n"):
        import io
        import sys as _sys

        from adversarial_spec_tpu import cli

        monkeypatch.setattr(_sys, "stdin", io.StringIO(stdin))
        rc = cli.main(argv)
        out = capsys.readouterr().out
        return rc, out

    def test_flags_reach_config_and_perf_block(
        self, monkeypatch, capsys, tmp_path
    ):
        # Restore the production env default (conftest pins the suite
        # to ADVSPEC_KV_TIER=0 for wall budget; this test IS the
        # default's coverage).
        monkeypatch.delenv("ADVSPEC_KV_TIER", raising=False)
        store = str(tmp_path / "kv")
        rc, out = self._run(
            [
                "critique",
                "--models",
                "mock://critic",
                "--json",
                "--kv-host-mb",
                "7",
                "--kv-store-dir",
                store,
            ],
            monkeypatch,
            capsys,
        )
        assert rc == 0
        payload = json.loads(out)
        tier = payload["perf"]["kv_tier"]
        assert tier["enabled"] is True
        assert tier["host_mb"] == 7
        assert tier["store_dir"] == store
        assert tier["store_writes"] > 0  # write-through persisted blocks

    def test_no_kv_tier_disables_and_does_not_leak(
        self, monkeypatch, capsys
    ):
        monkeypatch.delenv("ADVSPEC_KV_TIER", raising=False)
        rc, out = self._run(
            ["critique", "--models", "mock://critic", "--json",
             "--no-kv-tier"],
            monkeypatch,
            capsys,
        )
        assert rc == 0
        assert json.loads(out)["perf"]["kv_tier"]["enabled"] is False
        # The next invocation re-resolves to env defaults: no leak.
        rc, out = self._run(
            ["critique", "--models", "mock://critic", "--json"],
            monkeypatch,
            capsys,
        )
        assert rc == 0
        tier = json.loads(out)["perf"]["kv_tier"]
        assert tier["enabled"] is True
        assert tier["host_mb"] == kvtier.DEFAULT_HOST_MB

    def test_env_defaults_respected(self, monkeypatch, capsys, tmp_path):
        monkeypatch.setenv("ADVSPEC_KV_TIER", "0")
        rc, out = self._run(
            ["critique", "--models", "mock://critic", "--json"],
            monkeypatch,
            capsys,
        )
        assert rc == 0
        assert json.loads(out)["perf"]["kv_tier"]["enabled"] is False


class TestObsDumpTimeline:
    def test_swap_events_validate_and_annotate_timeline(self, tmp_path):
        """SwapEvent rides the EVENT_FIELDS schema and the occupancy
        timeline annotates per-tier residency."""
        from adversarial_spec_tpu import obs
        from adversarial_spec_tpu.engine.mock import MockEngine

        from tools.obs_dump import load_events, occupancy_timeline

        kvtier.configure(enabled=True, host_mb=16, store_dir="")
        prefix_mod.configure(enabled=True, max_pages=16)
        obs.reset_stats()
        eng = MockEngine()
        _mock_round(eng, TestMockTier.DOC, 1)
        path = tmp_path / "ev.jsonl"
        obs.dump_events(str(path))
        events, errors = load_events(str(path))
        assert errors == []
        assert any(e["type"] == "swap" for e in events)
        timeline = occupancy_timeline(events)
        assert "host=" in timeline and "disk=" in timeline
        assert "demote" in timeline


class TestFlushThreshold:
    """``--kv-flush-blocks``: write-through flush every N enqueued
    blocks instead of only at settle — the disagg publication window
    bound (docs/kv_tiering.md)."""

    def _tiers(self, tmp_path):
        kvtier.reset_stats()
        return kvtier.TieredStore(
            None, kvtier.DiskStore(str(tmp_path / "store"), "fp-a")
        )

    def _payload(self):
        return {"k": np.zeros(2, dtype=np.float32)}

    def test_env_default(self, monkeypatch):
        monkeypatch.delenv("ADVSPEC_KV_FLUSH_BLOCKS", raising=False)
        assert kvtier.env_flush_blocks() == 0  # settle-only
        monkeypatch.setenv("ADVSPEC_KV_FLUSH_BLOCKS", "8")
        assert kvtier.env_flush_blocks() == 8
        monkeypatch.setenv("ADVSPEC_KV_FLUSH_BLOCKS", "junk")
        assert kvtier.env_flush_blocks() == 0

    def test_settle_only_by_default(self, tmp_path):
        tiers = self._tiers(tmp_path)
        for i in range(6):
            tiers.enqueue_store(
                kvtier.chain_hash("", (i,)), (i,), self._payload()
            )
        assert kvtier.stats.store_writes == 0  # nothing mid-drain
        assert tiers.settle() == 6
        assert kvtier.stats.store_writes == 6

    def test_threshold_flushes_mid_drain(self, tmp_path):
        kvtier.configure(flush_blocks=3)
        tiers = self._tiers(tmp_path)
        for i in range(5):
            tiers.enqueue_store(
                kvtier.chain_hash("", (i,)), (i,), self._payload()
            )
        # The 3rd enqueue crossed the threshold: one flush of 3.
        assert kvtier.stats.store_writes == 3
        assert tiers.settle() == 2  # the tail still settles
        assert kvtier.stats.store_writes == 5

    def test_threshold_flush_never_resolves_lazies(self, tmp_path):
        """A threshold flush must not sync the device mid-drain: lazy
        payloads stay queued for settle (the sanctioned point)."""
        kvtier.configure(flush_blocks=2)
        tiers = self._tiers(tmp_path)
        calls = []

        def lazy():
            calls.append(1)
            return self._payload()

        tiers.enqueue_store(kvtier.chain_hash("", (1,)), (1,), lazy)
        tiers.enqueue_store(
            kvtier.chain_hash("", (2,)), (2,), self._payload()
        )
        # Threshold crossed: the plain payload flushed, the lazy held.
        assert kvtier.stats.store_writes == 1
        assert calls == []
        assert tiers.settle() == 1  # lazy resolves only at settle
        assert calls == [1]
        assert kvtier.stats.store_writes == 2
