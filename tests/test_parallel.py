"""Mesh, sharding, and ring-attention tests on the virtual 8-device CPU
mesh (SURVEY §4: the host-platform device-count trick — multi-chip
semantics in one process; the reference has no multi-node story to copy)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine.generate import generate
from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.parallel.mesh import DP, SP, TP, make_mesh, mesh_shape_from_spec
from adversarial_spec_tpu.parallel.ring import ring_attention
from adversarial_spec_tpu.parallel.sharding import (
    param_shardings,
    shard_params,
)


@pytest.fixture(scope="module", autouse=True)
def _needs_8_devices():
    if len(jax.devices()) < 8:
        pytest.skip("requires 8 virtual devices (see conftest XLA_FLAGS)")


class TestMeshShape:
    def test_defaults_fill_dp(self):
        assert mesh_shape_from_spec({"tp": 2}, 8) == {DP: 4, TP: 2, SP: 1}

    def test_empty_spec_all_dp(self):
        assert mesh_shape_from_spec({}, 8) == {DP: 8, TP: 1, SP: 1}

    def test_explicit_full(self):
        assert mesh_shape_from_spec({"dp": 2, "tp": 2, "sp": 2}, 8) == {
            DP: 2,
            TP: 2,
            SP: 2,
        }

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="does not divide"):
            mesh_shape_from_spec({"tp": 3}, 8)

    def test_overcommit_raises(self):
        with pytest.raises(ValueError, match="!= device count"):
            mesh_shape_from_spec({"dp": 8, "tp": 2}, 8)

    def test_unknown_axis_raises(self):
        with pytest.raises(ValueError, match="unknown mesh axes"):
            mesh_shape_from_spec({"pp": 2}, 8)

    def test_make_mesh_axis_names(self):
        mesh = make_mesh({"tp": 2})
        assert set(mesh.axis_names) == {DP, SP, TP}
        assert mesh.shape[TP] == 2


class TestShardedParams:
    def test_tp_shards_heads_and_ffn(self):
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        mesh = make_mesh({"tp": 2})
        sharded = shard_params(mesh, params)
        # Column-parallel: wq last dim split over tp.
        wq_shard = sharded["layers"]["wq"].sharding
        assert wq_shard.spec == jax.sharding.PartitionSpec(None, None, TP)
        # Row-parallel: wo middle dim split.
        assert sharded["layers"]["wo"].sharding.spec == (
            jax.sharding.PartitionSpec(None, TP, None)
        )
        # Values unchanged by sharding.
        np.testing.assert_array_equal(
            np.asarray(sharded["layers"]["wq"]),
            np.asarray(params["layers"]["wq"]),
        )

    def test_materialize_random_respects_tp_rules(self):
        """The random-checkpoint branch builds every weight straight
        into its tp sharding (a miss silently replicates every param —
        OOM at 70B/tp=8)."""
        from adversarial_spec_tpu.engine.loader import materialize_params

        mesh = make_mesh({"tp": 2})
        params, _ = materialize_params(
            "random",
            "llama",
            "tiny",
            dtype=jnp.float32,
            mesh=mesh,
        )
        assert params["layers"]["wq"].sharding.spec == (
            jax.sharding.PartitionSpec(None, None, TP)
        )
        assert params["layers"]["wo"].sharding.spec == (
            jax.sharding.PartitionSpec(None, TP, None)
        )
        assert params["lm_head"].sharding.spec == (
            jax.sharding.PartitionSpec(None, TP)
        )

    def test_sharding_tree_matches_params_tree(self):
        cfg = get_config("qwen2", "tiny")  # includes biases
        params = T.init_params(jax.random.key(0), cfg)
        mesh = make_mesh({"tp": 2})
        shardings = param_shardings(mesh, params)
        assert jax.tree_util.tree_structure(
            shardings
        ) == jax.tree_util.tree_structure(params)


class TestShardedGenerate:
    @pytest.mark.parametrize(
        "mesh_spec", [{"tp": 2}, {"dp": 4, "tp": 2}, {"dp": 8}]
    )
    def test_sharded_matches_single_device(self, mesh_spec):
        """Greedy decode on a dp×tp mesh must reproduce the single-device
        tokens exactly — numerical parity across sharding layouts is the
        correctness bar for the TP/DP implementation."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3], [2, 6], [8, 8, 8], [4]]
        kw = dict(max_new_tokens=6, eos_ids=[], greedy=True)

        ref = generate(params, cfg, prompts, **kw)

        mesh = make_mesh(mesh_spec)
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(sharded, cfg, prompts, mesh=mesh, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)
        np.testing.assert_array_equal(ref.n_generated, out.n_generated)

    def test_batch_not_multiple_of_dp(self):
        """3 opponents on dp=4: rows padded internally, result unpadded."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 2], [3, 4, 5], [6]]
        ref = generate(
            params, cfg, prompts, max_new_tokens=4, eos_ids=[], greedy=True
        )
        mesh = make_mesh({"dp": 4, "tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded,
                cfg,
                prompts,
                max_new_tokens=4,
                eos_ids=[],
                greedy=True,
                mesh=mesh,
            )
        assert out.tokens.shape[0] == 3
        np.testing.assert_array_equal(ref.tokens, out.tokens)


class TestSequenceParallelPrefill:
    def test_sp_prefill_matches_dense(self):
        """Full-model sequence-parallel prefill (ring attention inside the
        layer scan) must reproduce the dense single-device prefill: same
        last-position logits, same KV cache contents."""
        from adversarial_spec_tpu.engine.generate import prefill_chunk
        from adversarial_spec_tpu.parallel.sp import (
            reshard_cache_for_decode,
            sp_prefill,
        )

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        mesh = make_mesh({"sp": 4})
        B, S = 2, 32
        tokens = jax.random.randint(
            jax.random.key(5), (B, S), 0, cfg.vocab_size
        )
        pad_lens = jnp.array([3, 0], jnp.int32)
        # Left-pad semantics: zero out the pad slots.
        tokens = jnp.where(
            jnp.arange(S)[None, :] < pad_lens[:, None], 0, tokens
        )

        with mesh:
            logits_sp, cache_sp = sp_prefill(params, cfg, tokens, pad_lens, mesh)

        dense_cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
        dense_cache, last_logits = prefill_chunk(
            params, cfg, tokens, pad_lens, dense_cache, jnp.int32(0)
        )
        np.testing.assert_allclose(
            np.asarray(logits_sp),
            np.asarray(last_logits),
            rtol=2e-4,
            atol=2e-4,
        )
        np.testing.assert_allclose(
            np.asarray(cache_sp["k"]),
            np.asarray(dense_cache["k"]),
            rtol=2e-4,
            atol=2e-4,
        )
        np.testing.assert_allclose(
            np.asarray(cache_sp["v"]),
            np.asarray(dense_cache["v"]),
            rtol=2e-4,
            atol=2e-4,
        )

        with mesh:
            resharded = reshard_cache_for_decode(cache_sp, mesh, S + 8)
        assert resharded["k"].shape[3] == S + 8
        np.testing.assert_allclose(
            np.asarray(resharded["k"][..., :S, :]),
            np.asarray(dense_cache["k"]),
            rtol=2e-4,
            atol=2e-4,
        )

    def test_generate_end_to_end_on_sp_mesh(self):
        """generate() on an sp>1 mesh routes prefill through the
        sequence-parallel path and must reproduce single-device tokens."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8]]
        kw = dict(max_new_tokens=6, eos_ids=[], greedy=True)
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"sp": 4, "dp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(sharded, cfg, prompts, mesh=mesh, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_sp_times_tp_matches_dense(self):
        """tp×sp composition (the config-5 shape: TP judge + long
        context): manual-collective TP inside the sp shard_map must
        reproduce dense single-device prefill exactly."""
        from adversarial_spec_tpu.engine.generate import prefill_chunk
        from adversarial_spec_tpu.parallel.sp import sp_prefill

        cfg = get_config("llama", "tiny")  # 4 heads, 2 kv heads
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        mesh = make_mesh({"sp": 4, "tp": 2, "dp": 1})
        sharded = shard_params(mesh, params)
        B, S = 2, 32
        tokens = jax.random.randint(
            jax.random.key(7), (B, S), 0, cfg.vocab_size
        )
        pad_lens = jnp.array([5, 0], jnp.int32)
        tokens = jnp.where(
            jnp.arange(S)[None, :] < pad_lens[:, None], 0, tokens
        )
        with mesh:
            logits_sp, cache_sp = sp_prefill(
                sharded, cfg, tokens, pad_lens, mesh
            )
        dense_cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
        dense_cache, ref_logits = prefill_chunk(
            params, cfg, tokens, pad_lens, dense_cache, jnp.int32(0)
        )
        np.testing.assert_allclose(
            np.asarray(logits_sp), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(cache_sp["k"]),
            np.asarray(dense_cache["k"]),
            rtol=2e-4,
            atol=2e-4,
        )

    def test_generate_on_sp_tp_dp_mesh(self):
        """All three axes at once through the public generate()."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3], [2, 6, 4, 8]]
        kw = dict(max_new_tokens=4, eos_ids=[], greedy=True)
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"sp": 2, "tp": 2, "dp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(sharded, cfg, prompts, mesh=mesh, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_speculative_decode_on_sp_mesh_matches_dense(self):
        """The 16k-context config's decode lever:
        after sp prefill reshards the cache into the standard decode
        layout, speculation runs as one GSPMD program (sp axis
        replicated) and must reproduce single-device greedy tokens.
        max_new > GAMMA+1 so the speculative path actually engages;
        repetitive prompts so drafts actually accept."""
        from adversarial_spec_tpu.engine.speculative import GAMMA

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        base = [3, 7, 11, 5] * 4
        prompts = [base + [9], base + [13]]
        # Budget derived from GAMMA so an ADVSPEC_GAMMA override can't
        # silently disable the speculative path under test.
        kw = dict(max_new_tokens=2 * GAMMA + 8, eos_ids=[], greedy=True)
        ref = generate(params, cfg, prompts, speculative=False, **kw)
        mesh = make_mesh({"sp": 4, "dp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh, speculative=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_speculative_decode_on_sp_tp_mesh_matches_dense(self):
        """Speculation composes with sp×tp×dp (config-5 shape)."""
        from adversarial_spec_tpu.engine.speculative import GAMMA

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        base = [2, 6, 4, 8] * 4
        prompts = [base, base[::-1]]
        kw = dict(max_new_tokens=2 * GAMMA + 4, eos_ids=[], greedy=True)
        ref = generate(params, cfg, prompts, speculative=False, **kw)
        mesh = make_mesh({"sp": 2, "tp": 2, "dp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh, speculative=True, **kw
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_sp_tp_indivisible_heads_raises(self):
        from adversarial_spec_tpu.parallel.sp import sp_prefill

        cfg = get_config("llama", "tiny")  # 2 kv heads
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        mesh = make_mesh({"sp": 2, "tp": 4})
        tokens = jnp.zeros((1, 32), jnp.int32)
        with pytest.raises(ValueError, match="must divide"):
            sp_prefill(params, cfg, tokens, jnp.zeros((1,), jnp.int32), mesh)

    @pytest.mark.parametrize("family", ["mistral", "gemma2"])
    def test_sp_prefill_windowed_families(self, family):
        """Sliding windows (incl. gemma-2's alternating layers) inside the
        ring must reproduce dense prefill exactly. Window shrunk to 8 so
        it genuinely truncates across block boundaries (blocks of 8 at
        sp=4, S=32)."""
        from dataclasses import replace as dc_replace

        from adversarial_spec_tpu.engine.generate import prefill_chunk
        from adversarial_spec_tpu.parallel.sp import sp_prefill

        cfg = dc_replace(get_config(family, "tiny"), sliding_window=8)
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        mesh = make_mesh({"sp": 4})
        B, S = 2, 32
        tokens = jax.random.randint(
            jax.random.key(9), (B, S), 0, cfg.vocab_size
        )
        pad_lens = jnp.array([3, 0], jnp.int32)
        tokens = jnp.where(
            jnp.arange(S)[None, :] < pad_lens[:, None], 0, tokens
        )
        with mesh:
            logits_sp, cache_sp = sp_prefill(
                params, cfg, tokens, pad_lens, mesh
            )
        dense_cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
        dense_cache, ref_logits = prefill_chunk(
            params, cfg, tokens, pad_lens, dense_cache, jnp.int32(0)
        )
        np.testing.assert_allclose(
            np.asarray(logits_sp), np.asarray(ref_logits), rtol=3e-4, atol=3e-4
        )
        np.testing.assert_allclose(
            np.asarray(cache_sp["k"]),
            np.asarray(dense_cache["k"]),
            rtol=3e-4,
            atol=3e-4,
        )
        np.testing.assert_allclose(
            np.asarray(cache_sp["v"]),
            np.asarray(dense_cache["v"]),
            rtol=3e-4,
            atol=3e-4,
        )


class TestRingAttention:
    def _dense_ref(self, q, k, v, causal=True):
        B, S, H, D = q.shape
        Hkv = k.shape[2]
        g = H // Hkv
        qg = q.reshape(B, S, Hkv, g, D)
        s = jnp.einsum("bshgd,bthd->bhgst", qg, k) / math.sqrt(D)
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhgst,bthd->bshgd", p, v).reshape(B, S, H, D)

    @pytest.mark.parametrize("sp", [2, 4, 8])
    def test_causal_matches_dense(self, sp):
        mesh = make_mesh({"sp": sp})
        B, S, H, Hkv, D = 2, 32, 4, 2, 16
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        out = ring_attention(q, k, v, mesh, causal=True)
        ref = self._dense_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5
        )

    def test_non_causal_matches_dense(self):
        mesh = make_mesh({"sp": 4})
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (1, 16, 2, 8), jnp.float32)
        k = jax.random.normal(ks[1], (1, 16, 2, 8), jnp.float32)
        v = jax.random.normal(ks[2], (1, 16, 2, 8), jnp.float32)
        out = ring_attention(q, k, v, mesh, causal=False)
        ref = self._dense_ref(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5
        )

    def test_indivisible_sequence_raises(self):
        mesh = make_mesh({"sp": 4})
        x = jnp.zeros((1, 30, 2, 8))
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(x, x, x, mesh)

    def test_matches_jitted(self):
        """Ring attention must be jittable (it runs inside prefill)."""
        mesh = make_mesh({"sp": 4})
        ks = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(ks[0], (1, 16, 2, 8), jnp.float32)
        k = jax.random.normal(ks[1], (1, 16, 2, 8), jnp.float32)
        v = jax.random.normal(ks[2], (1, 16, 2, 8), jnp.float32)
        jit_out = jax.jit(
            lambda a, b, c: ring_attention(a, b, c, mesh, causal=True)
        )(q, k, v)
        eager = ring_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(jit_out), np.asarray(eager), rtol=1e-6, atol=1e-6
        )


class TestLongContext16k:
    """16k-token sp prefill numerics (BASELINE
    config 5's context scale). A thin 2-layer model keeps the CPU cost
    tractable; the sequence length is the real thing."""

    @pytest.mark.slow
    def test_sp_prefill_matches_chunked_at_16k(self):
        """Ring-attention sp prefill vs the chunked dense reference at a
        REAL 16384-token sequence (a 1-layer thin model keeps the S²
        attention tractable on CPU; ~80 s)."""
        from dataclasses import replace

        from adversarial_spec_tpu.engine.generate import prefill_chunk
        from adversarial_spec_tpu.parallel.sp import sp_prefill

        S = 16384
        cfg = replace(
            get_config("llama", "tiny"),
            n_layers=1,
            n_heads=2,
            n_kv_heads=2,
            dim=128,
            ffn_dim=256,
            max_seq_len=S + 64,
        )
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(5).integers(3, cfg.vocab_size, (1, S)),
            jnp.int32,
        )
        pads = jnp.zeros((1,), jnp.int32)

        mesh = make_mesh({"sp": 4, "dp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            logits_sp, _ = sp_prefill(sharded, cfg, tokens, pads, mesh)

        cache = T.init_cache(cfg, 1, S, dtype=jnp.float32)
        last = None
        for ci in range(0, S, 1024):
            cache, last = prefill_chunk(
                params, cfg, tokens[:, ci : ci + 1024], pads, cache,
                jnp.int32(ci),
            )
        np.testing.assert_allclose(
            np.asarray(logits_sp), np.asarray(last), rtol=3e-4, atol=3e-4
        )


class TestWindowedRingEarlyOut:
    """Sliding-window layers stop the ring after ring_hops hops instead
    of masking dead compute (NOTES round-2 shortcut)."""

    def test_hop_bound_formula(self):
        from adversarial_spec_tpu.parallel.ring import ring_hops

        # Global attention or non-causal: every hop can contribute.
        assert ring_hops(8, 512, 0, True) == 8
        assert ring_hops(8, 512, 64, False) == 8
        # Window within one block: diagonal + one predecessor.
        assert ring_hops(8, 512, 8, True) == 2
        assert ring_hops(8, 512, 512, True) == 2
        # Window a hair past a block boundary pulls in one more hop.
        assert ring_hops(8, 512, 513, True) == 2
        assert ring_hops(8, 512, 514, True) == 3
        # Huge windows clamp at sp.
        assert ring_hops(4, 512, 10**6, True) == 4
        # Traced window (gemma2 alternation) gives the same numbers.
        import jax.numpy as jnp

        assert int(ring_hops(8, 512, jnp.int32(8), True)) == 2
        assert int(ring_hops(8, 512, jnp.int32(0), True)) == 8

    def test_windowed_ring_matches_full_ring(self):
        """Early-out must not change the result: windowed ring output ==
        the same ring forced to run all sp hops (window as mask only)."""
        if len(jax.devices()) < 4:
            pytest.skip("requires 4 virtual devices")
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from adversarial_spec_tpu.parallel import ring as ring_mod
        from adversarial_spec_tpu.parallel.mesh import make_mesh

        B, S, H, Hkv, D, W = 2, 64, 4, 2, 16, 7
        ks = jax.random.split(jax.random.key(21), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        mesh = make_mesh({"sp": 4, "dp": 2})
        spec = P(None, "sp", None, None)

        def run(window):
            def local(qb, kb, vb):
                return ring_mod.ring_attention_local(
                    qb, kb, vb, 4, causal=True, window=window
                )

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)

        early = run(W)  # static int window → shortened fori_loop
        # Force all hops by passing the window traced-but-equal: trip
        # count identical math, exercises the traced path too.
        traced = run(jnp.int32(W))
        np.testing.assert_allclose(
            np.asarray(early), np.asarray(traced), rtol=1e-6, atol=1e-6
        )
        # And against the full-hop reference: window big enough to keep
        # all hops, then mask manually via a huge-window run on the
        # windowed mask — i.e., compare W-windowed early-out vs the old
        # behavior (all hops, W mask) reconstructed with hops forced to
        # sp by monkeypatching ring_hops.
        orig = ring_mod.ring_hops
        ring_mod.ring_hops = lambda sp_, b_, w_, c_: sp_
        try:
            full = run(W)
        finally:
            ring_mod.ring_hops = orig
        np.testing.assert_allclose(
            np.asarray(early), np.asarray(full), rtol=1e-6, atol=1e-6
        )


class TestSpInt8:
    def test_generate_int8_on_sp_mesh(self):
        """kv_dtype=int8 on an sp mesh: prefill rides the ring at full
        precision, the decode cache quantizes at the reshard boundary —
        greedy tokens must match the single-device int8 run (identical
        prompt-KV quantization; decode math identical)."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3, 7, 2], [4, 4, 8]]
        kw = dict(
            max_new_tokens=6, eos_ids=[], greedy=True,
            kv_dtype="int8", speculative=False,
        )
        ref = generate(params, cfg, prompts, **kw)
        mesh = make_mesh({"sp": 4, "dp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(sharded, cfg, prompts, mesh=mesh, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)
