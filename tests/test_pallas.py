"""Pallas kernel tests under interpret mode (CPU) against jnp references,
plus end-to-end decode parity when the fused kernel is routed into the
generation loop."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine.generate import generate
from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.ops.pallas_decode import decode_attention
from adversarial_spec_tpu.ops.pallas_paged import paged_decode_attention


def test_pick_block_t_refuses_indivisible_T():
    """No silent [Hkv, T, D] VMEM-exploding fallback for direct callers
    with a non-8-multiple cache length (ADVICE r3)."""
    from adversarial_spec_tpu.ops.pallas_decode import _pick_block_t

    assert _pick_block_t(1280, 8, 64, 2) in (512, 256, 128)
    with pytest.raises(ValueError, match="no block_t divisor"):
        _pick_block_t(1283, 8, 64, 2)


def _dense_ref(q, k, v, bounds, attn_softcap=0.0):
    B, Hq, D = q.shape
    Hkv, T_ = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k) / math.sqrt(D)
    if attn_softcap > 0:
        s = jnp.tanh(s / attn_softcap) * attn_softcap
    slot = jnp.arange(T_)
    valid = (slot[None, :] >= bounds[:, 0:1]) & (slot[None, :] < bounds[:, 1:2])
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhgt,bhtd->bhgd", p, v).reshape(B, Hq, D)


class TestDecodeKernel:
    def _rand(self, B=3, Hq=8, Hkv=2, D=64, T_=512, dtype=jnp.float32):
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), dtype)
        k = jax.random.normal(ks[1], (B, Hkv, T_, D), dtype)
        v = jax.random.normal(ks[2], (B, Hkv, T_, D), dtype)
        return q, k, v

    def test_matches_dense(self):
        q, k, v = self._rand()
        bounds = jnp.array([[0, 100], [37, 412], [5, 6]], jnp.int32)
        out = decode_attention(q, k, v, bounds, interpret=True)
        ref = _dense_ref(q, k, v, bounds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_softcap(self):
        q, k, v = self._rand(T_=256)
        bounds = jnp.array([[0, 256], [0, 128], [10, 200]], jnp.int32)
        out = decode_attention(q, k, v, bounds, attn_softcap=50.0, interpret=True)
        ref = _dense_ref(q, k, v, bounds, attn_softcap=50.0)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_mha_no_gqa(self):
        q, k, v = self._rand(Hq=4, Hkv=4, T_=256)
        bounds = jnp.array([[0, 256], [0, 10], [100, 256]], jnp.int32)
        out = decode_attention(q, k, v, bounds, interpret=True)
        ref = _dense_ref(q, k, v, bounds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_single_valid_slot(self):
        """end-start == 1: softmax over one key must return exactly v."""
        q, k, v = self._rand(B=1, T_=256)
        bounds = jnp.array([[17, 18]], jnp.int32)
        out = decode_attention(q, k, v, bounds, interpret=True)
        g = 8 // 2
        expect = jnp.repeat(v[:, :, 17], g, axis=1).reshape(1, 8, 64)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-5
        )

    def test_non_block_aligned_window(self):
        """Bounds crossing block_t tile boundaries mask correctly."""
        q, k, v = self._rand(B=1, T_=512)
        bounds = jnp.array([[250, 270]], jnp.int32)  # spans block edge 256
        out = decode_attention(q, k, v, bounds, interpret=True)
        ref = _dense_ref(q, k, v, bounds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


class TestPagedKernel:
    def test_matches_gathered_dense(self):
        B, Hq, Hkv, D = 2, 8, 2, 64
        page_size, n_pages, P = 16, 32, 8
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        kp = jax.random.normal(ks[1], (n_pages, Hkv, page_size, D), jnp.float32)
        vp = jax.random.normal(ks[2], (n_pages, Hkv, page_size, D), jnp.float32)
        table = np.full((B, P), -1, np.int32)
        table[0, :3] = [3, 7, 1]
        table[1, 0] = 5
        bounds = jnp.array([[2, 40], [0, 9]], jnp.int32)

        out = paged_decode_attention(
            q, kp, vp, jnp.asarray(table), bounds, interpret=True
        )

        for b in range(B):
            pages = [p for p in table[b] if p > 0]
            k = jnp.concatenate([kp[p] for p in pages], 1)[None]
            v = jnp.concatenate([vp[p] for p in pages], 1)[None]
            ref = _dense_ref(q[b : b + 1], k, v, bounds[b : b + 1])
            np.testing.assert_allclose(
                np.asarray(out[b]), np.asarray(ref[0]), rtol=2e-5, atol=2e-5
            )

    def test_unmapped_rows_after_first_page(self):
        """A row using 1 of 8 table slots must ignore the -1 slots."""
        B, Hq, Hkv, D = 1, 4, 2, 64
        page_size, n_pages, P = 8, 4, 8
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        kp = jax.random.normal(ks[1], (n_pages, Hkv, page_size, D), jnp.float32)
        vp = jax.random.normal(ks[2], (n_pages, Hkv, page_size, D), jnp.float32)
        table = np.full((B, P), -1, np.int32)
        table[0, 0] = 2
        bounds = jnp.array([[0, 8]], jnp.int32)
        out = paged_decode_attention(
            q, kp, vp, jnp.asarray(table), bounds, interpret=True
        )
        ref = _dense_ref(q, kp[2][None], vp[2][None], bounds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_trash_page_zero_is_masked(self):
        """Physical page 0 is the reserved trash page (callers shift real
        ids +1): a table entry of 0 must contribute nothing, even when
        bounds would otherwise admit its slots. Kills a '> 0' → '>= 0'
        regression that every other case in this class would miss (their
        tables never contain 0)."""
        B, Hq, Hkv, D = 1, 4, 2, 64
        page_size, n_pages, P = 8, 4, 4
        ks = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        kp = jax.random.normal(ks[1], (n_pages, Hkv, page_size, D), jnp.float32)
        vp = jax.random.normal(ks[2], (n_pages, Hkv, page_size, D), jnp.float32)
        # Logical page 0 → physical 2 (real), logical page 1 → physical 0
        # (trash). Bounds cover both pages' slots.
        table = np.array([[2, 0, 0, 0]], np.int32)
        bounds = jnp.array([[0, 16]], jnp.int32)
        out = paged_decode_attention(
            q, kp, vp, jnp.asarray(table), bounds, interpret=True
        )
        # Reference attends ONLY to physical page 2's slots.
        ref = _dense_ref(q, kp[2][None], vp[2][None], jnp.array([[0, 8]]))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


class TestPallasInGenerate:
    @pytest.mark.parametrize("family", ["llama", "gemma2", "mistral"])
    def test_generate_parity_with_jnp_path(self, family):
        """Routing decode through the fused kernel must not change greedy
        tokens. Windowed families run with sliding_window=8 so the window
        start actually exceeds the pad boundary during decode (prompts pad
        to bucket 128, so cache_index - 8 + 1 > pad_len from the first
        decode steps) — otherwise the windowed and global paths would
        compute identical bounds and window bugs would pass unnoticed."""
        from dataclasses import replace

        cfg = get_config(family, "tiny")
        if cfg.sliding_window > 0:
            cfg = replace(cfg, sliding_window=8)
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3] * 4, [2, 6] * 5]
        # speculative=False: these tests target the shared-slot single-
        # query decode loop (decode_chunk_steps); the MQ/spec path has
        # its own parity tests in TestMultiQueryKernel.
        kw = dict(
            max_new_tokens=12, eos_ids=[], greedy=True, speculative=False
        )
        ref = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        out = generate(params, cfg, prompts, use_pallas_decode=True, **kw)
        np.testing.assert_array_equal(ref.tokens, out.tokens)

    def test_window_actually_truncates_in_this_setup(self):
        """Guard for the test above: with window=8 the pallas bounds start
        must differ between windowed and unwindowed configs (i.e. the
        window path is genuinely exercised, not vacuously equal)."""
        from dataclasses import replace

        cfg = get_config("mistral", "tiny")
        cfg_w = replace(cfg, sliding_window=8)
        cfg_g = replace(cfg, sliding_window=0)
        params = T.init_params(jax.random.key(0), cfg_w, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3] * 4]
        kw = dict(
            max_new_tokens=12, eos_ids=[], greedy=True, speculative=False
        )
        out_w = generate(params, cfg_w, prompts, use_pallas_decode=True, **kw)
        out_g = generate(params, cfg_g, prompts, use_pallas_decode=True, **kw)
        assert not np.array_equal(out_w.tokens, out_g.tokens)


class TestShardedPallasDecode:
    """decode_attention_tp: the fused kernel under shard_map (dp×tp).

    BASELINE configs 3-5 decode through Pallas instead
    of the jnp fallback. Parity on the virtual 8-device mesh is the
    correctness bar; interpret mode stands in for the Mosaic compile.
    """

    @pytest.fixture(autouse=True)
    def _needs_8_devices(self):
        if len(jax.devices()) < 8:
            pytest.skip("requires 8 virtual devices")

    def test_kernel_parity_on_mesh(self):
        from adversarial_spec_tpu.ops.pallas_decode import (
            decode_attention,
            decode_attention_tp,
        )
        from adversarial_spec_tpu.parallel.mesh import make_mesh

        B, Hq, Hkv, D, T_ = 4, 8, 2, 64, 256
        ks = jax.random.split(jax.random.key(7), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, T_, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, T_, D), jnp.float32)
        bounds = jnp.array(
            [[0, 256], [3, 100], [100, 256], [17, 18]], jnp.int32
        )
        ref = decode_attention(q, k, v, bounds, interpret=True)
        mesh = make_mesh({"dp": 4, "tp": 2})
        with mesh:
            out = decode_attention_tp(
                q, k, v, bounds, mesh, interpret=True
            )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("mesh_spec", [{"tp": 2}, {"dp": 4, "tp": 2}])
    def test_generate_parity_sharded_kernel_vs_jnp(self, mesh_spec):
        """Greedy decode through the shard_mapped kernel must reproduce
        the single-device jnp tokens on dp×tp meshes."""
        from adversarial_spec_tpu.engine.generate import generate
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        cfg = get_config("llama", "tiny")  # n_kv_heads=2 — tp=2 divides
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3], [2, 6], [8, 8, 8], [4]]
        kw = dict(max_new_tokens=6, eos_ids=[], greedy=True)

        ref = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        mesh = make_mesh(mesh_spec)
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, speculative=False, **kw,
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)


class TestInt8KernelTiles:
    """int8 KV dequant inside the fused kernel tiles:
    the int8 cache and the Pallas kernel are no longer mutually
    exclusive."""

    def test_kernel_matches_dequant_dense(self):
        B, Hq, Hkv, D, T_ = 2, 8, 2, 64, 256
        ks = jax.random.split(jax.random.key(9), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, T_, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, T_, D), jnp.float32)
        # Quantize exactly as the cache does (per-token-head symmetric).
        amax = jnp.max(jnp.abs(k), axis=-1, keepdims=True)
        ksc = jnp.maximum(amax, 1e-8) / 127.0
        k8 = jnp.clip(jnp.round(k / ksc), -127, 127).astype(jnp.int8)
        amax = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
        vsc = jnp.maximum(amax, 1e-8) / 127.0
        v8 = jnp.clip(jnp.round(v / vsc), -127, 127).astype(jnp.int8)
        bounds = jnp.array([[0, 200], [37, 256]], jnp.int32)

        out = decode_attention(
            q, k8, v8, bounds, interpret=True, k_scale=ksc, v_scale=vsc
        )
        # Reference: dense attention over the DEQUANTIZED cache.
        ref = _dense_ref(q, k8 * ksc, v8 * vsc, bounds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_generate_int8_pallas_matches_int8_jnp(self):
        """Greedy tokens through (int8 cache + fused kernel) must equal
        (int8 cache + jnp path) — same quantization, different attention
        implementation."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[3, 7, 11, 15], [2, 4]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            kv_dtype="int8", speculative=False,
        )
        jnp_path = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        kern = generate(params, cfg, prompts, use_pallas_decode=True, **kw)
        np.testing.assert_array_equal(jnp_path.tokens, kern.tokens)

    def test_generate_int8_on_mesh(self):
        """int8 KV + sharded fused kernel on a dp×tp mesh."""
        if len(jax.devices()) < 8:
            pytest.skip("requires 8 virtual devices")
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3], [2, 6], [8, 8, 8], [4]]
        kw = dict(
            max_new_tokens=6, eos_ids=[], greedy=True,
            kv_dtype="int8", speculative=False,
        )
        ref = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        mesh = make_mesh({"dp": 4, "tp": 2})
        sharded = shard_params(mesh, params)
        with mesh:
            out = generate(
                sharded, cfg, prompts, mesh=mesh,
                use_pallas_decode=True, **kw,
            )
        np.testing.assert_array_equal(ref.tokens, out.tokens)


class TestMultiQueryKernel:
    """decode_attention_mq: γ+1-wide speculative verification spans in
    one pass over the KV cache (reunifies speculation with the fused
    kernels — round-1's 'speculation forces jnp attention' shortcut)."""

    def test_matches_dense_per_query_bounds(self):
        import math as _math

        from adversarial_spec_tpu.ops.pallas_decode import (
            decode_attention_mq,
        )

        B, S, Hq, Hkv, D, T_ = 2, 9, 8, 2, 64, 256
        ks = jax.random.split(jax.random.key(11), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, T_, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, T_, D), jnp.float32)
        base = np.array([100, 37])
        starts = np.tile(np.array([[3], [0]]), (1, S)).astype(np.int32)
        ends = (base[:, None] + np.arange(1, S + 1)[None, :]).astype(np.int32)

        out = decode_attention_mq(
            q, k, v, jnp.asarray(starts), jnp.asarray(ends), interpret=True
        )

        g = Hq // Hkv
        qg = q.reshape(B, S, Hkv, g, D)
        s = jnp.einsum("bshgd,bhtd->bhsgt", qg, k) / _math.sqrt(D)
        slot = np.arange(T_)
        mask = (slot[None, None, :] >= starts[:, :, None]) & (
            slot[None, None, :] < ends[:, :, None]
        )
        s = jnp.where(jnp.asarray(mask)[:, None, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        ref = jnp.einsum("bhsgt,bhtd->bshgd", p, v).reshape(B, S, Hq, D)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_speculative_with_kernels_matches_jnp(self):
        """Greedy speculative decode routed through the MQ (verify) +
        SQ (tail) kernels must produce the same tokens as the jnp
        speculative path — and as plain decode (transitivity)."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [
            [((i * 13) % 500) + 3 for i in range(40)],
            [5, 9, 7, 5, 9, 7, 5, 9, 7, 5, 9, 7, 5, 9],
        ]
        kw = dict(
            max_new_tokens=24, eos_ids=[], greedy=True, speculative=True
        )
        jnp_spec = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        kern_spec = generate(params, cfg, prompts, use_pallas_decode=True, **kw)
        np.testing.assert_array_equal(jnp_spec.tokens, kern_spec.tokens)
        plain = generate(
            params, cfg, prompts,
            max_new_tokens=24, eos_ids=[], greedy=True, speculative=False,
        )
        np.testing.assert_array_equal(plain.tokens, kern_spec.tokens)

    def test_windowed_family_mq_path(self):
        """Sliding-window layers tighten per-query starts inside the MQ
        span; gemma2-style alternation must match the jnp path."""
        from dataclasses import replace

        cfg = replace(get_config("gemma2", "tiny"), sliding_window=8)
        params = T.init_params(jax.random.key(2), cfg, dtype=jnp.float32)
        prompts = [[((i * 7) % 500) + 3 for i in range(30)]]
        kw = dict(
            max_new_tokens=20, eos_ids=[], greedy=True, speculative=True
        )
        a = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        b = generate(params, cfg, prompts, use_pallas_decode=True, **kw)
        np.testing.assert_array_equal(a.tokens, b.tokens)


class TestInt8PagedPool:
    """int8 pages + scale pages: the paged pool and the int8 KV cache are
    not mutually exclusive."""

    def test_paged_kernel_matches_gathered_dequant(self):
        from adversarial_spec_tpu.ops.pallas_paged import (
            paged_decode_attention,
        )

        B, Hq, Hkv, D, page, P_ = 2, 4, 2, 64, 16, 6
        ks = jax.random.split(jax.random.key(11), 3)
        n_pages = 1 + B * P_  # page 0 = trash
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        kf = jax.random.normal(ks[1], (n_pages, Hkv, page, D), jnp.float32)
        vf = jax.random.normal(ks[2], (n_pages, Hkv, page, D), jnp.float32)
        amax = jnp.max(jnp.abs(kf), axis=-1, keepdims=True)
        ksc = jnp.maximum(amax, 1e-8) / 127.0
        k8 = jnp.clip(jnp.round(kf / ksc), -127, 127).astype(jnp.int8)
        amax = jnp.max(jnp.abs(vf), axis=-1, keepdims=True)
        vsc = jnp.maximum(amax, 1e-8) / 127.0
        v8 = jnp.clip(jnp.round(vf / vsc), -127, 127).astype(jnp.int8)
        table = (
            1 + jnp.arange(B * P_, dtype=jnp.int32).reshape(B, P_)
        )
        bounds = jnp.array([[0, 90], [5, 96]], jnp.int32)

        out = paged_decode_attention(
            q, k8, v8, table, bounds, interpret=True,
            k_scale=ksc, v_scale=vsc,
        )
        # Reference: dense attention over the DEQUANTIZED gathered pages.
        kd = (k8 * ksc)[table]  # [B, P, Hkv, page, D]
        vd = (v8 * vsc)[table]
        kd = jnp.swapaxes(kd, 1, 2).reshape(B, Hkv, P_ * page, D)
        vd = jnp.swapaxes(vd, 1, 2).reshape(B, Hkv, P_ * page, D)
        ref = _dense_ref(q, kd, vd, bounds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_generate_paged_int8_matches_dense_int8(self):
        """Greedy tokens through (int8 paged pool) must equal (int8 dense
        cache) — identical per-token quantization, different storage."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[3, 7, 11, 15], [2, 4]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            kv_dtype="int8", speculative=False, share_prefix=False,
        )
        dense = generate(params, cfg, prompts, paged=False, **kw)
        paged = generate(params, cfg, prompts, paged=True, page_size=16, **kw)
        np.testing.assert_array_equal(dense.tokens, paged.tokens)

    def test_generate_paged_int8_kernel_matches_gather(self):
        """Same quantized pool, kernel (interpret) vs gather path."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompts = [[1, 5, 9, 3, 7, 2]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            kv_dtype="int8", speculative=False, paged=True, page_size=16,
        )
        gather = generate(params, cfg, prompts, use_pallas_decode=False, **kw)
        kern = generate(params, cfg, prompts, use_pallas_decode=True, **kw)
        np.testing.assert_array_equal(gather.tokens, kern.tokens)


class TestInt8MqKernel:
    def test_mq_kernel_matches_dequant_reference(self):
        from adversarial_spec_tpu.ops.pallas_decode import (
            decode_attention_mq,
        )

        B, S, Hq, Hkv, D, T_ = 2, 5, 4, 2, 64, 128
        ks = jax.random.split(jax.random.key(13), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
        kf = jax.random.normal(ks[1], (B, Hkv, T_, D), jnp.float32)
        vf = jax.random.normal(ks[2], (B, Hkv, T_, D), jnp.float32)
        amax = jnp.max(jnp.abs(kf), axis=-1, keepdims=True)
        ksc = jnp.maximum(amax, 1e-8) / 127.0
        k8 = jnp.clip(jnp.round(kf / ksc), -127, 127).astype(jnp.int8)
        amax = jnp.max(jnp.abs(vf), axis=-1, keepdims=True)
        vsc = jnp.maximum(amax, 1e-8) / 127.0
        v8 = jnp.clip(jnp.round(vf / vsc), -127, 127).astype(jnp.int8)
        starts = jnp.zeros((B, S), jnp.int32)
        ends = 100 + jnp.arange(S, dtype=jnp.int32)[None, :] + jnp.array(
            [[0], [7]], jnp.int32
        )

        out = decode_attention_mq(
            q, k8, v8, starts, ends, interpret=True,
            k_scale=ksc, v_scale=vsc,
        )
        ref = decode_attention_mq(
            q, k8 * ksc, v8 * vsc, starts, ends, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_int8_speculative_generate_matches_int8_plain(self, ):
        """Greedy speculation with an int8 cache (MQ kernel verify +
        single-query kernel tail, both on int8 tiles) must equal plain
        int8 greedy decode bit-for-bit."""
        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        prompt = [5, 9, 7, 5, 9, 7, 5, 9, 7, 5, 9, 7, 5, 9]
        kw = dict(
            max_new_tokens=20, eos_ids=[], greedy=True,
            kv_dtype="int8", use_pallas_decode=True,
        )
        plain = generate(params, cfg, [prompt], speculative=False, **kw)
        spec = generate(params, cfg, [prompt], speculative=True, **kw)
        np.testing.assert_array_equal(plain.tokens, spec.tokens)


class TestFusedQuantMatmul:
    """ops/pallas_quant.py: the in-kernel dequant-matmul over int8 /
    packed-int4 weights (interpret mode) against the XLA dequant-fusion
    path in ops/quant.py — the stream-packed-once contract must not
    change the math."""

    def _xw(self, M=24, K=256, N=128, key=0):
        ks = jax.random.split(jax.random.key(key), 2)
        x = jax.random.normal(ks[0], (M, K), jnp.float32)
        w = jax.random.normal(ks[1], (K, N), jnp.float32)
        return x, w

    def test_int8_bit_exact_vs_xla(self):
        from adversarial_spec_tpu.ops import pallas_quant, quant

        x, w = self._xw()
        w8 = quant.quantize_int8(w)
        got = pallas_quant.matmul_int8(
            x, w8["q"], w8["scale"], interpret=True
        )
        # Whole-K accumulation matches XLA's order: byte parity.
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(quant.matmul(x, w8))
        )

    def test_int4_matches_xla_even_and_odd_width(self):
        from adversarial_spec_tpu.ops import pallas_quant, quant

        for K in (256, 255):  # odd width: the packed zero-row pad
            x, w = self._xw(M=8, K=K, key=K)
            w4 = quant.quantize_int4(w)
            got = pallas_quant.matmul_int4(
                x, w4["q4"], w4["scale"], interpret=True
            )
            # The kernel contracts x_even@lo + x_odd@hi — a reassociated
            # sum vs XLA's single contraction, so close not bit-equal.
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(quant.matmul(x, w4)),
                rtol=2e-4, atol=2e-4,
            )

    def test_stacked_activation_batch(self):
        from adversarial_spec_tpu.ops import pallas_quant, quant

        x = jax.random.normal(jax.random.key(3), (2, 3, 256), jnp.float32)
        _, w = self._xw(key=4)
        w8 = quant.quantize_int8(w)
        got = pallas_quant.matmul_int8(
            x, w8["q"], w8["scale"], interpret=True
        )
        assert got.shape == (2, 3, 128)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(quant.matmul(x, w8))
        )

    def test_dispatch_and_fallback(self):
        """quant.matmul(use_pallas=True) routes supported shapes to the
        kernel and silently keeps the XLA path for layer-stacked
        weights (3-D q: no flat [K, N] operand to stream)."""
        from adversarial_spec_tpu.ops import pallas_quant, quant

        x, w = self._xw(M=4)
        w4 = quant.quantize_int4(w)
        assert pallas_quant.fused_supported(x, w4)
        got = quant.matmul(x, w4, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(
                pallas_quant.matmul_int4(
                    x, w4["q4"], w4["scale"], interpret=True
                )
            ),
        )
        # Layer-stacked leaves (3-D q) have no flat [K, N] operand to
        # stream. The model scans per-layer slices, so the dispatcher
        # only ever sees 2-D weights; a stack that reaches it is a
        # caller's mistake and is refused aloud, never handed to XLA
        # without a word (stacks are matmul_int8_grouped's operand).
        stacked = {
            "q4": jnp.stack([w4["q4"]] * 2),
            "scale": jnp.stack([w4["scale"]] * 2),
        }
        with pytest.raises(ValueError, match="stacked weight"):
            pallas_quant.fused_supported(x, stacked)
        assert not pallas_quant.fused_supported(x, w)  # plain array

    # A [L, K, N] stack read at a prefetched layer index: (K, N) of a
    # plan that keeps the contraction whole, and of one that splits it
    # (K x bn alone is over the whole-K budget).
    STACK_PLANS = {"whole_k": (256, 128), "split_k": (8192, 512)}

    def _stack(self, fmt, plan, L=3):
        K, N = self.STACK_PLANS[plan]
        kq, ks = jax.random.split(jax.random.key(K), 2)
        # int8 values (for int4: packed bytes) and scales drawn as they
        # are stored: quantizing an [L, 8192, 512] float stack is slow
        q = jax.random.randint(kq, (L, K, N), -127, 128, jnp.int8)
        scale = jax.random.uniform(ks, (L, 1, N), jnp.float32, 1e-3, 1e-2)
        return {"q" if fmt == "int8" else "q4": q, "scale": scale}

    @pytest.mark.parametrize("layer", [0, 1, 2])
    @pytest.mark.parametrize("plan", list(STACK_PLANS))
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_layer_of_a_stack_is_the_flat_call_bit_for_bit(
        self, fmt, dtype, plan, layer
    ):
        """The stacked operand changes where a block is fetched from,
        not what is computed: layer l of the stack through the
        prefetched index equals the flat kernel on the slice, for the
        first, a middle and the last layer, rows that need padding
        (5 -> 8), and both kinds of plan."""
        from adversarial_spec_tpu.ops import pallas_quant

        w = self._stack(fmt, plan)
        name, fn = (
            ("q", pallas_quant.matmul_int8)
            if fmt == "int8"
            else ("q4", pallas_quant.matmul_int4)
        )
        K = w[name].shape[1] * (1 if fmt == "int8" else 2)
        x = jax.random.normal(jax.random.key(7), (5, K), jnp.dtype(dtype))
        bk = pallas_quant._plan_blocks(
            5, w[name].shape[1], w[name].shape[2],
            x.dtype.itemsize * (1 if fmt == "int8" else 2), 1,
        )[1]
        assert (bk == w[name].shape[1]) == (plan == "whole_k")
        got = fn(x, w[name], w["scale"], layer=jnp.int32(layer), interpret=True)
        flat = fn(x, w[name][layer], w["scale"][layer], interpret=True)
        assert got.dtype == x.dtype and got.shape == (5, w[name].shape[2])
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(flat, np.float32)
        )

    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_stack_dispatch_with_and_without_a_layer(self, fmt):
        """`fused_supported` takes a stack WITH its layer index and still
        refuses one without; `quant.matmul` on a `StackedLayer` is the
        kernel with it and a slice into XLA's dot without."""
        from adversarial_spec_tpu.ops import pallas_quant, quant

        w = self._stack(fmt, "whole_k")
        x = jax.random.normal(jax.random.key(8), (2, 3, 256 if fmt == "int8" else 512))
        assert pallas_quant.fused_supported(x, w, layer=0)
        assert pallas_quant.fused_supported(
            jax.ShapeDtypeStruct((6, 1), jnp.bfloat16), w, layer=0
        )
        with pytest.raises(ValueError, match="stacked weight"):
            pallas_quant.fused_supported(x, w)
        flat = jax.tree.map(lambda a: a[1], w)
        with pytest.raises(ValueError, match="stacked weight"):
            pallas_quant.fused_supported(x, flat, layer=1)
        with pytest.raises(ValueError, match="layer"):
            pallas_quant.matmul_int8(x, w["scale"], w["scale"], interpret=True)
        lw = quant.StackedLayer(w, jnp.int32(1))
        np.testing.assert_array_equal(
            np.asarray(quant.matmul(x, lw, use_pallas=True, interpret=True)),
            np.asarray(quant.matmul(x, flat, use_pallas=True, interpret=True)),
        )
        np.testing.assert_array_equal(
            np.asarray(quant.matmul(x, lw)), np.asarray(quant.matmul(x, flat))
        )

    def test_preferred_element_type(self):
        from adversarial_spec_tpu.ops import pallas_quant, quant

        x, w = self._xw(M=8)
        w8 = quant.quantize_int8(w)
        xb = x.astype(jnp.bfloat16)
        got = pallas_quant.matmul_int8(
            xb, w8["q"], w8["scale"],
            preferred_element_type=jnp.float32, interpret=True,
        )
        assert got.dtype == jnp.float32
        default = pallas_quant.matmul_int8(
            xb, w8["q"], w8["scale"], interpret=True
        )
        assert default.dtype == jnp.bfloat16


def _narrow_plan(M, K, N, x_itemsize, w_itemsize):
    """The block plan before a weight-bound call widened its output
    block: ``bn`` the widest of (512, 256, 128) dividing N, ``bk`` the
    whole contraction within the 3 MiB working set, else the widest of
    (2048 .. 128) dividing K."""
    from adversarial_spec_tpu.ops.pallas_quant import _pick_tile

    bn = _pick_tile(N, (512, 256, 128))
    if bn is None:
        if N > 2048:
            return None
        bn = N
    bm = min(256, -(-M // 8) * 8)
    if bm * K * x_itemsize + K * bn * w_itemsize <= 3 << 20:
        return bm, K, bn
    bk = _pick_tile(K, (2048, 1024, 512, 256, 128))
    if bk is None:
        return None if K > 8192 else (bm, K, bn)
    return bm, bk, bn


# The configurations of the benchmark's cells, at their widths (depth,
# experts and vocabulary as the cells cut them; the widths are what a
# block plan reads).
BENCH_CONFIGS = {
    "mistral-7b-int8": (("mistral", "7b"), {}),
    "qwen2-7b-int8": (("qwen2", "7b"), {}),
    "mistral-small-4-int8-ep4": (
        ("mistral4", "small-119b"),
        dict(n_layers=9, experts_held=(0, 32), vocab_rows=32768),
    ),
    "granite-4.0-h-micro-bf16": (("granitemoehybrid", "h-micro"), {}),
    "trinity-large-int8-ep8": (
        ("afmoe", "trinity-large"),
        dict(max_seq_len=32768, n_layers=9, experts_held=(0, 32),
             vocab_rows=25024),
    ),
}


def _int8_shapes(config):
    """Shapes of a configuration's int8 checkpoint (no array is made)."""
    from adversarial_spec_tpu.ops import quant

    args, kw = BENCH_CONFIGS[config]
    cfg = get_config(*args, **kw)
    routed = cfg.ffn_kind == "routed"
    return jax.eval_shape(
        lambda: quant.quantize_params(
            T.init_params(
                jax.random.key(0), cfg, jnp.bfloat16,
                expert_quant="int8" if routed else "",
            )
        )
    )


def _layer_matrices(config):
    """{(K, N): leaf paths} of every quantized matrix of a configuration:
    layer stacks, expert stacks and the head alike."""
    mats = {}
    leaves = jax.tree_util.tree_flatten_with_path(_int8_shapes(config))[0]
    for path, leaf in leaves:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        if keys[-1] == "q":
            name = "/".join(str(k) for k in keys[:-1])
            mats.setdefault(tuple(leaf.shape[-2:]), []).append(name)
    return mats


class TestBlockPlan:
    """`_plan_blocks` widens the output block of a weight-bound call (at
    most 128 rows) to stream weight blocks of up to 2 MiB, and keeps the
    row and contraction blocks, so the sums run in the same order."""

    # What the widening changes at the cells' widths (bk, bn), at 36 rows
    # (a verify step) and 128 (an admission): the block grows to 1.75 MiB
    # (Qwen2's w_down, from 256 KB) or 2 MiB (from 1 MiB). Mistral-Small-4's
    # matrices (4-8 MB, 8 blocks of 0.5-1 MiB) keep theirs: 2 MiB blocks
    # would leave them 2-4.
    WIDENED = {
        ("qwen2-7b-int8", (18944, 3584)): (512, 3584),
        ("mistral-7b-int8", (14336, 4096)): (2048, 1024),
        ("trinity-large-int8-ep8", (6144, 3072)): (2048, 1024),
        ("trinity-large-int8-ep8", (12288, 3072)): (2048, 1024),
    }

    @pytest.mark.parametrize("M", [36, 128, 512])
    @pytest.mark.parametrize("config", list(BENCH_CONFIGS))
    def test_plan_of_every_layer_matrix(self, config, M):
        """Every quantized matrix of the five benchmark configurations:
        ``bm`` and ``bk`` as the narrow plan has them; ``bn`` divides N,
        is never narrower, and where it grew it is a lane multiple whose
        block is at most 2 MiB, that leaves the matrix at least 8 blocks
        and whose footprint fits; at 512 rows (``bm`` 256, the MXU's
        bound) the plan is the narrow one."""
        from adversarial_spec_tpu.ops import pallas_quant as pq

        for (K, N), names in _layer_matrices(config).items():
            got, old = pq._plan_blocks(M, K, N, 2, 1), _narrow_plan(M, K, N, 2, 1)
            assert (got is None) == (old is None), names
            if got is None:
                continue
            bm, bk, bn = got
            assert (bm, bk) == old[:2], names
            assert N % bn == 0 and bn >= old[2], names
            if M == 512:
                assert got == old, names
            if bn != old[2]:
                assert bn % 128 == 0 and bk * bn <= 2 << 20, names
                assert (K // bk) * (N // bn) >= 8, names
                assert pq._vmem_bytes(bm, bk, bn, 2, 1, 0) <= 12 << 20, names
                # of what the kernel reads in an int8 cell, only these
                # (granite runs bfloat16; `wkv_b` is dequantized whole)
                if not config.startswith("granite") and names != [
                    "layers/wkv_b"
                ]:
                    assert (config, (K, N)) in self.WIDENED, names
            if M < 512 and (config, (K, N)) in self.WIDENED:
                assert (bk, bn) == self.WIDENED[config, (K, N)], names

    @pytest.mark.parametrize(
        "config,want",
        [("qwen2-7b-int8", 1_835_008), ("mistral-7b-int8", 2_097_152)],
    )
    def test_smallest_block_a_verify_step_streams(self, config, want):
        """`min_weight_block_bytes` (the gauge `advspec_qmm_min_block_bytes`)
        at the dense cells' verify step of 4 x 9 rows: every layer stack
        streams 1.75 MiB blocks in Qwen2 (w_down's were 256 KB) and 2 MiB
        in Mistral (w_down's were 1 MiB)."""
        params = _int8_shapes(config)
        assert T.min_weight_block_bytes(params, True, 36) == want
        assert T.min_weight_block_bytes(params, False, 36) == 0

    def test_int4_counts_its_unpack(self):
        """The int4 body shifts its packed block as int32, so a plan of
        int4 counts four bytes a packed byte more: at an admission's 128
        rows Mistral's packed w_down [7168, 4096] keeps 1 MiB blocks
        where a footprint without the unpack would take 2 MiB."""
        from adversarial_spec_tpu.ops import pallas_quant as pq

        assert pq._plan_blocks(128, 7168, 4096, 4, 1, 4) == (128, 1024, 1024)
        assert pq._plan_blocks(128, 7168, 4096, 4, 1) == (128, 1024, 2048)

    # Split K (bk 2048 of 8192 stored rows at a verify step's 40 rows)
    # and N 1,536: the narrow plan's bn 512 widens to 768, a block of
    # 1.5 MiB (int4 packs 16,384 rows into the same 8,192).
    K, N, ROWS = 8192, 1536, 40

    def _weights(self, lead=()):
        kq, ks = jax.random.split(jax.random.key(11), 2)
        q = jax.random.randint(kq, lead + (self.K, self.N), -127, 128, jnp.int8)
        scale = jax.random.uniform(
            ks, lead + (1, self.N), jnp.float32, 1e-3, 1e-2
        )
        return q, scale

    @pytest.mark.parametrize(
        "call", ["flat", "stacked", "stacked_int4", "grouped"]
    )
    def test_widened_plan_is_the_narrow_plan_bit_for_bit(
        self, monkeypatch, call
    ):
        """The same call under the widened plan and under the narrow one
        (the planner swapped for the narrow rule, in a fresh trace) gives
        the same bits: the flat and the stacked call, int8 and int4, and
        the grouped kernel over two experts."""
        from adversarial_spec_tpu.ops import pallas_quant as pq

        int4 = call.endswith("int4")
        x = jax.random.normal(
            jax.random.key(12), (self.ROWS, self.K * (1 + int4)), jnp.bfloat16
        )
        xi = 2 * (1 + int4)
        wide = pq._plan_blocks(self.ROWS, self.K, self.N, xi, 1, 4 * int4)
        assert wide[2] > _narrow_plan(self.ROWS, self.K, self.N, xi, 1)[2]
        if call == "grouped":
            q, scale = self._weights((1, 2))
            xg = jnp.concatenate([x, -x])  # two row tiles, one an expert
            args = (
                xg, q, scale, jnp.int32(0), jnp.array([1, 0], jnp.int32),
                jnp.int32(2),
            )
            fn, kw = pq.matmul_int8_grouped, dict(bm=self.ROWS, interpret=True)
        else:
            lead = (2,) if call.startswith("stacked") else ()
            q, scale = self._weights(lead)
            args = (x, q, scale) + ((jnp.int32(1),) if lead else ())
            fn = pq.matmul_int4 if int4 else pq.matmul_int8
            kw = dict(interpret=True)
        got = fn(*args, **kw)
        plans = []

        def narrow(M, K, N, xi, wi, *_):
            plans.append(_narrow_plan(M, K, N, xi, wi))
            return plans[-1]

        monkeypatch.setattr(pq, "_plan_blocks", narrow)
        # a new function, so that jax traces it anew and asks the planner
        fresh = jax.jit(lambda *a: fn.__wrapped__(*a, **kw))
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(fresh(*args), np.float32)
        )
        assert plans and plans[-1][2] < wide[2]


class TestPagedMqKernel:
    """paged_decode_attention_mq: the γ+1-position verify span over the
    PAGED pool — per-position causal bounds, one pass over the row's
    pages, trash/unmapped sentinel discipline unchanged."""

    def _pool(self, B=2, Hkv=2, D=64, page=16, P=6, key=21, poison=False):
        n_pages = 1 + B * P  # physical page 0 = trash
        ks = jax.random.split(jax.random.key(key), 2)
        kp = jax.random.normal(ks[0], (n_pages, Hkv, page, D), jnp.float32)
        vp = jax.random.normal(ks[1], (n_pages, Hkv, page, D), jnp.float32)
        if poison:
            kp = kp.at[0].set(1e9)
            vp = vp.at[0].set(1e9)
        return kp, vp

    def _ref(self, q, kp, vp, table, starts, ends, softcap=0.0):
        """Dense gather + per-position masked softmax (numpy, f64)."""
        qn, kn, vn = (np.asarray(a, np.float64) for a in (q, kp, vp))
        tb, st, en = (np.asarray(a) for a in (table, starts, ends))
        B, S, Hq, D = qn.shape
        Hkv, page = kn.shape[1], kn.shape[2]
        g, T_ = Hq // Hkv, tb.shape[1] * page
        out = np.zeros((B, S, Hq, D))
        slot = np.arange(T_)
        for b in range(B):
            ids = np.maximum(tb[b], 0)
            kd = kn[ids].transpose(1, 0, 2, 3).reshape(Hkv, T_, D)
            vd = vn[ids].transpose(1, 0, 2, 3).reshape(Hkv, T_, D)
            mapped = np.repeat(tb[b] > 0, page)
            for s in range(S):
                ok = mapped & (slot >= st[b, s]) & (slot < en[b, s])
                for h in range(Hq):
                    logits = kd[h // g] @ qn[b, s, h] / math.sqrt(D)
                    if softcap > 0:
                        logits = np.tanh(logits / softcap) * softcap
                    logits[~ok] = -np.inf
                    top = logits.max() if ok.any() else 0.0
                    p = np.exp(logits - top)
                    p[~ok] = 0.0
                    out[b, s, h] = (p @ vd[h // g]) / max(p.sum(), 1e-30)
        return out

    def test_matches_gathered_dense_per_position_bounds(self):
        from adversarial_spec_tpu.ops.pallas_paged import (
            paged_decode_attention_mq,
        )

        B, S, Hq, Hkv, D, page, P = 2, 5, 8, 2, 64, 16, 6
        q = jax.random.normal(jax.random.key(22), (B, S, Hq, D), jnp.float32)
        kp, vp = self._pool(B=B, Hkv=Hkv, D=D, page=page, P=P)
        table = np.full((B, P), -1, np.int32)
        table[0, :4] = 1 + np.arange(4)
        table[1, :3] = 1 + P + np.arange(3)
        base = np.array([[50], [33]])
        starts = np.zeros((B, S), np.int32)
        starts[0, :] = 3  # a windowed row
        ends = (base + 1 + np.arange(S)[None, :]).astype(np.int32)

        out = paged_decode_attention_mq(
            q, kp, vp, jnp.asarray(table),
            jnp.asarray(starts), jnp.asarray(ends), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), self._ref(q, kp, vp, table, starts, ends),
            rtol=2e-5, atol=2e-5,
        )

    def test_trash_page_zero_is_masked(self):
        """Speculative verify parks non-writable span positions on
        physical page 0; a poisoned trash page must not leak into any
        span position's output."""
        from adversarial_spec_tpu.ops.pallas_paged import (
            paged_decode_attention_mq,
        )

        B, S, Hq, Hkv, D, page, P = 1, 3, 4, 2, 64, 8, 4
        q = jax.random.normal(jax.random.key(23), (B, S, Hq, D), jnp.float32)
        kp, vp = self._pool(B=B, Hkv=Hkv, D=D, page=page, P=P, poison=True)
        table = np.array([[1, 0, 2, -1]], np.int32)  # a 0 sentinel mid-table
        starts = np.zeros((B, S), np.int32)
        ends = np.array([[20, 21, 22]], np.int32)  # spans the unmapped page

        out = paged_decode_attention_mq(
            q, kp, vp, jnp.asarray(table),
            jnp.asarray(starts), jnp.asarray(ends), interpret=True,
        )
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(
            np.asarray(out), self._ref(q, kp, vp, table, starts, ends),
            rtol=2e-5, atol=2e-5,
        )

    def test_row_count_not_sublane_multiple(self):
        """S·g = 6 pads to the 8-sublane tile; pad rows get an empty
        window and must not perturb the real rows."""
        from adversarial_spec_tpu.ops.pallas_paged import (
            paged_decode_attention_mq,
        )

        B, S, Hq, Hkv, D, page, P = 2, 3, 4, 2, 64, 16, 4
        q = jax.random.normal(jax.random.key(24), (B, S, Hq, D), jnp.float32)
        kp, vp = self._pool(B=B, Hkv=Hkv, D=D, page=page, P=P)
        table = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
        starts = np.zeros((B, S), np.int32)
        ends = np.asarray(
            40 + np.arange(S)[None, :] + np.zeros((B, 1), np.int32),
            np.int32,
        )
        out = paged_decode_attention_mq(
            q, kp, vp, jnp.asarray(table),
            jnp.asarray(starts), jnp.asarray(ends), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), self._ref(q, kp, vp, table, starts, ends),
            rtol=2e-5, atol=2e-5,
        )

    def test_single_position_matches_single_query_kernel(self):
        """S=1 must agree with paged_decode_attention — the MQ kernel is
        a strict generalization of the decode kernel's contract."""
        from adversarial_spec_tpu.ops.pallas_paged import (
            paged_decode_attention,
            paged_decode_attention_mq,
        )

        B, Hq, Hkv, D, page, P = 2, 8, 2, 64, 16, 6
        q = jax.random.normal(jax.random.key(25), (B, 1, Hq, D), jnp.float32)
        kp, vp = self._pool(B=B, Hkv=Hkv, D=D, page=page, P=P)
        table = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
        bounds = jnp.array([[2, 40], [0, 90]], jnp.int32)
        mq = paged_decode_attention_mq(
            q, kp, vp, jnp.asarray(table),
            bounds[:, 0:1], bounds[:, 1:2], interpret=True,
        )
        sq = paged_decode_attention(
            q[:, 0], kp, vp, jnp.asarray(table), bounds, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(mq[:, 0]), np.asarray(sq), rtol=2e-5, atol=2e-5
        )

    def test_int8_pool_scales_match_dequant_reference(self):
        from adversarial_spec_tpu.ops.pallas_paged import (
            paged_decode_attention_mq,
        )

        B, S, Hq, Hkv, D, page, P = 2, 3, 4, 2, 64, 16, 4
        q = jax.random.normal(jax.random.key(26), (B, S, Hq, D), jnp.float32)
        kf, vf = self._pool(B=B, Hkv=Hkv, D=D, page=page, P=P)
        amax = jnp.max(jnp.abs(kf), axis=-1, keepdims=True)
        ksc = jnp.maximum(amax, 1e-8) / 127.0
        k8 = jnp.clip(jnp.round(kf / ksc), -127, 127).astype(jnp.int8)
        amax = jnp.max(jnp.abs(vf), axis=-1, keepdims=True)
        vsc = jnp.maximum(amax, 1e-8) / 127.0
        v8 = jnp.clip(jnp.round(vf / vsc), -127, 127).astype(jnp.int8)
        table = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
        starts = np.zeros((B, S), np.int32)
        ends = np.asarray(
            30 + np.arange(S)[None, :] + np.zeros((B, 1), np.int32),
            np.int32,
        )
        out = paged_decode_attention_mq(
            q, k8, v8, jnp.asarray(table),
            jnp.asarray(starts), jnp.asarray(ends), interpret=True,
            k_scale=ksc, v_scale=vsc,
        )
        ref = paged_decode_attention_mq(
            q, k8 * ksc, v8 * vsc, jnp.asarray(table),
            jnp.asarray(starts), jnp.asarray(ends), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    # The walk of _paged_mq_attn_kernel: the live range of each row, K pages
    # at a time (K from the slab's bytes: _pages_per_block). Pages of 64
    # tokens, 4 KV heads and D = 256 in float32 make K = 8, so tables of a
    # dozen pages already hold several blocks. Each case, from K: the
    # table's width, the mapped pages of each row, and what it changes.
    WALK_CASES = {
        "rows_of_unequal_length": lambda K: dict(
            P=2 * K + 2, pages=[1, K - 1, K, K + 1, 2 * K + 2]
        ),
        "table_narrower_than_a_block": lambda K: dict(P=K - 1, pages=[1, K - 1]),
        "table_width_no_multiple_of_a_block": lambda K: dict(
            P=K + 3, pages=[K + 3, K + 1]
        ),
        "unmapped_entries_inside_the_range": lambda K: dict(
            P=2 * K + 2, pages=[2 * K + 2, K + 3],
            holes=[(0, 2, 0), (0, K + 1, -1), (1, K, 0)],
        ),
        "window_starts_past_the_first_block": lambda K: dict(
            P=2 * K + 2, pages=[2 * K + 2, 2 * K],
            first=[(K + 1) * 64 + 3, K * 64],
        ),
        "row_with_no_live_page": lambda K: dict(P=K + 2, pages=[K + 2, 0, 3]),
        # scale pages are [page, 1]: the (B, P) grid kernel takes these
        "int8_pages_with_scales": lambda K: dict(
            P=K + 2, pages=[K + 2, 3], int8=True
        ),
        "pad_query_rows": lambda K: dict(P=K + 2, pages=[K + 1, 2], S=5, Hq=4),
        "softcap": lambda K: dict(P=K + 2, pages=[K + 2, 2], softcap=30.0),
    }

    @pytest.mark.parametrize("case", WALK_CASES)
    def test_live_range_walk_matches_gathered_dense(self, case):
        from adversarial_spec_tpu.ops.pallas_paged import (
            _pages_per_block,
            paged_decode_attention_mq,
        )

        Hkv, D, page = 4, 256, 64
        K = _pages_per_block(Hkv, page, D, 4, 1 << 20)
        assert K >= 4, K
        spec = self.WALK_CASES[case](K)
        S, Hq = spec.get("S", 3), spec.get("Hq", 8)  # S·g = 6 or 5: padded
        int8 = spec.get("int8", False)
        P, pages = spec["P"], spec["pages"]
        B = len(pages)
        assert _pages_per_block(Hkv, page, D, 4, P) == min(K, P)

        rng = np.random.default_rng(31)
        n_pages = 1 + sum(pages)
        perm = 1 + rng.permutation(n_pages - 1)  # pages scattered in the pool
        table = np.full((B, P), -1, np.int32)
        table[:, P // 2 :] = 0  # both kinds of padding past a row's pages
        used = 0
        for b, n in enumerate(pages):
            table[b, :n] = perm[used : used + n]
            used += n
        for b, p, v in spec.get("holes", []):
            table[b, p] = v
        kp, vp = (
            jax.random.normal(k, (n_pages, Hkv, page, D), jnp.float32)
            for k in jax.random.split(jax.random.key(32), 2)
        )
        # The trash page is never read into a result.
        kp, vp = kp.at[0].set(1e9), vp.at[0].set(1e9)
        q = jax.random.normal(jax.random.key(33), (B, S, Hq, D), jnp.float32)
        # The span ends a few slots short of the row's last page's end.
        last = np.maximum(np.asarray(pages) * page - S - 2, 0)[:, None]
        ends = np.where(
            np.asarray(pages)[:, None] > 0, last + 1 + np.arange(S)[None, :], 0
        ).astype(np.int32)
        starts = np.zeros((B, S), np.int32)
        starts[:] = np.asarray(spec.get("first", [0] * B))[:, None]
        softcap = spec.get("softcap", 0.0)

        kw = {}
        kd, vd = kp, vp
        if int8:
            def quantize(x):
                sc = jnp.maximum(
                    jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8
                ) / 127.0
                return jnp.clip(jnp.round(x / sc), -127, 127).astype(jnp.int8), sc

            (kp, ksc), (vp, vsc) = quantize(kp.at[0].set(1.0)), quantize(vp.at[0].set(1.0))
            kw = dict(k_scale=ksc.at[0].set(1e9), v_scale=vsc.at[0].set(1e9))
            kd, vd = kp * ksc, vp * vsc
        out = paged_decode_attention_mq(
            q, kp, vp, jnp.asarray(table), jnp.asarray(starts),
            jnp.asarray(ends), attn_softcap=softcap, interpret=True, **kw,
        )
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(
            np.asarray(out),
            self._ref(q, kd, vd, table, starts, ends, softcap=softcap),
            rtol=2e-5, atol=2e-5,
        )
        for b, n in enumerate(pages):
            if n == 0:  # no live page: zeros, not NaN (l clamped at 1e-30)
                assert not np.asarray(out[b]).any()


class TestFusedMatmulInGenerate:
    """End-to-end: the fused dequant-matmul routed through the model's
    projection/MLP/lm-head sites must leave greedy transcripts
    byte-identical, for both quantized formats, dense and paged."""

    def _quantized(self, fmt):
        from adversarial_spec_tpu.ops import quant

        cfg = get_config("llama", "tiny")
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        return quant.quantize_params(params, fmt=fmt), cfg

    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_generate_transcript_parity(self, fmt):
        qp, cfg = self._quantized(fmt)
        prompts = [[((i * 13) % 500) + 3 for i in range(24)], [5, 9, 7, 5]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            speculative=False, share_prefix=False,
        )
        off = generate(qp, cfg, prompts, use_pallas_matmul=False, **kw)
        on = generate(qp, cfg, prompts, use_pallas_matmul=True, **kw)
        np.testing.assert_array_equal(off.tokens, on.tokens)

    def test_generate_paged_int4_parity(self):
        qp, cfg = self._quantized("int4")
        prompts = [[3, 7, 11, 15, 2, 4, 6, 8]]
        kw = dict(
            max_new_tokens=8, eos_ids=[], greedy=True,
            speculative=False, paged=True, page_size=16,
        )
        off = generate(qp, cfg, prompts, use_pallas_matmul=False, **kw)
        on = generate(qp, cfg, prompts, use_pallas_matmul=True, **kw)
        np.testing.assert_array_equal(off.tokens, on.tokens)

    def test_batcher_both_kernels_zero_recompiles(self):
        """Two drains through the batcher with the span-verify kernel
        AND the fused int4 matmul live: greedy parity with the XLA
        batcher and no seen-key recompile (the promoted-q4 residency
        contract rides on this same signature stability)."""
        from adversarial_spec_tpu import obs
        from adversarial_spec_tpu.engine import spec as spec_mod
        from adversarial_spec_tpu.engine.scheduler import (
            ContinuousBatcher,
            SchedRequest,
        )

        qp, cfg = self._quantized("int4")
        prompt = [5 + (i % 7) for i in range(40)]
        spec_mod.configure(enabled=True, gamma=4)
        was_enabled = obs.config().enabled
        obs.configure(enabled=True)
        obs.retrace.clear()

        def drain(use_pallas, n=6):
            b = ContinuousBatcher(
                qp, cfg, max_batch=1, max_new_cap=n,
                speculative=True, gamma=4,
                use_pallas_matmul=use_pallas,
            )
            b._use_pallas = use_pallas
            b._pallas_interpret = True
            out = {}
            for _ in range(2):  # two drains: reuse, not recompile
                b.submit(
                    SchedRequest(
                        req_id=0, prompt_ids=list(prompt), max_new_tokens=n
                    )
                )
                [r] = b.run_all()
                out = r.tokens.tolist()
            return out

        try:
            ref = drain(False)
            obs.retrace.clear()
            fused = drain(True)
            snap = obs.retrace.snapshot()
        finally:
            obs.retrace.clear()
            obs.configure(enabled=was_enabled)
            spec_mod.configure(enabled=True, gamma=spec_mod.DEFAULT_GAMMA)
        assert fused == ref
        assert snap["programs"], "no program dispatched"
        assert snap["unexpected_recompiles"] == 0, snap
