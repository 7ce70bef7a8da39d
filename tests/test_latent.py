"""Latent attention (MLA) through the program's two forwards and its paged
cache, at tiny size on the CPU, against the plain reference
(perfbench/architectures/mistral4.py) that imports nothing of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine.kvcache import (
    PagedCacheLayout,
    init_page_pool,
    read_tokens,
    write_tokens,
)
from adversarial_spec_tpu.models import transformer as tf
from adversarial_spec_tpu.models.config import YarnRope, get_config
from adversarial_spec_tpu.ops import pallas_paged, rope
from tests.test_moe import _reference, _tiny_file_config, program_params

PAGE = 8
HELD = [2, 4]  # the rehearsal's share: experts 2..5 of 8


@pytest.fixture(scope="module")
def model():
    cfg = get_config("mistral4", "tiny", experts_held=HELD, vocab_rows=384)
    return cfg, program_params(cfg)


def _prefill(cfg, params, tokens, cache_len):
    B, T = tokens.shape
    cache = tf.init_cache(cfg, B, cache_len, dtype=jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    return tf.forward(
        params, cfg, tokens, pos, cache, jnp.int32(0),
        jnp.ones((B, cache_len), bool),
    )


def _paged_setup(cfg, cache, n_pre, table):
    heads, k_dim, v_dim = cfg.kv_layout
    B, P = table.shape
    pool = init_page_pool(
        PagedCacheLayout(
            n_pages=int(table.max()) + 1, page_size=PAGE, n_layers=cfg.n_layers,
            n_kv_heads=heads, head_dim=k_dim, v_dim=v_dim,
        ),
        dtype=jnp.float32,
    )
    pages = np.repeat(np.asarray(table), PAGE, axis=1)[:, :n_pre]
    offs = np.tile(np.arange(PAGE), P)[None, :n_pre].repeat(B, 0)
    pool = write_tokens(
        pool, cache["k"][..., :n_pre, :], cache["v"][..., :n_pre, :], pages, offs
    )
    return pool, pages, offs


def _span(table, start, S):
    B = table.shape[0]
    q_pos = jnp.broadcast_to(start + jnp.arange(S), (B, S))
    wp = jnp.take_along_axis(table, q_pos // PAGE, axis=1)
    bounds = jnp.stack([jnp.zeros_like(q_pos), q_pos + 1], -1)
    return q_pos, wp, q_pos % PAGE, bounds


# 13 pages of 8 slots a row (104 positions), then an unmapped entry
TABLE = jnp.asarray(
    [list(range(1, 14)) + [-1], list(range(14, 27)) + [-1]], jnp.int32
)


# -- (a) the program against the reference's full forward -----------------------


@pytest.mark.parametrize("bits,agrees", [(8, True), (4, False)])
def test_prefill_and_paged_verify_agree_with_the_reference(model, bits, agrees):
    """Prefill 72 tokens through the dense admission cache, hand them to
    the latent pages, then a verify span of 9 and single-token decode
    steps through the paged cache: every logit against the reference's one
    full forward over the whole sequence (96 positions: past the tiny
    `original_max` of 64, so YaRN's ramp and the query scaling both act).

    Tolerance: both sides compute in float32 from the same int8 weights,
    so they differ by summation order alone, 1e-3 of a logit's scale at
    most (the logits' standard deviation is ~0.7). The int4 control moves
    logits by tenths and has to fail it."""
    cfg, params = model
    ref = _reference()
    file_cfg = _tiny_file_config(HELD)
    T, n_pre, S = 96, 72, 9
    tokens = jax.random.randint(jax.random.key(11), (2, T), 3, 259)
    weights = ref.make_weights(file_cfg, seed=0, bits=bits)
    want = np.stack(
        [ref.logits_for(file_cfg, weights, list(map(int, row)), 0) for row in tokens]
    )  # [2, T, V]

    logits, cache = _prefill(cfg, params, tokens[:, :n_pre], 80)
    got = [np.asarray(logits)]
    pool, _, _ = _paged_setup(cfg, cache, n_pre, TABLE)
    at = n_pre
    for width in (S, 1, 1, S - 3, 1, 1, 1, 1, 3):
        q_pos, wp, wo, bounds = _span(TABLE, at, width)
        out, pool, _ = tf.forward_paged_decode(
            params, cfg, tokens[:, at : at + width], q_pos, pool, TABLE,
            wp, wo, bounds, q_pos,
        )
        got.append(np.asarray(out))
        at += width
    got = np.concatenate(got, axis=1)
    assert at == T and got.shape == want.shape
    err = np.abs(got - want).max()
    if agrees:
        assert err < 2e-3, err
        assert (got.argmax(-1) == want.argmax(-1)).all()
    else:
        assert err > 0.1, err


# -- (b) absorbed form == expanded form ------------------------------------------


def test_absorbed_and_expanded_forms_are_the_same_mathematics(model):
    """`forward` attends in the expanded form, the paged decode in the
    absorbed one: the same 104 tokens prefilled as one chunk and as
    96 + 8 (the second chunk over the cache the first left), and the
    paged decode's absorbed form over the pages, give the same logits."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(12), (2, 104), 3, 259)
    whole, cache_whole = _prefill(cfg, params, tokens, 104)

    first, cache = _prefill(cfg, params, tokens[:, :96], 104)
    pos = jnp.broadcast_to(96 + jnp.arange(8), (2, 8))
    second, cache = tf.forward(
        params, cfg, tokens[:, 96:], pos, cache, jnp.int32(96),
        jnp.ones((2, 104), bool),
    )
    np.testing.assert_allclose(first, whole[:, :96], atol=2e-5)
    np.testing.assert_allclose(second, whole[:, 96:], atol=2e-5)
    for name in ("k", "v"):
        np.testing.assert_allclose(cache[name], cache_whole[name], atol=2e-5)

    pool, _, _ = _paged_setup(cfg, cache_whole, 96, TABLE)
    q_pos, wp, wo, bounds = _span(TABLE, 96, 8)
    paged, _, _ = tf.forward_paged_decode(
        params, cfg, tokens[:, 96:], q_pos, pool, TABLE, wp, wo, bounds, q_pos
    )
    np.testing.assert_allclose(paged, whole[:, 96:], atol=2e-5)


def test_latent_pages_hold_the_compressed_vector_and_one_shared_key(model):
    """The page layout: one head; "v" the compressed vector, "k" the rotated
    key with zeros up to whole lanes; what write_tokens puts in,
    read_tokens gives back (adoption and the host tier move pages through
    the same two calls)."""
    cfg, params = model
    la = cfg.latent
    assert cfg.kv_layout == (1, 128, la.kv_rank) and la.rope_pad % 128 == 0
    tokens = jax.random.randint(jax.random.key(13), (2, 24), 3, 259)
    _, cache = _prefill(cfg, params, tokens, 24)
    assert cache["k"].shape == (cfg.n_layers, 2, 1, 24, 128)
    assert cache["v"].shape == (cfg.n_layers, 2, 1, 24, la.kv_rank)
    assert not np.asarray(cache["k"][..., la.rope_dim :]).any()  # the padding
    assert np.asarray(cache["k"][..., : la.rope_dim]).any()
    pool, pages, offs = _paged_setup(cfg, cache, 24, TABLE)
    back = read_tokens(pool, pages, offs)
    for name in ("k", "v"):
        np.testing.assert_array_equal(back[name], cache[name])
    with pytest.raises(NotImplementedError):
        tf.init_cache(cfg, 1, 8, kv_dtype="int8")


# -- (e) YaRN frequencies and the query scaling ------------------------------------


def test_yarn_frequencies_and_query_scaling_follow_the_published_rule():
    ref = _reference()
    for dim, theta, yarn in (
        (64, 10000.0, YarnRope(128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1)),
        (32, 10000.0, YarnRope(4.0, 64, 32.0, 1.0, 1.0, 1.0, 0.1)),
    ):
        got = np.asarray(rope.yarn_freqs(dim, theta, yarn))
        want = np.asarray(
            ref.yarn_inv_freq(
                dim, theta,
                (yarn.factor, yarn.original_max, yarn.beta_fast, yarn.beta_slow),
            )
        )
        np.testing.assert_allclose(got, want, rtol=1e-6)
        plain = 1.0 / theta ** (np.arange(dim // 2) / (dim // 2))
        # the fastest pairs keep their frequency, the slowest are stretched
        # by the factor, the ramp lies between
        np.testing.assert_allclose(got[0], plain[0], rtol=1e-6)
        np.testing.assert_allclose(got[-1], plain[-1] / yarn.factor, rtol=1e-6)
        assert ((got <= plain * (1 + 1e-6)) & (got >= plain / yarn.factor * (1 - 1e-6))).all()
        assert rope.yarn_attention_factor(yarn) == 1.0  # mscale / mscale_all_dim
    # at the published sizes, by hand: 64 ln(8192 / (32 * 2 pi)) / (2 ln 1e4)
    # = 12.88 and 64 ln(8192 / (2 pi)) / (2 ln 1e4) = 24.92, so pairs 0..12
    # keep their frequency, 25..31 are stretched, the ramp is (i - 12) / 13
    big = YarnRope(128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1)
    f = np.asarray(rope.yarn_freqs(64, 10000.0, big))
    plain = 1.0 / 10000.0 ** (np.arange(32) / 32)
    np.testing.assert_allclose(f[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(f[25:], plain[25:] / 128.0, rtol=1e-6)
    np.testing.assert_allclose(
        f[18], plain[18] * (6 / 13 / 128.0 + 7 / 13), rtol=1e-5
    )
    # the query scaling: 1 under original_max, then 1 + 0.1 ln(1 + floor(p / max))
    tiny = YarnRope(4.0, 64, query_scaling_beta=0.1)
    pos = jnp.asarray([0, 63, 64, 127, 128, 640])
    np.testing.assert_allclose(
        rope.query_position_scale(pos, tiny),
        [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(3),
         1 + 0.1 * np.log(11)],
        rtol=1e-6,
    )
    # softmax scale: qk_head_dim^-0.5 times (0.1 ln(factor) + 1)^2
    cfg = get_config("mistral4", "small-119b")
    assert cfg.attn_scale == pytest.approx(128**-0.5 * (0.1 * np.log(128) + 1) ** 2)
    assert cfg.attn_scale == pytest.approx(0.19497, rel=1e-4)


def test_interleaved_rope_rotates_neighbouring_pairs():
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 1, 8)
    ang = jnp.asarray([[[0.1, 0.2, 0.3, 0.4]]])
    y = np.asarray(rope.apply_rope_interleaved(x, jnp.cos(ang), jnp.sin(ang)))[0, 0, 0]
    for i, a in enumerate([0.1, 0.2, 0.3, 0.4]):
        x1, x2 = 2 * i, 2 * i + 1
        np.testing.assert_allclose(y[x1], x1 * np.cos(a) - x2 * np.sin(a), rtol=1e-6)
        np.testing.assert_allclose(y[x2], x2 * np.cos(a) + x1 * np.sin(a), rtol=1e-6)


# -- (f) the kernel, in interpret mode, against the gather path -----------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_latent_kernel_equals_the_gather_path(dtype):
    """`paged_latent_attention_mq` (interpret mode) over a pool whose rows
    own scattered pages, unmapped table entries and per-query windows,
    against plain attention over the densified pages: p . c per query and
    head. Rows that hold several blocks of pages (the table is wider than
    one block) and a row whose window is empty."""
    B, S, H, R, RP, P = 3, 5, 4, 128, 128, 40
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.key(3), 4)
    q_lat = jax.random.normal(ks[0], (B, S, H, R), jnp.float32).astype(dtype)
    q_rot = jax.random.normal(ks[1], (B, S, H, RP), jnp.float32).astype(dtype)
    c_pages = jax.random.normal(ks[2], (n_pages, 1, PAGE, R), jnp.float32).astype(dtype)
    r_pages = jax.random.normal(ks[3], (n_pages, 1, PAGE, RP), jnp.float32).astype(dtype)
    perm = np.random.default_rng(0).permutation(np.arange(1, n_pages))
    table = perm.reshape(B, P).astype(np.int32)
    table[0, 30:] = -1  # row 0 holds 30 pages
    table[2, :] = 0  # row 2 holds nothing
    lens = np.array([30 * PAGE - 3, P * PAGE - S, 0])
    starts = np.zeros((B, S), np.int32)
    starts[1] = 17  # a window that skips the row's first pages
    ends = np.stack([np.maximum(lens - S + 1 + j, 0) for j in range(S)], 1).astype(np.int32)
    ends[2] = 0
    scale = 0.19
    got = pallas_paged.paged_latent_attention_mq(
        q_lat, q_rot, r_pages, c_pages, jnp.asarray(table), jnp.asarray(starts),
        jnp.asarray(ends), scale=scale, interpret=True,
    )
    safe = np.maximum(table, 0)
    c = np.asarray(c_pages, np.float32)[safe][:, :, 0].reshape(B, P * PAGE, R)
    r = np.asarray(r_pages, np.float32)[safe][:, :, 0].reshape(B, P * PAGE, RP)
    s = (
        np.einsum("bshr,btr->bsht", np.asarray(q_lat, np.float32), c)
        + np.einsum("bshr,btr->bsht", np.asarray(q_rot, np.float32), r)
    ) * scale
    slot = np.arange(P * PAGE)[None, None, None, :]
    ok = (
        (slot >= starts[:, :, None, None]) & (slot < ends[:, :, None, None])
        & np.repeat(table > 0, PAGE, axis=1)[:, None, None, :]
    )
    s = np.where(ok, s, -np.inf)
    m = np.where(np.isfinite(s.max(-1, keepdims=True)), s.max(-1, keepdims=True), 0.0)
    p = np.exp(s - m)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    want = np.einsum("bsht,btr->bshr", p, c)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2  # bfloat16: 8 bits of p and c
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol, rtol=tol)
    assert not np.asarray(got[2], np.float32).any()  # the empty row: zeros
    with pytest.raises(ValueError, match="whole lanes"):
        pallas_paged.paged_latent_attention_mq(
            q_lat, q_rot[..., :64], r_pages[..., :64], c_pages, jnp.asarray(table),
            jnp.asarray(starts), jnp.asarray(ends), scale=scale, interpret=True,
        )
