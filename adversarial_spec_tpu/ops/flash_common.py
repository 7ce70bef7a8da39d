"""Shared flash-attention (online-softmax) update for the Pallas kernels.

Both decode kernels (dense ops/pallas_decode.py, paged ops/pallas_paged.py)
accumulate attention block-by-block with the same recurrence; the -inf
handling for fully-masked blocks (m stays -inf, alpha forced to 0 so no
NaN ever enters l/acc) is subtle enough that it must live in exactly one
place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_update_heads(
    q_ref,  # VMEM ref [1, n_kv, G, D]
    k_ref,  # VMEM ref [1, n_kv, Tb, D]
    v_ref,  # VMEM ref [1, n_kv, Tb, D]
    ks_ref,  # VMEM ref [1, n_kv, Tb, 1] or None (int8 KV scales)
    vs_ref,  # VMEM ref [1, n_kv, Tb, 1] or None
    m_ref,  # VMEM scratch [n_kv, G, 1]
    l_ref,  # VMEM scratch [n_kv, G, 1]
    acc_ref,  # VMEM scratch [n_kv, G, D]
    t0,  # scalar: global slot index of this tile's first token
    starts,  # scalar or [G, 1]: first valid slot per query row
    ends,  # scalar or [G, 1]
    *,
    scale: float,
    attn_softcap: float,
    live=None,  # bool [1, Tb] or None: see flash_update
) -> None:
    """One online-softmax accumulation over a HEAD-FOLDED K/V tile.

    The head-folded kernels (dense, multi-query, paged) all run this
    static per-head loop — 2D dots per head against head slices of one
    big resident tile (the fold is what makes each DMA large enough to
    amortize); like ``flash_update`` itself, it must live in exactly one
    place so the dense and paged paths can never drift numerically.

    Practical Hkv ceiling: the loop unrolls Hkv-fold in the kernel body
    (Mosaic code size/compile time scale with it), and the (Hkv, G8, D)
    f32 scratch plus double-buffered [Hkv, block_t, D] tiles share VMEM
    — fine for the supported configs (Hkv ≤ 16; _pick_block_t shrinks
    the tile as Hkv grows), but a many-KV-head config (Hkv ≥ 32) should
    fold only a fixed head group and keep the remainder in the grid.
    """
    n_kv = q_ref.shape[1]
    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32) * scale
        k = k_ref[0, h].astype(jnp.float32)
        v = v_ref[0, h].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, h]  # [Tb, 1] broadcasts over D
            v = v * vs_ref[0, h]
        m, l, acc = flash_update(
            q,
            k,
            v,
            t0,
            starts,
            ends,
            m_ref[h],
            l_ref[h],
            acc_ref[h],
            attn_softcap=attn_softcap,
            live=live,
        )
        m_ref[h] = m
        l_ref[h] = l
        acc_ref[h] = acc


def flash_update(
    q: jnp.ndarray,  # [G, D] f32, pre-scaled
    k: jnp.ndarray,  # [Tb, D] f32
    v: jnp.ndarray,  # [Tb, D] f32
    t0,  # scalar: global slot index of k[0]
    start,  # scalar: first valid slot (inclusive)
    end,  # scalar: first invalid slot (exclusive)
    m: jnp.ndarray,  # [G, 1] running max
    l: jnp.ndarray,  # [G, 1] running normalizer
    acc: jnp.ndarray,  # [G, D] running weighted values
    *,
    attn_softcap: float,
    live=None,  # bool [1, Tb] or None: slots that hold a mapped page (a
    # tile of several pages masks the page its row has not mapped)
    scores=None,  # f32 [G, Tb] or None: the block's scaled scores, where
    # the caller's keys are not one matrix (latent attention: q and k are
    # then unused and may be None)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One online-softmax accumulation over a K/V block; returns (m, l, acc)."""
    if scores is None:
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [G, Tb]
    s = scores
    G, Tb = s.shape
    if attn_softcap > 0.0:
        s = jnp.tanh(s / attn_softcap) * attn_softcap
    slot = t0 + jax.lax.broadcasted_iota(jnp.int32, (G, Tb), 1)
    valid = (slot >= start) & (slot < end)
    if live is not None:
        valid = valid & live
    s = jnp.where(valid, s, -jnp.inf)

    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    # Fully-masked-so-far rows keep m = -inf; m_safe pins the exp argument
    # so those rows contribute exact zeros instead of NaNs.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), jnp.zeros_like(m))
    p = jnp.exp(s - m_safe)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    # p meets v in v's own dtype (a no-op for the float32 tiles of the
    # per-head kernels; bfloat16 on the MXU for the latent kernel).
    acc_new = acc * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new
