"""Ring attention: causal attention with the sequence axis sharded over ICI.

Long-context subsystem (SURVEY §5 "long-context — ABSENT in the reference,
required new subsystem here"): when a 16k+-token spec exceeds what one
chip's HBM comfortably holds for prefill, the sequence axis is sharded over
the ``sp`` mesh axis and attention runs as a ring: each device computes
attention of its local query block against the K/V block it currently
holds, accumulates online-softmax statistics (running max / normalizer /
weighted values — the flash-attention recurrence), and passes its K/V block
to its ring neighbor with ``ppermute``. After ``sp`` hops every query block
has seen every key block, with peak memory O(S/sp) and the K/V transfers
riding neighbor ICI links.

Causality is enforced at two granularities: whole blocks are skipped when
the key block is entirely in the future (compute still runs — SPMD needs
identical programs — but is masked), and the diagonal block applies the
in-block triangular mask. Per-row ``kv_start`` bounds additionally mask
left-pad slots, so the same code serves padded batches.

``ring_attention_local`` is the per-device body, reused by the
sequence-parallel model prefill (parallel/sp.py) which runs its own
shard_map; ``ring_attention`` wraps it for standalone global-array use.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from adversarial_spec_tpu.parallel.mesh import SP


def _block_attend(
    q: jnp.ndarray,  # [B, Sq, H, D] f32
    k: jnp.ndarray,  # [B, Sk, Hkv, D]
    v: jnp.ndarray,  # [B, Sk, Hkv, D]
    mask: jnp.ndarray,  # [B, Sq, Sk] bool — True = attend
    m: jnp.ndarray,  # [B, H, Sq] running max
    l: jnp.ndarray,  # [B, H, Sq] running normalizer
    acc: jnp.ndarray,  # [B, Sq, H, D] running weighted values
    scale: float,
    attn_softcap: float = 0.0,
):
    """One flash-attention accumulation step over a K/V block."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D)
    s = jnp.einsum(
        "bshgd,bthd->bhgst", qg, k, preferred_element_type=jnp.float32
    ) * scale  # [B, Hkv, g, Sq, Sk]
    s = s.reshape(B, H, Sq, k.shape[1])
    if attn_softcap > 0.0:
        s = jnp.tanh(s / attn_softcap) * attn_softcap
    # -inf (not finfo.min): a fully-masked row must yield EXACT zeros —
    # finfo.min would make it a uniform average over however many keys
    # this run happened to process (hop-count-dependent garbage). The
    # m/alpha guards below keep -inf NaN-free; same contract as the
    # Pallas kernels (ops/flash_common.py) and attention().
    s = jnp.where(mask[:, None, :, :], s, -jnp.inf)

    m_new = jnp.maximum(m, s.max(axis=-1))
    # Guard fully-masked rows: keep m finite so exp() stays 0, not NaN.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    p = jnp.exp(s - m_safe[..., None])  # [B, H, Sq, Sk]
    l_new = l * alpha + p.sum(axis=-1)
    pg = p.reshape(B, Hkv, g, Sq, -1)
    delta = jnp.einsum("bhgst,bthd->bshgd", pg, v.astype(jnp.float32))
    delta = delta.reshape(B, Sq, H, D)
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + delta
    return m_new, l_new, acc_new


def ring_hops(sp: int, block: int, window, causal: bool):
    """Number of ring hops that can possibly contribute.

    Causal + sliding window W: hop h hands device idx the K block from
    src = idx - h (mod sp); non-wrapped blocks sit h·block slots behind
    the query block, and every (query, key) pair in hop h is outside the
    window once (h-1)·block + 1 >= W — the SAME bound on every device, so
    the trip count shrinks uniformly and ppermutes stay matched. Wrapped
    blocks are entirely in the future and already masked. Returns a
    Python int when ``window`` is static (fori_loop keeps a static trip
    count), a traced scalar when it is traced (gemma2's per-layer
    alternation inside scan — lowers to a uniform while_loop).
    """
    if not causal:
        return sp
    if isinstance(window, int):
        if window <= 0:
            return sp
        return min(sp, (window + block - 2) // block + 1)
    return jnp.where(
        window > 0,
        jnp.minimum(sp, (window + block - 2) // block + 1),
        sp,
    )


def ring_attention_local(
    qb: jnp.ndarray,  # [B, S_loc, H, D] — this device's query block
    kb: jnp.ndarray,  # [B, S_loc, Hkv, D] — this device's K block
    vb: jnp.ndarray,
    sp: int,
    causal: bool = True,
    kv_start: jnp.ndarray | None = None,  # [B] first valid global slot
    attn_softcap: float = 0.0,
    scale: float | None = None,
    window: jnp.ndarray | int = 0,  # sliding window in slots; 0 = global
    axis_name: str = SP,
) -> jnp.ndarray:
    """Per-device ring attention body (call inside shard_map over sp).

    ``window`` may be a traced scalar (per-layer alternation inside a
    scan): key slots below q_slot - window + 1 are masked. Sliding-window
    layers EARLY-OUT of the ring after ``ring_hops`` hops — the remaining
    blocks are fully outside every query's window on every device, so the
    trip count shrinks uniformly (SPMD-safe) instead of masking sp-1 hops
    of dead compute at 16k contexts.
    """
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = qb.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    m = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Sq), jnp.float32)
    acc = jnp.zeros((B, Sq, H, D), jnp.float32)
    rows = jnp.arange(Sq)[:, None]
    cols = jnp.arange(Sq)[None, :]

    def step(h, carry):
        m, l, acc, kb, vb = carry
        # After h hops, we hold the block originally on device idx-h.
        src = (idx - h) % sp
        if causal:
            diag = rows >= cols
            full = jnp.ones((Sq, Sq), bool)
            empty = jnp.zeros((Sq, Sq), bool)
            block_mask = jnp.where(
                src == idx, diag, jnp.where(src < idx, full, empty)
            )
        else:
            block_mask = jnp.ones((Sq, Sq), bool)
        mask = jnp.broadcast_to(block_mask[None], (B, Sq, Sq))
        key_slot = src * Sq + cols  # [Sq(q), Sq(k)]-broadcastable key slots
        if kv_start is not None:
            mask = mask & (key_slot[None] >= kv_start[:, None, None])
        # Sliding window (traced-scalar friendly): q at global slot
        # idx*Sq+row sees keys in (q_slot - window, q_slot].
        q_slot = idx * Sq + rows
        win_mask = (window <= 0) | (key_slot > q_slot - window)
        mask = mask & win_mask[None]
        m, l, acc = _block_attend(
            qb.astype(jnp.float32),
            kb,
            vb,
            mask,
            m,
            l,
            acc,
            scale,
            attn_softcap=attn_softcap,
        )
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return m, l, acc, kb, vb

    hops = ring_hops(sp, Sq, window, causal)
    m, l, acc, _, _ = jax.lax.fori_loop(0, hops, step, (m, l, acc, kb, vb))
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(qb.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S is the GLOBAL sequence length
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, D]
    mesh: Mesh,
    causal: bool = True,
    kv_start: jnp.ndarray | None = None,  # [B]
    attn_softcap: float = 0.0,
) -> jnp.ndarray:
    """Causal attention with sequence sharded over the mesh's ``sp`` axis.

    Inputs/outputs are global arrays; shard_map splits them into per-device
    sequence blocks and the ring runs ``sp`` ppermute hops.
    """
    sp = mesh.shape[SP]
    S = q.shape[1]
    if S % sp != 0:
        raise ValueError(f"sequence {S} not divisible by sp={sp}")

    spec = P(None, SP, None, None)
    if kv_start is None:

        def local(qb, kb, vb):
            return ring_attention_local(
                qb, kb, vb, sp, causal=causal, attn_softcap=attn_softcap
            )

        in_specs = (spec, spec, spec)
        args = (q, k, v)
    else:

        def local(qb, kb, vb, ks):
            return ring_attention_local(
                qb,
                kb,
                vb,
                sp,
                causal=causal,
                kv_start=ks,
                attn_softcap=attn_softcap,
            )

        in_specs = (spec, spec, spec, P(None))
        args = (q, k, v, kv_start)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )(*args)
