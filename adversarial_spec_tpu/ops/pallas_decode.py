"""Pallas TPU kernel: single-token (decode) attention over a dense KV cache.

The decode hot loop's attention reads the whole KV cache once per step; the
XLA fallback materializes [B, H, T] logits through HBM. This kernel fuses
QK^T → online softmax → PV into one pass with the cache genuinely streamed:

  grid = (B, T/block_t); the T dimension lives IN THE GRID, so only one
  [Hkv, block_t, D] K tile and V tile are VMEM-resident at a time (Pallas
  double-buffers the next tile's DMA behind the current tile's compute) —
  VMEM stays O(Hkv·block_t·D) regardless of context length, which is what
  makes 16k+ contexts decodable. Each row program folds ALL Hkv KV heads:
  a static per-head loop over [g, D] query groups (g = Hq/Hkv, padded to
  the f32 sublane tile of 8) against that head's K/V tile slice. Folding
  the head axis into the program (rather than the grid, the round-2
  design) matters at SHORT context — the north-star bench shape
  (B=4, Hkv=8, T=1280) drops from 160 sequential programs moving 32 KB
  tiles to 20 programs moving 256 KB tiles, so per-program dispatch
  overhead and sub-DMA-granularity transfers stop dominating (measured
  round 2: the 160-program grid LOST to XLA attention at T=1280, 384 vs
  491 tok/s, and had to hide behind a context-length threshold). The
  online-softmax state (m, l, acc — ops/flash_common.py) persists in VMEM
  scratch across the sequential innermost grid dimension, initialized at
  block 0 and finalized at the last block. Per-row validity windows
  [start, end) ride in as scalar prefetch so left-pad slots and
  not-yet-written slots never contribute.

North-star relevance: this is the op BASELINE.json names ("autoregressive
decode ... implemented as Pallas kernels"); tokens/sec/chip during a debate
round is bounded by this read of the cache (HBM bandwidth).

CPU testing runs the same kernel under ``interpret=True`` against the jnp
reference (tests/test_pallas.py), the SURVEY §4 fake-at-the-seam strategy
applied to kernels.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adversarial_spec_tpu.ops.flash_common import flash_update_heads

_SUBLANE = 8

# Per-K-tile VMEM budget for block_t selection: tiles are [Hkv, block_t, D],
# double-buffered, ×2 for K and V — 1 MiB per tile keeps the working set
# ≈4 MiB, well inside a TensorCore's ~16 MiB VMEM with room for q/scratch.
_TILE_VMEM_BUDGET = 1 << 20


# Operator override for the KV tile length: the VMEM-budget heuristic
# below picks the largest fitting block, but the DMA-size vs
# grid-parallelism balance is an empirical question only a chip run
# answers (not measured). 0 = auto.
_BLOCK_T_OVERRIDE = int(os.environ.get("ADVSPEC_BLOCK_T", "0"))
_warned_block_t: set[int] = set()


def _warn_block_t_fallback(T: int) -> None:
    """Say ONCE per cache length that the override was unusable there —
    a silent fallback would let an operator attribute auto-pick timings
    to the block_t they exported."""
    if T not in _warned_block_t:
        _warned_block_t.add(T)
        import sys

        # graftlint: disable=GL-TRACE -- deliberate trace-time warn-once: block_t is chosen at trace time (T is a static shape), so the fallback must report during tracing or never
        print(
            f"warning: ADVSPEC_BLOCK_T={_BLOCK_T_OVERRIDE} unusable at "
            f"cache length T={T} (needs a positive multiple of "
            f"{_SUBLANE} dividing T within 8x the VMEM budget); using "
            "the auto pick for this shape",
            file=sys.stderr,
        )


def _pick_block_t(T: int, n_kv: int, D: int, itemsize: int) -> int:
    """Largest block that divides the (static) cache length AND keeps one
    [Hkv, block_t, D] tile under the VMEM budget.

    T must be divisible by some candidate (generate() always passes a
    power-of-two bucket ≥128, which 128 or smaller divides). Silently
    falling back to block_t=T here would materialize an [Hkv, T, D]
    tile — Hkv× the VMEM blowup of a normal tile, a silent OOM trap for
    direct kernel callers — so refuse instead (ADVICE r3)."""
    if _BLOCK_T_OVERRIDE:
        ok = (
            _BLOCK_T_OVERRIDE > 0
            and _BLOCK_T_OVERRIDE % _SUBLANE == 0
            and T % _BLOCK_T_OVERRIDE == 0
            # Generous ceiling (8× the auto heuristic's budget): an
            # override may deliberately trade VMEM for DMA size, but an
            # [Hkv, T, D]-scale tile is the OOM trap this function
            # exists to refuse.
            and n_kv * _BLOCK_T_OVERRIDE * D * itemsize
            <= 8 * _TILE_VMEM_BUDGET
        )
        if ok:
            return _BLOCK_T_OVERRIDE
        _warn_block_t_fallback(T)
    # An unusable override falls through to the auto pick (a sweep must
    # stay valid across every shape the run touches); the auto path
    # still refuses shapes with NO valid block below.
    fit = [
        c
        for c in (512, 256, 128, 64, 32, 16, 8)
        if n_kv * c * D * itemsize <= _TILE_VMEM_BUDGET
    ]
    block = next((c for c in fit if T % c == 0), None)
    if block is None:
        raise ValueError(
            f"cache length T={T} has no block_t divisor in {fit}: pad T "
            "to a multiple of 8 (generate() buckets to powers of two "
            "≥128, which never hits this)"
        )
    return block


def _decode_attn_kernel(
    bounds_ref,  # SMEM [B, 2] int32: (start, end) valid-slot window per row
    q_ref,  # VMEM [1, Hkv, G8, D]
    k_ref,  # VMEM [1, Hkv, block_t, D] — one streamed tile (heads-major)
    v_ref,  # VMEM [1, Hkv, block_t, D]
    *rest,  # [ks_ref, vs_ref,] o_ref, m_ref, l_ref, acc_ref
    scale: float,
    attn_softcap: float,
    block_t: int,
    quantized: bool,
):
    # int8-KV mode streams per-(token, head) scale tiles alongside the
    # int8 K/V tiles and dequantizes IN VMEM — the HBM read per decoded
    # token stays at the int8 byte count (the whole point of the int8
    # cache; previously int8 forced the jnp fallback path).
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    t = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    n_kv, G8, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]

    @pl.when(t == 0)
    def _init():
        m_ref[:] = jnp.full((n_kv, G8, 1), -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros((n_kv, G8, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((n_kv, G8, D), jnp.float32)

    start = bounds_ref[b, 0]
    end = bounds_ref[b, 1]
    t0 = t * block_t

    # Skip compute for tiles wholly outside the valid window (the DMA still
    # lands — block skipping is a masking optimization, not a gather).
    @pl.when((t0 < end) & (t0 + block_t > start))
    def _accumulate():
        flash_update_heads(
            q_ref,
            k_ref,
            v_ref,
            ks_ref if quantized else None,
            vs_ref if quantized else None,
            m_ref,
            l_ref,
            acc_ref,
            t0,
            start,
            end,
            scale=scale,
            attn_softcap=attn_softcap,
        )

    @pl.when(t == n_blocks - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        ).astype(o_ref.dtype)


def _mq_attn_kernel(
    bounds_ref,  # VMEM [1, G8, 2]: per (query-row) [start, end) — shared
    # by every KV head of the row (bounds are per query position).
    # VMEM, not SMEM scalar-prefetch: Mosaic can only load SCALARS from
    # SMEM, and this kernel needs the whole per-query bounds vector.
    q_ref,  # VMEM [1, Hkv, G8, D] — G8 = pad(S·g) query rows per head
    k_ref,  # VMEM [1, Hkv, block_t, D]
    v_ref,  # VMEM [1, Hkv, block_t, D]
    *rest,  # [ks_ref, vs_ref,] o_ref, m_ref, l_ref, acc_ref
    scale: float,
    attn_softcap: float,
    block_t: int,
    quantized: bool,
):
    # int8-KV mode mirrors _decode_attn_kernel: scale tiles stream
    # alongside the int8 K/V tiles, dequant in VMEM.
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    n_kv, G8, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]

    @pl.when(t == 0)
    def _init():
        m_ref[:] = jnp.full((n_kv, G8, 1), -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros((n_kv, G8, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((n_kv, G8, D), jnp.float32)

    starts = bounds_ref[0, :, 0]  # [G8]
    ends = bounds_ref[0, :, 1]
    t0 = t * block_t

    # Skip tiles wholly outside EVERY query's window.
    @pl.when((t0 < jnp.max(ends)) & (t0 + block_t > jnp.min(starts)))
    def _accumulate():
        flash_update_heads(
            q_ref,
            k_ref,
            v_ref,
            ks_ref if quantized else None,
            vs_ref if quantized else None,
            m_ref,
            l_ref,
            acc_ref,
            t0,
            starts[:, None],  # per-query bounds broadcast inside
            ends[:, None],
            scale=scale,
            attn_softcap=attn_softcap,
        )

    @pl.when(t == n_blocks - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("attn_softcap", "scale", "interpret")
)
def decode_attention_mq(
    q: jnp.ndarray,  # [B, S, Hq, D] — a SHORT query span (spec verify)
    k_cache: jnp.ndarray,  # [B, Hkv, T, D] heads-major (any float or int8)
    v_cache: jnp.ndarray,  # [B, Hkv, T, D]
    starts: jnp.ndarray,  # [B, S] int32 first valid slot per query
    ends: jnp.ndarray,  # [B, S] int32 one-past-last valid slot per query
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,  # [B, Hkv, T, 1] f32 (int8 KV)
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Multi-query fused decode attention. Returns [B, S, Hq, D].

    The speculative-verification shape: γ+1 query positions per row, each
    attending to the KV cache under its OWN [start, end) window (end
    grows by one per query — in-span causality). Same streamed-tile
    flash recurrence as ``decode_attention``; the queries of one
    (row, kv-head) program stack into the sublane dimension, so the
    whole span costs ONE pass over the KV cache instead of γ+1. This is
    what lets speculative decoding keep the fused kernel instead of
    dropping the entire call to the jnp path (round-1 shortcut).
    """
    B, S, Hq, D = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    g = Hq // Hkv
    rows = S * g
    G8 = -(-rows // _SUBLANE) * _SUBLANE
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    quantized = k_scale is not None
    block_t = _pick_block_t(T, Hkv, D, k_cache.dtype.itemsize)

    # [B, Hkv, S·g, D]: row r = query (r // g), group lane (r % g).
    qg = jnp.transpose(
        q.reshape(B, S, Hkv, g, D), (0, 2, 1, 3, 4)
    ).reshape(B, Hkv, rows, D)
    # Per-row bounds; pad rows get an empty window [0, 0) → masked
    # everywhere → zero output (dropped below). starts/ends may arrive
    # [B, 1] (global layers share one start per row) — broadcast first.
    starts = jnp.broadcast_to(starts, (B, S))
    ends = jnp.broadcast_to(ends, (B, S))
    bnd = jnp.stack(
        [
            jnp.repeat(starts, g, axis=1),
            jnp.repeat(ends, g, axis=1),
        ],
        axis=2,
    ).astype(jnp.int32)  # [B, rows, 2]
    if G8 != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G8 - rows), (0, 0)))
        # Pad rows get the empty window [T, 0): a zero start would feed
        # the kernel's min(starts) tile-skip guard and silently disable
        # leading-tile skipping for windowed layers.
        bnd = jnp.pad(bnd, ((0, 0), (0, G8 - rows), (0, 0)))
        bnd = bnd.at[:, rows:, 0].set(T)

    kv_spec = pl.BlockSpec(
        (1, Hkv, block_t, D), lambda b, t: (b, 0, t, 0)
    )
    in_specs = [
        # Bounds ride in VMEM ([1, G8, 2] block — sublane G8 is a
        # multiple of 8, lane 2 spans the array) because the kernel
        # reads them as vectors; SMEM only serves scalar loads.
        pl.BlockSpec((1, G8, 2), lambda b, t: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, G8, D), lambda b, t: (b, 0, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [bnd, qg, k_cache, v_cache]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, Hkv, block_t, 1), lambda b, t: (b, 0, t, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        functools.partial(
            _mq_attn_kernel,
            scale=scale,
            attn_softcap=attn_softcap,
            block_t=block_t,
            quantized=quantized,
        ),
        grid=(B, T // block_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, Hkv, G8, D), lambda b, t: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G8, 1), jnp.float32),
            pltpu.VMEM((Hkv, G8, 1), jnp.float32),
            pltpu.VMEM((Hkv, G8, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G8, D), q.dtype),
        interpret=interpret,
        name="decode_attention_mq",
    )(*operands)

    out = out[:, :, :rows, :].reshape(B, Hkv, S, g, D)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, S, Hq, D)


def decode_attention_tp(
    q: jnp.ndarray,  # [B, Hq, D]
    k_cache: jnp.ndarray,  # [B, Hkv, T, D] heads-major
    v_cache: jnp.ndarray,  # [B, Hkv, T, D]
    bounds: jnp.ndarray,  # [B, 2]
    mesh,
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,  # [B, Hkv, T, 1] f32 (int8 KV)
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fused decode attention on a GSPMD-sharded mesh.

    GSPMD cannot partition a pallas_call, so the sharded configs
    (BASELINE 3-5: dp over opponents, tp over heads) would otherwise fall
    back to the jnp path. shard_map splits the batch over ``dp`` and the
    KV-head axis over ``tp`` and runs the single-device kernel on each
    device's local shard; GQA groups stay device-local (every KV head and
    its g query heads live on one chip), so there is no cross-device
    softmax and no collectives in the kernel at all.

    Requires B % dp == 0 (generate() pads rows to a dp multiple) and
    Hkv % tp == 0 — callers gate on ``tp_decode_supported``. Axes beyond
    dp/tp (sp during decode) see replicated operands and compute
    identical local results.
    """
    from jax.sharding import PartitionSpec as P

    from adversarial_spec_tpu.parallel.mesh import DP, TP

    kernel = functools.partial(
        decode_attention,
        attn_softcap=attn_softcap,
        scale=scale,
        interpret=interpret,
    )
    in_specs = [
        P(DP, TP, None),
        P(DP, TP, None, None),
        P(DP, TP, None, None),
        P(DP, None),
    ]
    operands = [q, k_cache, v_cache, bounds]
    if k_scale is not None:
        fn = lambda q_, k_, v_, b_, ks_, vs_: kernel(  # noqa: E731
            q_, k_, v_, b_, k_scale=ks_, v_scale=vs_
        )
        in_specs += [P(DP, TP, None, None), P(DP, TP, None, None)]
        operands += [k_scale, v_scale]
    else:
        fn = kernel
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(DP, TP, None),
        check_vma=False,
    )(*operands)


def tp_decode_supported(n_kv_heads: int, mesh) -> bool:
    """True iff the mesh's tp degree keeps GQA groups device-local."""
    from adversarial_spec_tpu.parallel.mesh import TP

    return n_kv_heads % mesh.shape.get(TP, 1) == 0


@functools.partial(
    jax.jit, static_argnames=("attn_softcap", "scale", "interpret")
)
def decode_attention(
    q: jnp.ndarray,  # [B, Hq, D] one query token per row
    k_cache: jnp.ndarray,  # [B, Hkv, T, D] heads-major (any float, or int8)
    v_cache: jnp.ndarray,  # [B, Hkv, T, D]
    bounds: jnp.ndarray,  # [B, 2] int32 (start, end) valid slot window
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,  # [B, Hkv, T, 1] f32 (int8 KV)
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fused decode attention. Returns [B, Hq, D] in q.dtype.

    ``k_scale``/``v_scale`` (both or neither): the caches are int8 with
    per-(token, head) symmetric scales (models/transformer.py:
    _quantize_kv); dequant happens inside the kernel tiles.
    """
    B, Hq, D = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    g = Hq // Hkv
    G8 = max(_SUBLANE, g)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    quantized = k_scale is not None
    block_t = _pick_block_t(T, Hkv, D, k_cache.dtype.itemsize)

    # [B, Hkv, G8, D] — query heads grouped under their KV head, padded to
    # the sublane tile. Pad rows attend to garbage harmlessly (dropped).
    qg = q.reshape(B, Hkv, g, D)
    if G8 != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G8 - g), (0, 0)))

    kv_spec = pl.BlockSpec(
        (1, Hkv, block_t, D), lambda b, t, _: (b, 0, t, 0)
    )
    scale_spec = pl.BlockSpec(
        (1, Hkv, block_t, 1), lambda b, t, _: (b, 0, t, 0)
    )
    in_specs = [
        pl.BlockSpec((1, Hkv, G8, D), lambda b, t, _: (b, 0, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [qg, k_cache, v_cache]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid = (B, T // block_t)
    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel,
            scale=scale,
            attn_softcap=attn_softcap,
            block_t=block_t,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, Hkv, G8, D), lambda b, t, _: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hkv, G8, 1), jnp.float32),
                pltpu.VMEM((Hkv, G8, 1), jnp.float32),
                pltpu.VMEM((Hkv, G8, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G8, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(bounds, *operands)

    return out[:, :, :g, :].reshape(B, Hq, D)
