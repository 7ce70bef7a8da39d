"""Pallas TPU kernel: decode attention over a PAGED KV cache.

Paged KV (the second kernel BASELINE.json's north star names): instead of
one dense [B, H, T_max, D] buffer per batch — which must be sized for the
longest sequence and reallocated/copied as debates grow — key/value live in
fixed-size pages [n_pages, Hkv, page_size, D] shared by all sequences, and
each row owns an ordered page list (the page table). Debate rounds grow
sequences at different rates (opponents finish at different lengths), so
paging keeps HBM occupancy at O(tokens actually written) and makes
prefix-sharing across opponents real: same spec prompt → same physical
pages, refcounted by engine/prefix_cache.py (shipped in PR 2 — rows
whose tables alias a cached prefix read it through this kernel like any
other page).

Kernel shape: grid (B, n_pages_per_seq); the page table rides in as a
scalar-prefetch operand so each grid step's BlockSpec ``index_map`` selects
the physical page to DMA next — the gather happens in the pipeline, not in
the kernel body. One physical page id selects the whole heads-major
[Hkv, page_size, D] slab, so each program folds ALL KV heads (static
per-head loop), mirroring ops/pallas_decode.py's short-context redesign:
Hkv× fewer sequential programs and Hkv× larger DMAs than the round-2
(B, Hkv, P) grid. Online-softmax state (m, l, acc) persists in VMEM
scratch across the sequential innermost grid dimension: initialized at
page 0, finalized and written at the last page.

Two entry shapes share that design: ``paged_decode_attention`` (S=1, one
query token per row — the decode hot loop) and
``paged_decode_attention_mq`` (a short S=γ+1 query span per row with
per-position causal bounds — speculative verify reads the pool ONCE for
the whole span instead of flattening the span into the batch axis and
re-gathering γ+1 times).

Tested under ``interpret=True`` on CPU against the dense jnp reference
(tests/test_pallas.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adversarial_spec_tpu.ops.flash_common import flash_update_heads

_SUBLANE = 8


def _layered(layer, *pages):
    """Normalize the pool operands to the layer-stacked form
    [L, n_pages, Hkv, page_size, *] plus an int32[1] layer index.

    ``layer`` given: the operands ARE the whole pool and the kernel DMAs
    pages of that layer straight out of it — the decode step hands the
    pool over untouched instead of slicing (= copying) one layer's pages
    per call. ``layer`` None: the operands are one layer's pages, viewed
    as a one-layer pool.
    """
    if layer is None:
        layer = 0
        pages = tuple(x if x is None else x[None] for x in pages)
    return jnp.asarray(layer, jnp.int32).reshape(1), pages


def _paged_attn_kernel(
    bounds_ref,  # SMEM [B, 2]: (start, end) token window per row
    table_ref,  # SMEM [B, P]: physical page id per (row, logical page)
    layer_ref,  # SMEM [1]: pool layer (consumed by the index_maps only)
    q_ref,  # VMEM [1, Hkv, G8, D]
    k_ref,  # VMEM [1, Hkv, page, D] — page slab selected by index_map
    v_ref,  # VMEM [1, Hkv, page, D]
    *rest,  # [ks_ref, vs_ref,] o_ref, m_ref, l_ref, acc_ref
    scale: float,
    page_size: int,
    attn_softcap: float,
    quantized: bool,
):
    # int8 pools stream per-(token, head) scale pages alongside the int8
    # K/V pages and dequantize IN VMEM — HBM read per decoded token stays
    # at the int8 byte count (mirrors ops/pallas_decode.py's dense mode).
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    n_kv, G8, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full((n_kv, G8, 1), -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros((n_kv, G8, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((n_kv, G8, D), jnp.float32)

    start = bounds_ref[b, 0]
    end = bounds_ref[b, 1]
    page_id = table_ref[b, p]
    t0 = p * page_size  # logical token offset of this page

    # Unmapped pages — id <= 0: physical page 0 is the reserved TRASH page
    # (callers shift allocator ids +1; engine/scheduler.py:TRASH_PAGE) and
    # negative ids are table padding — and pages wholly outside
    # [start, end) are masked; compute still runs (SPMD) but contributes
    # nothing.
    @pl.when((page_id > 0) & (t0 < end))
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

    @pl.when(p == n_pages - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("attn_softcap", "scale", "interpret")
)
def paged_decode_attention(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,  # [n_pages, Hkv, page_size, D] heads-major
    v_pages: jnp.ndarray,  # [n_pages, Hkv, page_size, D]
    page_table: jnp.ndarray,  # [B, P] int32; <= 0 = unmapped (see below)
    bounds: jnp.ndarray,  # [B, 2] int32 (start, end) token window
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,  # [n_pages, Hkv, page, 1] (int8)
    v_scale: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,  # int32 scalar: see _layered
) -> jnp.ndarray:
    """Fused paged decode attention. Returns [B, Hq, D].

    With ``layer``, ``k_pages``/``v_pages`` (and the scales) are the
    whole layer-stacked pool [L, n_pages, Hkv, page_size, *] and the
    kernel reads that layer's pages in place.

    Page-table sentinel convention (shared with the jnp gather path in
    models/transformer.py:forward_paged_decode): physical page 0 is the
    reserved TRASH page — callers allocate real pages from id 1 up — so
    any table entry <= 0 (trash or negative padding) is treated as
    unmapped and masked out of the softmax.

    ``k_scale``/``v_scale`` (both or neither): the pages are int8 with
    per-(token, head) symmetric scale pages; dequant happens inside the
    kernel on the VMEM-resident page.
    """
    layer, (k_pages, v_pages, k_scale, v_scale) = _layered(
        layer, k_pages, v_pages, k_scale, v_scale
    )
    B, Hq, D = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    P = page_table.shape[1]
    g = Hq // Hkv
    G8 = max(_SUBLANE, g)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    quantized = k_scale is not None

    qg = q.reshape(B, Hkv, g, D)
    if G8 != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G8 - g), (0, 0)))

    def page_map(b, p, bounds_ref, table_ref, layer_ref):
        return (layer_ref[0], jnp.maximum(table_ref[b, p], 0), 0, 0, 0)

    # The layer dim is squeezed: the kernel sees [1, Hkv, page_size, *].
    page_spec = pl.BlockSpec((None, 1, Hkv, page_size, D), page_map)
    in_specs = [
        pl.BlockSpec((1, Hkv, G8, D), lambda b, p, *_: (b, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec((None, 1, Hkv, page_size, 1), page_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel,
            scale=scale,
            page_size=page_size,
            attn_softcap=attn_softcap,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, P),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, Hkv, G8, D), lambda b, p, *_: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hkv, G8, 1), jnp.float32),
                pltpu.VMEM((Hkv, G8, 1), jnp.float32),
                pltpu.VMEM((Hkv, G8, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G8, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(bounds, page_table, layer, *operands)

    return out[:, :, :g, :].reshape(B, Hq, D)


def _paged_mq_attn_kernel(
    table_ref,  # SMEM [B, P]: physical page id per (row, logical page)
    layer_ref,  # SMEM [1]: pool layer (consumed by the index_maps only)
    bounds_ref,  # VMEM [1, G8, 2]: per query-row [start, end). VMEM, not
    # SMEM scalar-prefetch: Mosaic only loads SCALARS from SMEM and this
    # kernel needs the whole per-query bounds vector (the _mq_attn_kernel
    # pattern from ops/pallas_decode.py).
    q_ref,  # VMEM [1, Hkv, G8, D] — G8 = pad(S·g) query rows per head
    k_ref,  # VMEM [1, Hkv, page, D] — page slab selected by index_map
    v_ref,  # VMEM [1, Hkv, page, D]
    *rest,  # [ks_ref, vs_ref,] o_ref, m_ref, l_ref, acc_ref
    scale: float,
    page_size: int,
    attn_softcap: float,
    quantized: bool,
):
    # int8 pools mirror _paged_attn_kernel: scale pages stream alongside
    # the int8 K/V pages, dequant in VMEM.
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    n_kv, G8, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full((n_kv, G8, 1), -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros((n_kv, G8, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((n_kv, G8, D), jnp.float32)

    starts = bounds_ref[0, :, 0]  # [G8]
    ends = bounds_ref[0, :, 1]
    page_id = table_ref[b, p]
    t0 = p * page_size  # logical token offset of this page

    # Unmapped pages (id <= 0: trash page or table padding — the same
    # sentinel convention as _paged_attn_kernel) and pages wholly outside
    # EVERY query's window are skipped.
    @pl.when(
        (page_id > 0)
        & (t0 < jnp.max(ends))
        & (t0 + page_size > jnp.min(starts))
    )
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

    @pl.when(p == n_pages - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("attn_softcap", "scale", "interpret")
)
def paged_decode_attention_mq(
    q: jnp.ndarray,  # [B, S, Hq, D] — a SHORT query span (spec verify)
    k_pages: jnp.ndarray,  # [n_pages, Hkv, page_size, D] heads-major
    v_pages: jnp.ndarray,  # [n_pages, Hkv, page_size, D]
    page_table: jnp.ndarray,  # [B, P] int32; <= 0 = unmapped
    starts: jnp.ndarray,  # [B, S] int32 first valid slot per query
    ends: jnp.ndarray,  # [B, S] int32 one-past-last valid slot per query
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,  # [n_pages, Hkv, page, 1] (int8)
    v_scale: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,  # int32 scalar: see _layered
) -> jnp.ndarray:
    """Multi-position fused paged attention. Returns [B, S, Hq, D].

    The speculative-verification shape over the PAGED pool: γ+1 query
    positions per row, each attending through the row's page table under
    its OWN [start, end) window (end grows by one per position — in-span
    causality). Same (B, n_pages) grid and scalar-prefetch page gather
    as ``paged_decode_attention``; the span's queries stack into the
    sublane dimension (row r = query r//g, group lane r%g), so the whole
    span costs ONE pass over the row's pages instead of the batch-axis
    flatten paying the gather γ+1 times. Page-table sentinel convention
    unchanged: entries <= 0 are unmapped and masked.
    """
    layer, (k_pages, v_pages, k_scale, v_scale) = _layered(
        layer, k_pages, v_pages, k_scale, v_scale
    )
    B, S, Hq, D = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    P = page_table.shape[1]
    g = Hq // Hkv
    rows = S * g
    G8 = -(-rows // _SUBLANE) * _SUBLANE
    T = P * page_size  # logical slot horizon of the table
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    quantized = k_scale is not None

    # [B, Hkv, S·g, D]: row r = query (r // g), group lane (r % g).
    qg = jnp.transpose(
        q.reshape(B, S, Hkv, g, D), (0, 2, 1, 3, 4)
    ).reshape(B, Hkv, rows, D)
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
        # the min(starts) page-skip guard and disable leading-page
        # skipping for windowed layers (same trap as decode_attention_mq).
        bnd = jnp.pad(bnd, ((0, 0), (0, G8 - rows), (0, 0)))
        bnd = bnd.at[:, rows:, 0].set(T)

    def page_map(b, p, table_ref, layer_ref):
        return (layer_ref[0], jnp.maximum(table_ref[b, p], 0), 0, 0, 0)

    page_spec = pl.BlockSpec((None, 1, Hkv, page_size, D), page_map)
    in_specs = [
        pl.BlockSpec((1, G8, 2), lambda b, p, *_: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, G8, D), lambda b, p, *_: (b, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [bnd, qg, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec((None, 1, Hkv, page_size, 1), page_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(
            _paged_mq_attn_kernel,
            scale=scale,
            page_size=page_size,
            attn_softcap=attn_softcap,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, P),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, Hkv, G8, D), lambda b, p, *_: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hkv, G8, 1), jnp.float32),
                pltpu.VMEM((Hkv, G8, 1), jnp.float32),
                pltpu.VMEM((Hkv, G8, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G8, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention_mq",
    )(page_table, layer, *operands)

    out = out[:, :, :rows, :].reshape(B, Hkv, S, g, D)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, S, Hq, D)


def paged_decode_attention_dp_tp(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,  # [L, n_pages, Hkv, page_size, D] — the pool
    v_pages: jnp.ndarray,  # [L, n_pages, Hkv, page_size, D]
    page_table: jnp.ndarray,  # [B, P] GLOBAL physical ids (see contract)
    bounds: jnp.ndarray,  # [B, 2]
    mesh,
    layer: jnp.ndarray,  # int32 scalar: which layer's pages to read
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fused paged decode attention on a MIXED dp×tp mesh.

    Rows and page slabs shard over ``dp``, the head axis over ``tp`` —
    all heavy operands stay device-local; there are no collectives in or
    around the kernel.

    Layout contract (generate()'s mixed paged setup): the pages axis is
    laid out per-dp-slice — slice d owns global pages [d·Lp, (d+1)·Lp)
    with Lp = n_pages/dp, local page 0 of each slice is that slice's
    trash page, and every row's pages live in the row's OWN slice. The
    page table carries GLOBAL ids because the surrounding chunk loop
    (scatter + gather fallback) runs under GSPMD, which is global-view;
    this wrapper subtracts the slice base so the kernel indexes its
    local block. Global trash (id 0) and negative padding land ≤ 0
    after the shift and stay masked; out-of-slice ids cannot occur by
    construction.
    """
    from jax.sharding import PartitionSpec as P

    from adversarial_spec_tpu.parallel.mesh import DP, TP

    n_pages = k_pages.shape[1]
    dp = mesh.shape[DP]
    local_pages = n_pages // dp

    kernel = functools.partial(
        paged_decode_attention,
        attn_softcap=attn_softcap,
        scale=scale,
        interpret=interpret,
    )

    def fn(q_, k_, v_, t_, b_, layer_, *scales):
        base = jax.lax.axis_index(DP) * local_pages
        qkw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return kernel(q_, k_, v_, t_ - base, b_, layer=layer_, **qkw)

    page_spec = P(None, DP, TP, None, None)
    in_specs = [
        P(DP, TP, None), page_spec, page_spec, P(DP, None), P(DP, None), P(),
    ]
    operands = [q, k_pages, v_pages, page_table, bounds, layer]
    if k_scale is not None:
        in_specs += [page_spec, page_spec]
        operands += [k_scale, v_scale]
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(DP, TP, None),
        check_vma=False,
    )(*operands)


def paged_decode_attention_tp(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,  # [L, n_pages, Hkv, page_size, D] — the pool
    v_pages: jnp.ndarray,  # [L, n_pages, Hkv, page_size, D]
    page_table: jnp.ndarray,  # [B, P] GLOBAL physical ids
    bounds: jnp.ndarray,  # [B, 2]
    mesh,
    layer: jnp.ndarray,  # int32 scalar: which layer's pages to read
    attn_softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fused paged decode attention with the HEAD axis tp-sharded.

    The paged-pool counterpart of ops/pallas_decode.py:
    decode_attention_tp: GSPMD cannot partition a pallas_call, so
    tp-sharded paged configs (BASELINE 5: TP over a 70B judge) would
    fall back to the gather path. shard_map splits the pool's Hkv axis
    (and q's head axis) over ``tp``; the page table and bounds replicate
    — every device reads the same pages, its own head slice. GQA groups
    stay device-local (callers gate on tp | n_kv_heads), so there are no
    collectives in the kernel. The batch axis stays UNSHARDED here: the
    global-page-table layout has no per-device page locality (dp-local
    pools are the scheduler's sharded path, engine/scheduler.py).
    """
    from jax.sharding import PartitionSpec as P

    from adversarial_spec_tpu.parallel.mesh import TP

    kernel = functools.partial(
        paged_decode_attention,
        attn_softcap=attn_softcap,
        scale=scale,
        interpret=interpret,
    )
    page_spec = P(None, None, TP, None, None)  # pool: Hkv over tp
    in_specs = [
        P(None, TP, None),  # q: heads over tp
        page_spec,
        page_spec,
        P(None, None),  # table: replicated
        P(None, None),  # bounds: replicated
        P(),  # layer: replicated
    ]
    operands = [q, k_pages, v_pages, page_table, bounds, layer]
    if k_scale is not None:
        in_specs += [page_spec, page_spec]
        operands += [k_scale, v_scale]

    def fn(q_, k_, v_, t_, b_, layer_, *scales):
        qkw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return kernel(q_, k_, v_, t_, b_, layer=layer_, **qkw)

    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, TP, None),
        check_vma=False,
    )(*operands)
