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

Two entry shapes: ``paged_decode_attention`` (S=1, one query token per
row) and ``paged_decode_attention_mq`` (a short S=γ+1 query span per row
with per-position causal bounds — speculative verify reads the pool ONCE
for the whole span instead of flattening the span into the batch axis and
re-gathering γ+1 times; every decode step of the serving path is one).
In both, one physical page id selects the whole heads-major
[Hkv, page_size, D] slab, so a program folds ALL KV heads (static
per-head loop), mirroring ops/pallas_decode.py's short-context redesign,
and the online-softmax state (m, l, acc) of a row lives in VMEM scratch.

The span kernel WALKS a row's pages (``_paged_mq_attn_kernel``): grid
(B,), one program a row. Its work is the row's live range alone — the
logical pages [min(starts) // page_size, ceil(max(ends) / page_size))
over the span's queries — in blocks of K pages, K from the bytes of one
page slab (``_pages_per_block``: two megabytes of K and of V a block,
16 pages x 8 heads or 32 x 4 at head dim 128). The pools stay in HBM
(``memory_space=ANY``); a block's pages are copied through the page
table, each to its place in one [Hkv, K·page_size, D] tile of a
double-buffered VMEM scratch, and ``flash_update_heads`` folds the tile
while the next block — or after the row's last, the next row's first —
is in flight. A table of 512 pages costs a row of 85 pages 3 or 6
fetches, not 512 grid steps of one 64-128 KB page each.

The single-query kernel, and the span kernel over a pool Mosaic cannot
cut pages out of by hand (``_sliceable``: a head dim off the 128 lanes,
an int8 pool's [page_size, 1] scale pages), keep the pipelined gather:
grid (B, n_pages_per_seq), the page table rides in as a scalar-prefetch
operand so each grid step's BlockSpec ``index_map`` selects the physical
page to DMA next, one page a step whatever the row holds; state is
initialized at page 0, finalized and written at the last page.

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

from adversarial_spec_tpu.ops.flash_common import (
    flash_update,
    flash_update_heads,
)

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


# What one fetch of K (or of V) should move. A page slab alone is 64-128 KB
# at 7B widths: too small a copy to reach the chip's streaming rate, too
# small a tile to fold efficiently. Two megabytes do both (chip runs at
# Mistral-7B's and Qwen2-7B's shapes: PERF.md, PR 30), and two buffers each
# of K and V then take 8 MiB, half of the VMEM a kernel gets by default;
# flash_update_heads' temporaries need the rest.
_BLOCK_BYTES = 2 << 20
_LANES = 128


# A block's float32 scores [query rows, K·page_size] live in VMEM beside
# its tiles: the 288 rows of a latent verify span (9 positions x 32 heads
# on one shared key) would make 4.7 MB of them at the K the tiles' bytes
# allow. One megabyte of scores caps K there (14 pages) and leaves the
# per-head kernels' K as it was (their 40-64 rows allow over 60).
_SCORE_BYTES = 1 << 20


def _pages_per_block(
    n_kv: int,
    page_size: int,
    head_dim: int,
    itemsize: int,
    table_width: int,
    query_rows: int = _SUBLANE,
) -> int:
    """How many logical pages ``_paged_mq_attn_kernel`` fetches and folds
    at a time: as many page slabs [Hkv, page_size, D] as make
    ``_BLOCK_BYTES``, no more than keep a head's float32 scores under
    ``_SCORE_BYTES``, no more than the table holds. From the operands'
    shapes alone."""
    slab = n_kv * page_size * head_dim * itemsize
    by_scores = _SCORE_BYTES // (query_rows * 4 * page_size)
    return max(1, min(_BLOCK_BYTES // slab, by_scores, table_width))


# What the walk keeps in VMEM for every query row, whatever the block: the
# row's bounds (its own and the next program's, each [G8, 2] int32 in whole
# lanes, double-buffered), and a head its queries and its output
# (double-buffered), the running max and normalizer (one lane used of 128)
# and the float32 accumulator. Of the 16 MiB a kernel gets by default the
# tiles take up to 8 (``_BLOCK_BYTES``) and a block's scores, masks and
# float32 head slices about 3 more; the rows' state gets 3 (compiled for a
# v5e at Mistral-7B's 8 KV heads: 128 rows a head fit, 176 do not).
_ROW_STATE_BYTES = 3 << 20


def _queries_per_call(
    n_queries: int,
    rows_per_query: int,
    n_kv: int,
    q_width: int,
    v_width: int,
    itemsize: int,
) -> int:
    """How many of a span's queries one call of the walk takes: all of
    them while their state fits ``_ROW_STATE_BYTES`` (a verify span's 9
    always do), else the span in equal parts that do (an admission's 64
    positions x 4 query heads a KV head are 256 rows a head: two calls).
    From the operands' shapes alone."""
    per_row = 2 * 2 * _LANES * 4 + n_kv * (
        2 * q_width * itemsize  # the queries, double-buffered
        + 2 * v_width * itemsize  # the output, double-buffered
        + 2 * _LANES * 4  # m and l
        + v_width * 4  # the accumulator
    )
    fit = max(1, _ROW_STATE_BYTES // (rows_per_query * per_row))
    n_calls = -(-n_queries // fit)
    return -(-n_queries // n_calls)


def _span_calls(one_call, n_queries: int, per_call: int, *spans):
    """``one_call`` over the span's queries, ``per_call`` at a time (axis 1
    of every array in ``spans``), joined again; one call where it takes
    them all."""
    if per_call >= n_queries:
        return one_call(*spans)
    return jnp.concatenate(
        [
            one_call(*(x[:, s0 : s0 + per_call] for x in spans))
            for s0 in range(0, n_queries, per_call)
        ],
        axis=1,
    )


def _sliceable(pools) -> bool:
    """Whether a kernel can copy one page slab out of each pool by itself.
    Mosaic slices a ref in HBM only in whole lanes, along the minor
    dimension too where the slice takes all of it ("Slice shape along
    dimension 4 must be aligned to tiling (128)"): a head dim that is no
    multiple of 128 and an int8 pool's [page_size, 1] scale pages reach
    VMEM through BlockSpecs alone."""
    return all(x.shape[-1] % _LANES == 0 for x in pools)


def _paged_mq_attn_kernel(
    table_ref,  # SMEM [B, P]: physical page id per (row, logical page)
    layer_ref,  # SMEM [1]: pool layer
    bounds_ref,  # VMEM [1, G8, 2]: per query-row [start, end). VMEM, not
    # SMEM scalar-prefetch: Mosaic only loads SCALARS from SMEM and this
    # kernel needs the whole per-query bounds vector (the _mq_attn_kernel
    # pattern from ops/pallas_decode.py).
    next_bounds_ref,  # VMEM [1, G8, 2]: the same of row b + 1
    *refs,  # the ``n_q`` query refs, then the operands named below
    scale: float,
    page_size: int,
    pages_per_block: int,
    attn_softcap: float,
    n_q: int = 1,
    fold=None,
):
    # q_refs: VMEM [1, Hkv, G8, D] — G8 = pad(S·g) query rows per head
    #   (latent attention brings two: the absorbed and the rotated part)
    # k_hbm, v_hbm: HBM [L, n_pages, Hkv, page, D*]: the pools stay
    #   where they are (their widths may differ: a latent pool's do)
    # o_ref: VMEM [1, Hkv, G8, Dv]
    # m_ref, l_ref, acc_ref: VMEM scratch, the row's online softmax
    # k_buf, v_buf: VMEM scratch [2, Hkv, K·page, D*]: a block's tile, twice
    # sem: DMA semaphores [2], one a buffer
    # slot_ref: SMEM scratch [1]: the buffer the row's first block is in
    q_refs = refs[:n_q]
    (
        k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem,
        slot_ref,
    ) = refs[n_q:]
    fold = fold or _fold_heads
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_kv, G8, D = acc_ref.shape
    P = table_ref.shape[1]
    K = pages_per_block
    layer = layer_ref[0]

    def live_range(ref):
        """Logical pages [lo, hi) some query of the row attends into,
        and the blocks of K pages that cover them from ``lo`` up."""
        first = jnp.clip(jnp.min(ref[0, :, 0]), 0, P * page_size)
        last = jnp.clip(jnp.max(ref[0, :, 1]), 0, P * page_size)
        lo = jax.lax.div(first, page_size)
        hi = jax.lax.div(last + page_size - 1, page_size)
        return lo, hi, jax.lax.div(jnp.maximum(hi - lo, 0) + K - 1, K)

    def block_copies(row, lo, hi, blk, slot, *, wait):
        """Start, or wait for, the copy of every live page of the row's
        block ``blk`` into buffer ``slot``. Pages are not contiguous in
        the pool: each goes through the page table, to its place in the
        block's [Hkv, K·page, D] tile."""
        p0 = lo + blk * K

        def one(j, carry):
            page_id = jnp.maximum(table_ref[row, p0 + j], 0)
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                copy = pltpu.make_async_copy(
                    pool.at[layer, page_id],
                    buf.at[slot, :, at, :],
                    sem.at[slot],
                )
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, jnp.clip(hi - p0, 0, K), one, 0)

    lo, hi, n_blocks = live_range(bounds_ref)

    @pl.when(b == 0)
    def _first_row():
        # A tile's slots past the row's last page are never copied into
        # and are masked, but 0 · NaN would still reach acc: what they
        # hold must be finite from the start.
        for buf in (k_buf, v_buf):
            buf[:] = jnp.zeros(buf.shape, buf.dtype)
        slot_ref[0] = 0
        block_copies(b, lo, hi, 0, 0, wait=False)

    # Every later row's first block was started by the row before it.
    slot0 = slot_ref[0]

    m_ref[:] = jnp.full((n_kv, G8, 1), -jnp.inf, jnp.float32)
    l_ref[:] = jnp.zeros((n_kv, G8, 1), jnp.float32)
    acc_ref[:] = jnp.zeros((n_kv, G8, D), jnp.float32)
    starts = bounds_ref[0, :, 0][:, None]  # [G8, 1]: per-query bounds
    ends = bounds_ref[0, :, 1][:, None]  # broadcast inside flash_update

    def next_row_first_block(slot):
        @pl.when(b + 1 < n_rows)
        def _():
            nlo, nhi, _ = live_range(next_bounds_ref)
            block_copies(b + 1, nlo, nhi, 0, slot, wait=False)

    def fold_block(blk, carry):
        slot = (slot0 + blk) % 2

        # Keep one block in flight behind the one being folded: this
        # row's next, or after its last the next row's first.
        @pl.when(blk + 1 < n_blocks)
        def _():
            block_copies(b, lo, hi, blk + 1, 1 - slot, wait=False)

        @pl.when(blk + 1 == n_blocks)
        def _():
            next_row_first_block(1 - slot)

        block_copies(b, lo, hi, blk, slot, wait=True)

        # Unmapped pages (id <= 0: trash page or table padding — the same
        # sentinel convention as _paged_attn_kernel) inside the range and
        # the tile's slots past it are masked out of every query.
        p0 = lo + blk * K
        at = jax.lax.broadcasted_iota(jnp.int32, (1, K * page_size), 1)
        live = jnp.zeros((1, K * page_size), jnp.bool_)
        for j in range(K):
            mapped = (p0 + j < hi) & (
                table_ref[b, jnp.minimum(p0 + j, P - 1)] > 0
            )
            here = (at >= j * page_size) & (at < (j + 1) * page_size)
            live = live | (here & mapped)
        fold(
            q_refs,
            k_buf.at[pl.ds(slot, 1)],
            v_buf.at[pl.ds(slot, 1)],
            m_ref,
            l_ref,
            acc_ref,
            p0 * page_size,  # logical token offset of the tile
            starts,
            ends,
            scale=scale,
            attn_softcap=attn_softcap,
            live=live,
        )
        return carry

    jax.lax.fori_loop(0, n_blocks, fold_block, 0)

    @pl.when(n_blocks == 0)
    def _():
        next_row_first_block(slot0)

    slot_ref[0] = (slot0 + n_blocks) % 2
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _fold_heads(q_refs, k_tile, v_tile, m_ref, l_ref, acc_ref, t0, starts,
                ends, **kw):
    """A tile of per-head keys and values: the head loop every kernel shares."""
    flash_update_heads(
        q_refs[0], k_tile, v_tile, None, None, m_ref, l_ref, acc_ref, t0,
        starts, ends, **kw,
    )


def _fold_latent(q_refs, k_tile, v_tile, m_ref, l_ref, acc_ref, t0, starts,
                 ends, *, scale, attn_softcap, live):
    """A tile of the latent cache, read ONCE for all heads: the compressed
    vectors (``v_tile``) are the values and, against the absorbed part of
    the queries, the unrotated part of the scores; the shared rotated keys
    (``k_tile``) give the rest. Operands meet in their stored dtype on the
    MXU, sums in float32."""
    q_lat_ref, q_rot_ref = q_refs
    c = v_tile[0, 0]  # [K·page, kv_rank]
    r = k_tile[0, 0]  # [K·page, rope_pad]
    contract_last = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(
        q_lat_ref[0, 0], c, contract_last, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        q_rot_ref[0, 0], r, contract_last, preferred_element_type=jnp.float32
    )
    m_ref[0], l_ref[0], acc_ref[0] = flash_update(
        None, None, c, t0, starts, ends, m_ref[0], l_ref[0], acc_ref[0],
        attn_softcap=attn_softcap, live=live, scores=s * scale,
    )


def _paged_mq_attn_grid_kernel(
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
    causality). The span's queries stack into the sublane dimension
    (row r = query r//g, group lane r%g), so the whole span costs ONE
    pass over the row's pages instead of the batch-axis flatten paying
    the gather γ+1 times. Page-table sentinel convention unchanged:
    entries <= 0 are unmapped and masked; a row no query reaches into
    returns zeros.

    The walk (``_paged_mq_attn_kernel``): one program a row, over the
    row's LIVE pages only — the logical pages [min(starts) // page_size,
    ceil(max(ends) / page_size)), so a windowed layer skips its leading
    pages and nothing is paid for the table's width — K =
    ``_pages_per_block`` pages at a time. The pools stay in HBM; a
    block's pages are copied through the page table into one
    [Hkv, K·page_size, D] tile, double-buffered, and the next block (or
    the next row's first) is in flight while ``flash_update_heads`` folds
    this one. Mosaic cuts a page out of an HBM pool only in whole lanes
    (``_sliceable``); a pool it cannot cut — a head dim off the 128 lanes,
    an int8 pool's [page_size, 1] scale pages — keeps the pipelined
    gather of ``paged_decode_attention``: a (B, P) grid, one page a
    step (``_paged_mq_attn_grid_kernel``).
    """
    layer, (k_pages, v_pages, k_scale, v_scale) = _layered(
        layer, k_pages, v_pages, k_scale, v_scale
    )
    B, S, Hq, D = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    P = page_table.shape[1]
    g = Hq // Hkv
    T = P * page_size  # logical slot horizon of the table
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    quantized = k_scale is not None
    pools = [k_pages, v_pages] + ([k_scale, v_scale] if quantized else [])
    static = dict(scale=scale, page_size=page_size, attn_softcap=attn_softcap)

    def one_call(q, starts, ends):
        """One ``pallas_call`` over the span's queries, or a part of them."""
        S = q.shape[1]
        rows = S * g
        G8 = -(-rows // _SUBLANE) * _SUBLANE
        # [B, Hkv, S·g, D]: row r = query (r // g), group lane (r % g).
        qg = jnp.transpose(
            q.reshape(B, S, Hkv, g, D), (0, 2, 1, 3, 4)
        ).reshape(B, Hkv, rows, D)
        bnd = _span_bounds(starts, ends, B, S, g, G8, T)  # [B, G8, 2]
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G8 - rows), (0, 0)))
        if _sliceable(pools):
            call, operands = _walk_call(
                [qg], k_pages, v_pages, page_table, layer, bnd, static
            )
            out = pl.pallas_call(
                **call, interpret=interpret, name="paged_decode_attention_mq"
            )(*operands)
        else:
            kernel = functools.partial(
                _paged_mq_attn_grid_kernel, quantized=quantized, **static
            )

            def page_map(b, p, table_ref, layer_ref):
                page = jnp.maximum(table_ref[b, p], 0)
                return (layer_ref[0], page, 0, 0, 0)

            # The layer dim is squeezed: the kernel sees
            # [1, Hkv, page_size, *].
            in_specs = [
                pl.BlockSpec((1, G8, 2), lambda b, p, *_: (b, 0, 0)),
                pl.BlockSpec((1, Hkv, G8, D), lambda b, p, *_: (b, 0, 0, 0)),
            ] + [
                pl.BlockSpec((None, 1, Hkv, page_size, x.shape[-1]), page_map)
                for x in pools
            ]
            out = pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2,
                    grid=(B, P),
                    in_specs=in_specs,
                    out_specs=pl.BlockSpec(
                        (1, Hkv, G8, D), lambda b, *_: (b, 0, 0, 0)
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
            )(page_table, layer, bnd, qg, *pools)

        out = out[:, :, :rows, :].reshape(B, Hkv, S, g, D)
        return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, S, Hq, D)

    # A span wider than the walk's VMEM holds (an admission's delta) goes
    # through it in parts; a verify span is one call.
    return _span_calls(
        one_call,
        S,
        _queries_per_call(S, g, Hkv, D, D, q.dtype.itemsize),
        q,
        jnp.broadcast_to(starts, (B, S)),
        jnp.broadcast_to(ends, (B, S)),
    )


def _walk_call(
    qs, k_pages, v_pages, page_table, layer, bnd, static, fold=None
):
    """``_paged_mq_attn_kernel`` over the layer-stacked pools, one program
    a row, as (the ``pallas_call``'s arguments but its name, its
    operands): the entry point makes the call under its own name.
    ``qs``: the query operands [B, Hkv, G8, *]; ``bnd`` [B, G8, 2]. The
    call returns [B, Hkv, G8, Dv], Dv the width of ``v_pages``."""
    B, Hkv, G8 = qs[0].shape[:3]
    page_size, Dv = v_pages.shape[3], v_pages.shape[4]
    P = page_table.shape[1]
    K = _pages_per_block(
        Hkv,
        page_size,
        max(k_pages.shape[4], Dv),
        k_pages.dtype.itemsize,
        P,
        query_rows=G8,
    )
    kernel = functools.partial(
        _paged_mq_attn_kernel, pages_per_block=K, n_q=len(qs), fold=fold,
        **static,
    )
    in_specs = (
        [
            pl.BlockSpec((1, G8, 2), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(
                (1, G8, 2), lambda b, *_: (jnp.minimum(b + 1, B - 1), 0, 0)
            ),
        ]
        + [
            pl.BlockSpec((1, Hkv, G8, q.shape[3]), lambda b, *_: (b, 0, 0, 0))
            for q in qs
        ]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    )
    scratch = [
        pltpu.VMEM((Hkv, G8, 1), jnp.float32),
        pltpu.VMEM((Hkv, G8, 1), jnp.float32),
        pltpu.VMEM((Hkv, G8, Dv), jnp.float32),
        pltpu.VMEM((2, Hkv, K * page_size, k_pages.shape[4]), k_pages.dtype),
        pltpu.VMEM((2, Hkv, K * page_size, Dv), v_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),
    ]
    call = dict(
        kernel=kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, Hkv, G8, Dv), lambda b, *_: (b, 0, 0, 0)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G8, Dv), qs[0].dtype),
    )
    return call, (page_table, layer, bnd, bnd, *qs, k_pages, v_pages)


def _span_bounds(starts, ends, B: int, S: int, g: int, G8: int, T: int):
    """[B, G8, 2] per query row (row r = query r // g): its [start, end),
    the pad rows' the empty window [T, 0) — a zero start would pull the
    row's live range down to page 0 and disable leading-page skipping for
    windowed layers (same trap as decode_attention_mq)."""
    starts = jnp.broadcast_to(starts, (B, S))
    ends = jnp.broadcast_to(ends, (B, S))
    bnd = jnp.stack(
        [jnp.repeat(starts, g, axis=1), jnp.repeat(ends, g, axis=1)], axis=2
    ).astype(jnp.int32)
    rows = S * g
    if G8 != rows:
        bnd = jnp.pad(bnd, ((0, 0), (0, G8 - rows), (0, 0)))
        bnd = bnd.at[:, rows:, 0].set(T)
    return bnd


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_attention_mq(
    q_lat: jnp.ndarray,  # [B, S, H, kv_rank]: queries with W_UK absorbed
    q_rot: jnp.ndarray,  # [B, S, H, rope_pad]: rotated part, zero-padded
    r_pages: jnp.ndarray,  # [(L,) n_pages, 1, page, rope_pad] shared keys
    c_pages: jnp.ndarray,  # [(L,) n_pages, 1, page, kv_rank] compressed
    page_table: jnp.ndarray,  # [B, P] int32; <= 0 = unmapped
    starts: jnp.ndarray,  # [B, S]
    ends: jnp.ndarray,  # [B, S]
    scale: float,
    interpret: bool = False,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Paged latent attention for a verify span, absorbed form. Returns
    sum_t p_t · c_t per query and head, [B, S, H, kv_rank]; the caller
    applies W_UV.

    The latent cache has ONE head: a token's compressed vector c (values,
    and with the absorbed queries the unrotated part of the scores) and
    its rotated key r, shared by all H heads. So the span's S·H query
    rows fold into one pass of ``_paged_mq_attn_kernel``'s walk over the
    row's live pages, each page copied once (``_fold_latent``):
    s = (q_lat · c + q_rot · r) · scale, p = softmax(s), out = p · c. The
    zero padding of ``rope_pad`` (whole lanes, so that Mosaic can cut the
    pages: ``_sliceable``) adds nothing to a score and is no work."""
    layer, (r_pages, c_pages) = _layered(layer, r_pages, c_pages)
    if not _sliceable([r_pages, c_pages]):
        raise ValueError(
            "a latent pool's widths must be whole lanes: got "
            f"{r_pages.shape[-1]} and {c_pages.shape[-1]}"
        )
    B, S, H, R = q_lat.shape
    page_size = c_pages.shape[3]

    def one_call(q_lat, q_rot, starts, ends):
        S = q_lat.shape[1]
        rows = S * H
        G8 = -(-rows // _SUBLANE) * _SUBLANE
        bnd = _span_bounds(
            starts, ends, B, S, H, G8, page_table.shape[1] * page_size
        )
        qs = [
            jnp.pad(
                q.reshape(B, 1, rows, q.shape[-1]),
                ((0, 0), (0, 0), (0, G8 - rows), (0, 0)),
            )
            for q in (q_lat, q_rot)
        ]
        call, operands = _walk_call(
            qs, r_pages, c_pages, page_table, layer, bnd,
            dict(scale=scale, page_size=page_size, attn_softcap=0.0),
            fold=_fold_latent,
        )
        out = pl.pallas_call(
            **call, interpret=interpret, name="paged_latent_attention_mq"
        )(*operands)
        return out[:, 0, :rows].reshape(B, S, H, R)

    # The span's S·H query rows share one key: 64 positions are 2,048 rows,
    # more than the walk's VMEM holds, so a wide span goes in parts.
    return _span_calls(
        one_call,
        S,
        _queries_per_call(
            S, H, 1, R + q_rot.shape[-1], R, q_lat.dtype.itemsize
        ),
        q_lat,
        q_rot,
        jnp.broadcast_to(starts, (B, S)),
        jnp.broadcast_to(ends, (B, S)),
    )


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
