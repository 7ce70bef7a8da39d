"""A span's new keys and values into their pages: one function for the
verify step and the paged admission (``write_span``).

The pool is heads-major, ``[L, n_pages, Hkv, page_size, D]`` a leaf, and a
span brings T tokens, each with its page and its slot in the page. XLA's
scatter (``.at[layer, page, head, slot].set``) moves one D-row a (token,
head) and does it about one row at a time on a v5e, ~100 ns a row: 288 rows
a layer a leaf for a verify step of 4 rows x 9 positions at 8 KV heads.

``paged_kv_write`` does it by DMA instead. Mosaic cuts an HBM ref only in
whole tiles ("Slice shape along dimension 3 must be aligned to tiling"),
so a single slot of a page cannot be a copy's target; a TILE of
``_tile_rows`` slots of every head can. The kernel copies each tile the
span touches into VMEM once (every head at once, one copy a tile a leaf),
sets the span's rows in it, and copies it back: O(tokens) copies, none a
head. A verify row's nine positions touch one or two tiles.

The tiles are found from the span's pages alone (``write_plan``; in a
layer loop XLA computes it once, before the loop): token t's tile is the
first token's of its (page, tile), so no tile is read
or written twice in a call, and the pool comes out byte for byte what the
scatter leaves, where no two tokens name one slot (a span's positions never
do but on the trash page, which nobody reads: there the last token wins,
where the scatter leaves one of them).

The kernel runs where ``span_write_kernel`` says: one device, the Pallas
kernels on, and every page leaf a tile can be cut out of (whole lanes, a
page of whole tiles). Elsewhere, the int8 pool's [page, 1] scale pages and
meshes among them, the scatter runs as it always did.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128

# What one wave of tiles may hold in VMEM, every leaf together; a span
# that touches more tiles goes in several waves (one verify step or
# admission at 7B widths touches 4-12 tiles of 32 KB a leaf: one wave).
_WAVE_BYTES = 4 << 20


def _tile_rows(dtype) -> int:
    """Slots a tile of the pool holds: the second-minor tiling of an HBM
    array in this dtype (8 rows of 32 bits, packed)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def span_write_kernel(pool, use_pallas: bool) -> bool:
    """Whether a span's K/V go into ``pool`` through ``paged_kv_write``:
    the one predicate the traced programs and the host's counters share.
    ``use_pallas`` is the caller's "Pallas kernels on, one device"; the
    page leaves must each be cut into whole tiles in HBM."""
    leaves = [pool[name] for name in ("k", "v", "ks", "vs") if name in pool]
    return bool(use_pallas) and all(
        x.shape[-1] % _LANES == 0 and x.shape[3] % _tile_rows(x.dtype) == 0
        for x in leaves
    )


class WritePlan(NamedTuple):
    """Where a call's tokens go, tile by tile (all int32):
    ``n_tiles`` [1]; ``tile_page``, ``tile_index`` [T]: the page and the
    tile in it of tile g < n_tiles; ``token_tile``, ``token_row`` [T]:
    token t's tile and its row in the tile."""

    n_tiles: jnp.ndarray
    tile_page: jnp.ndarray
    tile_index: jnp.ndarray
    token_tile: jnp.ndarray
    token_row: jnp.ndarray


def write_plan(pages, offsets, tile_rows: int, page_size: int) -> WritePlan:
    """The tiles ``pages`` / ``offsets`` (any shape, T entries) touch,
    each once, numbered in the order their first token comes."""
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)
    offsets = jnp.asarray(offsets, jnp.int32).reshape(-1)
    T = pages.shape[0]
    tile = offsets // tile_rows
    key = pages * (page_size // tile_rows) + tile
    first = jnp.argmax(key[:, None] == key[None, :], axis=1)  # [T]
    t = jnp.arange(T, dtype=jnp.int32)
    leads = first == t
    number = jnp.cumsum(leads.astype(jnp.int32)) - 1  # of a leading token
    lead_of = jnp.zeros((T,), jnp.int32).at[jnp.where(leads, number, T)].set(
        t, mode="drop"
    )
    return WritePlan(
        n_tiles=jnp.sum(leads.astype(jnp.int32)).reshape(1),
        tile_page=pages[lead_of],
        tile_index=tile[lead_of],
        token_tile=number[first],
        token_row=offsets % tile_rows,
    )


def _kv_write_kernel(
    layer_ref,  # SMEM [1]
    n_tiles_ref,  # SMEM [1]
    tile_page_ref,  # SMEM [T]
    tile_index_ref,  # SMEM [T]
    token_tile_ref,  # SMEM [T]
    token_row_ref,  # SMEM [T]
    *refs,  # n new [T, Hkv, D] (VMEM), n pools (HBM, aliased to the
    # outputs), n outputs (HBM), n tile buffers [wave, Hkv, rows, D]
    # (VMEM), DMA semaphores [2]
    n: int,
    tile_rows: int,
):
    news, pools, outs, bufs = (refs[i * n : (i + 1) * n] for i in range(4))
    sem = refs[4 * n]
    wave = bufs[0].shape[0]
    T = token_tile_ref.shape[0]
    layer = layer_ref[0]
    n_tiles = n_tiles_ref[0]

    def copies(g, g0, into_pool):
        at = pl.ds(pl.multiple_of(tile_index_ref[g] * tile_rows, tile_rows),
                   tile_rows)
        page = tile_page_ref[g]
        for src, dst, buf in zip(pools, outs, bufs):
            if into_pool:
                yield pltpu.make_async_copy(
                    buf.at[g - g0], dst.at[layer, page, :, at, :], sem.at[1]
                )
            else:
                yield pltpu.make_async_copy(
                    src.at[layer, page, :, at, :], buf.at[g - g0], sem.at[0]
                )

    def each_tile(g0, g1, into_pool, wait):
        def body(g, carry):
            for copy in copies(g, g0, into_pool):
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(g0, g1, body, 0)

    def one_wave(w, carry):
        g0 = w * wave
        g1 = jnp.minimum(n_tiles, g0 + wave)
        each_tile(g0, g1, into_pool=False, wait=False)
        each_tile(g0, g1, into_pool=False, wait=True)

        def set_row(t, carry):
            g = token_tile_ref[t]

            @pl.when((g >= g0) & (g < g1))
            def _():
                for new, buf in zip(news, bufs):
                    here = jax.lax.broadcasted_iota(
                        jnp.int32, buf.shape[1:], 1
                    ) == token_row_ref[t]
                    row = jnp.broadcast_to(new[t][:, None, :], buf.shape[1:])
                    buf[g - g0] = jnp.where(here, row, buf[g - g0])

            return carry

        jax.lax.fori_loop(0, T, set_row, 0)
        each_tile(g0, g1, into_pool=True, wait=False)
        each_tile(g0, g1, into_pool=True, wait=True)
        return carry

    jax.lax.fori_loop(0, (n_tiles + wave - 1) // wave, one_wave, 0)


def paged_kv_write(pool, new, layer, plan: WritePlan, *, interpret=False):
    """``pool`` (a dict; its page leaves ``[L, n_pages, Hkv, page, D]``)
    with layer ``layer``'s slots of ``plan`` set to ``new`` ({name:
    [T, Hkv, D]}, token-major), in place: the leaves ``new`` names are
    aliased through one ``pallas_call``. The other leaves pass as they are."""
    names = list(new)
    leaves = [pool[name] for name in names]
    n = len(names)
    T, Hkv = new[names[0]].shape[:2]
    rows = _tile_rows(leaves[0].dtype)
    tile_bytes = sum(Hkv * rows * x.shape[-1] * x.dtype.itemsize for x in leaves)
    wave = max(1, min(T, _WAVE_BYTES // tile_bytes))
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),) + tuple(plan)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kv_write_kernel, n=n, tile_rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[
                pl.BlockSpec(new[name].shape, lambda i, *_: (0, 0, 0))
                for name in names
            ]
            + [hbm] * n,
            out_specs=[hbm] * n,
            scratch_shapes=[
                pltpu.VMEM((wave, Hkv, rows, x.shape[-1]), x.dtype)
                for x in leaves
            ]
            + [pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves],
        # operands count the prefetched scalars; the pools follow the news
        input_output_aliases={len(scalars) + n + i: i for i in range(n)},
        interpret=interpret,
        name="paged_kv_write",
    )(*scalars, *(new[name].astype(x.dtype) for name, x in zip(names, leaves)),
      *leaves)
    return {**pool, **dict(zip(names, out))}


def write_span(pool, layer, new, pages, offsets, *, use_pallas=False,
               interpret=False):
    """``pool`` with layer ``layer``'s K/V of a span set: ``new`` {name:
    [T, Hkv, D*]} token-major, token t at slot ``offsets[t]`` of page
    ``pages[t]`` (both [T], or any shape of T entries). Through
    ``paged_kv_write`` where ``span_write_kernel(pool, use_pallas)``, else
    XLA's scatter. Called once a layer in a layer loop, the kernel's
    ``write_plan`` depends on nothing the loop carries, and XLA computes
    it once before the loop.

    The scatter addresses (layer, page, head, slot) and moves whole
    D-rows, contiguous in the heads-major layout, so XLA keeps the pool in
    the layout the attention kernels require. (A [Hkv, D] update window a
    token makes it re-lay the WHOLE pool out token-major and back for
    every kernel call.)"""
    pages = jnp.asarray(pages).reshape(-1)
    offsets = jnp.asarray(offsets).reshape(-1)
    if span_write_kernel(pool, use_pallas):
        k = pool["k"]
        plan = write_plan(pages, offsets, _tile_rows(k.dtype), k.shape[3])
        return paged_kv_write(pool, new, layer, plan, interpret=interpret)
    heads = jnp.arange(next(iter(new.values())).shape[1])
    return {
        **pool,
        **{
            name: pool[name]
            .at[layer, pages[:, None], heads[None, :], offsets[:, None]]
            .set(val.astype(pool[name].dtype))
            for name, val in new.items()
        },
    }
