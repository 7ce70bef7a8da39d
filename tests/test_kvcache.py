"""Paged KV-cache manager tests: allocator bookkeeping and pool scatter."""

import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine.kvcache import (
    OutOfPages,
    PageAllocator,
    PagedCacheLayout,
    init_page_pool,
    token_positions_to_pages,
    write_tokens,
)


class TestPageAllocator:
    def test_extend_allocates_minimal_pages(self):
        a = PageAllocator(n_pages=8, page_size=4)
        a.new_sequence(0)
        new = a.extend(0, 3)  # 3 tokens → 1 page
        assert len(new) == 1
        assert a.length(0) == 3
        assert a.extend(0, 1) == []  # 4th token fits the same page
        new2 = a.extend(0, 1)  # 5th token → second page
        assert len(new2) == 1
        assert a.free_pages == 6

    def test_tables_are_ordered(self):
        a = PageAllocator(n_pages=8, page_size=2)
        a.new_sequence(1)
        a.extend(1, 6)
        assert len(a.table(1)) == 3

    def test_out_of_pages_rolls_back(self):
        a = PageAllocator(n_pages=2, page_size=2)
        a.new_sequence(0)
        a.extend(0, 4)  # both pages used
        a.new_sequence(1)
        with pytest.raises(OutOfPages):
            a.extend(1, 2)
        assert a.length(1) == 0
        assert a.free_pages == 0
        a.free_sequence(0)
        assert a.free_pages == 2
        a.extend(1, 2)  # now fits

    def test_free_sequence_recycles(self):
        a = PageAllocator(n_pages=4, page_size=2)
        a.new_sequence(0)
        a.extend(0, 8)
        assert a.free_pages == 0
        a.free_sequence(0)
        assert a.free_pages == 4

    def test_duplicate_sequence_rejected(self):
        a = PageAllocator(n_pages=4, page_size=2)
        a.new_sequence(0)
        with pytest.raises(ValueError, match="already allocated"):
            a.new_sequence(0)

    def test_table_array_padding(self):
        a = PageAllocator(n_pages=8, page_size=2)
        a.new_sequence(0)
        a.new_sequence(1)
        a.extend(0, 4)
        a.extend(1, 2)
        arr = a.table_array([0, 1], max_pages=4)
        assert arr.shape == (2, 4)
        assert (arr[0, :2] >= 0).all() and (arr[0, 2:] == -1).all()
        assert arr[1, 0] >= 0 and (arr[1, 1:] == -1).all()

    def test_table_array_overflow_raises(self):
        a = PageAllocator(n_pages=8, page_size=1)
        a.new_sequence(0)
        a.extend(0, 5)
        with pytest.raises(ValueError, match="spans"):
            a.table_array([0], max_pages=4)


class TestPagePool:
    def test_write_and_readback(self):
        layout = PagedCacheLayout(
            n_pages=4, page_size=2, n_layers=2, n_kv_heads=2, head_dim=4
        )
        pool = init_page_pool(layout, dtype=jnp.float32)
        a = PageAllocator(layout.n_pages, layout.page_size)
        a.new_sequence(0)
        a.extend(0, 3)

        L, B, S = 2, 1, 3
        # Heads-major cache layout [L, B, Hkv, S, D].
        k_new = jnp.arange(L * B * S * 2 * 4, dtype=jnp.float32).reshape(
            L, B, 2, S, 4
        )
        v_new = -k_new
        positions = np.array([[0, 1, 2]])
        page_ids, offsets = token_positions_to_pages(a, [0], positions)
        pool = write_tokens(pool, k_new, v_new, page_ids, offsets)

        table = a.table(0)
        # Token 0 → page table[0] slot 0; token 2 → page table[1] slot 0.
        np.testing.assert_array_equal(
            np.asarray(pool["k"][:, table[0], :, 0]),
            np.asarray(k_new[:, 0, :, 0]),
        )
        np.testing.assert_array_equal(
            np.asarray(pool["k"][:, table[0], :, 1]),
            np.asarray(k_new[:, 0, :, 1]),
        )
        np.testing.assert_array_equal(
            np.asarray(pool["k"][:, table[1], :, 0]),
            np.asarray(k_new[:, 0, :, 2]),
        )
        np.testing.assert_array_equal(
            np.asarray(pool["v"][:, table[0], :, 0]),
            np.asarray(v_new[:, 0, :, 0]),
        )

    def test_capacity(self):
        layout = PagedCacheLayout(
            n_pages=16, page_size=128, n_layers=1, n_kv_heads=1, head_dim=8
        )
        assert layout.tokens_capacity == 2048


PS = 8  # page size of the cases below


def _span(table, lo, hi):
    """(page_ids, offsets) [1, hi - lo] of positions [lo, hi) of one
    sequence whose pages are ``table`` (the handoff's construction)."""
    slots = np.arange(lo, hi, dtype=np.int32)[None, :]
    return np.asarray(table, np.int32)[slots // PS], slots % PS


def _promoted(pages):
    """A tier promotion's run: whole pages, the scheduler's construction."""
    return (
        np.repeat(np.asarray(pages, np.int32), PS)[None, :],
        np.tile(np.arange(PS, dtype=np.int32), len(pages))[None, :],
    )


def _rows(*spans):
    return tuple(np.concatenate(parts) for parts in zip(*spans))


# name: (page_ids, offsets, pool kind, whole pages written as pages)
WRITES = {
    "after a matched prefix": (*_span([3, 5, 7, 9, 1, 2], 2 * PS, 5 * PS + 3), "bf16", 3),
    "whole pages only": (*_span([4, 6, 8], 0, 3 * PS), "bf16", 3),
    "padded admission from 0": (*_rows(_span([1, 2, 3], 0, 2 * PS + 5), _span([4, 5, 6], 0, 2 * PS + 5)), "bf16", 4),
    "from mid-page": (*_span([9, 8, 7, 6], PS + 3, 4 * PS - 2), "bf16", 1),
    "shorter than one page": (*_span([2, 4], PS + 1, PS + 7), "bf16", 0),
    "int8 pool": (*_span([3, 5, 7, 9, 1], PS, 4 * PS + 6), "int8", 3),
    "latent pool": (*_span([3, 5, 7, 9], 0, 3 * PS + 2), "latent", 3),
    "pool with state": (*_span([3, 5, 7, 9], PS, 3 * PS + 1), "state", 2),
    "promoted run": (*_promoted([6, 2, 9]), "bf16", 3),
}


def _pool_and_new(kind, B, S, rng):
    L, H, n_pages = 3, 2, 12

    def arr(shape, dtype):
        if dtype == np.int8:
            return rng.integers(-128, 128, shape).astype(np.int8)
        return rng.normal(size=shape).astype(dtype)

    dk, dv = (4, 6) if kind == "latent" else (4, 4)
    kv = np.int8 if kind == "int8" else np.float32
    pool = {
        "k": arr((L, n_pages, H, PS, dk), kv),
        "v": arr((L, n_pages, H, PS, dv), kv),
    }
    new = {"k": arr((L, B, H, S, dk), kv), "v": arr((L, B, H, S, dv), kv)}
    if kind == "int8":
        for name in ("ks", "vs"):
            pool[name] = arr((L, n_pages, H, PS, 1), np.float32)
            new[name] = arr((L, B, H, S, 1), np.float32)
    if kind == "state":
        pool["ssm"] = arr((L, 4, 5, 6), np.float32)
        pool["conv"] = arr((L, 4, 3, 6), np.float32)
    return pool, new


class TestPageRows:
    @pytest.mark.parametrize("case", WRITES)
    def test_the_pool_is_what_writing_every_token_leaves(self, case):
        """A host write puts pages it fills whole in as pages, the rest
        token by token: the pool comes out byte for byte as writing each
        token by itself into its (page, slot) leaves it, slots past the
        span in its last page and every other leaf untouched."""
        from adversarial_spec_tpu.engine.kvcache import _whole_pages

        page_ids, offsets, kind, n_whole = WRITES[case]
        B, S = page_ids.shape
        pool, new = _pool_and_new(kind, B, S, np.random.default_rng(1))
        want = {name: x.copy() for name, x in pool.items()}
        for name in new:
            for b in range(B):
                for s in range(S):
                    want[name][:, page_ids[b, s], :, offsets[b, s]] = (
                        new[name][:, b, :, s]
                    )
        assert _whole_pages(page_ids, offsets, PS)[1].size == n_whole
        got = write_tokens(
            {name: jnp.asarray(x) for name, x in pool.items()},
            jnp.asarray(new["k"]),
            jnp.asarray(new["v"]),
            page_ids,
            offsets,
            ks_new=jnp.asarray(new["ks"]) if "ks" in new else None,
            vs_new=jnp.asarray(new["vs"]) if "vs" in new else None,
        )
        assert set(got) == set(want)
        for name in want:
            assert np.asarray(got[name]).dtype == want[name].dtype
            assert np.asarray(got[name]).tobytes() == want[name].tobytes(), name

    def test_a_page_another_token_targets_stays_token_rows(self):
        """Page 5 is filled whole and also written by the span's tail: it
        and the pages after it in the run stay token rows."""
        from adversarial_spec_tpu.engine.kvcache import _whole_pages

        ids, offs = _span([3, 5, 7], 0, 3 * PS)
        ids = np.concatenate([ids, [[5]]], axis=1)
        offs = np.concatenate([offs, [[0]]], axis=1)
        start, pages = _whole_pages(ids, offs, PS)
        assert (start, pages.tolist()) == (0, [[3]])

    def test_the_counters_count_host_writes_alone(self):
        """One host write counts its tokens and those that went in as
        pages; a traced ``layer`` write (the paged admission's) counts
        nothing."""
        import jax

        from adversarial_spec_tpu import obs

        written = obs.hot.kv_written_tokens
        paged = obs.hot.kv_page_written_tokens
        page_ids, offsets, kind, _ = WRITES["after a matched prefix"]
        pool, new = _pool_and_new(kind, 1, page_ids.shape[1], np.random.default_rng(2))
        pool = {name: jnp.asarray(x) for name, x in pool.items()}
        w0, p0 = written.value, paged.value
        pool = write_tokens(pool, jnp.asarray(new["k"]), jnp.asarray(new["v"]), page_ids, offsets)
        assert (written.value - w0, paged.value - p0) == (3 * PS + 3, 3 * PS)

        @jax.jit
        def one_layer(pool, k, v, layer):
            return write_tokens(pool, k, v, page_ids, offsets, layer=layer)

        w0, p0 = written.value, paged.value
        one_layer(pool, jnp.asarray(new["k"][0]), jnp.asarray(new["v"][0]), 1)
        assert (written.value, paged.value) == (w0, p0)


# -- a span's K/V by DMA (ops/kv_write.py) ------------------------------------

SPAN_PS = 32  # two bfloat16 tiles of 16 slots a page


def _span_rows(rows, width, first, trash_tail=0, seed=0):
    """(page_ids, offsets) [rows, width]: row b's positions first + b ..
    first + b + width - 1 of a sequence of its own, through pages of its
    own; the last ``trash_tail`` positions of a row aimed at page 0."""
    perm = np.random.default_rng(seed).permutation(np.arange(1, 13))
    pages = np.zeros((rows, width), np.int32)
    offs = np.zeros((rows, width), np.int32)
    for b in range(rows):
        table = perm[3 * b : 3 * b + 3] if width < 2 * SPAN_PS else perm[b:]
        q = first + b + np.arange(width)
        pages[b] = table[q // SPAN_PS]
        offs[b] = q % SPAN_PS
        if trash_tail:
            pages[b, -trash_tail:] = 0
    return pages, offs


# name: (page_ids, offsets, heads, k width, v width, what else the pool holds)
SPAN_WRITES = {
    "verify 4 x 9 across a page, drafts on the trash page": (
        *_span_rows(4, 9, SPAN_PS - 4, trash_tail=3), 2, 128, 128, None
    ),
    "admission of 64 from mid-page": (*_span_rows(1, 64, 13), 2, 128, 128, None),
    "admission of 128 from mid-page": (
        *_span_rows(1, 128, 21, trash_tail=5), 2, 128, 128, None
    ),
    "latent: one head, k 128, v 256": (
        *_span_rows(4, 9, 7, trash_tail=2), 1, 128, 256, None
    ),
    "64-wide heads on 128 lanes": (*_span_rows(4, 9, 3), 2, 64, 64, None),
    "beside state rows": (*_span_rows(2, 9, 26), 2, 128, 128, "state"),
}


def _span_pool(heads, dk, dv, rest, rng):
    L, n_pages = 3, 13
    lanes = lambda d: -(-d // 128) * 128  # noqa: E731 (stored on whole lanes)
    pool = {
        "k": rng.normal(size=(L, n_pages, heads, SPAN_PS, lanes(dk))),
        "v": rng.normal(size=(L, n_pages, heads, SPAN_PS, lanes(dv))),
    }
    pool = {name: jnp.asarray(x, jnp.bfloat16) for name, x in pool.items()}
    if rest == "state":
        pool["ssm"] = jnp.asarray(rng.normal(size=(L, 4, 5, 128)), jnp.float32)
        pool["conv"] = jnp.asarray(rng.normal(size=(L, 4, 3, 128)), jnp.bfloat16)
    return pool


class TestSpanWrite:
    @pytest.mark.parametrize("case", SPAN_WRITES)
    def test_the_kernel_leaves_the_scatter_s_bytes(self, case):
        """A span's K/V through ``paged_kv_write`` (interpret mode) leave
        the pool byte for byte what XLA's scatter leaves, on every page
        but the trash page: the verify step's form (token-major, in a
        layer loop) and the admission's (``write_tokens`` with a
        ``layer``, heads-major). The other leaves are untouched."""
        import jax

        from adversarial_spec_tpu.ops import kv_write

        page_ids, offsets, heads, dk, dv, rest = SPAN_WRITES[case]
        B, S = page_ids.shape
        rng = np.random.default_rng(3)
        pool = _span_pool(heads, dk, dv, rest, rng)
        assert kv_write.span_write_kernel(pool, True)

        def rows(d, width):  # heads-major [B, H, S, width], zero past d
            x = rng.normal(size=(B, heads, S, width)).astype(np.float32)
            x[..., d:] = 0
            return jnp.asarray(x, jnp.bfloat16)

        new = {"k": rows(dk, pool["k"].shape[-1]), "v": rows(dv, pool["v"].shape[-1])}
        flat = {  # the verify step's token-major rows
            name: jnp.swapaxes(x, 1, 2).reshape(B * S, heads, -1)
            for name, x in new.items()
        }

        def admission(use_pallas):
            return jax.jit(
                lambda pool, k, v: write_tokens(
                    pool, k, v, page_ids, offsets, layer=1,
                    use_pallas=use_pallas, interpret=True,
                )
            )(pool, new["k"], new["v"])

        def verify(use_pallas):
            def step(pool, new):
                return jax.lax.fori_loop(
                    1, 3,
                    lambda l, pool: kv_write.write_span(
                        pool, l, new, page_ids, offsets,
                        use_pallas=use_pallas, interpret=True,
                    ),
                    pool,
                )

            return jax.jit(step)(pool, flat)

        for form in (admission, verify):
            want, got = form(False), form(True)
            assert set(got) == set(pool)
            for name in pool:
                w, g = np.asarray(want[name]), np.asarray(got[name])
                if name in ("k", "v"):
                    w, g = w[:, 1:], g[:, 1:]  # page 0: the trash page
                    assert not (g == np.asarray(pool[name])[:, 1:]).all()
                assert g.tobytes() == w.tobytes(), (form.__name__, name)

    def test_int8_pools_and_meshes_take_the_scatter(self, monkeypatch):
        """One predicate decides: a bfloat16 pool of whole-lane heads goes
        through the kernel where the Pallas kernels are on; an int8 pool
        (its [page, 1] scale pages) and a span over a mesh of two
        devices go through XLA's scatter, in ``forward_paged_decode``
        as in ``span_write_kernel``."""
        import dataclasses

        import jax

        from adversarial_spec_tpu.models import transformer as T
        from adversarial_spec_tpu.models.config import get_config
        from adversarial_spec_tpu.ops import kv_write

        cfg = dataclasses.replace(get_config("llama", "tiny"), head_dim=128)
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        layout = PagedCacheLayout(
            n_pages=5, page_size=SPAN_PS, n_layers=cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        )
        calls = []
        real = kv_write.paged_kv_write

        def spy(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(kv_write, "paged_kv_write", spy)
        B, S = 2, 3
        q_pos = jnp.asarray([[5, 6, 7], [9, 10, 11]], jnp.int32)
        args = (
            jnp.ones((B, S), jnp.int32), q_pos,
        )
        rest = (
            jnp.asarray([[1, 2], [3, 4]], jnp.int32),  # page tables
            jnp.asarray([[1] * S, [3] * S], jnp.int32),  # write pages
            q_pos % SPAN_PS,
            jnp.stack([jnp.zeros_like(q_pos), q_pos + 1], -1),
            q_pos,
        )
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))

        def kernel_ran(kv_dtype, mesh):
            pool = init_page_pool(layout, jnp.float32, kv_dtype=kv_dtype)
            assert kv_write.span_write_kernel(
                pool, mesh is None
            ) == (kv_dtype == "" and mesh is None)
            del calls[:]
            T.forward_paged_decode(
                params, cfg, *args, pool, *rest, use_pallas=True,
                pallas_interpret=True, mesh=mesh,
            )
            return bool(calls)

        assert kernel_ran("", None)
        assert not kernel_ran("int8", None)
        assert not kernel_ran("", mesh)
        assert not kv_write.span_write_kernel(
            init_page_pool(layout, jnp.float32), False
        )
