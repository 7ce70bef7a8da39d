"""Paged KV-cache manager: page allocator + device page pool.

Host-side bookkeeping (free list, per-sequence page tables) stays in numpy
— it is O(pages) integer work with data-dependent control flow that has no
business inside an XLA program — while the page pool itself lives on
device as two dense arrays [n_pages, Hkv, page_size, D] per layer group,
written with vectorized scatters and read by the paged Pallas kernel
(ops/pallas_paged.py).

Sizing: a debate round's opponents share the pool; ``n_pages`` bounds
total resident tokens across all rows, not per-row length — the property
that lets a 16k-context judge coexist with short critics (SURVEY §5
long-context obligation).

Pages are REF-COUNTED: a page may back several sequences at once (a
cached prefix adopted by every opponent in a round — engine/
prefix_cache.py) plus one reference held by the prefix cache itself. A
page returns to the free list only when its last reference drops.
Sharing is copy-on-append rather than true copy-on-write: block content
is immutable once a page is full, and a writer's positions always lie
past its adopted prefix, so no write path ever touches a shared page.

jax is imported lazily (inside the device-pool functions only): the
host-side allocator must stay importable from jax-free flows (the mock
engine routes its prefix-cache accounting through ``PageAllocator``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np


class OutOfPages(RuntimeError):
    pass


# The pool's leaves that are pages of tokens; the others ("ssm", "conv":
# a recurrent state a row, beside state-space layers) no position addresses.
PAGE_LEAVES = ("k", "v", "ks", "vs")


@dataclass
class PagedCacheLayout:
    n_pages: int
    page_size: int
    n_layers: int
    n_kv_heads: int
    head_dim: int  # width of a "k" row
    # Width of a "v" row where it differs (0 = head_dim). A latent layout
    # (models/config.py ``kv_layout``) has one head, "k" = the rotated key
    # all heads share, zero-padded to whole lanes, and "v" = the
    # compressed vector that is the values and the rest of the keys.
    v_dim: int = 0

    @property
    def tokens_capacity(self) -> int:
        return self.n_pages * self.page_size


class PageAllocator:
    """Free-list page allocator with per-sequence ordered page tables.

    Every allocated page carries a reference count: 1 per sequence whose
    table contains it plus 1 if the prefix cache holds it. ``extend``
    allocates fresh pages at refcount 1; ``adopt`` appends already-
    allocated (shared) pages to a new sequence's table, bumping their
    counts; ``free_sequence`` / ``cache_unref`` drop references and a
    page returns to the free list only at zero.
    """

    def __init__(self, n_pages: int, page_size: int):
        self.n_pages = n_pages
        self.page_size = page_size
        self._free = list(range(n_pages - 1, -1, -1))  # pop() → page 0 first
        self._tables: dict[int, list[int]] = {}
        self._lengths: dict[int, int] = {}
        self._refs: dict[int, int] = {}  # page -> reference count
        # Pages with an in-flight tier swap (a host->device promotion
        # scatter targeting them — engine/kvtier.py): they must stay
        # referenced until the swap owner unpins, and freeing one is a
        # bookkeeping corruption check_invariants / _release catch.
        self._swap_pins: dict[int, int] = {}  # page -> pin count

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def held_pages(self) -> set[int]:
        """Every page some sequence or cache holds a reference to."""
        return set(self._refs)

    def pages_within(self, n_tokens: int) -> set[int]:
        """The pages of every live sequence that reach into its last
        ``n_tokens`` tokens: what a layer that sees ``n_tokens``
        positions back still needs for the sequence's next query."""
        out: set[int] = set()
        for seq_id, table in self._tables.items():
            first = max(0, self._lengths[seq_id] - n_tokens) // self.page_size
            out.update(table[first:])
        return out

    def new_sequence(self, seq_id: int) -> None:
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        self._tables[seq_id] = []
        self._lengths[seq_id] = 0

    def pages_needed(self, seq_id: int, n_tokens: int) -> int:
        """Fresh pages an ``extend(seq_id, n_tokens)`` would allocate."""
        needed = -(-(self._lengths[seq_id] + n_tokens) // self.page_size)
        return max(0, needed - len(self._tables[seq_id]))

    def extend(self, seq_id: int, n_tokens: int) -> list[int]:
        """Reserve room for n_tokens more; returns newly allocated pages."""
        table = self._tables[seq_id]
        length = self._lengths[seq_id]
        needed_pages = -(-(length + n_tokens) // self.page_size)
        new_pages = []
        while len(table) < needed_pages:
            if not self._free:
                # Roll back this call's allocations before failing.
                for p in new_pages:
                    table.remove(p)
                    del self._refs[p]
                    self._free.append(p)
                raise OutOfPages(
                    f"paged KV cache exhausted: {self.n_pages} pages of "
                    f"{self.page_size} tokens all in use"
                )
            p = self._free.pop()
            table.append(p)
            self._refs[p] = 1
            new_pages.append(p)
        self._lengths[seq_id] = length + n_tokens
        return new_pages

    def adopt(self, seq_id: int, pages: list[int], n_tokens: int) -> None:
        """Share already-allocated ``pages`` (a cached prefix) into a fresh
        sequence. Must precede any ``extend`` for the sequence — adopted
        pages form its table head, exactly covering ``n_tokens``."""
        if self._tables[seq_id] or self._lengths[seq_id]:
            raise ValueError(
                f"sequence {seq_id} already has pages; adopt must come first"
            )
        if n_tokens != len(pages) * self.page_size:
            raise ValueError(
                f"adopt of {len(pages)} pages must cover exactly "
                f"{len(pages) * self.page_size} tokens, got {n_tokens}"
            )
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"cannot adopt unallocated page {p}")
        for p in pages:
            self._refs[p] += 1
        self._tables[seq_id].extend(pages)
        self._lengths[seq_id] = n_tokens

    def cache_ref(self, page: int) -> None:
        """Take the prefix cache's reference on an allocated page."""
        if page not in self._refs:
            raise ValueError(f"cannot cache-ref unallocated page {page}")
        self._refs[page] += 1

    def cache_unref(self, page: int) -> None:
        """Drop the prefix cache's reference (page frees at zero)."""
        self._release(page)

    def swap_pin(self, page: int) -> None:
        """Mark ``page`` as the target of an in-flight tier swap (a
        promotion's host→device write — engine/kvtier.py). Freeing a
        pinned page is a refcount corruption: the swap would scatter
        into storage another sequence may own by then. Pins pair with
        ``swap_unpin`` in try/finally (GL-REFCOUNT enforces the
        pairing statically)."""
        if page not in self._refs:
            raise ValueError(f"cannot swap-pin unallocated page {page}")
        self._swap_pins[page] = self._swap_pins.get(page, 0) + 1

    def swap_unpin(self, page: int) -> None:
        """Drop one swap pin (the promotion write was dispatched — the
        page's owning references keep it alive from here)."""
        n = self._swap_pins.get(page, 0)
        if n <= 0:
            raise RuntimeError(f"swap-unpin without pin on page {page}")
        if n == 1:
            del self._swap_pins[page]
        else:
            self._swap_pins[page] = n - 1

    def _release(self, page: int) -> None:
        refs = self._refs.get(page, 0)
        if refs <= 0:
            raise RuntimeError(f"double free of page {page}")
        if refs == 1:
            if page in self._swap_pins:
                raise RuntimeError(
                    f"freeing page {page} with a tier swap in flight "
                    "(swap_pin held)"
                )
            del self._refs[page]
            self._free.append(page)
        else:
            self._refs[page] = refs - 1

    def truncate(self, seq_id: int, n_tokens: int) -> list[int]:
        """Shrink ``seq_id`` to ``n_tokens``, releasing tail pages that no
        longer back any of its tokens. The speculative-decode rollback
        primitive: a verify step reserves pages for the full γ-token
        draft up front, then rolls the rejected tail back here — each
        released page drops ONE reference, so a tail page shared with
        the prefix cache (or another sequence) merely loses this
        sequence's hold and stays resident for its other owners
        (callers never truncate below an adopted prefix: the accepted
        length always covers the prompt, and shared prefix pages sit at
        the table head — the copy-on-append boundary).

        Returns the pages this sequence released (refcount dropped; they
        are back on the free list only if that was the last reference).
        """
        length = self._lengths[seq_id]
        if not 0 <= n_tokens <= length:
            raise ValueError(
                f"cannot truncate sequence {seq_id} ({length} tokens) "
                f"to {n_tokens}"
            )
        table = self._tables[seq_id]
        keep = -(-n_tokens // self.page_size)
        released = table[keep:]
        del table[keep:]
        for p in released:
            self._release(p)
        self._lengths[seq_id] = n_tokens
        return released

    def length(self, seq_id: int) -> int:
        return self._lengths[seq_id]

    def covered_tokens(self, seq_id: int) -> int:
        """KV slots actually writable for this sequence — its page count
        times the page size (≥ ``length``; the page-rounded bound the
        scheduler's speculative write mask is built from)."""
        return len(self._tables[seq_id]) * self.page_size

    def table(self, seq_id: int) -> list[int]:
        return list(self._tables[seq_id])

    def free_sequence(self, seq_id: int) -> None:
        for p in self._tables.pop(seq_id):
            self._release(p)
        del self._lengths[seq_id]

    def check_invariants(self) -> None:
        """Raise RuntimeError on any bookkeeping violation: a page both
        free and referenced, a duplicate free-list entry, a table entry
        without a reference, a refcount below what the tables imply, or
        pages leaked/conjured. Cheap (O(pages)); the fuzz harness calls
        it after every operation."""
        free = self._free
        free_set = set(free)
        if len(free_set) != len(free):
            raise RuntimeError("free list contains duplicate pages")
        if free_set & self._refs.keys():
            raise RuntimeError(
                f"pages both free and referenced: "
                f"{sorted(free_set & self._refs.keys())}"
            )
        if len(free) + len(self._refs) != self.n_pages:
            raise RuntimeError(
                f"page conservation violated: {len(free)} free + "
                f"{len(self._refs)} referenced != {self.n_pages}"
            )
        table_refs: dict[int, int] = {}
        for seq_id, table in self._tables.items():
            if len(set(table)) != len(table):
                raise RuntimeError(f"sequence {seq_id} table has dup pages")
            for p in table:
                table_refs[p] = table_refs.get(p, 0) + 1
        for p, n in table_refs.items():
            if p in free_set:
                raise RuntimeError(f"free page {p} is in a live table")
            if self._refs.get(p, 0) < n:
                raise RuntimeError(
                    f"page {p}: {n} table refs exceed refcount "
                    f"{self._refs.get(p, 0)}"
                )
        for p, r in self._refs.items():
            if r < 1:
                raise RuntimeError(f"page {p} has nonpositive refcount {r}")
            # Leak check: a page's references are its table memberships
            # plus AT MOST ONE prefix-cache hold (one cache per pool;
            # PrefixCache._by_page is keyed by page, so it can never
            # double-ref). Anything beyond that is a leaked reference
            # that would keep the page out of the free list forever.
            if r > table_refs.get(p, 0) + 1:
                raise RuntimeError(
                    f"page {p}: refcount {r} exceeds "
                    f"{table_refs.get(p, 0)} table refs + 1 cache ref "
                    "(leaked reference)"
                )
        # Tier-swap pins: a pinned page must be live (referenced) — a
        # pin on a freed page means a promotion is scattering into
        # storage nobody owns — and pin counts must be positive.
        for p, n in self._swap_pins.items():
            if n < 1:
                raise RuntimeError(f"page {p} has nonpositive swap pin {n}")
            if p not in self._refs:
                raise RuntimeError(
                    f"page {p} swap-pinned but not referenced "
                    "(in-flight swap against a freed page)"
                )

    def table_array(self, seq_ids: list[int], max_pages: int) -> np.ndarray:
        """Batched page table [B, max_pages], -1-padded, for the kernel."""
        out = np.full((len(seq_ids), max_pages), -1, np.int32)
        for i, sid in enumerate(seq_ids):
            t = self._tables[sid]
            if len(t) > max_pages:
                raise ValueError(
                    f"sequence {sid} spans {len(t)} pages > {max_pages}"
                )
            out[i, : len(t)] = t
        return out


def init_page_pool(
    layout: PagedCacheLayout, dtype=None, kv_dtype: str = ""
) -> dict[str, "jnp.ndarray"]:
    """Device page pool: per-layer stacked K/V pages.

    ``kv_dtype="int8"``: pages store int8 K/V plus per-(token, head)
    f32 scale pages ("ks"/"vs", trailing dim 1) — the paged counterpart
    of the dense cache's int8 layout (models/transformer.py:init_cache).
    Presence of "ks" marks a quantized pool.
    """
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.bfloat16
    shape = (
        layout.n_layers,
        layout.n_pages,
        layout.n_kv_heads,
        layout.page_size,
        layout.head_dim,
    )
    vshape = shape[:-1] + (layout.v_dim or layout.head_dim,)
    if kv_dtype == "int8":
        if vshape != shape:
            raise NotImplementedError("a latent pool is stored in the model dtype")
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "ks": jnp.zeros(sshape, jnp.float32),
            "vs": jnp.zeros(sshape, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(vshape, dtype)}


def _row_index(layers, pool_array, page_ids, offsets):
    """Index tuple addressing pool[l, page_ids[b, s], h, offsets[b, s]]
    for every (l, b, h, s) — the dense cache's [L, B, Hkv, S] axes.
    ``layers`` is a scalar layer (the L axis drops) or an [L, 1, 1, 1]
    column of layers.

    Every leading pool axis is indexed, so a scatter or gather through
    it moves whole D-rows, contiguous in the pool's heads-major layout.
    Leaving the layer or head axis as a slice widens the window per
    token, and XLA:TPU then re-lays the WHOLE pool out around the
    operation: a pool-sized temporary a pool sized to the chip cannot
    pay (models/transformer.py:forward_paged_decode scatters the same
    way for the same reason).
    """
    import jax.numpy as jnp

    return (
        layers,
        jnp.asarray(page_ids)[:, None, :],
        jnp.arange(pool_array.shape[2])[None, :, None],
        jnp.asarray(offsets)[:, None, :],
    )


def write_tokens(
    pool: dict[str, jnp.ndarray],
    k_new: jnp.ndarray,  # [L, B, Hkv, S, D] — heads-major cache layout
    v_new: jnp.ndarray,
    page_ids: np.ndarray,  # [B, S] physical page per token
    offsets: np.ndarray,  # [B, S] slot within page per token
    ks_new: jnp.ndarray | None = None,  # [L, B, Hkv, S, 1] (int8 pools)
    vs_new: jnp.ndarray | None = None,
    layer=None,  # a (traced) layer: the arrays above are that layer's
    # alone, without the L axis
) -> dict[str, jnp.ndarray]:
    """Scatter freshly computed K/V into their pages (vectorized).

    The way a prompt's K/V reach the pool, whichever way it is admitted:
    a dense admission cache at its handoff and a promoted tier block go
    in whole; a span that runs over the pool (the scheduler's
    ``paged_admission``) writes a layer at a time as it goes (``layer``;
    traced into that program, where the pool is the program's own).

    The pool is DONATED and updated in place — callers rebind it
    (``pool = write_tokens(pool, ...)``). Dispatched eagerly, every
    ``.at[].set`` would instead allocate a second pool beside the first.

    Quantized pools take the matching scale slices (both or neither) —
    the same [L, B, Hkv, S, 1] layout the dense int8 cache stores.
    """
    new = {"k": k_new, "v": v_new}
    if "ks" in pool:
        if ks_new is None or vs_new is None:
            raise ValueError(
                "quantized pool requires ks_new/vs_new scale slices"
            )
        new.update(ks=ks_new, vs=vs_new)
    write, _ = _pool_jits()
    return write(pool, new, page_ids, offsets, layer)


def read_tokens(
    pool: dict[str, "jnp.ndarray"],
    page_ids: np.ndarray,  # [B, S] physical page per token
    offsets: np.ndarray,  # [B, S] slot within page per token
) -> dict[str, "jnp.ndarray"]:
    """Gather per-token K/V (and scales) back out of their pages.

    The exact inverse of ``write_tokens``: returns arrays in the
    heads-major dense-cache layout [L, B, Hkv, S, *]. Its callers
    (engine/scheduler.py): the demotion fetch of an evicted block on
    its way to the host tier, a page at a time, and the one admission
    that still copies a cached prefix into a dense cache: a hit whose
    remainder is longer than an ADMISSION_CHUNK, which prefills in
    chunks. A hit with a shorter remainder reads nothing out of the
    pool: it runs over the pages it adopted (``paged_admission``).
    """
    _, read = _pool_jits()
    return read(pool, page_ids, offsets)


@functools.cache
def _pool_jits():
    """(write, read) as jitted programs, built on first use: jax stays
    a lazy import here (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    def write(pool, new, page_ids, offsets, layer=None):
        # One scatter per layer: a single scatter over every (layer,
        # token, head) row compiles in time proportional to the token
        # count on XLA:TPU (25 s at 5k tokens against 0.2 s this way).
        def one_layer(l, pool):
            # the pool's pages alone: beside state-space layers it also
            # holds the rows' recurrent state, which no token position
            # addresses
            return {
                **pool,
                **{
                    name: pool[name]
                    .at[_row_index(l, pool[name], page_ids, offsets)]
                    .set(new[name][l] if layer is None else new[name])
                    for name in new
                },
            }

        if layer is not None:
            return one_layer(layer, pool)
        n_layers = pool["k"].shape[0]
        return jax.lax.fori_loop(0, n_layers, one_layer, pool)

    def read(pool, page_ids, offsets):
        return {
            name: x[
                _row_index(
                    jnp.arange(x.shape[0])[:, None, None, None],
                    x,
                    page_ids,
                    offsets,
                )
            ]
            for name, x in pool.items()
            if name in PAGE_LEAVES
        }

    return jax.jit(write, donate_argnames=("pool",)), jax.jit(read)


def token_positions_to_pages(
    allocator: PageAllocator, seq_ids: list[int], positions: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Map per-row token positions [B, S] → (page_ids, offsets) [B, S]."""
    B, S = positions.shape
    page_ids = np.zeros((B, S), np.int32)
    offsets = np.zeros((B, S), np.int32)
    for i, sid in enumerate(seq_ids):
        table = allocator.table(sid)
        for j in range(S):
            pos = int(positions[i, j])
            page_ids[i, j] = table[pos // allocator.page_size]
            offsets[i, j] = pos % allocator.page_size
    return page_ids, offsets
