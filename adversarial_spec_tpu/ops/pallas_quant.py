"""Pallas TPU kernels: fused dequant-matmul over int8 / packed-int4 weights.

Decode throughput is weight-bandwidth-bound: every generated token
re-reads every matmul weight (ops/quant.py's module docstring). The
quantized formats halve / quarter the bytes *stored*, and XLA usually
fuses the dequant multiply into the matmul's operand read — but "usually"
is a fusion-heuristic promise, not a contract: a materialized
full-precision dequant copy silently restores the bf16 byte count and
erases the entire point of the format. These kernels make the contract
explicit: the packed weight is the operand the kernel streams from HBM
(int8 bytes for ``{"q","scale"}``, nibble-packed bytes for
``{"q4","scale"}``), and the unpack + pure-shift dequant happens on the
VMEM-resident tile inside the kernel body. The weight travels HBM→VMEM
exactly once per matmul, at its packed width.

Kernel shape (both formats): grid (M/bm, N/bn, K/bk), K innermost so the
f32 accumulator tile persists in VMEM scratch across the contraction
(initialized at k==0, scaled + written at the last k block). The weight
is never padded or copied — block sizes are chosen to divide its true
dims (``_plan_blocks``); only the activation pads its row count (cheap:
activations are a few KB against MBs of weights).

int4 layout note: ``pack_int4`` interleaves rows (byte k holds row 2k in
its low nibble, 2k+1 in its high), so an in-kernel unpack to the dense
[K, N] layout would need a sublane interleave (stack + reshape) that
Mosaic lowers poorly. Instead the *activation* deinterleaves outside the
kernel — ``x_even = x[..., 0::2]``, ``x_odd = x[..., 1::2]`` — and the
kernel computes ``x_even @ lo + x_odd @ hi`` with ``lo``/``hi``
sign-extended from the packed byte by pure shifts. Same result, zero
reshapes on the weight path, and the packed operand streams as-is. An
odd contraction width pads one zero *activation* column, matching the
zero row ``pack_int4`` added.

Flag-gated like the attention kernels (``use_pallas_decode``): callers
pass ``use_pallas=True`` into ``ops.quant.matmul``, which dispatches
here when the weight leaf is quantized and the shape is supported
(``fused_supported``), and ``interpret=True`` runs the same kernels on
CPU for the tier-1 byte-parity pins (tests/test_pallas.py,
tests/test_quant.py). See docs/kernels.md for the full inventory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANE = 8
# Per-step VMEM working-set budget for the whole-K fast path (one x
# block + one weight block; Pallas double-buffers, scratch/out ride on
# top). Conservative against the ~16 MiB TensorCore VMEM.
_QMM_VMEM_BUDGET = 3 << 20


def _pick_tile(dim: int, candidates: tuple[int, ...]) -> int | None:
    """Largest candidate dividing ``dim`` exactly — the weight is never
    padded (padding would copy the packed operand, defeating the
    stream-once contract)."""
    for c in candidates:
        if dim % c == 0:
            return c
    return None


def _plan_blocks(
    M: int, K: int, N: int, x_itemsize: int, w_itemsize: int
) -> tuple[int, int, int] | None:
    """(bm, bk, bn) for an [M, K] @ [K, N] blocked matmul, or None when
    no block assignment divides the weight dims (caller falls back to
    the XLA path). ``K`` is the *stored* contraction width (packed rows
    for int4)."""
    bn = _pick_tile(N, (512, 256, 128))
    if bn is None:
        if N > 2048:
            return None
        bn = N
    bm = min(256, -(-M // _SUBLANE) * _SUBLANE)
    # Whole-K keeps one dot per (i, j) program — no partial-sum
    # reassociation vs the XLA path — whenever the working set fits.
    if bm * K * x_itemsize + K * bn * w_itemsize <= _QMM_VMEM_BUDGET:
        bk = K
    else:
        bk = _pick_tile(K, (2048, 1024, 512, 256, 128))
        if bk is None:
            if K > 8192:
                return None
            bk = K
    return bm, bk, bn


def _qmm_int8_kernel(
    x_ref,  # VMEM [bm, bk] activation block (f32/bf16)
    w_ref,  # VMEM [bk, bn] int8 weight block — streamed packed
    s_ref,  # VMEM [1, bn] f32 per-output-channel scales
    o_ref,  # VMEM [bm, bn]
    acc_ref,  # VMEM [bm, bn] f32 scratch, persists across the k grid dim
    *,
    compute_dtype,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Dequant is deferred: the int8 block upcasts in VMEM and the scale
    # multiplies the accumulator once at the end (scales are per output
    # channel, so they commute with the K sum).
    acc_ref[:] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...].astype(compute_dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = (acc_ref[:] * s_ref[...]).astype(o_ref.dtype)


def _qmm_int4_kernel(
    xe_ref,  # VMEM [bm, bk] even-position activation block
    xo_ref,  # VMEM [bm, bk] odd-position activation block
    p_ref,  # VMEM [bk, bn] packed int4 weight block — streamed packed
    s_ref,  # VMEM [1, bn] f32 scales
    o_ref,  # VMEM [bm, bn]
    acc_ref,  # VMEM [bm, bn] f32 scratch
    *,
    compute_dtype,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Pure-shift nibble dequant on the VMEM-resident tile: sign-extend
    # the low nibble (shift up, arithmetic shift back) and the high
    # nibble (arithmetic shift alone) — the same arithmetic as
    # ops.quant.unpack_int4, minus its row interleave (the activation
    # halves absorb it, see module docstring).
    p32 = p_ref[...].astype(jnp.int32)
    lo = ((p32 << 28) >> 28).astype(compute_dtype)
    hi = (p32 >> 4).astype(compute_dtype)
    acc_ref[:] += jax.lax.dot_general(
        xe_ref[...], lo, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        xo_ref[...], hi, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = (acc_ref[:] * s_ref[...]).astype(o_ref.dtype)


def _out_dtype(x: jnp.ndarray, preferred_element_type):
    return (
        preferred_element_type
        if preferred_element_type is not None
        else x.dtype
    )


def _pad_rows(x2: jnp.ndarray, bm: int) -> tuple[jnp.ndarray, int]:
    M = x2.shape[0]
    Mp = -(-M // bm) * bm
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    return x2, Mp


@functools.partial(
    jax.jit, static_argnames=("preferred_element_type", "interpret")
)
def matmul_int8(
    x: jnp.ndarray,  # [..., K] activations
    q: jnp.ndarray,  # [K, N] int8
    scale: jnp.ndarray,  # [1, N] f32
    preferred_element_type=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ (q * scale)`` with the int8 weight streamed packed and
    dequantized in-kernel. Returns [..., N]."""
    K, N = q.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm, bk, bn = _plan_blocks(M, K, N, x2.dtype.itemsize, 1)
    x2, Mp = _pad_rows(x2, bm)
    out = pl.pallas_call(
        functools.partial(_qmm_int8_kernel, compute_dtype=x.dtype),
        grid=(Mp // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(
            (Mp, N), _out_dtype(x, preferred_element_type)
        ),
        interpret=interpret,
        name="matmul_int8",
    )(x2, q, scale.reshape(1, N).astype(jnp.float32))
    return out[:M].reshape(lead + (N,))


@functools.partial(
    jax.jit, static_argnames=("preferred_element_type", "interpret")
)
def matmul_int4(
    x: jnp.ndarray,  # [..., K] activations (K = true contraction width)
    q4: jnp.ndarray,  # [ceil(K/2), N] int8 nibble-packed
    scale: jnp.ndarray,  # [1, N] f32
    preferred_element_type=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ dequant(q4)`` with the nibble-packed weight streamed as-is
    and unpacked in-kernel by pure shifts. Returns [..., N]."""
    K2, N = q4.shape
    K = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    if K != 2 * K2:
        # Odd true width: pack_int4 padded one zero row; the matching
        # zero activation column keeps the halves aligned.
        x2 = jnp.pad(x2, ((0, 0), (0, 2 * K2 - K)))
    xe = x2[:, 0::2]  # rows 2k of the unpacked weight
    xo = x2[:, 1::2]  # rows 2k+1
    M = x2.shape[0]
    bm, bk, bn = _plan_blocks(M, K2, N, 2 * x2.dtype.itemsize, 1)
    xe, Mp = _pad_rows(xe, bm)
    xo, _ = _pad_rows(xo, bm)
    half_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    out = pl.pallas_call(
        functools.partial(_qmm_int4_kernel, compute_dtype=x.dtype),
        grid=(Mp // bm, N // bn, K2 // bk),
        in_specs=[
            half_spec,
            half_spec,
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(
            (Mp, N), _out_dtype(x, preferred_element_type)
        ),
        interpret=interpret,
        name="matmul_int4",
    )(xe, xo, q4, scale.reshape(1, N).astype(jnp.float32))
    return out[:M].reshape(lead + (N,))


def _qmm_int8_grouped_kernel(
    tile_group_ref,  # SMEM [n_tiles]: the group whose rows tile i holds
    n_live_ref,  # SMEM [1]: tiles that hold rows at all
    layer_ref,  # SMEM [1]: which layer of the stack (index_maps only)
    x_ref,  # VMEM [bm, bk] rows of ONE group
    w_ref,  # VMEM [bk, bn] int8 block of that group's weight
    s_ref,  # VMEM [1, bn] f32 scales of the same
    o_ref,  # VMEM [bm, bn]
    acc_ref,  # VMEM [bm, bn] f32 scratch across the k grid dim
    *,
    compute_dtype,
):
    i = pl.program_id(0)
    k = pl.program_id(2)

    # Tiles past the last group hold no rows: their blocks were not
    # fetched anew (the index_maps stay on the last live block) and
    # nothing is computed or read back from them.
    @pl.when(i < n_live_ref[0])
    def _live():
        @pl.when(k == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot_general(
            x_ref[...],
            w_ref[...].astype(compute_dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(k == pl.num_programs(2) - 1)
        def _finalize():
            o_ref[:] = (acc_ref[:] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def matmul_int8_grouped(
    x: jnp.ndarray,  # [n_tiles * bm, K]: rows grouped, a group a whole number of tiles
    q: jnp.ndarray,  # [L, G, K, N] int8: the stacked weights of every group
    scale: jnp.ndarray,  # [L, G, 1, N] f32
    layer: jnp.ndarray,  # int32 scalar: the layer to read
    tile_group: jnp.ndarray,  # [n_tiles] int32: group of each row tile
    n_live: jnp.ndarray,  # int32 scalar: tiles that hold rows
    *,
    bm: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Row tile i of ``x`` times the weight of ITS group:
    ``x[i] @ (q[layer, tile_group[i]] * scale[layer, tile_group[i]])``.

    The stacked array is the operand: no layer and no group is sliced
    out of it beforehand. ``layer`` and ``tile_group`` are
    scalar-prefetched and the weight's index_map follows them, so a
    group that got no rows is never read, and the tiles after the last
    live one (``tile_group`` repeats the last live group there) neither
    fetch nor compute. Rows of tiles past ``n_live`` come back unwritten.
    Returns [n_tiles * bm, N] in ``x.dtype``."""
    L, G, K, N = q.shape
    M = x.shape[0]
    n_tiles = M // bm
    plan = _plan_blocks(bm, K, N, x.dtype.itemsize, 1)
    if plan is None or M % bm:
        raise ValueError(
            f"matmul_int8_grouped: no unpadded block assignment for "
            f"rows {M} (tile {bm}) x [{K}, {N}]"
        )
    _, bk, bn = plan
    nj, nk = N // bn, K // bk

    def hold(i, v, last, n_live_ref):
        # a dead tile stays on the last live tile's last block: no copy
        return jnp.where(i < n_live_ref[0], v, last)

    def w_map(i, j, k, tile_group_ref, n_live_ref, layer_ref):
        return (
            layer_ref[0],
            tile_group_ref[i],
            hold(i, k, nk - 1, n_live_ref),
            hold(i, j, nj - 1, n_live_ref),
        )

    def s_map(i, j, k, tile_group_ref, n_live_ref, layer_ref):
        return (
            layer_ref[0], tile_group_ref[i], 0, hold(i, j, nj - 1, n_live_ref)
        )

    def x_map(i, j, k, tile_group_ref, n_live_ref, layer_ref):
        live = jnp.maximum(n_live_ref[0] - 1, 0)
        return (jnp.minimum(i, live), hold(i, k, nk - 1, n_live_ref))

    return pl.pallas_call(
        functools.partial(_qmm_int8_grouped_kernel, compute_dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nj, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), x_map),
                pl.BlockSpec((None, None, bk, bn), w_map),
                pl.BlockSpec((None, None, 1, bn), s_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name="matmul_int8_grouped",
    )(
        tile_group.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        x,
        q,
        scale.astype(jnp.float32),
    )


def fused_supported(x, w) -> bool:
    """True iff the fused kernel covers this (activation, weight) pair:
    a flat quantized weight whose dims admit an unpadded block
    assignment. The caller (ops.quant.matmul) falls back to the XLA
    dequant-fusion path otherwise — same math, weaker streaming
    guarantee. A STACKED quantized weight is never turned away without a
    word: a stack is read by ``matmul_int8_grouped`` through a prefetched
    index (models/moe.py), and one that arrives here is a caller's
    mistake."""
    from adversarial_spec_tpu.ops.quant import is_quantized, is_quantized_int4

    if is_quantized(w):
        q = w["q"]
    elif is_quantized_int4(w):
        q = w["q4"]
    else:
        return False
    if q.ndim != 2:
        raise ValueError(
            f"fused dequant-matmul got a stacked weight {q.shape}: slice "
            "one matrix out, or read the stack with matmul_int8_grouped"
        )
    if x.ndim < 1 or x.size == 0:
        return False
    M = 1
    for d in x.shape[:-1]:
        M *= d
    return (
        _plan_blocks(M, q.shape[0], q.shape[1], x.dtype.itemsize, 1)
        is not None
    )


def quant_matmul(
    x: jnp.ndarray,
    w: dict,
    preferred_element_type=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Format dispatch for a quantized dict leaf (caller has already
    checked ``fused_supported``)."""
    from adversarial_spec_tpu.ops.quant import is_quantized_int4

    if is_quantized_int4(w):
        return matmul_int4(
            x,
            w["q4"],
            w["scale"],
            preferred_element_type=preferred_element_type,
            interpret=interpret,
        )
    return matmul_int8(
        x,
        w["q"],
        w["scale"],
        preferred_element_type=preferred_element_type,
        interpret=interpret,
    )
