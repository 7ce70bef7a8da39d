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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANE = 8
_LANE = 128
# Per-step VMEM working-set budget for the whole-K fast path (one x
# block + one weight block; Pallas double-buffers, scratch/out ride on
# top). Conservative against the ~16 MiB TensorCore VMEM.
_QMM_VMEM_BUDGET = 3 << 20
# A call of at most this many rows reads each weight byte for a few
# flops, far below the chip's ~240 flops a byte: the weight's stream
# bounds it, and the stream runs near the HBM rate only in large blocks
# (each grid step costs a fixed time besides its bytes). Such a call
# widens its output block up to ``_QMM_BLOCK_BYTES`` of weight, the size
# at which the whole-K plans already stream at ~90%, within
# ``_QMM_VMEM_LIMIT`` of VMEM in all (Mosaic's default scope is 16 MiB).
_QMM_WIDE_ROWS = 128
_QMM_BLOCK_BYTES = 2 << 20
_QMM_VMEM_LIMIT = 12 << 20
# A widened plan keeps at least this many weight blocks a matrix: over
# fewer, the pipeline's first fetch and last dot weigh more than the
# steps saved (on a TPU v5e a 4-8 MB matrix ran up to 6% slower in 2-4
# blocks of 2 MiB than in 8 of 0.5-1 MiB).
_QMM_MIN_BLOCKS = 8
# The int4 body shifts its packed block as int32 (``_qmm_int4_kernel``).
_INT4_UNPACK_ITEMSIZE = 4


def _pick_tile(dim: int, candidates: tuple[int, ...]) -> int | None:
    """Largest candidate dividing ``dim`` exactly — the weight is never
    padded (padding would copy the packed operand, defeating the
    stream-once contract)."""
    for c in candidates:
        if dim % c == 0:
            return c
    return None


def _vmem_bytes(
    bm: int, bk: int, bn: int, x_itemsize: int, w_itemsize: int,
    unpack_itemsize: int,
) -> int:
    """VMEM a plan takes: two buffers each of the activation, weight and
    scale blocks (a scale row pads to 8 sublanes), the weight block as
    the kernel body unpacks it, the f32 accumulator, and two output
    buffers (f32, the widest output)."""
    return (
        2 * bm * bk * x_itemsize
        + 2 * bk * bn * w_itemsize
        + 2 * _SUBLANE * bn * 4
        + bk * bn * unpack_itemsize
        + bm * bn * 4
        + 2 * bm * bn * 4
    )


def _plan_blocks(
    M: int, K: int, N: int, x_itemsize: int, w_itemsize: int,
    unpack_itemsize: int = 0,
) -> tuple[int, int, int] | None:
    """(bm, bk, bn) for an [M, K] @ [K, N] blocked matmul, or None when
    no block assignment divides the weight dims (caller falls back to
    the XLA path). ``K`` is the *stored* contraction width (packed rows
    for int4); ``unpack_itemsize`` the bytes a stored weight element
    takes while the kernel body unpacks it (int4's int32 shifts; 0 for
    int8, whose upcast Mosaic does not hold as a block of its own). A
    pure function of the shapes: the same call always gets the same
    plan."""
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
    if bm <= _QMM_WIDE_ROWS:
        # Widen the output block of a weight-bound call to the widest
        # lane multiple dividing N within the block, block-count and
        # VMEM limits. bm, bk and the K order stay: every output column
        # is summed exactly as before, so the result is bit for bit the
        # same.
        top = min(N, _QMM_BLOCK_BYTES // (bk * w_itemsize)) // _LANE * _LANE
        for wide in range(top, bn, -_LANE):
            if (
                N % wide == 0
                and (K // bk) * (N // wide) >= _QMM_MIN_BLOCKS
                and _vmem_bytes(
                    bm, bk, wide, x_itemsize, w_itemsize, unpack_itemsize
                ) <= _QMM_VMEM_LIMIT
            ):
                return bm, bk, wide
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


def _pad_rows(x2: jnp.ndarray, bm: int) -> jnp.ndarray:
    M = x2.shape[0]
    Mp = -(-M // bm) * bm
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    return x2


def _blocked(kernel, xs, w, scale, layer, plan, out_dtype):
    """(kernel, ``pallas_call`` keywords, operands) of one grid
    (M/bm, N/bn, K/bk) over the row-padded activation halves ``xs``
    ([Mp, K] each) and a weight that is flat ``[K, N]`` (``layer`` None)
    or a layer stack ``[L, K, N]`` read at the scalar-prefetched
    ``layer``: the weight's and the scale's index_maps follow it, so no
    layer is sliced out of the stack beforehand. Same blocks in the same
    order either way."""
    bm, bk, bn = plan
    Mp = xs[0].shape[0]
    K, N = w.shape[-2:]
    stacked = layer is not None
    lead = (None,) if stacked else ()

    def at(refs):  # the leading block index of the weight and its scale
        return (refs[0][0],) if stacked else ()

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(stacked),
        grid=(Mp // bm, N // bn, K // bk),
        in_specs=[x_spec] * len(xs)
        + [
            pl.BlockSpec(lead + (bk, bn), lambda i, j, k, *r: at(r) + (k, j)),
            pl.BlockSpec(lead + (1, bn), lambda i, j, k, *r: at(r) + (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    operands = [jnp.asarray(layer, jnp.int32).reshape(1)] if stacked else []
    operands += [
        *xs, w, scale.reshape(w.shape[:-2] + (1, N)).astype(jnp.float32)
    ]
    return (
        (lambda _layer_ref, *refs: kernel(*refs)) if stacked else kernel,
        dict(
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        ),
        operands,
    )


def _check_stack(q, layer) -> None:
    """A flat ``[K, N]`` weight, or a ``[L, K, N]`` layer stack WITH the
    layer to read. Anything else is a caller's mistake and is refused
    aloud, never handed on without a word."""
    if q.ndim != (2 if layer is None else 3):
        raise ValueError(
            f"fused dequant-matmul got a weight {q.shape} with "
            f"layer={layer!r}: a stacked weight [L, K, N] needs the layer "
            "to read (or slice one matrix out), a flat one takes none, and "
            "an expert stack [L, E, K, N] is matmul_int8_grouped's"
        )


@functools.partial(
    jax.jit, static_argnames=("preferred_element_type", "interpret")
)
def matmul_int8(
    x: jnp.ndarray,  # [..., K] activations
    q: jnp.ndarray,  # [K, N] int8, or the layer stack [L, K, N]
    scale: jnp.ndarray,  # [1, N] f32, or [L, 1, N]
    layer=None,  # int32 scalar (traced): the layer of the stack to read
    preferred_element_type=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ (q * scale)`` with the int8 weight streamed packed and
    dequantized in-kernel. Returns [..., N]. With ``layer``, ``q`` and
    ``scale`` are whole layer stacks and the kernel reads layer
    ``layer`` of them in place: bit for bit what the flat call gives on
    ``q[layer]``, ``scale[layer]``, without that slice's copy."""
    _check_stack(q, layer)
    K, N = q.shape[-2:]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    plan = _plan_blocks(M, K, N, x2.dtype.itemsize, 1)
    x2 = _pad_rows(x2, plan[0])
    kernel, kw, operands = _blocked(
        functools.partial(_qmm_int8_kernel, compute_dtype=x.dtype),
        [x2], q, scale, layer, plan, _out_dtype(x, preferred_element_type),
    )
    out = pl.pallas_call(
        kernel, interpret=interpret, name="matmul_int8", **kw
    )(*operands)
    return out[:M].reshape(lead + (N,))


@functools.partial(
    jax.jit, static_argnames=("preferred_element_type", "interpret")
)
def matmul_int4(
    x: jnp.ndarray,  # [..., K] activations (K = true contraction width)
    q4: jnp.ndarray,  # [ceil(K/2), N] int8 nibble-packed, or [L, .., N]
    scale: jnp.ndarray,  # [1, N] f32, or [L, 1, N]
    layer=None,  # int32 scalar (traced): the layer of the stack to read
    preferred_element_type=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ dequant(q4)`` with the nibble-packed weight streamed as-is
    and unpacked in-kernel by pure shifts. Returns [..., N]. ``layer``:
    as ``matmul_int8``."""
    _check_stack(q4, layer)
    K2, N = q4.shape[-2:]
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
    plan = _plan_blocks(
        M, K2, N, 2 * x2.dtype.itemsize, 1, _INT4_UNPACK_ITEMSIZE
    )
    xe = _pad_rows(xe, plan[0])
    xo = _pad_rows(xo, plan[0])
    kernel, kw, operands = _blocked(
        functools.partial(_qmm_int4_kernel, compute_dtype=x.dtype),
        [xe, xo], q4, scale, layer, plan, _out_dtype(x, preferred_element_type),
    )
    out = pl.pallas_call(
        kernel, interpret=interpret, name="matmul_int4", **kw
    )(*operands)
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


def _leaf_plan(x, w) -> tuple[int, int, int] | None:
    """The plan ``quant_matmul`` takes for activations ``x`` (a shape
    and dtype suffice) against the quantized leaf ``w``, flat or a
    layer stack, or None where none divides the weight."""
    from adversarial_spec_tpu.ops.quant import is_quantized

    if is_quantized(w):
        q, halves, unpack = w["q"], 1, 0
    else:  # two activation halves against the packed rows
        q, halves, unpack = w["q4"], 2, _INT4_UNPACK_ITEMSIZE
    K, N = q.shape[-2:]
    return _plan_blocks(
        math.prod(x.shape[:-1]), K, N, halves * x.dtype.itemsize, 1, unpack
    )


def fused_supported(x, w, layer=None) -> bool:
    """True iff the fused kernel covers this (activation, weight) pair:
    a quantized weight, flat or a ``[L, K, N]`` layer stack given WITH
    the ``layer`` to read, whose dims admit an unpadded block
    assignment. ``x`` may be a ``jax.ShapeDtypeStruct``. The caller
    (ops.quant.matmul) falls back to the XLA dequant-fusion path
    otherwise — same math, weaker streaming guarantee. A stack WITHOUT a
    layer index is never turned away without a word: nothing can read
    it whole, and one that arrives here is a caller's mistake (the
    routed experts' ``[L, E, K, N]`` stacks are
    ``matmul_int8_grouped``'s operand, models/moe.py)."""
    from adversarial_spec_tpu.ops.quant import is_quantized, is_quantized_int4

    if is_quantized(w):
        q = w["q"]
    elif is_quantized_int4(w):
        q = w["q4"]
    else:
        return False
    _check_stack(q, layer)
    if len(x.shape) < 1 or math.prod(x.shape) == 0:
        return False
    return _leaf_plan(x, w) is not None


def weight_block_bytes(x, w) -> int:
    """Bytes of the packed weight block the fused kernel streams a grid
    step for ``x`` against the quantized leaf ``w`` that
    ``fused_supported`` covers."""
    _, bk, bn = _leaf_plan(x, w)
    return bk * bn


def quant_matmul(
    x: jnp.ndarray,
    w: dict,
    layer=None,
    preferred_element_type=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Format dispatch for a quantized dict leaf, flat or a layer stack
    with its ``layer`` (caller has already checked ``fused_supported``)."""
    from adversarial_spec_tpu.ops.quant import is_quantized_int4

    fn, q = (
        (matmul_int4, w["q4"]) if is_quantized_int4(w) else (matmul_int8, w["q"])
    )
    return fn(
        x,
        q,
        w["scale"],
        layer,
        preferred_element_type=preferred_element_type,
        interpret=interpret,
    )
