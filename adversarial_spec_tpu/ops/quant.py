"""Weight-only quantization: int8 and packed int4.

Decode throughput on TPU is HBM-bandwidth-bound: every generated token
re-reads all matmul weights. Storing those weights int8 (per-output-channel
symmetric scales) halves the bytes read per token vs bf16 — the dequant
multiply fuses into the matmul's operand read under XLA, so the MXU still
computes in bf16/f32. int4 halves it again (two weights per byte, packed
along the contraction axis) — the format that makes a multi-model
opponent POOL resident on one chip (engine/weightres.py): four int4
checkpoints weigh what one bf16 checkpoint does.

Representation: a quantized matmul weight is a dict leaf — int8
``{"q": int8 [..., in, out], "scale": f32 [..., 1, out]}``, int4
``{"q4": int8 [..., ceil(in/2), out], "scale": f32 [..., 1, out]}``
(each ``q4`` byte packs rows ``2k`` in its low nibble and ``2k+1`` in
its high nibble; an odd contraction axis pads one zero row, sliced back
off at dequant against the activation's true width). Dicts (not custom
pytree nodes) so the sharding rules, loaders, and tree utilities need
no new node types; the transformer's ``matmul`` helper dispatches on
the key set. The unpack is pure shift arithmetic
(sign-extend-low-nibble / arithmetic-shift-high-nibble), so it traces
into the jitted forwards and XLA fuses the dequant into the operand
read — the in-kernel dequant the parity tests pin against dense fp.

Only matmul weights quantize (wq/wk/wv/wo/w_gate/w_up/w_down, lm_head,
and the tied-embedding transposed head copy lm_head_t); embeddings and
norms stay full precision (gather tables and scale vectors are
bandwidth-trivial and precision-sensitive).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

QUANTIZABLE = frozenset(
    {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
        "lm_head_t",
        # latent attention's projections and the routed experts' stacks
        # ([L, E, in, out]: scales per layer, expert and output channel);
        # the router stays full precision (its top-k is rounding-sensitive)
        "wq_a", "wq_b", "wkv_a", "wkv_b", "we_gate", "we_up", "we_down",
        # gated attention's output gate (models/config.py GatedAttention)
        "wg",
    }
)

# The registry's ``quant`` vocabulary lives jax-free in
# engine/registry.py (QUANT_FORMATS); this module implements the
# non-empty formats.


def div_const(x: jnp.ndarray, c: float) -> jnp.ndarray:
    """``x / c`` that stays a division when traced into a jitted
    program. XLA rewrites divide-by-constant into a multiply by the
    reciprocal, one ulp off the eager result; the barrier hides the
    constant, so weights built one per program (engine/loader.py) are
    bit-identical to the eagerly built tree the tests pin tokens on."""
    return x / jax.lax.optimization_barrier(jnp.float32(c))


def quantize_int8(w: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Symmetric per-output-channel int8 over the contraction (-2) axis."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = div_const(jnp.maximum(amax, 1e-8), 127.0)
    q = jnp.clip(
        jnp.round(w.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int8 values in [-8, 7] two-per-byte along the contraction
    (-2) axis: row ``2k`` in the low nibble, ``2k+1`` in the high. An
    odd row count pads one zero row (``unpack_int4`` slices it back off
    against the caller's true width)."""
    rows = q.shape[-2]
    if rows % 2:
        pad = [(0, 0)] * q.ndim
        pad[-2] = (0, 1)
        q = jnp.pad(q, pad)
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    # Two's-complement nibble packing: the low nibble keeps lo's bits,
    # hi shifts into the high nibble ([-8, 7] << 4 stays within int8).
    return (lo & jnp.int8(0x0F)) | jnp.left_shift(hi, 4).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: int8 values back out of the
    nibbles (``rows`` = the true contraction width; a padded zero row
    is sliced off). Pure shift arithmetic — traces into jitted
    forwards, so the dequant fuses into the matmul's operand read."""
    # Sign-extend the low nibble (shift up, arithmetic shift back);
    # the high nibble sign-extends by arithmetic right shift alone.
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    q = jnp.stack([lo, hi], axis=-2)  # [..., R/2, 2, out]
    q = q.reshape(q.shape[:-3] + (q.shape[-3] * 2, q.shape[-1]))
    return q[..., :rows, :]


def quantize_int4(w: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Symmetric per-output-channel packed int4 over the contraction
    (-2) axis (range [-7, 7]: symmetric, so dequant is one multiply)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = div_const(jnp.maximum(amax, 1e-8), 7.0)
    q = jnp.clip(
        jnp.round(w.astype(jnp.float32) / scale), -7, 7
    ).astype(jnp.int8)
    return {"q4": pack_int4(q), "scale": scale.astype(jnp.float32)}


class StackedLayer(NamedTuple):
    """Layer ``layer`` of a layer-stacked quantized weight, NOT sliced
    out of it: ``stack`` is the whole dict leaf (``q``/``q4`` [L, in,
    out], ``scale`` [L, 1, out]) and ``layer`` a traced int32 scalar.
    :func:`matmul` takes it where it takes a weight: the fused kernels
    read the layer in place through a prefetched index
    (ops/pallas_quant.py), which a slice made beforehand, as a layer
    scan makes of its operands, would turn into a copy of the layer
    every step."""

    stack: dict
    layer: jnp.ndarray


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"q", "scale"}


def is_quantized_int4(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"q4", "scale"}


def dequantize(leaf, dtype=jnp.float32, rows: int | None = None) -> jnp.ndarray:
    """Materialize a quantized dict leaf back to a dense array (tests,
    oracles — the serving path never calls this; its dequant fuses
    inside :func:`matmul`).

    ``rows`` is the true contraction width for int4 leaves (the packed
    form cannot record it: an odd width padded one zero row at pack
    time). Without it an odd-width int4 leaf dequantizes to the padded
    shape — pass the original weight's ``shape[-2]`` to slice exactly.
    """
    if is_quantized(leaf):
        return leaf["q"].astype(dtype) * leaf["scale"].astype(dtype)
    if is_quantized_int4(leaf):
        if rows is None:
            rows = leaf["q4"].shape[-2] * 2
        scale = leaf["scale"].astype(dtype)
        return unpack_int4(leaf["q4"], rows).astype(dtype) * scale
    return jnp.asarray(leaf, dtype)


def matmul(
    x: jnp.ndarray,
    w,
    preferred_element_type=None,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """x @ w for plain, int8-, or int4-quantized weights.

    Default path: XLA's dequant fusion — the unpack/scale multiply is
    elementwise on the matmul operand, so XLA *usually* folds it into
    the operand read. ``use_pallas=True`` routes supported quantized
    shapes through the fused Pallas kernels (ops/pallas_quant.py), which
    make the stream-packed-once contract explicit instead of relying on
    the fusion heuristic; unsupported shapes (dims with no unpadded
    block assignment) silently keep the XLA path — same math either way
    (docs/kernels.md pins the parity). ``w`` may be a
    :class:`StackedLayer`: the kernels read that layer of the stack in
    place; off them the layer is sliced out here, for XLA's dot.
    ``interpret=True`` runs those kernels in Pallas interpret mode (the
    CPU-parity harness; flag-gated exactly like ``use_pallas_decode``).
    """
    layer = None
    if isinstance(w, StackedLayer):
        w, layer = w
    # One device-side name for the (dequant-)matmul wherever a layer
    # calls it: ``.../attn/qmm``, ``.../mlp/qmm``, ``head/qmm``.
    with jax.named_scope("qmm"):
        if use_pallas and (is_quantized(w) or is_quantized_int4(w)):
            from adversarial_spec_tpu.ops import pallas_quant

            if pallas_quant.fused_supported(x, w, layer):
                return pallas_quant.quant_matmul(
                    x,
                    w,
                    layer,
                    preferred_element_type=preferred_element_type,
                    interpret=interpret,
                )
        if layer is not None:
            w = jax.tree.map(lambda a: a[layer], w)
        if is_quantized_int4(w):
            q = unpack_int4(w["q4"], x.shape[-1])
            y = jnp.matmul(
                x,
                q.astype(x.dtype),
                preferred_element_type=preferred_element_type,
            )
            scale = w["scale"][..., 0, :]
            return y * (
                scale
                if preferred_element_type is not None
                else scale.astype(x.dtype)
            )
        if is_quantized(w):
            y = jnp.matmul(
                x,
                w["q"].astype(x.dtype),
                preferred_element_type=preferred_element_type,
            )
            scale = w["scale"][..., 0, :]
            return y * (
                scale
                if preferred_element_type is not None
                else scale.astype(x.dtype)
            )
        return jnp.matmul(
            x, w, preferred_element_type=preferred_element_type
        )


def has_quantized_weights(params) -> bool:
    """True iff any leaf of the param pytree is a quantized dict —
    the auto-enable predicate for the fused Pallas matmul path (a
    full-precision checkpoint has nothing to dequantize)."""
    leaves = jax.tree.leaves(
        params,
        is_leaf=lambda n: is_quantized(n) or is_quantized_int4(n),
    )
    return any(
        is_quantized(leaf) or is_quantized_int4(leaf) for leaf in leaves
    )


def quantize_params(params: dict, names=QUANTIZABLE, fmt: str = "int8") -> dict:
    """Quantize matmul weights in a (possibly nested) param pytree.

    ``fmt`` selects the storage format (``"int8"`` or ``"int4"``).
    Works on the layer-stacked layout: per-layer scales fall out of the
    keepdims amax over the contraction axis.
    """
    if fmt not in ("int8", "int4"):
        raise ValueError(
            f"unknown weight quantization format {fmt!r}; known: int8, int4"
        )
    one = quantize_int8 if fmt == "int8" else quantize_int4

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if (
                k in names
                and not is_quantized(v)
                and not is_quantized_int4(v)
            ):
                out[k] = one(v)
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    return walk(params)
