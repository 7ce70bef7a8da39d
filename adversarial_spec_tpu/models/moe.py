"""Routed feed-forward layer: a router over ALL experts, the experts this
chip HOLDS, and the shared expert beside them.

The layer is told which experts it holds (``RoutedExperts.held`` =
(first, count): the chip's share of an expert-parallel deployment). It
routes every token over the whole published expert count, computes the
part of the result that its own experts give for the tokens routed to
them, and adds the shared expert, which every chip computes whole. What
the absent experts would add is left out: on one chip the layer runs
without its exchange, and nothing stands in for the other chips. Summed
over every share (the shared expert counted once) the parts are the
uncut layer (tests/test_moe.py, the share test).

No token is dropped and there is no capacity factor: the (token, expert)
pairs that land on held experts are grouped by expert (a one-hot
cumsum gives each pair its rank in its group), every group is padded to
whole row tiles, and one grouped matmul per projection multiplies each
tile by its own expert's weight, read out of the STACKED ``[L, E, in,
out]`` array through prefetched indices
(ops/pallas_quant.py:matmul_int8_grouped), so an expert that got no
token is never read and no layer is sliced out of the stack first. Off
the kernel (full precision, int4, a mesh, the CPU without interpret
mode) the same grouped rows meet the same tiles' weights in one einsum.
The tile height comes from the shapes: the mean group size rounded up to
a power of two between 8 and 128.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from adversarial_spec_tpu.models.config import RoutedExperts
from adversarial_spec_tpu.ops.quant import is_quantized, is_quantized_int4

# Per-layer stacked expert weights [L, E_held, in, out]: kept OUT of the
# layer scan's sliced operands (a slice would copy every held expert
# every step) and read by layer index instead.
EXPERT_WEIGHTS = ("we_gate", "we_up", "we_down")

def route(h2: jnp.ndarray, w_router, ex: RoutedExperts, bias=None):
    """Top-k of float32 scores over all experts. h2 [T, D] normed
    activations -> (weights [T, k] f32, expert ids [T, k] int32).

    "softmax": the top k of a softmax, renormalised. "sigmoid": the
    scores are sigmoids; the k are chosen by score + ``bias`` [n_routed]
    (an expert's learned correction of its load), but weighted by the
    scores alone, renormalised over sum + 1e-20."""
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(
            h2.astype(jnp.float32),
            w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if ex.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(
                scores + bias.astype(jnp.float32), ex.top_k
            )
            w = jnp.take_along_axis(scores, idx, axis=-1)
            if ex.norm_topk:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        else:
            gates = jax.nn.softmax(logits, axis=-1)
            w, idx = jax.lax.top_k(gates, ex.top_k)
            if ex.norm_topk:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w * ex.routed_scaling, idx.astype(jnp.int32)


def _tile_rows(n_pairs: int, n_held: int) -> int:
    mean = -(-n_pairs // n_held)
    bm = 8
    while bm < min(mean, 128):
        bm *= 2
    return bm


def group_pairs(idx: jnp.ndarray, ex: RoutedExperts, bm: int):
    """Place every (token, choice) pair that lands on a held expert in
    its expert's group of rows.

    Returns (dest [T, k]: the pair's row in the grouped layout, or the
    row count ``M`` for a pair on an absent expert; tile_group
    [n_tiles]; n_live scalar; counts [E_held] pairs per held expert; M)."""
    T, k = idx.shape
    E = ex.n_held
    local = idx.reshape(-1) - ex.first_held  # [P]
    held = (local >= 0) & (local < E)
    onehot = (local[:, None] == jnp.arange(E)[None, :]) & held[:, None]
    onehot = onehot.astype(jnp.int32)  # [P, E]
    counts = jnp.sum(onehot, axis=0)  # [E]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)  # [P]
    padded = -(-counts // bm) * bm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    # Worst case: every pair held, every group with a ragged last tile.
    n_tiles = -(-(T * k) // bm) + E
    M = n_tiles * bm
    dest = jnp.where(
        held, jnp.sum(starts[None, :] * onehot, axis=1) + rank, M
    )
    n_live = ends[-1] // bm
    tile_row = jnp.arange(n_tiles) * bm
    tile_group = jnp.sum(
        (tile_row[:, None] >= ends[None, :]).astype(jnp.int32), axis=1
    )
    # tiles past the last live one repeat its group: nothing new to fetch
    last = jnp.take(tile_group, jnp.maximum(n_live - 1, 0))
    tile_group = jnp.where(jnp.arange(n_tiles) < n_live, tile_group, last)
    tile_group = jnp.minimum(tile_group, E - 1)
    return dest.reshape(T, k), tile_group, n_live, counts, M


def grouped_matmul(
    x, w, layer, tile_group, n_live, bm: int, *, use_pallas: bool,
    interpret: bool,
):
    """Rows ``x`` [n_tiles * bm, in], tile i times expert
    ``tile_group[i]``'s matrix of layer ``layer`` of the stack ``w``
    ([L, E, in, out], plain or a quantized pair)."""
    with jax.named_scope("qmm"):
        if use_pallas and is_quantized(w):
            from adversarial_spec_tpu.ops.pallas_quant import (
                matmul_int8_grouped,
            )

            return matmul_int8_grouped(
                x, w["q"], w["scale"], layer, tile_group, n_live,
                bm=bm, interpret=interpret,
            )
        if is_quantized_int4(w):
            raise NotImplementedError(
                "expert stacks are int8 or full precision; int4 has no "
                "grouped path"
            )
        xt = x.reshape(-1, bm, x.shape[-1])
        if is_quantized(w):
            wt = w["q"][layer, tile_group].astype(x.dtype)
            y = jnp.einsum(
                "tmk,tkn->tmn", xt, wt, preferred_element_type=jnp.float32
            ) * w["scale"][layer, tile_group]
        else:
            y = jnp.einsum(
                "tmk,tkn->tmn", xt, w[layer, tile_group],
                preferred_element_type=jnp.float32,
            )
        return y.astype(x.dtype).reshape(x.shape[0], -1)


def routing_stats(idx, ex: RoutedExperts, mask=None) -> jnp.ndarray:
    """int32 [3] over the positions ``mask`` [T] keeps (all if None):
    pairs on held experts, held experts with at least one pair, the
    busiest held expert's pairs."""
    local = idx - ex.first_held  # [T, k]
    held = (local >= 0) & (local < ex.n_held)
    if mask is not None:
        held = held & mask[:, None]
    per = jnp.sum(
        (
            (local[..., None] == jnp.arange(ex.n_held)) & held[..., None]
        ).astype(jnp.int32),
        axis=(0, 1),
    )
    return jnp.stack(
        [jnp.sum(per), jnp.sum((per > 0).astype(jnp.int32)), jnp.max(per)]
    )


def routed_ffn(
    h: jnp.ndarray,  # [B, S, D] normed activations
    w_router,  # [D, n_routed] this layer's router
    experts: dict,  # the WHOLE stacks {we_gate, we_up, we_down}: [L, E, ..]
    layer,  # int32 scalar
    ex: RoutedExperts,
    activation,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
    router_bias=None,  # [n_routed]: a sigmoid router's selection bias
):
    """The held experts' part of the routed result, [B, S, D], and the
    routing ([B*S, k] expert ids) for the caller's counters."""
    B, S, D = h.shape
    h2 = h.reshape(B * S, D)
    w, idx = route(h2, w_router, ex, router_bias)
    with jax.named_scope("moe.experts"):
        T, k = idx.shape
        bm = _tile_rows(T * k, ex.n_held)
        dest, tile_group, n_live, _counts, M = group_pairs(idx, ex, bm)
        # row -> the token it holds (rows no pair landed on hold token 0,
        # times nothing: their products are never gathered back)
        token = jnp.repeat(jnp.arange(T), k)
        src = (
            jnp.zeros((M,), jnp.int32)
            .at[dest.reshape(-1)]
            .set(token, mode="drop")
        )
        x = h2[src]
        mm = lambda a, name: grouped_matmul(  # noqa: E731
            a, experts[name], layer, tile_group, n_live, bm,
            use_pallas=use_pallas, interpret=interpret,
        )
        y = mm(activation(mm(x, "we_gate")) * mm(x, "we_up"), "we_down")
        held = dest < M
        picked = y[jnp.minimum(dest, M - 1)]  # [T, k, D]
        out = jnp.sum(
            jnp.where(
                held[..., None], picked.astype(jnp.float32) * w[..., None], 0.0
            ),
            axis=1,
        )
    return out.astype(h.dtype).reshape(B, S, D), idx
