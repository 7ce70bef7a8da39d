"""The plain reference: Mistral / Qwen2 (a Llama-style decoder with grouped
queries, an optional bias on Q, K and V, an optional sliding window, an
untied head) in straightforward jax.numpy, float32 at `highest` matmul
precision, one layer at a time and one sequence at a time. No kernels, no
cache, no batching, and nothing imported from the program.

The weights are data, made here from the seed by the recipe the synthetic
checkpoint states (configs/*.json "assumed"): each matmul weight a
truncated normal in [-2, 2] over sqrt(fan_in), rounded to bfloat16, then
int8 per output channel (scale = max|w| / 127 over the contraction axis);
norms one, biases zero, the embedding bfloat16. The recipe's keys are the
sixteen splits of `jax.random.key(seed)`, taken in the order wq, wk, wv,
wo, w_gate, w_up, w_down, embed, lm_head. The reference then computes with
the dequantized values in float32: what the configuration states, with no
rounding of activations, scales or cached keys and values.

`bits=4` makes the same weights in the nearest precision below (int4 per
output channel, range [-7, 7]): the control of the comparison.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 1024  # sequence lengths are padded up to a multiple: few programs


def _div(x, c: float):
    # The recipe divides; a compiler that turns the division into a
    # multiplication by the reciprocal is one ulp off.
    return x / jax.lax.optimization_barrier(jnp.float32(c))


@partial(jax.jit, static_argnames=("shape", "fan_in", "bits"))
def _make_weight(key, shape: tuple, fan_in: int, bits: int):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    w = _div(w, math.sqrt(fan_in)).astype(jnp.bfloat16).astype(jnp.float32)
    if bits == 16:
        return w.astype(jnp.bfloat16), jnp.ones(shape[:-2] + (1, shape[-1]), jnp.float32)
    qmax = {8: 127.0, 4: 7.0}[bits]
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = _div(jnp.maximum(amax, 1e-8), qmax)
    q = jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8)
    return q, scale


def make_weights(cfg: dict, seed: int, bits: int = 8) -> dict:
    """The whole model's weights on the device: int8 values and float32
    scales per matmul, stacked over layers; the embedding in bfloat16."""
    D = int(cfg["hidden_size"])
    L = int(cfg["num_hidden_layers"])
    F = int(cfg["intermediate_size"])
    V = int(cfg["vocab_size"])
    hd = int(cfg.get("head_dim") or D // int(cfg["num_attention_heads"]))
    QD = int(cfg["num_attention_heads"]) * hd
    KD = int(cfg["num_key_value_heads"]) * hd
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    w = {}
    for name, shape, fan_in in (
        ("wq", (L, D, QD), D),
        ("wk", (L, D, KD), D),
        ("wv", (L, D, KD), D),
        ("wo", (L, QD, D), QD),
        ("w_gate", (L, D, F), D),
        ("w_up", (L, D, F), D),
        ("w_down", (L, F, D), F),
    ):
        w[name] = _make_weight(next(keys), shape, fan_in, bits)
    w["embed"] = _make_weight(next(keys), (V, D), D, 16)[0]
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("the reference has an untied head only")
    w["lm_head"] = _make_weight(next(keys), (D, V), D, bits)
    return w


def _rms(x, eps: float):
    # the synthetic checkpoint's norm weights are all one
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta: float):
    """Half-rotation (the layout of the published checkpoints): x is
    [T, heads, head_dim], positions 0 .. T-1."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(x, w):
    """x @ (q * scale) in float32. The int8 values are exact in bfloat16,
    so x is split into three bfloat16 terms (x = x1 + x2 + x3 to float32's
    24 bits) and each term's products with q are exact: what `highest`
    precision does for two float32 operands in six passes takes three here,
    at the same accuracy. The scale multiplies the float32 sum."""
    q, scale = w
    qb = q.astype(jnp.bfloat16)
    acc = None
    rest = x
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        term = jnp.matmul(part, qb, preferred_element_type=jnp.float32)
        acc = term if acc is None else acc + term
    return acc * scale[..., 0, :]


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "theta", "window"))
def _layer(x, layer, stacked, *, n_heads, n_kv, eps, theta, window):
    """Decoder layer `layer` of the stacked weights over one sequence
    x [T, D], causal."""
    T, D = x.shape
    w = {name: (q[layer], scale[layer]) for name, (q, scale) in stacked.items()}
    h = _rms(x, eps)
    q = _mm(h, w["wq"])  # the checkpoint's Q, K, V biases are zero
    k = _mm(h, w["wk"])
    v = _mm(h, w["wv"])
    hd = q.shape[-1] // n_heads
    q = _rope(q.reshape(T, n_heads, hd), theta)
    k = _rope(k.reshape(T, n_kv, hd), theta)
    v = v.reshape(T, n_kv, hd)
    g = n_heads // n_kv
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    if window > 0:
        mask = mask & (pos[None, :] > pos[:, None] - window)

    def one_group(args):
        qg, kg, vg = args  # [T, g, hd], [T, hd], [T, hd]
        s = jnp.einsum("tgd,sd->gts", qg, kg) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vg)

    qg = q.reshape(T, n_kv, g, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = out.transpose(1, 0, 2, 3).reshape(T, n_heads * hd)
    x = x + _mm(attn, w["wo"])
    h = _rms(x, eps)
    ff = jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"])
    return x + _mm(ff, w["w_down"])


@jax.jit
def _embed_rows(table, tokens):
    return table[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("n",))
def _tail(x, start, n):
    return jax.lax.dynamic_slice_in_dim(x, start, n, 0)


@partial(jax.jit, static_argnames=("eps", "blocks"))
def _head(x, lm_head, *, eps, blocks):
    """Logits [n, V] of the rows x [n, D], the head taken in column blocks."""
    q, scale = lm_head
    h = _rms(x, eps)
    V = q.shape[-1]
    step = -(-V // blocks)
    outs = []
    for b in range(blocks):
        sl = slice(b * step, min(V, (b + 1) * step))
        outs.append(_mm(h, (q[:, sl], scale[:, sl])))
    return jnp.concatenate(outs, axis=-1)


def logits_for(cfg: dict, weights: dict, ids: list[int], first: int) -> np.ndarray:
    """Float32 logits at positions first .. len(ids)-1 of the sequence
    `ids` (the logits at position i are the model's choice of token i+1)."""
    n_heads = int(cfg["num_attention_heads"])
    n_kv = int(cfg["num_key_value_heads"])
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    window = int(cfg.get("sliding_window") or 0)
    if cfg.get("use_sliding_window") is False:
        window = 0
    T = len(ids)
    T_pad = -(-T // PAD_TO) * PAD_TO
    tokens = jnp.asarray(list(ids) + [0] * (T_pad - T), jnp.int32)
    stacked = {
        name: weights[name]
        for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    }
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(weights["embed"], tokens)
        for layer in range(weights["wq"][0].shape[0]):
            x = _layer(
                x, jnp.int32(layer), stacked, n_heads=n_heads, n_kv=n_kv, eps=eps,
                theta=theta, window=window,
            )
        # the tail padded to a fixed number of rows: one program for the head
        n = T - first
        n_pad = -(-n // 8) * 8
        start = min(first, T_pad - n_pad)
        rows = _tail(x, jnp.int32(start), n_pad)
        logits = _head(rows, weights["lm_head"], eps=eps, blocks=8)
    off = first - start
    return np.asarray(logits, np.float32)[off : off + n]
