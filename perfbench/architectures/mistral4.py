"""`model_type` "mistral4" (Mistral-Small-4, the language model): every layer
latent attention (MLA) followed by routed experts beside one shared expert.

The plain reference, in jax.numpy and float32 at `highest` matmul precision,
one layer and one sequence at a time, nothing imported from the program:

1. h = norm(x). c_q = norm(h W_qa); q = c_q W_qb -> heads x (q_nope | q_rope).
   [c_kv | k_r] = h W_kva; c_kv = norm(c_kv); k_r and q_rope are rotated:
   YaRN inverse frequencies over the rope dims, neighbouring pairs (2i, 2i+1),
   cos and sin times mscale / mscale_all_dim. Queries are multiplied by
   1 + beta * ln(1 + floor(position / original_max)).
2. Expanded form: [k_nope_h | v_h] = c_kv W_kvb per head;
   s = (q_nope_h . k_nope_h + q_rope_h . k_r) * scale, causal softmax,
   o_h = p v_h; x = x + concat_h(o_h) W_o. scale = qk_head_dim^-0.5 * m^2,
   m = 0.1 * mscale_all_dim * ln(factor) + 1.
3. h = norm(x); g = softmax(h W_r) over ALL published experts; the top k,
   their weights divided by their sum, times routed_scaling_factor;
   y = sum over the chosen experts HELD HERE (`serving.experts_held`) of
   w_e * W_down,e(silu(h W_gate,e) * (h W_up,e)), the experts taken one by
   one, + the shared expert's SwiGLU(h). x = x + y. What the absent experts
   would add is left out, as in the program (the chip's share of the
   deployment the configuration's file states).
4. Final norm, head over the vocabulary rows held.

The weights are data, made here from the seed by the recipe the program's
synthetic checkpoint follows (`models/transformer.py` `init_params`): sixteen
splits of `jax.random.key(seed)` taken in the order wq_a, wq_b, wkv_a, wkv_b,
wo, w_gate, w_up, w_down (the shared expert), w_router, we_gate, we_up,
we_down, embed, lm_head; a matmul weight is a truncated normal in [-2, 2]
over sqrt(fan_in), rounded to bfloat16, then int8 per output channel; the
router and the embedding stay bfloat16; norms are one. Expert (layer l,
expert e of ALL routed experts) draws from `fold_in(key, l * n_routed + e)`,
so a share holds the values the uncut model has. `bits=4` is the control.

The counts (`work`) are least counts: the dense int8 weights once a step; of
the routed experts the weights of those an EMITTED token's position routes
to, once a layer a step (the program's routing counters over the window);
the latent cache's published bytes (c_kv and k_r, no padding), each row once
a step; per emitted token its FLOPs, attention in the absorbed form (with a
latent cache, the cheaper of the two).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import (
    PAD_TO,
    _div,
    _embed_rows,
    _head,
    _make_weight,
    _mm,
    _rms,
    _tail,
)
from perfbench.shapes import matmul_weight_bytes

EXPERT = ("we_gate", "we_up", "we_down")


def sizes(cfg: dict) -> dict:
    held = cfg["serving"].get("experts_held") or [0, int(cfg["n_routed_experts"])]
    rope = cfg["rope_parameters"]
    return {
        "D": int(cfg["hidden_size"]),
        # the layers this chip runs (one pipeline stage), or the whole depth
        "L": int(cfg["serving"].get("n_layers") or cfg["num_hidden_layers"]),
        "H": int(cfg["num_attention_heads"]),
        "V": int(cfg["vocab_size"]),
        "q_rank": int(cfg["q_lora_rank"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v": int(cfg["v_head_dim"]),
        "F": int(cfg["moe_intermediate_size"]),
        "n_routed": int(cfg["deployment"]["n_routed_experts_published"]),
        "first": int(held[0]),
        "held": int(held[1]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(rope["rope_theta"]),
        "yarn": (
            float(rope["factor"]),
            int(rope["original_max_position_embeddings"]),
            float(rope["beta_fast"]),
            float(rope["beta_slow"]),
            float(rope["mscale"]),
            float(rope["mscale_all_dim"]),
            float(rope.get("llama_4_scaling_beta", 0.0)),
        ),
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
    }


def matmuls(cfg: dict) -> list[tuple]:
    """(name, in, out) of one layer's dense matrix multiplications."""
    s = sizes(cfg)
    qk = s["nope"] + s["rope"]
    return [
        ("wq_a", s["D"], s["q_rank"]),
        ("wq_b", s["q_rank"], s["H"] * qk),
        ("wkv_a", s["D"], s["kv_rank"] + s["rope"]),
        ("wkv_b", s["kv_rank"], s["H"] * (s["nope"] + s["v"])),
        ("wo", s["H"] * s["v"], s["D"]),
        ("w_gate", s["D"], s["F"]),
        ("w_up", s["D"], s["F"]),
        ("w_down", s["F"], s["D"]),
    ]


def expert_matmuls(cfg: dict) -> list[tuple]:
    s = sizes(cfg)
    return [("we_gate", s["D"], s["F"]), ("we_up", s["D"], s["F"]), ("we_down", s["F"], s["D"])]


# -- the weights -------------------------------------------------------------


@partial(jax.jit, static_argnames=("shape", "fan_in", "bits"))
def _expert_pieces(key, ids, shape: tuple, fan_in: int, bits: int):
    """One [in, out] weight a piece id, each from `fold_in(key, id)`."""
    qmax = {8: 127.0, 4: 7.0}[bits]

    def piece(i):
        w = jax.random.truncated_normal(jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
        w = _div(w, math.sqrt(fan_in)).astype(jnp.bfloat16).astype(jnp.float32)
        amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
        scale = _div(jnp.maximum(amax, 1e-8), qmax)
        return jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8), scale

    return jax.lax.map(piece, ids)


def make_weights(cfg: dict, seed: int, bits: int = 8) -> dict:
    s = sizes(cfg)
    L = s["L"]
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    w = {}
    for name, n_in, n_out in matmuls(cfg):
        w[name] = _make_weight(next(keys), (L, n_in, n_out), n_in, bits)
    w["w_router"] = _make_weight(next(keys), (L, s["D"], s["n_routed"]), s["D"], 16)[0]
    ids = (
        jnp.arange(L)[:, None] * s["n_routed"] + s["first"] + jnp.arange(s["held"])[None, :]
    ).reshape(-1)
    for name, n_in, n_out in expert_matmuls(cfg):
        q, scale = _expert_pieces(next(keys), ids, (n_in, n_out), n_in, bits)
        w[name] = (
            q.reshape(L, s["held"], n_in, n_out),
            scale.reshape(L, s["held"], 1, n_out),
        )
    w["embed"] = _make_weight(next(keys), (s["V"], s["D"]), s["D"], 16)[0]
    w["lm_head"] = _make_weight(next(keys), (s["D"], s["V"]), s["D"], bits)
    return w


# -- the forward -------------------------------------------------------------


def yarn_inv_freq(dim: int, theta: float, yarn: tuple):
    factor, original_max, beta_fast, beta_slow = yarn[:4]

    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    pos_freqs = theta ** (2.0 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_factor(yarn: tuple) -> float:
    """What cos and sin are multiplied by: mscale / mscale_all_dim where the
    configuration gives both (1 when they are equal)."""
    factor, mscale, mscale_all_dim = yarn[0], yarn[4], yarn[5]
    if mscale and mscale_all_dim:
        return _mscale(factor, mscale) / _mscale(factor, mscale_all_dim)
    return _mscale(factor, 1.0)


def softmax_scale(s: dict) -> float:
    factor, mscale_all_dim = s["yarn"][0], s["yarn"][5]
    m = _mscale(factor, mscale_all_dim) if mscale_all_dim else 1.0
    return (s["nope"] + s["rope"]) ** -0.5 * m * m


def _rope_pairs(x, cos, sin):
    """x [T, heads, dim]: pairs (2i, 2i+1) rotated by angle i."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("s",))
def _attention(x, layer, w, *, s):
    """Latent attention of layer `layer` over one sequence x [T, D], causal,
    in the expanded form."""
    s = dict(s)
    T = x.shape[0]
    H, nope, rope, kv_rank = s["H"], s["nope"], s["rope"], s["kv_rank"]
    lw = {name: (q[layer], scale[layer]) for name, (q, scale) in w.items()}
    h = _rms(x, s["eps"])
    q = _mm(_rms(_mm(h, lw["wq_a"]), s["eps"]), lw["wq_b"]).reshape(T, H, nope + rope)
    kv = _mm(h, lw["wkv_a"])
    c_kv = _rms(kv[:, :kv_rank], s["eps"])
    pos = jnp.arange(T, dtype=jnp.float32)
    ang = pos[:, None] * yarn_inv_freq(rope, s["theta"], s["yarn"])
    original_max, beta = s["yarn"][1], s["yarn"][6]
    f = rope_factor(s["yarn"])
    cos, sin = jnp.cos(ang) * f, jnp.sin(ang) * f
    k_r = _rope_pairs(kv[:, None, kv_rank:], cos, sin)[:, 0]  # [T, rope], all heads'
    q_scale = 1.0 + beta * jnp.log1p(jnp.floor(pos / original_max))
    q_nope = q[..., :nope] * q_scale[:, None, None]
    q_rope = _rope_pairs(q[..., nope:], cos, sin) * q_scale[:, None, None]
    kvb = _mm(c_kv, lw["wkv_b"]).reshape(T, H, nope + s["v"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    scale = softmax_scale(s)

    def one_head(args):
        qn, qr, kn, vh = args  # [T, nope], [T, rope], [T, nope], [T, v]
        sc = (qn @ kn.T + qr @ k_r.T) * scale
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return p @ vh

    heads_first = lambda a: a.transpose(1, 0, 2)  # noqa: E731
    out = jax.lax.map(
        one_head, (heads_first(q_nope), heads_first(q_rope), heads_first(k_nope), heads_first(v))
    )
    return x + _mm(out.transpose(1, 0, 2).reshape(T, H * s["v"]), lw["wo"])


@partial(jax.jit, static_argnames=("s", "drop_expert"))
def _ffn(x, layer, w, router, experts, *, s, drop_expert: int = -1):
    """Routed experts (the held ones, one by one) + the shared expert.
    `drop_expert` (a test's planted fault): that held expert's output is
    left out."""
    s = dict(s)
    h = _rms(x, s["eps"])
    gates = jax.nn.softmax(h @ router[layer].astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(gates, s["top_k"])
    if s["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * s["routed_scaling"]

    def one_expert(e, y):
        # the tokens that chose expert first + e, each with its weight
        # (zero for a token that did not choose it)
        w_e = jnp.sum(jnp.where(top_i == s["first"] + e, top_w, 0.0), axis=-1)
        ew = {name: (q[layer, e], scale[layer, e]) for name, (q, scale) in experts.items()}
        out = _mm(jax.nn.silu(_mm(h, ew["we_gate"])) * _mm(h, ew["we_up"]), ew["we_down"])
        return y + jnp.where(e == drop_expert, 0.0, 1.0) * w_e[:, None] * out

    y = jax.lax.fori_loop(0, s["held"], one_expert, jnp.zeros_like(x))
    lw = {name: (w[name][0][layer], w[name][1][layer]) for name in ("w_gate", "w_up", "w_down")}
    shared = _mm(jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"]), lw["w_down"])
    return x + y + shared


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


def logits_for(cfg: dict, weights: dict, ids: list[int], first: int, drop_expert: int = -1) -> np.ndarray:
    """Float32 logits at positions first .. len(ids)-1 of the sequence `ids`."""
    s = _static(sizes(cfg))
    T = len(ids)
    T_pad = -(-T // PAD_TO) * PAD_TO
    tokens = jnp.asarray(list(ids) + [0] * (T_pad - T), jnp.int32)
    attn_w = {n: weights[n] for n in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
    ffn_w = {n: weights[n] for n in ("w_gate", "w_up", "w_down")}
    experts = {n: weights[n] for n in EXPERT}
    eps = dict(s)["eps"]
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(weights["embed"], tokens)
        for layer in range(dict(s)["L"]):
            x = _attention(x, jnp.int32(layer), attn_w, s=s)
            x = _ffn(
                x, jnp.int32(layer), ffn_w, weights["w_router"], experts, s=s,
                drop_expert=drop_expert,
            )
        n = T - first
        n_pad = -(-n // 8) * 8
        start = min(first, T_pad - n_pad)
        rows = _tail(x, jnp.int32(start), n_pad)
        logits = _head(rows, weights["lm_head"], eps=eps, blocks=8)
    off = first - start
    return np.asarray(logits, np.float32)[off : off + n]


# -- bytes and work ----------------------------------------------------------


def expert_bytes(cfg: dict, quant: str) -> int:
    """One routed expert's three matrices as the tree holds them."""
    return sum(matmul_weight_bytes(i, o, quant) for _, i, o in expert_matmuls(cfg))


def weight_bytes(cfg: dict, quant: str) -> dict:
    s = sizes(cfg)
    dense = sum(matmul_weight_bytes(i, o, quant) for _, i, o in matmuls(cfg))
    router = 2 * s["D"] * s["n_routed"]  # bfloat16
    out = {
        "layers_matmul": s["L"] * (dense + router + s["held"] * expert_bytes(cfg, quant)),
        "layers_small": s["L"] * 2 * (2 * s["D"] + s["q_rank"] + s["kv_rank"]),  # four norms
        "embed": 2 * s["V"] * s["D"],
        "final_norm": 2 * s["D"],
        "lm_head": matmul_weight_bytes(s["D"], s["V"], quant),
    }
    out["total"] = sum(out.values())
    return out


def latent_bytes_per_token(cfg: dict) -> int:
    """c_kv and k_r of one token over all layers, bfloat16, as published
    (the pool's padding of k_r to whole lanes is not work)."""
    s = sizes(cfg)
    return 2 * s["L"] * (s["kv_rank"] + s["rope"])


def attention_flops(cfg: dict, context: int) -> int:
    """One token attending to `context` cached positions, all layers, in the
    absorbed form: scores against [c_kv | k_r], the weighted sum of c_kv."""
    s = sizes(cfg)
    return 2 * context * s["H"] * (2 * s["kv_rank"] + s["rope"]) * s["L"]


def _dense_params(cfg: dict) -> int:
    s = sizes(cfg)
    return sum(i * o for _, i, o in matmuls(cfg)) + s["D"] * s["n_routed"]


def _expert_params(cfg: dict) -> int:
    return sum(i * o for _, i, o in expert_matmuls(cfg))


def _routing(r) -> tuple[float, float] | None:
    """(pairs, expert reads) the window's decode steps' EMITTED positions
    cost, from the program's routing counters; None where it has none."""

    def delta(name):
        key = f'obs.advspec_moe_{name}_total{{positions="emitted",program="decode"}}'
        if key not in r.counters_end:
            return None
        return r.counters_end[key] - r.counters_start.get(key, 0)

    pairs, reads = delta("pairs"), delta("active_experts")
    if pairs is None or reads is None:
        return None
    s = sizes(r.config)
    expect = len(r.token_contexts) * s["L"] * s["top_k"] * s["held"] / s["n_routed"]
    note = (
        f"moe: {pairs:.0f} (token, expert) pairs on held experts counted over the emitted "
        f"positions of the window's decode steps, {reads:.0f} expert reads (an expert a layer a "
        f"step); positions x layers x {s['top_k']} x {s['held']}/{s['n_routed']} = {expect:.0f}"
    )
    if note not in r.notes:
        r.notes.append(note)
    return pairs, reads


def work(kind: str, r, n_steps: float | None):
    cfg, quant = r.config, r.quant
    s = sizes(cfg)
    if kind == "decode":
        routed = _routing(r)
        if not n_steps or not r.token_contexts or routed is None:
            return None
        pairs, reads = routed
        w = weight_bytes(cfg, quant)
        dense = sum(matmul_weight_bytes(i, o, quant) for _, i, o in matmuls(cfg))
        per_step = (
            s["L"] * (dense + 2 * s["D"] * s["n_routed"])
            + w["layers_small"] + w["final_norm"] + w["lm_head"]
        )
        kv = latent_bytes_per_token(cfg)
        return {
            "bytes": int(round(n_steps)) * per_step
            + reads * expert_bytes(cfg, quant)
            + sum(r.row_step_contexts) * kv
            + len(r.token_contexts) * (2 * s["D"] + kv),
            "flops": len(r.token_contexts) * 2 * (s["L"] * _dense_params(cfg) + s["D"] * s["V"])
            + pairs * 2 * _expert_params(cfg)
            + sum(attention_flops(cfg, c) for c in r.token_contexts),
        }
    if kind == "prefill":
        if not r.prefill_spans:
            return None
        # expanded form; a token's expected share of held experts
        per_token = 2 * s["L"] * (
            _dense_params(cfg) + s["top_k"] * s["held"] / s["n_routed"] * _expert_params(cfg)
        )
        attn_one = 2 * s["H"] * (s["nope"] + s["rope"] + s["v"]) * s["L"]
        flops, tokens = 0, 0
        for start, end in r.prefill_spans:
            n = max(0, end - start)
            tokens += n
            flops += n * per_token + attn_one * ((start + 1 + end) * n // 2)
            if n:
                flops += 2 * s["D"] * s["V"]
        return {"flops": flops, "tokens": tokens, "bytes": 0}
    if kind == "latent_attention":
        if not r.row_step_contexts:
            return None
        return {
            "bytes": sum(r.row_step_contexts) * latent_bytes_per_token(cfg),
            "flops": sum(attention_flops(cfg, c) for c in r.token_contexts),
        }
    if kind == "moe_experts":
        routed = _routing(r)
        if routed is None:
            return None
        pairs, reads = routed
        return {
            "bytes": reads * expert_bytes(cfg, quant),
            "flops": pairs * 2 * _expert_params(cfg),
        }
    raise KeyError(f"unknown work {kind!r}")
