"""`model_type` "afmoe" (Arcee Trinity): gated grouped-query attention whose
layers are windowed and rotated, or global and unrotated, under sandwich
norms; leading dense layers, then a sigmoid router with a selection bias over
routed experts beside one shared expert.

The plain reference, in jax.numpy and float32 at `highest` matmul precision,
one layer and one sequence at a time, attention a block of queries at a time,
nothing imported from the program. The published description is the `afmoe`
modelling code of `transformers` (`models/afmoe/modeling_afmoe.py`):

    x0 = E[token] * sqrt(hidden)
    a = norm(x);  q, k, v = a Wq, a Wk, a Wv      (no bias)
    q, k = norm(q), norm(k)                       (over each head's 128)
    sliding_attention:  q, k rotated (theta 10000, half rotation);
                        keys seen: the last `sliding_window` positions, own included
    full_attention:     nothing rotated; every earlier position
    o = softmax(q k^T / sqrt(head_dim)) v;  o = o * sigmoid(a Wg)
    x = x + norm(o Wo)
    m = norm(x)
    a dense layer:   f = SwiGLU(m), `intermediate_size` wide
    a routed layer:  s = sigmoid(m Wr) in float32;  idx = top-k(s + b)
                     w = s[idx] / (sum s[idx] + 1e-20) * route_scale
                     f = SwiGLU_shared(m) + sum over idx HELD HERE of w SwiGLU_idx(m)
    x = x + norm(f)
    logits = norm(x) W_head

Which layers this chip runs is `deployment.layers_here`: published layer
indices, each dense iff below `num_dense_layers`, each of the kind
`layer_types` gives it. What the absent experts would add is left out, as in
the program (`serving.experts_held` of `deployment.num_experts_published`);
the head is over the vocabulary rows held.

The weights are data, made here from the seed by the recipe the program's
synthetic checkpoint follows (`models/transformer.py` `init_params`). The
routed layers' stack: sixteen splits of `jax.random.key(seed)` taken in the
order wq, wk, wv, wo, wg, w_gate, w_up, w_down (the shared expert), w_router,
router_bias, we_gate, we_up, we_down, embed, lm_head. The leading dense
layers' stack: sixteen splits of `fold_in(key, 1)` in the order wq, wk, wv,
wo, wg, w_gate, w_up, w_down. A matmul weight is a truncated normal in
[-2, 2] over sqrt(fan_in), rounded to bfloat16, then int8 per output
channel; the router and the embedding stay bfloat16; norms are one; the
router's bias is `assumed_sizes.router_bias_std` times a standard normal,
float32. Expert (routed layer l, expert e of ALL routed experts) draws from
`fold_in(key, l * n_routed + e)`. `bits=4` is the control.

The counts (`work`) are least counts: the dense int8 weights once a step; of
the routed experts those an EMITTED token's position routes to, once a layer
a step (the program's routing counters); of the keys and values only the
positions inside each layer's bounds (min(context, window) on a windowed
layer), each row once a step: whole pages that the kernel touches beyond
them are not work, so the share is understated and never overstated.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import (
    PAD_TO,
    _embed_rows,
    _head,
    _make_weight,
    _mm,
    _rms,
    _rope,
    _tail,
)
from perfbench.shapes import matmul_weight_bytes

EXPERT = ("we_gate", "we_up", "we_down")
ATTN = ("wq", "wk", "wv", "wo", "wg")
FFN = ("w_gate", "w_up", "w_down")
QUERY_BLOCK = 1024  # queries a block of the reference's attention; divides PAD_TO


def sizes(cfg: dict) -> dict:
    held = cfg["serving"].get("experts_held") or [0, int(cfg["num_experts"])]
    here = [int(i) for i in cfg["deployment"]["layers_here"]]
    n_dense = int(cfg["num_dense_layers"])
    if int(cfg["num_shared_experts"]) != 1:
        raise NotImplementedError("one shared expert beside the routed")
    return {
        "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]),
        "F_dense": int(cfg["intermediate_size"]),
        "F": int(cfg["moe_intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        # the layers this chip runs, in order: (windowed and rotated?, dense FFN?)
        "layers": tuple(
            (cfg["layer_types"][i] == "sliding_attention", i < n_dense) for i in here
        ),
        "window": int(cfg["sliding_window"]),
        "n_routed": int(cfg["deployment"]["num_experts_published"]),
        "first": int(held[0]),
        "held": int(held[1]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "route_scale": float(cfg["route_scale"]),
        "route_norm": bool(cfg["route_norm"]),
        "bias_std": float(cfg["assumed_sizes"]["router_bias_std"]),
    }


def n_dense(s: dict) -> int:
    """The leading dense layers (they stand first: a stack of their own)."""
    dense = [d for _, d in s["layers"]]
    n = sum(dense)
    if dense != [True] * n + [False] * (len(dense) - n):
        raise ValueError("dense layers lead the stack")
    return n


def attn_matmuls(s: dict) -> list[tuple]:
    qd, kd = s["H"] * s["hd"], s["KV"] * s["hd"]
    return [
        ("wq", s["D"], qd), ("wk", s["D"], kd), ("wv", s["D"], kd),
        ("wo", qd, s["D"]), ("wg", s["D"], qd),
    ]


def ffn_matmuls(s: dict, width: int) -> list[tuple]:
    return [("w_gate", s["D"], width), ("w_up", s["D"], width), ("w_down", width, s["D"])]


def expert_matmuls(s: dict) -> list[tuple]:
    return [("we_gate", s["D"], s["F"]), ("we_up", s["D"], s["F"]), ("we_down", s["F"], s["D"])]


# -- the weights -------------------------------------------------------------


@partial(jax.jit, static_argnames=("shape", "fan_in", "bits"))
def _expert_pieces(key, ids, shape: tuple, fan_in: int, bits: int):
    """One [in, out] weight a piece id, each from `fold_in(key, id)`."""

    def piece(i):
        return _make_weight(jax.random.fold_in(key, i), shape, fan_in, bits)

    return jax.lax.map(piece, ids)


def make_weights(cfg: dict, seed: int, bits: int = 8) -> dict:
    s = sizes(cfg)
    lead = n_dense(s)
    L = len(s["layers"]) - lead
    root = jax.random.key(seed)
    keys = iter(jax.random.split(root, 16))
    w = {}
    for name, n_in, n_out in attn_matmuls(s) + ffn_matmuls(s, s["F"]):
        w[name] = _make_weight(next(keys), (L, n_in, n_out), n_in, bits)
    w["w_router"] = _make_weight(next(keys), (L, s["D"], s["n_routed"]), s["D"], 16)[0]
    w["router_bias"] = s["bias_std"] * jax.random.normal(
        next(keys), (L, s["n_routed"]), jnp.float32
    )
    ids = (
        jnp.arange(L)[:, None] * s["n_routed"] + s["first"] + jnp.arange(s["held"])[None, :]
    ).reshape(-1)
    for name, n_in, n_out in expert_matmuls(s):
        q, scale = _expert_pieces(next(keys), ids, (n_in, n_out), n_in, bits)
        w[name] = (
            q.reshape(L, s["held"], n_in, n_out),
            scale.reshape(L, s["held"], 1, n_out),
        )
    w["embed"] = _make_weight(next(keys), (s["V"], s["D"]), s["D"], 16)[0]
    w["lm_head"] = _make_weight(next(keys), (s["D"], s["V"]), s["D"], bits)
    lead_keys = iter(jax.random.split(jax.random.fold_in(root, 1), 16))
    w["leading"] = {
        name: _make_weight(next(lead_keys), (lead, n_in, n_out), n_in, bits)
        for name, n_in, n_out in attn_matmuls(s) + ffn_matmuls(s, s["F_dense"])
    }
    return w


# -- the forward -------------------------------------------------------------


def _swiglu(h, lw):
    return _mm(jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"]), lw["w_down"])


@partial(jax.jit, static_argnames=("s", "windowed"))
def _attention(x, row, w, *, s, windowed: bool):
    """Gated attention of row `row` of the stack `w` over one sequence
    x [T, D], causal; `windowed`: rotated, and the last `window` positions."""
    s = dict(s)
    T = x.shape[0]
    H, KV, hd = s["H"], s["KV"], s["hd"]
    g = H // KV
    lw = {name: (q[row], scale[row]) for name, (q, scale) in w.items()}
    a = _rms(x, s["eps"])
    q = _rms(_mm(a, lw["wq"]).reshape(T, H, hd), s["eps"])
    k = _rms(_mm(a, lw["wk"]).reshape(T, KV, hd), s["eps"])
    v = _mm(a, lw["wv"]).reshape(T, KV, hd)
    if windowed:
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    cols = jnp.arange(T)[None, :]

    def one_group(args):
        qg, kg, vg = args  # [T, g, hd], [T, hd], [T, hd]

        def one_block(i):
            rows = (i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK))[:, None]
            mask = cols <= rows
            if windowed:
                mask = mask & (cols > rows - s["window"])
            qb = jax.lax.dynamic_slice_in_dim(qg, i * QUERY_BLOCK, QUERY_BLOCK, 0)
            sc = jnp.einsum("tgd,sd->gts", qb, kg) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gts,sd->tgd", p, vg)

        return jax.lax.map(one_block, jnp.arange(T // QUERY_BLOCK)).reshape(T, g, hd)

    out = jax.lax.map(
        one_group,
        (
            q.reshape(T, KV, g, hd).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2),
            v.transpose(1, 0, 2),
        ),
    )
    o = out.transpose(1, 0, 2, 3).reshape(T, H * hd)
    o = o * jax.nn.sigmoid(_mm(a, lw["wg"]))
    return x + _rms(_mm(o, lw["wo"]), s["eps"])


@partial(jax.jit, static_argnames=("s",))
def _dense_ffn(x, row, w, *, s):
    s = dict(s)
    lw = {name: (q[row], scale[row]) for name, (q, scale) in w.items()}
    return x + _rms(_swiglu(_rms(x, s["eps"]), lw), s["eps"])


@partial(jax.jit, static_argnames=("s", "drop_expert"))
def _routed_ffn(x, row, w, router, bias, experts, *, s, drop_expert: int = -1):
    m = _rms(x, dict(s)["eps"])
    f = _routed_mix(m, row, w, router, bias, experts, s=s, drop_expert=drop_expert)
    return x + _rms(f, dict(s)["eps"])


@partial(jax.jit, static_argnames=("s", "drop_expert"))
def _routed_mix(m, row, w, router, bias, experts, *, s, drop_expert: int = -1):
    """Routed experts (the held ones, one by one) + the shared expert over
    the normed activations `m`, before the block's second norm: what the
    shares of a deployment add up in. `drop_expert` (a test's planted
    fault): that held expert's output is left out."""
    s = dict(s)
    scores = jax.nn.sigmoid(m @ router[row].astype(jnp.float32))
    _, top_i = jax.lax.top_k(scores + bias[row], s["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if s["route_norm"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * s["route_scale"]

    def one_expert(e, y):
        # the tokens that chose expert first + e, each with its weight
        # (zero for a token that did not choose it)
        w_e = jnp.sum(jnp.where(top_i == s["first"] + e, top_w, 0.0), axis=-1)
        ew = {
            {"we_gate": "w_gate", "we_up": "w_up", "we_down": "w_down"}[name]: (
                q[row, e], scale[row, e],
            )
            for name, (q, scale) in experts.items()
        }
        return y + jnp.where(e == drop_expert, 0.0, 1.0) * w_e[:, None] * _swiglu(m, ew)

    y = jax.lax.fori_loop(0, s["held"], one_expert, jnp.zeros_like(m))
    lw = {name: (w[name][0][row], w[name][1][row]) for name in FFN}
    return y + _swiglu(m, lw)


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


def chosen_experts(cfg: dict, weights: dict, ids: list[int], with_bias: bool) -> np.ndarray:
    """The experts the first routed layer's router chooses for each token of
    `ids` over the embedding alone (no layer before it), [T, top_k] sorted:
    how a test measures the share of tokens whose choice the bias moves."""
    s = sizes(cfg)
    x = _embed_rows(weights["embed"], jnp.asarray(ids, jnp.int32)) * math.sqrt(s["D"])
    scores = jax.nn.sigmoid(_rms(x, s["eps"]) @ weights["w_router"][0].astype(jnp.float32))
    if with_bias:
        scores = scores + weights["router_bias"][0]
    return np.sort(np.asarray(jax.lax.top_k(scores, s["top_k"])[1]), axis=-1)


def logits_for(cfg: dict, weights: dict, ids: list[int], first: int, drop_expert: int = -1) -> np.ndarray:
    """Float32 logits at positions first .. len(ids)-1 of the sequence `ids`."""
    sz = sizes(cfg)
    s = _static(sz)
    T = len(ids)
    T_pad = -(-T // PAD_TO) * PAD_TO
    tokens = jnp.asarray(list(ids) + [0] * (T_pad - T), jnp.int32)
    lead = n_dense(sz)
    stacks = (
        {n: weights["leading"][n] for n in ATTN},
        {n: weights[n] for n in ATTN},
    )
    lead_ffn = {n: weights["leading"][n] for n in FFN}
    shared = {n: weights[n] for n in FFN}
    experts = {n: weights[n] for n in EXPERT}
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(weights["embed"], tokens) * math.sqrt(sz["D"])
        for i, (windowed, dense) in enumerate(sz["layers"]):
            row = jnp.int32(i if dense else i - lead)
            x = _attention(x, row, stacks[0 if dense else 1], s=s, windowed=windowed)
            if dense:
                x = _dense_ffn(x, row, lead_ffn, s=s)
            else:
                x = _routed_ffn(
                    x, row, shared, weights["w_router"], weights["router_bias"], experts,
                    s=s, drop_expert=drop_expert,
                )
        n = T - first
        n_pad = -(-n // 8) * 8
        start = min(first, T_pad - n_pad)
        rows = _tail(x, jnp.int32(start), n_pad)
        logits = _head(rows, weights["lm_head"], eps=sz["eps"], blocks=8)
    off = first - start
    return np.asarray(logits, np.float32)[off : off + n]


# -- bytes and work ----------------------------------------------------------


def _bytes(matmuls: list[tuple], quant: str) -> int:
    return sum(matmul_weight_bytes(i, o, quant) for _, i, o in matmuls)


def _params(matmuls: list[tuple]) -> int:
    return sum(i * o for _, i, o in matmuls)


def expert_bytes(cfg: dict, quant: str) -> int:
    """One routed expert's three matrices as the tree holds them."""
    return _bytes(expert_matmuls(sizes(cfg)), quant)


def _norm_bytes(s: dict) -> int:
    """A layer's four sandwich norms and its two head norms, bfloat16."""
    return 2 * (4 * s["D"] + 2 * s["hd"])


def weight_bytes(cfg: dict, quant: str) -> dict:
    s = sizes(cfg)
    lead = n_dense(s)
    L = len(s["layers"]) - lead
    dense = _bytes(attn_matmuls(s) + ffn_matmuls(s, s["F"]), quant)
    router = 2 * s["D"] * s["n_routed"]  # bfloat16
    out = {
        "leading": lead
        * (_bytes(attn_matmuls(s) + ffn_matmuls(s, s["F_dense"]), quant) + _norm_bytes(s)),
        "layers_matmul": L * (dense + router + s["held"] * expert_bytes(cfg, quant)),
        "layers_small": L * (_norm_bytes(s) + 4 * s["n_routed"]),  # + the bias, float32
        "embed": 2 * s["V"] * s["D"],
        "final_norm": 2 * s["D"],
        "lm_head": matmul_weight_bytes(s["D"], s["V"], quant),
    }
    out["total"] = sum(out.values())
    return out


def kv_bytes_per_token_layer(s: dict) -> int:
    """Keys and values of one token in one layer, bfloat16."""
    return 2 * s["KV"] * s["hd"] * 2


def tokens_in_bounds(s: dict, context: int) -> int:
    """Cached positions a query at the end of `context` tokens attends to,
    summed over the layers: min(context, window) on a windowed layer."""
    return sum(min(context, s["window"]) if w else context for w, _ in s["layers"])


def attention_flops(s: dict, context: int) -> int:
    """One token attending to the positions inside each layer's bounds:
    scores and the weighted sum, two FLOPs a multiply-add."""
    return 4 * tokens_in_bounds(s, context) * s["H"] * s["hd"]


def _dense_params(s: dict) -> int:
    """The parameters every token meets: attention of every layer, the
    leading layers' FFN, the shared expert and the router of the others."""
    lead = n_dense(s)
    L = len(s["layers"]) - lead
    return (
        len(s["layers"]) * _params(attn_matmuls(s))
        + lead * _params(ffn_matmuls(s, s["F_dense"]))
        + L * (_params(ffn_matmuls(s, s["F"])) + s["D"] * s["n_routed"])
    )


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
    L = len(s["layers"]) - n_dense(s)
    expect = len(r.token_contexts) * L * s["top_k"] * s["held"] / s["n_routed"]
    note = (
        f"moe: {pairs:.0f} (token, expert) pairs on held experts counted over the emitted "
        f"positions of the window's decode steps, {reads:.0f} expert reads (an expert a layer a "
        f"step); positions x routed layers x {s['top_k']} x {s['held']}/{s['n_routed']} = {expect:.0f}"
    )
    if note not in r.notes:
        r.notes.append(note)
    return pairs, reads


def work(kind: str, r, n_steps: float | None):
    cfg, quant = r.config, r.quant
    s = sizes(cfg)
    kv = kv_bytes_per_token_layer(s)
    if kind == "decode":
        routed = _routing(r)
        if not n_steps or not r.token_contexts or routed is None:
            return None
        pairs, reads = routed
        w = weight_bytes(cfg, quant)
        L = len(s["layers"]) - n_dense(s)
        per_step = (
            w["total"] - w["embed"] - L * s["held"] * expert_bytes(cfg, quant)
        )
        return {
            "bytes": int(round(n_steps)) * per_step
            + reads * expert_bytes(cfg, quant)
            + sum(tokens_in_bounds(s, c) for c in r.row_step_contexts) * kv
            # its embedding row, its own keys and values written in every layer
            + len(r.token_contexts) * (2 * s["D"] + len(s["layers"]) * kv),
            "flops": len(r.token_contexts) * 2 * (_dense_params(s) + s["D"] * s["V"])
            + pairs * 2 * _params(expert_matmuls(s))
            + sum(attention_flops(s, c) for c in r.token_contexts),
        }
    if kind == "paged_attention":
        if not r.row_step_contexts:
            return None
        return {
            "bytes": sum(tokens_in_bounds(s, c) for c in r.row_step_contexts) * kv,
            "flops": sum(attention_flops(s, c) for c in r.token_contexts),
        }
    if kind == "moe_experts":
        routed = _routing(r)
        if routed is None:
            return None
        pairs, reads = routed
        return {
            "bytes": reads * expert_bytes(cfg, quant),
            "flops": pairs * 2 * _params(expert_matmuls(s)),
        }
    raise KeyError(f"unknown work {kind!r}")
