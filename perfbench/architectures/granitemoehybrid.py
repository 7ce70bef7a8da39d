"""`model_type` "granitemoehybrid" (Granite-4.0-H with no routed experts):
state-space (Mamba-2) layers beside a few attention layers, the shared
SwiGLU MLP after every mixer.

The plain reference, in jax.numpy and float32 at `highest` matmul precision,
one layer and one sequence at a time, nothing imported from the program. Per
layer i, with `layer_types[i]` saying which mixer:

    x = x + residual_multiplier * mixer(rmsnorm(x))
    x = x + residual_multiplier * W_down(silu(W_gate h) * (W_up h)),  h = rmsnorm(x)

- "mamba": [z | xBC | dt] = h W_in (inner, inner + 2 N, heads wide);
  xBC_t = silu(b + sum_k w_k * xBC_{t-3+k}) per channel, zeros before the
  sequence; [x | B | C] = xBC; dt = softplus(dt + dt_bias), A = -exp(A_log);
  **token by token** S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t, a
  [head_dim, N] state a head, y_t = S_t C_t + D x_t (a `lax.scan` over the
  positions: no chunked form here); y = rmsnorm(y * silu(z)) over all inner
  channels; out = y W_out.
- "attention": q, k, v without bias and WITHOUT rotary embedding
  (`position_embedding_type` "nope"), scores q k^T * attention_multiplier,
  causal, grouped queries; out = concat(heads) W_o.

Embedding rows times `embedding_multiplier`; logits = rmsnorm(x) E^T /
`logits_scaling` (tied embeddings).

Departures from the published description: none in the arithmetic. The
published module fuses W_gate and W_up into one `input_linear` and splits
its output in halves (the same numbers); its conv, norms and projections
are as above. The state is float32 (`assumed.state_dtype`).

The weights are data, made here by the recipe of the program's synthetic
checkpoint (`models/transformer.py` `init_params`): sixteen splits of
`jax.random.key(seed)` taken in the order wq, wk, wv, wo (the attention
layers' stacks), w_in, w_out, conv_w, the dt draw (the state-space layers'),
w_gate, w_up, w_down (every layer's), embed; a matmul weight and the conv's
taps are a truncated normal in [-2, 2] over sqrt(fan_in), rounded to
bfloat16; the embedding's rows are that divided by `embedding_multiplier`
and rounded again (at the recipe's own scale the tied head would make a
greedy reply one repeated token, whatever the state holds); dt_bias = inverse softplus of exp(uniform[ln 1e-3, ln 1e-1]),
A_log = ln(1..heads), D = 1, norms one, the conv's bias zero.

**What `bits` means here.** `perfbench/run.py` passes `bits=8` for "the
weights as served" and `bits=4` for "the control, the nearest precision
below". This configuration is served in bfloat16, so `bits=8` gives the
bfloat16-rounded weights and `bits=4` the same weights through int8 per
output channel (the embedding, the conv and the small vectors stay as they
are). A second control, not run.py's, keeps the weights and holds the
recurrent state in bfloat16 (`state_dtype`; tests/benchmark/granite_controls.py).

The counts (`work`) are least counts: every weight once a step; of the
state, each row's 36 states once read and once written at their stored
width (float32) and its conv windows, once a verify step the row took part
in; the 4 attention layers' published keys and values of the row's context
once such a step; per emitted token its FLOPs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import PAD_TO, _div, _embed_rows, _make_weight, _mm, _rms, _tail

MATMULS_ATTN = ("wq", "wk", "wv", "wo")
MATMULS_SSM = ("w_in", "w_out")
MATMULS_MLP = ("w_gate", "w_up", "w_down")


def sizes(cfg: dict) -> dict:
    D = int(cfg["hidden_size"])
    kinds = tuple(cfg["layer_types"])
    H = int(cfg["mamba_n_heads"])
    P = int(cfg["mamba_d_head"])
    N = int(cfg["mamba_d_state"])
    G = int(cfg["mamba_n_groups"])
    heads = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or D // heads)
    inner = H * P
    if inner != int(cfg["mamba_expand"]) * D or len(kinds) != int(cfg["num_hidden_layers"]):
        raise ValueError("mamba_expand / layer_types disagree with the other sizes")
    return {
        "D": D,
        "L": len(kinds),
        "kinds": kinds,
        "n_ssm": sum(k == "mamba" for k in kinds),
        "n_attn": sum(k == "attention" for k in kinds),
        "H": H, "P": P, "N": N, "G": G,
        "K": int(cfg["mamba_d_conv"]),
        "inner": inner,
        "conv": inner + 2 * G * N,
        "in": 2 * inner + 2 * G * N + H,
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": hd,
        "F": int(cfg["shared_intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "emb": float(cfg["embedding_multiplier"]),
        "res": float(cfg["residual_multiplier"]),
        "att": float(cfg["attention_multiplier"]),
        "logit": float(cfg["logits_scaling"]),
    }


def matmuls(cfg: dict) -> dict:
    """name -> (layers in its stack, in, out)."""
    s = sizes(cfg)
    QD, KD = s["heads"] * s["hd"], s["kv_heads"] * s["hd"]
    return {
        "wq": (s["n_attn"], s["D"], QD),
        "wk": (s["n_attn"], s["D"], KD),
        "wv": (s["n_attn"], s["D"], KD),
        "wo": (s["n_attn"], QD, s["D"]),
        "w_in": (s["n_ssm"], s["D"], s["in"]),
        "w_out": (s["n_ssm"], s["inner"], s["D"]),
        "w_gate": (s["L"], s["D"], s["F"]),
        "w_up": (s["L"], s["D"], s["F"]),
        "w_down": (s["L"], s["F"], s["D"]),
    }


# -- the weights -------------------------------------------------------------


def make_weights(cfg: dict, seed: int, bits: int = 8, state_dtype: str = "float32") -> dict:
    """`bits` 8: the weights as served (bfloat16); 4: the control (the same
    through int8 per output channel). See the module's docstring."""
    s = sizes(cfg)
    if s["G"] != 1:
        raise NotImplementedError("one group of B and C")
    made = {8: 16, 4: 8}[bits]
    mm = matmuls(cfg)
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    w: dict = {"state_dtype": state_dtype}
    for name in MATMULS_ATTN + MATMULS_SSM:
        n, n_in, n_out = mm[name]
        w[name] = _make_weight(next(keys), (n, n_in, n_out), n_in, made)
    w["conv_w"] = _make_weight(next(keys), (s["n_ssm"], s["K"], s["conv"]), s["K"], 16)[0]
    dt = jnp.exp(
        jax.random.uniform(
            next(keys), (s["n_ssm"], s["H"]), jnp.float32, math.log(1e-3), math.log(1e-1)
        )
    )
    w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    w["A"] = -jnp.arange(1, s["H"] + 1, dtype=jnp.float32)  # -exp(A_log), every layer's
    for name in MATMULS_MLP:
        n, n_in, n_out = mm[name]
        w[name] = _make_weight(next(keys), (n, n_in, n_out), n_in, made)
    embed = _make_weight(next(keys), (s["V"], s["D"]), s["D"], 16)[0]
    w["embed"] = _div(embed.astype(jnp.float32), s["emb"]).astype(jnp.bfloat16)
    return w


# -- the forward -------------------------------------------------------------


def _at(w: dict, names, i):
    return {n: (w[n][0][i], w[n][1][i]) for n in names}


def _mlp(x, lw, s):
    h = _rms(x, s["eps"])
    ff = jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"])
    return x + s["res"] * _mm(ff, lw["w_down"])


@partial(jax.jit, static_argnames=("s",))
def _attention_layer(x, i, layer, w, *, s):
    s = dict(s)
    T = x.shape[0]
    lw = {**_at(w, MATMULS_ATTN, i), **_at(w, MATMULS_MLP, layer)}
    h = _rms(x, s["eps"])
    q = _mm(h, lw["wq"]).reshape(T, s["heads"], s["hd"])
    k = _mm(h, lw["wk"]).reshape(T, s["kv_heads"], s["hd"])
    v = _mm(h, lw["wv"]).reshape(T, s["kv_heads"], s["hd"])
    g = s["heads"] // s["kv_heads"]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]

    def one_group(args):
        qg, kg, vg = args  # [T, g, hd], [T, hd], [T, hd]
        sc = jnp.einsum("tgd,sd->gts", qg, kg) * s["att"]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vg)

    qg = q.reshape(T, s["kv_heads"], g, s["hd"]).transpose(1, 0, 2, 3)
    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = out.transpose(1, 0, 2, 3).reshape(T, s["heads"] * s["hd"])
    return _mlp(x + s["res"] * _mm(attn, lw["wo"]), lw, s)


@partial(jax.jit, static_argnames=("s", "state_dtype"))
def _mamba_layer(x, i, layer, w, conv_w, dt_bias, a, *, s, state_dtype):
    s = dict(s)
    T = x.shape[0]
    inner, N, H, P, K = s["inner"], s["N"], s["H"], s["P"], s["K"]
    lw = {**_at(w, MATMULS_SSM, i), **_at(w, MATMULS_MLP, layer)}
    h = _rms(x, s["eps"])
    zxd = _mm(h, lw["w_in"])
    z, raw, dt = zxd[:, :inner], zxd[:, inner : inner + s["conv"]], zxd[:, inner + s["conv"] :]
    taps = conv_w[i].astype(jnp.float32)  # [K, conv]; the bias is zero
    padded = jnp.concatenate([jnp.zeros((K - 1, s["conv"]), jnp.float32), raw], axis=0)
    xbc = jax.nn.silu(sum(taps[k] * padded[k : k + T] for k in range(K)))
    xs = xbc[:, :inner].reshape(T, H, P)
    b_in, c_in = xbc[:, inner : inner + N], xbc[:, inner + N :]
    dt = jax.nn.softplus(dt + dt_bias[i])  # [T, H]
    dtype = jnp.dtype(state_dtype)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (
            jnp.exp(dt_t * a)[:, None, None] * state.astype(jnp.float32)
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        ).astype(dtype)
        y_t = jnp.einsum("hpn,n->hp", state.astype(jnp.float32), c_t) + x_t  # D = 1
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), dtype), (xs, b_in, c_in, dt))
    gated = _rms(y.reshape(T, inner) * jax.nn.silu(z), s["eps"])
    return _mlp(x + s["res"] * _mm(gated, lw["w_out"]), lw, s)


@partial(jax.jit, static_argnames=("eps", "scaling", "blocks"))
def _tied_head(x, embed, *, eps, scaling, blocks):
    """Logits [n, V] of the rows x [n, D] against the embedding's rows, in
    blocks of the vocabulary."""
    h = _rms(x, eps)
    V = embed.shape[0]
    step = -(-V // blocks)
    outs = [
        h @ embed[b * step : min(V, (b + 1) * step)].astype(jnp.float32).T
        for b in range(blocks)
    ]
    return jnp.concatenate(outs, axis=-1) / scaling


def _static(s: dict) -> tuple:
    return tuple(sorted(s.items()))


def logits_for(cfg: dict, weights: dict, ids: list[int], first: int) -> np.ndarray:
    """Float32 logits at positions first .. len(ids)-1 of the sequence `ids`."""
    sd = sizes(cfg)
    s = _static(sd)
    T = len(ids)
    T_pad = -(-T // PAD_TO) * PAD_TO
    tokens = jnp.asarray(list(ids) + [0] * (T_pad - T), jnp.int32)
    mats = {n: weights[n] for n in MATMULS_ATTN + MATMULS_SSM + MATMULS_MLP}
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(weights["embed"], tokens) * sd["emb"]
        n_attn = n_ssm = 0
        for layer, kind in enumerate(sd["kinds"]):
            if kind == "mamba":
                x = _mamba_layer(
                    x, jnp.int32(n_ssm), jnp.int32(layer), mats, weights["conv_w"],
                    weights["dt_bias"], weights["A"], s=s,
                    state_dtype=weights.get("state_dtype", "float32"),
                )
                n_ssm += 1
            else:
                x = _attention_layer(x, jnp.int32(n_attn), jnp.int32(layer), mats, s=s)
                n_attn += 1
        n = T - first
        n_pad = -(-n // 8) * 8
        start = min(first, T_pad - n_pad)
        rows = _tail(x, jnp.int32(start), n_pad)
        logits = _tied_head(
            rows, weights["embed"], eps=sd["eps"], scaling=sd["logit"], blocks=8
        )
    off = first - start
    return np.asarray(logits, np.float32)[off : off + n]


# -- bytes and work ----------------------------------------------------------


def _itemsize(quant: str) -> int:
    if quant:
        raise NotImplementedError(f"granitemoehybrid is served in bfloat16, not {quant!r}")
    return 2


def weight_bytes(cfg: dict, quant: str) -> dict:
    """The parameter tree by part, as the program holds it. `lm_head_t` is
    the program's transposed copy of the tied embedding (the head matmul
    contracts the major axis): device bytes, not published parameters."""
    s = sizes(cfg)
    b = _itemsize(quant)
    mm = matmuls(cfg)
    out = {
        "layers_matmul": sum(b * n * i * o for n, i, o in mm.values()),
        "layers_small": 2 * b * s["L"] * s["D"]  # two norms a layer
        + s["n_ssm"] * (b * (s["K"] + 1) * s["conv"] + b * s["inner"] + 3 * 4 * s["H"]),
        "embed": b * s["V"] * s["D"],
        "final_norm": b * s["D"],
        "lm_head_t": b * s["V"] * s["D"],
    }
    out["total"] = sum(out.values())
    out["published_params"] = (out["total"] - out["lm_head_t"]) // b
    return out


def state_bytes_per_row(cfg: dict) -> int:
    """One sequence's recurrent state as stored: float32 states, and the
    conv windows in bfloat16."""
    s = sizes(cfg)
    return s["n_ssm"] * (4 * s["inner"] * s["N"] + 2 * (s["K"] - 1) * s["conv"])


def kv_bytes_per_token(cfg: dict) -> int:
    s = sizes(cfg)
    return 2 * s["n_attn"] * s["kv_heads"] * s["hd"] * 2


def attention_flops(cfg: dict, context: int) -> int:
    s = sizes(cfg)
    return 4 * context * s["heads"] * s["hd"] * s["n_attn"]


def ssm_flops_per_token(cfg: dict) -> int:
    """The recurrence alone: decay, outer product and readout of a
    [head_dim, N] state a head a layer, two FLOPs a multiply-add."""
    s = sizes(cfg)
    return s["n_ssm"] * 6 * s["inner"] * s["N"]


def _matmul_params(cfg: dict) -> int:
    return sum(n * i * o for n, i, o in matmuls(cfg).values())


def token_flops(cfg: dict, context: int) -> int:
    s = sizes(cfg)
    return (
        2 * (_matmul_params(cfg) + s["D"] * s["V"])
        + ssm_flops_per_token(cfg)
        + attention_flops(cfg, context)
    )


def work(kind: str, r, n_steps: float | None):
    cfg = r.config
    s = sizes(cfg)
    if kind == "decode":
        if not n_steps or not r.token_contexts:
            return None
        w = weight_bytes(cfg, r.quant)
        per_step = w["total"] - w["embed"]  # the head reads its transposed copy
        return {
            "bytes": int(round(n_steps)) * per_step
            + len(r.row_step_contexts) * 2 * state_bytes_per_row(cfg)
            + sum(r.row_step_contexts) * kv_bytes_per_token(cfg)
            + len(r.token_contexts) * (2 * s["D"] + kv_bytes_per_token(cfg)),
            "flops": sum(token_flops(cfg, c) for c in r.token_contexts),
        }
    if kind == "prefill":
        if not r.prefill_spans:
            return None
        flops, tokens = 0, 0
        for start, end in r.prefill_spans:
            n = max(0, end - start)
            tokens += n
            flops += n * (2 * _matmul_params(cfg) + ssm_flops_per_token(cfg))
            flops += attention_flops(cfg, 1) * ((start + 1 + end) * n // 2)
            if n:
                flops += 2 * s["D"] * s["V"]
        return {"flops": flops, "tokens": tokens, "bytes": 0}
    if kind == "paged_attention":
        if not r.row_step_contexts:
            return None
        return {
            "bytes": sum(r.row_step_contexts) * kv_bytes_per_token(cfg),
            "flops": sum(attention_flops(cfg, c) for c in r.token_contexts),
        }
    if kind == "ssm_state":
        if not r.row_step_contexts:
            return None
        return {
            "bytes": len(r.row_step_contexts) * 2 * state_bytes_per_row(cfg),
            "flops": len(r.token_contexts) * ssm_flops_per_token(cfg),
        }
    raise KeyError(f"unknown work {kind!r}")
