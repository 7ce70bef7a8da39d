"""Generic decoder-only transformer — pure-functional JAX, scan-over-layers.

TPU-first design decisions (why this is not a torch translation):

- **Pure functions over param pytrees.** ``init_params`` builds a pytree;
  ``forward`` is a pure function of (params, tokens, cache). Sharding is
  applied by annotating the pytree leaves (parallel/sharding.py) and jitting
  — the model code itself is mesh-oblivious.
- **Layer-stacked params + ``lax.scan``.** Every per-layer weight carries a
  leading ``n_layers`` dim and the layer loop is one ``scan`` — one traced
  layer body regardless of depth, which keeps XLA compile time flat from
  2-layer test configs to 80-layer 70B. What a Pallas kernel reads is
  NOT among the scan's sliced operands (``_split_layers``): a slice that
  XLA cannot fold into the kernel's operand is a copy of the layer every
  step, so the quantized matmul stacks (with the fused dequant-matmul
  on) and the routed expert stacks stay whole, and the kernels read
  layer ``l`` of them through a scalar-prefetched index, as the paged
  attention kernels read the pool. The scan is ROLLED for decode spans
  too: it used to unroll them by four so that XLA could start layer
  i+1's weight DMA under layer i's compute, but every kernel now streams
  its own weights, and on the chip the rolled verify step is as fast
  (17.07 against 17.28 ms at Mistral-7B, 14.35 against 14.47 at
  Qwen2-7B; PERF.md section 6, PR 33) in a quarter of the program.
- **Static shapes everywhere.** Batches are left-padded to a bucketed length
  (engine/generate.py); the KV cache is a dense preallocated
  ``[L, B, H_kv, S_max, D]`` buffer written with ``dynamic_update_slice``.
  Heads-major layout is a Mosaic requirement, not a style choice: the
  Pallas decode kernels stream ``[block_t, D]`` tiles, and TPU block
  shapes must keep the (sublane, lane) = (seq, head_dim) axes minor —
  a seq-major cache would need per-head blocks of sublane size 1, which
  the TPU lowering rejects. It also makes each tp shard's cache slice
  contiguous (heads axis is the sharded one).
  No data-dependent Python control flow — decode early-exit lives in a
  ``lax.while_loop`` in the generation loop, not here.
- **bf16 params/activations, f32 where it matters** (RMSNorm accumulation,
  attention softmax, final logits).

Family coverage (flags in models/config.py): Llama-3, Mistral (sliding
window), Gemma-2 (sandwich norms, softcaps, scaled/tied embeddings,
alternating window), Qwen-2 (QKV bias). GQA throughout.

Replaces (reference): nothing — the reference delegates all inference to
remote APIs (SURVEY §2: zero tensor math in the tree). This module is the
"native component" obligation of the TPU build (SURVEY §2, BASELINE north
star).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from adversarial_spec_tpu.models import moe
from adversarial_spec_tpu.models.config import ModelConfig
from adversarial_spec_tpu.ops import ssm as ssm_ops
from adversarial_spec_tpu.ops.quant import (
    StackedLayer,
    dequantize,
    div_const,
    matmul,
    quantize_int8,
)
from adversarial_spec_tpu.ops.rope import (
    apply_rope,
    apply_rope_interleaved,
    query_position_scale,
    rope_angles,
    yarn_angles,
)

Params = dict[str, Any]
Cache = dict[str, jnp.ndarray]


def init_params(
    rng: jax.Array,
    cfg: ModelConfig,
    dtype: jnp.dtype = jnp.bfloat16,
    transposed_head: bool = True,
    expert_quant: str = "",
) -> Params:
    """Random init with truncated-normal fan-in scaling (for synthetic
    checkpoints and tests; real weights come from engine/loader.py).

    ``expert_quant="int8"``: routed expert stacks are quantized piece by
    piece as they are made (``_expert_stack``), so that no full-precision
    stack ever exists; ``quantize_params`` leaves them as they are.

    ``transposed_head``: for tied-embedding configs, also store the
    ``[dim, vocab]`` transposed head copy (see the comment at the
    assignment below). Disable to save the V·D bytes on memory-tight
    fits; the einsum fallback over the embed table computes the same
    logits (exactly equivalent until ``quantize_params`` runs — the
    copy quantizes like any head matmul, the embed-table einsum stays
    full precision).
    """
    keys = iter(jax.random.split(rng, 16))

    def dense(key, shape, fan_in):
        w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
        return div_const(w, math.sqrt(fan_in)).astype(dtype)

    # ``L``: the layers of the periods' stack (all of them but a dense
    # prefix's, which has its own)
    L, D, F = cfg.n_layers - cfg.n_leading, cfg.dim, cfg.ffn_dim
    QD = cfg.n_heads * cfg.head_dim
    KD = cfg.n_kv_heads * cfg.head_dim

    def gqa_stack(keys, n: int) -> dict:
        # wq, wk, wv, wo; gated attention's wg after them
        stack = {
            "attn_norm": jnp.ones((n, D), dtype),
            "wq": dense(next(keys), (n, D, QD), D),
            "wk": dense(next(keys), (n, D, KD), D),
            "wv": dense(next(keys), (n, D, KD), D),
            "wo": dense(next(keys), (n, QD, D), QD),
        }
        if cfg.gated is not None:
            stack["wg"] = dense(next(keys), (n, D, QD), D)
            stack["q_head_norm"] = jnp.ones((n, cfg.head_dim), dtype)
            stack["k_head_norm"] = jnp.ones((n, cfg.head_dim), dtype)
        return stack

    def ffn_stack(keys, n: int, width: int) -> dict:
        stack = {
            "ffn_norm": jnp.ones((n, D), dtype),
            "w_gate": dense(next(keys), (n, D, width), D),
            "w_up": dense(next(keys), (n, D, width), D),
            "w_down": dense(next(keys), (n, width, D), width),
        }
        if cfg.post_norms:
            stack["post_attn_norm"] = jnp.ones((n, D), dtype)
            stack["post_ffn_norm"] = jnp.ones((n, D), dtype)
        return stack

    # THE RECIPE (perfbench/architectures/*.py follow it): sixteen splits
    # of the seed's key, taken in the order the weights are named below;
    # norms one, biases zero. A dense prefix's stack takes its own
    # sixteen from ``fold_in(rng, 1)``, in the same order.
    mixers: dict[str, dict] = {}
    if cfg.ssm is not None:
        # A period longer than one layer: what every layer has (the
        # mixer's norm, the FFN) stays in "layers", [n_layers, ...]; each
        # kind of mixer has a stack of its own depth under "mixers".
        # wq, wk, wv, wo; w_in, w_out, conv_w, the dt draw.
        n_kv, n_ssm = cfg.mixer_counts
        sp = cfg.ssm
        mixers["gqa"] = {
            "wq": dense(next(keys), (n_kv, D, QD), D),
            "wk": dense(next(keys), (n_kv, D, KD), D),
            "wv": dense(next(keys), (n_kv, D, KD), D),
            "wo": dense(next(keys), (n_kv, QD, D), QD),
        }
        mixers["ssm"] = {
            "w_in": dense(next(keys), (n_ssm, D, sp.in_dim), D),
            "w_out": dense(next(keys), (n_ssm, sp.inner_dim, D), sp.inner_dim),
            "conv_w": dense(
                next(keys), (n_ssm, sp.conv_width, sp.conv_dim), sp.conv_width
            ),
            "conv_b": jnp.zeros((n_ssm, sp.conv_dim), dtype),
            # dt = softplus(raw + dt_bias) starts log-uniform in
            # [1e-3, 1e-1]; A = -exp(A_log) = -(1..H), as the published
            # module initialises both.
            "dt_bias": _inverse_softplus(
                jnp.exp(
                    jax.random.uniform(
                        next(keys), (n_ssm, sp.n_heads), jnp.float32,
                        math.log(1e-3), math.log(1e-1),
                    )
                )
            ),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, sp.n_heads + 1, dtype=jnp.float32)),
                (n_ssm, sp.n_heads),
            ),
            "d_skip": jnp.ones((n_ssm, sp.n_heads), jnp.float32),
            "gate_norm": jnp.ones((n_ssm, sp.inner_dim), dtype),
        }
        layers: dict[str, jnp.ndarray] = {"attn_norm": jnp.ones((L, D), dtype)}
    elif cfg.latent is not None:
        # wq_a, wq_b, wkv_a, wkv_b, wo
        la = cfg.latent
        OD = cfg.n_heads * la.v_dim
        layers = {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq_a": dense(next(keys), (L, D, la.q_rank), D),
            "q_norm": jnp.ones((L, la.q_rank), dtype),
            "wq_b": dense(next(keys), (L, la.q_rank, QD), la.q_rank),
            "wkv_a": dense(next(keys), (L, D, la.kv_rank + la.rope_dim), D),
            "kv_norm": jnp.ones((L, la.kv_rank), dtype),
            "wkv_b": dense(
                next(keys),
                (L, la.kv_rank, cfg.n_heads * (la.nope_dim + la.v_dim)),
                la.kv_rank,
            ),
            "wo": dense(next(keys), (L, OD, D), OD),
        }
    else:
        layers = gqa_stack(keys, L)
    # The dense FFN; beside routed experts it is the shared expert.
    layers.update(ffn_stack(keys, L, F))
    if cfg.experts is not None:
        ex = cfg.experts
        if ex.n_shared != 1:
            raise NotImplementedError("one shared expert beside the routed")
        layers["w_router"] = dense(next(keys), (L, D, ex.n_routed), D)
        if ex.scoring == "sigmoid":
            layers["router_bias"] = ex.bias_std * jax.random.normal(
                next(keys), (L, ex.n_routed), jnp.float32
            )
        for name, shape, fan_in in (
            ("we_gate", (D, ex.expert_dim), D),
            ("we_up", (D, ex.expert_dim), D),
            ("we_down", (ex.expert_dim, D), ex.expert_dim),
        ):
            layers[name] = _expert_stack(
                next(keys), cfg, shape, fan_in, dtype, expert_quant
            )
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, QD), dtype)
        layers["bk"] = jnp.zeros((L, KD), dtype)
        layers["bv"] = jnp.zeros((L, KD), dtype)
    if cfg.norm_scale_plus_one:
        # Gemma stores RMSNorm scale as (1 + w); init w at zero.
        for name in ("attn_norm", "ffn_norm", "post_attn_norm", "post_ffn_norm"):
            if name in layers:
                layers[name] = jnp.zeros_like(layers[name])

    embed = dense(next(keys), (cfg.vocab_size, D), D)
    if cfg.embedding_multiplier != 1.0:
        # Rows at 1 / embedding_multiplier of the recipe's scale: the
        # multiplied embedding then enters the residual stream at the
        # scale a row of any other weight has. At the recipe's own scale
        # a tied head puts the current token's logit six deviations over
        # the rest and a greedy reply is one repeated token whatever the
        # mixers (and the state they keep) compute.
        embed = div_const(
            embed.astype(jnp.float32), cfg.embedding_multiplier
        ).astype(dtype)
    params: Params = {
        "embed": embed,
        "layers": layers,
        "final_norm": (
            jnp.zeros((D,), dtype)
            if cfg.norm_scale_plus_one
            else jnp.ones((D,), dtype)
        ),
    }
    if mixers:
        params["mixers"] = mixers
    if cfg.prefix is not None:
        lead_keys = iter(jax.random.split(jax.random.fold_in(rng, 1), 16))
        params["leading"] = {
            **gqa_stack(lead_keys, cfg.n_leading),
            **ffn_stack(lead_keys, cfg.n_leading, cfg.prefix.ffn_dim),
        }
    if not cfg.tied_embeddings:
        params["lm_head"] = dense(next(keys), (D, cfg.vocab_size), D)
    elif transposed_head:
        # Tied embeddings force the head matmul to contract the embed
        # table's MINOR axis ("bsd,vd->bsv") — measured ~2-5x slower than
        # a [D, V] layout on TPU (the MXU wants the contraction on the
        # major axis; XLA inserts a relayout of the full table). A decode
        # step re-reads the whole head every token, so the head is the
        # single largest per-step HBM item for small models. Materialize
        # a transposed copy once at init/load: +V·D bytes of HBM buys the
        # full-bandwidth matmul every step.
        params["lm_head_t"] = jnp.swapaxes(params["embed"], 0, 1)
    return params


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def _expert_stack(key, cfg: ModelConfig, shape, fan_in, dtype, quant: str):
    """The held experts' weights of every routed layer, [L, E_held, in,
    out] (``l`` counts from the first layer behind a dense prefix), made
    ONE LAYER AT A TIME: piece (layer l, expert e) draws from
    ``fold_in(key, l * n_routed + e)`` with e the expert's index among ALL
    routed experts, so every share of a deployment holds the values the
    uncut model has; a layer's pieces are drawn together and quantized
    (``quant="int8"``) before the next layer's are: the float32 in flight
    is one layer's held experts (1.07 GB at 32 x [4096, 2048]), whatever
    the stack weighs."""
    ex = cfg.experts
    ids = (
        jnp.arange(cfg.n_layers - cfg.n_leading)[:, None] * ex.n_routed
        + ex.first_held
        + jnp.arange(ex.n_held)[None, :]
    )

    def piece(i):
        w = jax.random.truncated_normal(
            jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32
        )
        w = div_const(w, math.sqrt(fan_in)).astype(dtype)
        return quantize_int8(w) if quant == "int8" else w

    return jax.lax.map(jax.vmap(piece), ids)


def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    dtype: jnp.dtype = jnp.bfloat16,
    device=None,
    kv_dtype: str = "",
) -> Cache:
    """``device`` may be a Sharding so the cache is born sharded (never
    materialized replicated on one chip).

    ``kv_dtype="int8"``: store K/V int8 with per-(token, head) symmetric
    scales (keys "ks"/"vs") — half the HBM bytes read per decoded token
    (decode is KV-bandwidth-bound at long contexts); dequant fuses into
    the attention matmuls. Presence of "ks" marks a quantized cache.
    """
    heads, k_dim, v_dim = cfg.kv_layout
    shape = (cfg.n_kv_layers, batch, heads, max_seq, k_dim)
    kw = {"device": device} if device is not None else {}
    if cfg.ssm is not None:
        if kv_dtype:
            raise NotImplementedError(
                f"int8 KV beside state-space layers ({cfg.attn_kind} + ssm) "
                "is not wired: the cache is stored in the model dtype"
            )
        return {
            "k": jnp.zeros(shape, dtype, **kw),
            "v": jnp.zeros(shape, dtype, **kw),
            **init_recurrent_state(cfg, batch, dtype, **kw),
        }
    if cfg.latent is not None:
        # "k": the shared rotated key (zero-padded to whole lanes), "v":
        # the compressed vector (values, and the keys' unrotated part).
        if kv_dtype:
            raise NotImplementedError("the latent cache is stored in the model dtype")
        return {
            "k": jnp.zeros(shape, dtype, **kw),
            "v": jnp.zeros(shape[:-1] + (v_dim,), dtype, **kw),
        }
    if kv_dtype == "int8":
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8, **kw),
            "v": jnp.zeros(shape, jnp.int8, **kw),
            "ks": jnp.zeros(sshape, jnp.float32, **kw),
            "vs": jnp.zeros(sshape, jnp.float32, **kw),
        }
    return {
        "k": jnp.zeros(shape, dtype, **kw),
        "v": jnp.zeros(shape, dtype, **kw),
    }


# The leaves of a cache or a pool that are a recurrent state a row, not
# keys and values a token.
STATE_LEAVES = ("ssm", "conv")


def init_recurrent_state(cfg: ModelConfig, rows: int, dtype, **kw) -> Cache:
    """What ``rows`` sequences keep of the state-space layers: "ssm", the
    recurrent state, float32 and transposed (ops/ssm.py), and "conv", the
    conv's last inputs in the model dtype. Zeros are a sequence's start."""
    sp = cfg.ssm
    n_ssm = cfg.mixer_counts[1]
    return {
        "ssm": jnp.zeros(
            (n_ssm, rows, sp.state_dim, sp.inner_dim), jnp.float32, **kw
        ),
        "conv": jnp.zeros(
            (n_ssm, rows, sp.conv_width - 1, sp.conv_dim), dtype, **kw
        ),
    }


def _quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(token, head) symmetric int8 over the feature axis."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale


def rms_norm(
    x: jnp.ndarray, weight: jnp.ndarray, eps: float, plus_one: bool
) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    scale = weight.astype(jnp.float32)
    if plus_one:
        scale = scale + 1.0
    return (norm * scale).astype(x.dtype)


def _softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap


def _activation(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def attention(
    q: jnp.ndarray,  # [B, S, Hq, D]
    k: jnp.ndarray,  # [B, Hkv, T, D] — heads-major (cache layout)
    v: jnp.ndarray,  # [B, Hkv, T, D]
    mask: jnp.ndarray,  # [B, S, T] bool — True = attend
    attn_softcap: float = 0.0,
    scale: float | None = None,
) -> jnp.ndarray:
    """Masked GQA attention, f32 softmax. Returns [B, S, Hq, Dv] (values
    may be narrower or wider than keys: latent attention's are)."""
    B, S, Hq, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, Hkv, g, D)
    # [B, Hkv, g, S, T]
    logits = jnp.einsum(
        "bshgd,bhtd->bhgst", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    if attn_softcap > 0.0:
        logits = _softcap(logits, attn_softcap)
    # Masked softmax with the framework-wide contract that FULLY-masked
    # rows (left-pad query slots) produce EXACT zeros — matching the
    # Pallas kernels and the ring (which early-outs of windowed hops, so
    # pad garbage may not even see the same key set twice). -inf masking
    # with a guarded max keeps those rows NaN-free.
    logits = jnp.where(mask[:, None, None, :, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m)
    probs = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum(
        "bhgst,bhtd->bshgd", probs.astype(v.dtype), v
    )
    return out.reshape(B, S, Hq, v.shape[-1])


def _project_qkv(
    lp, cfg: ModelConfig, h, B: int, S: int, cos, sin, mm=matmul, kind="gqa"
):
    """Shared QKV projection + bias + head reshape + RoPE (dense & paged).
    Gated attention norms each head of q and k first, and a layer of
    ``kind`` "swa" rotates them where one of "nope" does not; "gqa"
    follows the model's ``rope``.

    ``mm`` is the matmul implementation — the plain dispatch by default,
    or a partial carrying ``use_pallas``/``interpret`` when the caller
    enables the fused dequant-matmul kernels (ops/pallas_quant.py)."""
    q = mm(h, lp["wq"])
    k = mm(h, lp["wk"])
    v = mm(h, lp["wv"])
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.gated is not None:
        q = rms_norm(q, lp["q_head_norm"], cfg.rms_eps, False)
        k = rms_norm(k, lp["k_head_norm"], cfg.rms_eps, False)
    if cfg.rope if kind == "gqa" else kind == "swa":
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    pad = cfg.kv_layout[1] - cfg.head_dim
    if pad:
        # cached zero-padded to whole lanes (``kv_layout``): zeros add
        # nothing to a score, and the output's are cut off again
        q, k, v = (
            jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, pad))) for t in (q, k, v)
        )
    return q, k, v


def _attn_gate(lp, cfg: ModelConfig, h, out, mm):
    """Gated attention: the heads' output [B, S, Hq, D] times
    sigmoid(h W_g), elementwise, before ``W_o`` (``h``: the block's
    normed input). Nothing for any other attention."""
    if cfg.gated is None:
        return out
    with jax.named_scope("attn.gate"):
        gate = jax.nn.sigmoid(mm(h, lp["wg"]).astype(jnp.float32))
        return (out.astype(jnp.float32) * gate.reshape(out.shape)).astype(
            out.dtype
        )


def _attn_scope(kind: str):
    """The device-side name of a gated layer's attention, by its kind."""
    if kind == "gqa":
        return contextlib.nullcontext()
    return jax.named_scope({"swa": "attn.window", "nope": "attn.full"}[kind])


def _rope_tables(cfg: ModelConfig, positions):
    """cos/sin for the layer kind's rotated features: the whole head under
    plain or llama-3 frequencies, or latent attention's ``rope_dim`` under
    YaRN."""
    la = cfg.latent
    if not cfg.rope:
        return None, None
    if la is None:
        return rope_angles(
            positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
        )
    if la.yarn is None:
        return rope_angles(positions, la.rope_dim, cfg.rope_theta)
    return yarn_angles(positions, la.rope_dim, cfg.rope_theta, la.yarn)


def _project_latent(lp, cfg: ModelConfig, h, B, S, positions, cos, sin, mm):
    """Latent attention's projections. Returns queries split into their
    unrotated and rotated parts ([B, S, H, nope], [B, S, H, rope]), the
    compressed vector c_kv [B, S, kv_rank] (normed) and the one rotated
    key all heads share [B, S, rope]: the last two are what is cached."""
    la = cfg.latent
    c_q = rms_norm(mm(h, lp["wq_a"]), lp["q_norm"], cfg.rms_eps, False)
    q = mm(c_q, lp["wq_b"]).reshape(
        B, S, cfg.n_heads, la.nope_dim + la.rope_dim
    )
    kv = mm(h, lp["wkv_a"])
    c_kv = rms_norm(kv[..., : la.kv_rank], lp["kv_norm"], cfg.rms_eps, False)
    k_r = apply_rope_interleaved(
        kv[..., la.kv_rank :][:, :, None, :], cos, sin
    )[:, :, 0]
    q_nope = q[..., : la.nope_dim]
    q_rope = apply_rope_interleaved(q[..., la.nope_dim :], cos, sin)
    if la.yarn is not None and la.yarn.query_scaling_beta:
        qs = query_position_scale(positions, la.yarn)[..., None, None]
        q_nope = (q_nope * qs).astype(q.dtype)
        q_rope = (q_rope * qs).astype(q.dtype)
    return q_nope, q_rope, c_kv, k_r


def _latent_up(lp, cfg: ModelConfig, dtype):
    """W_kvb split per head: W_UK [kv_rank, H, nope] (c_kv -> a head's
    unrotated keys) and W_UV [kv_rank, H, v] (c_kv -> its values)."""
    la = cfg.latent
    w = dequantize(lp["wkv_b"], dtype).reshape(
        la.kv_rank, cfg.n_heads, la.nope_dim + la.v_dim
    )
    return w[..., : la.nope_dim], w[..., la.nope_dim :]


def _absorbed_attention(cfg, q_nope, q_rope, r_dense, c_dense, mask, w_uk, w_uv):
    """Latent attention with W_UK folded into the queries and W_UV into
    the output, over dense [B, 1, T, *] rotated keys (zero-padded to
    ``rope_pad``) and compressed vectors: one multi-query attention whose
    keys are [c_kv | k_r] and whose values are c_kv. Returns [B, S, H, v]."""
    la = cfg.latent
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
    q_rot = jnp.pad(
        q_rope, ((0, 0), (0, 0), (0, 0), (0, la.rope_pad - la.rope_dim))
    )
    o_lat = attention(
        jnp.concatenate([q_lat, q_rot], axis=-1),
        jnp.concatenate([c_dense, r_dense], axis=-1),
        c_dense,
        mask,
        scale=cfg.attn_scale,
    )
    return jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)


def _latent_cache_rows(cfg: ModelConfig, c_kv, k_r):
    """(k, v) rows of the latent cache, [B, S, 1, *]: the rotated key
    zero-padded to whole lanes, and the compressed vector."""
    la = cfg.latent
    k = jnp.pad(k_r, ((0, 0), (0, 0), (0, la.rope_pad - la.rope_dim)))
    return k[:, :, None, :], c_kv[:, :, None, :]


def _ssm_inputs(sp_w, cfg: ModelConfig, h, window, valid, mm):
    """A state-space layer's projections and conv over a span ``h``
    [B, W, dim]: (gate z, x [B, W, H, P], B and C [B, W, N], dt
    [B, W, H], all float32 but z; the conv's inputs in order, window
    first). ``valid`` [B, W] marks the positions that count: the others'
    conv inputs are zeros and their dt is 0, so the state passes over
    them (pads to the left of a row's first token)."""
    sp = cfg.ssm
    if sp.n_groups != 1:
        raise NotImplementedError("one group of B and C for all heads")
    B_, W = h.shape[:2]
    zxd = mm(h, sp_w["w_in"])
    z = zxd[..., : sp.inner_dim]
    raw = zxd[..., sp.inner_dim : sp.inner_dim + sp.conv_dim]
    dt = jax.nn.softplus(
        zxd[..., sp.inner_dim + sp.conv_dim :].astype(jnp.float32)
        + sp_w["dt_bias"]
    )
    if valid is not None:
        raw = jnp.where(valid[..., None], raw, 0)
        dt = jnp.where(valid[..., None], dt, 0.0)
    with jax.named_scope("ssm.conv"):
        xbc, seq = ssm_ops.causal_conv(
            raw, window, sp_w["conv_w"], sp_w["conv_b"]
        )
    xbc = xbc.astype(jnp.float32)
    x = xbc[..., : sp.inner_dim].reshape(B_, W, sp.n_heads, sp.head_dim)
    b_in = xbc[..., sp.inner_dim : sp.inner_dim + sp.state_dim]
    c_in = xbc[..., sp.inner_dim + sp.state_dim :]
    return z, x, b_in, c_in, dt, seq


def _ssm_gated(sp_w, cfg: ModelConfig, y, z, dtype):
    """rmsnorm(y * silu(z)) * w over all inner channels (one group), in
    the model dtype: what the output projection takes."""
    with jax.named_scope("ssm.gate_norm"):
        g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
        return rms_norm(g, sp_w["gate_norm"], cfg.rms_eps, False).astype(dtype)


def _ssm_chunked(lp, cfg: ModelConfig, h, window, state, valid, mm):
    """A state-space mixer over a span in the chunked form, from a dense
    state [B, N, HP] and conv window: (the mixer's output before its
    projection, the state after the span's valid positions, the conv's
    inputs in order). A prefill chunk and an admission's wide delta."""
    with jax.named_scope("ssm"):
        z, xs, b_in, c_in, dt, seq = _ssm_inputs(lp, cfg, h, window, valid, mm)
        with jax.named_scope("ssm.scan"):
            y, state = ssm_ops.chunked_scan(
                xs, b_in, c_in, dt, -jnp.exp(lp["A_log"]), lp["d_skip"],
                state, cfg.ssm.chunk,
            )
        return _ssm_gated(lp, cfg, y, z, h.dtype), state, seq


def _layer_of(stack: dict, index) -> dict:
    """Row ``index`` (traced) of every leaf of a stack of layers (a
    quantized weight's values and scales alike)."""
    return jax.tree.map(lambda a: a[index], stack)


def _commit_layer(pool, cfg, li, rows, saved, n_keep, use_pallas, interpret):
    """The pool with state-space layer ``li``'s state and conv window of
    ``rows`` advanced over the first ``n_keep`` [B] positions of a span
    whose inputs are ``saved``: the one way a span reaches the state."""
    sp = cfg.ssm
    B_, W = saved["dt"].shape[:2]
    # (named inside: a loop's body is lowered apart from its caller's scopes)
    with jax.named_scope("ssm.commit"):
        decay, xs = ssm_ops.commit_terms(
            saved["x"], saved["dt"], saved["cum"], n_keep
        )
        xs = xs.reshape(B_, W, sp.inner_dim)
        decay = jnp.repeat(decay, sp.head_dim, axis=1)
        if use_pallas:
            state = ssm_ops.ssm_span_update(
                pool["ssm"], li, rows, saved["b"], xs, decay, interpret=interpret
            )
        else:
            state = pool["ssm"].at[li, rows].set(
                ssm_ops.state_update(pool["ssm"][li, rows], saved["b"], xs, decay)
            )
        window = ssm_ops.conv_window(saved["seq"], n_keep, sp.conv_width - 1)
    return {
        **pool,
        "ssm": state,
        "conv": pool["conv"].at[li, rows].set(window.astype(pool["conv"].dtype)),
    }


def commit_span(
    cfg: ModelConfig, pool: Cache, n_keep, *, rows=None,
    use_pallas: bool = False, pallas_interpret: bool = False,
) -> Cache:
    """Close a span that ``forward_paged_decode`` left open (``state_keep``
    None: a verify step, whose acceptance is sampled from the span's own
    logits): every state-space layer's state and conv window of ``rows``
    become those after the span's first ``n_keep`` [B] positions, and
    nothing of a later position stays. Returns the pool without the
    span's saved inputs."""
    pool = dict(pool)
    saved = pool.pop("span")
    n_keep = n_keep.astype(jnp.int32)
    if rows is None:
        rows = jnp.arange(n_keep.shape[0], dtype=jnp.int32)

    def one(li, pool):
        return _commit_layer(
            pool, cfg, li, rows, _layer_of(saved, li), n_keep,
            use_pallas, pallas_interpret,
        )

    return jax.lax.fori_loop(0, cfg.mixer_counts[1], one, pool)


def _attn_out_and_ffn(
    x, attn_out, lp, cfg: ModelConfig, B: int, S: int, psum_axis=None,
    mm=matmul, routed=None,
):
    """Shared post-attention projection, residuals, and FFN block.

    ``psum_axis``: when running inside a manual-collective region
    (shard_map) with Megatron-style TP, the row-parallel matmuls (wo,
    w_down) produce partial sums that must all-reduce over the tp axis —
    BEFORE any post-norm reads them (norms of partial sums are wrong).
    Under GSPMD (jit) leave it None; the compiler inserts the psums.

    ``mm``: matmul implementation (see ``_project_qkv``).

    ``routed``: the layer's FFN kind is "routed": a callable h -> the
    held experts' part of the result (models/moe.py), added to the dense
    FFN below, which is then the shared expert.
    """
    with jax.named_scope("attn"):
        out = mm(attn_out.reshape(B, S, -1), lp["wo"])
        if psum_axis is not None:
            out = jax.lax.psum(out, psum_axis)
        if cfg.post_norms:
            out = rms_norm(
                out,
                lp["post_attn_norm"],
                cfg.rms_eps,
                cfg.norm_scale_plus_one,
            )
        x = _residual(cfg, x, out)

    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["ffn_norm"], cfg.rms_eps, cfg.norm_scale_plus_one)
        with (
            jax.named_scope("moe.shared")
            if routed is not None
            else contextlib.nullcontext()
        ):
            ff = _activation(mm(h, lp["w_gate"]), cfg.activation) * mm(
                h, lp["w_up"]
            )
            ff = mm(ff, lp["w_down"])
        if routed is not None:
            ff = ff + routed(h)
        if psum_axis is not None:
            ff = jax.lax.psum(ff, psum_axis)
        if cfg.post_norms:
            ff = rms_norm(
                ff, lp["post_ffn_norm"], cfg.rms_eps, cfg.norm_scale_plus_one
            )
        return _residual(cfg, x, ff)


def _residual(cfg: ModelConfig, x, out):
    if cfg.residual_multiplier != 1.0:
        out = (out.astype(jnp.float32) * cfg.residual_multiplier).astype(
            out.dtype
        )
    return x + out


def _embed(params: Params, cfg: ModelConfig, tokens):
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)
    return x


def _layer_window_start(
    cfg: ModelConfig, layer_id, base_start, q_pos, kind: str = "gqa"
):
    """Per-layer valid-window start: sliding window tightens it (on the
    windowed layers only: a gated layer's ``kind`` says which it is, an
    alternating-pattern family's layer index)."""
    if kind != "gqa":
        if kind == "nope":
            return base_start
        return jnp.maximum(base_start, q_pos - cfg.gated.window + 1)
    if cfg.sliding_window <= 0:
        return base_start
    win_start = jnp.maximum(base_start, q_pos - cfg.sliding_window + 1)
    if cfg.sliding_window_pattern > 1:
        use_window = (layer_id % cfg.sliding_window_pattern) == 0
        return jnp.where(use_window, win_start, base_start)
    return win_start


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] rope positions (0 at each row's start)
    cache: Cache,
    cache_index: jnp.ndarray,  # scalar or [B]: slot where this chunk's KV goes
    kv_valid: jnp.ndarray,  # [B, T] bool: slots holding real tokens
    *,
    use_pallas_decode: bool = False,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    lm_head_last_only: bool = False,
    mesh=None,
) -> tuple[jnp.ndarray, Cache]:
    """One forward pass over a chunk (prefill: S=chunk, decode: S=1).

    The caller maintains left-padded rows so every row writes its KV at the
    same scalar ``cache_index`` (static-shape dynamic_update_slice), and
    passes ``kv_valid`` marking which cache slots are real (pads
    excluded). A vector ``cache_index`` ([B]) writes each row's KV at its
    own slot (vmapped update) — the layout speculative decoding needs once
    rows accept different draft lengths and desynchronize.
    Returns (logits [B, S, vocab] f32, updated cache).

    ``use_pallas_decode`` routes S==1 attention through the fused Pallas
    flash-decoding kernel (ops/pallas_decode.py). On a multi-device
    ``mesh`` the kernel runs under shard_map — batch over dp, KV heads
    over tp (ops/pallas_decode.py:decode_attention_tp); callers gate on
    ``tp_decode_supported``. ``use_pallas_matmul`` routes quantized
    projection/MLP/head weights through the fused dequant-matmul kernels
    (ops/pallas_quant.py) — single-device only (a pallas_call cannot be
    GSPMD-partitioned, and the matmul weights shard under jit), so
    callers gate on ``mesh is None or mesh.size == 1``.
    """
    B, S = tokens.shape
    fused_mm = use_pallas_matmul and (mesh is None or mesh.size == 1)
    mm = (
        functools.partial(
            matmul, use_pallas=True, interpret=pallas_interpret
        )
        if fused_mm
        else matmul
    )
    T = cache["k"].shape[3]  # [L, B, Hkv, T, D]
    latent = cfg.latent is not None
    # The dense-cache decode kernels read per-head keys and values; a
    # latent cache has neither (the batcher's decode is the paged one).
    use_pallas_decode = use_pallas_decode and not latent
    pallas_decode = use_pallas_decode and S == 1
    # Short multi-query spans (speculative verification: S = γ+1) run
    # the multi-query kernel — one pass over the KV cache for the whole
    # span, int8 tiles included (scale tiles stream like the
    # single-query kernel's). Single-device.
    pallas_mq = (
        use_pallas_decode
        and 1 < S <= 16
        and (mesh is None or mesh.size == 1)
    )

    x = _embed(params, cfg, tokens)

    cos, sin = _rope_tables(cfg, positions)

    # Masks shared by all layers. Slot j is visible to in-chunk query i iff
    # it holds a real token and j <= cache_index + i (causality in slot
    # space — valid because rows are left-padded so slot order = position
    # order). Reshape unifies scalar ([1,1,1]) and per-row ([B,1,1])
    # cache_index under one broadcast.
    slot_ids = jnp.arange(T)[None, None, :]  # [1, 1, T]
    q_slot = (
        jnp.reshape(cache_index, (-1, 1, 1))
        + jnp.arange(S)[None, :, None]
    )  # [1|B, S, 1]
    causal = slot_ids <= q_slot
    base_mask = kv_valid[:, None, :] & causal  # [B, S, T]
    window = cfg.gated.window if cfg.gated else cfg.sliding_window
    if window > 0:
        window_mask = base_mask & (slot_ids > q_slot - window)
    else:
        window_mask = base_mask

    layer_ids = jnp.arange(cfg.n_layers)

    if pallas_decode or pallas_mq:
        # Per-row valid window [start, end) for the fused kernels; the
        # sliding-window start tightening happens per layer below.
        pallas_start = jnp.argmax(kv_valid.astype(jnp.int32), axis=1).astype(
            jnp.int32
        )
        pallas_end = jnp.full((B,), 0, jnp.int32) + cache_index + 1
    if pallas_mq:
        # Per-query positions: query j of row b sits at slot
        # cache_index_b + j, sees [start_bj, cache_index_b + j + 1).
        mq_q_pos = jnp.broadcast_to(
            jnp.reshape(cache_index, (-1, 1))
            + jnp.arange(S, dtype=jnp.int32),
            (B, S),
        )

    quant_kv = "ks" in cache  # int8 K/V with per-(token, head) scales

    vector_index = jnp.ndim(cache_index) > 0

    def _write_and_read_kv(cache_l: Cache, k, v, x_dtype):
        """Store this chunk's K/V into the layer's cache slice and return
        (updated slice, attention-readable K, V). One site owns both the
        plain and int8 layouts, and both index modes (shared scalar slot
        vs per-row slots).

        Fresh k/v arrive token-major [B, S, Hkv, D|1] and are transposed
        to the heads-major cache layout [B, Hkv, S, D|1] here — the chunk
        transpose is O(S·H·D), negligible next to the cache read."""
        if vector_index:
            # Per-row slots: buf [Hkv, T, D], val [Hkv, S, D], seq at dim 1.
            upd = lambda buf, val: jax.vmap(  # noqa: E731
                lambda b, v_, i: jax.lax.dynamic_update_slice(
                    b, v_, (0, i) + (0,) * (b.ndim - 2)
                )
            )(buf, val, cache_index)
        else:
            upd = lambda buf, val: jax.lax.dynamic_update_slice(  # noqa: E731
                buf, val, (0, 0, cache_index, 0)
            )
        k = jnp.swapaxes(k, 1, 2)  # [B, Hkv, S, D]
        v = jnp.swapaxes(v, 1, 2)
        if quant_kv:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            out = {
                "k": upd(cache_l["k"], kq),
                "v": upd(cache_l["v"], vq),
                "ks": upd(cache_l["ks"], ks),
                "vs": upd(cache_l["vs"], vs),
            }
            # Dequant feeds the attention matmuls directly; XLA fuses the
            # elementwise producer into the dot's operand read.
            k_read = (out["k"].astype(jnp.float32) * out["ks"]).astype(x_dtype)
            v_read = (out["v"].astype(jnp.float32) * out["vs"]).astype(x_dtype)
            return out, k_read, v_read
        out = {
            "k": upd(cache_l["k"], k.astype(cache_l["k"].dtype)),
            "v": upd(cache_l["v"], v.astype(cache_l["v"].dtype)),
        }
        return out, out["k"], out["v"]

    scanned_layers, indexed, experts = _split_layers(
        params["layers"], fused_mm, B * S, x.dtype
    )

    def layer_body(x, scanned):
        lp, layer_id, cache_l = scanned
        lp = _layer_weights(lp, indexed, layer_id)
        with jax.named_scope("attn"):
            out, cache_l = (latent_block if latent else attn_block)(
                x, (lp, layer_id, cache_l)
            )
        routed, _ = _routed_ffn_of(
            cfg, lp, experts, layer_id, fused_mm, pallas_interpret
        )
        x = _attn_out_and_ffn(x, out, lp, cfg, B, S, mm=mm, routed=routed)
        return x, cache_l

    def latent_block(x, scanned):
        # The EXPANDED form: the cache takes only c_kv and the shared
        # rotated key, and every cached position's per-head keys and
        # values are rebuilt from its compressed vector (c_kv W_kvb), so
        # the chunk attends as plain multi-head attention. The same
        # mathematics as forward_paged_decode's absorbed form
        # (tests/test_latent.py).
        lp, layer_id, cache_l = scanned
        la = cfg.latent
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, False)
        with jax.named_scope("attn.latent"):
            q_nope, q_rope, c_kv, k_r = _project_latent(
                lp, cfg, h, B, S, positions, cos, sin, mm
            )
            cache_l, k_read, v_read = _write_and_read_kv(
                cache_l, *_latent_cache_rows(cfg, c_kv, k_r), x.dtype
            )
            w_uk, w_uv = _latent_up(lp, cfg, x.dtype)
            c_all = v_read[:, 0]  # [B, T, kv_rank]
            k_nope = jnp.einsum("btr,rhn->bhtn", c_all, w_uk)
            v_all = jnp.einsum("btr,rhv->bhtv", c_all, w_uv)
            k_rope = jnp.broadcast_to(
                k_read[:, :, :, : la.rope_dim],
                (B, cfg.n_heads, T, la.rope_dim),
            )
            out = attention(
                jnp.concatenate([q_nope, q_rope], axis=-1),
                jnp.concatenate([k_nope, k_rope], axis=-1),
                v_all,
                base_mask,
                scale=cfg.attn_scale,
            )
        return out, cache_l

    def attn_block(x, scanned, kind="gqa"):
        lp, layer_id, cache_l = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, cfg.norm_scale_plus_one)
        with _attn_scope(kind):
            out, cache_l = attend(h, lp, layer_id, cache_l, kind)
        return _attn_gate(lp, cfg, h, out, mm), cache_l

    def attend(h, lp, layer_id, cache_l, kind):
        q, k, v = _project_qkv(lp, cfg, h, B, S, cos, sin, mm=mm, kind=kind)
        cache_l, k_read, v_read = _write_and_read_kv(cache_l, k, v, h.dtype)

        if pallas_decode:
            from adversarial_spec_tpu.ops.pallas_decode import (
                decode_attention,
                decode_attention_tp,
            )

            start = _layer_window_start(
                cfg, layer_id, pallas_start, cache_index, kind
            )
            bounds = jnp.stack([start, pallas_end], axis=1)
            if quant_kv:
                # Hand the kernel the raw int8 tiles + scale tiles; the
                # dequantized k_read/v_read above are dead code here and
                # XLA drops them — HBM traffic stays at int8 bytes.
                k_in, v_in = cache_l["k"], cache_l["v"]
                qkw = dict(
                    k_scale=cache_l["ks"], v_scale=cache_l["vs"]
                )
            else:
                k_in, v_in, qkw = k_read, v_read, {}
            if mesh is not None and mesh.size > 1:
                out = decode_attention_tp(
                    q[:, 0],
                    k_in,
                    v_in,
                    bounds,
                    mesh,
                    attn_softcap=cfg.attn_softcap,
                    scale=cfg.attn_scale,
                    interpret=pallas_interpret,
                    **qkw,
                )[:, None]
            else:
                out = decode_attention(
                    q[:, 0],
                    k_in,
                    v_in,
                    bounds,
                    attn_softcap=cfg.attn_softcap,
                    scale=cfg.attn_scale,
                    interpret=pallas_interpret,
                    **qkw,
                )[:, None]
        elif pallas_mq:
            from adversarial_spec_tpu.ops.pallas_decode import (
                decode_attention_mq,
            )

            starts_l = _layer_window_start(
                cfg, layer_id, pallas_start[:, None], mq_q_pos, kind
            )
            if quant_kv:
                # Raw int8 tiles + scale tiles; the dequantized
                # k_read/v_read are dead here (XLA drops them) so HBM
                # traffic stays at int8 bytes.
                mq_k, mq_v = cache_l["k"], cache_l["v"]
                mq_kw = dict(
                    k_scale=cache_l["ks"], v_scale=cache_l["vs"]
                )
            else:
                mq_k, mq_v, mq_kw = k_read, v_read, {}
            out = decode_attention_mq(
                q,
                mq_k,
                mq_v,
                starts_l,
                mq_q_pos + 1,
                attn_softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
                interpret=pallas_interpret,
                **mq_kw,
            )
        else:
            if kind != "gqa":
                mask = window_mask if kind == "swa" else base_mask
            elif cfg.sliding_window > 0 and cfg.sliding_window_pattern > 1:
                # Gemma-2: alternate windowed / global layers.
                use_window = (layer_id % cfg.sliding_window_pattern) == 0
                mask = jnp.where(use_window, window_mask, base_mask)
            elif cfg.sliding_window > 0:
                mask = window_mask
            else:
                mask = base_mask

            out = attention(
                q,
                k_read,
                v_read,
                mask,
                attn_softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
            )
        return out, cache_l

    # The cache dict scans as a pytree: every leaf carries a leading
    # n_layers axis, so one scan serves both cache layouts. The scan is
    # rolled for every span (see the module docstring). "layers" names
    # the scan itself: the slicing of each layer's weights out of the
    # stacked arrays has no other owner.
    if cfg.gated is not None:
        return _forward_segments(
            params, cfg, x, cache, attn_block, mm, lm_head_last_only,
            fused_mm, pallas_interpret,
        )
    if cfg.ssm is not None:
        return _forward_periods(
            params, cfg, x, cache, attn_block, mm, lm_head_last_only,
            # a chunk position counts iff its slot holds a real token
            valid=jnp.take_along_axis(
                kv_valid,
                jnp.minimum(jnp.broadcast_to(q_slot[..., 0], (B, S)), T - 1),
                axis=1,
            ),
        )
    with jax.named_scope("layers"):
        x, new_cache = jax.lax.scan(
            layer_body,
            x,
            (scanned_layers, layer_ids, cache),
        )

    logits = _lm_head_logits(params, cfg, x, lm_head_last_only, mm=mm)
    return logits, new_cache


class _Run(NamedTuple):
    """Layers that one scan covers: ``reps`` repetitions of ``kinds``."""

    stack: str  # the weight stack's name in the params
    row0: int  # the run's first row of that stack
    layer0: int  # the run's first layer of the cache or pool
    kinds: tuple[str, ...]  # the mixer kinds of one repetition, in order
    reps: int


def _segments(cfg: ModelConfig) -> list[_Run]:
    """A gated-attention stack as the runs of layers that one scan each
    covers. The body of a run's scan is its kinds' layers in order, each
    kind static (what it rotates, what it sees, its name in the trace);
    the weights are read out of their whole stacks by a traced row. The
    prefix is one run of its own layers; the periods are one run of whole
    periods and, where the depth cuts the last one short, one more of
    what is left of it."""
    lead = cfg.n_leading
    kinds = tuple(m for m, _ in cfg.layer_kinds)
    whole, rest = divmod(cfg.n_layers - lead, cfg.period)
    runs = []
    if lead:
        runs.append(_Run("leading", 0, 0, cfg.prefix.mixers, 1))
    if whole:
        runs.append(_Run("layers", 0, lead, kinds, whole))
    if rest:
        at = whole * cfg.period
        runs.append(_Run("layers", at, lead + at, kinds[:rest], 1))
    return runs


def _run_layers(params, cfg, run, fused_mm, interpret, rows: int, dtype, p):
    """The layers of repetition ``p`` (traced) of ``run``, in order, each
    as (kind, the pool's layer, its weights as its body reads them, its
    routed FFN or None, the list that leaves its routing in or None)."""
    scanned, indexed, experts = _split_layers(
        params[run.stack], fused_mm, rows, dtype
    )
    out = []
    for j, kind in enumerate(run.kinds):
        row = run.row0 + p * len(run.kinds) + j
        lp = _layer_weights(_layer_of(scanned, row), indexed, row)
        routed, routing = (
            _routed_ffn_of(cfg, lp, experts, row, fused_mm, interpret)
            if run.stack == "layers"
            else (None, None)
        )
        out.append((kind, run.layer0 - run.row0 + row, lp, routed, routing))
    return out


def _forward_segments(
    params, cfg, x, cache, attn_block, mm, lm_head_last_only, fused_mm,
    interpret,
):
    """``forward``'s layer scans for gated attention behind a dense
    prefix (``_segments``). A run's rows of the cache scan with it."""
    B, S = x.shape[:2]
    parts = []
    for run in _segments(cfg):
        layer0, n, reps = run.layer0, len(run.kinds), run.reps

        def body(x, scanned, run=run):
            p, cache_p = scanned
            new = []
            for j, (kind, layer, lp, routed, _) in enumerate(
                _run_layers(params, cfg, run, fused_mm, interpret, B * S, x.dtype, p)
            ):
                with jax.named_scope("attn"):
                    out, cache_l = attn_block(
                        x, (lp, layer, {k: v[j] for k, v in cache_p.items()}),
                        kind,
                    )
                x = _attn_out_and_ffn(x, out, lp, cfg, B, S, mm=mm, routed=routed)
                new.append(cache_l)
            return x, {k: jnp.stack([c[k] for c in new]) for k in cache_p}

        with jax.named_scope("layers"):
            x, new = jax.lax.scan(
                body,
                x,
                (
                    jnp.arange(reps),
                    {
                        k: v[layer0 : layer0 + n * reps].reshape(
                            (reps, n) + v.shape[1:]
                        )
                        for k, v in cache.items()
                    },
                ),
            )
        parts.append({k: v.reshape((-1,) + v.shape[2:]) for k, v in new.items()})
    new_cache = {k: jnp.concatenate([p[k] for p in parts]) for k in cache}
    logits = _lm_head_logits(params, cfg, x, lm_head_last_only, mm=mm)
    return logits, new_cache


def _period_layers(cfg: ModelConfig, params: Params, p):
    """The layers of period ``p`` (traced) in order, each as (mixer kind,
    its row in its kind's stacks, the layer's weights): what all layers
    have from ``params["layers"]``, the mixer's own under the same dict.
    Every weight is read out of its whole stack by a traced index, one
    layer at a time: no block of layers is ever sliced out together."""
    out = []
    for j in range(cfg.period):
        kind, idx, count = cfg.mixer_slot(j)
        row = p * count + idx
        lp = _layer_of(params["layers"], p * cfg.period + j)
        lp.update(_layer_of(params["mixers"]["ssm" if kind == "ssm" else "gqa"], row))
        out.append((kind, row, lp))
    return out


def _forward_periods(
    params, cfg, x, cache, attn_block, mm, lm_head_last_only, valid
):
    """``forward``'s layer scan for a period longer than one layer: one
    step of the scan is one period, its layers in order. The cache's
    leaves scan by period ([periods, layers of the kind a period, ...])."""
    B, S = x.shape[:2]
    n_periods = cfg.n_layers // cfg.period

    def by_period(v):
        return v.reshape((n_periods, v.shape[0] // n_periods) + v.shape[1:])

    def period_body(x, scanned):
        p, cache_p = scanned
        kv = {k: v for k, v in cache_p.items() if k not in STATE_LEAVES}
        new_kv, new_ssm, new_conv = [], [], []
        for kind, row, lp in _period_layers(cfg, params, p):
            if kind == "ssm":
                i = len(new_ssm)
                h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, False)
                out, state, seq = _ssm_chunked(
                    lp, cfg, h, cache_p["conv"][i], cache_p["ssm"][i], valid, mm
                )
                new_ssm.append(state)
                new_conv.append(seq[:, S:].astype(cache_p["conv"].dtype))
                lp = {**lp, "wo": lp["w_out"]}
            else:
                i = len(new_kv)
                with jax.named_scope("attn"):
                    out, cache_l = attn_block(
                        x, (lp, row, {k: v[i] for k, v in kv.items()})
                    )
                    out = out[..., : cfg.head_dim]
                new_kv.append(cache_l)
            x = _attn_out_and_ffn(x, out, lp, cfg, B, S, mm=mm)
        new_cache = {
            k: jnp.stack([c[k] for c in new_kv]) for k in kv
        }
        new_cache["ssm"] = jnp.stack(new_ssm)
        new_cache["conv"] = jnp.stack(new_conv)
        return x, new_cache

    with jax.named_scope("layers"):
        x, new_cache = jax.lax.scan(
            period_body,
            x,
            (jnp.arange(n_periods), {k: by_period(v) for k, v in cache.items()}),
        )
    new_cache = {
        k: v.reshape((-1,) + v.shape[2:]) for k, v in new_cache.items()
    }
    logits = _lm_head_logits(params, cfg, x, lm_head_last_only, mm=mm)
    return logits, new_cache


# The leaves of ``params["layers"]`` that a layer consumes through ``mm``:
# attention's and the (shared) FFN's projections, latent attention's down
# and up projections of the queries and its down projection of the keys
# and values. Not ``wkv_b``, which ``_latent_up`` dequantizes whole, nor
# the router, the norms and the biases.
_MM_WEIGHTS = (
    "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down",
    "wq_a", "wq_b", "wkv_a",
)


def _split_layers(layers: dict, fused_mm: bool, rows: int, dtype):
    """(the leaves of ``params["layers"]`` that the layer scan slices
    layer by layer, the matmul stacks it must not, the expert stacks it
    must not): the one place that decides.

    A sliced operand that a Pallas kernel reads is a COPY of the layer
    every step (XLA cannot fold the slice into the kernel's operand), so
    what a kernel reads stays whole and is read by layer index: the
    routed expert stacks (models/moe.py ``EXPERT_WEIGHTS``) always, and
    with the fused dequant-matmul on (``fused_mm``) every quantized
    ``_MM_WEIGHTS`` stack that the kernel covers for ``rows`` activation
    rows of ``dtype``. Everything else, and every matmul weight with the
    kernel off, goes on as the scan's operand, where XLA folds the slice
    into its own dot."""
    experts = {k: layers[k] for k in moe.EXPERT_WEIGHTS if k in layers}
    indexed = {}
    if fused_mm:
        from adversarial_spec_tpu.ops.pallas_quant import fused_supported

        # the plan asks the activation's row count and item size alone
        x = jax.ShapeDtypeStruct((rows, 1), dtype)
        indexed = {
            k: layers[k]
            for k in _MM_WEIGHTS
            if k in layers and fused_supported(x, layers[k], layer=0)
        }
    scanned = {
        k: v for k, v in layers.items() if k not in experts and k not in indexed
    }
    return scanned, indexed, experts


def _layer_weights(lp: dict, indexed: dict, layer_id) -> dict:
    """One layer's weights as its body reads them: the scan's slices,
    and under their own names the whole matmul stacks with the layer to
    read (``mm`` takes a ``StackedLayer`` where it takes a weight)."""
    return {
        **lp, **{k: StackedLayer(v, layer_id) for k, v in indexed.items()}
    }


def n_indexed_stacks(params: Params, fused_mm: bool, rows: int) -> int:
    """How many quantized matmul stacks the decode step of ``rows``
    positions reads by layer index instead of slicing (0 with the fused
    dequant-matmul off; the expert stacks are not counted)."""
    _, indexed, _ = _split_layers(
        params["layers"], fused_mm, rows, params["embed"].dtype
    )
    return len(indexed)


def _routed_ffn_of(cfg, lp, experts, layer_id, use_pallas, interpret):
    """(callable h -> the routed part, the list it leaves the layer's
    routing in), or (None, None) for a dense FFN. ``use_pallas``: the
    caller's ``use_pallas_matmul`` on one device: int8 stacks are read by
    the grouped kernel (off it every row tile gathers a whole expert
    matrix out of the stack: fine at a test's sizes, ruinous at a
    model's)."""
    if cfg.ffn_kind != "routed":
        return None, None
    routing: list = []

    def routed(h):
        out, idx = moe.routed_ffn(
            h, lp["w_router"], experts, layer_id, cfg.experts,
            functools.partial(_activation, kind=cfg.activation),
            use_pallas=use_pallas, interpret=interpret,
            router_bias=lp.get("router_bias"),
        )
        routing.append(idx)
        return out

    return routed, routing


def _lm_head_logits(
    params: Params, cfg: ModelConfig, x, lm_head_last_only: bool, mm=matmul
):
    with jax.named_scope("head"):
        return _lm_head(params, cfg, x, lm_head_last_only, mm)


def _lm_head(params: Params, cfg: ModelConfig, x, lm_head_last_only, mm):
    x = rms_norm(x, params["final_norm"], cfg.rms_eps, cfg.norm_scale_plus_one)
    if lm_head_last_only:
        # Prompt chunks only ever need the final position's logits; skip
        # the [B, S, vocab] projection (the largest prefill activation).
        x = x[:, -1:]
    if cfg.tied_embeddings:
        if "lm_head_t" in params:
            # Pre-transposed [D, V] copy (init_params/loader): contracts
            # the major axis at full HBM bandwidth instead of relayouting
            # the embed table every decode step.
            logits = mm(
                x, params["lm_head_t"], preferred_element_type=jnp.float32
            )
        else:
            logits = jnp.einsum(
                "bsd,vd->bsv",
                x,
                params["embed"],
                preferred_element_type=jnp.float32,
            )
    else:
        logits = mm(
            x, params["lm_head"], preferred_element_type=jnp.float32
        )
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap > 0.0:
        logits = _softcap(logits, cfg.logit_softcap)
    return logits


def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32 — decode step (S=1) or a short
    # multi-position verify span (S=γ+1, speculative decoding)
    positions: jnp.ndarray,  # [B, S] rope positions
    pool: Cache,  # {"k","v": [L, n_pages, Hkv, page_size, D]} (+"ks"/"vs"
    # [..., 1] f32 scale pages when the pool is int8)
    page_table: jnp.ndarray,  # [B, Pmax] int32; <= 0 = unmapped (0=trash)
    write_page: jnp.ndarray,  # [B(, S)] physical page per token's KV
    write_off: jnp.ndarray,  # [B(, S)] slot within that page
    bounds: jnp.ndarray,  # [B(, S), 2] (start, end) valid-slot window
    q_pos: jnp.ndarray,  # scalar, [B], or [B, S]: logical slot per token
    *,
    logits_at: jnp.ndarray | None = None,  # [B] int32: the one span
    # position a row's logits are wanted for (None: every position)
    write_kv=None,  # (pool, layer_id, {name: [B, Hkv, S, *]}, write_page,
    # write_off) -> pool: the caller's own way of putting a layer's new
    # K/V into their pages (None: the scatter below)
    state_rows: jnp.ndarray | None = None,  # [B] int32: the row of the
    # pool's recurrent state each span row owns (None: row b is b's)
    state_keep: jnp.ndarray | None = None,  # [B] int32: the state after
    # the span's first ``state_keep`` positions is written back (None:
    # nothing is; the span stays open in the pool for ``commit_span``)
    use_pallas: bool = False,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
) -> tuple[jnp.ndarray, Cache, jnp.ndarray | None]:
    """One decode step (or one multi-position span: a verify step's γ+1
    positions, an admission's delta over its adopted pages) over the
    PAGED KV pool.

    A latent pool ({"k": the shared rotated key, "v": the compressed
    vector}, one head) is read in the ABSORBED form: W_UK folds into the
    queries and W_UV into the output, so a page is read once and serves
    as keys and values of all heads (``paged_latent_attention_mq``).

    Same math as ``forward`` with short S (shared helpers), but K/V live
    in pages shared across rows: token (b, j)'s K/V scatters to
    (write_page[b, j], write_off[b, j]) and attention reads through the
    page table — fused Pallas kernels on real TPUs (S=1:
    paged_decode_attention; S>1: paged_decode_attention_mq, one pass
    over the pool for the whole span), a gather + masked jnp reference
    path elsewhere (same bounds semantics on every path).
    Returns (logits [B, S, vocab] — [B, 1, vocab] with ``logits_at``:
    an admission samples from its last real position alone, and the head
    is the widest matmul of the step — updated pool, the routed layers'
    choices: int32 [L, B*S, top_k] expert ids for the caller's routing
    counters, None for a dense FFN).

    Beside state-space layers (``cfg.ssm``) the pool also holds a
    recurrent state and a conv window a row (``init_recurrent_state``),
    and a row's K/V pages belong to the attention layers alone. Such a
    layer reads its state once for the whole span; what it writes back is
    the state after ``state_keep`` positions, and with ``state_keep``
    None nothing: the span's inputs ride out in ``pool["span"]`` and the
    caller closes it with ``commit_span`` once it knows how many
    positions stand (a verify step after ``accept_spans``), so the state
    never holds a rejected draft.

    In-span causality (S>1, the speculative verify shape) comes from the
    per-query bounds: position j's window ends at its own slot
    (``bounds[b, j, 1] = q_pos[b, j] + 1``), and every span position's
    K/V scatters before attention in each layer, so position j sees
    exactly [start, q_pos_bj + 1) — byte-compatible with flattening the
    span into the batch axis, without paying B·span densifications.

    On a multi-device ``mesh`` the S=1 kernel runs under shard_map with
    the pool's head axis tp-sharded (ops/pallas_paged.py:
    paged_decode_attention_tp); callers gate on tp | n_kv_heads. The
    multi-position kernel is single-device (sharded spans take the
    gather path). The non-kernel math (projections, scatter, gather
    path) partitions under GSPMD as usual. ``use_pallas_matmul`` routes
    quantized weights through the fused dequant-matmul kernels
    (ops/pallas_quant.py) — single-device, like ``forward``.

    Composition contract: this function and ``forward`` are pure
    traceable graphs over disjoint state (the paged pool here, a dense
    per-call cache there), so the scheduler's fused step traces BOTH
    into one program (engine/scheduler.py:fused_prefill_decode_chunk —
    a newcomer's prompt chunk riding the residents' decode chunk).
    Nothing in either body may grow module-level state or host callbacks
    that would make the fused composition diverge from the standalone
    dispatches.
    """
    B, S = tokens.shape
    page_size = pool["k"].shape[3]
    layer_ids = jnp.arange(cfg.n_layers)
    quant_kv = "ks" in pool  # int8 pages + per-(token, head) scale pages
    single_device = mesh is None or mesh.size == 1
    # The S=1 legacy calling convention passes [B]/[B,2]/scalar shapes;
    # normalize everything to the per-(row, span-position) layout.
    write_page = write_page.reshape(B, S)
    write_off = write_off.reshape(B, S)
    bounds = bounds.reshape(B, S, 2)
    if jnp.ndim(q_pos) <= 1:
        q_pos = jnp.broadcast_to(jnp.reshape(q_pos, (-1, 1)), (B, S))
    fused_mm = use_pallas_matmul and single_device
    mm = (
        functools.partial(
            matmul, use_pallas=True, interpret=pallas_interpret
        )
        if fused_mm
        else matmul
    )
    cos, sin = _rope_tables(cfg, positions)
    latent = cfg.latent is not None

    x = _embed(params, cfg, tokens)

    flat_page = write_page.reshape(-1)
    flat_off = write_off.reshape(-1)
    heads = jnp.arange(cfg.kv_layout[0])
    scanned_layers, indexed, experts = _split_layers(
        params["layers"], fused_mm, B * S, x.dtype
    )

    def scatter(pool, layer_id, new_kv):
        # Pages are heads-major [L, n_pages, Hkv, page_size, D]. The
        # scatter addresses (layer, page, head, offset) and moves whole
        # D-rows — contiguous in that layout, so XLA keeps the pool in
        # the layout the attention kernels require. (A [Hkv, D] update
        # window per token makes it re-lay the WHOLE pool out
        # token-major and convert it back for every kernel call.) One
        # scatter per pool array per layer regardless of span width
        # (rejected-draft targets are the trash page, never read).
        if write_kv is not None:
            heads_major = {
                name: jnp.swapaxes(val.reshape(B, S, *val.shape[1:]), 1, 2)
                for name, val in new_kv.items()
            }
            return write_kv(pool, layer_id, heads_major, write_page, write_off)
        return {
            **pool,
            **{
                name: pool[name]
                .at[
                    layer_id, flat_page[:, None], heads[None, :], flat_off[:, None]
                ]
                .set(val)
                for name, val in new_kv.items()
            },
        }

    def layer_body(carry, scanned):
        x, pool = carry
        lp, layer_id = scanned
        lp = _layer_weights(lp, indexed, layer_id)
        with jax.named_scope("attn"):
            out, pool = (latent_block if latent else attn_block)(
                x, pool, (lp, layer_id)
            )
        routed, routing = _routed_ffn_of(
            cfg, lp, experts, layer_id, fused_mm, pallas_interpret
        )
        x = _attn_out_and_ffn(x, out, lp, cfg, B, S, mm=mm, routed=routed)
        return (x, pool), (routing[0] if routing else None)

    def latent_block(x, pool, scanned):
        lp, layer_id = scanned
        la = cfg.latent
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, False)
        with jax.named_scope("attn.latent"):
            q_nope, q_rope, c_kv, k_r = _project_latent(
                lp, cfg, h, B, S, positions, cos, sin, mm
            )
            k_new, v_new = _latent_cache_rows(cfg, c_kv, k_r)
            pool = scatter(
                pool,
                layer_id,
                {
                    "k": k_new.reshape(B * S, 1, -1).astype(pool["k"].dtype),
                    "v": v_new.reshape(B * S, 1, -1).astype(pool["v"].dtype),
                },
            )
            w_uk, w_uv = _latent_up(lp, cfg, x.dtype)
            start, end = bounds[..., 0], bounds[..., 1]
            if use_pallas and single_device:
                from adversarial_spec_tpu.ops.pallas_paged import (
                    paged_latent_attention_mq,
                )

                # Absorb W_UK into the queries (q' . c_kv = q_nope .
                # k_nope) and W_UV into the output ((p c_kv) W_UV = p v).
                o_lat = paged_latent_attention_mq(
                    jnp.einsum("bshn,rhn->bshr", q_nope, w_uk),
                    jnp.pad(
                        q_rope,
                        ((0, 0),) * 3 + ((0, la.rope_pad - la.rope_dim),),
                    ),
                    pool["k"],
                    pool["v"],
                    page_table,
                    start,
                    end,
                    scale=cfg.attn_scale,
                    interpret=pallas_interpret,
                    layer=layer_id,
                )
                out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
            else:
                # Gather path: the same absorbed mathematics over the
                # row's pages, densified once.
                safe_table = jnp.maximum(page_table, 0)

                def to_dense(pages):
                    g = pages[layer_id, safe_table]  # [B, P, 1, page, *]
                    return jnp.swapaxes(g, 1, 2).reshape(
                        B, 1, -1, pages.shape[-1]
                    )

                c_dense, r_dense = to_dense(pool["v"]), to_dense(pool["k"])
                slot = jnp.arange(c_dense.shape[2])[None, None, :]
                mapped = jnp.repeat(
                    page_table > 0, page_size, axis=1
                )[:, None, :]
                mask = (
                    mapped & (slot >= start[..., None]) & (slot < end[..., None])
                )
                out = _absorbed_attention(
                    cfg, q_nope, q_rope, r_dense, c_dense, mask, w_uk, w_uv
                )
        return out, pool

    def attn_block(x, pool, scanned, kind="gqa"):
        lp, layer_id = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, cfg.norm_scale_plus_one)
        with _attn_scope(kind):
            out, pool = attend(h, pool, lp, layer_id, kind)
        return _attn_gate(lp, cfg, h, out, mm), pool

    def attend(h, pool, lp, layer_id, kind):
        # The WHOLE pool rides the scan carry and every layer updates and
        # reads it in place, addressed by layer index. Scanning it as
        # per-layer xs/ys instead makes XLA slice one layer's pages out
        # (a copy), restack them into a second pool-sized buffer, and
        # copy that back over the carried pool every step: temporaries
        # of twice the pool, which a pool sized to the chip cannot pay.
        q, k, v = _project_qkv(lp, cfg, h, B, S, cos, sin, mm=mm, kind=kind)

        kf = k.reshape(B * S, cfg.n_kv_heads, k.shape[-1])
        vf = v.reshape(B * S, cfg.n_kv_heads, v.shape[-1])
        if quant_kv:
            kq, ks = _quantize_kv(kf)  # [B·S, Hkv, D], [B·S, Hkv, 1]
            vq, vs = _quantize_kv(vf)
            new_kv = {"k": kq, "v": vq, "ks": ks, "vs": vs}
        else:
            new_kv = {
                "k": kf.astype(pool["k"].dtype),
                "v": vf.astype(pool["v"].dtype),
            }
        pool = scatter(pool, layer_id, new_kv)
        qkw = (
            dict(k_scale=pool["ks"], v_scale=pool["vs"]) if quant_kv else {}
        )

        start = _layer_window_start(
            cfg, layer_id, bounds[..., 0], q_pos, kind
        )  # [B, S]
        end = bounds[..., 1]  # [B, S]

        if use_pallas and S == 1:
            from adversarial_spec_tpu.ops.pallas_paged import (
                paged_decode_attention,
                paged_decode_attention_dp_tp,
                paged_decode_attention_tp,
            )

            layer_bounds = jnp.stack([start[:, 0], end[:, 0]], axis=1)
            if not single_device:
                from adversarial_spec_tpu.parallel.mesh import DP as _DPAX

                # Mixed dp×tp meshes shard rows + page slabs over dp as
                # well (per-slice pool layout, global ids — see the
                # wrapper's contract); tp-only meshes replicate the pool
                # over dp=1 trivially via the same specs.
                wrapper = (
                    paged_decode_attention_dp_tp
                    if mesh.shape[_DPAX] > 1
                    else paged_decode_attention_tp
                )
                out = wrapper(
                    q[:, 0],
                    pool["k"],
                    pool["v"],
                    page_table,
                    layer_bounds,
                    mesh,
                    layer_id,
                    attn_softcap=cfg.attn_softcap,
                    scale=cfg.attn_scale,
                    interpret=pallas_interpret,
                    **qkw,
                )[:, None]
            else:
                out = paged_decode_attention(
                    q[:, 0],
                    pool["k"],
                    pool["v"],
                    page_table,
                    layer_bounds,
                    attn_softcap=cfg.attn_softcap,
                    scale=cfg.attn_scale,
                    interpret=pallas_interpret,
                    layer=layer_id,
                    **qkw,
                )[:, None]
        elif use_pallas and single_device:
            from adversarial_spec_tpu.ops.pallas_paged import (
                paged_decode_attention_mq,
            )

            # Multi-position span: the γ+1 queries of each row fold into
            # one grid pass over the row's pages, each under its OWN
            # [start, end) window (in-span causality).
            out = paged_decode_attention_mq(
                q,
                pool["k"],
                pool["v"],
                page_table,
                start,
                end,
                attn_softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
                interpret=pallas_interpret,
                layer=layer_id,
                **qkw,
            )
        else:
            # Gather reference path: page table → dense [B, Hkv, T, D]
            # (densified ONCE per row — the whole span reads it).
            safe_table = jnp.maximum(page_table, 0)

            def to_dense(pages):  # [L, n_pages, Hkv, page, *] → [B, Hkv, T, *]
                g = pages[layer_id, safe_table]  # [B, P, Hkv, page, *]
                return jnp.swapaxes(g, 1, 2).reshape(
                    B, cfg.n_kv_heads, -1, pages.shape[-1]
                )

            if quant_kv:
                k_dense = (
                    to_dense(pool["k"]).astype(jnp.float32)
                    * to_dense(pool["ks"])
                ).astype(h.dtype)
                v_dense = (
                    to_dense(pool["v"]).astype(jnp.float32)
                    * to_dense(pool["vs"])
                ).astype(h.dtype)
            else:
                k_dense = to_dense(pool["k"])
                v_dense = to_dense(pool["v"])
            T = k_dense.shape[2]
            slot = jnp.arange(T)[None, None, :]
            # <= 0 is unmapped: page 0 is the reserved trash page (callers
            # shift allocator ids +1), negatives are table padding. Same
            # convention as ops/pallas_paged.py.
            mapped = jnp.repeat(
                page_table > 0, page_size, axis=1
            )[:, None, :]
            mask = (
                mapped
                & (slot >= start[..., None])
                & (slot < end[..., None])
            )  # [B, S, T]
            out = attention(
                q,
                k_dense,
                v_dense,
                mask,
                attn_softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
            )
        return out, pool

    def ssm_block(x, pool, lp, row, rows):
        """A state-space layer over the span, on row ``row`` of the state
        stacks: (the mixer's output before its projection, the pool, the
        span's inputs where it stays open)."""
        sp = cfg.ssm
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, False)
        if state_keep is not None and S > sp.chunk:
            # A span wider than the scan's chunk whose count is known (an
            # admission's long delta): the chunked form, as a prefill chunk
            # runs it; the span form's [S, S] decay matrix grows with S^2.
            keep = state_keep.astype(jnp.int32)
            out, state, seq = _ssm_chunked(
                lp, cfg, h, pool["conv"][row, rows], pool["ssm"][row, rows],
                jnp.arange(S)[None, :] < keep[:, None], mm,
            )
            window = ssm_ops.conv_window(seq, keep, sp.conv_width - 1)
            pool = {
                **pool,
                "ssm": pool["ssm"].at[row, rows].set(state),
                "conv": pool["conv"].at[row, rows].set(
                    window.astype(pool["conv"].dtype)
                ),
            }
            return out, pool, None
        kernels = use_pallas and single_device
        with jax.named_scope("ssm"):
            z, xs, b_in, c_in, dt, seq = _ssm_inputs(
                lp, cfg, h, pool["conv"][row, rows], None, mm
            )
            with jax.named_scope("ssm.scan"):
                if kernels:
                    ys = ssm_ops.ssm_span_read(
                        pool["ssm"], row, rows, c_in, interpret=pallas_interpret
                    )
                else:
                    ys = ssm_ops.state_read(pool["ssm"][row, rows], c_in)
                y, cum = ssm_ops.span_outputs(
                    xs, b_in, c_in, dt, -jnp.exp(lp["A_log"]), lp["d_skip"],
                    ys.reshape(B, S, sp.n_heads, sp.head_dim),
                )
            saved = {"x": xs, "b": b_in, "dt": dt, "cum": cum, "seq": seq}
            if state_keep is not None:
                pool = _commit_layer(
                    pool, cfg, row, rows, saved, state_keep.astype(jnp.int32),
                    kernels, pallas_interpret,
                )
                saved = None
            return _ssm_gated(lp, cfg, y, z, x.dtype), pool, saved

    def period_body(carry, p):
        # A period longer than one layer: its layers in order, the
        # attention layers through ``attn_block`` on their own row of the
        # page pool, the state-space layers on theirs of the state.
        x, pool = carry
        rows = (
            jnp.arange(B, dtype=jnp.int32) if state_rows is None else state_rows
        )
        spans = []
        for kind, row, lp in _period_layers(cfg, params, p):
            if kind == "ssm":
                out, pool, saved = ssm_block(x, pool, lp, row, rows)
                if saved is not None:
                    spans.append(saved)
                lp = {**lp, "wo": lp["w_out"]}
            else:
                with jax.named_scope("attn"):
                    out, pool = attn_block(x, pool, (lp, row))
                    out = out[..., : cfg.head_dim]
            x = _attn_out_and_ffn(x, out, lp, cfg, B, S, mm=mm)
        span = (
            {k: jnp.stack([s[k] for s in spans]) for k in spans[0]}
            if spans
            else None
        )
        return (x, pool), span

    def run_body(run):
        # One repetition of a gated stack's run (``_segments``): its
        # layers in order on their own layer of the pool; a routed run
        # hands its layers' routing out, stacked.
        def body(carry, p):
            x, pool = carry
            routings = []
            for kind, layer, lp, routed, routing in _run_layers(
                params, cfg, run, fused_mm, pallas_interpret, B * S, x.dtype, p
            ):
                with jax.named_scope("attn"):
                    out, pool = attn_block(x, pool, (lp, layer), kind)
                x = _attn_out_and_ffn(x, out, lp, cfg, B, S, mm=mm, routed=routed)
                if routing:
                    routings.append(routing[0])
            return (x, pool), (jnp.stack(routings) if routings else None)

        return body

    with jax.named_scope("layers"):
        if cfg.gated is not None:
            new_pool, routed_runs = pool, []
            for run in _segments(cfg):
                (x, new_pool), routing = jax.lax.scan(
                    run_body(run), (x, new_pool), jnp.arange(run.reps)
                )
                if routing is not None:
                    routed_runs.append(
                        routing.reshape((-1,) + routing.shape[2:])
                    )
            routing = jnp.concatenate(routed_runs) if routed_runs else None
        elif cfg.ssm is not None:
            (x, new_pool), span = jax.lax.scan(
                period_body,
                (x, pool),
                jnp.arange(cfg.n_layers // cfg.period),
            )
            routing = None
            if span is not None:
                new_pool = {
                    **new_pool,
                    "span": {
                        k: v.reshape((-1,) + v.shape[2:])
                        for k, v in span.items()
                    },
                }
        else:
            (x, new_pool), routing = jax.lax.scan(
                layer_body,
                (x, pool),
                (scanned_layers, layer_ids),
            )
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    logits = _lm_head_logits(params, cfg, x, lm_head_last_only=False)
    return logits, new_pool, routing


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
