"""Sequence-parallel (long-context) prefill: the whole transformer forward
with the sequence axis sharded over ``sp`` — composable with tensor
parallelism over ``tp``.

BASELINE config 5 is a 16k-context PRD against a TP=8 70B judge; at that
shape prefill needs BOTH axes at once. Inside one shard_map over the full
mesh:

- the prompt is split into ``sp`` contiguous blocks (embeddings, QKV
  projections, FFNs run on local blocks; attention is a K/V ring over the
  sp axis — parallel/ring.py);
- weights enter tp-sharded per the Megatron rules (parallel/sharding.py):
  this is a manual-collective region, so the body works on a "shard view"
  of the config (heads/FFN columns divided by tp) and the row-parallel
  matmuls all-reduce explicitly (``psum_axis`` in the shared layer tail);
- last-position logits are vocab-sharded under tp (column-parallel
  lm_head) and all-gather only at the very end.

Activation and attention memory are O(S/sp) per device; K/V ring traffic
rides sp-neighbor ICI links and the TP all-reduces ride the tp axis.

The resulting KV cache comes back sequence-sharded (heads tp-sharded);
the caller reshards to the decode layout (batch over dp, heads over tp).

Sliding-window families are supported: each layer's window (including
gemma-2's alternating pattern) is applied as a mask inside the ring.
Constraints (v1): the padded length must divide sp;
n_heads/n_kv_heads/ffn_dim/vocab must divide tp.
"""

from __future__ import annotations

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from adversarial_spec_tpu.models.config import ModelConfig
from adversarial_spec_tpu.models.transformer import (
    _attn_out_and_ffn,
    _lm_head_logits,
    _project_qkv,
    rms_norm,
)
from adversarial_spec_tpu.ops.rope import rope_angles
from adversarial_spec_tpu.parallel.mesh import SP, TP
from adversarial_spec_tpu.parallel.ring import ring_attention_local
from adversarial_spec_tpu.parallel.sharding import param_sharding_rules


def _param_in_specs(params):
    """Per-leaf PartitionSpecs for shard_map: the tp placements from the
    Megatron rules (sp/dp never appear on weights)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: param_sharding_rules(path), params
    )


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def sp_prefill(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] left-padded, S % sp == 0
    pad_lens: jnp.ndarray,  # [B]
    mesh: Mesh,
):
    """Sequence-parallel (× tensor-parallel) prefill over the full prompt.

    Returns (last_logits [B, vocab] f32, cache {"k","v": [L, B, Hkv, S, D]}
    sequence-sharded over sp and head-sharded over tp).

    Sliding-window families work too: the per-layer window (including
    gemma-2's alternating pattern) is applied as a mask inside the ring —
    every hop still runs (SPMD uniformity), distant blocks contribute
    zeros.
    """
    sp = mesh.shape[SP]
    tp = mesh.shape[TP]
    B, S = tokens.shape
    if S % sp != 0:
        raise ValueError(f"padded length {S} not divisible by sp={sp}")
    if tp > 1 and (
        cfg.n_heads % tp
        or cfg.n_kv_heads % tp
        or cfg.ffn_dim % tp
        or cfg.vocab_size % tp
    ):
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads}, "
            f"n_kv_heads={cfg.n_kv_heads}, ffn_dim={cfg.ffn_dim}, "
            f"vocab={cfg.vocab_size}"
        )

    # The body sees LOCAL shards: express the per-device shapes as a
    # shard-view config (full head_dim/dim; heads and FFN columns split).
    local_cfg = (
        replace(
            cfg,
            n_heads=cfg.n_heads // tp,
            n_kv_heads=cfg.n_kv_heads // tp,
            ffn_dim=cfg.ffn_dim // tp,
        )
        if tp > 1
        else cfg
    )
    psum_axis = TP if tp > 1 else None

    def local(tokens_l, pad_lens_rep, params_l):
        # tokens_l: [B, S/sp]; params_l: tp-local weight shards.
        idx = jax.lax.axis_index(SP)
        S_loc = tokens_l.shape[1]
        base = idx * S_loc
        positions = jnp.maximum(
            base + jnp.arange(S_loc, dtype=jnp.int32)[None, :]
            - pad_lens_rep[:, None],
            0,
        )
        cos, sin = rope_angles(
            positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
        )

        x = params_l["embed"][tokens_l]  # embed is tp-replicated
        if cfg.scale_embeddings:
            x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(x.dtype)

        layer_ids = jnp.arange(cfg.n_layers)

        def layer_body(x, scanned):
            lp, layer_id = scanned
            h = rms_norm(
                x, lp["attn_norm"], cfg.rms_eps, cfg.norm_scale_plus_one
            )
            q, k, v = _project_qkv(lp, local_cfg, h, B, S_loc, cos, sin)
            if cfg.sliding_window > 0 and cfg.sliding_window_pattern > 1:
                # Gemma-2: alternate windowed / global layers.
                window = jnp.where(
                    layer_id % cfg.sliding_window_pattern == 0,
                    cfg.sliding_window,
                    0,
                )
            else:
                window = cfg.sliding_window
            out = ring_attention_local(
                q,
                k.astype(jnp.float32),
                v.astype(jnp.float32),
                sp,
                causal=True,
                kv_start=pad_lens_rep,
                attn_softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
                window=window,
            )
            x = _attn_out_and_ffn(
                x, out, lp, local_cfg, B, S_loc, psum_axis=psum_axis
            )
            return x, (k, v)

        x, (k_all, v_all) = jax.lax.scan(
            layer_body, x, (params_l["layers"], layer_ids)
        )
        # Scan stacks token-major [L, B, S_loc, H, D]; the cache contract
        # is heads-major [L, B, H, S_loc, D] (models/transformer.py).
        k_all = jnp.swapaxes(k_all, 2, 3)
        v_all = jnp.swapaxes(v_all, 2, 3)

        # Last-position logits: the shared lm-head tail (final norm +
        # tied/untied projection + softcap — one source of truth with the
        # dense path), computed on every sp block for SPMD uniformity,
        # zeroed except on the last block, psum'd over sp. Under tp the
        # lm_head is column-parallel; softcap is elementwise so it
        # commutes with the vocab all-gather.
        logits_local = _lm_head_logits(
            params_l, cfg, x, lm_head_last_only=True
        )[:, 0]
        if tp > 1 and not cfg.tied_embeddings:
            logits_local = jax.lax.all_gather(
                logits_local, TP, axis=1, tiled=True
            )
        logits_local = jnp.where(idx == sp - 1, logits_local, 0.0)
        logits = jax.lax.psum(logits_local, SP)
        return logits, k_all, v_all

    seq_spec = P(None, SP)
    cache_spec = P(None, None, TP, SP, None)  # [L, B, Hkv(tp), S(sp), D]
    logits, k_all, v_all = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(seq_spec, P(None), _param_in_specs(params)),
        out_specs=(P(None, None), cache_spec, cache_spec),
        check_vma=False,
    )(tokens, pad_lens, params)
    return logits, {"k": k_all, "v": v_all}


def reshard_cache_for_decode(
    cache, mesh: Mesh, total_len: int, kv_dtype: str = ""
):
    """Sequence-sharded prefill cache → decode layout: gather the sequence
    axis, pad to ``total_len`` slots, shard batch over dp / heads over tp.

    ``kv_dtype="int8"``: quantize the gathered cache into the int8
    decode layout (models/transformer.py:init_cache). The ring attention
    itself ran on full-precision K/V — sp prefill quantizes at this
    boundary, where the dense path quantizes at each prefill write
    (prompt-token KV values are identical either way; prefill-attention
    reads differ in the int8 rounding, in sp's favor)."""
    from adversarial_spec_tpu.parallel.sharding import cache_sharding

    S = cache["k"].shape[3]
    out = {}
    for name, arr in cache.items():
        arr = jax.device_put(arr, cache_sharding(mesh))  # gathers sp
        if total_len > S:
            pad = [(0, 0)] * arr.ndim
            pad[3] = (0, total_len - S)
            arr = jnp.pad(arr, pad)
        out[name] = arr
    if kv_dtype == "int8":
        from adversarial_spec_tpu.models.transformer import _quantize_kv

        k8, ks = _quantize_kv(out["k"])
        v8, vs = _quantize_kv(out["v"])
        out = {"k": k8, "v": v8, "ks": ks, "vs": vs}
    return out
