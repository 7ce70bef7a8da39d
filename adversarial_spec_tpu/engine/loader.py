"""Checkpoint materialization: HF safetensors → layer-stacked JAX pytrees.

TPU-native replacement for the reference's "model access" (API keys →
remote weights, scripts/providers.py:418-486): here access = reading HF
checkpoint dirs (``*.safetensors`` + config) into the transformer's
layer-stacked param pytree (models/transformer.py), transposing Linear
weights from torch's [out, in] to matmul-friendly [in, out] and stacking
per-layer tensors along a leading ``n_layers`` axis for scan-over-layers.

``checkpoint == "random"`` materializes synthetic weights of the family's
real shape (zero-egress test/bench path). Host RAM during load is bounded
to ONE stacked parameter in the target dtype: each stacked param is
assembled layer-by-layer into a single preallocated buffer (no per-layer
list, no np.stack double copy), placed on device via the caller's
``device_put`` hook, then freed before the next param is read (SURVEY §7
hard part (c): 70B within host RAM).
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from adversarial_spec_tpu.engine.checkpoint import transposed_head_flag
from adversarial_spec_tpu.models.config import ModelConfig, get_config
from adversarial_spec_tpu.models.transformer import Params, init_params

# Our layer-param name → HF per-layer tensor name (layers.{i} prefix added).
_HF_LAYER_MAP = {
    "attn_norm": "input_layernorm.weight",
    "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "bq": "self_attn.q_proj.bias",
    "bk": "self_attn.k_proj.bias",
    "bv": "self_attn.v_proj.bias",
    "ffn_norm": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight",
    "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    # Gemma-2 sandwich norms (HF names).
    "post_attn_norm": "post_attention_layernorm.weight",
    "ffn_norm_gemma2": "pre_feedforward_layernorm.weight",
    "post_ffn_norm": "post_feedforward_layernorm.weight",
}

_TRANSPOSE = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}


class CheckpointConfigError(ValueError):
    """Registered architecture contradicts the checkpoint's config.json."""


def preflight_config(
    ckpt_dir: str | Path, cfg: ModelConfig, family: str
) -> None:
    """Cross-check the registered ModelConfig against the checkpoint's own
    ``config.json`` before any tensor is read.

    A mis-registered alias (wrong --family/--size for the directory it
    points at) would otherwise produce garbage logits with no error —
    shapes can coincide while rope_theta, GQA ratio, or tied embeddings
    differ. The reference fails fast with an actionable message at model
    access time (scripts/providers.py:418-486, key/alias preflight); this
    is the checkpoint-dir analog. A checkpoint without config.json (e.g.
    bare safetensors exports, test fixtures) is not checked.
    """
    path = Path(ckpt_dir) / "config.json"
    if not path.is_file():
        return
    try:
        hf = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointConfigError(
            f"unreadable config.json under {ckpt_dir}: {e}"
        ) from e

    problems: list[str] = []
    model_type = hf.get("model_type")
    if model_type is not None and str(model_type) != family:
        problems.append(
            f"model_type: checkpoint is {model_type!r}, "
            f"alias registered as family {family!r}"
        )

    scalar_checks = [
        ("hidden_size", "dim", cfg.dim),
        ("num_hidden_layers", "n_layers", cfg.n_layers),
        ("num_attention_heads", "n_heads", cfg.n_heads),
        ("num_key_value_heads", "n_kv_heads", cfg.n_kv_heads),
        ("intermediate_size", "ffn_dim", cfg.ffn_dim),
        ("vocab_size", "vocab_size", cfg.vocab_size),
        ("head_dim", "head_dim", cfg.head_dim),
        ("tie_word_embeddings", "tied_embeddings", cfg.tied_embeddings),
    ]
    # Qwen2 configs ship "sliding_window": 131072 with
    # "use_sliding_window": false — the declared window is inert, so
    # only compare when the checkpoint actually uses it.
    if hf.get("use_sliding_window", True):
        scalar_checks.append(
            ("sliding_window", "sliding_window", cfg.sliding_window)
        )
    for hf_key, field, want in scalar_checks:
        got = hf.get(hf_key)
        if got is None:
            continue
        try:
            if isinstance(want, bool):
                # Only a real JSON boolean (or 0/1) may match — bool([])
                # style coercion would silently pass malformed values.
                ok = (
                    isinstance(got, bool)
                    or (isinstance(got, int) and got in (0, 1))
                ) and bool(got) == want
            elif isinstance(want, float):
                ok = abs(float(got) - float(want)) < 1e-6
            else:
                ok = int(got) == want
        except (TypeError, ValueError):
            # A malformed value (string where a number belongs) is a
            # mismatch to report, never a crash.
            ok = False
        if not ok:
            problems.append(
                f"{hf_key}: checkpoint has {got!r}, registered config "
                f"({field}) has {want!r}"
            )

    theta = hf.get("rope_theta")
    if theta is not None:
        try:
            theta_mismatch = abs(float(theta) - cfg.rope_theta) > 1e-3
        except (TypeError, ValueError):
            theta_mismatch = True
        if theta_mismatch:
            problems.append(
                f"rope_theta: checkpoint has {theta!r}, registered config "
                f"has {cfg.rope_theta!r}"
            )

    rs = hf.get("rope_scaling")
    if rs is not None and not isinstance(rs, dict):
        problems.append(
            f"rope_scaling: checkpoint value {rs!r} is not an object"
        )
        rs = None
    rs_type = (rs or {}).get("rope_type", (rs or {}).get("type"))
    if rs and rs_type == "llama3":
        if cfg.rope_scaling is None:
            problems.append(
                "rope_scaling: checkpoint uses llama3 scaling "
                f"(factor={rs.get('factor')}), registered config is "
                "unscaled — long-context positions would be wrong"
            )
        else:
            want_f, want_lo, want_hi, want_orig = cfg.rope_scaling
            pairs = [
                ("factor", rs.get("factor"), want_f),
                ("low_freq_factor", rs.get("low_freq_factor"), want_lo),
                ("high_freq_factor", rs.get("high_freq_factor"), want_hi),
                (
                    "original_max_position_embeddings",
                    rs.get("original_max_position_embeddings"),
                    want_orig,
                ),
            ]
            for key, got, want in pairs:
                if got is None:
                    continue
                try:
                    pair_mismatch = abs(float(got) - want) > 1e-6
                except (TypeError, ValueError):
                    pair_mismatch = True
                if pair_mismatch:
                    problems.append(
                        f"rope_scaling.{key}: checkpoint has {got!r}, "
                        f"registered config has {want!r}"
                    )
    elif not rs and cfg.rope_scaling is not None:
        problems.append(
            "rope_scaling: registered config expects llama3 scaling "
            f"(factor={cfg.rope_scaling[0]}), checkpoint has none"
        )

    if problems:
        detail = "\n  - ".join(problems)
        raise CheckpointConfigError(
            f"checkpoint {ckpt_dir} does not match the registered "
            f"architecture for family {family!r}:\n  - {detail}\n"
            "Fix: re-register the alias with the family/size that matches "
            "this checkpoint (`registry` action, see `status`), or point "
            "it at the right directory. Loading anyway would produce "
            "garbage logits, not an error."
        )


def _open_safetensors(ckpt_dir: Path):
    """Return {tensor_name: (file, name)} across all shards."""
    from safetensors import safe_open

    index_path = ckpt_dir / "model.safetensors.index.json"
    files: dict[str, Path] = {}
    if index_path.is_file():
        index = json.loads(index_path.read_text())
        for name, fname in index["weight_map"].items():
            files[name] = ckpt_dir / fname
    else:
        shards = sorted(ckpt_dir.glob("*.safetensors"))
        if not shards:
            raise FileNotFoundError(f"no *.safetensors under {ckpt_dir}")
        for shard in shards:
            with safe_open(str(shard), framework="numpy") as f:
                for name in f.keys():
                    files[name] = shard
    return files


def _read_tensor(files: dict, name: str) -> np.ndarray:
    from safetensors import safe_open

    if name not in files:
        raise KeyError(f"tensor {name!r} missing from checkpoint")
    with safe_open(str(files[name]), framework="numpy") as f:
        return f.get_tensor(name)


def load_hf_checkpoint(
    ckpt_dir: str | Path,
    cfg: ModelConfig,
    family: str,
    dtype: jnp.dtype = jnp.bfloat16,
    device_put=None,
    transposed_head: bool | None = None,
) -> Params:
    """Read an HF checkpoint dir into the layer-stacked pytree.

    ``device_put(path_tuple, np_array) -> jax.Array`` lets the caller shard
    each tensor as it is read (defaults to plain jnp.asarray on the default
    device).

    ``transposed_head``: materialize the [D, V] head copy for tied
    configs (models/transformer.py:init_params). None reads the
    ADVSPEC_TRANSPOSED_HEAD env var (default on); set it to 0 on
    memory-tight fits to save the V·D bytes.
    """
    import ml_dtypes

    ckpt_dir = Path(ckpt_dir)
    preflight_config(ckpt_dir, cfg, family)
    files = _open_safetensors(ckpt_dir)
    put = device_put or (lambda path, arr: jnp.asarray(arr, dtype=dtype))
    np_dtype = np.dtype(
        {jnp.bfloat16: ml_dtypes.bfloat16}.get(dtype, np.dtype(dtype))
    )

    prefix = "model."

    def hf_name(layer_key: str) -> str:
        if family == "gemma2" and layer_key == "ffn_norm":
            return _HF_LAYER_MAP["ffn_norm_gemma2"]
        return _HF_LAYER_MAP[layer_key]

    def stack(layer_key: str) -> np.ndarray:
        """Assemble one layer-stacked param into a single preallocated
        target-dtype buffer — peak host RAM is this buffer plus one layer."""
        suffix = hf_name(layer_key)
        buf = None
        for i in range(cfg.n_layers):
            t = np.asarray(_read_tensor(files, f"{prefix}layers.{i}.{suffix}"))
            if layer_key in _TRANSPOSE:
                t = t.T  # torch Linear [out, in] → [in, out]
            if buf is None:
                buf = np.empty((cfg.n_layers,) + t.shape, np_dtype)
            buf[i] = t.astype(np_dtype)
        return buf

    layer_keys = [
        "attn_norm",
        "wq",
        "wk",
        "wv",
        "wo",
        "ffn_norm",
        "w_gate",
        "w_up",
        "w_down",
    ]
    if cfg.qkv_bias:
        layer_keys += ["bq", "bk", "bv"]
    if cfg.post_norms:
        layer_keys += ["post_attn_norm", "post_ffn_norm"]

    layers = {
        k: put(("layers", k), stack(k)) for k in layer_keys
    }
    embed_np = np.asarray(
        _read_tensor(files, f"{prefix}embed_tokens.weight")
    )
    params: Params = {
        "embed": put(("embed",), embed_np),
        "layers": layers,
        "final_norm": put(
            ("final_norm",), np.asarray(_read_tensor(files, f"{prefix}norm.weight"))
        ),
    }
    if transposed_head is None:
        transposed_head = transposed_head_flag()
    if not cfg.tied_embeddings:
        head = np.asarray(_read_tensor(files, "lm_head.weight")).T
        params["lm_head"] = put(("lm_head",), head)
    elif transposed_head:
        # Transposed [D, V] head copy for tied embeddings — the decode
        # hot path's head matmul at full bandwidth (see
        # models/transformer.py:init_params). np .T is a view of the
        # table already read for "embed"; `put` materializes it in the
        # target dtype/sharding.
        params["lm_head_t"] = put(("lm_head_t",), embed_np.T)
    return params


def _random_params(
    cfg: ModelConfig, dtype, seed: int, quant: str, mesh
) -> Params:
    """Synthetic weights, built ONE WEIGHT AT A TIME on the device.

    Each weight is its own jitted program: it traces the whole
    ``init_params`` (+ quantization), returns one weight, and XLA drops
    the rest as dead code — so the values are ``init_params``' own for
    the same seed, the weight is born in its target sharding, and
    neither the full-precision tree nor a host copy ever exists. At 7B
    widths the bf16 tree alone is 14.5 GB; a 16 GB chip holds the int8
    model only if it never sees that tree.
    """
    from adversarial_spec_tpu.ops import quant as quant_mod
    from adversarial_spec_tpu.parallel.sharding import param_shardings

    def build(key):
        # expert stacks are quantized piece by piece as they are drawn
        # (transformer._expert_stack): no full-precision stack exists
        p = init_params(key, cfg, dtype=dtype, expert_quant=quant)
        return quant_mod.quantize_params(p, fmt=quant) if quant else p

    def is_weight(node) -> bool:  # a quantized {q|q4, scale} pair is ONE weight
        return quant_mod.is_quantized(node) or quant_mod.is_quantized_int4(
            node
        )

    key = jax.random.key(seed)
    shapes = jax.eval_shape(build, key)
    shardings = (
        param_shardings(mesh, shapes)
        if mesh is not None
        else jax.tree.map(lambda _: None, shapes)
    )

    def one(path, _shape, sharding):
        def pick(k):
            node = build(k)
            for entry in path:
                node = node[entry.key]
            return node

        return jax.jit(pick, out_shardings=sharding)(key)

    return jax.tree_util.tree_map_with_path(
        one, shapes, shardings, is_leaf=is_weight
    )


def materialize_params(
    checkpoint: str,
    family: str,
    size: str,
    dtype: jnp.dtype = jnp.bfloat16,
    seed: int = 0,
    max_seq_len: int = 0,
    mesh=None,
    quant: str = "",
    n_layers: int = 0,
    experts_held: tuple[int, int] | list[int] = (),
    vocab_rows: int = 0,
) -> tuple[Params, ModelConfig]:
    """checkpoint == "random" → synthetic init; else HF safetensors dir.

    ``mesh``: place every tensor straight into its sharding on this
    mesh (parallel/sharding.py rules) as it is built or read; None =
    the default device.

    ``quant`` ("int8" / "int4", ops/quant.py) quantizes the matmul
    weights AT materialization, so every consumer (native-cache writer,
    residency estimate, serving path) sees one layout — the quantized
    shards are also what the weight-residency manager demotes to host
    RAM (engine/weightres.py), at a half/quarter of the bf16 bytes.
    """
    from adversarial_spec_tpu.ops.quant import quantize_params
    from adversarial_spec_tpu.parallel.sharding import make_device_put

    cfg = get_config(
        family, size, max_seq_len, n_layers, experts_held, vocab_rows
    )
    if cfg.ssm is not None and quant:
        from adversarial_spec_tpu.models.config import refuse_beside_state_space

        refuse_beside_state_space(cfg, f"{quant} weights")
    if checkpoint == "random":
        return _random_params(cfg, dtype, seed, quant, mesh), cfg
    if cfg.latent is not None or cfg.experts is not None or cfg.ssm is not None:
        raise NotImplementedError(
            f"{family}: only the synthetic checkpoint is wired; the "
            "published tensors' names are not mapped yet"
        )
    params = load_hf_checkpoint(
        checkpoint,
        cfg,
        family,
        dtype=dtype,
        device_put=make_device_put(mesh, dtype) if mesh is not None else None,
    )
    if quant:
        params = quantize_params(params, fmt=quant)
    return params, cfg
