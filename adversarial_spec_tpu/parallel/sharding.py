"""Parameter and cache sharding rules (Megatron-style TP via GSPMD).

The model code (models/transformer.py) is mesh-oblivious; parallelism is
expressed entirely by placing params/cache with NamedShardings and letting
GSPMD propagate through the jitted forward:

- ``wq/wk/wv`` and ``w_gate/w_up`` are column-sharded over ``tp`` (each
  device owns a slice of heads / FFN columns);
- ``wo`` and ``w_down`` are row-sharded over ``tp`` — GSPMD inserts the
  all-reduce (psum over ICI) after their matmuls;
- the KV cache shards its head axis over ``tp`` and batch over ``dp``;
- embeddings/norms are replicated; ``lm_head`` is column-sharded so the
  final logits are vocab-sharded until sampling.

This is the "NCCL-equivalent" seam of the framework (SURVEY §2.3): the
collectives exist only as XLA lowerings of these annotations.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adversarial_spec_tpu.parallel.mesh import DP, EP, TP

# Pytree path suffix → PartitionSpec. Layer-stacked params carry a leading
# n_layers dim (never sharded).
_PARAM_RULES: dict[str, P] = {
    "embed": P(),
    "final_norm": P(),
    "lm_head": P(None, TP),
    "lm_head_t": P(None, TP),
    "attn_norm": P(None, None),
    "ffn_norm": P(None, None),
    "post_attn_norm": P(None, None),
    "post_ffn_norm": P(None, None),
    "wq": P(None, None, TP),
    "wk": P(None, None, TP),
    "wv": P(None, None, TP),
    "bq": P(None, TP),
    "bk": P(None, TP),
    "bv": P(None, TP),
    "wo": P(None, TP, None),
    # Gated attention: the gate splits by head like wq; the head norms
    # are one head wide.
    "wg": P(None, None, TP),
    "q_head_norm": P(None, None),
    "k_head_norm": P(None, None),
    "w_gate": P(None, None, TP),
    "w_up": P(None, None, TP),
    "w_down": P(None, TP, None),
    # Latent attention: the query path and the up-projection split by
    # head like wq/wo; the compressed vector is one "head" and stays whole.
    "wq_a": P(None, None, None),
    "q_norm": P(None, None),
    "wq_b": P(None, None, TP),
    "wkv_a": P(None, None, None),
    "kv_norm": P(None, None),
    "wkv_b": P(None, None, TP),
    # Routed experts [L, E, in, out]: the expert axis over ``ep``, each
    # expert whole on its device; the router is everyone's.
    "w_router": P(None, None, None),
    "router_bias": P(None, None),
    "we_gate": P(None, EP, None, None),
    "we_up": P(None, EP, None, None),
    "we_down": P(None, EP, None, None),
    # State-space mixers (models/config.py StateSpace): whole on every
    # device; the family is served on one (no mesh is wired for it).
    "w_in": P(None, None, None),
    "w_out": P(None, None, None),
    "conv_w": P(None, None, None),
    "conv_b": P(None, None),
    "dt_bias": P(None, None),
    "A_log": P(None, None),
    "d_skip": P(None, None),
    "gate_norm": P(None, None),
}


def _dict_names(path) -> list[str]:
    return [
        str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)
    ]


def param_sharding_rules(path) -> P:
    names = _dict_names(path)
    if not names:
        raise ValueError(f"cannot name pytree path {path}")
    name = names[-1]
    # Quantized weights are dict leaves under the weight's name
    # (ops/quant.py): int8 {"q", "scale"}, int4 {"q4", "scale"}. "q"
    # and "q4" shard like the weight (int4 packing halves the
    # contraction axis — the axis ASSIGNMENT is unchanged); "scale"
    # ([..., 1, out]) keeps only the output-axis sharding — its kept
    # contraction axis has size 1 and must stay unsharded.
    if name in ("q", "q4", "scale") and len(names) >= 2:
        parent = _PARAM_RULES.get(names[-2])
        if parent is not None:
            if name in ("q", "q4"):
                return parent
            spec = list(parent)
            spec[-2] = None
            return P(*spec)
    if name not in _PARAM_RULES:
        raise KeyError(f"no sharding rule for param {name!r}")
    return _PARAM_RULES[name]


def _on_mesh(mesh: Mesh, spec: P) -> NamedSharding:
    """``spec`` over the axes this mesh has: an axis it lacks (``ep`` on a
    dp/sp/tp mesh, ``tp`` on an expert-parallel one) leaves that dimension
    whole on every device."""
    return NamedSharding(
        mesh, P(*(a if a in mesh.axis_names else None for a in spec))
    )


def param_shardings(mesh: Mesh, params) -> dict:
    """NamedSharding pytree matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _on_mesh(mesh, param_sharding_rules(path)),
        params,
    )


def shard_params(mesh: Mesh, params):
    """Place a host/any-device param pytree onto the mesh per the rules."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jax.device_put(
            x, _on_mesh(mesh, param_sharding_rules(path))
        ),
        params,
    )


def make_device_put(mesh: Mesh, dtype):
    """Loader hook: place each tensor as it is read (bounded host RAM).

    Host buffers go straight to their sharded placement — no intermediate
    copy on the default device.
    """
    import jax.numpy as jnp
    import ml_dtypes

    np_dtype = np.dtype(
        {jnp.bfloat16: ml_dtypes.bfloat16}.get(dtype, np.dtype(dtype))
    )

    def put(path_names: tuple, arr):
        spec = _PARAM_RULES.get(path_names[-1], P())
        if isinstance(arr, np.ndarray) and arr.dtype != np_dtype:
            arr = arr.astype(np_dtype)
        return jax.device_put(arr, _on_mesh(mesh, spec))

    return put


def cache_sharding(mesh: Mesh) -> NamedSharding:
    """KV cache [L, B, H_kv, S, D]: batch over dp, heads over tp."""
    return NamedSharding(mesh, P(None, DP, TP, None, None))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Token/batch arrays [B, ...]: rows over dp."""
    return NamedSharding(mesh, P(DP))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
