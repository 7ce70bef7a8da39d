"""A dense Llama-style decoder (grouped queries, SwiGLU, an optional bias
on Q, K and V, an untied head): what the harness asks of an architecture.

An architecture's module is found by the `model_type` of a configuration's
file (`manifest.load_architecture`) and gives four things and no more:

- `make_weights(cfg, seed, bits)` and `logits_for(cfg, weights, ids, first)`:
  the plain reference (`bits=4` is the control of the comparison);
- `weight_bytes(cfg, quant) -> dict` with `total`: the parameter tree's
  bytes, which every run prints beside the tree the program holds;
- `work(kind, reading, n_steps) -> dict | None`: bytes and FLOPs of the work
  a metric's file names under "work", over a `reducers.Reading`; None where
  the reading holds nothing to count, KeyError for a name it does not know.

This one is shared: `mistral.py` and `qwen2.py` take everything from here.
The reference is `perfbench/reference.py`, the counts are `perfbench/shapes.py`.
"""

from __future__ import annotations

from perfbench import shapes
from perfbench.reference import logits_for, make_weights  # noqa: F401
from perfbench.shapes import weight_bytes  # noqa: F401


def work(kind: str, r, n_steps: float | None):
    if kind == "decode":
        if not n_steps or not r.token_contexts:
            return None
        return shapes.decode_work(
            r.config, r.quant, int(round(n_steps)), r.token_contexts, r.row_step_contexts
        )
    if kind == "prefill":
        if not r.prefill_spans:
            return None
        return shapes.prefill_work(r.config, r.prefill_spans)
    if kind == "paged_attention":
        if not r.row_step_contexts:
            return None
        return shapes.paged_attention_work(r.config, r.token_contexts, r.row_step_contexts)
    if kind == "qmm":
        if not n_steps:
            return None
        return shapes.qmm_work(r.config, r.quant, int(round(n_steps)), r.rows)
    raise KeyError(f"unknown work {kind!r}")
