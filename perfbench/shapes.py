"""What the algorithm needs, from the model's own sizes: parameters, bytes
and FLOPs of a decode step and of a prefill, and the least time a chip
with given peaks could take for them.

Nothing here looks at how the program does the work: a decode step reads
each weight once and each live row's keys and values once (once a step
in which the row took part, however many tokens that step emitted for
it), and computes the tokens it emits, not the positions a verify step
spends. A dense
decoder of another size needs no code, only its configuration file (the
Hugging Face key names). These are the dense decoder's terms, and
`architectures/dense.py` is what calls them for the harness. A new kind of
layer (experts, a latent cache, a linear-attention state) brings its own
terms in its own `architectures/<model_type>.py` (its `weight_bytes` and the
branches of its `work`), and imports from here what is common:
`matmul_weight_bytes`, `least_seconds`, `peaks_for`.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}: "
            "add a row with its source, a default would make every share wrong"
        )
    return table[device_kind]


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    head_dim = int(cfg.get("head_dim") or d // heads)
    return {
        "D": d,
        "L": int(cfg["num_hidden_layers"]),
        "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "QD": heads * head_dim,
        "KD": int(cfg["num_key_value_heads"]) * head_dim,
        "bias": bool(cfg.get("attention_bias", False)),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def layer_matmuls(cfg: dict) -> list[tuple]:
    """(name, in, out) of one layer's matrix multiplications."""
    s = sizes(cfg)
    return [
        ("wq", s["D"], s["QD"]),
        ("wk", s["D"], s["KD"]),
        ("wv", s["D"], s["KD"]),
        ("wo", s["QD"], s["D"]),
        ("w_gate", s["D"], s["F"]),
        ("w_up", s["D"], s["F"]),
        ("w_down", s["F"], s["D"]),
    ]


def layer_params(cfg: dict) -> int:
    return sum(i * o for _, i, o in layer_matmuls(cfg))


def head_params(cfg: dict) -> int:
    s = sizes(cfg)
    return s["D"] * s["V"]


def matmul_weight_bytes(n_in: int, n_out: int, quant: str) -> int:
    """Bytes of one weight as the parameter tree holds it: int8 values and
    one float32 scale per output channel, or bfloat16."""
    if quant == "int8":
        return n_in * n_out + 4 * n_out
    return 2 * n_in * n_out


def weight_bytes(cfg: dict, quant: str) -> dict:
    """Bytes of the parameter tree by part, as it sits in device memory."""
    s = sizes(cfg)
    layer_mm = sum(matmul_weight_bytes(i, o, quant) for _, i, o in layer_matmuls(cfg))
    layer_small = 2 * 2 * s["D"]  # two norms, bfloat16
    if s["bias"]:
        layer_small += 2 * (s["QD"] + 2 * s["KD"])
    out = {
        "layers_matmul": s["L"] * layer_mm,
        "layers_small": s["L"] * layer_small,
        "embed": 2 * s["V"] * s["D"],
        "final_norm": 2 * s["D"],
        "lm_head": 0 if s["tied"] else matmul_weight_bytes(s["D"], s["V"], quant),
    }
    out["total"] = sum(out.values())
    return out


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """Keys and values of one token over all layers (bfloat16 unless said)."""
    s = sizes(cfg)
    return 2 * s["L"] * s["KD"] * kv_itemsize


def attention_flops(cfg: dict, context: int) -> int:
    """One new token attending to `context` positions, all layers: scores
    and the weighted sum, two FLOPs a multiply-add."""
    s = sizes(cfg)
    return 4 * context * s["QD"] * s["L"]


def token_flops(cfg: dict, context: int, with_head: bool = True) -> int:
    """One token through the stack (and the head) at a context length."""
    flops = 2 * sizes(cfg)["L"] * layer_params(cfg) + attention_flops(cfg, context)
    if with_head:
        flops += 2 * head_params(cfg)
    return flops


def decode_work(
    cfg: dict, quant: str, n_steps: int, token_contexts: list[int], row_step_contexts: list[int]
) -> dict:
    """Bytes and FLOPs of `n_steps` decode steps that emitted one token at
    each of `token_contexts` (the context length of the row that emitted
    it), a row taking part in a step once for each of `row_step_contexts`
    (the row's length after that step): the weights once a step, each
    row's keys and values once a step in which it took part, the emitted
    tokens' arithmetic."""
    w = weight_bytes(cfg, quant)
    per_step = w["layers_matmul"] + w["layers_small"] + w["final_norm"] + w["lm_head"]
    s = sizes(cfg)
    kv = kv_bytes_per_token(cfg)
    return {
        "bytes": n_steps * per_step
        + sum(row_step_contexts) * kv
        + len(token_contexts) * (2 * s["D"] + kv),  # its embedding row, its own K/V written
        "flops": sum(token_flops(cfg, c) for c in token_contexts),
    }


def prefill_work(cfg: dict, spans: list[tuple]) -> dict:
    """FLOPs of prefilling positions [start, end) of each prompt: every
    token through the stack at its own context length, the head once."""
    s = sizes(cfg)
    stack = 2 * s["L"] * layer_params(cfg)
    flops = 0
    tokens = 0
    for start, end in spans:
        n = max(0, end - start)
        tokens += n
        # sum of contexts start+1 .. end
        ctx_sum = (start + 1 + end) * n // 2
        flops += n * stack + attention_flops(cfg, 1) * ctx_sum
        if n:
            flops += 2 * head_params(cfg)
    return {"flops": flops, "tokens": tokens, "bytes": 0}


def paged_attention_work(
    cfg: dict, token_contexts: list[int], row_step_contexts: list[int]
) -> dict:
    """The decode attention kernel alone: each row's live keys and values
    once a step in which it took part (no kernel has to read them twice
    for a step's second token), and the emitted tokens' arithmetic."""
    return {
        "bytes": sum(row_step_contexts) * kv_bytes_per_token(cfg),
        "flops": sum(attention_flops(cfg, c) for c in token_contexts),
    }


def qmm_work(cfg: dict, quant: str, n_steps: int, rows_per_step: float) -> dict:
    """The layers' dequant-matmuls of `n_steps` decode steps: each weight
    once a step as the tree holds it, the rows' activations in and out in
    bfloat16, and the arithmetic of one token a row."""
    w = weight_bytes(cfg, quant)
    s = sizes(cfg)
    act = sum(2 * (i + o) for _, i, o in layer_matmuls(cfg)) * s["L"]
    return {
        "bytes": n_steps * (w["layers_matmul"] + rows_per_step * act),
        "flops": n_steps * rows_per_step * 2 * s["L"] * layer_params(cfg),
    }


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip needs, and which peak bounds it."""
    t_bytes = work.get("bytes", 0) / peaks["hbm_bytes_per_s"]
    t_flops = work.get("flops", 0) / peaks["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
