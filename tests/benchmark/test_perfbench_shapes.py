"""The shape functions against hand-computed bytes and FLOPs, and against
the parameter tree the program really builds (its shapes, at full size)."""

import json
from pathlib import Path

import pytest

from perfbench import shapes

BENCH = Path(__file__).resolve().parents[2] / "perfbench"


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


MISTRAL, QWEN = _cfg("mistral-7b-int8"), _cfg("qwen2-7b-int8")


def test_kv_bytes_per_token_by_hand():
    # keys and values x layers x kv heads x head size x 2 bytes
    assert shapes.kv_bytes_per_token(MISTRAL) == 2 * 32 * 8 * 128 * 2 == 131072
    assert shapes.kv_bytes_per_token(QWEN) == 2 * 28 * 4 * 128 * 2 == 57344


def test_parameter_counts_by_hand():
    assert shapes.head_params(MISTRAL) == 4096 * 32768 == 134_217_728
    assert shapes.head_params(QWEN) == 3584 * 152064 == 544_997_376
    # wq, wo 4096x4096; wk, wv 4096x1024; three of 4096x14336
    assert shapes.layer_params(MISTRAL) == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert shapes.layer_params(QWEN) == 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944


def test_int8_weight_bytes_by_hand():
    w = shapes.weight_bytes(MISTRAL, "int8")
    per_layer_outs = 4096 + 1024 + 1024 + 4096 + 14336 + 14336 + 4096
    assert w["layers_matmul"] == 32 * (shapes.layer_params(MISTRAL) + 4 * per_layer_outs)
    assert w["lm_head"] == 4096 * 32768 + 4 * 32768
    assert w["embed"] == 2 * 32768 * 4096
    assert 7.3e9 < w["total"] < 7.5e9  # "7.39 GB of weights"
    q = shapes.weight_bytes(QWEN, "int8")
    assert q["layers_small"] == 28 * (2 * 2 * 3584 + 2 * (3584 + 2 * 512))  # norms and QKV bias
    assert q["lm_head"] == 3584 * 152064 + 4 * 152064


@pytest.mark.parametrize("cfg", [MISTRAL, QWEN], ids=["mistral", "qwen2"])
def test_weight_bytes_equal_the_tree_the_program_builds(cfg):
    """Read from the tree, not assumed: the program's own init and
    quantization, traced for their shapes at the published widths."""
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models.config import get_config
    from adversarial_spec_tpu.models.transformer import init_params
    from adversarial_spec_tpu.ops.quant import quantize_params

    s = cfg["serving"]
    mc = get_config(s["family"], s["size"], s.get("max_seq_len", 0), 0)
    tree = jax.eval_shape(
        lambda k: quantize_params(init_params(k, mc, jnp.bfloat16), fmt=s["quant"]),
        jax.random.key(0),
    )

    def nbytes(node):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(node))

    want = shapes.weight_bytes(cfg, s["quant"])
    layers = tree["layers"]
    mm = sum(nbytes(v) for k, v in layers.items() if k.startswith("w"))
    assert mm == want["layers_matmul"]
    assert nbytes(layers) - mm == want["layers_small"]
    assert nbytes(tree["lm_head"]) == want["lm_head"]
    assert nbytes(tree["embed"]) == want["embed"]
    assert nbytes(tree) == want["total"]
    # and the configuration file states the sizes the program runs
    sz = shapes.sizes(cfg)
    assert (mc.dim, mc.n_layers, mc.ffn_dim, mc.vocab_size) == (sz["D"], sz["L"], sz["F"], sz["V"])
    assert (mc.n_heads * mc.head_dim, mc.n_kv_heads * mc.head_dim) == (sz["QD"], sz["KD"])
    assert mc.qkv_bias == sz["bias"] and mc.rms_eps == cfg["rms_norm_eps"]
    assert mc.rope_theta == cfg["rope_theta"]


def _weights_a_step(cfg):
    wb = shapes.weight_bytes(cfg, "int8")
    return wb["layers_matmul"] + wb["layers_small"] + wb["final_norm"] + wb["lm_head"]


def test_decode_work_counts_useful_work_only():
    # 2 steps of 4 rows, every row emitting 2 tokens a step: the weights twice, every row's
    # keys and values once a step (8 row-steps, the row 1002 long after its first, 1004
    # after its second), and 16 tokens' arithmetic, embedding rows and own K/V written
    tokens = [1000, 1001] * 4 + [1002, 1003] * 4
    row_steps = [1002] * 4 + [1004] * 4
    w = shapes.decode_work(MISTRAL, "int8", 2, tokens, row_steps)
    kv_read = (4 * 1002 + 4 * 1004) * 131072
    assert w["bytes"] == 2 * _weights_a_step(MISTRAL) + kv_read + 16 * (2 * 4096 + 131072)
    stack_and_head = 2 * (32 * shapes.layer_params(MISTRAL) + shapes.head_params(MISTRAL))
    assert w["flops"] == 16 * stack_and_head + 4 * sum(tokens) * 4096 * 32
    # bandwidth-bound by far: the least time is bytes over 819 GB/s
    least, bound = shapes.least_seconds(w, shapes.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and least == pytest.approx(w["bytes"] / 819e9)


def test_a_steps_second_token_costs_arithmetic_and_no_second_read_of_the_rows_kv():
    """The same steps, rows and lengths, emitting 1 token a delivery or 2:
    the same keys and values to read, twice the arithmetic."""
    row_steps = [5000, 5000, 6000, 6000] * 3  # 3 steps of 4 rows
    one = [c - 1 for c in row_steps]
    two = [c - 1 for c in row_steps for _ in range(2)]  # both tokens at the row's length
    a1 = shapes.paged_attention_work(QWEN, one, row_steps)
    a2 = shapes.paged_attention_work(QWEN, two, row_steps)
    assert a1["bytes"] == a2["bytes"] == sum(row_steps) * 57344
    assert a2["flops"] == 2 * a1["flops"]
    d1 = shapes.decode_work(QWEN, "int8", 3, one, row_steps)
    d2 = shapes.decode_work(QWEN, "int8", 3, two, row_steps)
    per_token = 2 * 3584 + 57344  # its embedding row, its own K/V written
    kv_term = sum(row_steps) * 57344
    assert d1["bytes"] - len(one) * per_token == d2["bytes"] - len(two) * per_token
    assert d1["bytes"] - len(one) * per_token == 3 * _weights_a_step(QWEN) + kv_term
    assert d2["flops"] == 2 * d1["flops"]


def test_prefill_work_by_hand():
    w = shapes.prefill_work(QWEN, [(0, 3), (10, 12)])
    stack = 2 * 28 * shapes.layer_params(QWEN)
    attn1 = 4 * 1 * 3584 * 28
    assert w["tokens"] == 5
    assert w["flops"] == 5 * stack + attn1 * ((1 + 2 + 3) + (11 + 12)) + 2 * 2 * shapes.head_params(QWEN)
    assert shapes.prefill_work(QWEN, [(7, 7)])["flops"] == 0


def test_kernel_work_by_hand():
    # two tokens of one row in one step, the row 21 long after it: its K/V once
    a = shapes.paged_attention_work(MISTRAL, [19, 20], [21])
    assert a["bytes"] == 21 * 131072
    assert a["flops"] == 4 * (19 + 20) * 4096 * 32
    assert shapes.paged_attention_work(MISTRAL, [10, 20], [11, 21])["bytes"] == 32 * 131072
    q = shapes.qmm_work(MISTRAL, "int8", 3, 4)
    acts = 32 * 2 * sum(i + o for _, i, o in shapes.layer_matmuls(MISTRAL))
    assert q["bytes"] == 3 * (shapes.weight_bytes(MISTRAL, "int8")["layers_matmul"] + 4 * acts)
    assert q["flops"] == 3 * 4 * 2 * 32 * shapes.layer_params(MISTRAL)


def test_an_unknown_device_is_an_error_not_a_default():
    assert shapes.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        shapes.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        shapes.peaks_for("source")
