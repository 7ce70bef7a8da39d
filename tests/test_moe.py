"""The routed feed-forward layer (models/moe.py) and its grouped int8 matmul
(ops/pallas_quant.py ``matmul_int8_grouped``), at tiny size on the CPU.

The share test ties the chip's share of a deployment to the model: the parts
of the routed result that the four expert shares give, with what every chip
computes alike (attention, the shared expert) counted once, add up to what
the plain reference (perfbench/architectures/mistral4.py) gives for the
uncut layer.
"""

import importlib.util
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.models import moe
from adversarial_spec_tpu.models import transformer as tf
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.ops import pallas_quant, quant

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "perfbench"


def _reference():
    spec = importlib.util.spec_from_file_location(
        "m4ref", BENCH / "architectures/mistral4.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_file_config(held):
    """The benchmark configuration's file at its rehearsal sizes, holding
    ``held`` = [first, count] of the 8 routed experts."""
    cfg = json.loads(
        (BENCH / "configs/mistral-small-4-int8-ep4.json").read_text()
    )
    from perfbench.manifest import _merge

    cfg = _merge(cfg, cfg["rehearsal"])
    cfg["serving"]["experts_held"] = list(held)
    cfg["n_routed_experts"] = held[1]
    return cfg


# -- (d) the grouped matmul against a per-expert loop -------------------------


def _stack(key, L, E, K, N):
    w = jax.random.normal(key, (L, E, K, N), jnp.float32) / np.sqrt(K)
    return quant.quantize_int8(w)


@pytest.mark.parametrize(
    "choice",
    ["spread", "empty_experts", "all_on_one", "none_held"],
)
@pytest.mark.parametrize("kernel", ["pallas_interpret", "einsum"])
def test_grouped_matmul_equals_a_per_expert_loop(choice, kernel):
    """Rows grouped by expert times each group's own weight, against
    multiplying every expert's rows one expert at a time: with experts
    that got no row, with every row on one expert, and with no row on a
    held expert at all."""
    L, E, K, N, T, k = 2, 4, 128, 256, 12, 2
    ex = replace(get_config("mistral4", "tiny").experts, held=(2, E))
    w = _stack(jax.random.key(0), L, E, K, N)
    x = jax.random.normal(jax.random.key(1), (T, K), jnp.float32)
    idx = {
        "spread": jax.random.randint(jax.random.key(2), (T, k), 0, 8),
        "empty_experts": jnp.full((T, k), 3).at[:, 1].set(7),  # 3 held, 7 not
        "all_on_one": jnp.full((T, k), 4),
        "none_held": jnp.full((T, k), 0).at[:, 1].set(7),
    }[choice].astype(jnp.int32)
    layer = jnp.int32(1)
    bm = moe._tile_rows(T * k, E)
    dest, tile_group, n_live, counts, M = moe.group_pairs(idx, ex, bm)
    token = jnp.repeat(jnp.arange(T), k)
    src = jnp.zeros((M,), jnp.int32).at[dest.reshape(-1)].set(token, mode="drop")
    y = moe.grouped_matmul(
        x[src], w, layer, tile_group, n_live, bm,
        use_pallas=kernel == "pallas_interpret", interpret=True,
    )
    dense = quant.dequantize(w)[1]  # [E, K, N]
    held = np.asarray(dest) < M
    assert int(np.asarray(counts).sum()) == held.sum()
    for t in range(T):
        for j in range(k):
            if not held[t, j]:
                continue
            want = x[t] @ dense[int(idx[t, j]) - 2]
            np.testing.assert_allclose(
                y[int(dest[t, j])], want, rtol=2e-5, atol=2e-5
            )
    if choice == "none_held":
        assert int(n_live) == 0 and not held.any()
    if choice == "all_on_one":
        assert set(np.asarray(tile_group)[: int(n_live)]) == {2}


def test_a_stacked_weight_never_falls_back_without_a_word():
    """ops.quant.matmul's fused path refuses a stack aloud (it used to
    hand it to XLA silently); int4 stacks have no grouped path."""
    w = _stack(jax.random.key(0), 1, 2, 128, 128)
    x = jnp.ones((8, 128), jnp.float32)
    with pytest.raises(ValueError, match="matmul_int8_grouped"):
        pallas_quant.fused_supported(x, w)
    w4 = quant.quantize_int4(jnp.ones((1, 2, 128, 128), jnp.float32))
    with pytest.raises(NotImplementedError):
        moe.grouped_matmul(
            x, w4, 0, jnp.zeros((1,), jnp.int32), 1, 8,
            use_pallas=False, interpret=False,
        )


# -- (c) the share test ---------------------------------------------------------


def program_params(cfg, seed=0):
    """The synthetic checkpoint as the registry serves it (bfloat16 weights,
    int8 per output channel: the recipe the reference follows), with what
    stays floating point widened to float32 so that the program computes as
    the reference does."""
    params = quant.quantize_params(
        tf.init_params(jax.random.key(seed), cfg, dtype=jnp.bfloat16)
    )
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params,
    )


def _program_layer0(cfg, params, tokens):
    """Layer 0's output x_1 [T, D] as the program's prefill computes it:
    one layer of the stack, float32."""
    one = replace(cfg, n_layers=1)
    layers = jax.tree.map(lambda a: a[:1], params["layers"])
    T = tokens.shape[1]
    cache = tf.init_cache(one, 1, T, dtype=jnp.float32)
    captured = {}
    real = tf._lm_head_logits

    def grab(params_, cfg_, x, *a, **k):
        captured["x"] = x
        return real(params_, cfg_, x, *a, **k)

    tf._lm_head_logits = grab
    try:
        tf.forward(
            {**params, "layers": layers}, one, tokens,
            jnp.arange(T)[None], cache, jnp.int32(0), jnp.ones((1, T), bool),
        )
    finally:
        tf._lm_head_logits = real
    return captured["x"][0]


@pytest.mark.parametrize("placement", ["one_device", "four_cpu_devices"])
def test_the_four_shares_add_up_to_the_uncut_layer(placement):
    """x_1 = x_0 + attention + shared expert + sum over ALL experts. Each
    share computes x_0 + attention + shared + ITS experts' part, so the
    four shares' outputs minus three times what they all compute alike
    (the share holding no expert... is not a share: the common part is a
    share's output minus its routed part) equal the uncut reference."""
    ref = _reference()
    T = 96  # past the tiny original_max of 64: the query scaling acts too
    tokens = jax.random.randint(jax.random.key(5), (1, T), 3, 259)
    uncut_cfg = _tiny_file_config([0, 8])
    weights = ref.make_weights(uncut_cfg, seed=0, bits=8)

    # the uncut layer, by the plain reference
    s = ref._static(ref.sizes(uncut_cfg))
    with jax.default_matmul_precision("highest"):
        x0 = ref._embed_rows(weights["embed"], tokens[0])
        attn_w = {n: weights[n] for n in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
        x_attn = ref._attention(x0, jnp.int32(0), attn_w, s=s)
        uncut = ref._ffn(
            x_attn, jnp.int32(0), {n: weights[n] for n in ("w_gate", "w_up", "w_down")},
            weights["w_router"], {n: weights[n] for n in ref.EXPERT}, s=s,
        )

    # the four shares, by the program (the vocabulary whole: layer 0 only)
    outs = []
    for first in (0, 2, 4, 6):
        cfg = get_config("mistral4", "tiny", experts_held=(first, 2), vocab_rows=384)
        params = program_params(cfg)
        if placement == "four_cpu_devices":
            from jax.sharding import Mesh

            from adversarial_spec_tpu.parallel.mesh import EP
            from adversarial_spec_tpu.parallel.sharding import (
                param_shardings,
                shard_params,
            )

            devs = jax.devices()
            if len(devs) < 2:
                pytest.skip("needs virtual CPU devices")
            mesh = Mesh(np.asarray(devs[:2]), (EP,))
            sh = param_shardings(mesh, params)
            # the expert axis over ep, everything else whole on each device
            assert sh["layers"]["we_up"]["q"].spec[1] == EP
            assert all(a is None for a in sh["layers"]["wq_a"]["q"].spec)
            params = shard_params(mesh, params)
        outs.append(_program_layer0(cfg, params, tokens))

    # what every share computes alike: a share's output minus its routed part
    # = x_0 + attention + shared expert. Take it from the reference's own
    # pieces (the shares' attention and shared expert are the uncut model's).
    no_expert = ref._ffn(
        x_attn, jnp.int32(0), {n: weights[n] for n in ("w_gate", "w_up", "w_down")},
        weights["w_router"], {n: weights[n] for n in ref.EXPERT},
        s=ref._static({**dict(s), "held": 0}),
    )
    total = sum(np.asarray(o, np.float64) for o in outs) - 3 * np.asarray(
        no_expert, np.float64
    )
    # float32 throughout; the program's int8 weights are the reference's
    # (same recipe, same values), so only summation order differs
    np.testing.assert_allclose(total, np.asarray(uncut), rtol=2e-3, atol=2e-3)
    # ... and no share alone is the uncut layer (each leaves something out)
    assert all(
        np.abs(np.asarray(o) - np.asarray(uncut)).max() > 1e-2 for o in outs
    )


def test_expert_weights_follow_the_global_expert_index():
    """A share's stack holds the values the uncut model has for those
    experts: piece (layer, expert of ALL) draws from its own key."""
    whole = tf.init_params(
        jax.random.key(3), get_config("mistral4", "tiny"), dtype=jnp.float32
    )
    share = tf.init_params(
        jax.random.key(3), get_config("mistral4", "tiny", experts_held=(5, 2)),
        dtype=jnp.float32,
    )
    for name in moe.EXPERT_WEIGHTS:
        np.testing.assert_array_equal(
            whole["layers"][name][:, 5:7], share["layers"][name]
        )
    # quantized piece by piece as drawn, or as one stack afterwards: the
    # same int8 values (a value in a few hundred thousand may sit on a
    # rounding edge that the compiled and the eager division split)
    piecewise = tf.init_params(
        jax.random.key(3), get_config("mistral4", "tiny"), dtype=jnp.float32,
        expert_quant="int8",
    )
    stacked = quant.quantize_params(whole)
    for name in moe.EXPERT_WEIGHTS:
        a = np.asarray(piecewise["layers"][name]["q"], np.int32)
        b = np.asarray(stacked["layers"][name]["q"], np.int32)
        assert np.abs(a - b).max() <= 1 and (a != b).mean() < 1e-4
        np.testing.assert_allclose(
            piecewise["layers"][name]["scale"], stacked["layers"][name]["scale"],
            rtol=1e-6,
        )


def test_routing_stats_count_pairs_experts_and_the_busiest():
    ex = replace(get_config("mistral4", "tiny").experts, held=(2, 4))
    idx = jnp.asarray([[2, 3], [2, 7], [5, 2], [0, 1]], jnp.int32)
    # held experts 2..5: expert 2 three times, 3 once, 5 once
    np.testing.assert_array_equal(moe.routing_stats(idx, ex), [5, 3, 3])
    mask = jnp.asarray([True, False, False, True])
    np.testing.assert_array_equal(moe.routing_stats(idx, ex, mask), [2, 2, 1])


@pytest.mark.parametrize("family,routed", [("mistral4", True), ("mistral", False)])
def test_the_scheduler_decides_the_admission_prefills_kernels(family, routed, monkeypatch):
    """One layer decides which matmul an admission prefill takes: the
    batcher hands `forward` the flag (a routed family's expert stacks are
    read by the grouped kernel in prefill as in decode; a dense family's
    prefill keeps XLA's dequant-matmul), and the model takes what it is
    given. The served tokens are the same either way."""
    from adversarial_spec_tpu.engine import scheduler as sched

    cfg = get_config(family, "tiny")
    params = quant.quantize_params(
        tf.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    )
    given = []
    real = sched.prefill_chunk

    def tapped(*a, **k):
        given.append(k.get("use_pallas_matmul"))
        return real(*a, **k)

    monkeypatch.setattr(sched, "prefill_chunk", tapped)

    def serve(use_pallas_matmul):
        b = sched.ContinuousBatcher(
            params, cfg, max_batch=2, capacity_tokens=1024, max_new_cap=8,
            eos_ids=[], greedy=True, use_pallas_matmul=use_pallas_matmul,
        )
        assert b._prefill_pallas_matmul is (use_pallas_matmul and routed)
        b.submit(sched.SchedRequest(req_id=0, prompt_ids=list(range(5, 45)), max_new_tokens=6))
        return list(b.run_all()[0].tokens)

    with_kernels = serve(True)
    assert given and all(g is routed for g in given)
    del given[:]
    assert serve(False) == with_kernels
    assert given and not any(given)


def test_generate_hands_its_prefill_the_same_kernels(monkeypatch):
    """`generate()` (a registry entry with `kv: dense`, the default) makes
    the same choice for its prefill chunks as the batcher, and serves the
    same tokens with the kernels as without."""
    from adversarial_spec_tpu.engine import generate as gen

    cfg = get_config("mistral4", "tiny")
    params = quant.quantize_params(
        tf.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    )
    given = []
    real = gen.prefill_chunk

    def tapped(*a, **k):
        given.append(k.get("use_pallas_matmul"))
        return real(*a, **k)

    monkeypatch.setattr(gen, "prefill_chunk", tapped)
    prompts = [list(range(5, 45)), list(range(7, 47))]
    run = lambda flag: gen.generate(  # noqa: E731
        params, cfg, prompts, max_new_tokens=6, eos_ids=[], greedy=True,
        use_pallas_matmul=flag, speculative=False,
    ).tokens
    with_kernels = run(True)
    assert given and all(given)
    del given[:]
    np.testing.assert_array_equal(run(False), with_kernels)
    assert given and not any(given)
