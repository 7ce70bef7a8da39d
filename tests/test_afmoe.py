"""The `afmoe` family (Trinity) through the program's forwards, its paged
cache and the batcher, at tiny size on the CPU, against the plain reference
(perfbench/architectures/afmoe.py) that imports nothing of the program.
Logits, not tokens, wherever a forward can be called; each tolerance with
its reason.

The tiny preset has two leading dense layers (a windowed one and a global
one: the prefix is a stack, not a special case of one layer) and a period
of two; its window is 128, so the 400-token sequences here are over three
windows long.
"""

import importlib.util
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu import obs as obs_mod
from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
from adversarial_spec_tpu.engine import spec as spec_mod
from adversarial_spec_tpu.engine.kvcache import (
    PagedCacheLayout,
    init_page_pool,
    write_tokens,
)
from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher, SchedRequest
from adversarial_spec_tpu.models import config as config_mod
from adversarial_spec_tpu.models import moe
from adversarial_spec_tpu.models import transformer as tf
from adversarial_spec_tpu.models.config import get_config
from tests.benchmark.fault_rehearsal_trinity import FAULTS, plant
from tests.test_moe import program_params

BENCH = Path(__file__).resolve().parents[1] / "perfbench"
PAGE = 8
HELD = [2, 4]  # the rehearsal's share: experts 2..5 of 8
T, N_PRE = 400, 300  # over three windows of 128; prefilled, then paged
# Both sides compute in float32 from the same int8 weights and differ by
# summation order alone: a few 1e-6 of logits whose deviation is ~0.9 (read:
# 6e-6). A hundred times that still lies a hundred times under what any
# planted fault or the int4 control moves a logit by (over 0.05).
TOL = 5e-4
FAULT_MOVES = 0.05


def _reference():
    spec = importlib.util.spec_from_file_location(
        "afmoe_ref", BENCH / "architectures/afmoe.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_file_config(held=HELD):
    """The benchmark configuration's file at its rehearsal sizes, holding
    ``held`` = [first, count] of the 8 routed experts."""
    from perfbench.manifest import _merge

    cfg = json.loads((BENCH / "configs/trinity-large-int8-ep8.json").read_text())
    cfg = _merge(cfg, cfg["rehearsal"])
    cfg["serving"]["experts_held"] = list(held)
    cfg["num_experts"] = held[1]
    return cfg


def _tiny():
    return get_config("afmoe", "tiny", experts_held=HELD, vocab_rows=384)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(11), (1, T), 3, 259)


@pytest.fixture(scope="module")
def want(tokens):
    """The reference's logits over the whole sequence, one full forward."""
    ref, file_cfg = _reference(), _tiny_file_config()
    weights = ref.make_weights(file_cfg, seed=0, bits=8)
    return ref.logits_for(file_cfg, weights, list(map(int, tokens[0])), 0)


TABLE = jnp.asarray([list(range(1, 53)) + [-1]], jnp.int32)


def _span(start, width, first_slot=0):
    q_pos = (start + jnp.arange(width))[None]
    wp = jnp.take_along_axis(TABLE, q_pos // PAGE, axis=1)
    bounds = jnp.stack([jnp.full_like(q_pos, first_slot), q_pos + 1], -1)
    return q_pos, wp, q_pos % PAGE, bounds


def _prefill_into_pages(cfg, params, tokens, n_pre):
    """``n_pre`` tokens through the dense admission cache, in two chunks
    (the second over the cache the first left), handed to the pages."""
    cache = tf.init_cache(cfg, 1, n_pre + 4, dtype=jnp.float32)
    valid = jnp.ones((1, n_pre + 4), bool)
    cut = 200  # past the first window: the second chunk's window starts inside the first
    pos = jnp.arange(n_pre)[None]
    first, cache = tf.forward(
        params, cfg, tokens[:, :cut], pos[:, :cut], cache, jnp.int32(0), valid
    )
    second, cache = tf.forward(
        params, cfg, tokens[:, cut:n_pre], pos[:, cut:], cache, jnp.int32(cut), valid
    )
    heads, k_dim, v_dim = cfg.kv_layout
    pool = init_page_pool(
        PagedCacheLayout(
            n_pages=53, page_size=PAGE, n_layers=cfg.n_layers, n_kv_heads=heads,
            head_dim=k_dim, v_dim=v_dim,
        ),
        dtype=jnp.float32,
    )
    pages = np.repeat(np.asarray(TABLE), PAGE, axis=1)[:, :n_pre]
    offs = np.tile(np.arange(PAGE), 53)[None, :n_pre]
    pool = write_tokens(
        pool, cache["k"][..., :n_pre, :], cache["v"][..., :n_pre, :], pages, offs
    )
    return np.concatenate([first, second], axis=1), pool


def _program_logits(cfg, params, tokens, widths=(9, 1, 1, 6, 1, 64, 18)):
    """The program's logits over the whole sequence: prefill, then spans
    through the paged cache: a verify span of 9, single tokens, an
    admission's 64-wide delta."""
    got, pool = _prefill_into_pages(cfg, params, tokens, N_PRE)
    got, at = [got], N_PRE
    for width in widths:
        q_pos, wp, wo, bounds = _span(at, width)
        out, pool, _ = tf.forward_paged_decode(
            params, cfg, tokens[:, at : at + width], q_pos, pool, TABLE, wp, wo,
            bounds, q_pos,
        )
        got.append(np.asarray(out))
        at += width
    assert at == T
    return np.concatenate(got, axis=1)[0]


# -- (a) the program against the reference's full forward -----------------------


def test_prefill_then_paged_decode_agree_with_the_reference(tokens, want):
    """Prefill 300 tokens in two chunks through the dense admission cache,
    hand them to the pages, then spans through the paged cache to 400:
    every logit against the reference's one full forward. The windowed
    layers' bounds are in both forwards (a chunk's mask, a span's starts),
    the global layers rotate nothing in either."""
    got = _program_logits(_tiny(), program_params(_tiny()), tokens)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_the_int4_control_is_failed_by_logits(tokens, want):
    ref, file_cfg = _reference(), _tiny_file_config()
    low = ref.logits_for(
        file_cfg, ref.make_weights(file_cfg, seed=0, bits=4),
        list(map(int, tokens[0])), 0,
    )
    assert np.abs(low - want).max() > FAULT_MOVES


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_is_failed_by_logits(monkeypatch, tokens, want, fault):
    """The faults `limits/trinity-large-int8-ep8.json` reads on the chip,
    planted here under the same forwards: each moves some logit by far more
    than the tolerance. (What `correct` sees of each at the cell's size is
    another matter: the limits file says.)"""
    plant(fault, monkeypatch.setattr)
    cfg = _tiny()
    got = _program_logits(cfg, program_params(cfg), tokens)
    assert np.abs(got - want).max() > FAULT_MOVES, fault


# -- (b) a prefix hit admitted over adopted pages --------------------------------


def test_a_delta_over_adopted_pages_agrees_with_the_reference(tokens, want):
    """What `paged_admission` runs for a cached prompt: the pages of the
    matched prefix hold what an earlier admission wrote (here: 296 of the
    300 prefilled tokens, a page-aligned match), and the delta is one
    span over them, padded to 64, its logits taken at the last real
    position alone. The delta's windowed layers start inside the adopted
    pages; the pads beyond the prompt write to the trash page."""
    cfg = _tiny()
    params = program_params(cfg)
    _, pool = _prefill_into_pages(cfg, params, tokens, N_PRE)
    matched, end, width = 296, 330, 64
    q_pos, wp, wo, bounds = _span(matched, width)
    real = q_pos < end
    padded = jnp.where(real, tokens[:, matched : matched + width], 0)
    out, pool, _ = tf.forward_paged_decode(
        params, cfg, padded, q_pos, pool, TABLE, jnp.where(real, wp, 0), wo,
        bounds, q_pos, logits_at=jnp.asarray([end - 1 - matched], jnp.int32),
    )
    assert np.abs(np.asarray(out)[0, 0] - want[end - 1]).max() < TOL
    # ... and the sequence decodes on from there over the same pages
    q_pos, wp, wo, bounds = _span(end, 9)
    nxt, _, _ = tf.forward_paged_decode(
        params, cfg, tokens[:, end : end + 9], q_pos, pool, TABLE, wp, wo,
        bounds, q_pos,
    )
    assert np.abs(np.asarray(nxt)[0] - want[end : end + 9]).max() < TOL


# -- (c) a verify step with a partial acceptance ---------------------------------


def test_a_partly_accepted_verify_span_leaves_the_cache_sound(tokens, want):
    """A verify span of 9 whose tokens are wrong from its fourth position
    on: the first three positions' logits are the reference's, the
    rejected positions' keys and values stay in their slots above the
    accepted prefix, and the next span, which starts there and overwrites
    them, reads the reference's logits again (a family with no recurrent
    state has nothing for `commit_span` to roll back: the pool holds no
    span)."""
    cfg = _tiny()
    params = program_params(cfg)
    _, pool = _prefill_into_pages(cfg, params, tokens, N_PRE)
    drafts = tokens[:, N_PRE : N_PRE + 9].at[:, 3:].set(7)
    q_pos, wp, wo, bounds = _span(N_PRE, 9)
    out, pool, _ = tf.forward_paged_decode(
        params, cfg, drafts, q_pos, pool, TABLE, wp, wo, bounds, q_pos
    )
    assert "span" not in pool
    assert np.abs(np.asarray(out)[0, :3] - want[N_PRE : N_PRE + 3]).max() < TOL
    assert np.abs(np.asarray(out)[0, 3:] - want[N_PRE + 3 : N_PRE + 9]).max() > FAULT_MOVES
    at = N_PRE + 3  # two drafts accepted; the bonus token starts the next span
    q_pos, wp, wo, bounds = _span(at, 9)
    out, pool, _ = tf.forward_paged_decode(
        params, cfg, tokens[:, at : at + 9], q_pos, pool, TABLE, wp, wo, bounds,
        q_pos,
    )
    assert np.abs(np.asarray(out)[0] - want[at : at + 9]).max() < TOL


# -- (d) through the batcher -----------------------------------------------------


@pytest.fixture()
def _fresh_state():
    prev = spec_mod.config()
    prefix_mod.configure(enabled=True, max_pages=0)
    prefix_mod.reset_stats()
    spec_mod.reset_stats()
    yield
    spec_mod.configure(enabled=prev.enabled, gamma=prev.gamma)
    prefix_mod.reset_stats()
    spec_mod.reset_stats()


def _serve(batcher, prompt, req_id):
    batcher.submit(
        SchedRequest(req_id=req_id, prompt_ids=list(prompt), max_new_tokens=12)
    )
    [res] = batcher.run_all()
    assert res.error is None, res.error
    return [int(t) for t in res.tokens[: res.n_generated]]


def test_the_batcher_serves_the_references_tokens_cold_and_over_adopted_pages(
    _fresh_state, tokens
):
    """The same 330-token prompt three times through one batcher,
    speculation on: a cold chunked admission, then two prefix hits
    admitted over the pages they adopted. Every served token is the
    reference's greedy choice given the tokens served before it (float32
    program, so no near-tie is decided by rounding), and the window's
    counters count."""
    cfg = _tiny()
    ref, file_cfg = _reference(), _tiny_file_config()
    weights = ref.make_weights(file_cfg, seed=0, bits=8)
    prompt = list(map(int, tokens[0, :330]))
    batcher = ContinuousBatcher(
        program_params(cfg), cfg, max_batch=1, max_new_cap=12, page_size=16,
        prefix_cache=True, speculative=True,
    )
    before = obs_mod.metrics.snapshot()
    served = [_serve(batcher, prompt, i) for i in range(3)]
    assert served[0] == served[1] == served[2] and len(served[0]) == 12
    logits = ref.logits_for(file_cfg, weights, prompt + served[0][:-1], len(prompt) - 1)
    assert list(logits.argmax(-1)) == served[0]
    stats = prefix_mod.snapshot()
    assert stats["hit_admissions"] == 2 and stats["paged_admissions"] == 2
    assert spec_mod.snapshot()["spec_steps"] > 0
    after = obs_mod.metrics.snapshot()

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)

    inside = grew('advspec_attn_kv_tokens_total{bounds="in",layers="window"}')
    every = grew('advspec_attn_kv_tokens_total{bounds="all",layers="window"}')
    full = grew('advspec_attn_kv_tokens_total{bounds="all",layers="full"}')
    # two windowed and two global layers; every step's row is over 330
    # tokens long, so a windowed layer covers its 128 and no more
    assert 0 < inside < every == full and inside % (2 * 128) == 0
    assert grew('advspec_moe_pairs_total{positions="all",program="decode"}') > 0
    # the pool's holdings behind the windows: three pages of sixteen hold
    # the cached prompt's last 128 tokens... the rest of its 20 is dead in
    # the two windowed layers of four
    dead = after["advspec_kv_window_dead_bytes"]
    held = after["advspec_kv_held_bytes"]
    assert 0 < dead < held / 2


def test_what_is_not_wired_is_refused_by_the_familys_name():
    cfg = _tiny()
    params = program_params(cfg)
    with pytest.raises(NotImplementedError, match="afmoe.*int8 KV"):
        ContinuousBatcher(
            params, cfg, max_batch=1, max_new_cap=4, page_size=16, kv_dtype="int8"
        )


# -- (e) the router, the shares and the stack ------------------------------------


def test_the_bias_moves_the_choice_and_never_the_weights():
    """`route`: the k are chosen by score + bias, weighted by the scores
    alone over their sum, times the scale. The synthetic bias (deviation
    0.01) has to move the choice of a fair share of tokens, or a program
    that drops it serves the same tokens: measured here over 4,096 random
    activations at the tiny router's shape (7%) and at the published
    one's (52%)."""
    for n_routed, top_k, dim, low, high in (
        (8, 2, 128, 0.04, 0.12), (256, 4, 3072, 0.4, 0.65),
    ):
        ex = replace(
            _tiny().experts, n_routed=n_routed, top_k=top_k, held=(0, 0)
        )
        h = jax.random.normal(jax.random.key(1), (4096, dim), jnp.float32)
        w_router = jax.random.normal(jax.random.key(2), (dim, n_routed)) / np.sqrt(dim)
        bias = ex.bias_std * jax.random.normal(jax.random.key(3), (n_routed,))
        w, idx = moe.route(h, w_router, ex, bias)
        w0, idx0 = moe.route(h, w_router, ex, bias * 0)
        scores = jax.nn.sigmoid(h @ w_router)
        picked = jnp.take_along_axis(scores, idx, axis=-1)
        np.testing.assert_allclose(
            w, picked / picked.sum(-1, keepdims=True) * 2.448, rtol=1e-5
        )
        moved = float((jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1).mean())
        assert low < moved < high, (n_routed, moved)


def test_the_eight_shares_add_up_to_the_uncut_routed_layer():
    """f = shared expert + sum over ALL experts of w_e expert_e(m), before
    the block's second norm (the norm of a sum is not the sum of norms:
    the shares of a deployment add up in f). Eight shares of one expert
    each, by the program's `routed_ffn`, plus the shared expert ONCE, by
    the reference, equal the uncut reference's f."""
    ref = _reference()
    uncut_cfg = _tiny_file_config([0, 8])
    weights = ref.make_weights(uncut_cfg, seed=0, bits=8)
    s = ref._static(ref.sizes(uncut_cfg))
    m = jax.random.normal(jax.random.key(5), (96, 128), jnp.float32)
    row = jnp.int32(0)
    shared = {n: weights[n] for n in ref.FFN}
    experts = {n: weights[n] for n in ref.EXPERT}
    with jax.default_matmul_precision("highest"):
        uncut = ref._routed_mix(
            m, row, shared, weights["w_router"], weights["router_bias"], experts, s=s
        )
        alone = ref._routed_mix(
            m, row, shared, weights["w_router"], weights["router_bias"], experts,
            s=ref._static({**dict(s), "held": 0}),
        )  # the shared expert, no routed one
    total = np.asarray(alone, np.float64)
    parts = []
    for first in range(8):
        cfg = get_config("afmoe", "tiny", experts_held=(first, 1))
        layers = program_params(cfg)["layers"]
        out, _ = moe.routed_ffn(
            m[None], layers["w_router"][0],
            {k: layers[k] for k in moe.EXPERT_WEIGHTS}, 0, cfg.experts,
            jax.nn.silu, router_bias=layers["router_bias"][0],
        )
        parts.append(np.asarray(out[0], np.float64))
    total = total + sum(parts)
    # float32 throughout over the same int8 values: summation order alone
    np.testing.assert_allclose(total, np.asarray(uncut), rtol=2e-3, atol=2e-3)
    assert all(np.abs(p).max() > 1e-2 for p in parts)  # every share adds something


def test_a_cut_in_depth_keeps_whole_periods_of_the_published_numbering():
    """Trinity's 60 layers: 6 dense (s,s,s,f,s,s), then 54 routed that begin
    in the middle of a period (s,f, then s,s,s,f thirteen times). The cell's
    9: published layer 0, then layers 8-15."""
    full = get_config("afmoe", "trinity-large")
    kinds = ["swa", "swa", "swa", "nope"] * 15
    assert list(full.layer_mixers) == kinds and full.n_leading == 6
    assert full.layer_windows.count(4096) == 45 and full.layer_windows.count(0) == 15
    cut = get_config(
        "afmoe", "trinity-large", n_layers=9, experts_held=(0, 32),
        vocab_rows=25024, max_seq_len=32768,
    )
    assert cut.layer_mixers == ("swa",) + ("swa", "swa", "swa", "nope") * 2
    assert cut.n_leading == 1 and cut.n_kv_layers == 9
    assert [(run.kinds, run.reps) for run in tf._segments(cut)] == [
        (("swa",), 1), (("swa", "swa", "swa", "nope"), 2),
    ]
    # the whole depth: the last period is cut short, a run of its own
    assert [(r.stack, r.row0, r.layer0, len(r.kinds), r.reps) for r in tf._segments(full)] == [
        ("leading", 0, 0, 6, 1), ("layers", 0, 6, 4, 13), ("layers", 52, 58, 2, 1),
    ]
    with pytest.raises(ValueError, match="leading"):
        get_config("afmoe", "trinity-large", n_layers=8)
    assert config_mod.family_of(cut) == "afmoe"
    # the source's values, as the preset states them
    assert (full.rms_eps, full.rope_theta, full.max_seq_len) == (1e-5, 10000.0, 262144)
