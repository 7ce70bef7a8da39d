"""State-space (Mamba-2) layers beside attention: the granitemoehybrid
family at its tiny preset (one whole period: nine state-space layers, one
NoPE GQA layer), against the plain reference
`perfbench/architectures/granitemoehybrid.py` (float32, token by token).

(a) prefill and decode, directly and through the batcher, give the
reference's logits and its best tokens; (b) the chunked form is the
token-by-token recurrence; (c) a verify step's state is the state at the
last accepted position, for every acceptance; (d) a prefix hit resumed
from a snapshot serves what a cold prefill serves; (e) snapshots are
evicted under their byte budget, and release, cancellation and eviction
leak nothing; (f) planted faults come out wrong; (g) what is not wired
refuses by the family's name.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
from adversarial_spec_tpu.engine import scheduler as sched
from adversarial_spec_tpu.engine.kvcache import PageAllocator
from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher, SchedRequest
from adversarial_spec_tpu.models import transformer as tf
from adversarial_spec_tpu.models.config import family_of, get_config
from adversarial_spec_tpu.ops import ssm as ssm_ops

ROOT = Path(__file__).resolve().parents[1]
PAGE = 64


@pytest.fixture(scope="module")
def model():
    """(program config, float32 params whose values are the bfloat16
    checkpoint's, the benchmark's tiny configuration, the reference module,
    its weights)."""
    from perfbench import manifest

    config = json.loads((ROOT / "perfbench/configs/granite-4.0-h-micro-bf16.json").read_text())
    config = manifest._merge(config, config["rehearsal"])  # the tiny sizes, as a rehearsal runs them
    arch = manifest.load_architecture(ROOT / "perfbench", config)
    cfg = get_config("granitemoehybrid", "tiny", max_seq_len=4096)  # the prompts below
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tf.init_params(jax.random.key(0), cfg, jnp.bfloat16),
    )
    weights = arch.make_weights(config, 0, bits=8)
    return cfg, params, config, arch, weights


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(3, 259, size=n)]


def _pool(cfg, rows=3, n_pages=5):
    heads, k_dim, v_dim = cfg.kv_layout
    return {
        "k": jnp.zeros((cfg.n_kv_layers, n_pages, heads, PAGE, k_dim), jnp.float32),
        "v": jnp.zeros((cfg.n_kv_layers, n_pages, heads, PAGE, v_dim), jnp.float32),
        **tf.init_recurrent_state(cfg, rows, jnp.float32),
    }


TABLE = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
ROWS = jnp.asarray([2], jnp.int32)  # the span's one row owns state row 2


def _span(cfg, params, pool, ids, start, n, keep, use_pallas=False):
    q = (start + jnp.arange(n))[None]
    bounds = jnp.stack([jnp.zeros_like(q), q + 1], -1).astype(jnp.int32)
    logits, pool, _ = tf.forward_paged_decode(
        params, cfg, jnp.asarray([ids[start : start + n]], jnp.int32), q, pool,
        TABLE, TABLE[0][q // PAGE], q % PAGE, bounds, q,
        state_rows=ROWS, state_keep=keep, use_pallas=use_pallas, pallas_interpret=True,
    )
    return np.asarray(logits[0]), pool


# -- the shapes ---------------------------------------------------------------


def test_the_presets_are_the_published_layout(model):
    cfg = model[0]
    big = get_config("granitemoehybrid", "h-micro")
    assert big.period == 10 and big.mixer_counts == (4, 36) and cfg.mixer_counts == (1, 9)
    assert [i for i in range(40) if big.layer_kinds[i % 10][0] == "gqa"] == [5, 15, 25, 35]
    s = big.ssm
    assert (s.inner_dim, s.conv_dim, s.in_dim, s.state_dim) == (4096, 4352, 8512, 128)
    assert (big.attn_scale, big.rope, big.tied_embeddings) == (0.015625, False, True)
    assert big.kv_layout == (8, 128, 128)  # 64-wide heads cached on whole lanes
    assert big.mixer_slot(5) == ("gqa", 0, 1) and big.mixer_slot(6) == ("ssm", 5, 9)
    assert family_of(big) == family_of(cfg) == "granitemoehybrid"
    assert family_of(get_config("mistral", "7b")) != "granitemoehybrid"
    # 3,191 M parameters: the issue's arithmetic, from the shapes alone
    shapes = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), big, jnp.bfloat16))
    n = sum(x.size for x in jax.tree.leaves(shapes)) - shapes["lm_head_t"].size
    assert 3.18e9 < n < 3.20e9
    # a period of one is what it was
    assert get_config("mistral", "7b").period == 1


# -- (a) the reference's logits ----------------------------------------------


def test_prefill_chunks_give_the_reference_logits(model):
    """`forward` over the dense cache in chunks of 64, 32 and 4: across the
    scan's chunk of 32 and from a non-zero carried state."""
    cfg, params, tiny, arch, weights = model
    ids = _ids(100)
    ref = arch.logits_for(tiny, weights, ids, 0)
    cache = tf.init_cache(cfg, 1, 128, jnp.float32)
    assert cache["k"].shape[0] == 1 and cache["ssm"].shape == (9, 1, 128, 256)
    got, pos = [], 0
    for n in (64, 32, 4):
        logits, cache = tf.forward(
            params, cfg, jnp.asarray([ids[pos : pos + n]], jnp.int32),
            jnp.arange(pos, pos + n)[None], cache, jnp.int32(pos), jnp.ones((1, 128), bool),
        )
        got.append(np.asarray(logits[0]))
        pos += n
    np.testing.assert_allclose(np.concatenate(got), ref, atol=2e-4)


def test_left_pads_do_not_enter_the_state(model):
    """A padded admission (prefix cache off) prefills pads first: their
    positions are not valid slots, and the state passes over them."""
    cfg, params, tiny, arch, weights = model
    ids = _ids(40, seed=3)
    ref = arch.logits_for(tiny, weights, ids, 0)
    pad = 24
    tokens = jnp.asarray([[0] * pad + ids], jnp.int32)
    valid = (jnp.arange(64) >= pad)[None]
    logits, cache = tf.forward(
        params, cfg, tokens, jnp.maximum(jnp.arange(64) - pad, 0)[None],
        tf.init_cache(cfg, 1, 64, jnp.float32), jnp.int32(0), valid,
    )
    np.testing.assert_allclose(np.asarray(logits[0, pad:]), ref, atol=2e-4)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_paged_spans_give_the_reference_logits(model, use_pallas):
    """An admission's span from a zero state, a verify span left open and
    committed at 4 of 9, the next span from there, a single step, a span
    that keeps nothing and the same span again: the kernels (interpret
    mode) and the plain path."""
    cfg, params, tiny, arch, weights = model
    ids = _ids(100)
    ref = arch.logits_for(tiny, weights, ids, 0)
    pool = _pool(cfg)
    lg, pool = _span(cfg, params, pool, ids, 0, 64, jnp.asarray([64]), use_pallas)
    np.testing.assert_allclose(lg, ref[:64], atol=2e-4)
    lg, pool = _span(cfg, params, pool, ids, 64, 9, None, use_pallas)
    np.testing.assert_allclose(lg, ref[64:73], atol=2e-4)
    assert set(pool["span"]) == {"x", "b", "dt", "cum", "seq"}
    pool = tf.commit_span(
        cfg, pool, jnp.asarray([4]), rows=ROWS, use_pallas=use_pallas, pallas_interpret=True
    )
    assert "span" not in pool
    lg, pool = _span(cfg, params, pool, ids, 68, 9, jnp.asarray([9]), use_pallas)
    np.testing.assert_allclose(lg, ref[68:77], atol=2e-4)
    lg, pool = _span(cfg, params, pool, ids, 77, 1, jnp.asarray([1]), use_pallas)
    np.testing.assert_allclose(lg, ref[77:78], atol=2e-4)
    _, pool = _span(cfg, params, pool, ids, 78, 3, jnp.asarray([0]), use_pallas)
    lg, pool = _span(cfg, params, pool, ids, 78, 3, jnp.asarray([3]), use_pallas)
    np.testing.assert_allclose(lg, ref[78:81], atol=2e-4)
    # the other rows' state was never touched
    assert not np.asarray(pool["ssm"][:, :2]).any() and not np.asarray(pool["conv"][:, :2]).any()


# -- (b) the chunked form is the recurrence ------------------------------------


def test_the_chunked_form_is_the_token_by_token_recurrence():
    B, S, H, P, N = 2, 96, 4, 8, 16
    k = jax.random.split(jax.random.key(1), 6)
    x = jax.random.normal(k[0], (B, S, H, P))
    b_in, c_in = jax.random.normal(k[1], (B, S, N)), jax.random.normal(k[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(k[3], (B, S, H)))
    dt = dt.at[1, 40:50].set(0.0)  # positions that do not count
    a, d = -jnp.arange(1.0, H + 1), jnp.ones((H,))
    state0 = jax.random.normal(k[4], (B, N, H * P))  # a carried state, not zeros
    y, state = ssm_ops.chunked_scan(x, b_in, c_in, dt, a, d, state0, chunk=32)
    # token by token, in numpy: S [B, H, P, N]
    s = np.asarray(state0, np.float64).reshape(B, N, H, P).transpose(0, 2, 3, 1)
    xs, bs, cs, dts = (np.asarray(v, np.float64) for v in (x, b_in, c_in, dt))
    want = np.zeros((B, S, H, P))
    for t in range(S):
        decay = np.exp(dts[:, t] * np.asarray(a))[:, :, None, None]
        s = decay * s + (dts[:, t][..., None] * xs[:, t])[..., None] * bs[:, t][:, None, None, :]
        want[:, t] = np.einsum("bhpn,bn->bhp", s, cs[:, t]) + xs[:, t]
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)
    got = np.asarray(state).reshape(B, N, H, P).transpose(0, 2, 3, 1)
    np.testing.assert_allclose(got, s, rtol=2e-4, atol=2e-4)
    # one chunk over the whole span, and chunks of 32, are the same numbers
    y1, state1 = ssm_ops.chunked_scan(x, b_in, c_in, dt, a, d, state0, chunk=96)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state1), np.asarray(state), rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        ssm_ops.chunked_scan(x[:, :40], b_in[:, :40], c_in[:, :40], dt[:, :40], a, d, state0, 32)


def test_the_span_kernels_equal_the_plain_path():
    k = jax.random.split(jax.random.key(2), 5)
    stack = jax.random.normal(k[0], (3, 4, 128, 2048))  # two blocks of lanes a row
    c_in, b_in = jax.random.normal(k[1], (2, 9, 128)), jax.random.normal(k[2], (2, 9, 128))
    xs, decay = jax.random.normal(k[3], (2, 9, 2048)), jax.random.uniform(k[4], (2, 2048))
    rows = jnp.asarray([2, 0])
    ys = ssm_ops.ssm_span_read(stack, 1, rows, c_in, interpret=True)
    np.testing.assert_allclose(ys, ssm_ops.state_read(stack[1][rows], c_in), atol=1e-5)
    want = stack.at[1, rows].set(ssm_ops.state_update(stack[1][rows], b_in, xs, decay))
    got = ssm_ops.ssm_span_update(jnp.copy(stack), 1, rows, b_in, xs, decay, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- (c) rollback ----------------------------------------------------------------


@pytest.mark.parametrize("accepted", range(6))
def test_the_state_after_a_verify_step_is_the_state_at_the_accepted_position(model, accepted):
    """A span of gamma + 1 = 5 positions of which `accepted` stand (0: an
    idle row): state and conv window equal those of a plain run, one
    position at a time, over the accepted tokens alone. The last case
    keeps all five."""
    cfg, params = model[:2]
    ids = _ids(80, seed=5)
    n_keep = min(accepted, 5)
    pool = _pool(cfg)
    _, pool = _span(cfg, params, pool, ids, 0, 64, jnp.asarray([64]))
    stepped = pool
    for t in range(n_keep):
        _, stepped = _span(cfg, params, stepped, ids, 64 + t, 1, jnp.asarray([1]))
    _, pool = _span(cfg, params, pool, ids, 64, 5, None, use_pallas=accepted % 2 == 1)
    pool = tf.commit_span(
        cfg, pool, jnp.asarray([n_keep]), rows=ROWS,
        use_pallas=accepted % 2 == 1, pallas_interpret=True,
    )
    np.testing.assert_allclose(pool["ssm"], stepped["ssm"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(pool["conv"], stepped["conv"], rtol=1e-4, atol=1e-5)


# -- through the batcher -----------------------------------------------------------


def _batcher(model, **kw):
    cfg, params = model[:2]
    kw = {"max_batch": 4, "page_size": PAGE, "capacity_tokens": 8192, "max_new_cap": 64,
          "gamma": 4, "prefix_cache": True, **kw}
    return ContinuousBatcher(params, cfg, **kw)


def _serve(b, prompts, max_new=16, **req_kw):
    for i, p in prompts.items():
        b.submit(SchedRequest(i, p, max_new, **req_kw))
    out = {r.req_id: [int(t) for t in r.tokens[: r.n_generated]] for r in b.run_all()}
    b.check_invariants()
    return out


def _gap(model, prompt, served):
    from perfbench import correct

    _, _, tiny, arch, weights = model
    logits = correct.served_logits(arch, tiny, weights, prompt, served)
    return correct.compare_request(logits, served)


DOC = _ids(1300, seed=1)
ROUND1 = {0: DOC[:1200] + [7, 8, 9], 1: DOC[:1200] + [7, 8, 9], 2: DOC[:1100] + [50] * 30,
          3: DOC[:700]}
ROUND2 = {10: DOC[:1200] + [7, 8, 9, 10, 11], 11: DOC[:1250], 12: DOC[:1300]}


@pytest.fixture(scope="module")
def served(model):
    """Two rounds through one batcher with speculation on and one with it
    off: (tokens by request id, the prefix statistics after round 2)."""
    out = {}
    for spec in (True, False):
        prefix_mod.reset_stats()
        b = _batcher(model, speculative=spec)
        tokens = {**_serve(b, ROUND1), **_serve(b, ROUND2)}
        out[spec] = (tokens, dict(prefix_mod.stats.as_dict()), b)
    return out


@pytest.mark.parametrize("spec", [True, False])
def test_the_batcher_serves_the_references_best_tokens(model, served, spec):
    tokens, stats, _ = served[spec]
    for rid, prompt in {**ROUND1, **ROUND2}.items():
        res = _gap(model, prompt, tokens[rid])
        assert res["n"] == 16 and res["match"] == 16 and res["gap_max"] == 0.0, (rid, res)
    assert tokens[0] == tokens[1]  # the same prompt twice
    # speculation changes how the tokens are found, not the tokens
    assert tokens == served[not spec][0]


def test_a_hit_resumes_from_the_deepest_snapshot_under_the_match(served):
    """Round 1's cold prompt (1,203 tokens: chunks of 512, 512, 128 and a
    tail under a page) leaves snapshots at 512, 1,024 and 1,152; every later
    admission resumes from the deepest of them under its radix match
    (1,152, 1,088, 640 and three times 1,152) and recomputes the rest,
    K/V and state."""
    _, stats, b = served[True]
    assert stats["snapshots_taken"] == 3 and stats["snapshots_evicted"] == 0
    assert stats["hit_admissions"] == stats["state_restores"] == 6
    assert stats["paged_admissions"] == 6  # every remainder fits a span
    # request 11 is admitted after its round's sibling 12 cached block 18 too: 1,216
    assert stats["matched_tokens"] == 1152 + 1088 + 640 + 1152 + 1216 + 1152
    assert stats["resumed_tokens"] == 1152 + 1024 + 512 + 3 * 1152
    assert stats["snapshot_bytes"] == b.prefix_cache.state_bytes == 3 * b._snapshot_bytes
    assert b._snapshot_bytes == 9 * (128 * 256 * 4 + 3 * 512 * 4)  # float32 params here


def test_a_hit_serves_what_a_cold_prefill_serves(model, served):
    """(d): round 2's prompts, each cold on a batcher of its own."""
    tokens = served[True][0]
    for rid, prompt in ROUND2.items():
        cold = _serve(_batcher(model, speculative=True), {rid: prompt})
        assert cold[rid] == tokens[rid]


def test_a_long_remainder_prefills_in_chunks_from_the_snapshot(model):
    """A hit whose remainder is longer than one ADMISSION_CHUNK takes the
    dense chunks, from the snapshot's state."""
    prefix_mod.reset_stats()
    b = _batcher(model, speculative=True)
    first = _serve(b, {0: DOC[:600]})
    long = DOC[:600] + _ids(700, seed=9)
    got = _serve(b, {1: long})
    st = prefix_mod.stats
    assert st.hit_admissions == 1 and st.paged_admissions == 0 and st.resumed_tokens == 576
    assert _gap(model, long, got[1])["gap_max"] == 0.0 and first[0]
    # 600 tokens left snapshots at 512 and 576 (its 64-token chunk ended on a
    # page); the long one's own chunks from 576 left more, at 1,088 and on
    assert st.snapshots_taken >= 3


def test_the_padded_layout_without_a_prefix_cache_serves_the_same(model, served):
    b = _batcher(model, speculative=True, prefix_cache=False)
    got = _serve(b, {3: ROUND1[3]})
    assert _gap(model, ROUND1[3], got[3])["gap_max"] == 0.0


# -- (e) the second evictable resource ------------------------------------------------


def test_snapshots_are_evicted_least_recently_used_under_the_budget():
    alloc = PageAllocator(16, 4)
    stats = prefix_mod.PrefixCacheStats()
    cache = prefix_mod.PrefixCache(alloc, 4, stats=stats)
    cache.state_budget = 250
    alloc.new_sequence(0)
    pages = alloc.extend(0, 32)
    toks = list(range(32))
    cache.insert(toks, pages)
    assert cache.lookup_state(toks, 32) == (0, None)  # pages alone restore nothing
    assert cache.attach_state(toks, 8, "s8", 100) and cache.attach_state(toks, 16, "s16", 100)
    assert cache.lookup_state(toks, 32) == (16, "s16") and cache.lookup_state(toks, 12) == (8, "s8")
    assert cache.lookup_state(toks[:10] + [99] * 22, 32) == (8, "s8")  # the match ends at 8
    cache.lookup_state(toks, 8)  # touch s8: s16 is now the least recently used
    assert cache.attach_state(toks, 24, "s24", 100)
    assert stats.snapshots_evicted == 1 and cache.state_bytes == 200 == stats.snapshot_bytes
    assert cache.lookup_state(toks, 32) == (24, "s24") and cache.lookup_state(toks, 20) == (8, "s8")
    assert not cache.attach_state(toks, 32, "big", 300)  # alone over the budget
    assert not cache.attach_state([5] * 8, 8, "gone", 10)  # no such block
    with pytest.raises(ValueError):
        cache.attach_state(toks, 6, "x", 10)
    cache.check_invariants()
    # a snapshot goes with its block
    alloc.free_sequence(0)
    cache.clear()
    assert cache.state_bytes == 0 and stats.snapshot_bytes == 0 and stats.snapshots_evicted == 3
    cache.check_invariants()
    alloc.check_invariants()
    cache.state_bytes = 7
    with pytest.raises(RuntimeError, match="snapshot bytes"):
        cache.check_invariants()


def test_the_batcher_keeps_its_snapshots_under_the_budget(model):
    prefix_mod.reset_stats()
    b = _batcher(model, speculative=True)
    b.prefix_cache.state_budget = b._snapshot_bytes  # room for one
    _serve(b, {0: DOC[:1200]})  # snapshots at 512, 1,024 and 1,152: the last stays
    st = prefix_mod.stats
    assert st.snapshots_taken == 3 and st.snapshots_evicted == 2
    assert b.prefix_cache.state_bytes == b._snapshot_bytes
    got = _serve(b, {1: DOC[:1250]})  # resumes from the one that stayed
    assert st.resumed_tokens == 1152 and _gap(model, DOC[:1250], got[1])["gap_max"] == 0.0
    # the budget's derivation: a snapshot a chunk of capacity where the device says nothing
    assert _batcher(model).prefix_cache.state_budget == (8192 // 512) * b._snapshot_bytes


def test_release_cancel_and_evict_free_the_state_row(model):
    b = _batcher(model, speculative=True)
    cancelled = []

    def stop_at_3(tokens):
        cancelled.append(len(tokens))
        return len(tokens) < 3

    b.submit(SchedRequest(0, DOC[:300], 16, on_tokens=stop_at_3))
    b.submit(SchedRequest(1, DOC[:200], 16))
    res = {r.req_id: r for r in b.run_all()}
    assert res[0].cancelled and res[1].n_generated == 16
    b.check_invariants()
    assert b._state_owner == [None] * 4 and b._slot_seq == [None] * 4
    # a fault evicts a resident: its row is free again, the other finishes
    from adversarial_spec_tpu.resilience import injector

    injector.install(
        injector.FaultInjector(injector.parse_chaos_spec("bug@scheduler_chunk:after=1:times=1"))
    )
    try:
        for i in (2, 3):
            b.submit(SchedRequest(i, DOC[: 150 + i], 8))
        res = {r.req_id: r for r in b.run_all()}
    finally:
        injector.reset()
    b.check_invariants()
    assert b._state_owner == [None] * 4
    assert sum(bool(r.error) for r in res.values()) == 1  # one evicted, one served
    b._state_owner[1] = 99
    with pytest.raises(RuntimeError, match="state row 1"):
        b.check_invariants()


# -- (f) planted faults ----------------------------------------------------------------


@pytest.fixture
def fresh_programs():
    """A patched function is traced anew only if jax holds no trace of the
    program that calls it; the faulted traces go again afterwards."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_a_verify_step_that_keeps_a_rejected_drafts_state_is_wrong(
    model, monkeypatch, fresh_programs
):
    """The commit keeps every span position whatever was accepted: the
    daemon would stream and finish as ever; the tokens are not the
    reference's."""
    sound = sched.commit_span

    def keep_all(cfg, pool, n_keep, **kw):
        span = pool["span"]["dt"].shape[2]
        return sound(cfg, pool, jnp.where(n_keep > 0, span, 0), **kw)

    monkeypatch.setattr(sched, "commit_span", keep_all)
    got = _serve(_batcher(model, speculative=True), {3: ROUND1[3]})
    res = _gap(model, ROUND1[3], got[3])
    assert res["match"] < 16 and res["gap_max"] > 0.5, res


def test_a_hit_that_restores_no_snapshot_is_wrong(model, monkeypatch, fresh_programs):
    b = _batcher(model, speculative=True)
    _serve(b, {0: DOC[:1200]})
    _serve(b, {5: _ids(900, seed=4)})  # another document's state stays in the slot
    jax.clear_caches()
    monkeypatch.setattr(sched, "_write_state_row_impl", lambda pool, slot, state: pool)
    got = _serve(b, {1: ROUND2[11]})
    res = _gap(model, ROUND2[11], got[1])
    assert res["match"] < 14 and res["gap_max"] > 0.2, res


# -- (g) what is not wired refuses by name ---------------------------------------------


def test_what_is_not_wired_refuses_by_the_familys_name(model):
    cfg, params = model[:2]
    from adversarial_spec_tpu.engine.generate import generate
    from adversarial_spec_tpu.engine.loader import materialize_params
    from adversarial_spec_tpu.ops.quant import quantize_params

    with pytest.raises(NotImplementedError, match="granitemoehybrid.*generate"):
        generate(params, cfg, [[3, 4, 5]], max_new_tokens=2, eos_ids=[2])
    with pytest.raises(NotImplementedError, match="granitemoehybrid.*int8 KV"):
        _batcher(model, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="int8 KV"):
        tf.init_cache(cfg, 1, 8, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="granitemoehybrid.*int8 weights"):
        materialize_params("random", "granitemoehybrid", "tiny", quant="int8")
    with pytest.raises(NotImplementedError, match="granitemoehybrid.*int8 / int4 weights"):
        ContinuousBatcher(quantize_params(params), cfg, max_batch=2, capacity_tokens=1024)
    with pytest.raises(NotImplementedError, match="published tensors"):
        materialize_params("/nowhere", "granitemoehybrid", "tiny")
    # an unknown pattern is refused where it is written down
    from dataclasses import replace

    with pytest.raises(ValueError, match="whole number of periods"):
        replace(cfg, n_layers=12)
    with pytest.raises(ValueError, match="disagree"):
        replace(cfg, ssm=None)


def test_the_configuration_file_holds_the_catalogs_row():
    cfg = json.loads((ROOT / "perfbench/configs/granite-4.0-h-micro-bf16.json").read_text())
    row = next(
        json.loads(line)
        for line in Path("/opt/skills/guides/model-configs/architectures.jsonl").read_text().splitlines()
        if '"granite-4.0-h-micro"' in line
    ) if Path("/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    if row is not None:
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == [] and cfg["serving"]["quant"] == ""
    assert {"state_dtype", "ssm_constants", "max_seq_len", "tokenizer", "weights"} <= set(cfg["assumed"])
