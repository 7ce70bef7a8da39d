"""A cached prompt is admitted over the pages it adopted.

``ContinuousBatcher._start_admission_cached`` gives a prompt whose prefix
the pool holds, and whose remainder is at most one ADMISSION_CHUNK, no
dense cache: ``_finish_admission`` runs the delta through
``forward_paged_decode`` against the adopted pages, samples the first
token and writes the slot's rows in ONE program (``paged_admission``).
Pinned here at tiny size on the CPU (the gather path of
``forward_paged_decode``): the tokens are the cache-off batcher's, the
adopted pages keep their bytes, nothing is read out of the pool and no
dense cache is made on that path, ``prefix.*`` counts it, a tier-promoted
prefix takes it too, a fault in the program unwinds through
``_abort_admission``, and a longer remainder keeps the dense chunks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.engine import kvtier
from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
from adversarial_spec_tpu.engine import scheduler as sched_mod
from adversarial_spec_tpu.engine import spec as spec_mod
from adversarial_spec_tpu.engine.scheduler import (
    ADMISSION_CHUNK,
    ContinuousBatcher,
    SchedRequest,
)
from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.resilience import faults as faults_mod

PAGE = 16
MAX_NEW = 10
# dense GQA; dense GQA with QKV bias and an untied head; latent + routed
FAMILIES = {
    "llama": dict(),
    "qwen2": dict(),
    "mistral4": dict(experts_held=(2, 4), vocab_rows=384),
}


@pytest.fixture(autouse=True)
def _fresh_state():
    prev = spec_mod.config()
    prev_spec = (prev.enabled, prev.gamma)
    prefix_mod.configure(enabled=True, max_pages=0)
    prefix_mod.reset_stats()
    faults_mod.reset()
    yield
    spec_mod.configure(enabled=prev_spec[0], gamma=prev_spec[1])
    prefix_mod.configure(enabled=True, max_pages=0)
    prefix_mod.reset_stats()
    kvtier.configure(enabled=False)
    faults_mod.reset()


@pytest.fixture(scope="module")
def models():
    out = {}
    for family, kw in FAMILIES.items():
        cfg = get_config(family, "tiny", **kw)
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        if cfg.qkv_bias:  # born zero: make them count
            for i, b in enumerate(("bq", "bk", "bv")):
                params["layers"][b] = 0.1 * jax.random.normal(
                    jax.random.key(i), params["layers"][b].shape
                )
        assert family != "qwen2" or not cfg.tied_embeddings
        out[family] = (params, cfg)
    return out


def _tokens(n: int, salt: int = 7) -> list[int]:
    return [((i * salt) % 350) + 3 for i in range(n)]


def _batcher(params, cfg, *, speculative, prefix_cache, max_batch=1, **kw):
    return ContinuousBatcher(
        params, cfg, max_batch=max_batch, max_new_cap=MAX_NEW, page_size=PAGE,
        prefix_cache=prefix_cache, speculative=speculative, **kw,
    )


def _serve(b, prompt, req_id=0, max_new=MAX_NEW):
    b.submit(
        SchedRequest(
            req_id=req_id, prompt_ids=list(prompt), max_new_tokens=max_new
        )
    )
    [res] = b.run_all()
    assert res.error is None, res.error
    return res


def _count_calls(monkeypatch, name):
    """Count the scheduler's calls of its module global ``name``."""
    real = getattr(sched_mod, name)
    calls = []

    def counted(*a, **kw):
        calls.append(name)
        return real(*a, **kw)

    monkeypatch.setattr(sched_mod, name, counted)
    return calls


# The three admissions of a case: a cold one, then two hits whose delta
# is the case's. An unchanged prompt of k pages + 1 token re-runs its
# last token alone; one of whole pages re-runs its last page (the match
# stops a page short of the last token); a prompt that grows by three
# pages a round re-runs those.
def _rounds(delta: str) -> tuple[list[list[int]], int]:
    base = _tokens(4 * PAGE + (1 if delta == "one_token" else 0))
    if delta == "three_pages":
        grown = base + _tokens(3 * PAGE, salt=11)
        return [base, grown, grown + _tokens(3 * PAGE, salt=13)], 3 * PAGE
    return [base] * 3, 1 if delta == "one_token" else PAGE


@pytest.mark.parametrize("speculative", [True, False], ids=["spec", "plain"])
@pytest.mark.parametrize("delta", ["one_token", "one_page", "three_pages"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_hit_is_admitted_over_its_pages(
    models, monkeypatch, family, delta, speculative
):
    params, cfg = models[family]
    prompts, n_delta = _rounds(delta)
    off = _batcher(params, cfg, speculative=speculative, prefix_cache=False)
    want = [_serve(off, p).tokens.tolist() for p in prompts]

    b = _batcher(params, cfg, speculative=speculative, prefix_cache=True)
    assert _serve(b, prompts[0]).tokens.tolist() == want[0]
    reads = _count_calls(monkeypatch, "read_tokens")
    caches = _count_calls(monkeypatch, "init_cache")
    for rnd in (1, 2):
        prompt = prompts[rnd]
        matched, pages = b.prefix_cache.lookup(prompt, record=False)
        limit = ((len(prompt) - 1) // PAGE) * PAGE
        pages = pages[: min(matched, limit) // PAGE]
        assert len(prompt) - len(pages) * PAGE == n_delta
        at = np.asarray(pages) + 1  # physical ids: page 0 is trash
        before = {k: np.asarray(v[:, at]) for k, v in b.pool.items()}
        stats0 = prefix_mod.stats.as_dict()

        res = _serve(b, prompt)

        assert res.tokens.tolist() == want[rnd], (rnd, want[rnd])
        assert res.cached_tokens == len(pages) * PAGE
        for k, v in b.pool.items():
            np.testing.assert_array_equal(np.asarray(v[:, at]), before[k])
        stats1 = prefix_mod.stats.as_dict()
        assert stats1["hit_admissions"] - stats0["hit_admissions"] == 1
        assert stats1["paged_admissions"] - stats0["paged_admissions"] == 1
        assert stats1["prefilled_tokens"] - stats0["prefilled_tokens"] == n_delta
        b.allocator.check_invariants()
    assert reads == [] and caches == []


def test_int8_pages_take_the_span_with_their_scales(models):
    """An int8 pool: the span's K/V are quantized as they are written, a
    layer at a time, scale pages beside them; the tokens are the
    cache-off batcher's over the same int8 pages."""
    params, cfg = models["llama"]
    prompt = _tokens(4 * PAGE + 5)
    off = _batcher(
        params, cfg, speculative=True, prefix_cache=False, kv_dtype="int8"
    )
    want = _serve(off, prompt).tokens.tolist()
    b = _batcher(
        params, cfg, speculative=True, prefix_cache=True, kv_dtype="int8"
    )
    assert [_serve(b, prompt).tokens.tolist() for _ in range(2)] == [want] * 2
    assert prefix_mod.stats.paged_admissions == 1


def test_cold_admission_counts_no_hit(models):
    params, cfg = models["llama"]
    b = _batcher(params, cfg, speculative=False, prefix_cache=True)
    _serve(b, _tokens(40))
    assert prefix_mod.stats.hit_admissions == 0
    assert prefix_mod.stats.paged_admissions == 0


@pytest.mark.parametrize(
    "n_delta,paged", [(ADMISSION_CHUNK, True), (ADMISSION_CHUNK + 64, False)]
)
def test_the_widest_span_and_the_dense_path_past_it(
    models, monkeypatch, n_delta, paged
):
    """A delta of one whole ADMISSION_CHUNK is the widest span; one page
    more and the admission prefills into a dense cache, in chunks, as a
    cold one does, with the matched prefix gathered into that cache."""
    params, cfg = models["llama"]
    base = _tokens(2 * PAGE)
    grown = base + _tokens(n_delta, salt=11)
    off = _batcher(params, cfg, speculative=False, prefix_cache=False)
    want = _serve(off, grown).tokens.tolist()
    b = _batcher(params, cfg, speculative=False, prefix_cache=True)
    _serve(b, base)
    reads = _count_calls(monkeypatch, "read_tokens")
    caches = _count_calls(monkeypatch, "init_cache")
    res = _serve(b, grown)
    assert res.tokens.tolist() == want
    assert res.cached_tokens == 2 * PAGE
    assert prefix_mod.stats.hit_admissions == 1
    assert prefix_mod.stats.paged_admissions == int(paged)
    assert (reads, caches) == (
        ([], []) if paged else (["read_tokens"], ["init_cache"])
    )
    b.allocator.check_invariants()


def test_co_admitted_opponent_adopts_and_is_admitted_over_the_pages(models):
    """Two rows of one debate in one drain: the first is cold, the second
    adopts its blocks at the handoff's insert and runs its last page
    over them while the first decodes."""
    params, cfg = models["llama"]
    prompt = _tokens(4 * PAGE)
    off = _batcher(params, cfg, speculative=True, prefix_cache=False)
    want = _serve(off, prompt).tokens.tolist()
    b = _batcher(
        params, cfg, speculative=True, prefix_cache=True, max_batch=2
    )
    for i in range(2):
        b.submit(
            SchedRequest(req_id=i, prompt_ids=list(prompt), max_new_tokens=MAX_NEW)
        )
    results = b.run_all()
    assert [r.tokens.tolist() for r in results] == [want, want]
    assert [r.cached_tokens for r in results] == [0, 3 * PAGE]
    assert prefix_mod.stats.paged_admissions == 1


def test_tier_promoted_prefix_is_admitted_over_its_promoted_pages(models):
    """The radix keeps three pages; the rest of the prompt's blocks were
    demoted to host RAM. The next admission adopts the three, promotes
    the others into the pages it reserved, and runs the last page over
    all of them."""
    params, cfg = models["llama"]
    kvtier.configure(enabled=True, host_mb=16, store_dir="")
    kvtier.reset_stats()
    prefix_mod.configure(enabled=True, max_pages=3)
    prompt = _tokens(6 * PAGE)
    b = _batcher(params, cfg, speculative=False, prefix_cache=True)
    cold = _serve(b, prompt)
    assert b.tiers is not None and b.tiers.host_resident > 0
    res = _serve(b, prompt)
    assert res.tokens.tolist() == cold.tokens.tolist()
    assert kvtier.snapshot()["promoted_tokens"] > 0
    assert res.cached_tokens == 5 * PAGE  # three adopted + two promoted
    assert prefix_mod.stats.paged_admissions == 1
    b.allocator.check_invariants()
    b.tiers.check_invariants()


@pytest.mark.parametrize(
    "error,kind",
    [
        (RuntimeError("RESOURCE_EXHAUSTED: paged admission"), "oom"),
        (ValueError("a bug in the admission program"), "bug"),
    ],
    ids=["transient", "permanent"],
)
def test_fault_in_the_admission_program_is_isolated(
    models, monkeypatch, error, kind
):
    """``_abort_admission``'s cases along the new path: the program
    faults once; the sequence's pages (its references on the adopted
    ones among them) are freed, the resident row decodes to its end, a
    transient fault gets its one requeue and a permanent one resolves
    the request with the error."""
    params, cfg = models["llama"]
    cached, other = _tokens(4 * PAGE), _tokens(40, salt=13)
    off = _batcher(params, cfg, speculative=True, prefix_cache=False)
    want = {0: _serve(off, other).tokens.tolist(),
            1: _serve(off, cached).tokens.tolist()}
    b = _batcher(params, cfg, speculative=True, prefix_cache=True, max_batch=2)
    _serve(b, cached, req_id=9)
    free0 = b.allocator.free_pages
    cached0 = b.prefix_cache.cached_pages
    real = sched_mod.paged_admission
    fired = []

    def once(*a, **kw):
        if not fired:
            fired.append(1)
            raise error
        return real(*a, **kw)

    monkeypatch.setattr(sched_mod, "paged_admission", once)
    b.submit(SchedRequest(req_id=0, prompt_ids=other, max_new_tokens=MAX_NEW))
    b.submit(SchedRequest(req_id=1, prompt_ids=cached, max_new_tokens=MAX_NEW))
    results = {r.req_id: r for r in b.run_all()}
    assert fired and faults_mod.snapshot() == {f"admission.{kind}": 1}
    assert results[0].error is None
    assert results[0].tokens.tolist() == want[0]
    if kind == "oom":  # requeued once, then admitted over its pages
        assert results[1].error is None
        assert results[1].tokens.tolist() == want[1]
        assert prefix_mod.stats.paged_admissions == 2
    else:
        assert results[1].fault_kind == "bug"
        assert results[1].n_generated == 0
        assert results[1].cached_tokens == 3 * PAGE
    # the other prompt's blocks joined the cache; nothing leaked
    assert b.prefix_cache.cached_pages >= cached0
    assert b.allocator.free_pages == free0 - (
        b.prefix_cache.cached_pages - cached0
    )
    b.allocator.check_invariants()


def test_span_widths():
    assert [sched_mod._span_width(n) for n in (1, 64, 65, 128, 300, 512)] == [
        64, 64, 128, 128, 512, 512
    ]
    assert sched_mod._SPAN_WIDTHS[-1] == ADMISSION_CHUNK


def test_the_programs_names_are_their_own_and_the_metric_reads_the_counters():
    """No trace pattern of the benchmark matches the admission's program
    (the step's time, the prefill chunk's and the rooflines go on reading
    what they read), and `batcher.paged_admission_share` reads two
    `prefix.*` counters the program keeps, in the two dense critique
    cells."""
    import json
    import re
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    names = {
        "jit_" + fn.__name__
        for fn in (sched_mod.paged_admission, sched_mod.activate_slot)
    }
    assert names == {"jit__paged_admission_impl", "jit__activate_slot_impl"}

    def patterns(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k in ("pattern", "within") and isinstance(v, str):
                    yield v
                else:
                    yield from patterns(v)

    for path in (root / "perfbench/metrics").glob("*.json"):
        for pattern in patterns(json.loads(path.read_text())):
            assert not any(re.search(pattern, n) for n in names), path.name

    spec = json.loads(
        (root / "perfbench/metrics/batcher.paged_admission_share.json").read_text()
    )
    fields = prefix_mod.snapshot()
    for key in spec["params"]["num"] + spec["params"]["den"]:
        assert key.startswith("prefix.") and key[len("prefix."):] in fields
    bench = json.loads((root / "BENCHMARK.json").read_text())
    [entry] = [
        m for m in bench["per_layer"]
        if m["name"] == "batcher.paged_admission_share"
    ]
    assert entry["workloads"] == [
        "mistral-7b-int8.critique", "qwen2-7b-int8.critique"
    ]
    assert (entry["layer"], entry["moves"]) == ("batcher", "out_tokens_per_s")


def test_a_write_that_leaves_the_pool_as_it_was_shows_in_a_hits_tokens(
    models, monkeypatch
):
    """The span's K/V reach their pages through ``write_tokens``, where
    the benchmark plants its ``state_unchanged`` fault: with that write a
    no-op the hit decodes over a delta that was never written."""
    params, cfg = models["llama"]
    prompt = _tokens(4 * PAGE)
    b = _batcher(params, cfg, speculative=False, prefix_cache=True)
    want = _serve(b, prompt).tokens.tolist()
    monkeypatch.setattr(sched_mod, "write_tokens", lambda pool, *a, **kw: pool)
    sched_mod.paged_admission.clear_cache()
    try:
        got = _serve(b, prompt).tokens.tolist()
    finally:
        monkeypatch.undo()
        sched_mod.paged_admission.clear_cache()
    assert prefix_mod.stats.paged_admissions == 1
    assert got != want
