"""Benchmark: critique tokens/sec/chip for a batched multi-opponent decode.

Measures the north-star metric (BASELINE.json): decode throughput of one
debate round's opponent pool run as a single batched generate — 4 opponents
(batch rows) critiquing the SAME spec prompt on one model (shared-prefix
prefill fires), temperature-0.7 sampling with a fixed seed so rows diverge
the way a real round does, synthetic weights (zero egress). Baseline
target: 1500 critique tokens/sec/chip.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "tok/s/chip", "vs_baseline": N/1500}
On the CPU (and --long-context, which has no published baseline)
"vs_baseline" is null — a CPU ratio against the TPU north star is
machine noise, not signal.

Every mode runs IN THIS PROCESS on the platform jax gives it (one process
owns a chip) and names "platform", "device_kind" and "device_count" in
its payload. JAX_PLATFORMS=cpu is the one way to ask for the CPU; a
runner that fails is a non-zero exit, never a number from another device.

Modes:
  python bench.py                 # north-star decode bench (one JSON line)
  python bench.py --long-context  # 16k-token prefill bench (one JSON line)
  python bench.py --round-loop    # BASELINE config 4 shape: 5 rounds,
                                  # growing spec, 4 opponents (one line)
  python bench.py --mode prefix   # prefix-KV-cache micro-bench: 3 rounds
                                  # of a growing spec through the
                                  # continuous batcher, cache on vs off;
                                  # also writes BENCH_prefix.json
  python bench.py --mode obs-overhead
                                  # flight recorder + metrics registry
                                  # emit-path cost over the mock mixed
                                  # workload (CPU host-overhead pin,
                                  # budget < 3%); writes BENCH_obs.json
  python bench.py --mode spec     # per-slot speculation in the batcher:
                                  # growing-spec rounds under the mock
                                  # acceptance model (tokens/step,
                                  # acceptance) + real-batcher spec-on
                                  # vs spec-off walls with identical
                                  # greedy tokens; writes BENCH_spec.json
  python bench.py --mode tier     # tiered KV cache: restart-rehydration
                                  # (disk store) + pressure-thrash
                                  # (host tier) workloads on the CPU
                                  # mock, plus a real-batcher parity/
                                  # retrace phase; writes BENCH_tier.json
  python bench.py --mode cancel   # streaming early-convergence
                                  # cancellation: mock debate rounds
                                  # with verbose early-[AGREE]
                                  # opponents (tokens-saved fraction,
                                  # byte-identical prefixes) + real-
                                  # batcher freed-slot re-admission;
                                  # writes BENCH_cancel.json
  python bench.py --mode recover  # mid-round kill recovery: SIGKILL a
                                  # subprocess round after 2 of 4
                                  # opponents journal, resume, pin the
                                  # fraction of round tokens salvaged
                                  # (journal + KV disk store) vs a cold
                                  # re-run; writes BENCH_recover.json
  python bench.py --mode serve    # advspec serve daemon: capacity
                                  # point (debates/s), overload storm
                                  # (typed sheds, brownout, zero
                                  # accepted loss), SIGTERM drain
                                  # drill; writes BENCH_serve.json
  python bench.py --mode residency
                                  # opponent-pool weight residency: a
                                  # 4-model pool under a 2-model HBM
                                  # budget, host-paging (demote/
                                  # promote) vs naive evict-reload
                                  # weight-load seconds, swap-overlap
                                  # fraction, byte-identical
                                  # transcripts, zero re-promotion
                                  # recompiles (mock + tiny-real);
                                  # writes BENCH_residency.json
  python bench.py --mode fleet    # replicated engines: aggregate
                                  # mock tokens/s of 3 replicas with
                                  # prefix-affinity routing vs 1
                                  # replica, affinity vs random
                                  # cross-round cache hit-rate, plus
                                  # the replica-kill recovery drill;
                                  # writes BENCH_fleet.json
  python bench.py --mode kernels  # fused serving kernels: interpret-
                                  # mode parity pins (int8/int4 dequant-
                                  # matmul vs XLA, multi-position span
                                  # verify vs dense gather) + real-
                                  # batcher A/B on int4 weights with
                                  # byte-identical transcripts and zero
                                  # unexpected recompiles; writes
                                  # BENCH_kernels.json
  python bench.py --mode elastic  # elastic fleet: accepted-debate
                                  # throughput + p99 TTFT under a
                                  # paced load step, autoscaled
                                  # (floor 1, ceiling 3) vs fixed
                                  # 3-replica fleet at equal chip
                                  # ceiling, plus the lose-nothing
                                  # scale-in drill (byte-identical
                                  # transcripts, zero duplicated
                                  # completions); writes
                                  # BENCH_elastic.json
  python bench.py --mode disagg   # prefill/decode disaggregation:
                                  # decode-side p99 TTFT + accepted-
                                  # debate throughput, role-split fleet
                                  # (2 prefill + 2 decode) vs symmetric
                                  # 4-replica fleet at equal replica
                                  # count on a prefill-heavy workload,
                                  # plus the cross-replica KV handoff
                                  # hit fraction (byte-identical
                                  # transcripts, zero duplicated
                                  # completions); writes
                                  # BENCH_disagg.json
  python bench.py --mode capacity # capacity frontier: seeded open-loop
                                  # trace replay (tools/load_replay.py)
                                  # binary-searched to the SLO breach
                                  # per knob arm (replicas 1 vs 3);
                                  # writes BENCH_capacity.json
  --no-speculative                # escape hatch: plain token-at-a-time
                                  # decode (ADVSPEC_SPECULATIVE=0)
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

BASELINE_TOK_S_CHIP = 1500.0
N_OPPONENTS = 4
PROMPT_TOKENS = 1024
DECODE_TOKENS = 256
LONG_CONTEXT_TOKENS = 16384


def _bench_model(platform: str):
    """Shared model setup for the decode benches (_run_bench and
    _run_round_loop): size/dtype by platform, dp×tp mesh sharding on
    multi-chip hosts — ONE copy so a mode can't silently drop the mesh
    and misreport 'per chip'."""
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    size = "1b" if platform != "cpu" else "tiny"
    cfg = get_config("llama", size)
    params = T.init_params(
        jax.random.key(0),
        cfg,
        dtype=jnp.bfloat16 if platform != "cpu" else jnp.float32,
    )
    n_devices = len(jax.devices())
    mesh = None
    n_chips = 1
    if platform != "cpu" and n_devices > 1:
        import math as _math

        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        dp = _math.gcd(N_OPPONENTS, n_devices)
        mesh = make_mesh({"dp": dp, "tp": n_devices // dp})
        params = shard_params(mesh, params)
        n_chips = n_devices
    return cfg, params, mesh, n_chips, size


def _run_bench(platform: str) -> dict:
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()  # persistent compile cache: repeat runs skip XLA compiles
    import jax

    from adversarial_spec_tpu.engine.generate import generate

    # Real-accelerator bench uses the 1b llama shape (fits one v5e chip
    # in bf16 with cache headroom); a JAX_PLATFORMS=cpu run uses the
    # tiny config (a control-flow check, not a multi-hour crawl).
    # The real debate-round shape: every opponent critiques the SAME
    # spec prompt (shared-prefix prefill fires on one chip), and
    # temperature sampling diverges the rows.
    cfg, params, mesh, n_chips, size = _bench_model(platform)
    rng = __import__("random").Random(0)
    prompt = [rng.randrange(3, cfg.vocab_size) for _ in range(PROMPT_TOKENS)]
    prompts = [list(prompt) for _ in range(N_OPPONENTS)]

    kw = dict(
        max_new_tokens=DECODE_TOKENS,
        eos_ids=[],  # synthetic model: measure the full decode length
        temperature=0.7,
        seed=0,
        mesh=mesh,
    )
    # Warmup: compile prefill + decode chunk.
    generate(params, cfg, prompts, **kw)
    # Measured run.
    t0 = time.monotonic()
    result = generate(params, cfg, prompts, **kw)
    wall = time.monotonic() - t0

    tok_s_chip = result.decode_tokens / result.decode_time_s / n_chips
    return {
        "metric": "critique_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 1),
        "unit": "tok/s/chip",
        # The 1500 north star is a TPU-chip number; a CPU ratio against
        # it is machine noise, so report null there.
        "vs_baseline": (
            round(tok_s_chip / BASELINE_TOK_S_CHIP, 3)
            if platform != "cpu"
            else None
        ),
        "platform": platform,
        "model": f"llama-{size}",
        "opponents": N_OPPONENTS,
        "prompt_tokens": PROMPT_TOKENS,
        "decode_tokens_per_opponent": DECODE_TOKENS,
        "decode_time_s": round(result.decode_time_s, 3),
        "prefill_time_s": round(result.prefill_time_s, 3),
        "round_wall_s": round(wall, 3),
    }


def _run_long_context(platform: str) -> dict:
    """16k-token prefill (BASELINE config 5's context scale).

    Multi-device meshes prefill sequence-parallel (ring attention over
    sp — parallel/sp.py); single device uses chunked prefill. CPU runs a
    thin model so the 16k×16k attention is tractable; the measurement
    structure is identical either way.
    """
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.engine.generate import generate
    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    if platform != "cpu":
        cfg = get_config("llama", "1b", max_seq_len=LONG_CONTEXT_TOKENS + 64)
        dtype = jnp.bfloat16
    else:
        from dataclasses import replace

        cfg = replace(
            get_config("llama", "tiny"),
            n_layers=2,
            max_seq_len=LONG_CONTEXT_TOKENS + 64,
        )
        dtype = jnp.float32
    params = T.init_params(jax.random.key(0), cfg, dtype=dtype)

    rng = __import__("random").Random(1)
    prompt = [
        rng.randrange(3, cfg.vocab_size) for _ in range(LONG_CONTEXT_TOKENS)
    ]

    n_devices = len(jax.devices())
    mesh = None
    mode = "chunked"
    if n_devices > 1:
        from adversarial_spec_tpu.parallel.mesh import make_mesh
        from adversarial_spec_tpu.parallel.sharding import shard_params

        sp = max(d for d in (4, 2, 1) if n_devices % d == 0)
        mesh = make_mesh({"sp": sp, "dp": n_devices // sp})
        params = shard_params(mesh, params)
        mode = f"sp{sp}"

    kw = dict(
        max_new_tokens=8,  # prefill is the measurement; decode is a tail
        eos_ids=[],
        greedy=True,
        mesh=mesh,
        speculative=False,
    )
    generate(params, cfg, [prompt], **kw)  # warmup/compile
    t0 = time.monotonic()
    result = generate(params, cfg, [prompt], **kw)
    wall = time.monotonic() - t0

    prefill_tok_s = LONG_CONTEXT_TOKENS / result.prefill_time_s
    return {
        "metric": "prefill_16k_tokens_per_sec",
        "value": round(prefill_tok_s, 1),
        "unit": "tok/s",
        "vs_baseline": None,  # BASELINE publishes no prefill number
        "platform": platform,
        "mode": mode,
        "model": "llama-1b" if platform != "cpu" else "llama-tiny-2L",
        "context_tokens": LONG_CONTEXT_TOKENS,
        "prefill_time_s": round(result.prefill_time_s, 3),
        "wall_s": round(wall, 3),
    }


def _run_round_loop(platform: str) -> dict:
    """BASELINE config 4's loop shape: 5 critique rounds over one spec,
    4 opponents per round, the spec GROWING by one revision per round
    (each round re-prefills the larger context — the part the one-round
    bench cannot see). Decode throughput is the north-star metric; the
    whole-loop wall time additionally covers the prefill regrowth."""
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()

    from adversarial_spec_tpu.engine.generate import generate

    n_rounds = 5
    revision_tokens = 256  # per round: the synthesized revision delta

    cfg, params, mesh, n_chips, size = _bench_model(platform)
    rng = __import__("random").Random(0)
    spec = [rng.randrange(3, cfg.vocab_size) for _ in range(PROMPT_TOKENS)]

    kw = dict(
        max_new_tokens=DECODE_TOKENS,
        eos_ids=[],
        temperature=0.7,
        seed=0,
        mesh=mesh,
    )
    # Warm up EVERY bucket the loop will hit (prompts pad to power-of-two
    # buckets; round 1's 1024 bucket and rounds 2-5's 2048 bucket are
    # different compiled programs) so the timed loop measures steady
    # state, never an XLA compile.
    largest = spec + [5] * (revision_tokens * (n_rounds - 1))
    generate(params, cfg, [list(largest)] * N_OPPONENTS, **kw)
    generate(params, cfg, [list(spec)] * N_OPPONENTS, **kw)

    decode_tokens = 0
    decode_time = prefill_time = 0.0
    t0 = time.monotonic()
    for _ in range(n_rounds):
        r = generate(
            params, cfg, [list(spec)] * N_OPPONENTS, **kw
        )
        decode_tokens += r.decode_tokens
        decode_time += r.decode_time_s
        prefill_time += r.prefill_time_s
        # Synthesize: the spec grows by one revision's worth of tokens.
        spec = spec + [
            rng.randrange(3, cfg.vocab_size) for _ in range(revision_tokens)
        ]
    wall = time.monotonic() - t0

    tok_s = decode_tokens / decode_time / n_chips
    return {
        "metric": "round_loop_critique_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tok/s/chip",
        "vs_baseline": (
            round(tok_s / BASELINE_TOK_S_CHIP, 3)
            if platform != "cpu"
            else None
        ),
        "platform": platform,
        "model": f"llama-{size}",
        "rounds": n_rounds,
        "opponents": N_OPPONENTS,
        "spec_tokens_start": PROMPT_TOKENS,
        "spec_tokens_end": PROMPT_TOKENS + revision_tokens * n_rounds,
        "decode_tokens_total": decode_tokens,
        "decode_time_s": round(decode_time, 3),
        "prefill_time_s": round(prefill_time, 3),
        "loop_wall_s": round(wall, 3),
    }


def _run_prefix(platform: str) -> dict:
    """Prefix-KV-cache micro-bench: 3 debate-shaped rounds (2 opponents
    sharing one growing spec) through the ContinuousBatcher, greedy, with
    the prefix cache ON vs OFF. Reports per-round prefill tokens, the
    hit rate, tokens saved, decode tok/s both ways, and whether the two
    configurations produced identical tokens (they must)."""
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import random

    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
    from adversarial_spec_tpu.engine.scheduler import (
        ContinuousBatcher,
        SchedRequest,
    )
    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    size = "1b" if platform != "cpu" else "tiny"
    cfg = get_config("llama", size)
    params = T.init_params(
        jax.random.key(0),
        cfg,
        dtype=jnp.bfloat16 if platform != "cpu" else jnp.float32,
    )
    n_rounds, n_opp = 3, 2
    base_len, delta_len, max_new = (
        (1024, 256, 64) if platform != "cpu" else (512, 64, 16)
    )

    def run(enabled):
        prefix_mod.configure(enabled=enabled)
        prefix_mod.reset_stats()
        rng = random.Random(1)
        spec = [rng.randrange(3, cfg.vocab_size) for _ in range(base_len)]
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=n_opp,
            max_new_cap=max_new,
            page_size=64,
            capacity_tokens=1 << 15,
            greedy=True,
            prefix_cache=enabled,
        )
        per_round, toks = [], []
        decode_tokens = 0
        t0 = time.monotonic()
        for _ in range(n_rounds):
            before = prefix_mod.stats.prefilled_tokens
            for i in range(n_opp):
                b.submit(
                    SchedRequest(
                        req_id=i,
                        prompt_ids=list(spec),
                        max_new_tokens=max_new,
                    )
                )
            results = b.run_all()
            toks.append([r.tokens.tolist() for r in results])
            decode_tokens += sum(r.n_generated for r in results)
            per_round.append(prefix_mod.stats.prefilled_tokens - before)
            spec = spec + [
                rng.randrange(3, cfg.vocab_size) for _ in range(delta_len)
            ]
        wall = time.monotonic() - t0
        return per_round, toks, wall, decode_tokens, prefix_mod.snapshot()

    off_rounds, off_toks, off_wall, off_dec, _ = run(False)
    on_rounds, on_toks, on_wall, on_dec, on_snap = run(True)
    tail_saving = 1.0 - (sum(on_rounds[1:]) / max(sum(off_rounds[1:]), 1))
    payload = {
        "metric": "prefix_cache_tail_prefill_saving",
        "value": round(tail_saving, 4),
        "unit": "fraction of rounds-2+ prefill tokens avoided",
        "vs_baseline": None,  # no published prefix-cache baseline yet
        "platform": platform,
        "model": f"llama-{size}",
        "rounds": n_rounds,
        "opponents": n_opp,
        "spec_tokens_start": base_len,
        "spec_tokens_delta_per_round": delta_len,
        "prefill_tokens_per_round_cache_on": on_rounds,
        "prefill_tokens_per_round_cache_off": off_rounds,
        "hit_rate": on_snap["hit_rate"],
        "cached_tokens": on_snap["cached_tokens"],
        "saved_tokens": on_snap["saved_tokens"],
        "tokens_identical": on_toks == off_toks,
        "wall_s_cache_on": round(on_wall, 3),
        "wall_s_cache_off": round(off_wall, 3),
        "decode_tokens": on_dec,
    }
    return payload


def _run_spec(platform: str) -> dict:
    """Per-slot speculation in the ContinuousBatcher, measured twice:

    1. MOCK ACCEPTANCE MODEL (engine/mock.py): a growing-spec
       multi-round debate workload — each round's ``[SPEC]`` revision is
       a near-copy of the document in the prompt, exactly the output
       shape prompt-lookup thrives on. Deterministic on CPU, so the
       headline mean tokens/step and acceptance rate are byte-stable
       run to run. Plain decode emits 1 token/step by definition, so
       tokens/step IS the speedup bound speculation buys at equal
       quality (transcripts must be byte-identical spec-on vs off).
    2. REAL BATCHER (llama tiny on CPU / 1b on TPU): the same growing
       workload through the paged serving path, spec-on vs spec-off —
       walls both ways, byte-identical greedy tokens, the measured
       acceptance on a real (random-weight) model, and the retrace
       watch's verdict that the verify program compiled once per
       distinct draft width (``unexpected_recompiles`` must be 0).
    """
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import random
    import re

    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu import obs
    from adversarial_spec_tpu.engine import spec as spec_mod
    from adversarial_spec_tpu.engine.mock import MockEngine
    from adversarial_spec_tpu.engine.scheduler import (
        ContinuousBatcher,
        SchedRequest,
    )
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams
    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    gamma = spec_mod.env_gamma()
    n_rounds, n_opp = 4, 2

    # --- 1. Mock acceptance model: growing-spec debate rounds. -------
    def mock_rounds(enabled: bool):
        spec_mod.configure(enabled=enabled, gamma=gamma)
        spec_mod.reset_stats()
        eng = MockEngine()
        doc = (
            "The allocator SHALL bound page reuse by refcount. "
            "Verification MUST cover every accepted draft position. "
        ) * 24
        texts = []
        t0 = time.monotonic()
        for rnd in range(1, n_rounds + 1):
            reqs = [
                ChatRequest(
                    model="mock://critic",
                    system="You are an adversarial spec critic.",
                    user=(
                        f"Debate round {rnd}\n--- DOCUMENT ---\n{doc}"
                        "\n--- END DOCUMENT ---"
                    ),
                )
                for _ in range(n_opp)
            ]
            outs = eng.chat(reqs, SamplingParams())
            texts.append([c.text for c in outs])
            m = re.search(r"\[SPEC\]\n(.*)\n\[/SPEC\]", outs[0].text, re.S)
            doc = m.group(1) if m else doc
        return texts, time.monotonic() - t0, spec_mod.stats.snapshot()

    mock_on_texts, mock_on_wall, mock_snap = mock_rounds(True)
    mock_off_texts, mock_off_wall, _ = mock_rounds(False)

    # --- 2. Real batcher: growing-spec rounds, spec on vs off. -------
    size = "1b" if platform != "cpu" else "tiny"
    cfg = get_config("llama", size)
    params = T.init_params(
        jax.random.key(0),
        cfg,
        dtype=jnp.bfloat16 if platform != "cpu" else jnp.float32,
    )
    base_len, delta_len, max_new = (
        (1024, 256, 64) if platform != "cpu" else (384, 64, 24)
    )

    def batcher_rounds(enabled: bool):
        spec_mod.configure(enabled=enabled, gamma=gamma)
        spec_mod.reset_stats()
        obs.configure(enabled=True)
        obs.reset_stats()
        rng = random.Random(1)
        # Tiled segments, not i.i.d. tokens: prompt-lookup drafts from
        # recurring n-grams, and a spec document genuinely repeats its
        # phrasing (section headers, SHALL/MUST boilerplate) — an
        # i.i.d.-random prompt has no bigram structure to draft from
        # and would measure the overhead half of the trade only.
        seg = [rng.randrange(3, cfg.vocab_size) for _ in range(16)]
        spec = (seg * (base_len // len(seg) + 1))[:base_len]
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=n_opp,
            max_new_cap=max_new,
            page_size=64,
            capacity_tokens=1 << 15,
            greedy=True,
            prefix_cache=False,
        )
        toks = []
        t0 = time.monotonic()
        for _ in range(n_rounds):
            for i in range(n_opp):
                b.submit(
                    SchedRequest(
                        req_id=i,
                        prompt_ids=list(spec),
                        max_new_tokens=max_new,
                    )
                )
            results = b.run_all()
            toks.append([r.tokens.tolist() for r in results])
            # The spec grows by round R's first revision — the debate
            # loop's shape (critique tokens re-enter the next prompt).
            spec = spec + toks[-1][0] + [
                rng.randrange(3, cfg.vocab_size) for _ in range(delta_len)
            ]
        wall = time.monotonic() - t0
        return toks, wall, spec_mod.stats.snapshot(), obs.snapshot()

    on_toks, on_wall, on_snap, on_obs = batcher_rounds(True)
    off_toks, off_wall, _, _ = batcher_rounds(False)
    retrace = on_obs["retrace"]
    verify = retrace["programs"].get("scheduler_spec_chunk", {})

    return {
        "metric": "spec_mock_tokens_per_step",
        # Plain decode = 1 token/step, so this IS the ≥2× criterion.
        "value": mock_snap["tokens_per_step"],
        "unit": "mean tokens emitted per verify step (mock model)",
        "vs_baseline": None,  # no published speculation baseline
        "platform": platform,
        "model": f"llama-{size}",
        "gamma": gamma,
        "rounds": n_rounds,
        "opponents": n_opp,
        "mock": {
            "tokens_per_step": mock_snap["tokens_per_step"],
            "acceptance_rate": mock_snap["acceptance_rate"],
            "spec_steps": mock_snap["spec_steps"],
            "transcripts_identical": mock_on_texts == mock_off_texts,
            "wall_s_spec_on": round(mock_on_wall, 3),
            "wall_s_spec_off": round(mock_off_wall, 3),
        },
        "batcher": {
            "tokens_per_step": on_snap["tokens_per_step"],
            "acceptance_rate": on_snap["acceptance_rate"],
            "spec_steps": on_snap["spec_steps"],
            "rolled_back_pages": on_snap["rolled_back_pages"],
            "tokens_identical": on_toks == off_toks,
            "wall_s_spec_on": round(on_wall, 3),
            "wall_s_spec_off": round(off_wall, 3),
            "unexpected_recompiles": retrace["unexpected_recompiles"],
            "verify_program": verify,
        },
        "escape_hatch": "--no-speculative / ADVSPEC_SPECULATIVE=0",
    }


def _run_tier(platform: str) -> dict:
    """Tiered-KV bench (engine/kvtier.py), three phases:

    1. RESTART REHYDRATION (mock, deterministic): a 5-round growing-spec
       session with the disk store armed, "restarted" after round 2 (a
       FRESH engine — new allocator, radix index, host tier — sharing
       only the store directory). The restarted process's rounds are
       the session's rounds 2+; the headline is the fraction of their
       prefill tokens the store rehydrates vs a tier-off restart, with
       byte-identical transcripts both ways.
    2. PRESSURE THRASH (mock, deterministic): the radix index capped
       far below the document's block count, so every insert LRU-evicts
       the tail. Tier-off re-prefills the evicted tail every round;
       tier-on promotes it back from host RAM. Reported as the fraction
       of tier-off's rounds-2+ re-prefill the host tier avoids.
    3. REAL BATCHER (llama tiny on CPU / 1b on TPU): the same two
       stories through the paged serving path — demote/promote under a
       page cap and restart-rehydration through a store dir — with
       byte-identical greedy tokens tier-on vs tier-off, allocator +
       tier invariants checked after every drain, and the retrace
       watch's verdict that tiering added zero unexpected recompiles.
    """
    import re
    import shutil

    from adversarial_spec_tpu import obs
    from adversarial_spec_tpu.engine import kvtier as kvtier_mod
    from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
    from adversarial_spec_tpu.engine.mock import MockEngine
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams

    n_opp = 2
    base_doc = (
        "The allocator SHALL bound page reuse by refcount. "
        "Demoted blocks MUST reach exactly one terminal state. "
        "Rehydrated prefixes MUST be byte-identical to recomputation. "
    ) * 64  # ~10.6 KB -> ~2600 mock tokens, ~165 blocks

    def mock_session(
        tier_on: bool,
        store_dir: str,
        restart_after: int,
        n_rounds: int,
        cap_pages: int = 0,
    ):
        """Drive a growing-spec session; returns (texts, per-round
        prefilled tokens, tier snapshot). ``restart_after=k`` swaps in a
        FRESH MockEngine after round k (the restart); per-round prefill
        is measured as deltas on the process-wide prefix stats."""
        kvtier_mod.configure(
            enabled=tier_on, host_mb=64, store_dir=store_dir
        )
        prefix_mod.configure(enabled=True, max_pages=cap_pages)
        prefix_mod.reset_stats()
        kvtier_mod.reset_stats()
        eng = MockEngine()
        doc = base_doc
        texts, per_round = [], []
        for rnd in range(1, n_rounds + 1):
            if restart_after and rnd == restart_after + 1:
                eng = MockEngine()  # the restart: only the store survives
            before = prefix_mod.stats.prefilled_tokens
            reqs = [
                ChatRequest(
                    model="mock://critic",
                    system="You are an adversarial spec critic.",
                    # PREFIX-STABLE ordering (the PR 2 template rule):
                    # document first, round header trailing — required
                    # for cross-round (and cross-restart) chain hits.
                    user=(
                        f"--- DOCUMENT ---\n{doc}\n--- END DOCUMENT ---\n"
                        f"Debate round {rnd}"
                    ),
                )
                for _ in range(n_opp)
            ]
            outs = eng.chat(reqs, SamplingParams())
            texts.append([c.text for c in outs])
            per_round.append(
                prefix_mod.stats.prefilled_tokens - before
            )
            m = re.search(r"\[SPEC\]\n(.*)\n\[/SPEC\]", outs[0].text, re.S)
            doc = m.group(1) if m else doc
        return texts, per_round, kvtier_mod.stats.snapshot()

    # --- 1. restart rehydration (disk store). ------------------------
    store = tempfile.mkdtemp(prefix="bench_tier_store_")
    restart_after, n_rounds = 2, 5
    on_texts, on_rounds, on_snap = mock_session(
        True, store, restart_after, n_rounds
    )
    off_texts, off_rounds, _ = mock_session(
        False, "", restart_after, n_rounds
    )
    tail_on = sum(on_rounds[restart_after:])
    tail_off = sum(off_rounds[restart_after:])
    rehydrated_fraction = 1.0 - tail_on / max(tail_off, 1)
    shutil.rmtree(store, ignore_errors=True)

    # --- 2. pressure thrash (host tier). -----------------------------
    cap = 64  # far under the document's block count: every insert evicts
    p_on_texts, p_on_rounds, p_snap = mock_session(True, "", 0, 4, cap)
    p_off_texts, p_off_rounds, _ = mock_session(False, "", 0, 4, cap)
    thrash_on = sum(p_on_rounds[1:])
    thrash_off = sum(p_off_rounds[1:])
    pressure_saving = 1.0 - thrash_on / max(thrash_off, 1)

    # --- 3. real batcher: parity + invariants + retrace. --------------
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import random

    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.engine import spec as spec_mod
    from adversarial_spec_tpu.engine.scheduler import (
        ContinuousBatcher,
        SchedRequest,
    )
    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    size = "1b" if platform != "cpu" else "tiny"
    cfg = get_config("llama", size)
    params = T.init_params(
        jax.random.key(0),
        cfg,
        dtype=jnp.bfloat16 if platform != "cpu" else jnp.float32,
    )
    base_len, delta_len, max_new, b_rounds = (
        (1024, 128, 48, 2) if platform != "cpu" else (512, 64, 16, 2)
    )
    spec_mod.configure(enabled=False)  # isolate the tier effect

    def batcher_rounds(tier_on: bool, cap_pages: int, store_dir: str):
        kvtier_mod.configure(
            enabled=tier_on, host_mb=64, store_dir=store_dir
        )
        prefix_mod.configure(enabled=True, max_pages=cap_pages)
        prefix_mod.reset_stats()
        kvtier_mod.reset_stats()
        obs.configure(enabled=True)
        obs.reset_stats()
        rng = random.Random(1)
        seg = [rng.randrange(3, cfg.vocab_size) for _ in range(16)]
        doc = (seg * (base_len // len(seg) + 1))[:base_len]
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=n_opp,
            max_new_cap=max_new,
            page_size=64,
            capacity_tokens=1 << 15,
            greedy=True,
        )
        toks, per_round = [], []
        t0 = time.monotonic()
        for _ in range(b_rounds):
            before = prefix_mod.stats.prefilled_tokens
            for i in range(n_opp):
                b.submit(
                    SchedRequest(
                        req_id=i,
                        prompt_ids=list(doc),
                        max_new_tokens=max_new,
                    )
                )
            results = b.run_all()
            toks.append([r.tokens.tolist() for r in results])
            per_round.append(prefix_mod.stats.prefilled_tokens - before)
            doc = doc + [
                rng.randrange(3, cfg.vocab_size) for _ in range(delta_len)
            ]
            b.allocator.check_invariants()
            if b.tiers is not None:
                b.tiers.check_invariants()
        wall = time.monotonic() - t0
        return (
            toks,
            per_round,
            wall,
            kvtier_mod.stats.snapshot(),
            obs.snapshot(),
        )

    # Pressure story (page cap forces demote/promote mid-session).
    bt_on, bp_on, bw_on, bsnap_on, bobs_on = batcher_rounds(True, 4, "")
    bt_off, bp_off, bw_off, _, _ = batcher_rounds(False, 4, "")
    # Restart story: batcher A populates the store; a FRESH batcher B
    # (same store) rehydrates; the tier-off fresh batcher is cold.
    bstore = tempfile.mkdtemp(prefix="bench_tier_bstore_")
    batcher_rounds(True, 0, bstore)
    rt_warm, rp_warm, _, rsnap, robs = batcher_rounds(True, 0, bstore)
    rt_cold, rp_cold, _, _, _ = batcher_rounds(False, 0, "")
    shutil.rmtree(bstore, ignore_errors=True)

    return {
        "metric": "tier_restart_rehydrated_fraction",
        # Fraction of the restarted process's rounds-2+ prefill tokens
        # served from the disk store (vs a tier-off restart).
        "value": round(rehydrated_fraction, 4),
        "unit": "fraction of rounds-2+ prefill tokens rehydrated after "
        "restart (mock)",
        "vs_baseline": None,  # no published tiering baseline
        "platform": platform,
        "model": f"llama-{size}",
        "opponents": n_opp,
        "restart": {
            "rounds": n_rounds,
            "restart_after_round": restart_after,
            "rehydrated_fraction": round(rehydrated_fraction, 4),
            "prefill_per_round_tier_on": on_rounds,
            "prefill_per_round_tier_off": off_rounds,
            "rehydrated_tokens": on_snap["rehydrated_tokens"],
            "disk_hit_rate": on_snap["disk_hit_rate"],
            "store_writes": on_snap["store_writes"],
            "transcripts_identical": on_texts == off_texts,
        },
        "pressure": {
            "rounds": 4,
            "prefix_cache_page_cap": cap,
            "reprefill_avoided_fraction": round(pressure_saving, 4),
            "prefill_per_round_tier_on": p_on_rounds,
            "prefill_per_round_tier_off": p_off_rounds,
            "promoted_tokens": p_snap["promoted_tokens"],
            "demoted_tokens": p_snap["demoted_tokens"],
            "host_hit_rate": p_snap["host_hit_rate"],
            "transcripts_identical": p_on_texts == p_off_texts,
        },
        "batcher": {
            "rounds": b_rounds,
            "pressure_tokens_identical": bt_on == bt_off,
            "pressure_prefill_tier_on": bp_on,
            "pressure_prefill_tier_off": bp_off,
            "pressure_promoted_tokens": bsnap_on["promoted_tokens"],
            "wall_s_tier_on": round(bw_on, 3),
            "wall_s_tier_off": round(bw_off, 3),
            "restart_tokens_identical": rt_warm == rt_cold,
            "restart_prefill_warm": rp_warm,
            "restart_prefill_cold": rp_cold,
            "restart_rehydrated_tokens": rsnap["rehydrated_tokens"],
            "unexpected_recompiles": (
                bobs_on["retrace"]["unexpected_recompiles"]
                + robs["retrace"]["unexpected_recompiles"]
            ),
        },
        "escape_hatch": "--no-kv-tier / ADVSPEC_KV_TIER=0",
    }


def _run_residency(platform: str) -> dict:
    """Weight-residency bench (engine/weightres.py), two phases:

    1. MOCK (deterministic): a 4-model opponent pool under an HBM
       budget that fits 2, six rounds. Host paging on (demote/promote)
       vs off (naive evict-reload) compared on total weight-load
       seconds — synthetic walls on exact binary fractions, so the
       ratio is a pinned number, not a measurement. Transcripts must be
       byte-identical across paging-on / paging-off / unconstrained
       (residency is pure accounting on the mock).
    2. TINY-REAL: four tiny families through the real TpuEngine with
       ``ADVSPEC_HBM_BUDGET_BYTES`` sized to the two largest models.
       Same three arms, measured walls; the resident arm additionally
       pins zero unexpected recompiles on re-promotion (promoted params
       restore their original committed shardings) and reports the
       swap-overlap fraction (promotions the prefetch thread ran under
       the current group's decode — the _stage_next path).
    """
    from adversarial_spec_tpu.engine import mock as mock_mod
    from adversarial_spec_tpu.engine import weightres
    from adversarial_spec_tpu.engine.mock import MockEngine
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams

    n_models = 4
    mock_rounds = 6

    def _set_budget(nbytes: int | None) -> None:
        if nbytes is None:
            os.environ.pop("ADVSPEC_HBM_BUDGET_BYTES", None)
        else:
            os.environ["ADVSPEC_HBM_BUDGET_BYTES"] = str(nbytes)

    def mock_arm(budget_models: int | None, paging: bool):
        _set_budget(
            budget_models * mock_mod._MODEL_BYTES
            if budget_models is not None
            else None
        )
        weightres.configure(enabled=paging, host_mb=1024)
        weightres.reset_stats()
        eng = MockEngine()
        texts = []
        for rnd in range(1, mock_rounds + 1):
            reqs = [
                ChatRequest(
                    model=f"mock://critic?pool={m}",
                    system="You are an adversarial spec critic.",
                    user=f"Critique the document.\nDebate round {rnd}",
                )
                for m in range(n_models)
            ]
            outs = eng.chat(reqs, SamplingParams())
            texts.append([c.text for c in outs])
        if eng.ledger is not None:
            eng.ledger.check_invariants()
        return texts, weightres.snapshot()

    try:
        m_res_texts, m_res = mock_arm(2, True)
        m_thrash_texts, m_thrash = mock_arm(2, False)
        m_free_texts, _ = mock_arm(None, True)
    finally:
        _set_budget(None)
    mock_identical = (
        m_res_texts == m_thrash_texts == m_free_texts
    )
    mock_ratio = m_thrash["weight_load_wall_s"] / max(
        m_res["weight_load_wall_s"], 1e-9
    )

    # --- 2. tiny-real: the same pool through the real engine. ---------
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    from adversarial_spec_tpu import obs
    from adversarial_spec_tpu.engine import spec as spec_mod
    from adversarial_spec_tpu.engine.tpu import TpuEngine

    aliases = [
        "random-tiny",
        "random-gemma-tiny",
        "random-mistral-tiny",
        "random-qwen-tiny",
    ]
    # Enough rounds that the steady-state swap cost dominates the
    # shared 4-load warm-up: the ratio's asymptote is load/promote
    # (~6x on CPU tiny models), and 6 rounds clears the 2.0 acceptance
    # floor with margin on a noisy host.
    real_rounds = 6
    sampling = SamplingParams(max_new_tokens=16, greedy=True, seed=0)
    spec_mod.configure(enabled=False)  # isolate the residency effect

    def real_arm(budget: int | None, paging: bool):
        _set_budget(budget)
        weightres.configure(enabled=paging, host_mb=4096)
        weightres.reset_stats()
        obs.configure(enabled=True)
        obs.reset_stats()
        obs.retrace.clear()
        eng = TpuEngine()
        texts = []
        for rnd in range(1, real_rounds + 1):
            reqs = [
                ChatRequest(
                    model=f"tpu://{a}",
                    system="You are an adversarial spec critic.",
                    user=f"Critique the document.\nDebate round {rnd}",
                )
                for a in aliases
            ]
            outs = eng.chat(reqs, sampling)
            errs = [c.error for c in outs if not c.ok]
            if errs:
                raise RuntimeError(f"residency bench arm failed: {errs}")
            texts.append([c.text for c in outs])
            eng.check_residency_invariants()
        snap = weightres.snapshot()
        retrace = obs.snapshot()["retrace"]
        bytes_by_alias = {
            a: eng.ledger._entries[a].bytes_device
            or eng.ledger._entries[a].bytes_host
            for a in eng.ledger._entries
        }
        return texts, snap, retrace, bytes_by_alias

    try:
        # Unconstrained arm first: baseline transcripts + model bytes
        # (everything fits, so the reported bytes are device bytes).
        base_texts, _, _, sizes = real_arm(None, True)
        two_largest = sum(sorted(sizes.values(), reverse=True)[:2])
        budget = int(two_largest * 1.05)  # fits 2, never 3
        res_texts, r_res, r_retrace, _ = real_arm(budget, True)
        thrash_texts, r_thrash, _, _ = real_arm(budget, False)
    finally:
        _set_budget(None)
    real_identical = base_texts == res_texts == thrash_texts
    real_ratio = r_thrash["weight_load_wall_s"] / max(
        r_res["weight_load_wall_s"], 1e-9
    )

    return {
        "metric": "residency_load_wall_ratio",
        # Naive evict-reload weight-load seconds over host-paging
        # weight-load seconds, 4-model pool / 2-model budget (real
        # engine; >= 2.0 is the acceptance floor, mock_ratio is the
        # deterministic pin of the same arithmetic).
        "value": round(real_ratio, 3),
        "unit": "x fewer weight-load seconds than evict-reload "
        "(4-model pool, 2-model HBM budget)",
        "vs_baseline": None,  # no published residency baseline
        "platform": platform,
        "within_budget": bool(real_ratio >= 2.0 and mock_ratio >= 2.0),
        "pool_models": n_models,
        "budget_models": 2,
        "load_wall_resident_s": round(r_res["weight_load_wall_s"], 4),
        "load_wall_thrash_s": round(r_thrash["weight_load_wall_s"], 4),
        "swap_overlap_fraction": r_res["swap_overlap_fraction"],
        "transcripts_byte_identical": {
            "mock": mock_identical,
            "real": real_identical,
        },
        "unexpected_recompiles": r_retrace["unexpected_recompiles"],
        "mock": {
            "rounds": mock_rounds,
            "load_wall_ratio": round(mock_ratio, 3),
            "resident": {
                k: m_res[k]
                for k in (
                    "loads",
                    "demotions",
                    "promotions",
                    "weight_load_wall_s",
                    "swap_overlap_fraction",
                    "coalesced_groups",
                )
            },
            "thrash": {
                k: m_thrash[k]
                for k in ("loads", "freed_models", "weight_load_wall_s")
            },
        },
        "real": {
            "rounds": real_rounds,
            "models": aliases,
            "budget_bytes": budget,
            "load_wall_ratio": round(real_ratio, 3),
            "resident": {
                k: r_res[k]
                for k in (
                    "loads",
                    "demotions",
                    "promotions",
                    "promotions_overlapped",
                    "weight_load_wall_s",
                    "coalesced_groups",
                )
            },
            "thrash": {
                k: r_thrash[k]
                for k in ("loads", "freed_models", "weight_load_wall_s")
            },
        },
        "escape_hatch": "--no-weight-res / ADVSPEC_WEIGHT_RES=0",
    }


def _run_kernels(platform: str) -> dict:
    """Fused serving-kernel bench (ops/pallas_quant.py dequant-matmuls +
    the multi-position verify kernel in ops/pallas_paged.py), two phases:

    1. PARITY (interpret mode): each fused kernel against its XLA
       reference — int8 dequant-matmul, int4 dequant-matmul (even and
       odd contraction width: the packed zero-row pad), and the
       multi-position paged-attention span verify against a dense
       gather/softmax reference with an unmapped trailing page.
    2. REAL BATCHER A/B (int4-quantized llama, spec on): one growing-
       spec workload three ways — XLA verify + XLA matmul, Pallas span
       verify, Pallas span verify + fused matmul — byte-identical
       greedy transcripts across arms, per-arm decode tokens/s, and the
       retrace watch pinning zero unexpected recompiles with both
       kernels live.
    """
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    from adversarial_spec_tpu import obs
    from adversarial_spec_tpu.engine import spec as spec_mod
    from adversarial_spec_tpu.engine.scheduler import (
        ContinuousBatcher,
        SchedRequest,
    )
    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config
    from adversarial_spec_tpu.ops import pallas_paged, pallas_quant, quant

    interpret = platform == "cpu"
    rng = np.random.default_rng(0)
    parity: dict[str, bool] = {}
    max_abs_diff: dict[str, float] = {}

    def _pin(name: str, got, ref, tol: float) -> None:
        d = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
        max_abs_diff[name] = d
        parity[name] = bool(d <= tol)

    # --- 1a. Fused dequant-matmuls vs the XLA dequant-fusion path. ---
    x = jnp.asarray(rng.standard_normal((24, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    w8 = quant.quantize_int8(w)
    _pin(
        "matmul_int8",
        pallas_quant.matmul_int8(x, w8["q"], w8["scale"], interpret=True),
        quant.matmul(x, w8),
        0.0,  # whole-K accumulation order matches XLA's: bit-exact
    )
    w4 = quant.quantize_int4(w)
    _pin(
        "matmul_int4",
        pallas_quant.matmul_int4(x, w4["q4"], w4["scale"], interpret=True),
        quant.matmul(x, w4),
        2e-4,  # even/odd K-split reassociates the contraction sum
    )
    xo = jnp.asarray(rng.standard_normal((8, 255)), jnp.float32)
    wo = quant.quantize_int4(
        jnp.asarray(rng.standard_normal((255, 128)), jnp.float32)
    )
    _pin(
        "matmul_int4_odd_k",
        pallas_quant.matmul_int4(xo, wo["q4"], wo["scale"], interpret=True),
        quant.matmul(xo, wo),
        2e-4,
    )

    # --- 1b. Multi-position span verify vs a dense gather reference. --
    B, S, Hq, Hkv, D, page, P = 2, 3, 4, 2, 64, 16, 4
    g, T_slots = Hq // Hkv, P * page
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k_pages = jnp.asarray(
        rng.standard_normal((B * P + 1, Hkv, page, D)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((B * P + 1, Hkv, page, D)), jnp.float32
    )
    # Three mapped pages per row, trailing page unmapped (sentinel 0).
    table = np.zeros((B, P), np.int32)
    for b in range(B):
        table[b, :3] = 1 + b * P + np.arange(3)
    base = 2 * page + 5  # the span starts mid-page-3
    starts = np.zeros((B, S), np.int32)
    ends = np.asarray(
        base + 1 + np.arange(S)[None, :] + np.zeros((B, 1), np.int32),
        np.int32,
    )
    scale = float(D) ** -0.5
    got_mq = pallas_paged.paged_decode_attention_mq(
        q, k_pages, v_pages, jnp.asarray(table),
        jnp.asarray(starts), jnp.asarray(ends), interpret=True,
    )
    qn, kn, vn = (np.asarray(a, np.float64) for a in (q, k_pages, v_pages))
    ref_mq = np.zeros((B, S, Hq, D))
    for b in range(B):
        ids = np.maximum(table[b], 0)
        kd = kn[ids].transpose(1, 0, 2, 3).reshape(Hkv, T_slots, D)
        vd = vn[ids].transpose(1, 0, 2, 3).reshape(Hkv, T_slots, D)
        mapped = np.repeat(table[b] > 0, page)
        slot = np.arange(T_slots)
        for s in range(S):
            valid = mapped & (slot >= starts[b, s]) & (slot < ends[b, s])
            for h in range(Hq):
                logits = kd[h // g] @ qn[b, s, h] * scale
                logits[~valid] = -np.inf
                wts = np.exp(logits - logits.max())
                wts[~valid] = 0.0
                ref_mq[b, s, h] = (wts @ vd[h // g]) / max(wts.sum(), 1e-30)
    _pin("paged_mq_verify", got_mq, jnp.asarray(ref_mq, jnp.float32), 1e-4)

    # --- 2. Real batcher: three arms over one growing-spec workload. --
    size = "1b" if platform != "cpu" else "tiny"
    cfg = get_config("llama", size)
    params = quant.quantize_params(
        T.init_params(
            jax.random.key(0),
            cfg,
            dtype=jnp.bfloat16 if platform != "cpu" else jnp.float32,
        ),
        fmt="int4",
    )
    gamma = 4
    n_rounds, n_opp = 2, 2
    base_len, delta_len, max_new = (
        (1024, 256, 64) if platform != "cpu" else (192, 32, 16)
    )

    def arm(use_pallas_verify: bool, use_pallas_matmul: bool):
        spec_mod.configure(enabled=True, gamma=gamma)
        spec_mod.reset_stats()
        obs.configure(enabled=True)
        obs.reset_stats()
        obs.retrace.clear()
        prng = random.Random(1)
        seg = [prng.randrange(3, cfg.vocab_size) for _ in range(16)]
        spec = (seg * (base_len // len(seg) + 1))[:base_len]
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=n_opp,
            max_new_cap=max_new,
            page_size=64,
            capacity_tokens=1 << 15,
            greedy=True,
            prefix_cache=False,
            use_pallas_matmul=use_pallas_matmul,
        )
        b._use_pallas = use_pallas_verify
        b._pallas_interpret = interpret
        toks, n_toks = [], 0
        t0 = time.monotonic()
        for _ in range(n_rounds):
            for i in range(n_opp):
                b.submit(
                    SchedRequest(
                        req_id=i,
                        prompt_ids=list(spec),
                        max_new_tokens=max_new,
                    )
                )
            results = b.run_all()
            toks.append([r.tokens.tolist() for r in results])
            n_toks += sum(len(t) for t in toks[-1])
            spec = spec + toks[-1][0] + [
                prng.randrange(3, cfg.vocab_size) for _ in range(delta_len)
            ]
        wall = time.monotonic() - t0
        return toks, n_toks / max(wall, 1e-9), obs.snapshot()["retrace"]

    xla_toks, xla_tps, _ = arm(False, False)
    pv_toks, pv_tps, _ = arm(True, False)
    pf_toks, pf_tps, pf_retrace = arm(True, True)

    tokens_per_s = {
        "xla": round(xla_tps, 2),
        "pallas_verify": round(pv_tps, 2),
        "pallas_verify_fused_matmul": round(pf_tps, 2),
    }
    transcripts = {
        "pallas_verify": xla_toks == pv_toks,
        "pallas_verify_fused_matmul": xla_toks == pf_toks,
    }
    recompiles = pf_retrace["unexpected_recompiles"]
    gates_ok = bool(
        all(parity.values()) and all(transcripts.values()) and not recompiles
    )

    return {
        "metric": "kernels_fused_decode_tok_s",
        # Decode throughput with BOTH fused kernels live (span verify +
        # int4 dequant-matmul). On CPU the kernels run in interpret mode
        # so the number is a functional pin, not a speed claim (on the
        # chip: not measured); the contract here is parity +
        # byte-identical transcripts + zero retraces.
        "value": tokens_per_s["pallas_verify_fused_matmul"],
        "unit": "decode tok/s, Pallas span verify + fused int4 matmul",
        "vs_baseline": None,  # no published fused-kernel baseline
        "platform": platform,
        "within_budget": gates_ok,
        "model": f"llama-{size}",
        "gamma": gamma,
        "rounds": n_rounds,
        "opponents": n_opp,
        "interpret": interpret,
        "parity": parity,
        "max_abs_diff": {k: float(v) for k, v in max_abs_diff.items()},
        "tokens_per_s": tokens_per_s,
        "transcripts_byte_identical": transcripts,
        "unexpected_recompiles": recompiles,
        "escape_hatch": "ContinuousBatcher(use_pallas_matmul=False) / "
        "generate(use_pallas_matmul=False)",
    }


def _run_cancel(platform: str) -> dict:
    """Streaming early-convergence cancellation bench, two phases:

    1. MOCK DEBATE ROUNDS (deterministic): a 4-opponent pool where two
       opponents agree IMMEDIATELY but keep talking (``agree_tail`` —
       the verbose-agreement failure mode the matched-ceiling debate
       study makes pure waste) and two critique normally. Early cancel
       stops each agreeing opponent the moment ``[AGREE]`` completes;
       the headline is the fraction of the round's decode tokens that
       never had to be produced, pinned ≥ 30%, with every streamed
       transcript the blocking reply's byte-identical prefix.
    2. REAL BATCHER (tiny CPU model / 1b TPU): one slot, two queued
       requests — the first cancels after a few tokens, so the second
       admits into the freed slot and the whole drain finishes in far
       fewer decode dispatches than the first request's budget alone
       would have taken (freed-slot re-admission, pinned), with
       ``check_invariants`` clean after the cancel and
       ``unexpected_recompiles`` 0 with streaming on.
    """
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu import obs
    from adversarial_spec_tpu.debate.core import run_round
    from adversarial_spec_tpu.engine import streaming as stream_mod
    from adversarial_spec_tpu.engine.scheduler import (
        ContinuousBatcher,
        SchedRequest,
    )
    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    spec_doc = (
        "## Goals\nServe heavy traffic fast.\n## Constraints\n"
        "The allocator SHALL bound page reuse by refcount.\n" * 8
    )
    models = [
        "mock://critic?agree_after=1&agree_tail=160",
        "mock://critic?agree_after=1&agree_tail=160",
        "mock://critic",
        "mock://critic",
    ]

    def mock_round(early_cancel: bool):
        stream_mod.configure(enabled=True, early_cancel=early_cancel)
        stream_mod.reset_stats()
        t0 = time.monotonic()
        result = run_round(spec_doc, list(models), round_num=1)
        wall = time.monotonic() - t0
        texts = [r.critique for r in result.responses]
        return texts, wall, stream_mod.snapshot()

    on_texts, on_wall, on_snap = mock_round(True)
    off_texts, off_wall, _ = mock_round(False)
    # Byte-identical transcripts up to each cancellation point: every
    # streamed reply is a prefix of the blocking reply.
    prefix_ok = all(
        full.startswith(part) for part, full in zip(on_texts, off_texts)
    )
    saved_fraction = on_snap["saved_fraction"]

    # --- 2. Real batcher: freed-slot re-admission. -------------------
    size = "1b" if platform != "cpu" else "tiny"
    cfg = get_config("llama", size)
    params = T.init_params(
        jax.random.key(0),
        cfg,
        dtype=jnp.bfloat16 if platform != "cpu" else jnp.float32,
    )
    budget = 256 if platform == "cpu" else 512
    prompts = [[5, 6, 7, 8] * 24, [9, 10, 11, 12] * 24]

    def batcher_drain(cancel: bool, only_req0: bool = False):
        stream_mod.configure(enabled=True, early_cancel=True)
        stream_mod.reset_stats()
        obs.configure(enabled=True)
        obs.reset_stats()
        obs.retrace.clear()
        b = ContinuousBatcher(
            params,
            cfg,
            max_batch=1,
            max_new_cap=budget,
            page_size=64,
            capacity_tokens=1 << 13,
            greedy=True,
        )
        cb = (lambda toks: len(toks) < 8) if cancel else None
        b.submit(
            SchedRequest(
                req_id=0,
                prompt_ids=list(prompts[0]),
                max_new_tokens=budget,
                on_tokens=cb,
            )
        )
        if not only_req0:
            b.submit(
                SchedRequest(
                    req_id=1,
                    prompt_ids=list(prompts[1]),
                    max_new_tokens=16,
                )
            )
        t0 = time.monotonic()
        results = b.run_all()
        wall = time.monotonic() - t0
        b.allocator.check_invariants()
        steps = sum(
            1
            for e in obs.recorder.events()
            if e["type"] == "step" and e["kind"] != "prefill"
        )
        return results, wall, steps, obs.snapshot()

    c_res, c_wall, c_steps, c_obs = batcher_drain(True)
    f_res, f_wall, f_steps, _ = batcher_drain(False)
    _, _, alone_steps, _ = batcher_drain(False, only_req0=True)
    r0 = next(r for r in c_res if r.req_id == 0)
    r1 = next(r for r in c_res if r.req_id == 1)
    # Re-admission pin: with the cancel, the whole 2-request drain (the
    # queued request included, START to FINISH) takes fewer decode
    # dispatches than request 0's budget ALONE takes uncancelled — the
    # queued request was admitted into the freed slot and completed
    # before the cancelled request's old budget would have elapsed.
    readmit_ok = bool(
        r0.cancelled
        and r1.n_generated == 16
        and r1.error is None
        and c_steps < alone_steps
    )
    within = saved_fraction >= 0.30 and prefix_ok and readmit_ok

    return {
        "metric": "cancel_tokens_saved_fraction",
        "value": round(saved_fraction, 4),
        "unit": "fraction of round decode tokens saved by early cancel",
        "vs_baseline": None,  # no published cancellation baseline
        "platform": platform,
        "within_budget": within,
        "budget": 0.30,
        "model": f"llama-{size}",
        "mock": {
            "opponents": len(models),
            "cancels": on_snap["cancels"],
            "tokens_saved": on_snap["tokens_saved"],
            "streamed_tokens": on_snap["streamed_tokens"],
            "saved_fraction": saved_fraction,
            "transcripts_prefix_identical": prefix_ok,
            "wall_s_cancel_on": round(on_wall, 3),
            "wall_s_cancel_off": round(off_wall, 3),
        },
        "batcher": {
            "budget": budget,
            "cancelled_at": int(r0.n_generated),
            "tokens_saved": int(r0.tokens_saved),
            "decode_steps_with_cancel": c_steps,
            "decode_steps_without": f_steps,
            "decode_steps_req0_alone_uncancelled": alone_steps,
            "readmission_before_old_budget": readmit_ok,
            "wall_s_with_cancel": round(c_wall, 3),
            "wall_s_without": round(f_wall, 3),
            "unexpected_recompiles": c_obs["retrace"][
                "unexpected_recompiles"
            ],
        },
        "escape_hatch": "--no-stream / --no-early-cancel "
        "(ADVSPEC_STREAM=0 / ADVSPEC_EARLY_CANCEL=0)",
    }


def _run_recover(platform: str) -> dict:
    """Mid-round kill recovery bench (deterministic CPU mock,
    subprocess-driven — writes BENCH_recover.json):

    A 4-opponent round is SIGKILLed the instant the 2nd opponent's
    journal record becomes durable (``ADVSPEC_JOURNAL_KILL_AFTER``),
    then resumed with ``--resume``; a cold re-run of the same round
    with fresh state is the baseline. The headline is the fraction of
    the round's ENGINE tokens (prefill actually computed + decode
    actually produced) that recovery salvaged vs that cold re-run —
    journal-served opponents pay zero engine work, and the
    content-addressed KV disk store (PR 7) rehydrates the re-issued
    opponents' shared prefix, so the budget is >= 50% salvaged
    (``within_budget``). Transcripts must be byte-identical to the
    cold run throughout. Escape hatch: ``--no-journal``
    (``ADVSPEC_JOURNAL=0``).
    """
    import signal
    import tempfile

    # ONE subprocess-CLI driver for the whole kill-recovery tooling:
    # the drill (tools/chaos_run.py --crash) and this bench must test
    # the same recovery contract, so they share the helper instead of
    # drifting apart.
    from tools.chaos_run import _cli

    repo = os.path.dirname(os.path.abspath(__file__))
    spec_doc = (
        "## Goals\nServe heavy traffic from millions of users, fast.\n"
        "## Constraints\n"
        "The allocator SHALL bound page reuse by refcount.\n" * 6
    )
    models = [f"mock://critic?v={k}" for k in range(1, 5)]
    kill_after = 2

    def _failed(stage: str, proc) -> dict:
        # A failed child is a bench VERDICT, not a crash: surface the
        # child's stderr in the payload instead of dying on its empty
        # stdout (the bench_trend lesson from PR 8).
        return {
            "metric": "recover_tokens_salvaged_fraction",
            "value": 0.0,
            "unit": "fraction of round prefill+decode tokens salvaged "
            "across a mid-round SIGKILL (journal + tier store) vs cold",
            "vs_baseline": None,
            "platform": platform,
            "within_budget": False,
            "budget": 0.5,
            "error": (
                f"{stage} subprocess failed rc={proc.returncode}: "
                f"{proc.stderr[-400:]}"
            ),
            "escape_hatch": "--no-journal (ADVSPEC_JOURNAL=0)",
        }

    with tempfile.TemporaryDirectory(prefix="advspec-recover-") as td:

        def run_cli(args, env, stdin=None):
            return _cli(args, env, td, stdin=stdin)

        base = {
            **os.environ,
            "PYTHONPATH": repo,
            "JAX_PLATFORMS": "cpu",
            # The tiered-KV disk store persists the crashed process's
            # prefix blocks; the resumed process rehydrates from it.
            "ADVSPEC_KV_TIER": "1",
        }
        critique = [
            "critique",
            "--models",
            ",".join(models),
            "--json",
        ]
        env_kill = {
            **base,
            "ADVSPEC_SESSIONS_DIR": os.path.join(td, "sessions"),
            "ADVSPEC_KV_STORE_DIR": os.path.join(td, "store"),
            "ADVSPEC_JOURNAL_KILL_AFTER": str(kill_after),
        }
        p_kill = run_cli(
            [*critique, "--session", "recover"], env_kill, stdin=spec_doc
        )
        killed_ok = p_kill.returncode == -signal.SIGKILL
        env_resume = dict(env_kill)
        env_resume.pop("ADVSPEC_JOURNAL_KILL_AFTER")
        p_resume = run_cli(["critique", "--resume", "recover", "--json"],
                           env_resume)
        if p_resume.returncode != 0:
            return _failed("resume", p_resume)
        resumed = json.loads(p_resume.stdout)
        env_cold = {
            **base,
            "ADVSPEC_SESSIONS_DIR": os.path.join(td, "sessions-cold"),
            "ADVSPEC_KV_STORE_DIR": os.path.join(td, "store-cold"),
        }
        p_cold = run_cli(
            [*critique, "--session", "recover"], env_cold, stdin=spec_doc
        )
        if p_cold.returncode != 0:
            return _failed("cold reference", p_cold)
        cold = json.loads(p_cold.stdout)

    def engine_tokens(payload: dict, salvaged_decode: float = 0.0) -> dict:
        # Prefill the engine actually computed this round (journal-
        # served opponents never reach the engine; tier-rehydrated
        # prefix tokens are already netted out by the cache stats) +
        # decode it actually produced (total output minus the decode
        # that came back off the journal).
        prefill = payload["perf"]["prefix_cache"]["prefilled_tokens"]
        out_total = sum(
            r["output_tokens"] for r in payload["results"]
        )
        return {
            "prefill_tokens": int(prefill),
            "decode_tokens": int(out_total - salvaged_decode),
            "total": int(prefill + out_total - salvaged_decode),
        }

    salvaged_decode = resumed["perf"]["counters"].get(
        "debate/journal.salvaged_decode_tokens", 0.0
    )
    served = int(
        resumed["perf"]["counters"].get("debate/journal.served", 0)
    )
    paid_cold = engine_tokens(cold)
    paid_resumed = engine_tokens(resumed, salvaged_decode)
    salvaged_fraction = (
        1.0 - paid_resumed["total"] / paid_cold["total"]
        if paid_cold["total"]
        else 0.0
    )
    transcripts_ok = all(
        a["response"] == b["response"]
        for a, b in zip(resumed["results"], cold["results"])
    )
    within = (
        killed_ok
        and served == kill_after
        and transcripts_ok
        and salvaged_fraction >= 0.5
    )
    return {
        "metric": "recover_tokens_salvaged_fraction",
        "value": round(salvaged_fraction, 4),
        "unit": "fraction of round prefill+decode tokens salvaged "
        "across a mid-round SIGKILL (journal + tier store) vs cold",
        "vs_baseline": None,  # no published recovery baseline
        "platform": platform,
        "within_budget": within,
        "budget": 0.5,
        "opponents": len(models),
        "kill_after_completions": kill_after,
        "victim_sigkilled": killed_ok,
        "journal_served": served,
        "salvaged_decode_tokens": int(salvaged_decode),
        "paid_cold": paid_cold,
        "paid_recovered": paid_resumed,
        "transcripts_byte_identical": transcripts_ok,
        "escape_hatch": "--no-journal (ADVSPEC_JOURNAL=0)",
    }


def _run_serve(platform: str) -> dict:
    """Serve-daemon bench (deterministic CPU mock — writes
    BENCH_serve.json):

    - **capacity point**: an in-process ``advspec serve`` daemon with
      wide-open caps takes a closed burst of debates; the measured
      completion rate (debates/s and charged tokens/s on the mock) is
      the capacity the admission caps should be sized against — the
      number "millions of users" divides by.
    - **overload storm** (shared with ``tools/chaos_run.py
      --overload`` so the bench and the drill can never test different
      contracts): an open-loop burst at several times the backlog cap
      must shed typed with zero accepted-request loss, brownout
      entered, interactive p99 TTFT within the drill SLO.
    - **SIGTERM drain drill** (shared with ``--drain``): a subprocess
      daemon SIGTERMed mid-burst exits 0 with a clean drain report and
      journal-resumable drained sessions.

    Headline: capacity (debates/s). ``shed_fraction``,
    ``brownout_transitions``, and ``capacity`` are the schema fields
    tools/bench_trend.py validates for this mode. Escape hatch: none
    needed — the daemon only runs when asked to (``debate serve``).
    """
    import asyncio
    import threading

    from adversarial_spec_tpu import serve as serve_mod
    from adversarial_spec_tpu.serve.client import ServeClient
    from adversarial_spec_tpu.serve.daemon import ServeDaemon

    n_debates, n_opp = 32, 2
    spec = (
        "## Goals\nServe heavy traffic from millions of users, fast.\n"
        "## Constraints\n" + "The daemon SHALL shed, not collapse. " * 24
    )
    models = [f"mock://critic?v={k}" for k in range(n_opp)]

    # Phase 1 — capacity point: wide-open caps, closed burst, measure
    # the drain rate the admission controller should be sized against.
    serve_mod.reset_stats()
    serve_mod.configure(
        max_queue_depth=n_debates + 1,
        max_backlog_tokens=10_000_000,
        tenant_quota_tokens=0,
        drain_deadline_s=5.0,
    )
    with tempfile.TemporaryDirectory(prefix="advspec-bench-serve-") as td:
        sock = os.path.join(td, "serve.sock")
        ready = threading.Event()
        daemon = ServeDaemon(sock, sessions_dir=os.path.join(td, "s"))
        th = threading.Thread(
            target=lambda: asyncio.run(daemon.run(ready=ready)),
            daemon=True,
        )
        th.start()
        if not ready.wait(10):
            raise RuntimeError("bench serve daemon did not come up")
        client = ServeClient(sock, timeout_s=120)
        try:
            t0 = time.monotonic()
            ids = [
                client.submit_debate(
                    spec,
                    models,
                    tenant=f"t{k % 4}",
                    stream=False,
                    max_new_tokens=512,
                )
                for k in range(n_debates)
            ]
            lost = 0
            for rid in ids:
                last = client.collect(rid, timeout_s=120)[-1]
                if last["event"] != "result" or last.get("error") or any(
                    r["error"] for r in last["results"]
                ):
                    lost += 1
            capacity_wall = time.monotonic() - t0
            cap_snap = serve_mod.snapshot()
            client.drain()
        finally:
            client.close()
            th.join(timeout=15)
    debates_per_s = round(n_debates / capacity_wall, 2)
    tokens_per_s = round(cap_snap["tokens_charged"] / capacity_wall, 1)

    # Phases 2+3 — the chaos drills, verbatim (one contract).
    from tools.chaos_run import run_drain_drill, run_overload

    overload_failures, overload = run_overload(verbose=False)
    drain_failures, drain = run_drain_drill(verbose=False)

    within = (
        lost == 0
        and debates_per_s > 0
        and not overload_failures
        and not drain_failures
    )
    return {
        "metric": "serve_capacity_debates_per_s",
        "value": debates_per_s,
        "unit": "mock debates/s through the serve daemon at the "
        "capacity point (closed burst, wide-open admission caps)",
        "vs_baseline": None,  # no published serving baseline
        "platform": platform,
        "within_budget": within,
        "capacity": {
            "debates": n_debates,
            "opponents": n_opp,
            "wall_s": round(capacity_wall, 3),
            "debates_per_s": debates_per_s,
            "tokens_per_s": tokens_per_s,
            "lost": lost,
        },
        "shed_fraction": overload.get("shed_fraction", 0.0),
        "brownout_transitions": int(
            overload.get("brownout_entries", 0)
            + overload.get("brownout_exits", 0)
        ),
        "overload": {**overload, "failures": overload_failures,
                     "ok": not overload_failures},
        "drain": {**drain, "failures": drain_failures,
                  "ok": not drain_failures},
        "escape_hatch": "the daemon only runs when asked to "
        "(debate serve); one-shot CLI rounds are unchanged",
    }


def _run_capacity(platform: str) -> dict:
    """Capacity-frontier bench (deterministic seeded replay on the CPU
    mock — writes BENCH_capacity.json): delegates to
    ``tools/load_replay.py`` — a seeded heavy-tailed synthetic trace is
    replayed open-loop against an in-process serve daemon, binary-
    searching the rate multiplier until the SLO breaches, per knob arm
    (replica count 1 vs 3 through the scheduler's capacity provider).

    Headline: accepted debates/s at the SLO frontier on the baseline
    arm. ``vs_baseline`` compares against the committed
    BENCH_capacity.json, and tools/bench_trend.py fails the gate when
    the frontier drops >10% — capacity regressions, not just single-
    stream wall, now fail loudly. Escape hatch: the harness only runs
    when asked to; deleting BENCH_capacity.json drops the gate."""
    import tools.load_replay as load_replay

    slo = load_replay.SLOSpec()
    reqs = load_replay.synthesize(load_replay.SynthSpec(seed=0, requests=64))
    frontier = load_replay.frontier_sweep(
        reqs,
        [
            load_replay.ServeKnobs(replicas=1),
            load_replay.ServeKnobs(replicas=3),
        ],
        slo,
        max_doublings=4,
        bisect_iters=2,
    )
    baseline = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_capacity.json"
    )
    from pathlib import Path

    payload = load_replay.bench_payload(
        frontier,
        slo,
        "synthetic seed=0 requests=64",
        platform=platform,
        baseline_path=Path(baseline),
    )
    return payload


def _run_fleet(platform: str) -> dict:
    """Fleet bench (deterministic CPU mock — writes BENCH_fleet.json):

    A multi-debate workload (6 debates x 3 rounds x 3 opponents, each
    debate its own document) runs through the fleet router three ways:

    - **single** — 1 in-process replica (the pre-fleet topology's
      capacity: every debate serializes onto one engine's busy clock);
    - **multi/affinity** — 3 replicas, prefix-affinity routing (each
      debate consistent-hashes onto one replica, so rounds 2+ re-hit
      the prefix KV that replica already holds);
    - **multi/random** — 3 replicas, round-robin routing (the control
      arm: a debate's rounds scatter, so cross-round prefix reuse
      mostly misses).

    Busy seconds are the mock's synthetic tokens/1024 clock summed per
    replica (prefill actually computed + decode produced), so the
    aggregate-throughput model is deterministic: single-replica
    tokens/s divides by the ONE replica's busy clock, fleet tokens/s
    by the SLOWEST replica's (replicas serve debates concurrently).
    Headline: the >= 2-replica aggregate speedup (budget > 1x), with
    affinity's cross-round cache saved-fraction required to beat
    random routing, transcripts byte-identical across all three arms,
    and the replica-kill recovery drill (tools/chaos_run.py
    --replica-kill: SIGKILL one of 2 worker replicas mid-round) green.
    Escape hatch: --no-fleet (ADVSPEC_FLEET=0) keeps the single-engine
    topology.
    """
    from adversarial_spec_tpu import fleet as fleet_mod
    from adversarial_spec_tpu.engine import kvtier
    from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams
    from adversarial_spec_tpu.fleet.router import FleetEngine

    n_debates, n_rounds, n_opp = 6, 3, 3
    docs = [
        f"## Spec {d}\n"
        + "The allocator SHALL bound page reuse by refcount. " * 40
        + f"\nDebate {d}'s own constraint body, revision zero.\n"
        for d in range(n_debates)
    ]
    params = SamplingParams()

    # The affinity phase measures DEVICE-cache reuse: tiering off so a
    # random-routed miss is a genuine re-prefill, not a disk save.
    kvtier.configure(enabled=False)

    def run_arm(replicas: int, affinity: bool) -> dict:
        prefix_mod.configure(enabled=True, max_pages=0)
        prefix_mod.reset_stats()
        fleet_mod.reset_stats()
        engine = FleetEngine(
            replicas=replicas, transport="inproc", affinity=affinity
        )
        transcripts = []
        for r in range(1, n_rounds + 1):
            for d in range(n_debates):
                reqs = [
                    ChatRequest(
                        model=f"mock://critic?v={k}",
                        system="You are an adversarial spec reviewer.",
                        user=(
                            f"Debate round {r}\n--- DOCUMENT ---\n"
                            f"{docs[d]}\n--- END DOCUMENT ---"
                        ),
                        affinity_key=f"debate-{d}",
                    )
                    for k in range(n_opp)
                ]
                comps = engine.chat(reqs, params)
                if not all(c.ok for c in comps):
                    raise RuntimeError("mock fleet round failed")
                transcripts.extend(c.text for c in comps)
        busys = sorted(
            (s["busy_s"] for s in engine.router.replica_stats()),
            reverse=True,
        )
        snap = prefix_mod.snapshot()
        fleet_snap = fleet_mod.snapshot()
        engine.shutdown()
        total = snap["prefilled_tokens"] + snap["saved_tokens"]
        decode = sum(_estimate(t) for t in transcripts)
        saved_fraction = snap["saved_tokens"] / total if total else 0.0
        return {
            "replicas": replicas,
            "affinity": affinity,
            "transcripts": transcripts,
            "busy_s": [round(b, 6) for b in busys],
            "tokens": int(snap["prefilled_tokens"] + decode),
            "tokens_per_s": round(
                (snap["prefilled_tokens"] + decode) / busys[0], 1
            ),
            "cache_saved_fraction": round(saved_fraction, 4),
            "affinity_hit_rate": fleet_snap["affinity_hit_rate"],
        }

    def _estimate(text: str) -> int:
        return max(1, len(text) // 4)

    single = run_arm(1, affinity=True)
    multi = run_arm(3, affinity=True)
    random_arm = run_arm(3, affinity=False)

    transcripts_ok = (
        single["transcripts"] == multi["transcripts"]
        and single["transcripts"] == random_arm["transcripts"]
    )
    speedup = (
        multi["tokens_per_s"] / single["tokens_per_s"]
        if single["tokens_per_s"]
        else 0.0
    )

    # Phase 2: the replica-loss recovery drill (worker subprocesses,
    # SIGKILL mid-round) — shared with tools/chaos_run.py so the bench
    # and the drill can never test different contracts.
    from tools.chaos_run import run_replica_kill

    kill_failures, kill_payload = run_replica_kill(verbose=False)

    for arm in (single, multi, random_arm):
        arm.pop("transcripts")
    within = (
        speedup > 1.0
        and multi["cache_saved_fraction"] > random_arm["cache_saved_fraction"]
        and transcripts_ok
        and not kill_failures
    )
    return {
        "metric": "fleet_aggregate_speedup",
        "value": round(speedup, 3),
        "unit": "aggregate mock tokens/s, 3 replicas w/ prefix-affinity "
        "routing vs 1 replica, equal workload",
        "vs_baseline": None,  # no published fleet baseline
        "platform": platform,
        "within_budget": within,
        "budget": 1.0,
        "workload": {
            "debates": n_debates,
            "rounds": n_rounds,
            "opponents": n_opp,
        },
        "single": single,
        "multi_affinity": multi,
        "multi_random": random_arm,
        "affinity_vs_random_saved_fraction": [
            multi["cache_saved_fraction"],
            random_arm["cache_saved_fraction"],
        ],
        "transcripts_byte_identical": transcripts_ok,
        "replica_kill": {
            **kill_payload,
            "failures": kill_failures,
            "ok": not kill_failures,
        },
        "escape_hatch": "--no-fleet (ADVSPEC_FLEET=0)",
    }


def _run_elastic(platform: str) -> dict:
    """Elastic-fleet bench (mock serve daemon — writes
    BENCH_elastic.json), two drills:

    **Load step** — the same wave-burst open-loop demand step runs
    against two fleets at the SAME chip ceiling (3 replicas):

    - **fixed** — 3 replicas from the start, no autoscaler: the serve
      scheduler's admission cap and brownout thresholds are sized for
      ONE engine (the pre-elastic coupling), so the step sheds at 1x
      the per-replica backlog cap no matter how many chips idle behind
      the router;
    - **elastic** — floor 1, ceiling 3, the autoscaler's capacity
      provider stretches the admission cap and brownout thresholds
      with LIVE membership: the fleet grows under the step and admits
      what the fixed arm refuses.

    Headline: accepted-debate throughput (completed debates per storm
    second), elastic vs fixed, with interactive p99 TTFT reported for
    both arms (growing must not trade admission for latency collapse).

    **Scale-in** — the fleet-bench debate workload runs once on a
    static 2-replica fleet and once with a PLANNED scale-in (drain ->
    retire through the autoscaler's lifecycle) between rounds:
    transcripts must be byte-identical and duplicated completions
    zero — membership change loses nothing.

    Escape hatch: --no-fleet / ADVSPEC_FLEET_AUTOSCALE=0 keeps the
    static topology.
    """
    import asyncio
    import threading

    from adversarial_spec_tpu import fleet as fleet_mod
    from adversarial_spec_tpu import serve as serve_mod
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams
    from adversarial_spec_tpu.fleet.autoscale import Autoscaler
    from adversarial_spec_tpu.fleet.router import FleetEngine
    from adversarial_spec_tpu.serve.client import ServeClient
    from adversarial_spec_tpu.serve.daemon import ServeDaemon

    n_waves, wave_size = 8, 6
    spec_doc = (
        "## Goals\nAbsorb a demand step without shedding accepted work.\n"
        "## Constraints\n" + "The fleet SHALL grow before it sheds. " * 10
    )
    models = ["mock://critic?v=1", "mock://critic?v=2"]
    old_serve = serve_mod.snapshot()
    old_fleet = fleet_mod.config()

    def run_step(elastic: bool) -> dict:
        serve_mod.reset_stats()
        serve_mod.configure(
            max_queue_depth=64,
            max_backlog_tokens=4000,  # per-replica; elastic stretches
            tenant_quota_tokens=0,
            drain_deadline_s=3.0,
        )
        fleet_mod.shutdown_fleet()
        fleet_mod.configure(
            enabled=True,
            replicas=1 if elastic else 3,  # equal CEILING, not floor
            transport="inproc",
            autoscale=elastic,
            min_replicas=1,
            max_replicas=3,
            scale_out_fraction=0.6,
            scale_in_fraction=0.15,
            scale_out_ticks=1,
            # Scale-in hysteresis must exceed the inter-wave gap or the
            # controller flaps the fleet down between bursts and pays a
            # re-warm on the next one — the drill pins the knob doing
            # its job, not a lucky cadence.
            scale_in_ticks=20,
            scale_cooldown_s=0.05,
            scale_interval_s=0.01,
        )
        fleet_mod.reset_stats()
        with tempfile.TemporaryDirectory(prefix="advspec-elastic-") as td:
            sock = os.path.join(td, "serve.sock")
            ready = threading.Event()
            daemon = ServeDaemon(
                sock, sessions_dir=os.path.join(td, "sessions")
            )
            th = threading.Thread(
                target=lambda: asyncio.run(daemon.run(ready=ready)),
                daemon=True,
            )
            th.start()
            if not ready.wait(10):
                raise RuntimeError("bench daemon did not come up")
            client = ServeClient(sock, timeout_s=60)
            try:
                # Warmup: one debate end-to-end so neither arm pays
                # first-request construction costs inside the
                # measured window (arm order must not decide the
                # headline).
                client.collect(
                    client.submit_debate(
                        spec_doc, models, tenant="warm", max_new_tokens=32
                    ),
                    timeout_s=60,
                )
                # The load step: waves of an UNPACED burst (each wave
                # alone overruns one replica's admission cap several
                # times) separated by a gap longer than the control
                # loop's tick — a demand step the fixed arm must shed
                # into and the elastic arm gets to grow into.
                t0 = time.monotonic()
                submitted = []
                for wave in range(n_waves):
                    for k in range(wave_size):
                        tier = "interactive" if k % 2 else "batch"
                        submitted.append(
                            (
                                client.submit_debate(
                                    spec_doc,
                                    models,
                                    tenant=f"t{k % 2}",
                                    tier=tier,
                                    max_new_tokens=1280,
                                ),
                                tier,
                            )
                        )
                    time.sleep(0.03)
                accepted = completed = shed = 0
                ttfts: list[float] = []
                for rid, tier in submitted:
                    evs = client.collect(rid, timeout_s=120)
                    last = evs[-1]
                    if evs[0]["event"] == "accepted":
                        accepted += 1
                        if last["event"] == "result" and not last.get(
                            "error"
                        ):
                            completed += 1
                            if tier == "interactive":
                                ttfts.append(float(last["ttft_s"]))
                    elif last["event"] == "shed":
                        shed += 1
                wall = time.monotonic() - t0
                client.drain()
            finally:
                client.close()
                th.join(timeout=15)
        from adversarial_spec_tpu.obs.metrics import percentile

        p99 = percentile(ttfts, 0.99)
        return {
            "elastic": {"yes": elastic},
            "accepted": accepted,
            "completed": completed,
            "shed": shed,
            "storm_wall_s": round(wall, 3),
            "accepted_debates_per_s": round(completed / wall, 3)
            if wall
            else 0.0,
            "ttft_p99_s": round(p99, 4),
            "scale_outs": fleet_mod.stats.scale_outs,
            "scale_ins": fleet_mod.stats.scale_ins,
            "flaps_suppressed": fleet_mod.stats.flaps_suppressed,
        }

    def run_scale_in(planned: bool) -> tuple[list[str], int]:
        """The fleet-bench workload with (optionally) a planned
        scale-in between rounds; returns (transcripts, dup count)."""
        fleet_mod.reset_stats()
        n_deb, n_rounds, n_opp = 4, 2, 3
        params = SamplingParams()
        engine = FleetEngine(replicas=2, transport="inproc")
        scaler = Autoscaler(
            engine,
            pressure=lambda: {"backlog_tokens": 0, "active_keys": []},
        )
        transcripts: list[str] = []
        try:
            for r in range(1, n_rounds + 1):
                for d in range(n_deb):
                    reqs = [
                        ChatRequest(
                            model=f"mock://critic?v={k}",
                            system="You are an adversarial spec reviewer.",
                            user=(
                                f"Debate round {r}\n--- DOCUMENT ---\n"
                                f"{spec_doc}\n--- END DOCUMENT ---"
                            ),
                            affinity_key=f"debate-{d}",
                        )
                        for k in range(n_opp)
                    ]
                    comps = engine.chat(reqs, params)
                    if not all(c.ok for c in comps):
                        raise RuntimeError("mock elastic round failed")
                    transcripts.extend(c.text for c in comps)
                if planned and r == 1:
                    # The planned handoff: drain the least-affine
                    # replica out of the ring, retire it through the
                    # lifecycle surgery, keep serving on the survivor.
                    fleet_mod.configure(min_replicas=1, scale_cooldown_s=0.0)
                    scaler._scale_in({}, 2, cfg=fleet_mod.config())
                    if len(engine.router.alive_ids()) != 1:
                        raise RuntimeError("planned scale-in did not land")
        finally:
            scaler.shutdown()
            dup = fleet_mod.stats.duplicated_completions
            engine.shutdown()
        return transcripts, dup

    try:
        fixed = run_step(elastic=False)
        elastic = run_step(elastic=True)
        base_transcripts, base_dup = run_scale_in(planned=False)
        scaled_transcripts, scaled_dup = run_scale_in(planned=True)
    finally:
        fleet_mod.shutdown_fleet()
        fleet_mod.configure(
            enabled=old_fleet.enabled,
            replicas=old_fleet.replicas,
            transport=old_fleet.transport,
            autoscale=old_fleet.autoscale,
            min_replicas=old_fleet.min_replicas,
            max_replicas=old_fleet.max_replicas,
            scale_out_fraction=old_fleet.scale_out_fraction,
            scale_in_fraction=old_fleet.scale_in_fraction,
            scale_out_ticks=old_fleet.scale_out_ticks,
            scale_in_ticks=old_fleet.scale_in_ticks,
            scale_cooldown_s=old_fleet.scale_cooldown_s,
            scale_interval_s=old_fleet.scale_interval_s,
        )
        fleet_mod.reset_stats()
        serve_mod.configure(
            max_queue_depth=old_serve["max_queue_depth"],
            max_backlog_tokens=old_serve["max_backlog_tokens"],
            tenant_quota_tokens=old_serve["tenant_quota_tokens"],
            drain_deadline_s=old_serve["drain_deadline_s"],
        )
        serve_mod.reset_stats()

    ratio = (
        elastic["accepted_debates_per_s"] / fixed["accepted_debates_per_s"]
        if fixed["accepted_debates_per_s"]
        else 0.0
    )
    transcripts_ok = base_transcripts == scaled_transcripts
    dup_total = base_dup + scaled_dup
    within = (
        ratio > 1.0
        and elastic["scale_outs"] >= 1
        and transcripts_ok
        and dup_total == 0
    )
    return {
        "metric": "elastic_accepted_throughput_ratio",
        "value": round(ratio, 3),
        "unit": "completed accepted debates/s under a wave-burst load "
        "step, "
        "elastic fleet (floor 1, ceiling 3) vs fixed 3-replica fleet "
        "with single-engine admission caps (equal chip ceiling)",
        "vs_baseline": None,  # no published elasticity baseline
        "platform": platform,
        "within_budget": within,
        "budget": 1.0,
        "workload": {
            "waves": n_waves,
            "wave_size": wave_size,
            "wave_gap_ms": 30,
        },
        "accepted_throughput_elastic": elastic["accepted_debates_per_s"],
        "accepted_throughput_fixed": fixed["accepted_debates_per_s"],
        "ttft_p99_s": {
            "elastic": elastic["ttft_p99_s"],
            "fixed": fixed["ttft_p99_s"],
        },
        "load_step": {"elastic": elastic, "fixed": fixed},
        "transcripts_byte_identical": {"scale_in": transcripts_ok},
        "duplicated_completions": dup_total,
        "escape_hatch": "--no-fleet (ADVSPEC_FLEET_AUTOSCALE=0)",
    }


def _run_disagg(platform: str) -> dict:
    """Prefill/decode disaggregation bench (deterministic CPU mock —
    writes BENCH_disagg.json).

    A prefill-heavy debate workload (8 debates sharing one large
    document, 2 rounds, 2 opponents, short decode budgets) runs
    through two fleets at EQUAL replica count (4):

    - **symmetric** — 4 undifferentiated replicas, prefix-affinity
      routing: every replica pays the shared document's full prefill
      the first time a debate lands on it, stalling that debate's
      first decode step behind ~P tokens of prefill;
    - **disagg** — 2 prefill + 2 decode replicas: round-1 admissions
      over the handoff threshold prefill on the prefill pool, publish
      their paged-KV blocks to the shared content-addressed store, and
      the decode replica promotes the shipped chains before its first
      step — decode-side prefill shrinks to the residual (unpaged
      tail) tokens.

    Both clocks are the mock's deterministic tokens/1024 busy model
    (prefill actually computed + decode produced), so the bench is
    exact on CPU: **decode-side TTFT** per request is (input -
    cached)/1024 synthetic seconds — the prefill stall the serving
    replica pays before its first decode step — and accepted-debate
    throughput divides completed debates by the BUSIEST replica's
    clock (replicas serve concurrently; the slowest pool gates).
    Headline: round-1 decode-side p99 TTFT, disagg vs symmetric, with
    the handoff hit fraction (adopted/attempts), byte-identical
    transcripts across arms, zero duplicated completions, and zero
    decode-side unexpected recompiles required. Escape hatch:
    ADVSPEC_FLEET_PREFILL_REPLICAS=0 keeps the symmetric topology.
    """
    from adversarial_spec_tpu import fleet as fleet_mod
    from adversarial_spec_tpu import obs as obs_mod
    from adversarial_spec_tpu.engine import kvtier
    from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams
    from adversarial_spec_tpu.fleet.router import FleetEngine

    n_debates, n_rounds, n_opp, n_replicas = 8, 2, 2, 4
    # One large shared document (the prefill-heavy part), with every
    # per-debate / per-round variation APPENDED so the shared prefix
    # stays block-aligned across debates, rounds, and opponents.
    shared_doc = (
        "## Goals\nServe first tokens before the prefill pool pays "
        "for them twice.\n## Constraints\n"
        + "The decode replica SHALL NOT re-prefill shipped blocks. " * 120
    )
    params = SamplingParams(max_new_tokens=64, greedy=True)

    def make_reqs(d: int, r: int) -> list:
        return [
            ChatRequest(
                model=f"mock://critic?v={k}",
                system="You are an adversarial spec reviewer.",
                user=(
                    f"--- DOCUMENT ---\n{shared_doc}\n--- END DOCUMENT "
                    f"---\nDebate {d} round {r}: focus on section {d}."
                ),
                affinity_key=f"debate-{d}",
            )
            for k in range(n_opp)
        ]

    def run_arm(prefill_replicas: int) -> dict:
        prefix_mod.configure(enabled=True, max_pages=0)
        prefix_mod.reset_stats()
        fleet_mod.reset_stats()
        obs_mod.reset_stats()
        obs_mod.retrace.clear()
        with tempfile.TemporaryDirectory(prefix="advspec-disagg-") as td:
            # The shared content-addressed store: the handoff's wire.
            # Both arms run the same tier config (only the topology
            # differs); write-through flush keeps the publish window
            # tight so a handoff's blocks are durable at publish time.
            kvtier.configure(
                enabled=True,
                host_mb=64,
                store_dir=os.path.join(td, "kvstore"),
                flush_blocks=8,
            )
            kvtier.reset_stats()
            engine = FleetEngine(
                replicas=n_replicas,
                transport="inproc",
                affinity=True,
                prefill_replicas=prefill_replicas,
            )
            transcripts: list[str] = []
            ttfts_r1: list[float] = []
            completed = 0
            try:
                for r in range(1, n_rounds + 1):
                    for d in range(n_debates):
                        comps = engine.chat(make_reqs(d, r), params)
                        if not all(c.ok for c in comps):
                            raise RuntimeError("mock disagg round failed")
                        completed += 1
                        transcripts.extend(c.text for c in comps)
                        if r == 1:
                            ttfts_r1.extend(
                                max(
                                    c.usage.input_tokens
                                    - c.usage.cached_tokens,
                                    0,
                                )
                                / 1024.0
                                for c in comps
                            )
                busys = sorted(
                    (
                        (s.get("role", ""), s["busy_s"])
                        for s in engine.router.replica_stats()
                    ),
                    key=lambda t: t[1],
                    reverse=True,
                )
                fleet_snap = fleet_mod.snapshot()
            finally:
                dup = fleet_mod.stats.duplicated_completions
                engine.shutdown()
            kvtier.configure(enabled=False, store_dir="", flush_blocks=0)
        from adversarial_spec_tpu.obs.metrics import percentile

        p99 = percentile(ttfts_r1, 0.99)
        busiest = busys[0][1] if busys else 0.0
        return {
            "prefill_replicas": prefill_replicas,
            "decode_replicas": n_replicas - prefill_replicas,
            "transcripts": transcripts,
            "ttft_p99_s": round(p99, 6),
            "busy_s_by_replica": [
                {"role": role or "any", "busy_s": round(b, 6)}
                for role, b in busys
            ],
            "accepted_debates_per_s": round(completed / busiest, 3)
            if busiest
            else 0.0,
            "completed": completed,
            "handoff": {
                "attempts": fleet_snap["handoff_attempts"],
                "adopted": fleet_snap["handoff_adopted"],
                "degraded": fleet_snap["handoff_degraded"],
                "abandoned": fleet_snap["handoff_abandoned"],
                "shipped_blocks": fleet_snap["handoff_shipped_blocks"],
                "hit_fraction": fleet_snap["handoff_hit_rate"],
            },
            "duplicated_completions": dup,
            "unexpected_recompiles": obs_mod.snapshot()["retrace"][
                "unexpected_recompiles"
            ],
        }

    symmetric = run_arm(prefill_replicas=0)
    disagg = run_arm(prefill_replicas=2)

    transcripts_ok = symmetric["transcripts"] == disagg["transcripts"]
    for arm in (symmetric, disagg):
        arm.pop("transcripts")
    dup_total = (
        symmetric["duplicated_completions"] + disagg["duplicated_completions"]
    )
    recompiles = disagg["unexpected_recompiles"]
    hit_fraction = disagg["handoff"]["hit_fraction"]
    # Guard the ratio: a fully-adopted handoff can drive the disagg
    # residual prefill to zero tokens.
    ratio = symmetric["ttft_p99_s"] / max(disagg["ttft_p99_s"], 1 / 1024.0)
    within = (
        disagg["ttft_p99_s"] < symmetric["ttft_p99_s"]
        and disagg["handoff"]["attempts"] >= n_debates
        and hit_fraction > 0.0
        and transcripts_ok
        and dup_total == 0
        and recompiles == 0
    )
    return {
        "metric": "disagg_decode_ttft_p99_speedup",
        "value": round(ratio, 3),
        "unit": "round-1 decode-side p99 TTFT (synthetic tokens/1024 "
        "prefill stall before the first decode step), symmetric "
        "4-replica fleet vs 2 prefill + 2 decode at equal replica "
        "count, prefill-heavy shared-document workload",
        "vs_baseline": None,  # no published disaggregation baseline
        "platform": platform,
        "within_budget": within,
        "budget": 1.0,
        "workload": {
            "debates": n_debates,
            "rounds": n_rounds,
            "opponents": n_opp,
            "replicas": n_replicas,
            "shared_doc_chars": len(shared_doc),
            "max_new_tokens": params.max_new_tokens,
        },
        "ttft_p99_s": {
            "disagg": disagg["ttft_p99_s"],
            "symmetric": symmetric["ttft_p99_s"],
        },
        "accepted_debates_per_s": {
            "disagg": disagg["accepted_debates_per_s"],
            "symmetric": symmetric["accepted_debates_per_s"],
        },
        "handoff": disagg["handoff"],
        "handoff_hit_fraction": hit_fraction,
        "transcripts_byte_identical": {"disagg": transcripts_ok},
        "duplicated_completions": dup_total,
        "unexpected_recompiles": recompiles,
        "arms": {"disagg": disagg, "symmetric": symmetric},
        "escape_hatch": "ADVSPEC_FLEET_PREFILL_REPLICAS=0 "
        "(symmetric topology)",
    }


def _run_obs_overhead(platform: str) -> dict:
    """Observability overhead bench: what fraction of the mock mixed
    workload's wall the recorder+metrics emit path costs. Budget < 3%
    (``within_budget`` in BENCH_obs.json); escape hatch ``--no-obs``.

    The pin is COMPOSITIONAL, not an on/off wall difference: shared-CPU
    noise on the bench host swings a ~30 ms drain by 3x at timescales
    longer than any affordable repeat budget, so differencing two noisy
    walls cannot resolve a ~1-2% effect (the A/B walls are still
    recorded, as ``ab_*``, for the honest record). Instead:

    - ``per_request_emit_s``: the wall floor (min over K tight-loop
      blocks, each long enough to average intra-block noise) of ONE
      request's worth of emits through the REAL entry points — the
      exact event mix + hot-handle metric ops the mock's per-request
      accounting performs (which is the schema/metric parity of the
      TPU scheduler's per-step sites).
    - ``wall_s_obs_off``: the drain's wall floor (min-of-N) with obs
      off — the fastest the workload demonstrably runs.
    - ``value`` = per_request_emit_s * requests_per_run / off-floor:
      the emit path's share of the best-case wall. Ratio of two floor
      measurements, stable where the A/B difference is not.
    """
    from adversarial_spec_tpu import obs
    from adversarial_spec_tpu.engine import interleave as interleave_mod
    from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
    from adversarial_spec_tpu.engine.mock import MockEngine
    from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams

    n_rounds, n_opp = 8, 4
    base = "# Spec\n" + ("lorem ipsum dolor sit amet " * 400)  # ~10.8 KB
    params = SamplingParams(max_new_tokens=1024)
    n_repeats = int(os.environ.get("BENCH_OBS_REPEATS", "7"))

    def drain(enabled: bool) -> float:
        # Arrivals armed whenever obs is: the < 3% budget covers the
        # worst case (the per-queued-event monotonic arrival stamp
        # included), not just the byte-deterministic default.
        obs.configure(enabled=enabled, arrivals=enabled)
        obs.reset_stats()
        prefix_mod.reset_stats()
        interleave_mod.reset_stats()
        engine = MockEngine()
        spec = base
        t0 = time.monotonic()
        for rnd in range(1, n_rounds + 1):
            reqs = [
                ChatRequest(
                    model="mock://critic",
                    system="You are a critic.",
                    user=(
                        f"--- DOCUMENT ---\n{spec}\n--- END DOCUMENT ---\n"
                        f"Debate round {rnd}"
                    ),
                )
                for _ in range(n_opp)
            ]
            comps = engine.chat(reqs, params)
            spec = spec + f"\n## Revision note (round {rnd})\n" + comps[0].text[:256]
        return time.monotonic() - t0

    def emit_requests(n: int) -> None:
        """One mock request's emit workload, n times, through the real
        entry points (obs.emit + the cached obs.hot handles — the same
        calls engine/mock.py and the scheduler's hot sites make)."""
        emit = obs.emit
        hot = obs.hot
        for i in range(n):
            # prefix-cache lookup funnel (stats.record_lookup)
            emit(obs.CacheEvent(op="lookup", matched_tokens=288, hit=True))
            hot.hit_ratio.set(0.666667)
            # _account_interleave: step event + 2 histogram observes
            emit(
                obs.StepEvent(
                    kind="fused", n_live=2, admission_slot=1,
                    prefill_tokens=13,
                )
            )
            hot.prefill_chunk.observe(0.012695)
            hot.prefill_wall.observe(0.012695)
            # _emit_lifecycle: 5 transitions + outcome counter + the
            # causal-trace span set (request envelope + stage walls)
            # + the two SLO gates, exactly the mock's per-request
            # accounting since the tracing PR.
            for st in ("queued", "admitted", "prefill", "decode", "finished"):
                emit(
                    obs.RequestEvent(
                        req_id=i, state=st, slot=1, tokens=99,
                        cached_tokens=288,
                        # queue-edge arrival stamp, as engine/mock.py
                        # pays it when ADVSPEC_OBS_ARRIVALS is armed
                        arrival_s=(
                            obs.arrival_now() if st == "queued" else 0.0
                        ),
                    )
                )
            for name, phase, wall in (
                ("request", "begin", 0.0),
                ("queued", "begin", 0.0),
                ("queued", "end", 0.0),
                ("prefill", "begin", 0.0),
                ("prefill", "end", 0.012695),
                ("decode", "begin", 0.0),
                ("decode", "end", 0.062695),
                ("request", "end", 0.07539),
            ):
                emit(
                    obs.SpanEvent(
                        name=name, phase=phase, req_id=i, slot=1,
                        wall_s=wall, span_id="tr-001-01/s01",
                    )
                )
            obs.slo_check("ttft", "tr-001-01/s01", 0.012695)
            obs.slo_check("round", "tr-001-01/s01", 0.07539)
            hot.req_finished.inc()
            # chat fan-in counter (1/len(batch) per request; count the
            # whole inc here — a deliberate overestimate)
            hot.mock_chat_requests.inc()

    # Warm both paths (allocator/caches/metric families), then measure.
    drain(False)
    drain(True)
    events_per_run = obs.recorder.seq
    requests_per_run = n_rounds * n_opp

    # Emit-cost floor: K blocks of N requests; each block is long
    # enough (tens of ms) that intra-block noise averages, and the min
    # across blocks floors inter-block noise.
    obs.configure(enabled=True, arrivals=True)
    n_block = int(os.environ.get("BENCH_OBS_EMIT_BLOCK", "50000"))
    per_request = []
    for _ in range(5):
        obs.reset_stats()
        t0 = time.monotonic()
        emit_requests(n_block)
        per_request.append((time.monotonic() - t0) / n_block)
    per_request_emit_s = min(per_request)
    obs.reset_stats()

    # A/B drain walls (auxiliary record) + the off-floor denominator.
    walls: dict[bool, list] = {False: [], True: []}
    for rep in range(n_repeats):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for enabled in order:
            walls[enabled].append(round(drain(enabled), 4))
    # leave the process default armed, arrivals back to the env default
    obs.configure(enabled=True, arrivals=obs.env_arrivals())
    off_wall, on_wall = min(walls[False]), min(walls[True])
    overhead = (
        per_request_emit_s * requests_per_run / off_wall if off_wall else 0.0
    )
    return {
        "metric": "obs_overhead_fraction",
        "value": round(overhead, 4),
        "unit": "per-request emit-path wall x requests / obs-off floor "
        "wall (CPU, mock)",
        "vs_baseline": None,  # budget pin, not a throughput baseline
        "budget": 0.03,
        "within_budget": overhead < 0.03,
        "platform": "cpu",  # mock workload: device-independent
        "rounds": n_rounds,
        "opponents": n_opp,
        "repeats": n_repeats,
        "events_recorded_per_run": events_per_run,
        "requests_per_run": requests_per_run,
        "per_request_emit_us": round(per_request_emit_s * 1e6, 3),
        "wall_s_obs_off": off_wall,
        "ab_wall_s_obs_on": on_wall,
        "ab_value": round(on_wall / off_wall - 1.0, 4) if off_wall else 0.0,
        "ab_walls_on": walls[True],
        "ab_walls_off": walls[False],
        "escape_hatch": "--no-obs / ADVSPEC_OBS=0",
    }


def main() -> int:
    args = sys.argv[1:]
    def _mode(name: str) -> bool:
        return f"--{name}" in args or (
            "--mode" in args
            and args[args.index("--mode") + 1 :][:1] == [name]
        )

    prefix_mode = _mode("prefix")
    obs_mode = _mode("obs-overhead")
    spec_mode = _mode("spec")
    tier_mode = _mode("tier")
    cancel_mode = _mode("cancel")
    recover_mode = _mode("recover")
    fleet_mode = _mode("fleet")
    serve_mode = _mode("serve")
    residency_mode = _mode("residency")
    elastic_mode = _mode("elastic")
    disagg_mode = _mode("disagg")
    kernels_mode = _mode("kernels")
    capacity_mode = _mode("capacity")
    if "--no-speculative" in args:
        # Escape hatch: batcher-driven modes decode token-at-a-time.
        os.environ["ADVSPEC_SPECULATIVE"] = "0"
        from adversarial_spec_tpu.engine import spec as _sp

        _sp.configure(enabled=False)
    if "--long-context" in args:
        runner = _run_long_context
    elif "--round-loop" in args:
        runner = _run_round_loop
    elif prefix_mode:
        runner = _run_prefix
    elif obs_mode:
        runner = _run_obs_overhead
    elif spec_mode:
        runner = _run_spec
    elif tier_mode:
        runner = _run_tier
    elif cancel_mode:
        runner = _run_cancel
    elif recover_mode:
        runner = _run_recover
    elif fleet_mode:
        runner = _run_fleet
    elif serve_mode:
        runner = _run_serve
    elif residency_mode:
        runner = _run_residency
    elif elastic_mode:
        runner = _run_elastic
    elif disagg_mode:
        runner = _run_disagg
    elif kernels_mode:
        runner = _run_kernels
    elif capacity_mode:
        runner = _run_capacity
    else:
        runner = _run_bench

    if (
        obs_mode
        or recover_mode
        or fleet_mode
        or serve_mode
        or elastic_mode
        or disagg_mode
        or capacity_mode
    ):
        # Mock-only workloads — no jax, no device: the obs budget is a
        # CPU host-overhead pin by definition, and the recovery/fleet/
        # serve drills are mock rounds (in-process replicas,
        # SIGKILL-able subprocess workers, and the serve daemon's
        # socket front).
        payload = runner("cpu")
        payload.update(device_kind="none (mock engine)", device_count=0)
    else:
        from adversarial_spec_tpu.utils.jaxenv import configure_jax

        configure_jax()
        import jax

        devices = jax.devices()
        payload = runner(devices[0].platform)
        payload.update(
            platform=devices[0].platform,
            device_kind=devices[0].device_kind,
            device_count=len(devices),
        )
    if (
        prefix_mode
        or obs_mode
        or spec_mode
        or tier_mode
        or cancel_mode
        or recover_mode
        or fleet_mode
        or serve_mode
        or residency_mode
        or elastic_mode
        or disagg_mode
        or kernels_mode
        or capacity_mode
    ):
        # Persist the perf trajectory point alongside the BENCH_r*
        # series the driver records.
        name = (
            "BENCH_prefix.json"
            if prefix_mode
            else "BENCH_obs.json"
            if obs_mode
            else "BENCH_spec.json"
            if spec_mode
            else "BENCH_tier.json"
            if tier_mode
            else "BENCH_cancel.json"
            if cancel_mode
            else "BENCH_recover.json"
            if recover_mode
            else "BENCH_fleet.json"
            if fleet_mode
            else "BENCH_residency.json"
            if residency_mode
            else "BENCH_elastic.json"
            if elastic_mode
            else "BENCH_disagg.json"
            if disagg_mode
            else "BENCH_kernels.json"
            if kernels_mode
            else "BENCH_capacity.json"
            if capacity_mode
            else "BENCH_serve.json"
        )
        out = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name
        )
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
