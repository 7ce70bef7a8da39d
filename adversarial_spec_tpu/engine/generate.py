"""Batched autoregressive generation: jitted prefill + chunked decode.

Execution model (TPU-first, SURVEY §3.1 "TPU mapping" — the reference's
network boundary becomes a device-program dispatch; its per-model retry hot
loop becomes this decode loop):

- **Left-padded static batches.** N opponents' prompts are left-padded to a
  shared bucketed length, so every row's KV lands at the same slot index
  (one ``dynamic_update_slice`` per layer, no per-row scatter) and the last
  prompt logit is always at slot ``S-1``. Bucketing (powers of two) bounds
  the number of compiled prefill programs.
- **Prefill** is one jitted forward over the whole padded prompt (MXU-sized
  matmuls), returning the first sampled token.
- **Decode** runs as a ``lax.while_loop`` of single-token steps *inside*
  jit, emitted in host-level chunks of ``DECODE_CHUNK`` steps: the loop
  early-exits when every row hit EOS, and the host checks the wall-clock
  budget between chunks (the enforcement point for SamplingParams.timeout_s
  — an XLA program cannot be interrupted mid-flight).

The same code path serves 1 opponent on 1 chip and N opponents TP-sharded
over a mesh: sharding enters via the params/cache shardings baked into the
jitted functions (parallel/sharding.py), not via this file's logic.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from adversarial_spec_tpu.engine.sampling import sample_tokens
from adversarial_spec_tpu.models.config import ModelConfig
from adversarial_spec_tpu.models.transformer import (
    Cache,
    Params,
    forward,
    init_cache,
)

DECODE_CHUNK = int(os.environ.get("ADVSPEC_DECODE_CHUNK", "128"))
MIN_BUCKET = 128

# Context-length floor below which decode auto-selects XLA attention over
# the fused Pallas kernel. Round 2's (B, Hkv, T/block) grid lost to XLA at
# short T (v5e: jnp 491 vs kernel 384 tok/s at T=1280 — 160 sequential
# tiny programs), hiding behind a 4096 floor; the round-3 head-folded grid
# (ops/pallas_decode.py: (B, T/block), Hkv-fold fewer programs with
# Hkv-fold larger DMAs) targets exactly that regime, so the default floor
# is now 0 (kernel always) until an on-chip crossover measurement says
# otherwise. Explicit use_pallas_decode=True always wins over this
# heuristic; ADVSPEC_PALLAS_MIN_T restores a floor without a code change.
PALLAS_DECODE_MIN_T = int(os.environ.get("ADVSPEC_PALLAS_MIN_T", "0"))


def _host_fetch(x) -> np.ndarray:
    """Fetch a possibly-sharded device array to every host.

    Single-process: plain np.asarray. Multi-host: dp-sharded arrays span
    non-addressable devices, so gather them to a replicated copy first
    (an ICI/DCN all_gather — once per generate() call, on the two small
    output arrays only, never in the decode loop)."""
    if jax.process_count() > 1 and not x.is_fully_replicated:
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x, tiled=True)
    return np.asarray(x)


def bucket_length(n: int, minimum: int = MIN_BUCKET) -> int:
    """Next power-of-two bucket ≥ n (≥ minimum) — bounds recompiles."""
    b = minimum
    while b < n:
        b *= 2
    return b


def pad_batch(
    prompt_ids: list[list[int]], pad_id: int, bucket: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Left-pad prompts to a shared bucketed length.

    Returns (tokens [B, S] int32, pad_lens [B] int32).
    """
    max_len = max(len(p) for p in prompt_ids)
    S = bucket if bucket is not None else bucket_length(max_len)
    if S < max_len:
        raise ValueError(f"bucket {S} smaller than longest prompt {max_len}")
    B = len(prompt_ids)
    tokens = np.full((B, S), pad_id, dtype=np.int32)
    pad_lens = np.zeros((B,), dtype=np.int32)
    for i, p in enumerate(prompt_ids):
        tokens[i, S - len(p) :] = np.asarray(p, dtype=np.int32)
        pad_lens[i] = S - len(p)
    return tokens, pad_lens


PREFILL_CHUNK = 1024

# One-time flag for the speculative×paged seam warning below: paged
# generate() has no dense speculative loop (paged speculation is the
# ContinuousBatcher's per-slot draft/verify step), and the combination
# used to be silently ignored.
_PAGED_SPEC_WARNED = False


def _sample_step(
    logits, key, finished, out_buf, step, eos_ids, *, greedy, top_k,
    temperature, top_p, use_top_p=True,
):
    """Shared per-decode-step tail for BOTH cache layouts: sample, record
    EOS (the EOS token itself is kept; finished rows emit 0 thereafter),
    write the output slot. Any change here applies to dense and paged
    decode alike — and must be mirrored in the vectorized emission logic
    of engine/speculative.py (same EOS contract, γ+1 tokens at a time)."""
    key, sub = jax.random.split(key)
    nxt = sample_tokens(
        logits,
        sub,
        greedy=greedy,
        top_k=top_k,
        temperature=temperature,
        top_p=top_p,
        use_top_p=use_top_p,
    )
    is_eos = (nxt[:, None] == eos_ids[None, :]).any(axis=-1)
    nxt = jnp.where(finished, 0, nxt)
    out_buf = jax.lax.dynamic_update_slice(out_buf, nxt[:, None], (0, step))
    return key, nxt, finished | is_eos, out_buf


def _chunk_bound(start_step, chunk, stop_at, max_new):
    return jnp.minimum(jnp.minimum(start_step + chunk, stop_at), max_new)


def _prefill_chunk_impl(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, Sc] one left-padded prompt chunk
    pad_lens: jnp.ndarray,  # [B]
    cache: Cache,
    cache_index: jnp.ndarray,  # scalar: slot of this chunk's first token
    *,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
) -> tuple[Cache, jnp.ndarray]:
    """Run ONE prompt chunk through the model.

    Long prompts (16k-context PRDs, BASELINE config 5) prefill as a
    sequence of fixed-size chunks: activation memory is O(chunk·dim)
    instead of O(S·dim), and every chunk reuses one compiled program.
    Returns (cache, last-position logits [B, vocab]).

    ``prefill_chunk`` is this body jitted (with cache donation); it is
    also inlined — alongside the decode-chunk body — into the
    scheduler's fused prefill+decode program
    (engine/scheduler.py:fused_prefill_decode_chunk), so the admission
    prompt math exists exactly once whether it runs standalone or rides
    a fused step.
    """
    B, Sc = tokens.shape
    T = cache["k"].shape[3]  # [L, B, Hkv, T, D]
    positions = jnp.maximum(
        cache_index + jnp.arange(Sc, dtype=jnp.int32)[None, :]
        - pad_lens[:, None],
        0,
    )
    kv_valid = jnp.arange(T)[None, :] >= pad_lens[:, None]
    logits, cache = forward(
        params,
        cfg,
        tokens,
        positions,
        cache,
        cache_index,
        kv_valid,
        use_pallas_matmul=use_pallas_matmul,
        pallas_interpret=pallas_interpret,
        lm_head_last_only=True,
    )
    return cache, logits[:, -1]


# The public jitted entry point — the same body, not a hand-forwarded
# wrapper (see scheduler_decode_chunk for the rationale).
prefill_chunk = partial(
    jax.jit,
    static_argnames=("cfg", "use_pallas_matmul", "pallas_interpret"),
    donate_argnames=("cache",),
)(_prefill_chunk_impl)


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "prompt_len",
        "chunk",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas_decode",
        "use_pallas_matmul",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("cache", "out_buf"),
)
def decode_chunk_steps(
    params: Params,
    cfg: ModelConfig,
    cache: Cache,
    cur_tokens: jnp.ndarray,  # [B] last sampled token per row
    pad_lens: jnp.ndarray,  # [B]
    finished: jnp.ndarray,  # [B] bool
    out_buf: jnp.ndarray,  # [B, max_new]
    start_step: jnp.ndarray,  # scalar: decode step at chunk entry
    stop_at: jnp.ndarray,  # scalar: decode no further than this step
    eos_ids: jnp.ndarray,  # [E]
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    prompt_len: int,
    chunk: int,
    greedy: bool,
    top_k: int,
    use_top_p: bool = True,
    use_pallas_decode: bool = False,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
) -> tuple[Cache, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Up to ``chunk`` single-token decode steps inside one XLA program.

    The while_loop early-exits once every row is finished, so converged
    batches don't burn MXU cycles padding out the chunk.
    """
    B = cur_tokens.shape[0]
    T = cache["k"].shape[3]  # [L, B, Hkv, T, D]
    max_new = out_buf.shape[1]
    kv_base = jnp.arange(T)[None, :] >= pad_lens[:, None]

    def cond(state):
        step, _, _, finished, _, _ = state
        return (
            step < _chunk_bound(start_step, chunk, stop_at, max_new)
        ) & ~finished.all()

    def body(state):
        step, cur, cache, finished, out_buf, key = state
        # ``cur`` is the token at out index step-1, i.e. sequence slot
        # prompt_len + step - 1 (slot prompt_len holds the first sampled
        # token; prompt KV occupies [0, prompt_len)).
        cache_index = prompt_len + step - 1
        positions = (cache_index - pad_lens)[:, None]
        kv_valid = kv_base & (jnp.arange(T)[None, :] <= cache_index)
        logits, cache = forward(
            params,
            cfg,
            cur[:, None],
            positions,
            cache,
            cache_index,
            kv_valid,
            use_pallas_decode=use_pallas_decode,
            use_pallas_matmul=use_pallas_matmul,
            pallas_interpret=pallas_interpret,
            mesh=mesh,
        )
        key, nxt, finished, out_buf = _sample_step(
            logits[:, 0],
            key,
            finished,
            out_buf,
            step,
            eos_ids,
            greedy=greedy,
            top_k=top_k,
            temperature=temperature,
            top_p=top_p,
            use_top_p=use_top_p,
        )
        return step + 1, nxt, cache, finished, out_buf, key

    step, cur, cache, finished, out_buf, key = jax.lax.while_loop(
        cond,
        body,
        (start_step, cur_tokens, cache, finished, out_buf, key),
    )
    return cache, cur, finished, out_buf, step


@dataclass
class GenerateResult:
    tokens: np.ndarray  # [B, <=max_new] generated ids (0 past each row's end)
    n_generated: np.ndarray  # [B] tokens produced per row (incl. EOS)
    prefill_time_s: float
    decode_time_s: float
    decode_tokens: int  # total across batch (north-star numerator)
    timed_out: bool = False


def generate(
    params: Params,
    cfg: ModelConfig,
    prompt_ids: list[list[int]],
    *,
    max_new_tokens: int,
    eos_ids: list[int],
    pad_id: int = 0,
    greedy: bool = False,
    temperature: float = 0.7,
    top_k: int = 0,
    top_p: float = 1.0,
    seed: int | None = None,
    timeout_s: float = 0.0,
    mesh=None,
    use_pallas_decode: bool | None = None,
    use_pallas_matmul: bool | None = None,
    share_prefix: bool = True,
    paged: bool = False,
    page_size: int = 128,
    speculative: bool | None = None,
    kv_dtype: str = "",
) -> GenerateResult:
    """End-to-end batched generation (host orchestration).

    With a ``mesh``, batch rows are sharded over ``dp`` (rows padded up to
    a dp multiple by replicating the last prompt; extra rows dropped from
    the result) and token inputs are placed with NamedShardings — GSPMD
    propagates dp through activations and the KV cache, while params carry
    their tp shardings from the loader (parallel/sharding.py). The fused
    decode kernel runs under shard_map on such meshes (dp over rows, tp
    over KV heads) whenever tp divides n_kv_heads.

    ``share_prefix``: a debate round sends IDENTICAL prompts to every
    opponent sharing a model (round-level focus/persona apply to all), so
    when all rows are equal the prompt prefills ONCE (B=1) and the KV
    cache is tiled to B rows before decode — prefill FLOPs drop by B×,
    SURVEY §7 hard part (e)'s prefix-caching lever. Rows then diverge via
    per-row sampling. Applies off-mesh only (dp sharding wants real rows).

    ``paged``: decode against the paged KV pool (engine/kvcache.py +
    ops/pallas_paged.py) instead of the dense per-row cache — prompt KV is
    scattered into pages after prefill and every decode step writes through
    the page table. Scales over dp-only meshes (per-device pools,
    independent per-device chunk loops), tp-only meshes (head-sharded
    global pool, kernel under shard_map), and mixed dp×tp meshes (one
    GSPMD chunk loop over a per-dp-slice pool layout, kernel under a
    dp×tp shard_map); sp meshes warn and use the dense path.

    ``speculative``: prompt-lookup speculative decoding
    (engine/speculative.py) — greedy, single-row, dense-cache runs draft
    tokens from n-gram matches in the prompt and verify several per
    forward; bit-identical outputs, multiple tokens per step on
    revision-style outputs. None = auto (on when eligible).

    ``kv_dtype="int8"``: store the KV cache int8 with per-token-head
    scales — half the cache HBM and half the bytes read per decoded
    token. Composes with the fused decode kernel (dequant inside the
    kernel tiles), with sharded meshes, with ``paged`` (int8 pages +
    scale pages, in-kernel dequant), and with sp prefill (quantized at
    the reshard-to-decode boundary).
    """
    if cfg.ssm is not None:
        from adversarial_spec_tpu.models.config import refuse_beside_state_space

        refuse_beside_state_space(
            cfg, "generate() (dense KV, or a mesh of more than one device)"
        )
    if cfg.gated is not None and (
        kv_dtype or paged or (mesh is not None and mesh.size > 1)
    ):
        from adversarial_spec_tpu.models.config import refuse_unwired

        refuse_unwired(
            cfg,
            "generate() over a mesh, over pages of its own or with int8 KV",
            "the ContinuousBatcher serves it on one device with paged KV "
            "in the model dtype, generate() on one device with dense KV",
        )
    # An explicit use_pallas_decode=True records caller intent (it
    # selects a louder fallback when the mesh can't support the kernel).
    explicit_pallas = use_pallas_decode is True
    # The PAGED kernel switch ignores the dense-path context-length
    # heuristic below: the paged alternative is the gather reference path
    # (densifies the whole pool every layer), strictly worse than the
    # kernel at any context length. Only an explicit caller False (or a
    # non-TPU backend) disables it.
    requested_pallas = use_pallas_decode

    n_real = len(prompt_ids)
    if mesh is not None:
        from adversarial_spec_tpu.parallel.mesh import DP

        dp = mesh.shape[DP]
        short = (-len(prompt_ids)) % dp
        prompt_ids = prompt_ids + [prompt_ids[-1]] * short

    tokens_np, pad_lens_np = pad_batch(prompt_ids, pad_id)
    B, S = tokens_np.shape
    max_new = bucket_length(max_new_tokens, minimum=DECODE_CHUNK)
    total_len = S + max_new

    if use_pallas_decode is None:
        # Auto: fused kernel on a real TPU, but only once the cache is
        # long enough for streaming to beat XLA's attention (see
        # PALLAS_DECODE_MIN_T). Multi-device meshes run it under
        # shard_map (batch over dp, KV heads over tp); the support gate
        # below demotes unsupported tp degrees for auto and explicit
        # callers alike.
        use_pallas_decode = (
            jax.default_backend() == "tpu"
            and total_len >= PALLAS_DECODE_MIN_T
        )
    pallas_interpret = jax.default_backend() == "cpu"
    # Fused dequant-matmul (ops/pallas_quant.py): auto = real TPU. Either
    # way it only engages when the params actually carry quantized
    # leaves, and only single-device (models/transformer.py gates on the
    # mesh) — CPU callers opt in explicitly to run the kernels under
    # interpret mode (the parity harness).
    from adversarial_spec_tpu.ops.quant import has_quantized_weights

    if use_pallas_matmul is None:
        use_pallas_matmul = jax.default_backend() == "tpu"
    use_pallas_matmul = bool(use_pallas_matmul) and has_quantized_weights(
        params
    )
    if use_pallas_decode and mesh is not None and mesh.size > 1:
        from adversarial_spec_tpu.ops.pallas_decode import (
            tp_decode_supported,
        )

        if not tp_decode_supported(cfg.n_kv_heads, mesh):
            if explicit_pallas:
                import sys as _sys

                print(
                    f"warning: fused decode needs tp | n_kv_heads "
                    f"({cfg.n_kv_heads}); using the jnp attention path",
                    file=_sys.stderr,
                )
            use_pallas_decode = False

    tokens = jnp.asarray(tokens_np)
    pad_lens = jnp.asarray(pad_lens_np)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from adversarial_spec_tpu.parallel.mesh import DP

        rows = NamedSharding(mesh, P(DP))
        tokens = jax.device_put(tokens, NamedSharding(mesh, P(DP, None)))
        pad_lens = jax.device_put(pad_lens, rows)
    if seed is None:
        # Fresh entropy per call: unseeded debate rounds must actually vary
        # (seed=0 aliasing would make every round's "samples" identical).
        seed = int.from_bytes(os.urandom(4), "little")
    # Sampling draws full-vocab uniforms every step (gumbel-max
    # categorical); threefry is pure ALU and shows up at 128k vocab. The
    # TPU's hardware RNG ("rbg") generates the same bits-shape orders of
    # magnitude cheaper. Tradeoffs, deliberate: (1) streams differ
    # between impls, so seeds are reproducible per platform, not across
    # platforms (never promised); (2) JAX only guarantees independent
    # streams after split/fold_in for threefry — this loop splits per
    # chunk and the dp wrappers fold_in per device, so rbg streams carry
    # a weaker (empirical, not proven) independence guarantee. For
    # sampling diversity in a debate round that is acceptable; callers
    # needing threefry's guarantees set ADVSPEC_PRNG=threefry (the full
    # impl string "threefry2x32" is accepted too).
    impl = (
        "rbg"
        if jax.default_backend() == "tpu"
        and not os.environ.get("ADVSPEC_PRNG", "rbg").startswith("threefry")
        else "threefry2x32"
    )
    key = jax.random.key(seed, impl=impl)
    key, prefill_key = jax.random.split(key)
    temp = jnp.float32(temperature)
    tp = jnp.float32(top_p)
    use_top_p = float(top_p) < 1.0  # static: skip the no-op vocab sort
    eos = jnp.asarray(sorted(set(eos_ids)) or [-1], dtype=jnp.int32)

    deadline = time.monotonic() + timeout_s if timeout_s > 0 else None
    # Paged decode scales over dp (per-device page pools, zero cross-
    # device page traffic — engine/scheduler.py:
    # sharded_scheduler_decode_chunk), over tp-only meshes (global
    # pool, head axis tp-sharded, kernel under shard_map —
    # ops/pallas_paged.py:paged_decode_attention_tp), over mixed
    # dp×tp meshes (per-dp-slice pool layout, GSPMD chunk loop, kernel
    # under the dp×tp wrapper), and over sp meshes (sp is a PREFILL
    # axis — during decode it idles/replicates, exactly as the dense
    # decode path behaves after reshard_cache_for_decode, so the
    # global-pool and per-dp-slice layouts carry over unchanged with
    # the sp axis simply unmentioned in the shard_map specs). Resolve
    # now so the prefill cache can be sized to the prompt only.
    paged_dp = paged_tp = 1
    paged_mixed = False
    paged_sp = False  # sp axis present: replicated during decode
    paged_gspmd = False  # multi-device paged, not dp-only: the chunk
    # loop runs under GSPMD and the kernel needs the mesh passed down
    if paged and mesh is not None and mesh.size > 1:
        from adversarial_spec_tpu.parallel.mesh import (
            DP as _DP,
            SP as _SP,
            TP as _TP,
        )

        if mesh.size == mesh.shape[_DP]:
            paged_dp = mesh.shape[_DP]
        elif cfg.n_kv_heads % mesh.shape[_TP] != 0:
            import sys

            print(
                f"warning: paged KV decode requires tp | n_kv_heads "
                f"({mesh.shape[_TP]} ∤ {cfg.n_kv_heads}); falling back "
                f"to the dense cache on this mesh ({dict(mesh.shape)})",
                file=sys.stderr,
            )
            paged = False
        elif mesh.shape[_DP] == 1:
            # tp-only, sp-only, or sp×tp: ONE global pool, heads
            # tp-sharded (trivially so when tp == 1), sp replicated.
            paged_tp = mesh.shape[_TP]
            paged_sp = mesh.shape[_SP] > 1
            paged_gspmd = True
        else:
            # Mixed dp×tp (a v5e-8 at dp=4×tp=2) — and dp×sp(×tp):
            # ONE GSPMD-partitioned chunk loop over a per-dp-slice
            # pool layout — rows + page slabs shard over dp, heads
            # over tp; the kernel runs under the dp×tp shard_map
            # wrapper with global→local id shift
            # (ops/pallas_paged.py:paged_decode_attention_dp_tp).
            paged_tp = mesh.shape[_TP]
            paged_mixed = True
            paged_sp = mesh.shape[_SP] > 1
            paged_gspmd = True

    # Shared-prefix: identical rows prefill once and tile. Qualifies off-
    # mesh and on single-device meshes (the TpuEngine always passes a
    # mesh, so the single-chip case — the common debate setup — must
    # qualify); dp>1 meshes want real rows for the sharded prefill.
    shared = (
        share_prefix
        and (mesh is None or mesh.size == 1)
        and B > 1
        and all(p == prompt_ids[0] for p in prompt_ids[1:])
    )
    # PARTIAL sharing: equal-length rows that diverge only in a suffix
    # (per-opponent personas over one spec) prefill their common prefix
    # ONCE at B=1, tile the cache, and run only the divergent tail at
    # full batch. Equal lengths ⇒ equal pads ⇒ the shared slots hold
    # identical KV for every row. Granularity is the prefill chunk.
    shared_until = 0
    if (
        share_prefix
        and not shared
        and (mesh is None or mesh.size == 1)
        and B > 1
        and all(len(p) == len(prompt_ids[0]) for p in prompt_ids[1:])
    ):
        p0 = prompt_ids[0]
        common = len(p0)
        for p in prompt_ids[1:]:
            i = 0
            while i < common and p[i] == p0[i]:
                i += 1
            common = i
        chunk0 = min(S, PREFILL_CHUNK)
        # Divergence slot in padded coordinates, floored to chunk grid.
        shared_until = ((S - len(p0) + common) // chunk0) * chunk0
    prefill_tokens = tokens[:1] if shared else tokens
    prefill_pads = pad_lens[:1] if shared else pad_lens

    t0 = time.monotonic()
    cache_device = None
    if mesh is not None and mesh.size > 1:
        from adversarial_spec_tpu.parallel.sharding import cache_sharding

        # Born sharded: batch over dp, heads over tp — never replicated
        # through one chip's HBM.
        cache_device = cache_sharding(mesh)

    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    use_sp_prefill = sp > 1 and S % sp == 0
    if use_sp_prefill:
        # Long-context path: sequence-parallel prefill (ring attention
        # over the sp axis — parallel/sp.py), then reshard the
        # sequence-sharded cache into the decode layout.
        from jax.sharding import NamedSharding, PartitionSpec as P
        from adversarial_spec_tpu.parallel.mesh import SP as SP_AXIS
        from adversarial_spec_tpu.parallel.sp import (
            reshard_cache_for_decode,
            sp_prefill,
        )

        # Tokens enter sequence-sharded so shard_map needs no reshard.
        sp_tokens = jax.device_put(
            prefill_tokens, NamedSharding(mesh, P(None, SP_AXIS))
        )
        last_logits, cache = sp_prefill(
            params, cfg, sp_tokens, prefill_pads, mesh
        )
        # int8 KV quantizes at this reshard boundary — the ring itself
        # ran on full-precision K/V. Paged runs migrate prompt KV into
        # pages right below, so their resharded dense cache only needs
        # the prompt slots, not the decode region.
        cache = reshard_cache_for_decode(
            cache, mesh, S if paged else total_len, kv_dtype=kv_dtype
        )
    else:
        # Paged runs drop the dense cache after migrating prompt KV, so
        # it only needs the prompt slots — not the decode region.
        cache = init_cache(
            cfg,
            1 if shared_until else prefill_tokens.shape[0],
            S if paged else total_len,
            dtype=params["embed"].dtype,
            device=cache_device,
            kv_dtype=kv_dtype,
        )
        chunk_len = min(S, PREFILL_CHUNK)
        last_logits = None
        for ci in range(0, S, chunk_len):
            if shared_until and ci == shared_until:
                # Common prefix done: fan the 1-row cache out to B rows
                # and finish the divergent tails at full batch.
                cache = jax.tree.map(
                    lambda x: jnp.repeat(x, B, axis=1), cache
                )
            one_row = bool(shared_until) and ci < shared_until
            cache, last_logits = prefill_chunk(
                params,
                cfg,
                (prefill_tokens[:1] if one_row else prefill_tokens)[
                    :, ci : ci + chunk_len
                ],
                prefill_pads[:1] if one_row else prefill_pads,
                cache,
                jnp.int32(ci),
                # as the batcher's admissions: expert stacks are read
                # by the grouped kernel, dense weights by XLA's
                # dequant-matmul (engine/scheduler.py)
                use_pallas_matmul=use_pallas_matmul
                and cfg.ffn_kind == "routed",
                pallas_interpret=pallas_interpret,
            )
        if shared_until:
            from adversarial_spec_tpu.engine import prefix_cache as _pc

            _pc.stats.record_prefill(0, (B - 1) * shared_until)
    # Paged + identical prompts: rows can SHARE physical prompt pages
    # (never written after migration — decode slots start at S, which is
    # page-aligned when page_size divides the pow2 bucket), so skip the
    # B-way cache tile entirely; only logits tile.
    share_prompt_pages = shared and paged and S % page_size == 0
    if shared:
        if not share_prompt_pages:
            cache = jax.tree.map(lambda x: jnp.repeat(x, B, axis=1), cache)
        last_logits = jnp.repeat(last_logits, B, axis=0)
        from adversarial_spec_tpu.engine import prefix_cache as _pc

        _pc.stats.record_prefill(0, (B - 1) * S)
    first = sample_tokens(
        last_logits,
        prefill_key,
        greedy=greedy,
        top_k=top_k,
        temperature=temp,
        top_p=tp,
        use_top_p=use_top_p,
    )
    first.block_until_ready()
    prefill_time = time.monotonic() - t0

    out_buf = jnp.zeros((B, max_new), jnp.int32)
    is_eos_first = (first[:, None] == eos[None, :]).any(axis=-1)
    out_buf = out_buf.at[:, 0].set(first)
    finished = is_eos_first
    cur = first
    step = jnp.int32(1)
    timed_out = False

    page_table = None
    if paged:
        from adversarial_spec_tpu.engine.kvcache import (
            PageAllocator,
            PagedCacheLayout,
            init_page_pool,
            write_tokens,
        )

        # Physical page 0 is the TRASH page (scheduler_decode_chunk
        # redirects inactive rows' writes there), so allocator ids shift
        # +1 — the scheduler's convention, which this path shares. Without
        # the reservation, an early-EOS row's redirected writes would
        # corrupt whichever row's KV occupied physical page 0.
        n_pages_per_row = -(-total_len // page_size)
        if share_prompt_pages:
            # One physical copy of the prompt pages, shared by all rows;
            # only the decode region is per-row.
            prompt_pages = S // page_size
            decode_pages = n_pages_per_row - prompt_pages
            allocator = PageAllocator(
                prompt_pages + B * decode_pages, page_size
            )
            allocator.new_sequence("prompt")
            allocator.extend("prompt", S)
            shared_table = np.asarray(allocator.table("prompt"), np.int32)
            for b in range(B):
                allocator.new_sequence(b)
                allocator.extend(b, total_len - S)
            table_np = (
                np.concatenate(
                    [
                        np.broadcast_to(shared_table, (B, prompt_pages)),
                        allocator.table_array(list(range(B)), decode_pages),
                    ],
                    axis=1,
                )
                + 1
            )
            n_phys_pages = prompt_pages + B * decode_pages
        elif paged_dp > 1 or paged_mixed:
            # Per-dp-slice pool layout, shared by the dp-only and mixed
            # dp×tp modes: slice d owns local pages [0, Lp) with local
            # page 0 reserved as that slice's trash page (shard sizes
            # stay equal); global id = local + d·Lp. The dp-only chunk
            # loop is shard_mapped — each device indexes its LOCAL pool
            # slice, so its table carries local ids and only the
            # (global-pool) migration uses global ids. The mixed chunk
            # loop runs under GSPMD — global view — so its table IS the
            # global one, and the kernel wrapper shifts back to local
            # (ops/pallas_paged.py:paged_decode_attention_dp_tp). The
            # TRASH_PAGE=0 write redirect lands on slice 0's trash page,
            # which no table ever references.
            slice_dp = paged_dp if paged_dp > 1 else mesh.shape[_DP]
            local_rows = B // slice_dp
            local_pool_pages = 1 + local_rows * n_pages_per_row
            lr = np.arange(B) % local_rows
            dev = np.arange(B) // local_rows
            local_table = (
                1
                + lr[:, None] * n_pages_per_row
                + np.arange(n_pages_per_row)[None, :]
            ).astype(np.int32)
            global_table = local_table + (dev * local_pool_pages)[:, None]
            table_np = global_table if paged_mixed else local_table
            migrate_table_np = global_table
            n_pool_pages = slice_dp * local_pool_pages
        else:
            allocator = PageAllocator(B * n_pages_per_row, page_size)
            for b in range(B):
                allocator.new_sequence(b)
                allocator.extend(b, total_len)
            table_np = (
                allocator.table_array(list(range(B)), n_pages_per_row) + 1
            )
            n_phys_pages = B * n_pages_per_row
        if paged_dp == 1 and not paged_mixed:
            migrate_table_np = table_np
            n_pool_pages = n_phys_pages + 1  # +1: trash page 0
        page_table = jnp.asarray(table_np)
        layout = PagedCacheLayout(
            n_pages=n_pool_pages,
            page_size=page_size,
            n_layers=cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
        )
        pool = init_page_pool(
            layout,
            dtype=params["embed"].dtype if kv_dtype else cache["k"].dtype,
            kv_dtype=kv_dtype,
        )
        if paged_dp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from adversarial_spec_tpu.parallel.mesh import DP as _DP

            pool = jax.tree.map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, P(None, _DP, None, None, None))
                ),
                pool,
            )
        elif paged_mixed:
            # Page slabs over dp (per-slice layout above), heads over tp.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from adversarial_spec_tpu.parallel.mesh import (
                DP as _DP,
                TP as _TP,
            )

            pool = jax.tree.map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, P(None, _DP, _TP, None, None))
                ),
                pool,
            )
        elif paged_tp > 1 or paged_sp:
            # Global pool, head axis tp-sharded — each device holds every
            # page's slice of its own KV heads (same placement the dense
            # tp cache uses). On sp(-only) meshes tp may be 1: the spec
            # then replicates the pool, matching the idle-sp decode
            # semantics of the dense path.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from adversarial_spec_tpu.parallel.mesh import TP as _TP

            pool = jax.tree.map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, P(None, None, _TP, None, None))
                ),
                pool,
            )
        # Migrate prompt KV (slots [0, S)) from the dense prefill cache
        # into pages (vectorized table lookup); pad-slot garbage lands too
        # but stays masked by the per-row bounds start. With shared prompt
        # pages the (untiled, single-row) cache scatters ONCE.
        B_mig = cache["k"].shape[1]
        slots = np.tile(np.arange(S, dtype=np.int32)[None, :], (B_mig, 1))
        page_ids = migrate_table_np[
            np.arange(B_mig)[:, None], slots // page_size
        ]
        offsets = slots % page_size
        pool = write_tokens(
            pool,
            cache["k"][..., :S, :],
            cache["v"][..., :S, :],
            page_ids,
            offsets,
            ks_new=cache["ks"][..., :S, :] if "ks" in cache else None,
            vs_new=cache["vs"][..., :S, :] if "ks" in cache else None,
        )
        cache = None  # dense cache no longer needed
        # NOT the dense-path switch: the paged fallback (gather path)
        # densifies the whole pool every layer, so the kernel wins at any
        # context length — only an explicit caller False or a non-TPU
        # backend turns it off (interpret mode keeps it testable on CPU).
        use_paged_kernel = (
            requested_pallas
            if requested_pallas is not None
            else jax.default_backend() == "tpu"
        )
        # Per-row decode state for the shared paged loop
        # (engine/scheduler.py::scheduler_decode_chunk — one loop serves
        # both this round-synchronous path and the continuous batcher).
        paged_cur_len = jnp.full((B,), S + 1, jnp.int32)
        paged_n_emitted = jnp.ones((B,), jnp.int32)
        paged_max_new = jnp.full((B,), max_new_tokens, jnp.int32)
        paged_active = ~finished

    # Speculative eligibility: dense cache and enough output budget for
    # at least one γ+1 span — every mesh shape (incl. sp and multi-host)
    # is served by one of three execution modes (any batch size, any
    # sampling mode — per-row accept lengths + rejection sampling; the
    # bench shape of 4 opponents at temperature 0.7 is the target
    # workload):
    #   - single device: plain jitted accept loop;
    #   - dp-only mesh: shard_map wrappers (rows shard over dp, each
    #     device runs its own INDEPENDENT accept loop — per-row desync
    #     never crosses devices);
    #   - any other mesh (tp, dp×tp, sp×…): one GSPMD-partitioned
    #     program — the layer matmuls shard via the params' Megatron
    #     shardings, the compiler inserts the psums, and idle axes
    #     (sp during decode) replicate (mesh=… below).
    # Composes with the fused kernels: the tail loop runs the
    # single-query kernel (under its shard_map wrapper on meshes); the
    # verification span runs the multi-query kernel single-device and
    # the jnp attention path (GSPMD head-sharded) under tp.
    from adversarial_spec_tpu.engine import spec as spec_cfg_mod

    _sp_cfg = spec_cfg_mod.config()
    gamma = _sp_cfg.gamma
    spec_explicit = speculative is not None
    if speculative is None:
        # Unspecified → the process switchboard (engine/spec.py): env
        # ADVSPEC_SPECULATIVE seeds it, CLI --no-speculative/--gamma and
        # tests retune it via configure() — the SAME knob the batcher
        # consults, so the documented escape hatch reaches the dense
        # fallback path (sharded meshes, non-paged calls) too. The
        # adaptive off-switch below still bounds the cost per call
        # either way.
        speculative = _sp_cfg.enabled
    spec_dp = 1
    spec_mesh = None
    if mesh is not None and mesh.size > 1:
        from adversarial_spec_tpu.parallel.mesh import DP as _SPEC_DP

        # Multi-host safe: speculation's host-side control flow
        # (spec_fits, _steps_exit, catch-up targets) reduces
        # steps_rows/finished to REPLICATED scalars on device before
        # fetching, so no host ever touches a non-addressable shard and
        # every host takes identical branches (BASELINE config 5's
        # v5p-16 decode lever; exercised by the two-process spec parity
        # test in tests/test_multihost.py).
        if mesh.size == mesh.shape[_SPEC_DP]:
            spec_dp = mesh.shape[_SPEC_DP]
        else:
            # tp / dp×tp / sp meshes: ONE GSPMD-partitioned program.
            # On sp meshes this runs AFTER reshard_cache_for_decode put
            # the cache in the standard decode layout (batch over dp,
            # heads over tp, sp idle/replicated — parallel/sp.py), so
            # the compiler partitions over dp×tp and replicates the sp
            # axis exactly as the plain chunked-decode path already
            # does. The 16k-context config keeps its decode lever
            #.
            spec_mesh = mesh
    use_spec = (
        speculative and not paged and max_new_tokens > gamma + 1
    )
    if spec_explicit and speculative and paged and (
        max_new_tokens > gamma + 1
    ):
        # The dense speculative loop has no paged variant here — paged
        # speculation lives in the ContinuousBatcher (engine/scheduler's
        # per-slot draft/verify step), which is where the serving path
        # already runs. Say so ONCE instead of silently decoding
        # token-at-a-time under a flag combination that reads like
        # "speculation on". Only for an EXPLICIT speculative=True: a
        # paged call that merely inherited the default-on process config
        # (the engine's dense fallback) asked for nothing and gets no
        # spurious warning.
        global _PAGED_SPEC_WARNED
        if not _PAGED_SPEC_WARNED:
            _PAGED_SPEC_WARNED = True
            import sys as _sys

            print(
                "warning: speculative=True is ignored when paged=True in "
                "generate() — dense-path speculation has no paged "
                "variant; paged speculation runs per-slot in the "
                "ContinuousBatcher (TpuEngine.chat / run_all). "
                "Pass speculative=False to silence this.",
                file=_sys.stderr,
            )
    desynced = False  # per-row steps diverge after any speculative phase
    steps_rows = None
    if use_spec:
        from adversarial_spec_tpu.engine.speculative import (
            rowwise_decode_steps,
            speculative_decode_steps,
        )

        prev_rows = tokens[:, -1]
        steps_rows = jnp.ones((B,), jnp.int32)
        # One attention implementation governs the whole speculative call
        # (verify and tail see the same near-tie argmaxes): MQ kernel for
        # spans, single-query kernel for the tail — both read int8 tiles.
        spec_pallas = use_pallas_decode

    t1 = time.monotonic()

    def _steps_exit() -> int:
        """Host-side loop scalar: min over rows of (done ? max_new :
        steps) — max_new only once every row is finished or at budget.

        The reduction runs ON DEVICE so only a replicated scalar is
        fetched: steps_rows/finished are dp-sharded, and on a multi-host
        mesh a host-side np.asarray of them would touch non-addressable
        shards and raise. Replicated scalars are identical on every
        host, so all hosts take the same branch (SPMD lockstep)."""
        if steps_rows is None:
            return int(step)
        return int(
            jnp.where(finished, jnp.int32(max_new_tokens), steps_rows).min()
        )

    while _steps_exit() < max_new_tokens and not bool(finished.all()):
        if deadline is not None and time.monotonic() >= deadline:
            timed_out = True
            break
        key, chunk_key = jax.random.split(key)
        if use_spec:
            # Device-side reduction → replicated bool (multi-host safe).
            spec_fits = bool(
                jnp.any(
                    ~finished & (steps_rows + gamma + 1 <= max_new_tokens)
                )
            )
        else:
            spec_fits = False
        if spec_fits:
            spec_static = dict(
                prompt_len=S,
                gamma=gamma,
                iters=max(1, DECODE_CHUNK // (gamma + 1)),
                greedy=greedy,
                top_k=top_k,
                use_top_p=use_top_p,
                use_pallas=spec_pallas,
                pallas_interpret=pallas_interpret,
            )
            spec_args = (
                tokens,
                prev_rows,
                cur,
                pad_lens,
                finished,
                out_buf,
                steps_rows,
                jnp.int32(max_new_tokens),
                eos,
                chunk_key,
                temp,
                tp,
            )
            if spec_dp > 1:
                from adversarial_spec_tpu.engine.speculative import (
                    speculative_decode_steps_dp,
                )

                ret = speculative_decode_steps_dp(
                    mesh, params, cfg, cache, *spec_args, **spec_static
                )
            else:
                ret = speculative_decode_steps(
                    params,
                    cfg,
                    cache,
                    *spec_args,
                    # None off-mesh; the tp/GSPMD path partitions the
                    # program over the mesh (dp wrappers take the mesh
                    # positionally instead, and their inner calls must
                    # see mesh=None — they already run under shard_map).
                    mesh=spec_mesh,
                    **spec_static,
                )
            (
                cache,
                prev_rows,
                cur,
                finished,
                out_buf,
                steps_rows,
                n_iters,
                n_emitted,
                n_row_iters,
            ) = ret
            desynced = True
            step = jnp.max(steps_rows)
            # Adaptive off-switch: each verification forward is γ+1 wide;
            # if it averages barely more than one emitted token per
            # active row-iteration (exact count from the device loop),
            # drafts aren't matching and plain decode is cheaper.
            if int(n_emitted) / max(int(n_row_iters), 1) < 1.5:
                use_spec = False
        elif desynced:
            # Rows no longer share a step count. If speculation is OFF
            # with budget left, only let the laggards CATCH UP to the
            # frontmost UNFINISHED row (rowwise slots are ~2x slower per
            # step than the shared-slot loop: per-row scattered cache
            # writes), then clear the desync so the rest of the budget
            # decodes synced. With speculation merely out of span-budget,
            # rowwise runs the whole tail.
            need_catchup = True
            if use_spec:
                target = max_new_tokens
            else:
                # Unfinished-row max as a replicated device scalar; the
                # outer loop guarantees at least one unfinished row.
                target = min(
                    int(
                        jnp.where(
                            finished, jnp.int32(-1), steps_rows
                        ).max()
                    ),
                    max_new_tokens,
                )
                if bool(jnp.all(finished | (steps_rows >= target))):
                    # Already level (e.g. B == 1, or equal accept
                    # counts): no catch-up dispatch needed.
                    desynced = False
                    step = jnp.int32(target)
                    need_catchup = False
            if need_catchup:
                rw_args = (
                    cur,
                    pad_lens,
                    finished,
                    out_buf,
                    steps_rows,
                    jnp.int32(target),
                    eos,
                    chunk_key,
                    temp,
                    tp,
                )
                rw_static = dict(
                    prompt_len=S,
                    chunk=DECODE_CHUNK,
                    greedy=greedy,
                    top_k=top_k,
                    use_top_p=use_top_p,
                    use_pallas=spec_pallas,
                    pallas_interpret=pallas_interpret,
                )
                if spec_dp > 1:
                    from adversarial_spec_tpu.engine.speculative import (
                        rowwise_decode_steps_dp,
                    )

                    cache, cur, finished, out_buf, steps_rows = (
                        rowwise_decode_steps_dp(
                            mesh, params, cfg, cache, *rw_args, **rw_static
                        )
                    )
                else:
                    cache, cur, finished, out_buf, steps_rows = (
                        rowwise_decode_steps(
                            params,
                            cfg,
                            cache,
                            *rw_args,
                            mesh=spec_mesh,
                            **rw_static,
                        )
                    )
                step = jnp.max(steps_rows)
                if not use_spec:
                    if bool(jnp.all(finished | (steps_rows >= target))):
                        # Level again: unfinished rows all sit at target.
                        desynced = False
                        step = jnp.int32(target)
        elif paged:
            from adversarial_spec_tpu.engine.scheduler import (
                scheduler_decode_chunk,
                sharded_scheduler_decode_chunk,
            )

            static_kw = dict(
                chunk=DECODE_CHUNK,
                greedy=greedy,
                top_k=top_k,
                use_top_p=use_top_p,
                use_pallas=use_paged_kernel,
                use_pallas_matmul=use_pallas_matmul,
                pallas_interpret=pallas_interpret,
            )
            chunk_args = (
                params,
                cfg,
                pool,
                page_table,
                cur,
                paged_cur_len,
                pad_lens,
                paged_n_emitted,
                paged_max_new,
                paged_active,
                out_buf,
                eos,
                chunk_key,
                temp,
                tp,
            )
            (
                pool,
                cur,
                paged_cur_len,
                paged_n_emitted,
                out_buf,
                paged_active,
            ) = (
                sharded_scheduler_decode_chunk(
                    mesh, *chunk_args, **static_kw
                )
                if paged_dp > 1
                # tp/sp/mixed meshes: the kernel runs under shard_map
                # inside the GSPMD program (head-sharded pool, sp
                # replicated); the dp path above shards whole
                # per-device pools instead.
                else scheduler_decode_chunk(
                    *chunk_args,
                    **static_kw,
                    mesh=mesh if paged_gspmd else None,
                )
            )
            step = jnp.max(paged_n_emitted)
            finished = ~paged_active
        else:
            # Plain chunked decode owns the rest of the budget (nothing
            # re-enables speculation once it is off, and paged never
            # reaches here) — run it PIPELINED: dispatch chunk N+1
            # before blocking on chunk N's exit flags, so the host's
            # per-chunk work (PRNG split, arg staging, dispatch) always
            # overlaps device compute and the device never idles on a
            # host round-trip between chunks. The exit check trails one
            # chunk behind; its cost is at most one extra dispatch whose
            # while_loop exits immediately (all rows finished or budget
            # reached) — and the FIRST trailing check is free, because
            # the outer loop condition already fetched the entry step.
            while True:
                # Deadline BEFORE dispatch (host clock only — no device
                # sync on the fast path): once the deadline passes, no
                # further chunk is dispatched, so a timeout overshoots
                # by at most the chunk already in flight. At the
                # deadline we DO sync on that in-flight chunk — if it
                # completed the generation, this is a finished result
                # that happens to end near the deadline, not a timeout.
                if deadline is not None and time.monotonic() >= deadline:
                    if not (
                        int(step) >= max_new_tokens
                        or bool(finished.all())
                    ):
                        timed_out = True
                    break
                prev_step, prev_finished = step, finished
                cache, cur, finished, out_buf, step = decode_chunk_steps(
                    params,
                    cfg,
                    cache,
                    cur,
                    pad_lens,
                    finished,
                    out_buf,
                    step,
                    jnp.int32(max_new_tokens),
                    eos,
                    chunk_key,
                    temp,
                    tp,
                    prompt_len=S,
                    chunk=DECODE_CHUNK,
                    greedy=greedy,
                    top_k=top_k,
                    use_top_p=use_top_p,
                    use_pallas_decode=use_pallas_decode,
                    use_pallas_matmul=use_pallas_matmul,
                    pallas_interpret=pallas_interpret,
                    mesh=mesh
                    if (mesh is not None and mesh.size > 1)
                    else None,
                )
                key, chunk_key = jax.random.split(key)
                if int(prev_step) >= max_new_tokens or bool(
                    prev_finished.all()
                ):
                    break
            if steps_rows is not None:
                # Synced again after a speculative phase + catch-up:
                # every unfinished row advanced to `step`. Raising a
                # finished row's count only widens its EOS-scan region —
                # the scan still stops at its first EOS (zeros follow).
                steps_rows = jnp.maximum(steps_rows, step)
    decode_time = time.monotonic() - t1

    out_np = _host_fetch(out_buf)[:n_real, :max_new_tokens]
    B = n_real  # dp-padding rows dropped
    # Per-row step counts: shared scalar on the synced paths; the
    # speculative paths desynchronize rows (a timeout can strand them at
    # different steps — a shared max would count a slower row's zero
    # slots as output).
    if steps_rows is not None:
        row_steps = np.minimum(
            _host_fetch(steps_rows)[:n_real], max_new_tokens
        )
    else:
        row_steps = np.full((B,), min(int(step), max_new_tokens))
    eos_np = np.asarray(sorted(set(eos_ids)) or [-1])
    n_generated = np.zeros((B,), np.int64)
    for b in range(B):
        row = out_np[b, : row_steps[b]]
        eos_hits = np.isin(row, eos_np)
        if eos_hits.any():
            n_generated[b] = int(np.argmax(eos_hits)) + 1
        else:
            n_generated[b] = row_steps[b]
    return GenerateResult(
        tokens=out_np,
        n_generated=n_generated,
        prefill_time_s=prefill_time,
        decode_time_s=decode_time,
        decode_tokens=int(n_generated.sum()),
        timed_out=timed_out,
    )
