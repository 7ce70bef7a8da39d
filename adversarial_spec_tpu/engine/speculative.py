"""Prompt-lookup speculative decoding — batched, any sampling mode.

The debate workload's dominant output is a ``[SPEC]...[/SPEC]`` revision —
a near-copy of the input document with edits. That makes *prompt-lookup*
drafting (LLMA / prompt-lookup decoding: match the last n-gram of the
generated text against the context and draft the tokens that followed it
there) exceptionally effective: long runs of the revision are verbatim
context spans, so most drafts verify and the model emits several tokens
per forward pass instead of one. No draft model, no extra weights — the
draft source is the prompt *plus the text generated so far* (revision
notes repeat across rounds, so generated text matters).

One step, per batch row: draft γ tokens from the most recent n-gram match;
run ONE verification forward over [cur, d_0..d_{γ-1}] (γ+1 positions, the
same KV-cached forward prefill chunks use, with per-row cache slots since
rows desynchronize); accept drafts by REJECTION SAMPLING against the true
sampling distribution (engine/sampling.py:filtered_logits):

    draft token d_i is a delta distribution, so accept with probability
    p_i(d_i) (u < p catches both: greedy p is one-hot → exact argmax
    match); on the first rejection sample from the residual p with d_i
    zeroed and renormalized — the marginal at every position is exactly p,
    so speculation is *distribution-preserving* at any temperature and
    bit-identical to plain decode when greedy.

Cache discipline: the verification forward writes γ+1 KV slots per row at
that row's own offset; rejected drafts leave stale KV above slot
cache_index+n_acc, but the row's next write region starts exactly there
(new cache_index = old + n_emit) and layer writes land before attention,
so stale slots are never read.

Because rows accept different draft counts, they desynchronize — after any
speculative phase the tail must finish on ``rowwise_decode_steps`` (per-row
cache slots), not the shared-slot loop in engine/generate.py.

Scope: dense KV cache, on any non-sp mesh — single device; dp-only
meshes via the ``*_dp`` shard_mapped wrappers below (rows shard over
dp, each device runs its own accept loop — per-row desync never
crosses devices); tp and mixed dp×tp meshes via one GSPMD-partitioned
accept loop (``mesh=`` on the entry points: heads shard over tp inside
the verification forward, the compiler inserts the collectives).
Multi-host dp meshes work too: generate()'s surrounding control flow
only fetches replicated scalars. sp decode meshes are the one
exclusion (ring-resharded caches; plain chunked decode serves them).
On TPU the verification forward runs the MULTI-QUERY fused kernel
(ops/pallas_decode.py:decode_attention_mq — the whole γ+1 span in one
pass over the KV cache) and the tail loop the single-query kernel, so
speculation no longer costs the fused-attention path (round-1's
shortcut). int8 KV composes: the MQ kernel reads int8 tiles and
dequantizes in-kernel.

EOS contract (mirror of generate._sample_step — change BOTH together):
the EOS token itself is kept in the output; slots after it emit 0.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from adversarial_spec_tpu.engine import spec as spec_config
from adversarial_spec_tpu.engine.sampling import (
    filtered_logits,
    sample_tokens,
)
from adversarial_spec_tpu.models.config import ModelConfig
from adversarial_spec_tpu.models.transformer import Cache, Params, forward

# Draft length per speculative step. Larger γ emits more tokens per
# verification forward when drafts match (revision-heavy [SPEC] output)
# but wastes a γ+1-wide forward when they miss; 8 is the prior (the
# on-chip crossover: not measured).
# The knob LIVES in engine/spec.py now (``ADVSPEC_GAMMA`` / ``--gamma``,
# reconfigurable per round without a reimport); this module-level value
# is the import-time snapshot kept for callers that treat γ as a
# constant — importing it validates the env var exactly as before
# (spec.env_gamma fails fast on γ < 1).
GAMMA = spec_config.config().gamma


def _rowwise_slice(buf: jnp.ndarray, starts: jnp.ndarray, size: int):
    """[B, N] gathered at per-row starts → [B, size]."""
    return jax.vmap(
        lambda row, s: jax.lax.dynamic_slice(row, (s,), (size,))
    )(buf, starts)


def _rowwise_write(buf: jnp.ndarray, vals: jnp.ndarray, starts: jnp.ndarray):
    """Write [B, size] into [B, N] at per-row starts."""
    return jax.vmap(
        lambda row, v, s: jax.lax.dynamic_update_slice(row, v, (s,))
    )(buf, vals, starts)


def accept_spans(
    probs: jnp.ndarray,  # [B, γ+1, V] filtered target distribution
    draft: jnp.ndarray,  # [B, γ]
    n_allowed: jnp.ndarray,  # [B] draft positions eligible to commit
    u_key: jax.Array,
    res_key: jax.Array,
    *,
    greedy: bool,
):
    """THE accept math — rejection-sample a per-row accept length against
    the true sampling distribution, shared verbatim by the dense path
    (``speculative_decode_steps``) and the paged ContinuousBatcher's
    verify step (engine/scheduler.py), so greedy output stays
    byte-identical to plain decode on both.

    ``n_allowed`` caps how many draft positions may commit this step
    (the dense path passes a constant γ; the batcher clamps per row by
    output budget and allocated pages). Positions at or past the cap are
    FORCED rejections — crucially, a forced stop draws the bonus token
    from the FULL distribution at that position, not the residual:
    zeroing a draft token the coin never rejected would bias the
    marginal (and break greedy parity whenever the draft equals the
    argmax). Returns ``(n_acc [B], bonus [B])``.
    """
    B, gamma = draft.shape
    rows = jnp.arange(B)
    p_draft = jnp.take_along_axis(
        probs[:, :-1], draft[..., None], axis=-1
    )[..., 0]  # [B, γ] target prob of each draft token
    u = jax.random.uniform(u_key, (B, gamma))
    pos = jnp.arange(gamma)[None, :]
    # greedy: p ∈ {0,1} ⇒ exact argmax match
    accept = (u < p_draft) & (pos < n_allowed[:, None])
    n_acc = jnp.sum(
        jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
    )  # [B]

    # --- The bonus token: residual draw at a NATURAL rejection point,
    # a fresh full-distribution draw when the allowed span ran out. ---
    at = probs[rows, n_acc]  # [B, V] distribution at emit position
    rejected = n_acc < n_allowed
    rej_draft = draft[rows, jnp.minimum(n_acc, gamma - 1)]
    # Residual: zero the rejected draft token, renormalize. Marginal
    # over (accept, residual) is exactly `at` — see module docstring.
    res = at.at[rows, rej_draft].set(
        jnp.where(rejected, 0.0, at[rows, rej_draft])
    )
    res = res / jnp.maximum(res.sum(-1, keepdims=True), 1e-30)
    bonus = jax.random.categorical(
        res_key, jnp.log(jnp.maximum(res, 1e-30)), axis=-1
    ).astype(jnp.int32)
    if greedy:
        # Bit-identical contract: no RNG in the greedy path. The
        # residual of a one-hot is one-hot ⇒ argmax, computed directly.
        bonus = jnp.argmax(res, axis=-1).astype(jnp.int32)
    return n_acc, bonus


def _draft(context, prev, cur, limits, gamma):
    """Most recent [prev, cur] bigram match in each row's context.

    context: [B, N] prompt ++ generated-so-far (zeros beyond ``limits``);
    limits: [B] one past the last real context token. Returns draft
    [B, gamma] — the tokens that followed the match (zeros when none;
    drafts never affect correctness, only acceptance rate).
    """
    B, N = context.shape
    pos = jnp.arange(N - 1)[None, :]
    match = (
        (context[:, :-1] == prev[:, None])
        & (context[:, 1:] == cur[:, None])
        # The bigram AND at least one drafted token must be real context.
        & (pos + 2 < limits[:, None])
    )
    best = jnp.max(jnp.where(match, pos, -1), axis=1)  # [B]
    has_match = best >= 0
    d_start = jnp.clip(best + 2, 0, N - gamma)
    draft = _rowwise_slice(context, d_start, gamma)
    return jnp.where(has_match[:, None], draft, jnp.zeros_like(draft))


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "prompt_len",
        "iters",
        "gamma",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("cache", "out_buf"),
)
def speculative_decode_steps(
    params: Params,
    cfg: ModelConfig,
    cache: Cache,
    prompt_tokens: jnp.ndarray,  # [B, S] left-padded prompts (draft source)
    prev_tokens: jnp.ndarray,  # [B] token before cur (n-gram context)
    cur_tokens: jnp.ndarray,  # [B] last emitted token per row
    pad_lens: jnp.ndarray,  # [B]
    finished: jnp.ndarray,  # [B] bool
    out_buf: jnp.ndarray,  # [B, max_new]
    steps: jnp.ndarray,  # [B] per-row decode step (out_buf position)
    stop_at: jnp.ndarray,  # scalar: decode no further than this step
    eos_ids: jnp.ndarray,  # [E]
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    prompt_len: int,
    iters: int,
    gamma: int = GAMMA,
    greedy: bool = False,
    top_k: int = 0,
    use_top_p: bool = True,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
):
    """Up to ``iters`` speculative rounds over whichever rows still fit a
    full γ+1 span.

    ``mesh`` (tp path): a single-host mesh whose tensor-parallel degree
    shards the layer matmuls via GSPMD — this whole function runs as ONE
    partitioned program (devices stay in lockstep, which tp requires
    anyway; collectives come from the compiler, not manual psums). The
    verify forward's attention takes the jnp path (the MQ kernel is
    single-device; GSPMD shards its heads axis), and the dp-only case
    uses the ``*_dp`` shard_map wrappers below instead (independent
    per-device accept loops beat a lockstep global loop when devices
    don't have to communicate).

    Returns (cache, prev, cur, finished, out_buf, steps, n_iters,
    n_emitted_total, n_row_iters) — the caller finishes budget-capped
    rows with ``rowwise_decode_steps`` and can use n_emitted_total /
    n_row_iters (exact per-active-row emit rate: n_row_iters counts
    active rows summed over iterations) to turn speculation OFF when
    drafts aren't matching (each rejected round costs a γ+1-wide forward
    to emit one token).
    """
    B, S = prompt_tokens.shape
    T = cache["k"].shape[3]  # [L, B, Hkv, T, D]
    max_new = out_buf.shape[1]
    kv_base = jnp.arange(T)[None, :] >= pad_lens[:, None]
    span = gamma + 1
    rows = jnp.arange(B)
    bound = jnp.minimum(stop_at, max_new)

    def active_rows(steps, finished):
        return ~finished & (steps + span <= bound)

    def cond(state):
        it, steps, finished = state[0], state[1], state[6]
        return (it < iters) & active_rows(steps, finished).any()

    def body(state):
        (
            it,
            steps,
            prev,
            cur,
            cache,
            out_buf,
            finished,
            key,
            n_emit_tot,
            n_row_iters,
        ) = state
        active = active_rows(steps, finished)

        # --- Draft from prompt ++ generated text (most recent match). ---
        context = jnp.concatenate([prompt_tokens, out_buf], axis=1)
        draft = _draft(context, prev, cur, prompt_len + steps, gamma)

        # --- Verify: one forward over [cur, draft] at per-row slots. ---
        toks = jnp.concatenate([cur[:, None], draft], axis=1)  # [B, γ+1]
        cache_index = prompt_len + steps - 1  # [B]
        positions = (
            cache_index[:, None]
            + jnp.arange(span, dtype=jnp.int32)[None, :]
            - pad_lens[:, None]
        )
        logits, cache = forward(
            params,
            cfg,
            toks,
            positions,
            cache,
            cache_index,
            kv_base,
            use_pallas_decode=use_pallas,
            pallas_interpret=pallas_interpret,
            mesh=mesh,
        )
        # The true per-position sampling distribution (one-hot if greedy).
        filt = filtered_logits(
            logits,
            greedy=greedy,
            top_k=top_k,
            temperature=temperature,
            top_p=top_p,
            use_top_p=use_top_p,
        )  # [B, γ+1, V]
        probs = jax.nn.softmax(filt, axis=-1)

        # --- Rejection-sample the accept length per row (accept_spans —
        # the same shared math the batcher's verify step runs; a full-γ
        # n_allowed makes the cap term an identity here). ---
        key, u_key, res_key = jax.random.split(key, 3)
        n_acc, bonus = accept_spans(
            probs,
            draft,
            jnp.full((B,), gamma, jnp.int32),
            u_key,
            res_key,
            greedy=greedy,
        )

        emitted = jnp.concatenate(
            [draft, jnp.zeros((B, 1), draft.dtype)], axis=1
        )
        emitted = emitted.at[rows, n_acc].set(bonus)

        # --- EOS + per-row emit counts (EOS kept, zeros after). ---
        is_eos = (emitted[..., None] == eos_ids[None, None, :]).any(-1)
        j = jnp.arange(span)[None, :]
        eos_hits = is_eos & (j <= n_acc[:, None])
        any_eos = eos_hits.any(axis=1)
        first_eos = jnp.argmax(eos_hits, axis=1)
        n_emit = jnp.where(any_eos, first_eos + 1, n_acc + 1)
        n_emit = jnp.where(active, n_emit, 0)
        emitted = jnp.where(j < n_emit[:, None], emitted, 0)

        # Inactive rows write their existing slots back (no-op write —
        # a clamped zero-write could smash a budget-capped row's tail).
        w_start = jnp.minimum(steps, max_new - span)
        current = _rowwise_slice(out_buf, w_start, span)
        out_buf = _rowwise_write(
            out_buf,
            jnp.where(active[:, None], emitted, current),
            w_start,
        )

        finished = finished | (any_eos & active)
        new_cur = jnp.where(
            active, emitted[rows, jnp.maximum(n_emit - 1, 0)], cur
        )
        new_prev = jnp.where(
            active,
            jnp.where(n_emit >= 2, emitted[rows, n_emit - 2], cur),
            prev,
        )
        return (
            it + 1,
            steps + n_emit,
            new_prev,
            new_cur,
            cache,
            out_buf,
            finished,
            key,
            n_emit_tot + n_emit.sum(),
            n_row_iters + active.sum(),
        )

    state = (
        jnp.int32(0),
        steps,
        prev_tokens,
        cur_tokens,
        cache,
        out_buf,
        finished,
        key,
        jnp.int32(0),
        jnp.int32(0),
    )
    (
        it,
        steps,
        prev,
        cur,
        cache,
        out_buf,
        finished,
        key,
        n_emit_tot,
        n_row_iters,
    ) = jax.lax.while_loop(cond, body, state)
    return (
        cache,
        prev,
        cur,
        finished,
        out_buf,
        steps,
        it,
        n_emit_tot,
        n_row_iters,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "prompt_len",
        "chunk",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("cache", "out_buf"),
)
def rowwise_decode_steps(
    params: Params,
    cfg: ModelConfig,
    cache: Cache,
    cur_tokens: jnp.ndarray,  # [B]
    pad_lens: jnp.ndarray,  # [B]
    finished: jnp.ndarray,  # [B] bool
    out_buf: jnp.ndarray,  # [B, max_new]
    steps: jnp.ndarray,  # [B] per-row decode step
    stop_at: jnp.ndarray,  # scalar
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    prompt_len: int,
    chunk: int,
    greedy: bool,
    top_k: int,
    use_top_p: bool = True,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
):
    """Plain single-token decode with PER-ROW cache slots.

    The tail loop after any speculative phase: rows desynchronize there
    (different accepted draft counts), so the shared-slot
    ``decode_chunk_steps`` can no longer drive them. Same sampling and
    EOS semantics as generate._sample_step. ``mesh``: tp via GSPMD, same
    contract as speculative_decode_steps (the S=1 forward routes the
    fused kernel through its shard_map wrapper on such meshes).
    """
    B = cur_tokens.shape[0]
    T = cache["k"].shape[3]  # [L, B, Hkv, T, D]
    max_new = out_buf.shape[1]
    kv_base = jnp.arange(T)[None, :] >= pad_lens[:, None]
    rows = jnp.arange(B)
    bound = jnp.minimum(stop_at, max_new)

    def active_rows(steps, finished):
        return ~finished & (steps < bound)

    def cond(state):
        it, steps, finished = state[0], state[1], state[4]
        return (it < chunk) & active_rows(steps, finished).any()

    def body(state):
        it, steps, cur, cache, finished, out_buf, key = state
        active = active_rows(steps, finished)
        cache_index = prompt_len + steps - 1  # [B]
        positions = (cache_index - pad_lens)[:, None]
        logits, cache = forward(
            params,
            cfg,
            cur[:, None],
            positions,
            cache,
            cache_index,
            kv_base,
            use_pallas_decode=use_pallas,
            pallas_interpret=pallas_interpret,
            mesh=mesh,
        )
        key, sub = jax.random.split(key)
        nxt = sample_tokens(
            logits[:, 0],
            sub,
            greedy=greedy,
            top_k=top_k,
            temperature=temperature,
            top_p=top_p,
            use_top_p=use_top_p,
        )
        is_eos = (nxt[:, None] == eos_ids[None, :]).any(axis=-1)
        nxt = jnp.where(finished, 0, nxt)
        idx = jnp.minimum(steps, max_new - 1)
        vals = jnp.where(active, nxt, out_buf[rows, idx])
        out_buf = out_buf.at[rows, idx].set(vals)
        finished = finished | (is_eos & active)
        steps = steps + active.astype(jnp.int32)
        cur = jnp.where(active, nxt, cur)
        return it + 1, steps, cur, cache, finished, out_buf, key

    state = (jnp.int32(0), steps, cur_tokens, cache, finished, out_buf, key)
    it, steps, cur, cache, finished, out_buf, key = jax.lax.while_loop(
        cond, body, state
    )
    return cache, cur, finished, out_buf, steps


def speculative_decode_steps_dp(
    mesh,
    params,
    cfg,
    cache,
    prompt_tokens,
    prev_tokens,
    cur_tokens,
    pad_lens,
    finished,
    out_buf,
    steps,
    stop_at,
    eos_ids,
    key,
    temperature,
    top_p,
    **static_kw,
):
    """``speculative_decode_steps`` with rows sharded over a dp-only mesh.

    dp-only (tp = sp = 1): inside shard_map the layer matmuls see full
    weights (replicated), so no manual tp collectives are needed. The
    engine gates on ``mesh.size == mesh.shape[DP]``.
    """
    from jax.sharding import PartitionSpec as P

    from adversarial_spec_tpu.parallel.mesh import DP

    row_arrays = (
        prompt_tokens,
        prev_tokens,
        cur_tokens,
        pad_lens,
        finished,
        out_buf,
        steps,
    )
    rowspec = tuple(P(DP, *([None] * (a.ndim - 1))) for a in row_arrays)
    cache_spec = jax.tree.map(
        lambda x: P(None, DP, *([None] * (x.ndim - 2))), cache
    )
    param_spec = jax.tree.map(lambda _: P(), params)

    def local(params_l, cache_l, prompt_l, prev_l, cur_l, pads_l, fin_l,
              out_l, steps_l, stop_at_l, eos_l, key_l, temp_l, tp_l):
        key_l = jax.random.fold_in(key_l, jax.lax.axis_index(DP))
        (
            cache_o, prev_o, cur_o, fin_o, out_o, steps_o,
            it, n_emit, n_row_iters,
        ) = speculative_decode_steps(
            params_l, cfg, cache_l, prompt_l, prev_l, cur_l, pads_l,
            fin_l, out_l, steps_l, stop_at_l, eos_l, key_l, temp_l, tp_l,
            **static_kw,
        )
        return (
            cache_o, prev_o, cur_o, fin_o, out_o, steps_o,
            jax.lax.pmax(it, DP),
            jax.lax.psum(n_emit, DP),
            jax.lax.psum(n_row_iters, DP),
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_spec, cache_spec, *rowspec,
                  P(), P(), P(), P(), P()),
        out_specs=(cache_spec, rowspec[1], rowspec[2], rowspec[4],
                   rowspec[5], rowspec[6], P(), P(), P()),
        check_vma=False,
    )(params, cache, *row_arrays, stop_at, eos_ids, key, temperature,
      top_p)


def rowwise_decode_steps_dp(
    mesh,
    params,
    cfg,
    cache,
    cur_tokens,
    pad_lens,
    finished,
    out_buf,
    steps,
    stop_at,
    eos_ids,
    key,
    temperature,
    top_p,
    **static_kw,
):
    """``rowwise_decode_steps`` with rows sharded over a dp-only mesh."""
    from jax.sharding import PartitionSpec as P

    from adversarial_spec_tpu.parallel.mesh import DP

    row_arrays = (cur_tokens, pad_lens, finished, out_buf, steps)
    rowspec = tuple(P(DP, *([None] * (a.ndim - 1))) for a in row_arrays)
    cache_spec = jax.tree.map(
        lambda x: P(None, DP, *([None] * (x.ndim - 2))), cache
    )
    param_spec = jax.tree.map(lambda _: P(), params)

    def local(params_l, cache_l, cur_l, pads_l, fin_l, out_l, steps_l,
              stop_at_l, eos_l, key_l, temp_l, tp_l):
        key_l = jax.random.fold_in(key_l, jax.lax.axis_index(DP))
        return rowwise_decode_steps(
            params_l, cfg, cache_l, cur_l, pads_l, fin_l, out_l, steps_l,
            stop_at_l, eos_l, key_l, temp_l, tp_l, **static_kw,
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_spec, cache_spec, *rowspec,
                  P(), P(), P(), P(), P()),
        out_specs=(cache_spec, rowspec[0], rowspec[2], rowspec[3],
                   rowspec[4]),
        check_vma=False,
    )(params, cache, *row_arrays, stop_at, eos_ids, key, temperature,
      top_p)
