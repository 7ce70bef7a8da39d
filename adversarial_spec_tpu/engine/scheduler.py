"""Continuous batching scheduler over the paged KV pool.

SURVEY §7 step 3's full form ("continuous batching across opponents
sharing weights"): a slot-based scheduler that keeps one decode batch hot
while sequences of different lengths join and leave it —

- ``max_batch`` slots decode together as rows of one jitted program;
- a finished row's pages free immediately and a queued request is admitted
  into the empty slot at the next chunk boundary — its prompt chunks ride
  INSIDE the residents' decode program (``fused_prefill_decode_chunk``,
  Sarathi-style piggybacked chunked prefill), so admission never pauses
  the batch;
- per-row lengths/budgets/EOS are tracked as device arrays, so rows at
  different positions coexist in the same while_loop (per-row ``q_pos``
  drives page writes, RoPE positions, and window bounds).

Drive loop (``_drive``; engine/interleave.py holds its telemetry): one
loop, whose iteration admits, dispatches one step (fused when an
admission's prompt chunk can ride the residents' step) and retires it,
and never calls a blanket ``jax.block_until_ready``. Both branches run
two steps deep (``_PIPELINE_DEPTH``): step N is enqueued before step
N-1's results are fetched, so queue admission, prefix-cache radix
lookups, page allocation, stream delivery and result collection overlap
device compute. A speculative step (the default) goes into the pipe of
verify steps: its pages are covered for the spans in flight, and its
per-row accepted counts and emitted tokens (one stacked array) are
fetched, applied and streamed while its successor runs; the iteration
runs one deep when no row is certain to need another step or the second
span finds no pages. A plain decode step goes into a double buffer: the
host applies step N-1's fetched ``active`` flags (async device→host
copy) while step N runs. Sanctioned sync
points, and ONLY these (enforced by graftlint's GL-SYNC rule, which
catches implicit syncs — np.asarray/.item()/int()/truthiness on device
values — as well as explicit block_until_ready;
docs/static_analysis.md): admission handoff (``_finish_admission``),
the speculative counts fetch, the double buffer's depth bound, a plain
row's completion (token fetch), fault decisions, and timeout expiry.

Inactive-slot safety: physical page 0 is a reserved TRASH page no
sequence owns. Allocator ids are shifted +1, the -1 "unmapped" sentinel
maps to 0, and inactive rows write their (masked, discarded) KV there —
a dead slot can never scribble into pages re-allocated to a newcomer.
Trash/unmapped pages are never read: every row's valid window
[pad, cur_len) ends before any unmapped logical slot.

Fault isolation: a fault at the decode-chunk, admission-prefill, or
page-allocation step evicts only the affected slot — its ``SchedResult``
carries the partial tokens plus ``error``/``fault_kind`` — frees its
pages, and leaves the rest of the batch decoding. Transient faults
(resilience/faults.py taxonomy) get one requeue before the partial result
is final, budgeted against the caller's existing deadline. The chaos
injector's ``scheduler_chunk`` and ``kv_alloc`` seams live here.

Per-request watchdog (``SchedRequest.deadline_s``, docs/resilience.md
"Durability and recovery"): the drive loop checks per-request
deadlines once per iteration — pure host clock math — and evict an
over-deadline slot as ``FaultKind.TIMEOUT`` through the same shared
surgery, partial text delivered to its stream consumer, co-residents
untouched, no batcher-level requeue (the debate layer owns the single
hedged re-admission). Zero new sync points: the eviction rides the
decode-fault path's existing sanctioned fetches.

The round-synchronous debate path (engine/tpu.py) doesn't need this; it
serves multi-session workloads (several debates sharing one model) and is
exercised directly in tests/test_scheduler.py.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from adversarial_spec_tpu.engine.generate import (
    _prefill_chunk_impl,
    bucket_length,
    pad_batch,
    prefill_chunk,
)
from adversarial_spec_tpu.engine import interleave as interleave_mod
from adversarial_spec_tpu.engine import kvtier as kvtier_mod
from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
from adversarial_spec_tpu.engine import spec as spec_mod
from adversarial_spec_tpu.engine import streaming as stream_mod
from adversarial_spec_tpu import obs as obs_mod
from adversarial_spec_tpu.engine.sampling import filtered_logits
from adversarial_spec_tpu.engine.speculative import (
    _draft,
    _rowwise_slice,
    _rowwise_write,
    accept_spans,
)
from adversarial_spec_tpu.engine.kvcache import (
    OutOfPages,
    PageAllocator,
    PagedCacheLayout,
    init_page_pool,
    read_tokens,
    write_tokens,
)
from adversarial_spec_tpu.engine.sampling import sample_tokens
from adversarial_spec_tpu.models import moe as moe_mod
from adversarial_spec_tpu.models.config import ModelConfig
from adversarial_spec_tpu.ops import kv_write, quant
from adversarial_spec_tpu.models.config import (
    refuse_beside_state_space,
    refuse_unwired,
)
from adversarial_spec_tpu.models.transformer import (
    STATE_LEAVES,
    commit_span,
    forward_paged_decode,
    init_cache,
    init_recurrent_state,
    min_weight_block_bytes,
    n_indexed_stacks,
)
from adversarial_spec_tpu.resilience import faults, injector

TRASH_PAGE = 0
# Admission prefill granularity — deliberately finer than generate.py's
# PREFILL_CHUNK (1024): smaller chunks mean decode chunks slot in between
# more often while a newcomer's prompt streams in.
ADMISSION_CHUNK = 512
# Steps the drive loop keeps in flight, on both of its branches: 2 is the
# double buffer (step n+1 is enqueued before step n's flags or counts are
# fetched, so the host's work of an iteration rides under a step on the
# device) — deeper would only delay fault/EOS detection by more steps for
# no extra overlap.
_PIPELINE_DEPTH = 2


@dataclass
class SchedRequest:
    req_id: int
    prompt_ids: list[int]
    max_new_tokens: int
    # Per-request watchdog deadline in seconds from submission (0 =
    # none). Checked by the drive loop's watchdog
    # (``_expire_request_deadlines``) — pure host clock math; the
    # eviction itself rides the decode-fault surgery's EXISTING
    # sanctioned fetches, so the watchdog adds zero new sync points.
    deadline_s: float = 0.0
    # Causal-trace ids (obs/trace.py), carried by value from the debate
    # round that issued this request; every flight-recorder event the
    # batcher emits for it is stamped with them (explicitly where the
    # emit site knows the request, via the ambient scope elsewhere).
    trace_id: str = ""
    span_id: str = ""
    # Host-side streaming consumer (engine/streaming.py): called at the
    # drive loop's existing fetch points with ALL token ids this
    # request has emitted so far (np.ndarray); return False to cancel
    # the request mid-decode (``_cancel_slot``). None = the blocking
    # path, byte-identical to pre-streaming behavior.
    on_tokens: object = None


@dataclass
class _Admission:
    """An in-flight admission: its prompt prefills one chunk per scheduler
    iteration (interleaved with resident rows' decode chunks) instead of
    stalling decode for the whole prompt.

    Two coordinate systems coexist (per admission, chosen at start):

    - padded (prefix cache off): tokens left-padded to the bucket, the
      original layout; KV slot = pad + logical position.
    - canonical (prefix cache on): tokens at slot = logical position,
      pad 0, right-padded to the bucket. The canonical layout is what
      makes page content layout-independent and therefore shareable: a
      token's K/V depends only on its logical position, so a block
      cached by one admission drops into any later one.
    """

    slot: int
    req: SchedRequest
    seq_id: int
    tokens: object  # [1, S] device array
    pads: object  # [1]
    # 1-row dense cache being prefilled; None for a cached prompt admitted
    # over its pages (``paged_admission``): then ``tokens`` and ``pads``
    # are None too, and ``[pos, S_real)`` is the delta its one program runs.
    cache: object
    pos: int  # next chunk start
    S: int  # bucketed token-array length
    last_logits: object = None
    # Canonical-layout (prefix cache) bookkeeping:
    canonical: bool = False
    S_real: int = 0  # true prompt length (== S when padded)
    matched: int = 0  # tokens adopted from the cache (page multiple)
    prefill_end: int = 0  # prefill covers [pos0, prefill_end)
    prefill_s: float = 0.0  # this request's own prefill wall-clock
    # Set when a fused dispatch carrying this admission faulted: the
    # next chunk runs STANDALONE so a prefill-side error is attributed
    # to the admission (_abort_admission) instead of evicting another
    # resident every iteration; a decode-side fault already evicted its
    # slot, and fusion resumes after one clean standalone chunk.
    fuse_deferred: bool = False
    # Beside state-space layers: the snapshot the admission resumes from
    # (None: a sequence's start) and those its own chunks leave behind,
    # by boundary, to hang on the prompt's blocks at the handoff.
    state0: object = None
    snapshots: dict = field(default_factory=dict)

    @property
    def remaining(self) -> int:
        return self.prefill_end - self.pos


@dataclass
class _SpecStep:
    """A verify program in flight: what its retirement needs."""

    counts: object  # the step's stacked counts, still on the device
    slots: tuple  # (slot, ownership generation) of the rows live at dispatch
    rider: _Admission | None  # the admission whose prompt chunk rode it
    chunk_len: int
    ahead: bool  # enqueued while another verify step was in flight


@dataclass
class SchedResult:
    req_id: int
    tokens: np.ndarray  # generated ids (0 past the row's end)
    n_generated: int
    # Set when a fault evicted this request: ``tokens`` then holds the
    # PARTIAL decode up to the fault and ``fault_kind`` is the
    # resilience-taxonomy value (resilience/faults.py). None = clean.
    error: str | None = None
    fault_kind: str | None = None
    # Per-request perf split: prompt tokens served from the prefix cache
    # and the wall-clock this request's own admission prefill took (the
    # decode share is apportioned by the caller — engine/tpu.py).
    cached_tokens: int = 0
    prefill_time_s: float = 0.0
    # Per-request speculation telemetry: verify steps this row took part
    # in, eligible draft positions verified, and positions accepted
    # (acceptance rate = accepted / drafted). All zero with
    # --no-speculative.
    spec_steps: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # This request's own decode wall: each drive-loop step's decode
    # share splits evenly over the rows live at dispatch, so the slot
    # sums reproduce the batcher's decode_time_s counter. Together with
    # prefill_time_s it IS the request's service wall — the end wall of
    # its ``request`` trace span (tools/trace_view.py checks the sum).
    decode_time_s: float = 0.0
    # Submit -> admission start: the wait for a free slot (and for the
    # one admission in flight), before any of this request's prefill.
    queue_wait_s: float = 0.0
    # Streaming early-convergence cancellation (engine/streaming.py):
    # ``cancelled`` marks a CLEAN mid-decode stop requested by the
    # consumer (``tokens`` holds the partial transcript, no error);
    # ``tokens_saved`` is the budget remainder never decoded.
    cancelled: bool = False
    tokens_saved: int = 0
    # Echo of the request's causal-trace ids.
    trace_id: str = ""
    span_id: str = ""


def _next_chunk_len(remaining: int) -> int:
    """Largest power-of-two chunk ≤ min(remaining, ADMISSION_CHUNK).

    Keeps compiled prefill-chunk shapes to a small fixed set (powers of
    two up to ADMISSION_CHUNK) while letting the canonical path start at
    an arbitrary page-aligned offset — cache granularity stays one PAGE,
    not one admission chunk.
    """
    c = ADMISSION_CHUNK
    while c > remaining:
        c //= 2
    return max(c, 1)


def _decode_chunk_impl(
    params,
    cfg: ModelConfig,
    pool,
    page_table: jnp.ndarray,  # [B, Pmax] physical ids (0 = trash/unmapped)
    cur_tok: jnp.ndarray,  # [B]
    cur_len: jnp.ndarray,  # [B] prompt+emitted tokens so far
    pad_lens: jnp.ndarray,  # [B]
    n_emitted: jnp.ndarray,  # [B]
    max_new: jnp.ndarray,  # [B] per-row budget
    active: jnp.ndarray,  # [B] bool
    out_buf: jnp.ndarray,  # [B, cap]
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    chunk: int,
    greedy: bool,
    top_k: int,
    use_top_p: bool = True,
    use_pallas: bool = False,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
):
    """Up to ``chunk`` decode steps over whatever rows are active.

    This is THE paged decode loop — generate()'s round-synchronous paged
    path calls it too (with uniform initial state), and it is inlined
    into ``fused_prefill_decode_chunk`` — so the per-step write-page
    lookup, bounds, and sampling glue exist exactly once for the
    standalone and fused programs alike. ``scheduler_decode_chunk`` is
    this body jitted (with pool/out_buf donation); call the bare impl
    only from inside another traced program.
    """
    B = cur_tok.shape[0]
    page_size = pool["k"].shape[3]
    cap = out_buf.shape[1]
    rows = jnp.arange(B)

    def cond(state):
        i, active = state[0], state[6]
        return (i < chunk) & active.any()

    def body(state):
        i, cur, cur_len, n_emitted, pool, out_buf, active, key = state
        q_pos = cur_len - 1  # [B] logical slot of cur's KV
        write_page = jnp.where(
            active,
            page_table[rows, q_pos // page_size],
            TRASH_PAGE,
        )
        write_off = q_pos % page_size
        bounds = jnp.stack([pad_lens, q_pos + 1], axis=1).astype(jnp.int32)
        positions = (q_pos - pad_lens)[:, None]
        logits, pool, _ = forward_paged_decode(
            params,
            cfg,
            cur[:, None],
            positions,
            pool,
            page_table,
            write_page,
            write_off,
            bounds,
            q_pos,
            # a live row's one position stands; an idle row's state stays
            state_keep=(
                active.astype(jnp.int32) if cfg.ssm is not None else None
            ),
            use_pallas=use_pallas,
            use_pallas_matmul=use_pallas_matmul,
            pallas_interpret=pallas_interpret,
            mesh=mesh,
        )
        with jax.named_scope("sample"):
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
        nxt = jnp.where(active, nxt, 0)
        write_pos = jnp.minimum(n_emitted, cap - 1)
        out_buf = out_buf.at[rows, write_pos].set(
            jnp.where(active, nxt, out_buf[rows, write_pos])
        )
        n_emitted = n_emitted + active.astype(jnp.int32)
        cur_len = cur_len + active.astype(jnp.int32)
        done = (is_eos | (n_emitted >= max_new)) & active
        active = active & ~done
        return i + 1, nxt, cur_len, n_emitted, pool, out_buf, active, key

    state = (
        jnp.int32(0),
        cur_tok,
        cur_len,
        n_emitted,
        pool,
        out_buf,
        active,
        key,
    )
    _, cur, cur_len, n_emitted, pool, out_buf, active, _ = jax.lax.while_loop(
        cond, body, state
    )
    return pool, cur, cur_len, n_emitted, out_buf, active


# The public jitted entry point — the same body, not a hand-forwarded
# wrapper (a wrapper that forgot to thread a new kwarg would silently pin
# its default on one path only and break fused/standalone token parity).
scheduler_decode_chunk = partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "chunk",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas",
        "use_pallas_matmul",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("pool", "out_buf"),
)(_decode_chunk_impl)


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "chunk",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas",
        "use_pallas_matmul",
        "prefill_pallas_matmul",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("adm_cache", "pool", "out_buf"),
)
def fused_prefill_decode_chunk(
    params,
    cfg: ModelConfig,
    adm_tokens: jnp.ndarray,  # [1, Sc] the admission's next prompt chunk
    adm_pads: jnp.ndarray,  # [1]
    adm_cache,  # 1-row dense cache being prefilled
    adm_cache_index: jnp.ndarray,  # scalar: slot of the chunk's 1st token
    pool,
    page_table: jnp.ndarray,
    cur_tok: jnp.ndarray,
    cur_len: jnp.ndarray,
    pad_lens: jnp.ndarray,
    n_emitted: jnp.ndarray,
    max_new: jnp.ndarray,
    active: jnp.ndarray,
    out_buf: jnp.ndarray,
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    chunk: int,
    greedy: bool,
    top_k: int,
    use_top_p: bool = True,
    use_pallas: bool = False,
    use_pallas_matmul: bool = False,
    prefill_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
):
    """ONE device program per scheduler iteration: the in-flight
    admission's prompt chunk AND every resident row's decode chunk
    (Sarathi-style piggybacked chunked prefill).

    The two halves touch disjoint state — the admission prefills into
    its private 1-row dense cache while residents decode against the
    paged pool (the admission's pages are only written at handoff, in
    ``_finish_admission``) — so fusing them is pure overlap: the
    newcomer's prompt math rides in the same dispatch instead of
    stalling the batch behind a separate program + host sync, and XLA is
    free to schedule the independent subgraphs together. Each half is
    the SAME traced body as its standalone program
    (``_prefill_chunk_impl`` / ``_decode_chunk_impl``), so greedy tokens
    are byte-identical either way. On sharded meshes the decode half
    carries the ``mesh`` down into ``forward_paged_decode`` exactly as
    ``scheduler_decode_chunk`` does (the dp-sharded wrapper —
    ``sharded_scheduler_decode_chunk`` — stays decode-only: admissions
    are a single-device batcher concern today).
    """
    adm_cache, adm_logits = _prefill_chunk_impl(
        params, cfg, adm_tokens, adm_pads, adm_cache, adm_cache_index,
        use_pallas_matmul=prefill_pallas_matmul,
        pallas_interpret=pallas_interpret,
    )
    pool, cur, cur_len, n_emitted, out_buf, active = _decode_chunk_impl(
        params,
        cfg,
        pool,
        page_table,
        cur_tok,
        cur_len,
        pad_lens,
        n_emitted,
        max_new,
        active,
        out_buf,
        eos_ids,
        key,
        temperature,
        top_p,
        chunk=chunk,
        greedy=greedy,
        top_k=top_k,
        use_top_p=use_top_p,
        use_pallas=use_pallas,
        use_pallas_matmul=use_pallas_matmul,
        pallas_interpret=pallas_interpret,
        mesh=mesh,
    )
    return (
        adm_cache,
        adm_logits,
        pool,
        cur,
        cur_len,
        n_emitted,
        out_buf,
        active,
    )


# Rows a routed model's step appends to its ``counts`` (each a scalar
# broadcast over B): pairs / active experts over the emitted tokens'
# positions, the same over every position the program ran, and the
# busiest expert's pairs; all summed over layers.
N_ROUTING_COUNTS = 5
# Rows of a verify step's ``counts`` before its emitted tokens: n_allowed,
# n_acc, n_emit, active, cur_len.
N_STEP_COUNTS = 5


def _routing_counts(cfg: ModelConfig, routing, emitted_mask) -> jnp.ndarray:
    """int32 [N_ROUTING_COUNTS] of one program's routing [L, T, k]."""
    stats = lambda mask: jnp.sum(  # noqa: E731
        jax.vmap(lambda idx: moe_mod.routing_stats(idx, cfg.experts, mask))(
            routing
        ),
        axis=0,
    )
    emitted, every = stats(emitted_mask), stats(None)
    return jnp.stack(
        [emitted[0], emitted[1], every[0], every[1], every[2]]
    ).astype(jnp.int32)


def _spec_chunk_impl(
    params,
    cfg: ModelConfig,
    pool,
    page_table: jnp.ndarray,  # [B, Pmax] physical ids (0 = trash/unmapped)
    ctx_buf: jnp.ndarray,  # [B, C] prompt ++ emitted tokens (draft source)
    ctx_len: jnp.ndarray,  # [B] tokens valid in ctx_buf
    prev_tok: jnp.ndarray,  # [B] token before cur (bigram context)
    cur_tok: jnp.ndarray,  # [B]
    cur_len: jnp.ndarray,  # [B] prompt+emitted tokens so far
    pad_lens: jnp.ndarray,  # [B]
    n_emitted: jnp.ndarray,  # [B]
    max_new: jnp.ndarray,  # [B] per-row budget
    alloc_len: jnp.ndarray,  # [B] KV slots covered by allocated pages
    active: jnp.ndarray,  # [B] bool
    out_buf: jnp.ndarray,  # [B, cap]
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    gamma: int,
    greedy: bool,
    top_k: int,
    use_top_p: bool = True,
    use_pallas: bool = False,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
):
    """ONE speculative step over whatever rows are active: draft up to γ
    tokens per row from that row's own context (prompt + generated so
    far — prompt-lookup, engine/speculative.py's bigram rule), run ONE
    batched multi-position verification forward over the paged pool, and
    accept a prefix by rejection sampling against the true sampling
    distribution (``accept_spans`` — the dense path's accept math, so
    greedy output stays byte-identical to plain decode).

    The verification forward IS ``forward_paged_decode`` — called
    span-native (tokens [B, γ+1], each position carrying its own write
    target and attention bounds), so the verify program shares the
    decode chunk's traced body the way ``fused_prefill_decode_chunk``
    shares the prefill's, and the Pallas route rides the multi-position
    paged kernel (ops/pallas_paged.py:paged_decode_attention_mq — one
    pass over the row's pages for the whole span, where the pre-PR-17
    batch-axis flatten re-gathered the pool γ+1 times). In-span
    causality comes from the bounds: position i's window ends at its own
    slot, and every span position's K/V is scattered before attention in
    each layer, so position i sees exactly [pad, cur_len+i).

    Rollback discipline: draft position k writes its K/V at slot
    ``cur_len-1+k`` only when the host's page allocation covers it AND
    the row's output budget could commit it (``n_allowed``); everything
    else lands on the trash page. Rejected drafts leave stale K/V above
    the accepted prefix — never read, because the row's next write
    region starts exactly there — and the host releases any page that
    no longer backs a committed token (``PageAllocator.truncate``) after
    fetching the accept counts. Emits 1..γ+1 tokens per active row;
    rows that cannot fit a draft (budget tail, pages short) degrade to a
    plain single-token step inside the SAME program, so the compiled
    shape is one per draft width γ.

    Returns the updated row state plus ``counts`` [5 + γ+1, B]
    (n_allowed, n_acc, n_emit, active, cur_len, then the step's emitted
    tokens, position-major, zeros past ``n_emit``) — ONE stacked array so
    the drive loop's sanctioned accept fetch is a single host copy, and
    the host needs no other device array to deliver or finish a row while
    the next step runs. A routed family's routing rows follow.
    """
    B = cur_tok.shape[0]
    page_size = pool["k"].shape[3]
    cap = out_buf.shape[1]
    C = ctx_buf.shape[1]
    span = gamma + 1
    rows = jnp.arange(B)
    j = jnp.arange(span)[None, :]  # [1, span]

    # Per-row draft positions eligible to COMMIT this step: bounded by
    # the output budget (the bonus token always needs one slot) and by
    # the KV slots the host has pages for.
    n_allowed = jnp.clip(
        jnp.minimum(max_new - n_emitted - 1, alloc_len - cur_len),
        0,
        gamma,
    )
    n_allowed = jnp.where(active, n_allowed, 0)

    # --- Draft from the row's own context (most recent bigram match). ---
    draft = _draft(ctx_buf, prev_tok, cur_tok, ctx_len, gamma)  # [B, γ]
    toks = jnp.concatenate([cur_tok[:, None], draft], axis=1)  # [B, span]
    q_pos = (cur_len - 1)[:, None] + jnp.arange(span)[None, :]  # [B, span]
    # Position 0 is cur (its slot is always covered: alloc_len ≥
    # cur_len); draft position k commits only while k ≤ n_allowed.
    writable = active[:, None] & (j <= n_allowed[:, None])
    safe_q = jnp.minimum(q_pos, page_table.shape[1] * page_size - 1)
    write_page = jnp.where(
        writable,
        page_table[rows[:, None], safe_q // page_size],
        TRASH_PAGE,
    )
    write_off = safe_q % page_size
    bounds = jnp.stack(
        [jnp.broadcast_to(pad_lens[:, None], q_pos.shape), q_pos + 1],
        axis=-1,
    ).astype(jnp.int32)  # [B, span, 2]
    positions = q_pos - pad_lens[:, None]

    # --- Verify: the paged forward, span-native ([B, γ+1] positions). ---
    logits, pool, routing = forward_paged_decode(
        params,
        cfg,
        toks,
        positions,
        pool,
        page_table,
        write_page,
        write_off,
        bounds,
        q_pos,
        use_pallas=use_pallas,
        use_pallas_matmul=use_pallas_matmul,
        pallas_interpret=pallas_interpret,
        mesh=mesh,
    )

    # --- Accept by rejection sampling against the true distribution. ---
    with jax.named_scope("sample"):
        filt = filtered_logits(
            logits,
            greedy=greedy,
            top_k=top_k,
            temperature=temperature,
            top_p=top_p,
            use_top_p=use_top_p,
        )  # [B, span, V]
        probs = jax.nn.softmax(filt, axis=-1)
        key, u_key, res_key = jax.random.split(key, 3)
        n_acc, bonus = accept_spans(
            probs, draft, n_allowed, u_key, res_key, greedy=greedy
        )
    emitted = jnp.concatenate(
        [draft, jnp.zeros((B, 1), draft.dtype)], axis=1
    )
    emitted = emitted.at[rows, n_acc].set(bonus)

    # --- EOS + per-row emit counts (EOS kept, zeros after). ---
    is_eos = (emitted[..., None] == eos_ids[None, None, :]).any(-1)
    eos_hits = is_eos & (j <= n_acc[:, None])
    any_eos = eos_hits.any(axis=1)
    first_eos = jnp.argmax(eos_hits, axis=1)
    n_emit = jnp.where(any_eos, first_eos + 1, n_acc + 1)
    n_emit = jnp.where(active, n_emit, 0)
    emitted = jnp.where(j < n_emit[:, None], emitted, 0)
    if cfg.ssm is not None:
        # State rollback: span position j fed the recurrent state token j
        # of [cur, drafts]; the row's next step starts at the LAST emitted
        # token, so exactly the first n_emit positions stand (cur and the
        # accepted drafts before the new cur). The state was read, not
        # written, by the forward above; it is advanced over those
        # positions here and never holds a rejected draft.
        pool = commit_span(
            cfg, pool, n_emit,
            use_pallas=use_pallas and (mesh is None or mesh.size == 1),
            pallas_interpret=pallas_interpret,
        )

    def append(buf, start_raw, width):
        """Write ``emitted[:n_emit]`` at per-row ``start_raw``, masked so
        every other slot keeps its current value (a clamped window near
        the buffer end must never smash earlier tokens)."""
        w_start = jnp.minimum(start_raw, width - span)
        d = start_raw - w_start  # [B] ≥ 0 in-window shift
        src = jnp.take_along_axis(
            emitted, jnp.clip(j - d[:, None], 0, span - 1), axis=1
        )
        current = _rowwise_slice(buf, w_start, span)
        mask = (
            active[:, None]
            & (j >= d[:, None])
            & (j < (d + n_emit)[:, None])
        )
        return _rowwise_write(buf, jnp.where(mask, src, current), w_start)

    out_buf = append(out_buf, jnp.minimum(n_emitted, cap - 1), cap)
    ctx_buf = append(ctx_buf, jnp.minimum(ctx_len, C - 1), C)

    new_cur = jnp.where(
        active, emitted[rows, jnp.maximum(n_emit - 1, 0)], cur_tok
    )
    new_prev = jnp.where(
        active,
        jnp.where(
            n_emit >= 2, emitted[rows, jnp.maximum(n_emit - 2, 0)], cur_tok
        ),
        prev_tok,
    )
    n_emitted = n_emitted + n_emit
    cur_len = cur_len + n_emit
    ctx_len = ctx_len + n_emit
    done = (any_eos | (n_emitted >= max_new)) & active
    active = active & ~done
    counts = jnp.concatenate(
        [
            jnp.stack(
                [n_allowed, n_acc, n_emit, active.astype(jnp.int32), cur_len]
            ),
            emitted.T.astype(jnp.int32),
        ]
    )
    if routing is not None:
        # Routed layers: the step's routing counts ride the same fetch,
        # one scalar a row of ``counts`` (broadcast over B). Span position
        # j of a row fed an emitted token iff j < n_emit.
        counts = jnp.concatenate(
            [
                counts,
                jnp.broadcast_to(
                    _routing_counts(
                        cfg, routing, (j < n_emit[:, None]).reshape(-1)
                    )[:, None],
                    (N_ROUTING_COUNTS, B),
                ),
            ]
        )
    return (
        pool,
        ctx_buf,
        ctx_len,
        new_prev,
        new_cur,
        cur_len,
        n_emitted,
        out_buf,
        active,
        counts,
    )


# The jitted verify program — the same body, not a hand-forwarded
# wrapper (the scheduler_decode_chunk convention: a wrapper that forgot
# to thread a kwarg would silently pin its default on one path only).
scheduler_spec_chunk = partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "gamma",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas",
        "use_pallas_matmul",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("pool", "out_buf", "ctx_buf"),
)(_spec_chunk_impl)


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "gamma",
        "greedy",
        "top_k",
        "use_top_p",
        "use_pallas",
        "use_pallas_matmul",
        "prefill_pallas_matmul",
        "pallas_interpret",
        "mesh",
    ),
    donate_argnames=("adm_cache", "pool", "out_buf", "ctx_buf"),
)
def fused_prefill_spec_chunk(
    params,
    cfg: ModelConfig,
    adm_tokens: jnp.ndarray,  # [1, Sc] the admission's next prompt chunk
    adm_pads: jnp.ndarray,  # [1]
    adm_cache,  # 1-row dense cache being prefilled
    adm_cache_index: jnp.ndarray,  # scalar: slot of the chunk's 1st token
    pool,
    page_table: jnp.ndarray,
    ctx_buf: jnp.ndarray,
    ctx_len: jnp.ndarray,
    prev_tok: jnp.ndarray,
    cur_tok: jnp.ndarray,
    cur_len: jnp.ndarray,
    pad_lens: jnp.ndarray,
    n_emitted: jnp.ndarray,
    max_new: jnp.ndarray,
    alloc_len: jnp.ndarray,
    active: jnp.ndarray,
    out_buf: jnp.ndarray,
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    *,
    gamma: int,
    greedy: bool,
    top_k: int,
    use_top_p: bool = True,
    use_pallas: bool = False,
    use_pallas_matmul: bool = False,
    prefill_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
    mesh=None,
):
    """``fused_prefill_decode_chunk``'s speculative sibling: the
    in-flight admission's prompt chunk AND every resident row's
    draft+verify step in ONE device program — a speculating slot rides
    the same dispatch as an in-flight admission, so turning speculation
    on never un-fuses chunked-prefill piggybacking. Each half is the
    SAME traced body as its standalone program (``_prefill_chunk_impl``
    / ``_spec_chunk_impl``), so greedy tokens are byte-identical either
    way."""
    adm_cache, adm_logits = _prefill_chunk_impl(
        params, cfg, adm_tokens, adm_pads, adm_cache, adm_cache_index,
        use_pallas_matmul=prefill_pallas_matmul,
        pallas_interpret=pallas_interpret,
    )
    (
        pool,
        ctx_buf,
        ctx_len,
        prev_tok,
        cur_tok,
        cur_len,
        n_emitted,
        out_buf,
        active,
        counts,
    ) = _spec_chunk_impl(
        params,
        cfg,
        pool,
        page_table,
        ctx_buf,
        ctx_len,
        prev_tok,
        cur_tok,
        cur_len,
        pad_lens,
        n_emitted,
        max_new,
        alloc_len,
        active,
        out_buf,
        eos_ids,
        key,
        temperature,
        top_p,
        gamma=gamma,
        greedy=greedy,
        top_k=top_k,
        use_top_p=use_top_p,
        use_pallas=use_pallas,
        use_pallas_matmul=use_pallas_matmul,
        pallas_interpret=pallas_interpret,
        mesh=mesh,
    )
    return (
        adm_cache,
        adm_logits,
        pool,
        ctx_buf,
        ctx_len,
        prev_tok,
        cur_tok,
        cur_len,
        n_emitted,
        out_buf,
        active,
        counts,
    )


# Names of the small per-row arrays a handoff writes one slot of; the
# batcher holds each as an attribute of the same name.
_ROW_STATE = (
    "page_table", "cur_tok", "cur_len", "pad_lens", "n_emitted", "max_new",
    "active", "ctx_len", "prev_tok",
)
# An admission over its pages pads its delta to one of these widths: one
# compiled program each. 64 serves an opponent that adopts its sibling's
# blocks (the match is clamped to whole pages short of the last token:
# 1-64 tokens at the serving page size), 128 a round's first opponent
# (the round number changes a block near the prompt's end); the widest
# is the most ``_admit`` finishes at once.
_SPAN_WIDTHS = (64, 128, 256, ADMISSION_CHUNK)


def _span_width(n_tokens: int) -> int:
    return next(w for w in _SPAN_WIDTHS if w >= n_tokens)


def _activate_slot_impl(
    rows: dict,  # the ``_ROW_STATE`` arrays, [B, ...] each
    out_buf: jnp.ndarray,  # [B, cap]
    ctx_buf,  # [B, C], or None without speculation
    slot: jnp.ndarray,  # scalar int32
    first: jnp.ndarray,  # scalar int32: the admission's sampled token
    row_table: jnp.ndarray,  # [Pmax] physical page ids (0 = unmapped)
    row_len: jnp.ndarray,  # scalar: KV slots the prompt holds
    pad: jnp.ndarray,  # scalar: left pad (0 in the canonical layout)
    max_new: jnp.ndarray,  # scalar: the request's budget
    eos_ids: jnp.ndarray,
    ctx_row,  # [C] the REAL prompt ids, zero past them; None as ctx_buf
    n_ctx,  # scalar: how many
    prev,  # scalar: the prompt's last token (bigram context)
):
    """The handoff's device half: slot ``slot`` of every per-row array
    takes its new owner, in one program (traced into the paged
    admission's; jitted alone as ``activate_slot`` for the dense
    handoff). ``first`` is already in ``out_buf`` and, under
    speculation, behind the prompt in the draft source; the row is live
    unless ``first`` ended it or used up its budget."""
    rows = dict(rows)
    live = (max_new > 1) & ~(first == eos_ids).any()
    for name, value in (
        ("page_table", row_table),
        ("cur_tok", first),
        ("cur_len", row_len + 1),
        ("pad_lens", pad),
        ("n_emitted", 1),
        ("max_new", max_new),
        ("active", live),
    ):
        rows[name] = rows[name].at[slot].set(value)
    out_buf = out_buf.at[slot].set(
        jnp.zeros_like(out_buf[0]).at[0].set(first)
    )
    if ctx_buf is not None:
        ctx_buf = ctx_buf.at[slot].set(ctx_row.at[n_ctx].set(first))
        rows["ctx_len"] = rows["ctx_len"].at[slot].set(n_ctx + 1)
        rows["prev_tok"] = rows["prev_tok"].at[slot].set(prev)
    return rows, out_buf, ctx_buf


activate_slot = partial(jax.jit, donate_argnames=("out_buf", "ctx_buf"))(
    _activate_slot_impl
)


def _write_state_row_impl(pool, slot, state):
    """The pool with state row ``slot`` set to ``state`` ({"ssm", "conv"},
    each [layers, ...] or [layers, 1, ...]): how a sequence's recurrent
    state reaches its slot, from a prefix block's snapshot or from the
    dense cache a cold prefill carried it in."""
    return {
        **pool,
        **{
            k: pool[k].at[:, slot].set(
                state[k].reshape(pool[k].shape[:1] + pool[k].shape[2:])
            )
            for k in STATE_LEAVES
        },
    }


write_state_row = partial(jax.jit, donate_argnames=("pool",))(
    _write_state_row_impl
)


@jax.jit
def snapshot_state(cache):
    """A copy of a 1-row dense cache's recurrent state, [layers, ...]: the
    cache itself is donated to the next chunk."""
    return {k: cache[k][:, 0] for k in STATE_LEAVES}


def _write_span_kv(pool, layer, new_kv, page_ids, offsets, **how):
    """A layer's K/V of an admission's span into their pages, through the
    function every admission's K/V reach the pool by (``write_tokens``:
    where the benchmark plants its ``state_unchanged`` fault); ``how``:
    ``forward_paged_decode``'s ``use_pallas`` and ``interpret``."""
    return write_tokens(
        pool, new_kv["k"], new_kv["v"], page_ids, offsets,
        ks_new=new_kv.get("ks"), vs_new=new_kv.get("vs"), layer=layer, **how,
    )


def _paged_admission_impl(
    params,
    cfg: ModelConfig,
    pool,
    rows: dict,
    out_buf: jnp.ndarray,
    ctx_buf,
    tokens: jnp.ndarray,  # [1, W] the delta's tokens, zero past ``n_real``
    start: jnp.ndarray,  # scalar: the delta's first position (= KV slot)
    n_real: jnp.ndarray,  # scalar: 1..W tokens of the delta
    row_table: jnp.ndarray,  # [Pmax] the sequence's pages, physical ids
    slot: jnp.ndarray,
    max_new: jnp.ndarray,
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    ctx_row,
    n_ctx,
    prev,
    state0=None,  # {"ssm", "conv"}: the recurrent state at ``start`` (a
    # prefix block's snapshot), beside state-space layers
    *,
    greedy: bool,
    top_k: int,
    table_pages: int,
    use_top_p: bool = True,
    use_pallas: bool = False,
    use_pallas_matmul: bool = False,
    pallas_interpret: bool = False,
):
    """A cached prompt's whole admission, over the pages it adopted: the
    delta ``[start, start + n_real)`` runs through ``forward_paged_decode``
    as a span of one row — the verify step's function, so its K/V land in
    the sequence's own pages (an adopted page is never a write target:
    the delta starts past the last of them) and its attention reads the
    prefix where it lies, through the page table — the first token is
    sampled from the last REAL position's logits, and the slot's rows
    take their owner (``_activate_slot_impl``). No dense cache, nothing
    gathered out of the pool, one program.

    Positions past ``n_real`` repeat the last real one with token 0 and
    write to the trash page: finite, never read. The span attends
    through the first ``table_pages`` entries of the row's table: all of
    them where a kernel walks the row's live pages, the prompt's bucket
    where the gather path would densify the table's whole width."""
    W = tokens.shape[1]
    page_size = pool["k"].shape[3]
    j = jnp.arange(W)
    q_pos = (start + jnp.minimum(j, n_real - 1))[None, :]  # [1, W]
    write_page = jnp.where(
        j < n_real, row_table[q_pos // page_size], TRASH_PAGE
    )
    bounds = jnp.stack([jnp.zeros_like(q_pos), q_pos + 1], axis=-1)
    state_kw = {}
    if cfg.ssm is not None:
        # The slot's state row starts from the snapshot at ``start`` and
        # ends after the delta's ``n_real`` positions, the pads not counted.
        pool = _write_state_row_impl(pool, slot, state0)
        state_kw = dict(state_rows=slot[None], state_keep=n_real[None])
    logits, pool, _ = forward_paged_decode(
        params,
        cfg,
        tokens,
        q_pos,  # canonical layout: rope position = KV slot
        pool,
        row_table[None, :table_pages],
        write_page,
        q_pos % page_size,
        bounds.astype(jnp.int32),
        q_pos,
        logits_at=(n_real - 1)[None],
        write_kv=_write_span_kv,
        **state_kw,
        use_pallas=use_pallas,
        use_pallas_matmul=use_pallas_matmul,
        pallas_interpret=pallas_interpret,
    )
    with jax.named_scope("sample"):
        first = sample_tokens(
            logits[:, 0],
            key,
            greedy=greedy,
            top_k=top_k,
            temperature=temperature,
            top_p=top_p,
            use_top_p=use_top_p,
        )[0]
    rows, out_buf, ctx_buf = _activate_slot_impl(
        rows, out_buf, ctx_buf, slot, first, row_table, start + n_real,
        jnp.int32(0), max_new, eos_ids, ctx_row, n_ctx, prev,
    )
    return pool, rows, out_buf, ctx_buf, first


# Its own name in a trace (``jit__paged_admission_impl``): no verify step,
# no prefill chunk.
paged_admission = partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "greedy",
        "top_k",
        "table_pages",
        "use_top_p",
        "use_pallas",
        "use_pallas_matmul",
        "pallas_interpret",
    ),
    donate_argnames=("pool", "out_buf", "ctx_buf"),
)(_paged_admission_impl)


def sharded_scheduler_decode_chunk(
    mesh,
    params,
    cfg: ModelConfig,
    pool,
    page_table: jnp.ndarray,  # [B, Pmax] DEVICE-LOCAL physical ids
    cur_tok: jnp.ndarray,
    cur_len: jnp.ndarray,
    pad_lens: jnp.ndarray,
    n_emitted: jnp.ndarray,
    max_new: jnp.ndarray,
    active: jnp.ndarray,
    out_buf: jnp.ndarray,
    eos_ids: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    **static_kw,
):
    """``scheduler_decode_chunk`` over a dp-sharded mesh.

    Paged decode scales over ``dp`` with ZERO cross-device page traffic:
    each device owns a slice of the page pool (pool axis 1 split over dp)
    holding its rows' pages plus its own trash page 0, and the page
    tables carry device-LOCAL physical ids (the caller lays pages out
    per-device — generate()'s paged setup). shard_map then runs the
    whole chunk loop independently per device; devices even early-exit
    their while_loops at different trip counts. tp/sp stay unsupported
    for paged (the kernel grid would need head sharding — dense decode
    covers those configs).

    Sampling keys are folded with the device index so rows on different
    devices draw independent randomness.
    """
    from jax.sharding import PartitionSpec as P

    from adversarial_spec_tpu.parallel.mesh import DP

    rows = P(DP)
    pool_spec = jax.tree.map(lambda _: P(None, DP), pool)

    def local_chunk(
        params_l,
        pool_l,
        table_l,
        cur_l,
        len_l,
        pads_l,
        nem_l,
        maxn_l,
        act_l,
        out_l,
        eos_l,
        key_l,
        temp_l,
        tp_l,
    ):
        key_l = jax.random.fold_in(key_l, jax.lax.axis_index(DP))
        return scheduler_decode_chunk(
            params_l,
            cfg,
            pool_l,
            table_l,
            cur_l,
            len_l,
            pads_l,
            nem_l,
            maxn_l,
            act_l,
            out_l,
            eos_l,
            key_l,
            temp_l,
            tp_l,
            **static_kw,
        )

    return jax.shard_map(
        local_chunk,
        mesh=mesh,
        in_specs=(
            P(),  # params replicated (dp-only gate: tp == 1)
            pool_spec,
            rows,  # page_table [B, Pmax]
            rows,
            rows,
            rows,
            rows,
            rows,
            rows,
            rows,  # out_buf [B, cap]
            P(),
            P(),
            P(),
            P(),
        ),
        out_specs=(pool_spec, rows, rows, rows, rows, rows),
        check_vma=False,
    )(
        params,
        pool,
        page_table,
        cur_tok,
        cur_len,
        pad_lens,
        n_emitted,
        max_new,
        active,
        out_buf,
        eos_ids,
        key,
        temperature,
        top_p,
    )


class ContinuousBatcher:
    """Admits requests into decode slots over one shared model + pool."""

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        *,
        max_batch: int = 4,
        page_size: int = 64,
        capacity_tokens: int = 16384,
        max_new_cap: int = 1024,
        eos_ids: list[int] | None = None,
        greedy: bool = True,
        temperature: float = 0.7,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        chunk: int = 32,
        kv_dtype: str = "",
        prefix_cache: bool | None = None,
        step_tokens: int = 0,
        speculative: bool | None = None,
        gamma: int | None = None,
        use_pallas_matmul: bool | None = None,
    ):
        self.params = params
        self.cfg = cfg
        self.B = max_batch
        # Replicated sharding of the params' mesh (None when params are
        # not mesh-sharded, e.g. direct CPU tests). Fresh admission
        # caches are committed to it at creation: an UNCOMMITTED fresh
        # cache and chunk 1's committed output otherwise present two jit
        # signatures for the same chunk length and XLA compiles the
        # whole prefill program twice — a genuine double compile the
        # retrace watch flagged on the first paged CLI drive.
        leaf = jax.tree_util.tree_leaves(params)[0]
        sh = getattr(leaf, "sharding", None)
        self._replicated = (
            jax.sharding.NamedSharding(sh.mesh, jax.sharding.PartitionSpec())
            if isinstance(sh, jax.sharding.NamedSharding)
            else None
        )
        self.page_size = page_size
        self.chunk = chunk
        self.kv_dtype = kv_dtype
        # ``step_tokens`` is the Sarathi-style shared per-step token
        # budget: a fused step's prompt chunk shrinks so
        # chunk_len + n_live·chunk stays under it. 0 = auto
        # (ADMISSION_CHUNK + max_batch·chunk — full-size prompt chunks
        # even with every slot decoding).
        self.step_tokens = step_tokens or (
            ADMISSION_CHUNK + max_batch * chunk
        )
        # Per-slot prompt-lookup speculation (None = process config,
        # engine/spec.py): each decode step drafts up to γ tokens per
        # resident row from that row's own context and verifies them in
        # ONE multi-position forward (_spec_chunk_impl). γ is validated
        # at the knob (spec.configure / env read), so any value that
        # reaches here is ≥ 1.
        cfg_sp = spec_mod.config()
        self.speculative = (
            cfg_sp.enabled if speculative is None else bool(speculative)
        )
        self.gamma = self._clamp_gamma(
            cfg_sp.gamma if gamma is None else int(gamma), max_new_cap
        )
        self.greedy = greedy
        self.top_k = top_k
        self._temp = jnp.float32(temperature)
        self._top_p = jnp.float32(top_p)
        self._eos = jnp.asarray(
            sorted(set(eos_ids or [])) or [-1], jnp.int32
        )
        self._eos_np = np.asarray(sorted(set(eos_ids or [])) or [-1])
        self._use_top_p = float(top_p) < 1.0
        self._key = jax.random.key(seed)

        if cfg.ssm is not None:
            if kv_dtype:
                refuse_beside_state_space(cfg, f"{kv_dtype} KV pages")
            if quant.has_quantized_weights(params):
                refuse_beside_state_space(cfg, "int8 / int4 weights")
            if self._replicated is not None and self._replicated.mesh.size > 1:
                refuse_beside_state_space(cfg, "a mesh of more than one device")
        if cfg.gated is not None:
            serves = (
                "the ContinuousBatcher serves it on one device with paged "
                "KV in the model dtype"
            )
            if kv_dtype:
                # int8 pages' scale pages keep the grid kernel, which no
                # test or chip run has put under per-layer windows
                refuse_unwired(cfg, f"{kv_dtype} KV pages", serves)
            if self._replicated is not None and self._replicated.mesh.size > 1:
                refuse_unwired(cfg, "a mesh of more than one device", serves)
        # The windowed layers' windows, one entry a layer (the host's
        # counters of what their bounds leave unread and unreadable).
        self._windows = tuple(w for w in cfg.layer_windows if w)
        n_pages = -(-capacity_tokens // page_size)
        # Physical page 0 is the trash page; allocator ids shift +1.
        self.allocator = PageAllocator(n_pages, page_size)
        # Cross-round prefix KV cache over this pool (None = disabled).
        # The batcher OWNS the cache: its lifetime is the pool's, so a
        # batcher kept alive across rounds (engine/tpu.py) carries round
        # R's spec+transcript blocks into round R+1's admissions.
        if prefix_cache is None:
            prefix_cache = prefix_mod.config().enabled
        self.prefix_cache = (
            prefix_mod.PrefixCache(
                self.allocator,
                page_size,
                max_pages=prefix_mod.config().max_pages,
            )
            if prefix_cache
            else None
        )
        kv_heads, k_dim, v_dim = cfg.kv_layout
        # Pages for the layers that cache keys and values (all of them but
        # beside state-space layers, which keep a state row a slot instead).
        layout = PagedCacheLayout(
            n_pages=n_pages + 1,
            page_size=page_size,
            n_layers=cfg.n_kv_layers,
            n_kv_heads=kv_heads,
            head_dim=k_dim,
            v_dim=v_dim,
        )
        self._dtype = jax.tree.leaves(params)[0].dtype
        self.pool = init_page_pool(
            layout, dtype=self._dtype, kv_dtype=kv_dtype
        )
        # The second kind of per-sequence state: a row of the recurrent
        # state a slot, in the pool's dict so that every step program
        # carries, donates and returns it with the pages. ``_state_owner``
        # is the host's record of which sequence a row belongs to.
        self._state_owner: list[int | None] = [None] * max_batch
        self._snapshot_bytes = 0
        if cfg.ssm is not None:
            self.pool.update(
                self._commit(init_recurrent_state(cfg, max_batch, self._dtype))
            )
            self._snapshot_bytes = sum(
                v.nbytes // max_batch
                for k, v in self.pool.items()
                if k in STATE_LEAVES
            )
            if self.prefix_cache is not None:
                self.prefix_cache.state_budget = self._snapshot_budget(
                    n_pages * page_size
                )
        # Tiered KV (engine/kvtier.py): host-RAM demotion of LRU-evicted
        # prefix blocks + the persistent content-addressed disk store,
        # both below this pool. The host budget is denominated in real
        # page bytes; the store is namespaced by a model/config/layout
        # fingerprint so incompatible KV can never rehydrate. None when
        # tiering (or the prefix cache) is off.
        self.tiers = None
        if self.prefix_cache is not None and kvtier_mod.armed():
            kv_bytes = (
                1 if kv_dtype == "int8" else np.dtype(self._dtype).itemsize
            )
            block_bytes = (
                cfg.n_kv_layers * kv_heads * page_size * (k_dim + v_dim)
            ) * kv_bytes
            if kv_dtype == "int8":  # per-(token, head) f32 scale pages
                block_bytes += cfg.n_kv_layers * kv_heads * page_size * 4 * 2
            self.tiers = kvtier_mod.build_for(
                block_bytes,
                (cfg, page_size, kv_dtype, self._dtype),
            )
            if self.tiers is not None:
                self.prefix_cache.attach_tiers(
                    self.tiers, kv_fetch=self._fetch_page_kv
                )
        self._demotion_warm = False
        self.max_pages_per_seq = -(-(cfg.max_seq_len) // page_size)
        # Fused paged kernel on real TPUs; gather path elsewhere.
        self._use_pallas = jax.default_backend() == "tpu"
        self._pallas_interpret = jax.default_backend() == "cpu"
        # Fused dequant-matmul (ops/pallas_quant.py) whenever the params
        # actually carry quantized leaves: on real TPUs by default, or
        # opted in anywhere via ``use_pallas_matmul`` (CPU runs the same
        # kernels under interpret mode — the parity harness). A
        # full-precision checkpoint never routes through the kernels.
        if use_pallas_matmul is None:
            use_pallas_matmul = jax.default_backend() == "tpu"
        self._use_pallas_matmul = bool(use_pallas_matmul) and (
            quant.has_quantized_weights(params)
        )
        # The admission prefill (``forward``) of a dense family keeps
        # XLA's dequant-matmul, as its cells were measured. Expert stacks
        # have no XLA path fit for a model's size (every row tile would
        # gather a whole expert matrix out of the stack), so a routed
        # family's prefill takes the kernels as its decode does.
        self._prefill_pallas_matmul = (
            self._use_pallas_matmul and cfg.ffn_kind == "routed"
        )
        # How the decode step reads its layer weights is fixed when it
        # is traced, so it is told once, here: the quantized stacks the
        # fused kernel reads by layer index instead of a slice's copy.
        # So is the block plan of each of them, and with it the smallest
        # weight block the step streams.
        if obs_mod.config().enabled:
            step_rows = self.B * (self.gamma + 1 if self.speculative else 1)
            obs_mod.hot.qmm_indexed_stacks.set(
                n_indexed_stacks(params, self._use_pallas_matmul, step_rows)
            )
            obs_mod.hot.qmm_min_block_bytes.set(
                min_weight_block_bytes(
                    params, self._use_pallas_matmul, step_rows
                )
            )

        B, cap = self.B, max_new_cap
        self.cap = cap
        # Persistent per-row device state is COMMITTED to the params'
        # replicated sharding at creation (``_commit``, no-op off-mesh)
        # for the same reason fresh admission caches are: these arrays
        # are program inputs on the very first dispatch and donated
        # outputs ever after — an uncommitted fresh array and a
        # mesh-committed step output present two jit signatures for the
        # same program, and XLA compiles it twice (the retrace watch
        # caught exactly this on the engine's first paged spec drive:
        # ctx_len/prev_tok/cur_len/n_emitted/active flipped
        # UnspecifiedValue → NamedSharding between step 1 and step 2).
        self.page_table = self._commit(
            jnp.zeros((B, self.max_pages_per_seq), jnp.int32)
        )
        self.cur_tok = self._commit(jnp.zeros((B,), jnp.int32))
        # ≥1 so q_pos ≥ 0
        self.cur_len = self._commit(jnp.ones((B,), jnp.int32))
        self.pad_lens = self._commit(jnp.zeros((B,), jnp.int32))
        self.n_emitted = self._commit(jnp.zeros((B,), jnp.int32))
        self.max_new = self._commit(jnp.zeros((B,), jnp.int32))
        self.active = self._commit(jnp.zeros((B,), bool))
        self.out_buf = self._commit(jnp.zeros((B, cap), jnp.int32))
        # Host-trailing view of ``active``: the drive loop dispatches
        # against this snapshot (updated at admission handoff, fault
        # eviction, and step N-1's async fetch) instead of syncing on the
        # in-flight device state. A stale True only costs one no-op
        # dispatch whose while_loop exits immediately; fetches only ever
        # DEACTIVATE slots, and only when the slot's OWNERSHIP GENERATION
        # still matches the one recorded at dispatch — a slot freed and
        # re-admitted while a step was in flight bumps the generation, so
        # the old step's "this row finished" flag can never truncate the
        # newcomer that now owns the slot.
        self._active_np = np.zeros((B,), bool)
        self._slot_gen = [0] * B
        # Speculation state. ctx_buf is the DRAFT SOURCE: each row's
        # real (unpadded) prompt ids followed by everything it has
        # emitted — the prompt-lookup bigram scan runs over it on
        # device. Sized to the model context: submit() guarantees
        # bucketed prompt + budget fits max_seq_len, so prompt+emitted
        # always fits too. cur_len/row_len/n_emitted host views trail
        # the device via the per-step counts fetch; the host needs them
        # to manage draft page coverage (extend before dispatch,
        # truncate after the accept counts land).
        self._ctx_cap = cfg.max_seq_len
        self.ctx_buf = self._commit(
            jnp.zeros((B, self._ctx_cap), jnp.int32)
        )
        self.ctx_len = self._commit(jnp.zeros((B,), jnp.int32))
        self.prev_tok = self._commit(jnp.zeros((B,), jnp.int32))
        self._cur_len_np = np.ones((B,), np.int64)
        self._row_len_np = np.zeros((B,), np.int64)
        self._max_new_np = np.zeros((B,), np.int64)
        # Each slot's emitted tokens as the host has them: the first from
        # the handoff's fetch, the rest from each verify step's counts. A
        # speculating row is delivered and finished from here, so neither
        # touches ``out_buf`` (donated to the step in flight).
        self._out_np = np.zeros((B, cap), np.int32)
        # The verify steps in flight, oldest first (``_SpecStep``); at
        # most ``_PIPELINE_DEPTH`` while one is being enqueued.
        self._pipe: deque[_SpecStep] = deque()
        # Wall clock up to which the drive loop has booked (or set aside)
        # its time: a retired step is booked from here to its retirement.
        self._step_mark = 0.0
        # Per-slot speculation telemetry [steps, drafted, accepted],
        # stamped onto SchedResult at completion/eviction.
        self._slot_spec: list[list[int]] = [[0, 0, 0] for _ in range(B)]

        self._slot_req: list[SchedRequest | None] = [None] * B
        self._slot_seq: list[int | None] = [None] * B
        # Streaming state (engine/streaming.py): the owner's consumer
        # callback and how many tokens it has been delivered so far —
        # deliveries happen at the drive loop's EXISTING fetch points
        # (no new sanctioned syncs), and a consumer returning False
        # triggers ``_cancel_slot``.
        self._slot_consumer: list = [None] * B
        self._slot_streamed: list[int] = [0] * B
        # Per-slot request telemetry, stamped at admission handoff.
        self._slot_cached: list[int] = [0] * B
        self._slot_prefill_s: list[float] = [0.0] * B
        self._slot_queue_s: list[float] = [0.0] * B
        # Per-slot causal-trace state: the owner's trace/span ids and
        # its accumulated decode wall (each step's decode share splits
        # evenly over the rows live at dispatch; the slot sums
        # reproduce decode_time_s).
        self._slot_trace: list[str] = [""] * B
        self._slot_span: list[str] = [""] * B
        self._slot_decode_s: list[float] = [0.0] * B
        # Host submit time per queued req_id: the 'queued' span's wall
        # (queue wait) measured at admission start.
        self._queued_t: dict[int, float] = {}
        # Per-request watchdog deadlines: req_id -> absolute monotonic
        # expiry, armed at submit for requests with ``deadline_s`` > 0.
        # The ABSOLUTE time survives a transient-fault requeue on
        # purpose — the watchdog bounds the request's total wall, not
        # its current residency. Entries clear when the request
        # finally resolves (finish/cancel/final fault/global timeout).
        self._deadline_t: dict[int, float] = {}
        self._admission: _Admission | None = None
        self._seq_counter = 0
        self.capacity_tokens = n_pages * page_size
        self.queue: list[SchedRequest] = []
        self.results: list[SchedResult] = []
        # req_ids that already consumed their one transient-fault requeue.
        # (Fault COUNTS live in the process-wide resilience.faults store —
        # one bookkeeping place, snapshotted by the CLI report.)
        self._retried: set[int] = set()
        # Wall-clock telemetry: admission prefills vs decode chunks.
        # decode_time_s feeds the engine's per-row usage attribution
        # (engine/tpu.py:_chat_continuous). Prefill time is split into
        # STALLED (the batch actually waited: standalone chunks with no
        # residents to overlap, and the admission-handoff scatter) vs
        # OVERLAPPED (the chunk rode inside a fused step while residents
        # decoded — hidden under compute). ``prefill_time_s`` is their
        # sum by construction; the same split feeds the process-wide
        # ``perf.interleave`` stats (engine/interleave.py).
        self.stalled_prefill_s = 0.0
        self.overlapped_prefill_s = 0.0
        self.decode_time_s = 0.0

    def _snapshot_budget(self, capacity_tokens: int) -> int:
        """Bytes the prefix blocks' state snapshots may hold together:
        one snapshot for every ADMISSION_CHUNK of the pool's capacity (a
        cold prefill leaves one at each chunk boundary, and the pool holds
        no more boundaries than that), and no more than half of what the
        device has free once the weights, the pool and the state rows are
        on it, where it says (the other half is the prefill's)."""
        budget = (capacity_tokens // ADMISSION_CHUNK) * self._snapshot_bytes
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        try:
            stats = next(iter(leaf.devices())).memory_stats() or {}
        except Exception:
            stats = {}
        if stats.get("bytes_limit"):
            free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            budget = min(budget, max(free // 2, 0))
        return budget

    def check_invariants(self) -> None:
        """The allocator's and the prefix cache's checks, and the state
        rows': a row is owned by exactly the sequence its slot holds, and
        by none while the slot is free."""
        self.allocator.check_invariants()
        if self.prefix_cache is not None:
            self.prefix_cache.check_invariants()
        if self.cfg.ssm is None:
            return
        for slot, owner in enumerate(self._state_owner):
            if owner != self._slot_seq[slot]:
                raise RuntimeError(
                    f"state row {slot} belongs to sequence {owner}, the "
                    f"slot to {self._slot_seq[slot]}"
                )

    @property
    def prefill_time_s(self) -> float:
        """Total admission-prefill wall clock. Exactly the sum of the
        stalled and overlapped buckets — there is no third place prefill
        time can accumulate (the invariant ``perf.interleave`` pins)."""
        return self.stalled_prefill_s + self.overlapped_prefill_s

    def _record_prefill_time(self, seconds: float, *, overlapped: bool) -> None:
        if overlapped:
            self.overlapped_prefill_s += seconds
        else:
            self.stalled_prefill_s += seconds
        interleave_mod.stats.record_prefill_time(
            seconds, overlapped=overlapped
        )

    def reconfigure_sampling(
        self,
        *,
        greedy: bool | None = None,
        temperature: float | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int | None = None,
    ) -> None:
        """Retune sampling between rounds on a REUSED batcher (the pool,
        allocator, and prefix cache survive; only sampling state moves).
        Pass ``seed`` to reseed the PRNG stream for the new round."""
        if greedy is not None:
            self.greedy = greedy
        if top_k is not None:
            self.top_k = top_k
        if temperature is not None:
            self._temp = jnp.float32(temperature)
        if top_p is not None:
            self._top_p = jnp.float32(top_p)
            self._use_top_p = float(top_p) < 1.0
        if seed is not None:
            self._key = jax.random.key(seed)

    def reconfigure_speculative(
        self, enabled: bool | None = None, gamma: int | None = None
    ) -> None:
        """Retune speculation between DRAINS on a reused batcher (CLI
        rounds re-resolve the process config each invocation; the
        engine's persistent batcher must follow it). Only legal while no
        rows are resident: the admission path's page-reservation
        discipline (full budget up front vs lazy per-verify-step)
        depends on the flag, so flipping it under a live row would break
        the row's coverage contract. ``run_all`` drains fully, so the
        engine's call-seam is always idle."""
        if any(self._active_np) or any(
            r is not None for r in self._slot_req
        ):
            raise RuntimeError(
                "reconfigure_speculative on a batcher with resident rows"
            )
        if enabled is not None:
            self.speculative = bool(enabled)
            if self.speculative:
                # Re-enabling must re-walk the γ-vs-cap clamp: the
                # constructor may have degraded this batcher to plain
                # decode (cap <= 1 with self.gamma left unclamped), and
                # skipping the clamp here would let a span wider than
                # the output buffer reach the compiled program.
                self.gamma = self._clamp_gamma(self.gamma, self.cap)
        if gamma is not None:
            # Same knob validation as engine/spec.py — a γ that reaches
            # the compiled program is always ≥ 1.
            self.gamma = self._clamp_gamma(
                spec_mod._validate_gamma(int(gamma)), self.cap
            )

    def _clamp_gamma(self, gamma: int, cap: int) -> int:
        """Bound γ so a step's full span (γ drafts + the bonus token)
        fits the per-row output buffer: the spec chunk's masked append
        window is ``span`` wide, so ``span > cap`` would push the write
        window start negative (dynamic-slice clamping would then smash
        tokens at the buffer head). A 1-token cap leaves nothing to
        draft for — degrade to plain decode rather than compile a
        0-wide verify."""
        if cap <= 1:
            self.speculative = False
            return gamma
        return max(1, min(gamma, cap - 1))

    # -- admission ---------------------------------------------------------

    def submit(self, req: SchedRequest) -> None:
        """Reject infeasible requests up front with actionable errors —
        anything accepted here is guaranteed schedulable once enough
        resident sequences finish."""
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.max_new_tokens > self.cap:
            raise ValueError(
                f"max_new_tokens {req.max_new_tokens} exceeds scheduler "
                f"cap {self.cap}"
            )
        total = bucket_length(len(req.prompt_ids)) + req.max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt (bucketed) + budget = {total} tokens exceeds the "
                f"model context {self.cfg.max_seq_len}"
            )
        # Pages back the REAL length under the prefix cache's canonical
        # layout, the left-padded bucket otherwise (_start_admission*).
        if self.prefix_cache is not None:
            total = len(req.prompt_ids) + req.max_new_tokens
        if total > self.capacity_tokens:
            raise ValueError(
                f"request needs {total} tokens but the pool holds only "
                f"{self.capacity_tokens}; raise capacity_tokens"
            )
        import time

        self.queue.append(req)
        self._queued_t[req.req_id] = time.monotonic()
        if req.deadline_s > 0:
            self._deadline_t[req.req_id] = time.monotonic() + req.deadline_s
        if obs_mod.config().enabled:
            obs_mod.emit(
                obs_mod.RequestEvent(
                    req_id=req.req_id,
                    state="queued",
                    tokens=len(req.prompt_ids),
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
            for name in ("request", "queued"):
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name=name,
                        phase="begin",
                        req_id=req.req_id,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )

    def _commit(self, cache: dict) -> dict:
        """Commit a freshly created admission cache to the params'
        replicated mesh sharding (see ``_replicated`` in __init__); a
        no-op off-mesh."""
        if self._replicated is None:
            return cache
        return jax.device_put(cache, self._replicated)

    def _start_admission(self, slot: int, req: SchedRequest) -> bool:
        """Reserve pages and set up the chunked prefill for ``slot``;
        False if the pool is momentarily full (the request stays queued
        and retries after residents free pages). Any other failure —
        including an injected ``kv_alloc`` fault — propagates with the
        allocator state rolled back; ``_admit`` isolates it to this
        request."""
        injector.fire("kv_alloc", slot)
        if self.prefix_cache is not None:
            return self._start_admission_cached(slot, req)
        tokens_np, pads_np = pad_batch([req.prompt_ids], pad_id=0)
        S = tokens_np.shape[1]
        # Speculative rows reserve only the prompt + the first decode
        # write slot; draft headroom (and committed growth) is allocated
        # lazily per verify step and rolled back past the accepted
        # prefix (_prepare_spec_step / _apply_spec_counts). Plain rows
        # keep the full up-front reservation: every admitted request is
        # guaranteed to decode to its budget without further allocation.
        total = S + (1 if self.speculative else req.max_new_tokens)
        seq_id = self._seq_counter
        self.allocator.new_sequence(seq_id)
        try:
            self.allocator.extend(seq_id, total)
            self._admission = _Admission(
                slot=slot,
                req=req,
                seq_id=seq_id,
                tokens=jnp.asarray(tokens_np),
                pads=jnp.asarray(pads_np),
                cache=self._commit(
                    init_cache(
                        self.cfg, 1, S,
                        dtype=self._dtype, kv_dtype=self.kv_dtype,
                    )
                ),
                pos=0,
                S=S,
                S_real=S,
                prefill_end=S,
            )
        except OutOfPages:
            self.allocator.free_sequence(seq_id)
            return False
        except Exception:
            self.allocator.free_sequence(seq_id)
            raise
        self._seq_counter += 1
        obs_mod.emit(
            obs_mod.RequestEvent(
                req_id=req.req_id, state="admitted", slot=slot, tokens=S
            )
        )
        self._emit_admitted_spans(req, slot)
        return True

    def _extend_evicting(self, seq_id: int, n_tokens: int) -> None:
        """``allocator.extend`` that converts allocation pressure into
        prefix-cache LRU eviction before giving up (the shared reclaim
        policy lives on PrefixCache — one implementation for the
        scheduler and the mock engine's accounting alike)."""
        if self.prefix_cache is None:
            self.allocator.extend(seq_id, n_tokens)
        else:
            self.prefix_cache.extend_evicting(seq_id, n_tokens)

    # -- tiered KV swaps ---------------------------------------------------

    def _fetch_page_kv(self, page: int, n_tokens: int):
        """Demotion fetch: gather one evicted block's KV off its pool
        page into an INDEPENDENT device array (the page returns to the
        free list right after and may be re-used by the very allocation
        that triggered the eviction), start the device→host copy async
        (the ``copy_to_host_async`` discipline — no sanctioned sync is
        added to the drive loop), and hand the tier a lazy materializer:
        by the time the host tier spills/promotes/settles, the copy has
        long resolved and the fetch is a free host read."""
        phys = np.full((1, n_tokens), page + 1, np.int32)
        offs = np.arange(n_tokens, dtype=np.int32)[None, :]
        demote_kv = read_tokens(self.pool, phys, offs)
        for v in demote_kv.values():
            try:
                v.copy_to_host_async()
            except Exception:
                pass  # optional fast path only

        def materialize() -> dict:
            # graftlint: disable=GL-SYNC -- demotion materializer: resolved lazily at spill/promotion/settle time, long after the async copy started at evict time landed — a free host read, not a drive-loop stall
            return {k: np.asarray(demote_kv[k]) for k in demote_kv}

        return materialize

    def _promote_tier_blocks(
        self, slot: int, seq_id: int, ids, matched: int, tier_hits: list
    ) -> int:
        """Promote a contiguous run of lower-tier blocks into this
        admission's freshly reserved pages: host→device ``device_put``
        + pool scatter per block, dispatched WITHOUT a host sync so the
        transfers overlap the admission's delta prefill chunks. Each
        target page is swap-pinned around its scatter (a fault
        mid-promotion must never leave an in-flight write against a
        freed page — ``PageAllocator.check_invariants`` enforces it).

        A hit whose entry vanished since lookup (host LRU overflow, a
        quarantined disk read — the promotion "lost the race") stops
        the run; the remaining tokens fall back to plain prefill, which
        is always correct. Returns the promoted token count; the
        promoted blocks are re-inserted into the radix index so
        co-admitted opponents share them immediately."""
        import time

        tiers = self.tiers
        ps = self.page_size
        consumed: list = []
        payloads: list[dict] = []
        t0 = time.monotonic()
        for hit in tier_hits:
            injector.fire("kv_swap", slot)
            ok, payload = tiers.materialize(hit)
            if not ok or payload is None:
                break  # lost the race: prefill recomputes from here
            consumed.append(hit)
            payloads.append(payload)
        if not consumed:
            return 0
        done = len(consumed) * ps
        table = self.allocator.table(seq_id)
        pages = [
            table[(matched + i * ps) // ps] for i in range(len(consumed))
        ]
        # ONE batched host→device transfer + pool scatter for the whole
        # promoted run: a per-block write_tokens would copy the full
        # pool per block on the eager path. Target pages stay
        # swap-pinned for the duration (a fault mid-scatter must never
        # leave an in-flight write against a freed page).
        phys = np.repeat(np.asarray(pages, np.int32) + 1, ps)[None, :]
        offs = np.tile(np.arange(ps, dtype=np.int32), len(consumed))[None, :]
        promo_kv = {
            k: jnp.asarray(np.concatenate([p[k] for p in payloads], axis=3))
            for k in payloads[0]
        }
        pinned: list[int] = []
        try:
            for page in pages:
                self.allocator.swap_pin(page)
                pinned.append(page)
            self.pool = write_tokens(
                self.pool,
                promo_kv["k"],
                promo_kv["v"],
                phys,
                offs,
                ks_new=promo_kv.get("ks"),
                vs_new=promo_kv.get("vs"),
            )
        finally:
            for page in pinned:
                self.allocator.swap_unpin(page)
        # Consume BEFORE the radix re-insert: insert's cap enforcement
        # may LRU-evict tail blocks straight back into the host tier,
        # and consuming afterwards would pop those freshly re-demoted
        # entries (emptying the tier the next admission needs).
        per = (time.monotonic() - t0) / len(consumed)
        for hit in consumed:
            tiers.consume(hit, slot=slot, wall_s=per)
        self.prefix_cache.insert(
            list(ids[: matched + done]),
            table[: (matched + done) // ps],
        )
        return done

    def _start_admission_cached(self, slot: int, req: SchedRequest) -> bool:
        """Prefix-cache admission: adopt the longest cached prefix and
        set up a CANONICAL-layout (pad 0, slot == logical position)
        prefill of only the remainder. The last prompt token is always
        re-run even on a full-prefix hit: its logits seed sampling.

        A hit whose remainder ``_admit`` finishes at once (at most one
        ADMISSION_CHUNK) is admitted over the pages it adopted: no dense
        cache, nothing read out of the pool; ``_finish_admission`` runs
        the delta, the first token and the slot's rows as one program
        (``paged_admission``). A miss, and a hit with a longer
        remainder, prefill into a dense cache in chunks that ride the
        residents' steps: the token array is right-padded to the usual
        power-of-two bucket (compiled shapes unchanged) but prefill only
        covers [matched, page_ceil(S_real)) — the bucket's garbage tail
        is never computed or attended (forward's causal mask stops at
        cache_index) — and a hit's prefix is gathered into that cache
        first.
        """
        ids = req.prompt_ids
        S_real = len(ids)
        ps = self.page_size
        # record=False: a pool-full deferral retries this whole method
        # every scheduler iteration — stats count once, on success, with
        # the clamped (actually adopted) match.
        with obs_mod.phase("admit.lookup"):
            if self.tiers is not None:
                matched, pages, tier_hits = self.prefix_cache.lookup_tiered(
                    ids, record=False
                )
            else:
                matched, pages = self.prefix_cache.lookup(ids, record=False)
                tier_hits = []
            # Keep at least the last token to prefill (logits source).
            limit = ((S_real - 1) // ps) * ps
            matched = min(matched, limit)
            radix_matched, state0 = None, None
            if self.cfg.ssm is not None:
                # Pages alone do not restore a prefix: the admission
                # resumes at the deepest boundary under the match whose
                # block still carries a snapshot of the recurrent state,
                # and recomputes the rest, K/V and state alike. A
                # host-tier block carries none, so nothing is promoted.
                radix_matched = matched
                matched, state0 = self.prefix_cache.lookup_state(
                    ids, matched
                )
                tier_hits = []
        pages = pages[: matched // ps]
        tier_hits = tier_hits[: (limit - matched) // ps]
        S = bucket_length(S_real)
        seq_id = self._seq_counter
        self.allocator.new_sequence(seq_id)
        try:
            if matched:
                self.allocator.adopt(seq_id, pages, matched)
            # Same lazy-reservation rule as the padded path: prompt + 1
            # under speculation, full budget otherwise.
            with obs_mod.phase("admit.evict"):
                self._extend_evicting(
                    seq_id,
                    (S_real - matched)
                    + (1 if self.speculative else req.max_new_tokens),
                )
            # Lower-tier blocks continuing the device match promote into
            # the pages the extend just reserved — async host→device
            # writes that overlap the delta prefill below; a hit that
            # lost the race degrades to prefill (chaos seam: kv_swap).
            promoted = 0
            if tier_hits:
                with obs_mod.phase("admit.promote"):
                    promoted = self._promote_tier_blocks(
                        slot, seq_id, ids, matched, tier_hits
                    )
            total = matched + promoted
            over_pages = 0 < total and S_real - total <= ADMISSION_CHUNK
            adm = _Admission(
                slot=slot,
                req=req,
                seq_id=seq_id,
                tokens=None,
                pads=None,
                cache=None,
                pos=total,
                S=S,
                canonical=True,
                S_real=S_real,
                matched=total,
                prefill_end=S_real,
                state0=state0,
            )
            if not over_pages:
                self._dense_admission_cache(adm)
            self._admission = adm
        except OutOfPages:
            self.allocator.free_sequence(seq_id)
            return False
        except Exception:
            self.allocator.free_sequence(seq_id)
            raise
        self._seq_counter += 1
        self.prefix_cache.stats.record_lookup(matched)
        self.prefix_cache.stats.record_admission(
            total, over_pages, matched=radix_matched
        )
        if self.tiers is not None:
            self.tiers.record_lookup(tier_hits)
        obs_mod.emit(
            obs_mod.RequestEvent(
                req_id=req.req_id,
                state="admitted",
                slot=slot,
                tokens=S_real,
                cached_tokens=total,
            )
        )
        self._emit_admitted_spans(req, slot)
        return True

    def _dense_admission_cache(self, adm: _Admission) -> None:
        """Give a canonical admission that prefills in chunks its dense
        cache and its bucketed token array; a matched prefix (one whose
        remainder is longer than ``_admit`` finishes at once) is gathered
        out of its pages into that cache, so that the chunks' attention
        sees it."""
        S, ps, total = adm.S, self.page_size, adm.pos
        tokens_np = np.zeros((1, S), np.int32)
        tokens_np[0, : adm.S_real] = np.asarray(adm.req.prompt_ids, np.int32)
        cache = self._commit(
            init_cache(
                self.cfg, 1, S, dtype=self._dtype, kv_dtype=self.kv_dtype
            )
        )
        if total:
            # The promoted blocks' scatter was dispatched before this
            # gather and queues ahead of it — no host sync.
            table = (
                np.asarray(
                    self.allocator.table(adm.seq_id)[: total // ps], np.int32
                )
                + 1
            )  # physical ids
            slots = np.arange(total, dtype=np.int32)[None, :]
            gathered = read_tokens(self.pool, table[slots // ps], slots % ps)
            for k in gathered:
                cache[k] = cache[k].at[:, :, :, :total, :].set(gathered[k])
            if adm.state0 is not None:
                for k, v in adm.state0.items():
                    cache[k] = v[:, None]
        adm.tokens = jnp.asarray(tokens_np)
        adm.pads = jnp.zeros((1,), jnp.int32)
        adm.cache = cache
        # A recurrent state consumes every position it is run over, so
        # beside state-space layers the chunks stop at the last real
        # token (a power-of-two tail) instead of the page's end: no
        # bucket garbage enters the state and no token is run twice.
        adm.prefill_end = (
            adm.S_real
            if self.cfg.ssm is not None
            else min(-(-adm.S_real // ps) * ps, S)
        )

    def _emit_admitted_spans(self, req: SchedRequest, slot: int) -> None:
        """Trace-span bookkeeping at admission start: the 'queued' span
        ends (wall = the measured queue wait) and the 'prefill' span
        opens. Called by both admission variants under the request's
        ambient scope (``_admit``)."""
        import time

        t0 = self._queued_t.pop(req.req_id, None)
        wait = (time.monotonic() - t0) if t0 is not None else 0.0
        self._slot_queue_s[slot] = wait
        if not obs_mod.config().enabled:
            return
        obs_mod.hot.batcher_queue_wait.observe(wait)
        obs_mod.emit(
            obs_mod.SpanEvent(
                name="queued",
                phase="end",
                req_id=req.req_id,
                slot=slot,
                wall_s=wait,
                trace_id=req.trace_id,
                span_id=req.span_id,
            )
        )
        obs_mod.emit(
            obs_mod.SpanEvent(
                name="prefill",
                phase="begin",
                req_id=req.req_id,
                slot=slot,
                trace_id=req.trace_id,
                span_id=req.span_id,
            )
        )

    def _advance_admission(self) -> None:
        """One STANDALONE prefill chunk of the in-flight admission —
        used when no resident row is decoding (nothing to fuse with), for
        an admission's final chunk and after a fused dispatch faulted.
        The fused path dispatches through
        ``_dispatch_fused`` instead, where the chunk rides the decode
        program and its time lands in the OVERLAPPED bucket."""
        import time

        adm = self._admission
        if adm.cache is None:
            # Admitted over its pages: the delta is part of the handoff's
            # one program.
            self._finish_admission()
            return
        with obs_mod.phase("prefill.chunk"):
            t0 = time.monotonic()
            chunk_len = _next_chunk_len(adm.remaining)
            adm.cache, adm.last_logits = prefill_chunk(
                self.params,
                self.cfg,
                adm.tokens[:, adm.pos : adm.pos + chunk_len],
                adm.pads,
                adm.cache,
                jnp.int32(adm.pos),
                use_pallas_matmul=self._prefill_pallas_matmul,
                pallas_interpret=self._pallas_interpret,
            )
            adm.pos += chunk_len
            self._keep_snapshot(adm, chunk_len)
            # Block before stamping: async dispatch would otherwise push this
            # chunk's device time into the NEXT decode chunk's blocked wait,
            # billing resident rows for the newcomer's prefill. A standalone
            # chunk is a genuine stall, so this sync is sanctioned (GL-SYNC
            # allowlists this method in [tool.graftlint]).
            jax.block_until_ready(adm.last_logits)
            elapsed = time.monotonic() - t0
            self._record_prefill_time(elapsed, overlapped=False)
            adm.prefill_s += elapsed
            interleave_mod.stats.record_step(fused=False, prefill_only=True)
            prefix_mod.stats.record_prefill(chunk_len, 0)
            if obs_mod.config().enabled:
                obs_mod.retrace.observe(
                    "prefill_chunk", ("prefill", chunk_len, adm.S),
                    fn=prefill_chunk,
                )
                obs_mod.hot.prefill_chunk.observe(elapsed)
                obs_mod.emit(
                    obs_mod.StepEvent(
                        kind="prefill",
                        n_live=int(sum(self._active_np)),
                        admission_slot=adm.slot,
                        prefill_tokens=chunk_len,
                    )
                )
                obs_mod.emit(
                    obs_mod.RequestEvent(
                        req_id=adm.req.req_id,
                        state="prefill",
                        slot=adm.slot,
                        tokens=chunk_len,
                    )
                )
        if adm.pos >= adm.prefill_end:
            self._finish_admission()

    def _keep_snapshot(self, adm: _Admission, chunk_len: int) -> None:
        """After a chunk of a chunked prefill that ends on a page boundary,
        keep a copy of the carried recurrent state: the boundaries a
        prefill reaches anyway, whose state is in hand (every
        ADMISSION_CHUNK, and the page-aligned ends of the power-of-two
        tail: 5,120 and 5,248 of a 5,308-token prompt). A paged admission
        leaves none: its delta passes a prompt's last full block
        mid-span, and a snapshot there would take a second commit pass
        in that program and a 76 MB copy (h-micro) an admission. So a
        sibling of a prompt that was admitted that way resumes at the
        last chunk boundary under its match: a delta of 188 on every
        admission of the benchmark's critique mix, where a snapshot at
        the last full block would make it 60 (PERF.md section 7 (v))."""
        if (
            self.cfg.ssm is None
            or self.prefix_cache is None
            or not adm.canonical
            or chunk_len < self.page_size
            or adm.pos % self.page_size
        ):
            return
        adm.snapshots[adm.pos] = snapshot_state(adm.cache)

    def _dense_cache_to_pages(
        self, adm: _Admission, row_table: np.ndarray, key, sampling: dict
    ):
        """The device work of a chunked admission's handoff: scatter the
        dense cache into the sequence's pages (``row_table``: physical
        ids, page 0 is trash) and sample the first token, returned still
        on the device."""
        cache, last_logits = adm.cache, adm.last_logits
        if adm.canonical:
            if adm.prefill_end > adm.S_real:
                # The final chunk's last slot is bucket garbage; re-run
                # the last REAL token (identical KV rewrite — same token,
                # position, and visible prefix) purely for its logits.
                cache, last_logits = prefill_chunk(
                    self.params,
                    self.cfg,
                    adm.tokens[:, adm.S_real - 1 : adm.S_real],
                    adm.pads,
                    cache,
                    jnp.int32(adm.S_real - 1),
                    use_pallas_matmul=self._prefill_pallas_matmul,
                    pallas_interpret=self._pallas_interpret,
                )
                if obs_mod.config().enabled:
                    # Same jitted callable as the chunked-prefill site:
                    # every dispatch must be observed or the cache-size
                    # probe misattributes this site's compiles to the
                    # other as phantom "unexpected recompiles".
                    obs_mod.retrace.observe(
                        "prefill_chunk", ("prefill", 1, adm.S),
                        fn=prefill_chunk,
                    )
            # Scatter only the delta: slots [matched, S_real). Adopted
            # prefix pages already hold [0, matched) and must never be
            # rewritten (shared, copy-on-append discipline).
            lo, hi = adm.matched, adm.S_real
        else:
            lo, hi = 0, adm.S
        slots = np.arange(lo, hi, dtype=np.int32)[None, :]
        self.pool = write_tokens(
            self.pool,
            cache["k"][..., lo:hi, :],
            cache["v"][..., lo:hi, :],
            row_table[slots // self.page_size],
            slots % self.page_size,
            ks_new=cache["ks"][..., lo:hi, :] if "ks" in cache else None,
            vs_new=cache["vs"][..., lo:hi, :] if "ks" in cache else None,
        )
        if self.cfg.ssm is not None:
            # the carried state goes to the slot with the pages
            self.pool = write_state_row(
                self.pool, jnp.int32(adm.slot), {k: cache[k] for k in STATE_LEAVES}
            )
        return sample_tokens(last_logits, key, **sampling)[0]

    def _finish_admission(self) -> None:
        """Prefill done (or, for a cached prompt admitted over its
        pages, about to be: its delta is part of this): the prompt's K/V
        reach the sequence's pages, the first token is sampled, and the
        slot takes its owner.

        ``self._admission`` stays set until the slot takes ownership of
        the sequence below: the device work here can fault, and
        ``_abort_admission`` needs the admission record to free its
        pages and resolve its request.
        """
        import time

        t0 = time.monotonic()
        adm = self._admission
        slot, req, seq_id = adm.slot, adm.req, adm.seq_id
        with obs_mod.phase("handoff.launch"):
            # Canonical rows live at pad 0 with their true length; padded
            # rows keep the bucketed length + left pad. Per-row pad_lens and
            # cur_len let both layouts coexist in one decode batch.
            row_len = adm.S_real if adm.canonical else adm.S
            row_table = np.zeros((self.max_pages_per_seq,), np.int32)
            table = self.allocator.table(seq_id)
            row_table[: len(table)] = np.asarray(table, np.int32) + 1
            ids_np = np.asarray(req.prompt_ids, np.int32)
            handoff = dict(
                rows={name: getattr(self, name) for name in _ROW_STATE},
                out_buf=self.out_buf,
                ctx_buf=None,
                slot=jnp.int32(slot),
                row_table=jnp.asarray(row_table),
                max_new=jnp.int32(req.max_new_tokens),
                eos_ids=self._eos,
                ctx_row=None,
                n_ctx=None,
                prev=None,
            )
            if self.speculative:
                # The draft source: the row's REAL (unpadded) prompt ids,
                # then its first sampled token. ctx coordinates are
                # independent of the KV layout — padded rows draft from the
                # same clean token stream canonical rows do.
                ctx_row = np.zeros((self._ctx_cap,), np.int32)
                ctx_row[: len(ids_np)] = ids_np
                handoff.update(
                    ctx_buf=self.ctx_buf,
                    ctx_row=jnp.asarray(ctx_row),
                    n_ctx=jnp.int32(len(ids_np)),
                    prev=jnp.int32(ids_np[-1] if len(ids_np) else 0),
                )
            self._key, sub = jax.random.split(self._key)
            sampling = dict(
                greedy=self.greedy,
                top_k=self.top_k,
                temperature=self._temp,
                top_p=self._top_p,
                use_top_p=self._use_top_p,
            )
            if adm.cache is None:
                n_real = adm.S_real - adm.pos
                width = _span_width(n_real)
                delta = np.zeros((1, width), np.int32)
                delta[0, :n_real] = ids_np[adm.pos :]
                # The walk kernel skips what a row does not hold; the gather
                # path (off the TPU) reads the table's whole width, so it
                # gets the prompt's bucket, as a dense cache would be sized.
                table_pages = (
                    self.max_pages_per_seq
                    if self._use_pallas
                    else min(
                        self.max_pages_per_seq, -(-adm.S // self.page_size)
                    )
                )
                (
                    self.pool, rows, self.out_buf, ctx_buf, first
                ) = paged_admission(
                    self.params,
                    self.cfg,
                    self.pool,
                    tokens=jnp.asarray(delta),
                    start=jnp.int32(adm.pos),
                    n_real=jnp.int32(n_real),
                    key=sub,
                    state0=adm.state0,
                    table_pages=table_pages,
                    use_pallas=self._use_pallas,
                    use_pallas_matmul=self._use_pallas_matmul,
                    pallas_interpret=self._pallas_interpret,
                    **handoff,
                    **sampling,
                )
                interleave_mod.stats.record_step(
                    fused=False, prefill_only=True
                )
                prefix_mod.stats.record_prefill(n_real, 0)
                self._count_span_write(n_real)
                if obs_mod.config().enabled:
                    obs_mod.retrace.observe(
                        "paged_admission",
                        (
                            "paged_admission", width, table_pages, self.B,
                            self.cap, self.greedy,
                        ),
                        fn=paged_admission,
                    )
                    obs_mod.emit(
                        obs_mod.StepEvent(
                            kind="prefill",
                            n_live=int(sum(self._active_np)),
                            admission_slot=slot,
                            prefill_tokens=n_real,
                        )
                    )
                    obs_mod.emit(
                        obs_mod.RequestEvent(
                            req_id=req.req_id,
                            state="prefill",
                            slot=slot,
                            tokens=n_real,
                        )
                    )
            else:
                first = self._dense_cache_to_pages(
                    adm, row_table, sub, sampling
                )
                # graftlint: disable=GL-SYNC -- admission handoff is a sanctioned sync point: a padded row's left pad is a host number from here on
                pad = 0 if adm.canonical else int(np.asarray(adm.pads)[0])
                rows, self.out_buf, ctx_buf = activate_slot(
                    first=first,
                    row_len=jnp.int32(row_len),
                    pad=jnp.int32(pad),
                    **handoff,
                )
            for name in _ROW_STATE:
                setattr(self, name, rows[name])
            if self.speculative:
                self.ctx_buf = ctx_buf
            # Admission handoff is a sanctioned sync point: ``first`` was
            # fetched above, blocking on every step in flight.
            interleave_mod.stats.record_sync()
            obs_mod.record_sync("admission_handoff")
        with obs_mod.phase("handoff.sync"):
            # graftlint: disable=GL-SYNC -- admission handoff is a sanctioned sync point: the first sampled token decides slot activation (and seeds the slot's stream delivery)
            first_np = np.asarray(first)
        # What ``_activate_slot_impl`` decided on the device, from the
        # same token.
        row_active = (req.max_new_tokens > 1) and not bool(
            np.isin(first_np, self._eos_np)
        )
        self._active_np[slot] = row_active
        self._slot_gen[slot] += 1  # new owner: expire in-flight flags
        self._out_np[slot, 0] = first_np
        if self.speculative:
            self._cur_len_np[slot] = row_len + 1
            self._row_len_np[slot] = row_len
            self._max_new_np[slot] = req.max_new_tokens
        # Unconditional: a reused batcher whose speculation was flipped
        # OFF between drains must not stamp the previous occupant's
        # counts onto this request's SchedResult ('all zero with
        # --no-speculative' is the field contract).
        self._slot_spec[slot] = [0, 0, 0]
        if adm.canonical and self.prefix_cache is not None:
            with obs_mod.phase("handoff.insert"):
                # Cache this prompt's full blocks (the already-adopted prefix
                # re-inserts as a no-op; only new tail blocks take refs).
                n_full = adm.S_real // self.page_size
                if n_full:
                    self.prefix_cache.insert(
                        list(req.prompt_ids[: n_full * self.page_size]),
                        self.allocator.table(seq_id)[:n_full],
                    )
                for boundary, snap in adm.snapshots.items():
                    self.prefix_cache.attach_state(
                        req.prompt_ids, boundary, snap, self._snapshot_bytes
                    )
                prefix_mod.stats.record_prefill(0, adm.matched)
        # Ownership handoff: from here the slot (not the admission)
        # accounts for the sequence.
        self._admission = None
        self._slot_req[slot] = req
        self._slot_seq[slot] = seq_id
        if self.cfg.ssm is not None:
            self._state_owner[slot] = seq_id
        if self.tiers is not None and not self._demotion_warm:
            # The demotion's one-page read is compiled at a batcher's
            # first handoff, not by its first eviction: that comes when
            # the pool first fills, in the middle of serving (0.3 s of
            # compiling inside a measured window: PERF.md section 6,
            # PR 37). Here and not at the build: the pool is a program's
            # output from now on, and a fresh array would be another
            # signature.
            self._demotion_warm = True
            with obs_mod.phase("handoff.insert"):
                self._fetch_page_kv(0, self.page_size)
        self._slot_cached[slot] = adm.matched
        self._slot_trace[slot] = req.trace_id
        self._slot_span[slot] = req.span_id
        self._slot_decode_s[slot] = 0.0
        self._slot_consumer[slot] = req.on_tokens
        self._slot_streamed[slot] = 0
        if req.on_tokens is not None:
            stream_mod.stats.record_request()
        elapsed = time.monotonic() - t0
        # The handoff (pool scatter + first-token sample + sync) is time
        # the batch genuinely waits on: stalled, in both loop modes.
        self._record_prefill_time(elapsed, overlapped=False)
        self._slot_prefill_s[slot] = adm.prefill_s + elapsed
        if obs_mod.config().enabled:
            with obs_mod.phase("handoff.books"):
                # This request's own prefill wall (stalled + overlapped
                # chunks) through the handoff that produced its first
                # sampled token. No queueing in it: not a TTFT.
                obs_mod.hot.prefill_wall.observe(self._slot_prefill_s[slot])
                self._gauge_pool()
                obs_mod.emit(
                    obs_mod.RequestEvent(
                        req_id=req.req_id,
                        state="decode",
                        slot=slot,
                        tokens=1,
                        cached_tokens=adm.matched,
                    )
                )
                # Trace spans: prefill closes with this request's OWN
                # prefill wall (stalled + overlapped chunks + handoff —
                # exactly SchedResult.prefill_time_s), decode opens. The
                # TTFT SLO gate sees the same wall the ttft histogram does;
                # a breach arms the once-per-request trace-scoped capture.
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="prefill",
                        phase="end",
                        req_id=req.req_id,
                        slot=slot,
                        wall_s=self._slot_prefill_s[slot],
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="decode",
                        phase="begin",
                        req_id=req.req_id,
                        slot=slot,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                obs_mod.slo_check(
                    "ttft", req.span_id, self._slot_prefill_s[slot]
                )
        with obs_mod.phase("handoff.deliver"):
            # First-token stream delivery: ``first`` was already fetched
            # for the EOS check above, so this rides the handoff sync. A
            # consumer that cancels on the very first token (its marker is
            # a single token, or the prompt itself decided the verdict)
            # stops the row before it ever joins a decode step.
            if req.on_tokens is not None:
                keep = self._deliver_stream(slot, 1, first_np.reshape(1))
                if not keep and row_active:
                    self._cancel_slot(slot, 1, first_np.reshape(1))
                    return
            if not row_active:
                self._finish_slot(slot)

    def _admit(self) -> None:
        """Fill free slots from the queue. Single-chunk (short) prompts
        admit to completion immediately so a burst of requests fills the
        batch BEFORE the next decode chunk, and so a newcomer occupies
        its slot within one scheduler iteration (slot-targeted fault
        injection and eviction surgery rely on that timing). The stall
        this costs is bounded by ONE admission chunk — the common
        cross-round case is a prefix-cache-hit delta far under it. The
        first MULTI-chunk prompt stays in flight and its remaining
        chunks ride the residents' fused steps (one chunked admission at
        a time)."""
        # Host bookkeeping only — no device sync: a slot without an
        # owner is never live (_finish_slot / fault eviction / timeout
        # all clear the trailing view before releasing the slot), so the
        # drive loop can admit while a step is still in flight.
        for slot in range(self.B):
            if self._admission is not None or not self.queue:
                return
            if self._slot_req[slot] is None and not self._active_np[slot]:
                # The request's ambient trace scope: cache lookups, tier
                # promotions, and retrace observations this admission
                # causes stamp with ITS trace/span (obs/trace.py).
                req0 = self.queue[0]
                try:
                    with obs_mod.trace_scope(
                        req0.trace_id, req0.span_id
                    ), obs_mod.phase("drive.admit"):
                        started = self._start_admission(slot, req0)
                except Exception as e:
                    # Fault isolation: only this request is affected —
                    # the batch keeps decoding and admission continues
                    # with the next queued request. Faults that know
                    # their seam (injected kv_swap mid-promotion) keep
                    # it; everything else faulted reserving pages.
                    self._fault_request(
                        self.queue.pop(0),
                        e,
                        getattr(e, "seam", "kv_alloc") or "kv_alloc",
                        slot=slot,
                    )
                    continue
                if not started:
                    # Pool full right now — the request stays queued
                    # (FIFO) until residents free pages.
                    return
                self.queue.pop(0)
                try:
                    # Short prefills (≤ one ADMISSION_CHUNK of work left —
                    # possibly several sub-chunk pieces on the canonical
                    # path) admit to completion immediately.
                    with obs_mod.trace_scope(
                        req0.trace_id, req0.span_id
                    ), obs_mod.phase("drive.prefill"):
                        while (
                            self._admission is not None
                            and self._admission.slot == slot
                            and self._admission.remaining <= ADMISSION_CHUNK
                        ):
                            self._advance_admission()
                except Exception as e:
                    self._abort_admission(e)

    # -- fault containment -------------------------------------------------

    def _fault_request(
        self,
        req: SchedRequest,
        exc: BaseException,
        seam: str,
        tokens: np.ndarray | None = None,
        n: int = 0,
        cached_tokens: int = 0,
        prefill_time_s: float = 0.0,
        slot: int = -1,
        pages_freed: int = 0,
        spec_counts: tuple[int, int, int] = (0, 0, 0),
        decode_time_s: float = 0.0,
    ) -> None:
        """Resolve one faulted request: requeue once if the fault is
        transient (OOM/device-loss/preemption/timeout) and this req_id
        hasn't been retried yet — budgeted against the caller's existing
        deadline, since the requeue drains through the same run_all loop
        — else finalize with the partial tokens + fault metadata. Every
        event here stamps the INJURED request's trace/span explicitly
        (the ambient scope may belong to a co-resident admission), so
        the auto-dump's JSONL resolves the fault to its victim."""
        kind = faults.classify(exc)
        faults.record(kind, seam)
        requeued = kind.transient and req.req_id not in self._retried
        obs_mod.emit(
            obs_mod.FaultEvent(
                seam=seam,
                kind=kind.value,
                slot=slot,
                req_id=req.req_id,
                pages_freed=pages_freed,
                requeued=requeued,
                error=f"{type(exc).__name__}: {exc}",
                trace_id=req.trace_id,
                span_id=req.span_id,
            )
        )
        if requeued:
            self._retried.add(req.req_id)
            self.queue.append(req)
            import time

            self._queued_t[req.req_id] = time.monotonic()
            obs_mod.emit(
                obs_mod.RequestEvent(
                    req_id=req.req_id,
                    state="queued",
                    tokens=len(req.prompt_ids),
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
            obs_mod.emit(
                obs_mod.SpanEvent(
                    name="queued",
                    phase="begin",
                    req_id=req.req_id,
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
            return
        # Final resolution: the watchdog stops tracking this request.
        self._deadline_t.pop(req.req_id, None)
        obs_mod.emit(
            obs_mod.RequestEvent(
                req_id=req.req_id,
                state="evicted",
                slot=slot,
                tokens=n,
                cached_tokens=cached_tokens,
                trace_id=req.trace_id,
                span_id=req.span_id,
            )
        )
        if obs_mod.config().enabled:
            obs_mod.hot.req_evicted.inc()
            # Close the request's trace envelope with what it actually
            # consumed — an evicted request still waterfalls.
            obs_mod.emit(
                obs_mod.SpanEvent(
                    name="request",
                    phase="end",
                    req_id=req.req_id,
                    slot=slot,
                    wall_s=prefill_time_s + decode_time_s,
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
        # The whole point of the flight recorder: when a fault evicts,
        # the last N events (reconstructing what the batcher was doing)
        # land on disk IMMEDIATELY, before any further unwind.
        obs_mod.autodump("fault")
        self.results.append(
            SchedResult(
                req_id=req.req_id,
                tokens=(
                    tokens if tokens is not None else np.zeros((0,), np.int32)
                ),
                n_generated=n,
                error=f"{type(exc).__name__}: {exc}",
                fault_kind=kind.value,
                cached_tokens=cached_tokens,
                prefill_time_s=prefill_time_s,
                spec_steps=spec_counts[0],
                spec_drafted=spec_counts[1],
                spec_accepted=spec_counts[2],
                decode_time_s=decode_time_s,
                trace_id=req.trace_id,
                span_id=req.span_id,
            )
        )

    def _abort_admission(self, exc: BaseException) -> None:
        """The in-flight admission's prefill faulted: free its pages and
        resolve its request; resident rows are untouched."""
        adm = self._admission
        self._admission = None
        if adm is None:
            # The fault landed after the slot already took ownership
            # (tail of _finish_admission): there is no admission record
            # to unwind here, so don't mask the original fault.
            raise exc
        free0 = self.allocator.free_pages
        self.allocator.free_sequence(adm.seq_id)
        if obs_mod.config().enabled:
            obs_mod.emit(
                obs_mod.SpanEvent(
                    name="prefill",
                    phase="end",
                    req_id=adm.req.req_id,
                    slot=adm.slot,
                    wall_s=adm.prefill_s,
                    trace_id=adm.req.trace_id,
                    span_id=adm.req.span_id,
                )
            )
        self._fault_request(
            adm.req,
            exc,
            "admission",
            cached_tokens=adm.matched,
            prefill_time_s=adm.prefill_s,
            slot=adm.slot,
            pages_freed=self.allocator.free_pages - free0,
        )

    def _handle_decode_fault(self, exc: BaseException) -> None:
        """A decode chunk faulted: evict ONE slot, keep the rest.

        The victim is the slot the fault names (injected faults carry
        one), else the occupied slot with the longest resident sequence
        — the best heuristic for a real OOM, since it owns the most
        pages. If the fault destroyed the donated device state (a real
        mid-execution abort invalidates the donated pool/out_buf), slot
        surgery is impossible — re-raise and let the engine degrade the
        whole group (the pre-isolation behavior).

        The verify steps in flight are retired first (the reads below
        wait for them anyway): the victim leaves with every step it
        took booked, and the rows that stay are seen as they are.
        """
        self._drain_spec_pipe()
        try:
            # graftlint: disable=GL-SYNC -- fault decision point: eviction surgery needs host lengths to pick the victim
            cur_len_np = np.asarray(self.cur_len)
            # graftlint: disable=GL-SYNC -- fault decision point: probes whether the donated device state survived the fault
            np.asarray(self.out_buf[:, :1])  # probe the donated buffer
        except Exception:
            raise exc from None
        slot = getattr(exc, "slot", None)
        if (
            slot is None
            or not 0 <= slot < self.B
            or self._slot_req[slot] is None
        ):
            occupied = [
                s for s in range(self.B) if self._slot_req[s] is not None
            ]
            if not occupied:
                raise exc
            slot = max(occupied, key=lambda s: int(cur_len_np[s]))
        # graftlint: disable=GL-SYNC -- fault decision point: the victim's partial tokens must be rescued before the slot is freed
        n = int(self.n_emitted[slot])
        # graftlint: disable=GL-SYNC -- fault decision point (partial-token rescue, same sanctioned sync as the count above)
        partial = np.asarray(self.out_buf[slot, :n])
        # Faults that know their seam keep it (the watchdog's
        # deadline evictions report at seam "watchdog"; injected
        # scheduler_chunk faults already carry that name).
        seam = getattr(exc, "seam", None) or "scheduler_chunk"
        self._evict_slot(slot, exc, seam, n, partial)

    def _evict_slot(
        self,
        slot: int,
        exc: BaseException,
        seam: str,
        n: int,
        partial: np.ndarray,
    ) -> None:
        """Shared slot-eviction surgery for both fault paths
        (``_handle_decode_fault``, ``_evict_spec_row``) — callers differ
        only in victim choice and where the partial-token rescue comes
        from. Eviction only drops this slot's REFERENCES: pages shared
        with the prefix cache (or other admissions) survive untouched —
        a faulted slot can never invalidate co-residents' prefix blocks;
        for a speculating row ``free_sequence`` drops its committed
        pages AND any in-flight draft pages."""
        req = self._slot_req[slot]
        st = self._slot_spec[slot]
        # The partial transcript reaches the stream consumer BEFORE the
        # slot frees: an evicted request's caller gets every token the
        # budget bought (the watchdog's contract — partial text
        # delivered, then the timeout fault). The cancel return is
        # moot; the slot is going away regardless.
        with obs_mod.phase("collect.deliver"):
            self._deliver_stream(slot, n, partial)
        with obs_mod.phase("collect.release"):
            pages_freed = self._release_slot(slot)
        interleave_mod.stats.record_sync()  # fault decision point
        obs_mod.record_sync("fault")
        if obs_mod.config().enabled:
            # The victim's decode span closes with its accumulated
            # share before the request envelope does (_fault_request).
            obs_mod.emit(
                obs_mod.SpanEvent(
                    name="decode",
                    phase="end",
                    req_id=req.req_id,
                    slot=slot,
                    wall_s=self._slot_decode_s[slot],
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
        self._fault_request(
            req,
            exc,
            seam,
            tokens=partial,
            n=n,
            cached_tokens=self._slot_cached[slot],
            prefill_time_s=self._slot_prefill_s[slot],
            slot=slot,
            pages_freed=pages_freed,
            spec_counts=(st[0], st[1], st[2]),
            decode_time_s=self._slot_decode_s[slot],
        )

    def _release_slot(self, slot: int) -> int:
        """THE slot-release surgery, shared by fault eviction
        (``_evict_slot``) and cancellation (``_cancel_slot``) — one
        implementation so a new release invariant cannot be added to
        one path and forgotten on the other (the PR 6 lesson, where the
        two fault paths had already drifted apart). Drops the slot's
        sequence references (pages shared with the prefix cache or
        other admissions survive untouched; for a speculating row this
        covers committed AND in-flight draft pages), clears ownership
        and streaming state, deactivates the device row, zeroes its
        page-table row, and bumps the ownership generation so any
        in-flight flags/counts/deliveries for the old owner expire.
        Returns the pages actually freed."""
        free0 = self.allocator.free_pages
        self.allocator.free_sequence(self._slot_seq[slot])
        self._slot_req[slot] = None
        self._slot_seq[slot] = None
        # The state row has no content to free: its next owner's handoff
        # overwrites it, and an idle row's state is never advanced.
        self._state_owner[slot] = None
        self._slot_consumer[slot] = None
        self._slot_streamed[slot] = 0
        self.active = self.active.at[slot].set(False)
        self._active_np[slot] = False
        self._slot_gen[slot] += 1
        self.page_table = self.page_table.at[slot].set(0)
        return self.allocator.free_pages - free0

    # -- streaming + cancellation ------------------------------------------

    def _stream_armed(self, slots) -> bool:
        """True when any of ``slots`` has a streaming consumer — the
        gate for the extra (same-sync-point) token fetches below."""
        return any(self._slot_consumer[s] is not None for s in slots)

    def _deliver_stream(self, slot: int, n: int, tokens) -> bool:
        """Deliver this slot's tokens-so-far to its streaming consumer
        (pure host callback — no device work, no sync). Returns False
        when the consumer asked for cancellation. A consumer that
        RAISES is disabled for the rest of the request and the row
        decodes to its budget — a broken callback must not corrupt the
        batcher or take co-residents down with it."""
        cb = self._slot_consumer[slot]
        if cb is None or n <= self._slot_streamed[slot]:
            return True
        new = n - self._slot_streamed[slot]
        self._slot_streamed[slot] = n
        stream_mod.stats.record_delivery(new)
        try:
            return bool(cb(np.asarray(tokens[:n])))
        except Exception:
            self._slot_consumer[slot] = None
            return True

    def _stream_entry(
        self, emitted_np: np.ndarray, out_np: np.ndarray, live_slots
    ) -> None:
        """Stream one fetched step's tokens to every live consumer and
        cancel the rows whose consumers are done. ``live_slots`` are
        (slot, generation) pairs recorded at dispatch — the same guard
        ``_fetch_entry`` uses, so a freed-and-readmitted slot can never
        have an old step's tokens delivered to its new owner."""
        for slot, gen in live_slots:
            if (
                gen != self._slot_gen[slot]
                or self._slot_req[slot] is None
                or self._slot_consumer[slot] is None
            ):
                continue
            n = int(emitted_np[slot])
            keep = self._deliver_stream(slot, n, out_np[slot])
            if not keep and self._active_np[slot]:
                # Still decoding: stop paying for the rest of the
                # budget. (An already-finished row resolves through
                # _collect with nothing left to save.)
                self._cancel_slot(slot, n, out_np[slot, :n])

    def _cancel_slot(
        self,
        slot: int,
        n: int,
        tokens,
        reason: str = "early_converge",
    ) -> None:
        """First-class mid-decode cancellation: the consumer has read
        everything the debate will ever use, so the request stops HERE
        — a clean result carrying the partial transcript, not a fault.

        The slot frees through the same reference-drop surgery fault
        eviction uses — ``_release_slot``, the ONE shared
        implementation — (pages shared with the prefix cache survive;
        for a speculating row ``free_sequence`` drops the committed
        coverage and whatever draft pages the rollback, which lags by
        the spans in flight, still holds), and the freed capacity re-admits
        queued work at the next ``_admit``. Before the refs drop, the
        computed KV is SALVAGED: the full pages covering
        prompt + emitted tokens insert into the prefix cache, so a
        later admission sharing the prefix adopts instead of
        re-prefilling (the canonical layout makes page content
        position-pure, hence cacheable mid-request).

        In-flight steps may still write this row's KV tail: device
        programs execute in dispatch order, so those stale writes land
        BEFORE any later owner's data (the fault-eviction discipline),
        and the inserted pages end strictly below every position an
        in-flight step can touch — full pages cover at most
        prompt + n - 1 tokens (the last emitted token's KV is only
        written when it is consumed), while in-flight writes start at
        or past that boundary, i.e. in the first NON-inserted page.
        The ownership-generation bump expires any in-flight flags or
        spec counts for the slot.
        """
        req = self._slot_req[slot]
        seq = self._slot_seq[slot]
        # Budget remainder: how much reserved decode capacity the
        # cancel returned to the pool. An UPPER bound on the decode
        # actually avoided — where EOS would have landed is unknowable
        # once we stop decoding (the mock, which scripts its own reply,
        # reports the exact remainder instead; engine/streaming.py).
        saved = max(int(req.max_new_tokens) - n, 0)
        if self.prefix_cache is not None:
            with obs_mod.phase("collect.books"):
                covered = len(req.prompt_ids) + max(n - 1, 0)
                n_full = covered // self.page_size
                if n_full:
                    ids = list(req.prompt_ids) + [
                        int(t) for t in tokens[: max(n - 1, 0)]
                    ]
                    self.prefix_cache.insert(
                        ids[: n_full * self.page_size],
                        self.allocator.table(seq)[:n_full],
                    )
        st = self._slot_spec[slot]
        cached = self._slot_cached[slot]
        prefill_s = self._slot_prefill_s[slot]
        decode_s = self._slot_decode_s[slot]
        queue_s = self._slot_queue_s[slot]
        with obs_mod.phase("collect.release"):
            self._release_slot(slot)
        self._deadline_t.pop(req.req_id, None)
        stream_mod.stats.record_cancel(n, saved)
        self.results.append(
            SchedResult(
                req_id=req.req_id,
                tokens=np.asarray(tokens[:n], np.int32),
                n_generated=n,
                cancelled=True,
                tokens_saved=saved,
                cached_tokens=cached,
                prefill_time_s=prefill_s,
                spec_steps=st[0],
                spec_drafted=st[1],
                spec_accepted=st[2],
                decode_time_s=decode_s,
                queue_wait_s=queue_s,
                trace_id=req.trace_id,
                span_id=req.span_id,
            )
        )
        if obs_mod.config().enabled:
            with obs_mod.phase("collect.books"):
                obs_mod.hot.cancel(reason).inc()
                obs_mod.hot.cancel_tokens_saved.observe(float(saved))
                if self.speculative and st[1]:
                    obs_mod.hot.spec_acceptance.observe(st[2] / st[1])
                self._gauge_pool()
                obs_mod.emit(
                    obs_mod.RequestEvent(
                        req_id=req.req_id,
                        state="cancelled",
                        slot=slot,
                        tokens=n,
                        cached_tokens=cached,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                obs_mod.emit(
                    obs_mod.CancelEvent(
                        req_id=req.req_id,
                        slot=slot,
                        reason=reason,
                        tokens_emitted=n,
                        tokens_saved=saved,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                # Truncated span set: decode closes with the slot's
                # accumulated share, the request envelope closes with
                # phase ``cancelled`` and the service wall SO FAR — still
                # exactly prefill + decode, so tools/trace_view.py's
                # decomposition check holds for cancelled requests too.
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="decode",
                        phase="end",
                        req_id=req.req_id,
                        slot=slot,
                        wall_s=decode_s,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="request",
                        phase="cancelled",
                        req_id=req.req_id,
                        slot=slot,
                        wall_s=prefill_s + decode_s,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                # A cancelled request still consumed service: the round SLO
                # judges the wall it actually paid, exactly as
                # ``_finish_slot`` does (and as the mock does for cancelled
                # lifecycles) — a breach that happens to end in a cancel
                # must still count and self-capture.
                obs_mod.slo_check("round", req.span_id, prefill_s + decode_s)

    # -- completion --------------------------------------------------------

    def _slot_tokens(self, slot: int) -> tuple[int, np.ndarray]:
        """How many tokens the slot's row has emitted, and the tokens. A
        speculating row's come from the host's copy (``_out_np``, exact
        once the row's last step is retired): no device array is read, so
        nothing waits for the step in flight. A plain row's are fetched:
        a sanctioned sync point (the row is frozen — its values read
        identically from any later state)."""
        if self.speculative:
            n = int(self._cur_len_np[slot] - self._row_len_np[slot])
            return n, self._out_np[slot, :n].copy()
        interleave_mod.stats.record_sync()
        obs_mod.record_sync("slot_complete")
        # graftlint: disable=GL-SYNC -- slot completion is a sanctioned sync point: the row is frozen, its count/tokens read identically from any later state
        n = int(self.n_emitted[slot])
        # graftlint: disable=GL-SYNC -- slot completion token fetch (same sanctioned point as the count above)
        return n, np.asarray(self.out_buf[slot, :n])

    def _finish_slot(self, slot: int) -> None:
        self._active_np[slot] = False  # invariant: no owner ⇒ not live
        req = self._slot_req[slot]
        with obs_mod.phase("collect.fetch"):
            n, row = self._slot_tokens(slot)
        st = self._slot_spec[slot]
        with obs_mod.phase("collect.deliver"):
            # Final-tail stream delivery: an EOS/budget-terminated row hands
            # its consumer the last tokens here (a late cancel is moot —
            # the row is already done, nothing left to save).
            self._deliver_stream(slot, n, row)
            self.results.append(
                SchedResult(
                    req_id=req.req_id,
                    tokens=row,
                    n_generated=n,
                    cached_tokens=self._slot_cached[slot],
                    prefill_time_s=self._slot_prefill_s[slot],
                    spec_steps=st[0],
                    spec_drafted=st[1],
                    spec_accepted=st[2],
                    decode_time_s=self._slot_decode_s[slot],
                    queue_wait_s=self._slot_queue_s[slot],
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
            if self.speculative and st[1] and obs_mod.config().enabled:
                # Per-request acceptance rate at completion — the obs
                # histogram the ISSUE's serving headline reads from.
                obs_mod.hot.spec_acceptance.observe(st[2] / st[1])
        # The shared release surgery (also fault eviction's and
        # cancellation's): beyond the ref drop it clears _slot_seq —
        # the hand-rolled version left it stale — and keeps every
        # release invariant in one place.
        with obs_mod.phase("collect.release"):
            self._release_slot(slot)
        self._deadline_t.pop(req.req_id, None)
        if obs_mod.config().enabled:
            with obs_mod.phase("collect.books"):
                obs_mod.hot.req_finished.inc()
                self._gauge_pool()
                obs_mod.emit(
                    obs_mod.RequestEvent(
                        req_id=req.req_id,
                        state="finished",
                        slot=slot,
                        tokens=n,
                        cached_tokens=self._slot_cached[slot],
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                # Close the trace spans: decode with the slot's accumulated
                # decode share, the request envelope with prefill + decode
                # (its SERVICE wall — the sum tools/trace_view.py checks
                # against the stage walls, and the value the per-request
                # round SLO gate judges).
                service_s = (
                    self._slot_prefill_s[slot] + self._slot_decode_s[slot]
                )
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="decode",
                        phase="end",
                        req_id=req.req_id,
                        slot=slot,
                        wall_s=self._slot_decode_s[slot],
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="request",
                        phase="end",
                        req_id=req.req_id,
                        slot=slot,
                        wall_s=service_s,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
                obs_mod.slo_check("round", req.span_id, service_s)

    def _collect(self, active_np: np.ndarray | None = None) -> None:
        """Resolve finished slots. Timeout expiry passes nothing (full
        device sync); the drive loop passes its trailing host snapshot
        so collection never blocks on the step in flight — a row
        inactive at step N-1 is frozen (masked writes, no count
        advance), so its tokens/counters read the same from any later
        state."""
        if active_np is None:
            # graftlint: disable=GL-SYNC -- full fetch only on the timeout-expiry path (the drive loop always passes its trailing host snapshot)
            active_np = np.asarray(self.active)
        for slot in range(self.B):
            if self._slot_req[slot] is not None and not active_np[slot]:
                self._finish_slot(slot)

    # -- main loop ---------------------------------------------------------

    def run_all(self, timeout_s: float = 0.0) -> list[SchedResult]:
        """Drain the queue: admit, step (fused prefill+decode), collect,
        repeat (``_drive``).

        ``timeout_s`` > 0 is a best-effort wall-clock budget (parity with
        generate()'s deadline, checked between chunks): on expiry, resident
        rows finish with whatever they have emitted and queued requests
        return zero tokens rather than blocking the caller.

        Fault isolation invariant: every submitted ``req_id`` gets exactly
        one ``SchedResult`` — a fault on one slot evicts that slot only
        (partial tokens + ``fault_kind`` on its result, one requeue first
        when transient) while co-resident rows keep decoding.
        """
        if obs_mod.config().enabled:
            obs_mod.hot.batcher_runs.inc()
            obs_mod.hot.batcher_rows.inc(len(self.queue))
            obs_mod.hot.batcher_distinct_prompts.inc(
                len({tuple(r.prompt_ids) for r in self.queue})
            )
        self._drive(timeout_s)
        if self.tiers is not None:
            # Drain-end settle: flush queued disk write-through entries
            # and resolve lazy demotion payloads — every async
            # device→host copy started this drain has resolved by now,
            # so this is host work (file I/O + free fetches), never a
            # serving-path stall.
            self.tiers.settle()
        out = sorted(self.results, key=lambda r: r.req_id)
        # Drain per-run state: a batcher kept alive across rounds (the
        # prefix cache's raison d'être) must not replay old results.
        self.results = []
        self._retried.clear()
        return out

    def _has_work(self) -> bool:
        return bool(
            self.queue
            or self._admission is not None
            or any(r is not None for r in self._slot_req)
        )

    def _expire_timeout(self) -> None:
        """Deadline hit: the in-flight admission unwinds (pages freed —
        including dropping refs on any adopted cached prefix; its request
        reports with the queue), resident rows finish with whatever the
        chunk in flight emitted, and every queued request resolves with
        zero tokens instead of blocking the caller."""
        interleave_mod.stats.record_sync()  # timeout decision point
        obs_mod.record_sync("timeout")
        if self._admission is not None:
            adm = self._admission
            self._admission = None
            self.allocator.free_sequence(adm.seq_id)
            self.queue.insert(0, adm.req)  # report with the queue
        self.active = jnp.zeros_like(self.active)
        self._active_np[:] = False
        self._collect()
        for req in self.queue:
            self.results.append(
                SchedResult(
                    req_id=req.req_id,
                    tokens=np.zeros((0,), np.int32),
                    n_generated=0,
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
            obs_mod.emit(
                obs_mod.RequestEvent(
                    req_id=req.req_id,
                    state="timeout",
                    trace_id=req.trace_id,
                    span_id=req.span_id,
                )
            )
            if obs_mod.config().enabled:
                obs_mod.hot.req_timeout.inc()
                obs_mod.emit(
                    obs_mod.SpanEvent(
                        name="request",
                        phase="end",
                        req_id=req.req_id,
                        trace_id=req.trace_id,
                        span_id=req.span_id,
                    )
                )
        self.queue.clear()
        # Queue-wait bookkeeping dies with the queue: a req_id reused
        # by a later drain must not inherit this round's submit time.
        # Per-request deadlines likewise — everything just resolved.
        self._queued_t.clear()
        self._deadline_t.clear()
        # Deadline evictions are triage material exactly like faults:
        # dump what the batcher was doing when the budget ran out.
        obs_mod.autodump("timeout")

    def _watchdog_exc(self, req: SchedRequest, where: str) -> TimeoutError:
        exc = TimeoutError(
            "DEADLINE_EXCEEDED: per-request watchdog deadline "
            f"{req.deadline_s:g}s expired ({where}, req {req.req_id})"
        )
        exc.seam = "watchdog"
        # The request's total budget is spent: no batcher-level requeue
        # (it would re-expire on arrival) — the single hedged
        # re-admission with a TIGHTENED budget is the debate layer's
        # decision (run_round), where the breaker can veto it.
        self._retried.add(req.req_id)
        return exc

    def _expire_request_deadlines(self) -> None:
        """Per-request watchdog (``SchedRequest.deadline_s``): called
        once per drive-loop iteration in BOTH loops, pure host clock
        math on the fast path (one dict check when no deadline is
        armed). An over-deadline RESIDENT row evicts through the
        decode-fault surgery — ``_handle_decode_fault`` → ``_evict_slot``
        → ``_release_slot`` — whose EXISTING sanctioned fetches rescue
        the partial tokens and deliver them to the stream consumer, so
        the watchdog introduces zero new sync points and co-residents
        keep decoding. An over-deadline in-flight ADMISSION aborts
        (pages freed, request resolved at the admission seam); an
        over-deadline QUEUED request resolves with zero tokens — a
        watchdog must also cover work that never got scheduled."""
        if not self._deadline_t:
            return
        import time

        now = time.monotonic()
        for slot in range(self.B):
            req = self._slot_req[slot]
            if req is None:
                continue
            dl = self._deadline_t.get(req.req_id)
            if dl is None or now <= dl:
                continue
            exc = self._watchdog_exc(req, "mid-decode")
            exc.slot = slot
            self._handle_decode_fault(exc)
        adm = self._admission
        if adm is not None:
            dl = self._deadline_t.get(adm.req.req_id)
            if dl is not None and now > dl:
                self._abort_admission(
                    self._watchdog_exc(adm.req, "mid-prefill")
                )
        expired = [
            r
            for r in self.queue
            if self._deadline_t.get(r.req_id, now) < now
        ]
        for req in expired:
            self.queue.remove(req)
            self._deadline_t.pop(req.req_id, None)
            self._fault_request(
                req, self._watchdog_exc(req, "queued"), "watchdog"
            )

    # -- drive loop -------------------------------------------------------

    def _fused_chunk_len(
        self, remaining: int, n_live: int, width: int | None = None
    ) -> int:
        """Prompt-chunk length for a fused step: largest power of two
        that fits the shared per-step token budget after the live rows'
        decode work is accounted (Sarathi-style — the newcomer's
        prefill shrinks before resident latency does). ``width`` is the
        per-row token budget of the riding step: the decode-chunk
        length normally, γ+1 verify positions under speculation."""
        w = self.chunk if width is None else width
        cap = min(ADMISSION_CHUNK, max(self.step_tokens - n_live * w, 1))
        c = ADMISSION_CHUNK
        while c > cap or c > remaining:
            c //= 2
        return max(c, 1)

    def _dispatch_fused(self, adm: _Admission, chunk_len: int) -> None:
        """Issue ONE device program advancing the admission's prompt
        chunk and all live rows' decode chunk; no host sync."""
        self._key, sub = jax.random.split(self._key)
        injector.fire("scheduler_chunk")
        (
            adm_cache,
            adm_logits,
            self.pool,
            self.cur_tok,
            self.cur_len,
            self.n_emitted,
            self.out_buf,
            self.active,
        ) = fused_prefill_decode_chunk(
            self.params,
            self.cfg,
            adm.tokens[:, adm.pos : adm.pos + chunk_len],
            adm.pads,
            adm.cache,
            jnp.int32(adm.pos),
            self.pool,
            self.page_table,
            self.cur_tok,
            self.cur_len,
            self.pad_lens,
            self.n_emitted,
            self.max_new,
            self.active,
            self.out_buf,
            self._eos,
            sub,
            self._temp,
            self._top_p,
            chunk=self.chunk,
            greedy=self.greedy,
            top_k=self.top_k,
            use_top_p=self._use_top_p,
            use_pallas=self._use_pallas,
            use_pallas_matmul=self._use_pallas_matmul,
            prefill_pallas_matmul=self._prefill_pallas_matmul,
            pallas_interpret=self._pallas_interpret,
        )
        adm.cache, adm.last_logits = adm_cache, adm_logits
        adm.pos += chunk_len
        self._keep_snapshot(adm, chunk_len)
        interleave_mod.stats.record_step(fused=True)
        prefix_mod.stats.record_prefill(chunk_len, 0)
        if obs_mod.config().enabled:
            obs_mod.retrace.observe(
                "fused_prefill_decode_chunk",
                ("fused", chunk_len, adm.S, self.B, self.cap, self.chunk),
                fn=fused_prefill_decode_chunk,
            )

    def _dispatch_decode(self) -> None:
        """Issue one decode-only chunk program; no host sync."""
        self._key, sub = jax.random.split(self._key)
        injector.fire("scheduler_chunk")
        (
            self.pool,
            self.cur_tok,
            self.cur_len,
            self.n_emitted,
            self.out_buf,
            self.active,
        ) = scheduler_decode_chunk(
            self.params,
            self.cfg,
            self.pool,
            self.page_table,
            self.cur_tok,
            self.cur_len,
            self.pad_lens,
            self.n_emitted,
            self.max_new,
            self.active,
            self.out_buf,
            self._eos,
            sub,
            self._temp,
            self._top_p,
            chunk=self.chunk,
            greedy=self.greedy,
            top_k=self.top_k,
            use_top_p=self._use_top_p,
            use_pallas=self._use_pallas,
            use_pallas_matmul=self._use_pallas_matmul,
            pallas_interpret=self._pallas_interpret,
        )
        interleave_mod.stats.record_step(fused=False)
        if obs_mod.config().enabled:
            obs_mod.retrace.observe(
                "scheduler_decode_chunk",
                ("decode", self.B, self.cap, self.chunk, self.greedy),
                fn=scheduler_decode_chunk,
            )

    # -- speculative stepping ----------------------------------------------

    def _evict_spec_row(
        self, slot: int, exc: BaseException, seam: str
    ) -> None:
        """A speculative step could not secure this row's next KV slot
        (genuine pool exhaustion after prefix-cache LRU eviction) or an
        injected ``kv_alloc`` fault fired mid-decode: evict ONLY this
        row (``_evict_slot``) while co-resident rows keep decoding."""
        # Count and tokens come from the host's views (exact: the caller
        # has retired every step in flight first).
        n, partial = self._slot_tokens(slot)
        self._evict_slot(slot, exc, seam, n, partial)

    def _steps_ahead(self, slot: int) -> int:
        """Verify steps in flight that hold this slot's current owner."""
        key = (slot, self._slot_gen[slot])
        return sum(key in step.slots for step in self._pipe)

    def _may_run_ahead(self) -> bool:
        """Whether the next verify step may be enqueued before the steps
        in flight are retired: only if some live row is certain to outlive
        them (budget left at the trailing view over what they can emit,
        γ+1 a step; EOS aside), so no program is ever enqueued whose rows
        are all certain to be finished. And not while a queued request or
        an admission in flight waits on a row that may finish in them:
        the slot, the pages and the step-token budget it frees are then
        seen exactly when the one-deep loop would see them."""
        span = self.gamma + 1
        certain = may_finish = False
        for slot in range(self.B):
            if not self._active_np[slot]:
                continue
            left = int(self._max_new_np[slot]) - int(
                self._cur_len_np[slot] - self._row_len_np[slot]
            )
            if left > self._steps_ahead(slot) * span:
                certain = True
            else:
                may_finish = True
        waiting = bool(self.queue) or self._admission is not None
        return certain and not (may_finish and waiting)

    def _extend_row(self, slot: int, n_tokens: int) -> None:
        """Extend the slot's sequence by ``n_tokens`` (none: a no-op)
        under its row's trace scope: a cache eviction / tier demotion
        the extend forces stamps with the request that caused the
        pressure."""
        if n_tokens > 0:
            with obs_mod.trace_scope(
                self._slot_trace[slot], self._slot_span[slot]
            ):
                self._extend_evicting(self._slot_seq[slot], n_tokens)

    def _cover_spec_rows(self, live: list[int]) -> np.ndarray | None:
        """Extend every row of ``live`` to the coverage its next verify
        step needs; returns each row's draft bound, or None when a row's
        extend failed with steps in flight: the caller retires them and
        sizes the step one deep."""
        span = self.gamma + 1
        alloc = np.zeros((self.B,), np.int64)
        for slot in list(live):
            seq = self._slot_seq[slot]
            cl = int(self._cur_len_np[slot])
            remaining = int(self._max_new_np[slot]) - (
                cl - int(self._row_len_np[slot])
            )
            length = self.allocator.length(seq)
            # ``cl`` trails the device by the steps in flight, each of
            # which may add a whole span before this one starts.
            spans = (self._steps_ahead(slot) + 1) * span
            want = cl + min(spans, max(remaining, 1))
            try:
                self._extend_row(slot, want - length)
            except Exception as e:
                if self._pipe:
                    return None
                fault = e
                if isinstance(e, OutOfPages):
                    try:
                        self._extend_row(slot, cl + 1 - length)
                        fault = None
                    except OutOfPages as e1:
                        fault = e1
                if fault is not None:
                    # No page even for the next token, or a bug at the
                    # alloc seam: isolate to this row, co-residents keep
                    # decoding.
                    self._evict_spec_row(slot, fault, "kv_alloc")
                    live.remove(slot)
                    continue
            alloc[slot] = self.allocator.covered_tokens(seq) - 1
        return alloc

    def _retire_and_collect(self, live: list[int]) -> None:
        """Bring a step's preparation to where the one-deep loop would
        stand: every step in flight retired, the rows that finished in
        them resolved (their pages are free again) and out of ``live``."""
        self._drain_spec_pipe()
        self._collect(self._active_np)
        live[:] = [s for s in live if self._active_np[s]]

    def _prepare_spec_step(self, live: list[int]) -> jnp.ndarray:
        """Size page coverage for ONE speculative step over ``live``
        rows and return the per-row draft bound (the device program's
        ``alloc_len``).

        Coverage discipline (the append/rollback contract with
        ``_apply_spec_counts``):

        - extend each row to ``cur_len + min(γ+1, budget left)`` KV
          slots — the full draft span, through the prefix cache's
          LRU-evicting extend so cache pages yield to live decode. A
          row with a step in flight is extended by a span more for each:
          the host's ``cur_len`` is then the view before that step,
          which may add up to γ+1 before this one writes its own;
        - under genuine pressure the steps in flight are retired first
          (the iteration runs one deep: their rollback returns the
          second span's pages), then fall back to ``cur_len + 1`` (the
          next mandatory single-token write), degrading the row to a
          plain step INSIDE the same compiled program (``n_allowed``
          clamps to 0); if even that page cannot be found, evict the row
          with a classified OOM (transient → one requeue);
        - the device receives ``covered_tokens - 1`` as its draft
          bound: the −1 reserves the slot the step's LAST emitted token
          (bonus or rejection draw) will need for its own KV write next
          step, so the post-step length fix-up in
          ``_apply_spec_counts`` NEVER has to allocate — rollback is
          the only page operation after a verify, and it cannot fail.

        An injected ``kv_alloc`` fault (the seam fires once a live row)
        evicts ONLY its row, after the steps in flight are retired, so
        the victim keeps every token the device had made for it.

        The device page table is re-pushed from the allocator's
        authoritative host tables every step: draft pages released by
        one row's rollback may have been re-acquired by another row
        since the last push, so tail entries can go stale across steps
        (never within one — writes/reads are bounded by ``alloc_len``).
        """
        faulted = []
        for slot in live:
            try:
                injector.fire("kv_alloc", slot)
            except Exception as e:
                # Injected fault at the alloc seam: isolate to this row,
                # co-residents keep decoding.
                faulted.append((slot, e))
        if faulted:
            self._retire_and_collect(live)
            for slot, e in faulted:
                if slot in live:
                    self._evict_spec_row(slot, e, "kv_alloc")
                    live.remove(slot)
        alloc = self._cover_spec_rows(live)
        if alloc is None:
            self._retire_and_collect(live)
            alloc = self._cover_spec_rows(live)
        tables = np.zeros((self.B, self.max_pages_per_seq), np.int32)
        for slot in live:
            t = self.allocator.table(self._slot_seq[slot])
            tables[slot, : len(t)] = np.asarray(t, np.int32) + 1
        # Committed like every other persistent row-state creation
        # (GL-COMMIT): the re-pushed table is a program input next
        # dispatch, and an uncommitted fresh array vs the committed
        # step output is two jit signatures — the PR 6 double-compile
        # class, which this site reintroduced on the spec path.
        self.page_table = self._commit(jnp.asarray(tables))
        return jnp.asarray(alloc, jnp.int32)

    def _dispatch_spec(
        self, alloc_len: jnp.ndarray, adm: _Admission | None, chunk_len: int
    ) -> jnp.ndarray:
        """Issue ONE speculative device program — every live row's
        draft+verify step, optionally fused with the in-flight
        admission's next prompt chunk — and return the stacked per-row
        counts array (still on device; the drive loop fetches it as the
        sanctioned spec sync)."""
        self._key, sub = jax.random.split(self._key)
        injector.fire("scheduler_chunk")
        if adm is not None:
            (
                adm_cache,
                adm_logits,
                self.pool,
                self.ctx_buf,
                self.ctx_len,
                self.prev_tok,
                self.cur_tok,
                self.cur_len,
                self.n_emitted,
                self.out_buf,
                self.active,
                counts,
            ) = fused_prefill_spec_chunk(
                self.params,
                self.cfg,
                adm.tokens[:, adm.pos : adm.pos + chunk_len],
                adm.pads,
                adm.cache,
                jnp.int32(adm.pos),
                self.pool,
                self.page_table,
                self.ctx_buf,
                self.ctx_len,
                self.prev_tok,
                self.cur_tok,
                self.cur_len,
                self.pad_lens,
                self.n_emitted,
                self.max_new,
                alloc_len,
                self.active,
                self.out_buf,
                self._eos,
                sub,
                self._temp,
                self._top_p,
                gamma=self.gamma,
                greedy=self.greedy,
                top_k=self.top_k,
                use_top_p=self._use_top_p,
                use_pallas=self._use_pallas,
                use_pallas_matmul=self._use_pallas_matmul,
                prefill_pallas_matmul=self._prefill_pallas_matmul,
                pallas_interpret=self._pallas_interpret,
            )
            adm.cache, adm.last_logits = adm_cache, adm_logits
            adm.pos += chunk_len
            self._keep_snapshot(adm, chunk_len)
            interleave_mod.stats.record_step(fused=True)
            prefix_mod.stats.record_prefill(chunk_len, 0)
            if obs_mod.config().enabled:
                obs_mod.retrace.observe(
                    "fused_prefill_spec_chunk",
                    (
                        "fused_spec",
                        chunk_len,
                        adm.S,
                        self.gamma,
                        self.B,
                        self.cap,
                    ),
                    fn=fused_prefill_spec_chunk,
                )
        else:
            (
                self.pool,
                self.ctx_buf,
                self.ctx_len,
                self.prev_tok,
                self.cur_tok,
                self.cur_len,
                self.n_emitted,
                self.out_buf,
                self.active,
                counts,
            ) = scheduler_spec_chunk(
                self.params,
                self.cfg,
                self.pool,
                self.page_table,
                self.ctx_buf,
                self.ctx_len,
                self.prev_tok,
                self.cur_tok,
                self.cur_len,
                self.pad_lens,
                self.n_emitted,
                self.max_new,
                alloc_len,
                self.active,
                self.out_buf,
                self._eos,
                sub,
                self._temp,
                self._top_p,
                gamma=self.gamma,
                greedy=self.greedy,
                top_k=self.top_k,
                use_top_p=self._use_top_p,
                use_pallas=self._use_pallas,
                use_pallas_matmul=self._use_pallas_matmul,
                pallas_interpret=self._pallas_interpret,
            )
            interleave_mod.stats.record_step(fused=False)
            if obs_mod.config().enabled:
                obs_mod.retrace.observe(
                    "scheduler_spec_chunk",
                    ("spec", self.gamma, self.B, self.cap, self.greedy),
                    fn=scheduler_spec_chunk,
                )
        return counts

    def _apply_spec_counts(
        self, counts_np: np.ndarray, step: _SpecStep
    ) -> None:
        """Apply one fetched spec step's per-row counts to the host
        state: advance the trailing cur_len/active views, keep the
        step's tokens (``_out_np``), ROLL BACK draft pages past each
        row's accepted prefix (``PageAllocator.truncate`` — the pages
        the step reserved but the rejection sampler didn't commit), and
        record telemetry.

        The rollback lags with the view: a page is released only when no
        step in flight can commit a token onto it. With a successor in
        flight the row keeps γ+1 slots over its new length for each (the
        successor starts at ``new_cl`` and writes at most γ+1 slots from
        ``new_cl - 1``, the last of them reserved); with the pipe empty
        it is truncated to ``new_cl`` as ever.

        Rows whose ownership generation changed since dispatch are
        skipped — the multi-token analog of ``_fetch_entry``'s guard (a
        freed-and-readmitted slot must not have the old step's counts
        corrupt its new owner's bookkeeping). So is a row that had
        already finished when the step ran (live in the trailing view at
        the enqueue, inactive on the device: it emitted nothing): that
        is no speculative step of its."""
        span = self.gamma + 1
        for slot, gen in step.slots:
            if gen != self._slot_gen[slot] or self._slot_seq[slot] is None:
                continue
            n_allowed = int(counts_np[0, slot])
            n_acc = int(counts_np[1, slot])
            n_emit = int(counts_np[2, slot])
            act = bool(counts_np[3, slot])
            new_cl = int(counts_np[4, slot])
            if not n_emit:
                self._active_np[slot] = False
                continue
            n0 = int(self._cur_len_np[slot] - self._row_len_np[slot])
            self._out_np[slot, n0 : n0 + n_emit] = counts_np[
                N_STEP_COUNTS : N_STEP_COUNTS + n_emit, slot
            ]
            seq = self._slot_seq[slot]
            length = self.allocator.length(seq)
            keep = new_cl + self._steps_ahead(slot) * span
            released = 0
            if new_cl > length:
                # Fully accepted span: a pure length bump within the
                # pages already held (the draft bound's −1 reserve
                # guarantees coverage) — never allocates, cannot fail.
                self.allocator.extend(seq, new_cl - length)
            elif keep < length:
                released = len(self.allocator.truncate(seq, keep))
            self._cur_len_np[slot] = new_cl
            st = self._slot_spec[slot]
            st[0] += 1
            st[1] += n_allowed
            st[2] += n_acc
            spec_mod.stats.record_step(
                n_allowed, n_acc, n_emit, pipelined=step.ahead
            )
            if released:
                spec_mod.stats.record_rollback(released)
            if obs_mod.config().enabled:
                obs_mod.hot.spec_tokens_per_step.observe(float(n_emit))
                obs_mod.emit(
                    obs_mod.SpecEvent(
                        slot=slot,
                        req_id=self._slot_req[slot].req_id,
                        drafted=n_allowed,
                        accepted=n_acc,
                        emitted=n_emit,
                        rolled_back_pages=released,
                        trace_id=self._slot_trace[slot],
                        span_id=self._slot_span[slot],
                    )
                )
            self._active_np[slot] = act
            if self.cfg.latent is not None and obs_mod.config().enabled:
                # the row's cached tokens, read once this step
                obs_mod.hot.latent_tokens_read.inc(new_cl)
            if self._windows and obs_mod.config().enabled:
                obs_mod.hot.record_attn_read(
                    new_cl,
                    self._windows,
                    self.cfg.n_kv_layers - len(self._windows),
                )
        n_rows = N_STEP_COUNTS + span
        if counts_np.shape[0] > n_rows and obs_mod.config().enabled:
            obs_mod.hot.record_routing(
                "decode",
                counts_np[n_rows:, 0],
                self.cfg.n_layers - self.cfg.n_leading,  # the routed layers
                self.cfg.experts.n_held,
            )

    def _gauge_pool(self) -> None:
        """The pool's gauges, where its holdings change (an admission's
        handoff, a release): the share of pages held, and beside windowed
        layers the bytes they hold behind every window."""
        held = self.allocator.n_pages - self.allocator.free_pages
        obs_mod.hot.pool_util.set(round(held / self.allocator.n_pages, 6))
        if not self._windows:
            return
        kv_heads, k_dim, v_dim = self.cfg.kv_layout
        layer_page = (
            kv_heads * self.page_size * (k_dim + v_dim)
            * np.dtype(self._dtype).itemsize
        )
        dead = sum(
            self._window_dead_pages(w) * self._windows.count(w)
            for w in set(self._windows)
        )
        obs_mod.hot.kv_window_dead_bytes.set(dead * layer_page)
        obs_mod.hot.kv_held_bytes.set(held * layer_page * self.cfg.n_kv_layers)

    def _window_dead_pages(self, window: int) -> int:
        """Pages held whose content, in a layer of ``window``, no query
        can reach any more: those that end more than ``window`` tokens
        before the end of EVERY live sequence and cached path that holds
        them (a sequence's next query sees ``window`` positions back from
        its own; a cached path is taken up again at its leaf). What a
        cache per kind of layer would free. A hit that ends inside a
        cached path would find such a page's window layers gone and
        recompute up to ``window`` tokens of them: the price of freeing
        them, not counted here."""
        live = self.allocator.pages_within(window)
        if self.prefix_cache is not None:
            live |= self.prefix_cache.pages_within(window)
        return len(self.allocator.held_pages() - live)

    @staticmethod
    def _entry_ready(entry: tuple) -> bool:
        """True when a step's flags have already resolved on device —
        fetching them is then free (no stall). Conservative False when
        the runtime can't say."""
        try:
            return bool(entry[0].is_ready())
        except Exception:
            return False

    def _fetch_entry(self, entry: tuple) -> None:
        """Apply one completed step's flags to the trailing host view.
        Fetches only DEACTIVATE, and only rows whose slot still belongs
        to the request that was live at dispatch (generation match) — a
        slot freed and re-admitted mid-flight must not have the old
        row's completion flag truncate its new owner.

        When streaming is armed the entry additionally carries the
        step's emitted counts and an out_buf SNAPSHOT (out_buf itself
        is donated to the next dispatch; the snapshot is an independent
        device copy taken at dispatch time): their fetch rides the SAME
        resolved/depth-bound point as the flags — this is exactly how
        decoded tokens already land on host every step, so the stream
        consumer adds no new sanctioned sync."""
        active_ref, emitted_ref, out_ref, live_slots = entry
        with obs_mod.phase("drive.fetch"):
            # graftlint: disable=GL-SYNC -- pipelined fetch: called only when the entry resolved (is_ready) or at the depth bound, the double buffer's one sanctioned blocking point
            act = np.asarray(active_ref)
            for s, gen in live_slots:
                if gen == self._slot_gen[s] and not act[s]:
                    self._active_np[s] = False
        if emitted_ref is None:
            return
        with obs_mod.phase("drive.stream"):
            # graftlint: disable=GL-SYNC -- stream token fetch riding the same resolved/depth-bound entry fetch as the flags above (no new sync point; the async copy started at dispatch)
            emitted_np = np.asarray(emitted_ref)
            # graftlint: disable=GL-SYNC -- stream token fetch (the out_buf snapshot in the same entry; see above)
            out_np = np.asarray(out_ref)
            self._stream_entry(emitted_np, out_np, live_slots)

    def _count_span_write(self, n_tokens: int) -> None:
        """Book the ``n_tokens`` span positions of live rows whose K/V a
        verify step or paged admission just dispatched writes into the
        pool, and whether the program writes them by DMA: the predicate
        its trace took (``forward_paged_decode``; a batcher is one
        device)."""
        if obs_mod.config().enabled:
            obs_mod.hot.kv_span_written_tokens.inc(n_tokens)
            # graftlint: disable=GL-SYNC -- the predicate reads the pool's shapes and dtypes alone, never a device value
            if kv_write.span_write_kernel(self.pool, self._use_pallas):
                obs_mod.hot.kv_span_kernel_tokens.inc(n_tokens)

    def _dispatch_step(self, live: list, alloc_len, adm, chunk_len: int):
        """Enqueue one step for the rows in ``live``; ``adm`` is the
        admission whose ``chunk_len`` prompt tokens ride it, or None. A
        verify step joins the pipe (``_SpecStep``: its counts ref and
        the (slot, generation) pairs it was dispatched for); the plain
        branch's flags travel through its double buffer."""
        if not self.speculative:
            if adm is not None:
                self._dispatch_fused(adm, chunk_len)
            else:
                self._dispatch_decode()
            return
        slots = tuple((s, self._slot_gen[s]) for s in live)
        counts = self._dispatch_spec(alloc_len, adm, chunk_len)
        self._count_span_write(len(live) * (self.gamma + 1))
        try:
            # Start the copy at the enqueue: the fetch, an iteration
            # later when a successor runs ahead, should find it landed.
            counts.copy_to_host_async()
        except Exception:
            pass  # optional fast path only
        self._pipe.append(
            _SpecStep(counts, slots, adm, chunk_len, ahead=bool(self._pipe))
        )

    def _retire_spec_step(self) -> None:
        """Counts fetch → apply → stream for the OLDEST verify step in
        flight, and its wall clock booked. While a successor runs on the
        device all of this rides under it; the fetch waits only for what
        is left of the retired step itself."""
        import time

        step = self._pipe.popleft()
        counts_ref, counts_np = step.counts, None
        with obs_mod.phase("drive.fetch"):
            try:
                # The spec path's ONE sanctioned sync a step: the host
                # rolls rejected drafts back, advances its trailing
                # views and sees completion from the accepted counts. A
                # [5 + γ+1, B] int fetch — the γ+1 tokens the step can
                # emit amortize it, and ride it.
                # graftlint: disable=GL-SYNC -- spec accept fetch: the host must know each row's accepted length to roll draft pages back and advance its trailing views (the one sanctioned speculative sync)
                counts_np = np.asarray(counts_ref)
            except Exception as e:
                # An async device fault surfaces at the fetch: same
                # eviction surgery as dispatch-time. The counts of the
                # steps behind it are lost with it.
                self._pipe.clear()
                self._handle_decode_fault(e)
                self._resync_spec_views()
        interleave_mod.stats.record_sync()
        obs_mod.record_sync("spec_counts")
        if counts_np is not None:
            with obs_mod.phase("drive.apply"):
                self._apply_spec_counts(counts_np, step)
            if self._stream_armed(s for s, _ in step.slots):
                # Stream delivery at the same sanctioned sync, from the
                # tokens that rode the counts: no device array is read.
                # Emitted counts come from the host views
                # _apply_spec_counts just advanced; the consumer gets a
                # copy (the host's buffer is overwritten by the slot's
                # next owner).
                with obs_mod.phase("drive.stream"):
                    self._stream_entry(
                        self._cur_len_np - self._row_len_np,
                        self._out_np.copy(),
                        step.slots,
                    )
        now = time.monotonic()
        dt, self._step_mark = now - self._step_mark, now
        # Rows the step still belongs to (a row that finished, was
        # cancelled or evicted since the enqueue has its result out).
        rows = [s for s, gen in step.slots if gen == self._slot_gen[s]]
        if rows:
            self._account_step(
                dt,
                rows,
                self.gamma + 1,
                step.rider,
                step.chunk_len,
                len(self._pipe) + 1,
                "spec_counts",
            )

    def _drain_spec_pipe(self) -> None:
        """Retire every verify step in flight: the host's trailing views
        are then the device's (one-deep semantics from here)."""
        while self._pipe:
            self._retire_spec_step()

    def _resync_spec_views(self) -> None:
        """After a fault at the counts fetch that the device state
        survived: the counts of the steps in flight are lost, so the
        host's trailing views (lengths, flags, tokens, coverage) are
        read back from the device."""
        # graftlint: disable=GL-SYNC -- fault decision point: the lost counts' views are read back whole
        cur_len, active = np.asarray(self.cur_len), np.asarray(self.active)
        # graftlint: disable=GL-SYNC -- fault decision point (the tokens of the lost steps, same sanctioned sync)
        out = np.asarray(self.out_buf)
        for slot, seq in enumerate(self._slot_seq):
            if seq is None:
                continue
            new_cl = int(cur_len[slot])
            n = new_cl - int(self._row_len_np[slot])
            self._out_np[slot, :n] = out[slot, :n]
            self._cur_len_np[slot] = new_cl
            self._active_np[slot] = bool(active[slot])
            # Within the pages held: every lost step's span was covered
            # before it was enqueued.
            length = self.allocator.length(seq)
            if new_cl > length:
                self.allocator.extend(seq, new_cl - length)
            else:
                self.allocator.truncate(seq, new_cl)

    def _retire_plain_step(self, inflight, entry: tuple) -> tuple:
        """Append the dispatched step's ``entry`` to the double buffer
        and retire the entries that are ready (or that the depth bound
        forces); returns the step's (depth, sync reason)."""
        inflight.append(entry)
        depth = len(inflight)
        step_sync = ""
        try:
            # Retire completed steps ADAPTIVELY: any entry whose flags
            # already resolved (is_ready — free to fetch) applies now,
            # so completions/slot-frees are seen with zero lag whenever
            # the device keeps up (CPU: effectively every iteration).
            # Only force a blocking fetch at the depth bound — that is
            # the double buffer proper, and it only engages when the
            # device is genuinely still executing step N-1.
            while inflight and (
                len(inflight) >= _PIPELINE_DEPTH
                or self._entry_ready(inflight[0])
            ):
                if not self._entry_ready(inflight[0]):
                    # The double buffer's one sanctioned blocking point,
                    # made runtime-visible.
                    obs_mod.record_sync("depth_fetch")
                    step_sync = "depth_fetch"
                self._fetch_entry(inflight.popleft())
        except Exception as e:
            # An async device fault surfaces at the fetch, one step
            # late: same eviction surgery as dispatch-time.
            inflight.clear()
            self._handle_decode_fault(e)
        return depth, step_sync

    def _account_step(
        self, dt, live, width, adm, chunk_len, depth, sync_reason
    ) -> None:
        """Book the wall clock ``dt`` of one dispatched-and-retired step
        over the rows ``live`` at dispatch, ``width`` tokens a row;
        ``adm`` is the admission whose ``chunk_len`` prompt tokens rode
        it, or None.

        The halves of a fused program aren't separately measurable
        without a profiler, so ``dt`` splits by token share (prompt
        tokens vs the decode/verify half's upper bound) — deterministic
        given host state. The prefill part lands in the OVERLAPPED
        bucket and in the riding admission; the rest is decode time,
        split evenly over the live rows (slot sums reproduce
        ``decode_time_s`` — the 'decode' trace span's wall)."""
        fused = adm is not None
        dec_dt = dt
        if fused:
            p = dt * (chunk_len / (chunk_len + len(live) * width))
            self._record_prefill_time(p, overlapped=True)
            adm.prefill_s += p
            dec_dt = dt - p
        self.decode_time_s += dec_dt
        for s in live:
            self._slot_decode_s[s] += dec_dt / len(live)
        if obs_mod.config().enabled:
            kind = "spec" if self.speculative else "decode"
            if fused:
                kind = "fused_spec" if self.speculative else "fused"
            obs_mod.hot.step_wall.observe(dt)
            obs_mod.emit(
                obs_mod.StepEvent(
                    kind=kind,
                    n_live=len(live),
                    admission_slot=adm.slot if fused else -1,
                    prefill_tokens=chunk_len if fused else 0,
                    decode_chunk=width,
                    pipeline_depth=depth,
                    sync_reason=sync_reason,
                    # The riding admission's span; batch-level
                    # otherwise (trace stamps from ambient).
                    span_id=adm.req.span_id if fused else "",
                    trace_id=adm.req.trace_id if fused else "",
                )
            )

    def _drive(self, timeout_s: float) -> None:
        """Admit → dispatch (fused when an admission and live rows
        coexist) → retire → collect, two steps deep on both branches:
        step n+1 is enqueued before step n's flags (plain) or counts
        (speculative) are fetched, so the host's own work (queue
        admission, radix lookups, page allocation, the counts' apply,
        stream delivery, collection) overlaps the step in flight. Host
        syncs happen only at admission handoff, fault decisions, timeout
        expiry, a plain row's completion and the one fetch a step —
        never as a blanket per-chunk barrier.

        The speculative branch: the step program holds a row's length,
        budget, draft source and tokens on the device and takes from the
        host only the page table, a coverage bound and a key, so a verify
        step needs nothing of its predecessor's counts to be ENQUEUED —
        only its pages covered for two spans (``_prepare_spec_step``).
        The counts are what the host rolls draft pages back by, advances
        its trailing views with and sees completion from; all of that
        happens while the successor runs (``_retire_spec_step``), and the
        rollback lags by the spans in flight (``_apply_spec_counts``). A
        step is enqueued ahead only when it is certain to have work
        (``_may_run_ahead``); otherwise, and under page pressure, the
        iteration retires first and runs one deep."""
        import time

        deadline = time.monotonic() + timeout_s if timeout_s > 0 else None
        inflight: deque[tuple] = deque()  # (active_ref, live_slots)
        self._pipe.clear()
        self._step_mark = time.monotonic()
        while self._has_work():
            # The whole body is ONE phase; every call it makes lies in
            # exactly one drive.* phase below, and what is left (the
            # ``live`` lists, the telemetry itself) is the remainder.
            with obs_mod.phase("drive.iteration"):
                if deadline is not None and time.monotonic() > deadline:
                    # Plain entries in flight resolve through the same
                    # lazy arrays _collect reads; their per-step flags
                    # are moot now. Verify steps are retired: resident
                    # rows finish with what the steps in flight emitted.
                    inflight.clear()
                    self._drain_spec_pipe()
                    self._expire_timeout()
                    break
                if self._pipe and not self._may_run_ahead():
                    # No row is certain to need another step (or a
                    # waiting request may get a finishing row's place):
                    # this iteration runs one deep.
                    self._drain_spec_pipe()
                    with obs_mod.phase("drive.collect"):
                        self._collect(self._active_np)
                # Per-request watchdog: evict over-deadline work before
                # admitting/dispatching more (host clock math; evictions
                # ride the fault surgery's existing sanctioned fetches).
                with obs_mod.phase("drive.admit"):
                    self._expire_request_deadlines()
                self._admit()
                adm = self._admission
                live = [s for s in range(self.B) if self._active_np[s]]
                # Collection and admission are not decode time: the clock
                # a retired step is booked from starts here.
                self._step_mark = t0 = time.monotonic()
                rider = None  # the admission whose chunk rode the step
                dispatched = False
                # Speculation: each iteration's "decode work" becomes one
                # γ-draft + verify program per live row, retired through
                # the pipe (``_retire_spec_step``) where the plain branch
                # retires through the double buffer.
                spec = self.speculative
                width = (self.gamma + 1) if spec else self.chunk
                alloc_len = None
                # Fuse only the LEADING prefill chunks (strictly more work
                # left after this chunk): the FINAL chunk runs standalone so
                # the handoff happens before this iteration's decode chunk
                # and the newcomer joins it immediately — fusing the last
                # chunk would push the join one chunk later, fragmenting
                # decode into extra programs for every admission (measured
                # net-negative: the join lag costs more than the one
                # remaining stall saves). Corollary: a fused step never
                # finishes a prefill; every handoff happens inside
                # _advance_admission.
                chunk_len = (
                    self._fused_chunk_len(adm.remaining, len(live), width)
                    if adm is not None and live
                    else 0
                )
                ride = (
                    adm is not None
                    and live
                    and not adm.fuse_deferred
                    and chunk_len < adm.remaining
                )
                if spec and live and (ride or adm is None):
                    # Coverage sizing for the step dispatched below. The
                    # standalone-admission branch prepares AFTER its
                    # handoff instead (the handoff may activate a new row,
                    # and preparing here too would repeat the per-row
                    # extend walk and a second full page-table push).
                    with obs_mod.phase("drive.prepare"):
                        alloc_len = self._prepare_spec_step(live)
                if ride:
                    try:
                        # Fused dispatches run under the riding admission's
                        # trace scope so its retrace/compile observations
                        # attribute to the request that shaped the program.
                        with obs_mod.trace_scope(
                            adm.req.trace_id, adm.req.span_id
                        ), obs_mod.phase("drive.dispatch"):
                            self._dispatch_step(
                                live, alloc_len, adm, chunk_len
                            )
                        rider, dispatched = adm, True
                    except Exception as e:
                        # A dispatch-time fault (chaos seam, trace error) is
                        # treated as decode-side surgery: the admission's
                        # state refs still point at the step before and it
                        # stays in flight; older in-flight entries stay
                        # valid (they can only deactivate). Defer the NEXT
                        # chunk to the standalone path so a fault that
                        # actually originates in the prefill half aborts the
                        # admission there instead of evicting another
                        # innocent resident every iteration.
                        adm.fuse_deferred = True
                        self._handle_decode_fault(e)
                else:
                    if adm is not None:
                        # Final chunk, nothing live to ride, or the last
                        # fused dispatch carrying this admission faulted: a
                        # standalone (stalled) chunk, timed + recorded
                        # inside _advance_admission — which also performs
                        # the handoff when the prefill completes, so the
                        # new row is live for the decode dispatch below.
                        if any(step.rider is adm for step in self._pipe):
                            # A fused step's prefill share is booked to
                            # its admission when the step retires, and
                            # the handoff reads the admission's books.
                            self._drain_spec_pipe()
                        try:
                            with obs_mod.trace_scope(
                                adm.req.trace_id, adm.req.span_id
                            ), obs_mod.phase("drive.prefill"):
                                self._advance_admission()
                            adm.fuse_deferred = False
                        except Exception as e:
                            self._abort_admission(e)
                        live = [
                            s for s in range(self.B) if self._active_np[s]
                        ]
                        if spec and live:
                            # The handoff may have activated a new row;
                            # its coverage must be sized before it joins
                            # the verify step.
                            with obs_mod.phase("drive.prepare"):
                                alloc_len = self._prepare_spec_step(live)
                        # Restart the clock: the standalone chunk's seconds
                        # are already in the stalled-prefill bucket — the
                        # decode dt below must not re-count them (their sum
                        # is what the engine subtracts from total wall).
                        self._step_mark = t0 = time.monotonic()
                    if live:
                        try:
                            with obs_mod.phase("drive.dispatch"):
                                self._dispatch_step(live, alloc_len, None, 0)
                            dispatched = True
                        except Exception as e:
                            self._handle_decode_fault(e)
                if spec:
                    # The double buffer proper: with the successor
                    # enqueued, the step before it is fetched, applied
                    # and streamed while the device runs on.
                    while len(self._pipe) >= _PIPELINE_DEPTH:
                        self._retire_spec_step()
                elif dispatched:
                    # Streaming consumers ride the double buffer: the
                    # entry carries the step's emitted counts plus an
                    # out_buf SNAPSHOT (jnp.copy — out_buf itself is
                    # donated to the next dispatch, so a raw ref would
                    # be deleted before the depth-bound fetch; the
                    # copy is a device-side op that overlaps compute
                    # and only exists while a consumer is attached).
                    streaming = self._stream_armed(live)
                    with obs_mod.phase("drive.dispatch"):
                        entry = (
                            self.active,
                            self.n_emitted if streaming else None,
                            jnp.copy(self.out_buf) if streaming else None,
                            tuple((s, self._slot_gen[s]) for s in live),
                        )
                        for ref in entry[:3]:
                            if ref is None:
                                continue
                            try:
                                # Start the device→host copy now; the
                                # fetch one iteration later should find
                                # it resolved.
                                ref.copy_to_host_async()
                            except Exception:
                                pass  # optional fast path only
                    depth, step_sync = self._retire_plain_step(
                        inflight, entry
                    )
                    self._account_step(
                        time.monotonic() - t0,
                        live,
                        width,
                        rider,
                        chunk_len,
                        depth,
                        step_sync,
                    )
                with obs_mod.phase("drive.collect"):
                    self._collect(self._active_np)
        self._pipe.clear()
