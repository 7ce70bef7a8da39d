"""The ``tpu://`` engine: local JAX inference over the device mesh.

The reference's L1 transport (litellm HTTP to remote APIs,
scripts/models.py:607-678) becomes: registry alias → checkpoint
materialized as a sharded param pytree on a {dp,tp,sp} mesh → batched
prefill + chunked decode (engine/generate.py). The thread-per-opponent
fan-out (models.py:699) becomes rows of one batch: every request for the
same model in a ``chat`` call decodes as one XLA program.

Heterogeneous opponent pools (SURVEY §7 hard part (b)): requests are
grouped by model alias; groups run sequentially with an LRU of loaded
models (weight swap). Same-model opponents — the common debate setup —
always batch.

Failure semantics (parity with reference retry/degrade policy,
models.py:46-47, 538-555): per-group exceptions are classified through the
resilience fault taxonomy (resilience/faults.py) and captured into
``Completion.error``; OOM/device-loss/preemption/timeout are marked
transient so the debate core's backoff retries them; a failed group never
kills the round. The chaos injector's ``generate`` and ``checkpoint_load``
seams live here.
"""

from __future__ import annotations

import math
import os
import sys
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from adversarial_spec_tpu import obs as obs_mod
from adversarial_spec_tpu.debate.usage import Usage
from adversarial_spec_tpu.engine import kvtier as kvtier_mod
from adversarial_spec_tpu.engine import prefix_cache as prefix_mod
from adversarial_spec_tpu.engine import registry as registry_mod
from adversarial_spec_tpu.engine import spec as spec_mod
from adversarial_spec_tpu.engine import streaming as stream_mod
from adversarial_spec_tpu.engine import weightres as weightres_mod
from adversarial_spec_tpu.engine.generate import (
    MIN_BUCKET,
    bucket_length,
    generate,
)
from adversarial_spec_tpu.engine.loader import materialize_params
from adversarial_spec_tpu.engine.scheduler import (
    ContinuousBatcher,
    SchedRequest,
)
from adversarial_spec_tpu.engine.registry import ModelSpec
from adversarial_spec_tpu.engine.tokenizer import (
    apply_chat_template,
    load_tokenizer,
)
from adversarial_spec_tpu.engine.types import (
    ChatRequest,
    Completion,
    SamplingParams,
    Served,
)
from adversarial_spec_tpu.models.config import ModelConfig
from adversarial_spec_tpu.parallel.mesh import (
    make_mesh,
    maybe_initialize_distributed,
)
from adversarial_spec_tpu.resilience import faults, injector
from adversarial_spec_tpu.resilience import lockdep as lockdep_mod

_GIB = 1 << 30


def hbm_budget_bytes() -> int:
    """Per-chip byte budget for resident model weights.

    Residency is BYTE-budgeted, not count-budgeted: two 8B bf16 models
    (~32 GB) exceed a v5e chip's 16 GB HBM, so a fixed two-model LRU
    would OOM on exactly the mix-families setup SKILL.md recommends.
    The budget is the device's reported HBM limit times a 0.75 headroom
    factor — the reserve covers KV cache, activations, and the
    transient peak while a swap is in flight. An accelerator that
    reports no limit is an error, not a guess; only the CPU backend
    (tests), which keeps no memory statistics, gets a stand-in 16 GiB.
    Override with ADVSPEC_HBM_BUDGET_BYTES (read per decision, so tests
    and operators can retune a live engine).
    """
    env = os.environ.get("ADVSPEC_HBM_BUDGET_BYTES")
    if env:
        return int(env)
    dev = jax.devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit <= 0:
        if dev.platform != "cpu":
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                "memory limit (memory_stats()['bytes_limit']); set "
                "ADVSPEC_HBM_BUDGET_BYTES to budget weight residency"
            )
        limit = 16 * _GIB
    return int(limit * 0.75)


def per_chip_param_bytes(params) -> int:
    """Per-chip bytes a (possibly sharded) param pytree occupies.

    Uses each leaf's sharding to count ONE device's shard — tp/sp-sharded
    weights divide across the mesh, dp-replicated ones do not. Works on
    concrete arrays and eval_shape/ShapeDtypeStruct trees alike; no data
    is fetched.
    """
    total = 0
    for leaf in jax.tree.leaves(params):
        shape = leaf.shape
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            try:
                shape = sharding.shard_shape(shape)
            except Exception:
                pass
        total += math.prod(shape) * np.dtype(leaf.dtype).itemsize
    return total


_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}

def _trim_prompt(ids: list[int], limit: int) -> list[int]:
    """Trim to ``limit`` tokens keeping the first token (BOS/template
    head) and the most recent tail — one definition for every serving
    path."""
    if limit > 0 and len(ids) > limit:
        return ids[:1] + ids[len(ids) - (limit - 1) :]
    return ids


@dataclass
class HostWeights:
    """A demoted model's host-resident shards plus everything needed to
    re-activate it with one committed ``device_put`` (the weight
    ledger's opaque payload — engine/weightres.py). ``shardings`` is
    the ORIGINAL params' sharding tree: promotion restores the exact
    jit signature the model compiled under, so re-promotion compiles
    nothing (the PR 5/6 committed-sharding discipline applied to
    params)."""

    spec: ModelSpec
    cfg: ModelConfig
    tokenizer: object
    mesh: object
    np_params: dict
    shardings: dict
    bytes_device: int


@dataclass
class LoadedModel:
    spec: ModelSpec
    cfg: ModelConfig
    params: dict
    tokenizer: object
    mesh: object
    last_used: float = 0.0
    bytes_per_chip: int = 0
    prefetched: bool = False  # loaded ahead of use by _maybe_prefetch
    # Persistent ContinuousBatcher (paged single-device serving): kept
    # alive ACROSS chat calls so its page pool + prefix cache carry one
    # round's spec/transcript KV into the next round's admissions —
    # the cross-round half of the prefix cache. Rebuilt when the shape
    # key (slots, capacity, budget, kv dtype, cache enablement) changes.
    batcher: object = None
    batcher_key: tuple | None = None


class TpuEngine:
    """Serves every ``tpu://`` alias; caches loaded models (weight swap).

    Residency is byte-budgeted against per-chip HBM (hbm_budget_bytes),
    and heterogeneous rounds overlap the NEXT group's weight load with
    the CURRENT group's decode (one background loader thread): device
    transfers are async, so the swap rides under compute instead of
    serializing after it (SURVEY §7 hard part (b)).
    """

    def __init__(self) -> None:
        self._models: dict[str, LoadedModel] = {}
        self._lock = lockdep_mod.make_lock("TpuEngine._lock")
        self._inflight: dict[str, Future] = {}
        # Estimated bytes of loads currently MATERIALIZING (foreground
        # or prefetch): counted alongside _models in every budget sum so
        # two concurrent loads can't each conclude they fit alone.
        self._loading: dict[str, int] = {}
        # The weight-residency state machine (engine/weightres.py):
        # resident/host/freed bookkeeping, eviction pins (mid-decode
        # models are acquire_weights-pinned, never victims), and the
        # host payloads evicted models demote into instead of paying a
        # full re-materialization on their next turn.
        self.ledger = weightres_mod.WeightLedger()
        # Demotions whose device→host gather is still in flight: the
        # victim is already out of _models (budget math stops counting
        # it) but not yet committed to the ledger's host tier. A load
        # of THAT alias must wait for the commit (then promote) instead
        # of racing a cold re-materialization against the gather;
        # loads of every other alias never block on the transfer.
        self._demoting: dict[str, threading.Event] = {}
        self.prefetch_hits = 0  # prefetched loads actually consumed

    def _committed_bytes_locked(self) -> int:
        """Resident + materializing bytes. Caller holds self._lock."""
        return sum(
            m.bytes_per_chip for m in self._models.values()
        ) + sum(self._loading.values())

    def validate(self, model: str) -> str | None:
        return registry_mod.validate_tpu_model(model)

    # -- model residency ---------------------------------------------------

    def _load(self, alias: str) -> LoadedModel:
        with self._lock:
            lm = self._models.get(alias)
            if lm is not None:
                # A completed prefetch pops its own _inflight entry
                # under the same lock that publishes the model, but
                # clear defensively on every hit so a stale future can
                # never shadow (or resurrect) an evicted model.
                self._inflight.pop(alias, None)
            fut = self._inflight.get(alias)
        if lm is not None:
            if lm.prefetched:
                self.prefetch_hits += 1
                lm.prefetched = False
            lm.last_used = time.monotonic()
            return lm
        if fut is not None:
            try:
                lm = fut.result()
            except Exception:
                lm = None  # prefetch died: retry on the caller's thread
            with self._lock:
                self._inflight.pop(alias, None)
            if lm is not None:
                self.prefetch_hits += 1
                lm.prefetched = False
                lm.last_used = time.monotonic()
                return lm
        self._wait_demoting(alias)
        if self.ledger.is_host(alias):
            # Demoted weights are host-resident: re-activate with one
            # committed device_put instead of a full materialization.
            return self._promote_sync(alias)
        return self._load_sync(alias)

    def _load_sync(
        self,
        alias: str,
        prefetched: bool = False,
        estimate: int | None = None,
        evict: bool = True,
        reserved: bool = False,  # caller already put alias in _loading
    ) -> LoadedModel:
        spec = registry_mod.resolve_model_spec(f"tpu://{alias}")
        dtype = _DTYPES.get(spec.dtype, jnp.bfloat16)
        maybe_initialize_distributed()
        mesh = make_mesh(spec.mesh)
        # Make room BEFORE materializing — otherwise both param sets
        # coexist in HBM during the swap. The estimate comes from
        # eval_shape + the real sharding rules, so it is exact. The
        # prefetch path passes evict=False (it already fit-checked and
        # must never evict on someone else's behalf) and its estimate
        # (no duplicate eval_shape trace).
        if estimate is None:
            estimate = self._estimate_per_chip_bytes(spec, dtype, mesh)
        if evict:
            # Eviction, the final fit check, and the reservation happen
            # under ONE lock hold (reserve_as) so two concurrent loads
            # can't both conclude they fit alone.
            self._evict_for(estimate, reserve_as=alias)
        elif not reserved:
            with self._lock:
                self._loading[alias] = estimate
        try:
            t_load = time.monotonic()
            params, cfg = self._materialize(spec, dtype, mesh)
            tokenizer = load_tokenizer(spec.tokenizer)
            if obs_mod.config().enabled:
                obs_mod.metrics.counter(
                    "advspec_model_loads_total",
                    help="model materializations (foreground + prefetch)",
                ).inc()
                obs_mod.metrics.histogram(
                    "advspec_model_load_seconds",
                    help="checkpoint materialization + tokenizer wall",
                ).observe(time.monotonic() - t_load)
            lm = LoadedModel(
                spec=spec,
                cfg=cfg,
                params=params,
                tokenizer=tokenizer,
                mesh=mesh,
                last_used=time.monotonic(),
                bytes_per_chip=per_chip_param_bytes(params) or estimate,
                prefetched=prefetched,
            )
            with self._lock:
                # Publish and retire the in-flight marker atomically: a
                # concurrent _load sees the alias in exactly one of
                # _models / _inflight, never neither.
                self._models[alias] = lm
                self._inflight.pop(alias, None)
            self.ledger.admit_load(
                alias, lm.bytes_per_chip, time.monotonic() - t_load
            )
            return lm
        finally:
            with self._lock:
                self._loading.pop(alias, None)

    def _estimate_per_chip_bytes(self, spec: ModelSpec, dtype, mesh) -> int:
        """Per-chip weight bytes the alias WILL occupy, before loading.

        eval_shape over the same builder _materialize uses (init +
        optional int8 quantization), mapped through the real sharding
        rules — no memory is touched.
        """
        from adversarial_spec_tpu.models.config import get_config
        from adversarial_spec_tpu.models.transformer import init_params
        from adversarial_spec_tpu.ops.quant import quantize_params
        from adversarial_spec_tpu.parallel.sharding import param_shardings

        cfg = get_config(
            spec.family, spec.size, spec.max_seq_len, spec.n_layers,
            spec.experts_held, spec.vocab_rows,
        )

        def build():
            p = init_params(jax.random.key(0), cfg, dtype)
            return quantize_params(p, fmt=spec.quant) if spec.quant else p

        shapes = jax.eval_shape(build)
        shardings = param_shardings(mesh, shapes)
        abstract = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes,
            shardings,
        )
        return per_chip_param_bytes(abstract)

    def _evict_for(
        self, needed_bytes: int, reserve_as: str | None = None
    ) -> None:
        """Evict LRU models until ``needed_bytes`` fits in the budget.

        Pinned aliases (mid-decode) are never victims. If everything
        evictable is gone and the budget still doesn't fit, proceed and
        let the device's own OOM surface as a transient error (the
        debate core retries after backoff) — a hard refusal here would
        also block single models legitimately larger than the estimate.
        """
        budget = hbm_budget_bytes()
        while True:
            with self._lock:
                resident = self._committed_bytes_locked()
                if not self._models or resident + needed_bytes <= budget:
                    break
                victims = [
                    a for a in self._models if not self.ledger.pinned(a)
                ]
                if not victims:
                    break
                oldest = min(
                    victims, key=lambda a: self._models[a].last_used
                )
                lm, ev = self._pop_for_demotion_locked(oldest)
            # The device→host gather runs OUTSIDE the engine lock: a
            # concurrent hit on an already-resident model must not
            # stall behind a GB-scale transfer. Budget math is already
            # right — the pop removed the victim from the committed
            # sum, and the _demoting event (registered under the same
            # lock hold) makes a racing load of the VICTIM wait for
            # the ledger commit instead of cold-loading against it.
            self._demote_popped(oldest, lm, ev)
        with self._lock:
            resident = self._committed_bytes_locked()
            if reserve_as is not None:
                # Reserve atomically with the final fit check: a
                # concurrent load's check now sees these bytes.
                self._loading[reserve_as] = needed_bytes
        if resident + needed_bytes > budget:
            print(
                f"warning: model needs {needed_bytes >> 20} MiB with "
                f"{resident >> 20} MiB pinned-resident, budget "
                f"{budget >> 20} MiB — loading anyway (OOM will retry "
                "as transient)",
                file=sys.stderr,
            )

    def _pop_for_demotion_locked(
        self, alias: str
    ) -> tuple[LoadedModel, threading.Event]:
        """Take one model out of the loaded dict for demotion. The
        batcher's device state (pool pages, row buffers) goes with the
        weights: a demoted model must hold ZERO HBM, and an unbounded
        per-model batcher cache is a leak in a long-lived serve daemon
        (its KV survives only through the tiered store's write-through,
        which already flushed at drain end). Caller holds
        ``self._lock``; the returned event is registered under the same
        hold, so a racing load of this alias observes the model in
        exactly one of _models / _demoting / the ledger's host tier."""
        lm = self._models.pop(alias)
        lm.batcher = None
        lm.batcher_key = None
        ev = threading.Event()
        self._demoting[alias] = ev
        return lm, ev

    def _demote_popped(
        self, alias: str, lm: LoadedModel, ev: threading.Event
    ) -> None:
        """Finish one eviction outside the engine lock. With weight
        paging armed the (typically quantized) shards demote to the
        host tier — the device→host copies are STARTED async for every
        leaf before any is resolved, so the gather overlaps itself;
        with paging off this is the classic free-and-reload
        eviction."""
        try:
            if not weightres_mod.paging_armed():
                self.ledger.free_model(alias)
                return
            t0 = time.monotonic()
            for leaf in jax.tree.leaves(lm.params):
                try:
                    leaf.copy_to_host_async()
                except AttributeError:  # non-jax leaf (tests)
                    pass
            np_params = jax.tree.map(np.asarray, lm.params)
            shardings = jax.tree.map(
                lambda x: getattr(x, "sharding", None), lm.params
            )
            holder = HostWeights(
                spec=lm.spec,
                cfg=lm.cfg,
                tokenizer=lm.tokenizer,
                mesh=lm.mesh,
                np_params=np_params,
                shardings=shardings,
                bytes_device=lm.bytes_per_chip,
            )
            bytes_host = sum(
                leaf.nbytes for leaf in jax.tree.leaves(np_params)
            )
            self.ledger.demote_model(
                alias, holder, bytes_host, time.monotonic() - t0
            )
        finally:
            with self._lock:
                self._demoting.pop(alias, None)
            ev.set()

    def _wait_demoting(self, alias: str) -> None:
        """Block until an in-flight demotion of ``alias`` (if any)
        commits to the ledger — the racing loader then promotes the
        freshly demoted shards instead of cold-loading against the
        gather. Never blocks for other aliases."""
        with self._lock:
            ev = self._demoting.get(alias)
        if ev is not None:
            ev.wait()

    def _promote_sync(
        self,
        alias: str,
        prefetched: bool = False,
        evict: bool = True,
        reserved: bool = False,
    ) -> LoadedModel:
        """Re-activate a host-demoted model: one async ``device_put``
        of the saved shards into their ORIGINAL shardings (committed —
        promoted params present the same jit signature the model
        compiled under, so nothing recompiles), dispatched without
        blocking so a prefetch-thread promotion overlaps the current
        model's decode. A fault mid-swap (the ``weight_swap`` chaos
        seam fires here) leaves the host entry untouched: only the
        waiting admission degrades, and the swap is declared
        (``swap_fault`` WeightEvent), never silent."""
        holder = self.ledger.peek_host(alias)
        if holder is None or not isinstance(holder.payload, HostWeights):
            return self._load_sync(
                alias, prefetched=prefetched, reserved=reserved
            )
        hw: HostWeights = holder.payload
        try:
            injector.fire("weight_swap")
            if evict:
                self._evict_for(hw.bytes_device, reserve_as=alias)
            elif not reserved:
                with self._lock:
                    self._loading[alias] = hw.bytes_device
            t0 = time.monotonic()
            params = jax.tree.map(
                lambda arr, sh: (
                    jax.device_put(arr, sh) if sh is not None
                    else jnp.asarray(arr)
                ),
                hw.np_params,
                hw.shardings,
            )
            lm = LoadedModel(
                spec=hw.spec,
                cfg=hw.cfg,
                params=params,
                tokenizer=hw.tokenizer,
                mesh=hw.mesh,
                last_used=time.monotonic(),
                bytes_per_chip=hw.bytes_device,
                prefetched=prefetched,
            )
            with self._lock:
                self._models[alias] = lm
                self._inflight.pop(alias, None)
            self.ledger.promote_model(
                alias,
                hw.bytes_device,
                time.monotonic() - t0,
                overlapped=prefetched,
            )
            return lm
        except BaseException:
            # Conservation: the host entry was never consumed — the
            # next _load retries the promotion; the fault evicts only
            # the admission that was waiting on this swap.
            self.ledger.note_swap_fault(alias)
            raise
        finally:
            with self._lock:
                self._loading.pop(alias, None)

    def check_residency_invariants(self) -> None:
        """Ledger conservation plus the ledger↔engine mirror: the
        ledger's resident set must be exactly the engine's loaded-model
        dict, and no demoted model may still hold a batcher (chaos
        drills and tests call this after every drill step)."""
        # Settle in-flight demotions first: mid-gather a victim is
        # transiently in neither _models nor the host tier (by design),
        # which is drift only if it persists past the commit.
        with self._lock:
            pending = list(self._demoting.values())
        for ev in pending:
            ev.wait()
        self.ledger.check_invariants()
        with self._lock:
            resident = set(self.ledger.resident_aliases())
            loaded = set(self._models)
        if resident != loaded:
            raise RuntimeError(
                f"weight ledger/engine drift: ledger resident "
                f"{sorted(resident)} != loaded models {sorted(loaded)}"
            )

    def _maybe_prefetch(self, alias: str) -> None:
        """Queue a background load of ``alias`` (non-blocking).

        All real work — spec resolution, the eval_shape estimate, the
        fit check, materialization — happens on the loader thread, so
        the serving path pays only two dict probes. chat() calls this
        AFTER the current group's model is loaded and pinned, so the
        fit check sees the full resident set.
        """
        with self._lock:
            if alias in self._models or alias in self._inflight:
                return
            fut: Future = Future()
            self._inflight[alias] = fut
        # A DAEMON thread, not a ThreadPoolExecutor: pool threads are
        # non-daemon and concurrent.futures joins them at interpreter
        # exit, so a prefetch stuck in a device call would hang the CLI
        # at exit. A daemon thread dies with the process instead; the
        # future carries results/exceptions exactly as before.
        def _work() -> None:
            try:
                fut.set_result(self._prefetch_task(alias))
            except BaseException as e:  # future owns error delivery
                fut.set_exception(e)

        try:
            threading.Thread(
                target=_work, daemon=True, name=f"advspec-prefetch-{alias}"
            ).start()
        except BaseException as e:
            # start() failing (thread exhaustion) must not leave a
            # forever-pending future registered — later loads would
            # block on it without timeout.
            with self._lock:
                self._inflight.pop(alias, None)
            fut.set_exception(e)

    def _prefetch_task(self, alias: str) -> LoadedModel | None:
        """Background half of _maybe_prefetch.

        Prefetch never evicts (the active model is mid-decode and
        pinned; evicting idle models during someone else's decode is a
        policy decision the foreground loader makes with better
        information): if the alias doesn't fit beside everything
        resident, give up — the load then serializes at use time,
        exactly as before prefetching existed. Exceptions stay in the
        future; the foreground _load falls back to a sync load and owns
        error reporting.
        """
        try:
            # A demotion of this alias may still be gathering: wait for
            # its ledger commit (cheap — this is the background thread)
            # so the prefetch promotes the shards instead of racing a
            # cold load against the transfer.
            self._wait_demoting(alias)
            host_entry = self.ledger.peek_host(alias)
            if host_entry is not None and isinstance(
                host_entry.payload, HostWeights
            ):
                # Host-demoted weights: the prefetch is a PROMOTION —
                # the async host→device transfer rides under the
                # current model's decode, which is the entire point of
                # overlapped swap (swap-overlap fraction in
                # perf.weights counts exactly these).
                estimate = host_entry.payload.bytes_device
            else:
                host_entry = None
                spec = registry_mod.resolve_model_spec(f"tpu://{alias}")
                dtype = _DTYPES.get(spec.dtype, jnp.bfloat16)
                mesh = make_mesh(spec.mesh)
                estimate = self._estimate_per_chip_bytes(spec, dtype, mesh)
            with self._lock:
                fits = (
                    self._committed_bytes_locked() + estimate
                    <= hbm_budget_bytes()
                )
                if fits:
                    # Reserve atomically with the check: a concurrent
                    # foreground load's budget math must see these
                    # bytes before this thread starts materializing.
                    self._loading[alias] = estimate
            if fits and host_entry is not None:
                return self._promote_sync(
                    alias, prefetched=True, evict=False, reserved=True
                )
            if fits:
                return self._load_sync(
                    alias,
                    prefetched=True,
                    estimate=estimate,
                    evict=False,
                    reserved=True,
                )
            return None
        finally:
            # _load_sync pops the markers when it publishes; pop here
            # for the not-fits and exception exits (including a raise
            # before _load_sync's own try/finally) so a dead future or
            # stale reservation never blocks later loads of this alias.
            with self._lock:
                if not isinstance(self._models.get(alias), LoadedModel):
                    self._inflight.pop(alias, None)
                    self._loading.pop(alias, None)

    def _materialize(self, spec: ModelSpec, dtype, mesh):
        """Params via the fastest available source: native Orbax cache
        (converted once, restored straight into target shardings) →
        HF safetensors conversion (then cached) → synthetic init."""
        from adversarial_spec_tpu.engine import checkpoint as ckpt_mod
        from adversarial_spec_tpu.models.config import get_config
        from adversarial_spec_tpu.models.transformer import init_params
        from adversarial_spec_tpu.ops.quant import quantize_params
        from adversarial_spec_tpu.parallel.sharding import param_shardings

        import shutil
        import sys

        injector.fire("checkpoint_load")
        quantize = bool(spec.quant)
        cfg = get_config(
            spec.family, spec.size, spec.max_seq_len, spec.n_layers,
            spec.experts_held, spec.vocab_rows,
        )
        cache_path = None
        if spec.checkpoint != "random":
            cache_path = ckpt_mod.cache_dir_for(
                spec.checkpoint,
                spec.family,
                spec.size,
                spec.dtype,
                spec.quant,
                tied_embeddings=cfg.tied_embeddings,
                n_layers=spec.n_layers,
            )
        if cache_path is not None and ckpt_mod.has_native(cache_path):
            # Cache is an optimization in BOTH directions: a corrupt or
            # layout-incompatible cache falls back to HF conversion
            # instead of permanently breaking the model.
            try:
                # The restore template must match the layout the cache was
                # SAVED with: same transposed-head flag reading as
                # load_hf_checkpoint and the cache fingerprint (a toggled
                # env selects a different cache dir rather than failing
                # restore against this template).
                t_head = ckpt_mod.transposed_head_flag()

                def build():
                    p = init_params(
                        jax.random.key(0), cfg, dtype,
                        transposed_head=t_head,
                    )
                    return (
                        quantize_params(p, fmt=spec.quant)
                        if quantize
                        else p
                    )

                shapes = jax.eval_shape(build)
                shardings = param_shardings(mesh, shapes)
                abstract = jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(
                        s.shape, s.dtype, sharding=sh
                    ),
                    shapes,
                    shardings,
                )
                return ckpt_mod.load_native(cache_path, abstract), cfg
            except Exception as e:
                print(
                    f"warning: native checkpoint cache unreadable "
                    f"({e}); reconverting from HF",
                    file=sys.stderr,
                )
                shutil.rmtree(cache_path, ignore_errors=True)

        params, cfg = materialize_params(
            spec.checkpoint,
            spec.family,
            spec.size,
            dtype=dtype,
            max_seq_len=spec.max_seq_len,
            n_layers=spec.n_layers,
            mesh=mesh,
            quant=spec.quant,
            experts_held=spec.experts_held,
            vocab_rows=spec.vocab_rows,
        )
        if cache_path is not None:
            try:  # write side is best-effort too
                ckpt_mod.save_native(params, cache_path)
            except Exception as e:
                print(
                    f"warning: native checkpoint cache write failed: {e}",
                    file=sys.stderr,
                )
        return params, cfg

    # -- serving -----------------------------------------------------------

    def chat(
        self,
        requests: list[ChatRequest],
        params: SamplingParams,
        consumer=None,
    ) -> list[Completion]:
        if obs_mod.config().enabled:
            obs_mod.metrics.counter(
                "advspec_engine_chat_requests_total",
                help="chat requests by serving engine",
                engine="tpu",
            ).inc(len(requests))
        # Group by alias: same-model opponents batch into one decode.
        groups: dict[str, list[int]] = {}
        for i, req in enumerate(requests):
            alias = registry_mod.parse_tpu_model_id(req.model)
            groups.setdefault(alias, []).append(i)

        # Residency-aware group order: serve the groups whose weights
        # are ALREADY resident before any group that forces a swap —
        # under a pool-larger-than-HBM budget this turns "one swap per
        # group" into "at most (pool − resident) swaps per round".
        # Groups decode independently, so reordering cannot change any
        # row's greedy tokens; the output list is re-indexed by the
        # original request positions either way.
        aliases = self.ledger.resident_first(list(groups))
        groups = {a: groups[a] for a in aliases}
        out: list[Completion | None] = [None] * len(requests)
        for gi, (alias, indices) in enumerate(groups.items()):
            batch = [requests[i] for i in indices]
            # The caller's stream consumer indexes rows of ITS batch;
            # re-map each group's row back through the group indices.
            group_consumer = None
            if consumer is not None:
                def group_consumer(
                    row, text, *n_tokens, _c=consumer, _ix=tuple(indices)
                ):
                    return _c(_ix[row], text, *n_tokens)

                group_consumer.wants_n_tokens = stream_mod.wants_n_tokens(
                    consumer
                )
            try:
                completions = self._chat_one_model(
                    alias,
                    batch,
                    params,
                    # Overlap the next group's weight load with this
                    # group's decode (async transfers ride under
                    # compute). Launched inside _chat_one_model, after
                    # this group's model is loaded and pinned, so the
                    # prefetch fit check sees the full resident set.
                    prefetch_next=(
                        aliases[gi + 1] if gi + 1 < len(aliases) else None
                    ),
                    consumer=group_consumer,
                )
            except Exception as e:  # degrade, never raise (parity: ref)
                msg = f"{type(e).__name__}: {e}"
                kind = faults.classify(e)
                # Injected faults know their seam; real ones are counted
                # where caught.
                faults.record(kind, getattr(e, "seam", "generate"))
                obs_mod.emit(
                    obs_mod.FaultEvent(
                        seam=getattr(e, "seam", "generate"),
                        kind=kind.value,
                        error=msg,
                        # Group-level failure: the round's trace, no
                        # single victim span.
                        trace_id=batch[0].trace_id if batch else "",
                    )
                )
                obs_mod.autodump("fault")
                completions = [
                    Completion(error=msg, transient=kind.transient)
                    for _ in batch
                ]
            for i, comp in zip(indices, completions):
                out[i] = comp
        return [c for c in out if c is not None]

    def _chat_one_model(
        self,
        alias: str,
        batch: list[ChatRequest],
        params: SamplingParams,
        prefetch_next: str | None = None,
        consumer=None,
    ) -> list[Completion]:
        # Pin BEFORE loading: from the moment this model can be resident
        # it must not be an eviction/demotion victim of a concurrent
        # background load (eviction only drops the dict entry; a
        # foreground reference would keep the bytes alive while the
        # budget math believes them freed). acquire/release is the
        # ledger's refcount pair — GL-REFCOUNT enforces the
        # try/finally shape.
        self.ledger.acquire_weights(alias)
        try:
            lm = self._load(alias)
            if prefetch_next is not None:
                self._stage_next(prefetch_next)
            injector.fire("generate")
            return self._chat_loaded(lm, batch, params, consumer)
        finally:
            self.ledger.release_weights(alias)

    def _stage_next(self, alias: str) -> None:
        """Make the NEXT group's swap overlap this group's decode: when
        the next model is host-demoted and HBM is full, demote the LRU
        resident NOW (the current group's model is pinned and can't be
        the victim) so the background promotion fits — without this,
        a budget-saturated pool can never overlap a promotion, because
        the prefetch thread refuses to evict on anyone's behalf. Only
        the cheap host-resident case stages eagerly (its byte estimate
        is already known); cold loads keep the fit-check-only prefetch
        policy."""
        entry = self.ledger.peek_host(alias)
        if entry is not None and isinstance(entry.payload, HostWeights):
            needed = entry.payload.bytes_device
            with self._lock:
                fits = (
                    self._committed_bytes_locked() + needed
                    <= hbm_budget_bytes()
                )
            if not fits:
                self._evict_for(needed)
        self._maybe_prefetch(alias)

    def _chat_loaded(
        self,
        lm: LoadedModel,
        batch: list[ChatRequest],
        params: SamplingParams,
        consumer=None,
    ) -> list[Completion]:
        tok = lm.tokenizer
        instruct = lm.spec.checkpoint != "random"

        prompts = []
        with obs_mod.phase("engine.tokenize"):
            for req in batch:
                text = apply_chat_template(
                    lm.spec.family, req.system, req.user, instruct
                )
                ids = tok.encode(text)
                # Reserve room for generation within the model's context.
                prompts.append(
                    _trim_prompt(
                        ids, lm.cfg.max_seq_len - params.max_new_tokens
                    )
                )

        # Paged single-device specs serve through the continuous batcher:
        # opponents occupy decode slots, early-EOS rows free their pages
        # mid-round, and queued requests (opponent pools larger than the
        # slot count) admit into freed slots without waiting for the whole
        # batch. Sharded meshes keep the round-synchronous generate()
        # (its paged path shards the pool over dp), as do budgets so large
        # that no bucketed prompt passes the batcher's context check (the
        # dense path has no such check and still serves them).
        fits_batcher = (
            lm.cfg.max_seq_len - params.max_new_tokens >= MIN_BUCKET
        )
        if lm.spec.kv == "paged" and lm.mesh.size == 1 and fits_batcher:
            return self._chat_continuous(lm, prompts, params, batch, consumer)
        # The round-synchronous generate() fallback has no per-request
        # token stream (one fused program decodes the whole batch to
        # budget): consumers are served the blocking result only —
        # streaming and early cancellation are batcher-path features
        # (docs/streaming.md).

        t0 = time.monotonic()
        with lm.mesh:
            result = generate(
                lm.params,
                lm.cfg,
                prompts,
                max_new_tokens=params.max_new_tokens,
                eos_ids=list(tok.eos_ids),
                pad_id=tok.pad_id,
                greedy=params.greedy,
                temperature=params.temperature,
                top_k=params.top_k,
                top_p=params.top_p,
                seed=params.seed,
                timeout_s=params.timeout_s,
                mesh=lm.mesh,
                paged=lm.spec.kv == "paged",
                kv_dtype=lm.spec.kv_dtype,
            )
        total_time = time.monotonic() - t0

        # Per-row attribution: decode time proportional to each row's
        # actual decoded tokens (an early-EOS row consumed fewer decode
        # steps than a full-budget row); the prefill/overhead remainder
        # splits evenly (prefill is genuinely shared batch work). Row
        # sums reproduce the call totals exactly.
        tok_total = float(result.n_generated.sum())
        prefill_share = (total_time - result.decode_time_s) / len(batch)
        completions = []
        for row, req in enumerate(batch):
            n = int(result.n_generated[row])
            frac = (n / tok_total) if tok_total > 0 else 1.0 / len(batch)
            decode_share = result.decode_time_s * frac
            text = tok.decode(result.tokens[row, :n])
            completions.append(
                Completion(
                    text=text,
                    usage=Usage(
                        input_tokens=len(prompts[row]),
                        output_tokens=n,
                        device_time_s=prefill_share + decode_share,
                        decode_tokens=n,
                        decode_time_s=decode_share,
                        # Batch prefill is shared work; an even split is
                        # the honest per-row attribution.
                        prefill_time_s=result.prefill_time_s / len(batch),
                    ),
                )
            )
        return completions

    def _chat_continuous(
        self,
        lm: LoadedModel,
        prompts: list[list[int]],
        params: SamplingParams,
        batch: list[ChatRequest] | None = None,
        consumer=None,
    ) -> list[Completion]:
        """Serve one model's requests through the ContinuousBatcher.

        Pool capacity is bucketed to a power of two so repeat rounds of
        similar size reuse the compiled chunk program (pool shape is a
        jit constant). ``batch`` carries the callers' ChatRequests so
        each SchedRequest inherits its causal trace/span ids — the hop
        that ties a debate round to the device steps that served it.
        """
        tok = lm.tokenizer
        # The batcher checks bucket_length(prompt) + budget against the
        # model context; the engine-level trim above only bounded the RAW
        # length, so a near-limit prompt would round up past the context
        # and error the whole group. Re-trim against the bucketed length.
        max_prompt = lm.cfg.max_seq_len - params.max_new_tokens
        while max_prompt > 1 and bucket_length(max_prompt) > max_prompt:
            nxt = bucket_length(max_prompt) // 2
            if nxt >= max_prompt:  # at the minimum bucket already
                break
            max_prompt = nxt
        prompts = [_trim_prompt(p, max_prompt) for p in prompts]
        # Pool capacity covers CONCURRENT residency (the max_batch largest
        # requests), not the whole queue — finished rows free their pages
        # and queued requests admit into them; sizing by the queue total
        # would make pool HBM scale with round size, which is exactly what
        # paging exists to avoid. A request occupies its REAL length under
        # the prefix cache's canonical layout and its left-padded bucket
        # otherwise (the batcher's own rule, ContinuousBatcher.submit):
        # charging the bucket either way doubles the pool for a prompt
        # just past a power of two, and at 7B widths that no longer fits
        # beside the weights.
        n_slots = min(len(prompts), 8)
        canonical = prefix_mod.config().enabled
        per_req = sorted(
            (
                (len(p) if canonical else bucket_length(len(p)))
                + params.max_new_tokens
                for p in prompts
            ),
            reverse=True,
        )
        need = sum(per_req[:n_slots])
        capacity = 2048
        while capacity < need:
            capacity *= 2

        seed = (
            params.seed
            if params.seed is not None
            # seed=None means fresh entropy (as generate() does) —
            # pinning 0 would make every unseeded round sample
            # identically.
            else int.from_bytes(os.urandom(4), "little")
        )
        batcher_key = (
            n_slots,
            capacity,
            params.max_new_tokens,
            lm.spec.kv_dtype,
            prefix_mod.config().enabled,
            prefix_mod.config().max_pages,
            # The batcher snapshots the tiered-KV knobs at construction:
            # flipping --no-kv-tier, the host budget, or the store dir
            # between rounds must rebuild the tiers (and re-fingerprint
            # the store) rather than keep serving under the old config.
            kvtier_mod.config().enabled,
            kvtier_mod.config().host_mb,
            kvtier_mod.config().store_dir,
        )
        t0 = time.monotonic()
        try:
            results, decode_time = self._run_batcher(
                lm, batcher_key, prompts, params, seed, batch, consumer
            )
        except BaseException:
            # An escaping exception (decode fault whose donated-state
            # probe failed, submit validation mid-loop, timeout plumbing)
            # leaves the batcher mid-drain: stale results, occupied
            # slots, leaked sequences. Reusing it next round would
            # replay that corruption — drop it; the next call rebuilds.
            lm.batcher = None
            lm.batcher_key = None
            raise
        total_time = time.monotonic() - t0

        # Same attribution scheme as the dense path: decode time splits
        # by decoded tokens, the prefill/overhead remainder evenly. No
        # double-billing under the fused loop: the batcher PARTITIONS
        # each fused step's wall clock between its decode counter and
        # the riding admission's prefill_time_s (token-share split), so
        # ``overhead`` (= total - decode) contains every prefill second
        # exactly once and a row's decode_share never re-counts time
        # already attributed to another row's admission.
        tok_total = float(sum(r.n_generated for r in results)) or 1.0
        overhead = total_time - decode_time
        completions = []
        with obs_mod.phase("engine.finish"):
            for r in results:  # sorted by req_id == prompt order
                frac = r.n_generated / tok_total
                decode_share = decode_time * frac
                completions.append(
                    Completion(
                        # Fault-evicted rows keep their partial decode
                        # in ``text`` (diagnostic value) but carry the
                        # error so the debate core's retry/degrade
                        # policy applies. Cancelled rows are CLEAN
                        # partials: the consumer read everything it
                        # needed before stopping them.
                        text=tok.decode(r.tokens[: r.n_generated]),
                        error=r.error,
                        cancelled=r.cancelled,
                        transient=(
                            r.fault_kind is not None
                            and faults.FaultKind(r.fault_kind).transient
                        ),
                        usage=Usage(
                            input_tokens=len(prompts[r.req_id]),
                            output_tokens=r.n_generated,
                            device_time_s=(
                                overhead / len(results) + decode_share
                            ),
                            decode_tokens=r.n_generated,
                            decode_time_s=decode_share,
                            cached_tokens=r.cached_tokens,
                            prefill_time_s=r.prefill_time_s,
                        ),
                        # What the batcher itself measured for this
                        # request, and the ids it was given and served
                        # (the daemon's ``timing`` /
                        # ``return_token_ids``).
                        served=Served(
                            prompt_token_ids=prompts[r.req_id],
                            token_ids=r.tokens[: r.n_generated],
                            batcher_queue_s=r.queue_wait_s,
                            prefill_s=r.prefill_time_s,
                            decode_s=r.decode_time_s,
                        ),
                    )
                )
        return completions

    @staticmethod
    def _make_stream_callback(tok, consumer, row):
        """Incremental detokenization for one request: the batcher
        hands ALL emitted ids so far (monotone supersets); decode the
        full prefix each delivery — a partial multi-byte token decodes
        differently once its continuation arrives, and HF detokenizers
        are not concatenative in general (metaspace/whitespace joining),
        so suffix-diffing could hand the consumer text the blocking
        path never produces, breaking the seam's byte-parity guarantee.
        The full re-decode is a DELIBERATE O(n²/chunk) host cost:
        deliveries happen once per fetched chunk (not per token), n is
        capped by max_new_tokens, and it is paid only while a consumer
        is attached — cheap against the 32 model forwards each chunk
        represents. Returning False asks the batcher to cancel the
        request mid-decode."""

        # A consumer may ask for the count of ids behind each text (the
        # daemon's ``n_tokens`` on stream events).
        counted = stream_mod.wants_n_tokens(consumer)

        def on_tokens(token_ids) -> bool:
            n_tokens = (len(token_ids),) if counted else ()
            return bool(consumer(row, tok.decode(token_ids), *n_tokens))

        return on_tokens

    def _run_batcher(
        self, lm, batcher_key, prompts, params, seed, batch=None,
        consumer=None,
    ):
        """Acquire (reuse or build) the model's persistent batcher and
        drain this call's requests through it.

        Returns ``(results, decode_time_s)`` where the decode time is
        THIS call's delta on the (cumulative) batcher counter. The
        watermark is per-call local state — engine-instance storage
        would be shared mutable telemetry that misattributes decode time
        whenever two drains interleave on one engine."""
        tok = lm.tokenizer
        n_slots, capacity = batcher_key[0], batcher_key[1]
        with lm.mesh, obs_mod.phase("engine.acquire_batcher"):
            if lm.batcher is not None and lm.batcher_key == batcher_key:
                # Round R+1 reuses round R's batcher: same compiled chunk
                # programs AND a warm prefix cache (the shared
                # spec+transcript prefix admits as a page-table adopt +
                # delta prefill instead of a full re-prefill).
                batcher = lm.batcher
                batcher.reconfigure_sampling(
                    greedy=params.greedy,
                    temperature=params.temperature,
                    top_k=params.top_k,
                    top_p=params.top_p,
                    seed=seed,
                )
                # Speculation knobs re-resolve from the process config
                # every drain (one CLI invocation = one round; a later
                # round's --no-speculative/--gamma must reach the
                # persistent batcher). The batcher is idle here —
                # run_all drains fully — so the flip is legal.
                sp = spec_mod.config()
                batcher.reconfigure_speculative(
                    enabled=sp.enabled, gamma=sp.gamma
                )
            else:
                batcher = ContinuousBatcher(
                    lm.params,
                    lm.cfg,
                    max_batch=n_slots,
                    capacity_tokens=capacity,
                    max_new_cap=params.max_new_tokens,
                    eos_ids=list(tok.eos_ids),
                    greedy=params.greedy,
                    temperature=params.temperature,
                    top_k=params.top_k,
                    top_p=params.top_p,
                    seed=seed,
                    # Same KV precision on both serving paths: the
                    # round-synchronous fallback passes spec.kv_dtype to
                    # generate(); the batcher must honor it too (int8
                    # pages + scale pages).
                    kv_dtype=lm.spec.kv_dtype,
                )
                lm.batcher = batcher
                lm.batcher_key = batcher_key
                if obs_mod.config().enabled:
                    obs_mod.hot.batcher_builds.inc()
        with lm.mesh:
            # Per-round telemetry delta: the persistent batcher's
            # counters accumulate across rounds.
            decode_t0 = batcher.decode_time_s
            stream_on = consumer is not None and stream_mod.config().enabled
            for i, ids in enumerate(prompts):
                src = batch[i] if batch is not None else None
                batcher.submit(
                    SchedRequest(
                        req_id=i,
                        prompt_ids=ids,
                        max_new_tokens=params.max_new_tokens,
                        # Per-request watchdog: a hung/slow request is
                        # evicted as TIMEOUT at this deadline while
                        # co-residents keep decoding (0 = disabled).
                        deadline_s=params.request_deadline_s,
                        # Trace propagation: the opponent request's ids
                        # ride into per-slot batcher state so every
                        # event of every device step resolves back to
                        # the debate round that caused it.
                        trace_id=src.trace_id if src is not None else "",
                        span_id=src.span_id if src is not None else "",
                        on_tokens=(
                            self._make_stream_callback(tok, consumer, i)
                            if stream_on
                            else None
                        ),
                    )
                )
            with obs_mod.phase("engine.run_all"):
                results = batcher.run_all(timeout_s=params.timeout_s)
            return results, batcher.decode_time_s - decode_t0
