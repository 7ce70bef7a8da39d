"""``[tool.graftlint]`` configuration, read from pyproject.toml.

Python here is 3.10 (no stdlib ``tomllib``) and third-party TOML readers
are not installable, so this module carries a deliberately small reader
for the subset pyproject actually uses: ``key = value`` pairs inside one
table, where value is a string, integer, boolean, or a (possibly
multi-line) array of strings. That subset is a hard contract — the
reader raises on anything it does not understand rather than guessing.

Every knob has a code default equal to the committed pyproject value, so
the linter still runs (e.g. on a fixture tree in a tempdir) when no
pyproject is present.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path


@dataclass(frozen=True)
class LockGuard:
    """One decoded ``lock_guards`` entry: a declared lock, the
    attribute aliases that count as holding it, and the state it
    guards. ``classname`` is "" for module-level locks; ``guarded``
    names instance attributes (class locks) or module globals."""

    module: str
    classname: str
    lock_attr: str
    aliases: tuple[str, ...]  # includes lock_attr itself
    guarded: tuple[str, ...]

    @property
    def name(self) -> str:
        """Canonical lock name — matches the runtime lockdep wrapper
        name so the static order graph and the sanitizer's violation
        reports speak one vocabulary."""
        if self.classname:
            return f"{self.classname}.{self.lock_attr}"
        return f"{self.module.rsplit('.', 1)[-1]}.{self.lock_attr}"


@dataclass
class GraftlintConfig:
    # Root package the domain rules reason about.
    package: str = "adversarial_spec_tpu"
    # Decorators that keep the wrapped function's calling convention
    # (GL-ARITY skips functions under anything else). Hoisted from
    # astlint's _SIG_PRESERVING.
    sig_preserving_decorators: list[str] = field(
        default_factory=lambda: [
            "jax.jit",
            "jit",
            "functools.lru_cache",
            "lru_cache",
            "functools.cache",
            "functools.wraps",
            "staticmethod",
            "classmethod",
            "contextmanager",
            "contextlib.contextmanager",
            "dataclass",
            "dataclasses.dataclass",
            "abstractmethod",
            "abc.abstractmethod",
            "pytest.fixture",
            "override",
        ]
    )
    # --- GL-SYNC -----------------------------------------------------
    # The class whose methods must not sync the host outside sanctioned
    # points (every indexed module is scanned for it), and the methods
    # allowed to sync blanket-style (hoisted from astlint's
    # _SCHEDULER_SYNC_ALLOWLIST).
    sync_class: str = "ContinuousBatcher"
    sync_allowlist: list[str] = field(
        default_factory=lambda: ["_advance_admission"]
    )
    # Attribute names whose values live on device inside the sync class
    # (``self.active``, ``adm.pads`` …): an np.asarray / int() / bool()
    # / .item() touching any of these is an implicit host sync.
    sync_device_attrs: list[str] = field(
        default_factory=lambda: [
            "pool",
            "page_table",
            "cur_tok",
            "cur_len",
            "pad_lens",
            "n_emitted",
            "max_new",
            "active",
            "out_buf",
            "last_logits",
            "pads",
        ]
    )
    # Bare local names that hold device values in the sync class but
    # whose provenance the dataflow engine cannot derive. Since the
    # interprocedural port this list holds ONLY the pipelined double
    # buffer's entry elements: the tuples round-trip through a deque
    # (an opaque container the flow analysis does not model), so the
    # unpacked refs in _fetch_entry, and the counts_ref of a verify step
    # in _retire_spec_step, are seeded by hand. Everything the
    # list used to carry because taint died at an assignment or a call
    # boundary (first, adm_logits, spec_counts, demote_kv, promo_kv) is
    # now DERIVED — see tools/graftlint/dataflow.py.
    sync_device_names: list[str] = field(
        default_factory=lambda: [
            "active_ref",
            "emitted_ref",
            "out_ref",
            "counts_ref",
        ]
    )
    # Bounded depth for the interprocedural passes: summary recursion,
    # call-site→parameter taint seeding rounds, and call-graph
    # reachability hops.
    dataflow_depth: int = 4
    # --- GL-TRACE ----------------------------------------------------
    # Dotted-call prefixes that are host side effects inside a traced
    # body (a trace-time call silently bakes a constant into the
    # compiled program and never runs again).
    trace_impure_calls: list[str] = field(
        default_factory=lambda: [
            "time.",
            "print",
            "input",
            "open",
            "os.environ",
            "injector.fire",
            "faults.record",
            "interleave_mod.stats.",
            "prefix_mod.stats.",
            "stats.record_",
            "random.random",
            "random.randint",
            # Observability (adversarial_spec_tpu/obs): event appends
            # and metric observes are host side effects — inside a
            # traced body they would fire once per compile shape.
            "obs.",
            "obs_mod.",
            "recorder.append",
            "metrics.",
            # Causal tracing (obs/trace.py): ambient-scope mutation and
            # span minting are host side effects — at trace time they
            # would stamp one compile's ids onto every later dispatch.
            "trace.",
            "trace_mod.",
            "trace_scope",
            "slo_check",
            # Streaming (engine/streaming.py): consumer delivery and
            # cancel accounting are host side effects — inside a traced
            # body they would fire once per compile shape, and a
            # trace-time consumer callback could never cancel anything.
            "stream_mod.",
        ]
    )
    # Extra dotted function names (module.func) to treat as trace roots
    # beyond what jit/pallas_call discovery finds. The fused serving
    # kernels are pinned so a refactor that indirects the pallas_call
    # kernel reference cannot silently drop their GL-TRACE coverage;
    # quant.matmul/unpack_int4 likewise, now that the forwards reach
    # them through an ``mm=`` parameter the callee resolver can't
    # follow.
    trace_extra_roots: list[str] = field(
        default_factory=lambda: [
            "adversarial_spec_tpu.ops.pallas_quant._qmm_int8_kernel",
            "adversarial_spec_tpu.ops.pallas_quant._qmm_int4_kernel",
            "adversarial_spec_tpu.ops.pallas_quant._qmm_int8_grouped_kernel",
            "adversarial_spec_tpu.ops.pallas_paged._paged_mq_attn_kernel",
            "adversarial_spec_tpu.ops.pallas_paged._paged_mq_attn_grid_kernel",
            "adversarial_spec_tpu.ops.quant.matmul",
            "adversarial_spec_tpu.ops.quant.unpack_int4",
        ]
    )
    # --- GL-RETRACE --------------------------------------------------
    # Functions that bound a Python scalar to a small fixed set of
    # values (pow2 buckets): their results may feed static args.
    # _plan_blocks buckets fused quant-matmul block shapes to a fixed
    # candidate table (ops/pallas_quant.py).
    retrace_bucketers: list[str] = field(
        default_factory=lambda: [
            "bucket_length",
            "_next_chunk_len",
            "_fused_chunk_len",
            "_plan_blocks",
        ]
    )
    # --- GL-REFCOUNT -------------------------------------------------
    # Modules whose PageAllocator call sites get path analysis, and the
    # acquire->release pairs ("acquire=release").
    refcount_modules: list[str] = field(
        default_factory=lambda: [
            "adversarial_spec_tpu.engine.scheduler",
            "adversarial_spec_tpu.engine.prefix_cache",
            "adversarial_spec_tpu.engine.tpu",
            "adversarial_spec_tpu.engine.mock",
        ]
    )
    # swap_pin marks a page as the target of an in-flight tier swap
    # (host->device promotion scatter): a raise between pin and unpin
    # would leave the allocator convinced a swap is forever in flight
    # (and _release refusing to free the page) — the demote/promote
    # release-path discipline, statically enforced.
    # acquire_weights pins a model's weights against demotion for the
    # duration of its serve (engine/weightres.py): a raise between pin
    # and unpin would leave the model unevictable forever — the weight
    # residency release-path discipline, statically enforced.
    refcount_pairs: list[str] = field(
        default_factory=lambda: [
            "new_sequence=free_sequence",
            "adopt=free_sequence",
            "cache_ref=cache_unref",
            "swap_pin=swap_unpin",
            "acquire_weights=release_weights",
        ]
    )

    # --- GL-COMMIT ---------------------------------------------------
    # Classes whose persistent device attributes must be committed to
    # the mesh sharding at creation, the attribute names, the calls
    # that CREATE fresh (uncommitted) device state, the sanctioned
    # committing wrappers, and holder constructors whose keyword args
    # are persistent sinks (_Admission(cache=...)). ``pool`` is
    # deliberately NOT in commit_attrs: its placement is owned by the
    # paged kernels (init_page_pool), not the replicated row-state
    # sharding.
    commit_classes: list[str] = field(
        default_factory=lambda: ["ContinuousBatcher"]
    )
    commit_attrs: list[str] = field(
        default_factory=lambda: [
            "page_table",
            "cur_tok",
            "cur_len",
            "pad_lens",
            "n_emitted",
            "max_new",
            "active",
            "out_buf",
            "ctx_buf",
            "ctx_len",
            "prev_tok",
            "cache",
        ]
    )
    commit_creators: list[str] = field(
        default_factory=lambda: [
            "init_cache",
            "jnp.zeros",
            "jnp.ones",
            "jnp.full",
            "jnp.arange",
            "jnp.asarray",
            "jnp.array",
        ]
    )
    commit_wrappers: list[str] = field(
        default_factory=lambda: ["_commit", "device_put"]
    )
    commit_holders: list[str] = field(
        default_factory=lambda: ["_Admission"]
    )
    # --- GL-DONATE ---------------------------------------------------
    # Calls that take an independent snapshot of a buffer (reading the
    # snapshot after the original was donated is safe).
    donate_snapshots: list[str] = field(
        default_factory=lambda: [
            "copy",
            "jnp.copy",
            "np.copy",
            "np.array",
            "np.asarray",
            "deepcopy",
        ]
    )
    # --- GL-ATOMIC ---------------------------------------------------
    # The sanctioned write implementations (module:func or
    # module:Class.method): every other file write inside the package
    # must route through one of them.
    atomic_funcs: list[str] = field(
        default_factory=lambda: [
            "adversarial_spec_tpu.obs.events:atomic_write_text",
            "adversarial_spec_tpu.debate.journal:RoundJournal._write",
            "adversarial_spec_tpu.engine.kvtier:DiskStore.put",
            # The fleet worker's stderr log: an OS-owned append stream
            # opened once at spawn for post-mortems — a torn line in a
            # crash log is evidence, not corruption.
            "adversarial_spec_tpu.fleet.replica:WorkerReplica._spawn",
        ]
    )
    # --- GL-LIFECYCLE ------------------------------------------------
    # The slot state machine: every exit path must reach the shared
    # release surgery, and the slot-ownership attributes may only be
    # written by the surgery, the acquisition path, and the listed
    # mutators (plus __init__).
    lifecycle_class: str = "ContinuousBatcher"
    lifecycle_release: str = "_release_slot"
    lifecycle_exits: list[str] = field(
        default_factory=lambda: [
            "_finish_slot",
            "_evict_slot",
            "_cancel_slot",
            "_expire_request_deadlines",
        ]
    )
    lifecycle_owned_attrs: list[str] = field(
        default_factory=lambda: [
            "_slot_req",
            "_slot_seq",
            "_slot_consumer",
            "_slot_streamed",
            "_slot_gen",
        ]
    )
    lifecycle_mutators: list[str] = field(
        default_factory=lambda: ["_finish_admission", "_deliver_stream"]
    )
    # The fleet router's replica state machine (fleet/router.py), the
    # second GL-LIFECYCLE machine: every path that takes a replica out
    # of service (transport death, heartbeat miss, shutdown) must reach
    # the one retirement surgery, and the dead-replica ledger is
    # written nowhere else. "" disables the machine (fixture trees).
    fleet_lifecycle_class: str = "FleetRouter"
    fleet_lifecycle_release: str = "_retire_replica"
    fleet_lifecycle_exits: list[str] = field(
        default_factory=lambda: [
            "_on_replica_fault",
            "_heartbeat_failure",
            "shutdown",
        ]
    )
    fleet_lifecycle_owned_attrs: list[str] = field(
        default_factory=lambda: ["_dead"]
    )
    fleet_lifecycle_mutators: list[str] = field(default_factory=list)
    # The serve daemon's request state machine (serve/sched.py), the
    # third GL-LIFECYCLE machine: every unit exit (finish, mid-round
    # quota shed, tier preemption, drain) must reach the one release
    # surgery, and the running-set ledger is written only by the
    # surgery and the acquisition. "" disables (fixture trees).
    serve_lifecycle_class: str = "ServeScheduler"
    serve_lifecycle_release: str = "_release_unit"
    serve_lifecycle_exits: list[str] = field(
        default_factory=lambda: [
            "_finish_unit",
            "_shed_unit",
            "_preempt_unit",
            "_drain_unit",
            "drain_cancelled",
        ]
    )
    serve_lifecycle_owned_attrs: list[str] = field(
        default_factory=lambda: ["_running"]
    )
    serve_lifecycle_mutators: list[str] = field(
        default_factory=lambda: ["_start_unit"]
    )
    # The weight-residency ledger's model state machine
    # (engine/weightres.py), the fourth GL-LIFECYCLE machine: every
    # path that takes a model out of its residency state (demotion,
    # promotion's host-side consume, free, teardown) must reach the one
    # retirement surgery, and the entries ledger is written only by the
    # surgery and the _admit_model acquisition. "" disables (fixtures).
    weightres_lifecycle_class: str = "WeightLedger"
    weightres_lifecycle_release: str = "_retire_model"
    weightres_lifecycle_exits: list[str] = field(
        default_factory=lambda: [
            "demote_model",
            "promote_model",
            "free_model",
            "clear",
        ]
    )
    weightres_lifecycle_owned_attrs: list[str] = field(
        default_factory=lambda: ["_entries"]
    )
    weightres_lifecycle_mutators: list[str] = field(
        default_factory=lambda: ["_admit_model"]
    )
    # The autoscaler's replica-membership state machine
    # (fleet/autoscale.py), the fifth GL-LIFECYCLE machine: every
    # terminal transition (aborted warm-up, planned scale-in, orderly
    # shutdown) must reach the one decommission surgery, and the
    # member-state ledger is written only by the surgery and the
    # sanctioned mutators. "" disables (fixture trees).
    autoscale_lifecycle_class: str = "Autoscaler"
    autoscale_lifecycle_release: str = "_decommission"
    autoscale_lifecycle_exits: list[str] = field(
        default_factory=lambda: [
            "_abort_warm",
            "_finish_scale_in",
            "shutdown",
        ]
    )
    autoscale_lifecycle_owned_attrs: list[str] = field(
        default_factory=lambda: ["_members"]
    )
    autoscale_lifecycle_mutators: list[str] = field(
        default_factory=lambda: ["_begin_provision", "_advance"]
    )
    # The cross-replica KV handoff ledger (fleet/handoff.py), the sixth
    # GL-LIFECYCLE machine: every terminal transition (adopt, degrade,
    # abandon) must reach the one publication surgery, and the
    # terminal-outcome ledger is written nowhere else — so a handoff
    # can neither be double-counted nor vanish between states. The
    # non-terminal ``note_*`` helpers mutate the in-flight record, not
    # the owned ledger, so they need no mutator entry. "" disables
    # (fixture trees).
    handoff_lifecycle_class: str = "HandoffLedger"
    handoff_lifecycle_release: str = "_publish_blocks"
    handoff_lifecycle_exits: list[str] = field(
        default_factory=lambda: [
            "_finish_adopt",
            "_degrade",
            "_abandon",
        ]
    )
    handoff_lifecycle_owned_attrs: list[str] = field(
        default_factory=lambda: ["_outcomes"]
    )
    handoff_lifecycle_mutators: list[str] = field(default_factory=list)
    # -- GL-LOCK (rules/locking.py) ------------------------------------
    # The lock-discipline map: one entry per declared lock, both the
    # guards table (GL-LOCK-GUARD) and the lock *inventory* GL-CONFIG
    # checks declarations against. Entry grammar (TOML-subset has no
    # tables, so each entry is one string):
    #   "<module>:<Class>.<lockattr>[|<alias>...]=<attr>,<attr>"
    #   "<module>:<globalname>[|<alias>...]=<global>,<global>"
    # Aliases name other attributes holding the SAME lock (a Condition
    # constructed over it: ``with self._cond`` == holding ``_lock``).
    # An empty right-hand side declares a pure ordering lock guarding
    # no named state.
    lock_guards: list[str] = field(
        default_factory=lambda: [
            "adversarial_spec_tpu.serve.sched:ServeScheduler._lock|_cond="
            "_queues,_passes,_running,_reserved,_reserved_prefill,"
            "_debate_tenant,_debate_models,_outstanding,_quota,"
            "_capacity_fn,brownout,_prev_gamma,draining,_drain_forced,"
            "_stopped,_charged_tokens",
            "adversarial_spec_tpu.fleet.autoscale:Autoscaler._lock="
            "_members,_pending,_out_streak,_in_streak,_out_streaks,"
            "_in_streaks,_last_change_t,_last_backlog,_desired",
            "adversarial_spec_tpu.fleet.router:FleetRouter._mlock="
            "_ring,_dead,_inflight,_rr",
            "adversarial_spec_tpu.engine.weightres:WeightLedger._lock="
            "_entries,_pre_pins,_clock",
            "adversarial_spec_tpu.engine.tpu:TpuEngine._lock="
            "_models,_inflight,_loading,_demoting",
            "adversarial_spec_tpu.engine.kvtier:DiskStore._put_lock="
            "_resident",
            "adversarial_spec_tpu.engine.dispatch:_CACHE_LOCK="
            "_ENGINE_CACHE",
            "adversarial_spec_tpu.obs.metrics:MetricsRegistry._lock="
            "_families",
            "adversarial_spec_tpu.obs.trace:_mint_lock="
            "_trace_counter,_scope_counters",
            "adversarial_spec_tpu.obs.events:FlightRecorder._lock=_buf",
            "adversarial_spec_tpu.resilience.faults:_lock=_counts",
            "adversarial_spec_tpu.resilience.injector:FaultInjector._lock="
            "fired,seam_hits",
            "adversarial_spec_tpu.resilience.injector:_active_lock=_active",
            "adversarial_spec_tpu.resilience.breaker:BreakerRegistry._lock="
            "_breakers",
            "adversarial_spec_tpu.resilience.breaker:_default_lock=_default",
        ]
    )
    # Thread entry points for GL-LOCK-GUARD reachability BEYOND the
    # auto-discovered ones (threading.Thread targets and Thread
    # subclass ``run``): "<module>:<func>" / "<module>:<Class>.<method>".
    # The daemon runs debates on executor threads (run_in_executor is
    # not statically resolvable) and drills drive the autoscaler's
    # ``tick`` directly.
    lock_thread_entries: list[str] = field(
        default_factory=lambda: [
            "adversarial_spec_tpu.serve.driver:run_debate",
            "adversarial_spec_tpu.fleet.autoscale:Autoscaler.tick",
        ]
    )
    # Call patterns GL-LOCK-BLOCKING refuses while any tracked lock is
    # held: a dotted pattern matches the dotted call name (suffix), a
    # bare name matches the final attribute/function segment. ``wait``
    # on an alias of a held lock's own Condition is exempt (the wait
    # RELEASES that lock); waiting on anything else while holding a
    # lock is the finding.
    lock_blocking_calls: list[str] = field(
        default_factory=lambda: [
            "time.sleep",
            "_sleep",
            "os.fsync",
            "fsync",
            "subprocess.run",
            "subprocess.check_output",
            "subprocess.Popen",
            "block_until_ready",
            "device_get",
            "chat",
            "wait",
            "join",
        ]
    )

    def parsed_lock_guards(self) -> list["LockGuard"]:
        """``lock_guards`` decoded into :class:`LockGuard` records.
        Raises ValueError on malformed entries (GL-CONFIG surfaces the
        same failure as a finding on full runs)."""
        out: list[LockGuard] = []
        for entry in self.lock_guards:
            head, sep, attrs = entry.partition("=")
            if not sep:
                raise ValueError(
                    f"lock_guards entry {entry!r}: missing '=' "
                    "(use '<module>:<lock>=<attr>,...')"
                )
            module, msep, lockpart = head.partition(":")
            module = module.strip()
            if not msep or not module or not lockpart.strip():
                raise ValueError(
                    f"lock_guards entry {entry!r}: head must be "
                    "'<module>:<lock>'"
                )
            names = [n.strip() for n in lockpart.split("|") if n.strip()]
            first = names[0]
            if "." in first:
                classname, lock_attr = first.split(".", 1)
            else:
                classname, lock_attr = "", first
            aliases = [lock_attr]
            for n in names[1:]:
                aliases.append(n.split(".", 1)[1] if "." in n else n)
            guarded = tuple(
                a.strip() for a in attrs.split(",") if a.strip()
            )
            out.append(
                LockGuard(
                    module=module,
                    classname=classname,
                    lock_attr=lock_attr,
                    aliases=tuple(aliases),
                    guarded=guarded,
                )
            )
        return out

    def parsed_thread_entries(self) -> list[tuple[str, str, str]]:
        """``lock_thread_entries`` decoded as (module, classname, func);
        classname is "" for module-level functions."""
        out: list[tuple[str, str, str]] = []
        for entry in self.lock_thread_entries:
            module, sep, func = entry.partition(":")
            if not sep or not module.strip() or not func.strip():
                raise ValueError(
                    f"lock_thread_entries entry {entry!r}: use "
                    "'<module>:<func>' or '<module>:<Class>.<method>'"
                )
            func = func.strip()
            if "." in func:
                classname, func = func.split(".", 1)
            else:
                classname = ""
            out.append((module.strip(), classname, func))
        return out

    def named_lifecycle_machines(
        self,
    ) -> list[tuple[str, tuple[str, str, list, list, list]]]:
        """Every configured GL-LIFECYCLE machine with its knob-name
        prefix: (prefix, (class, release, exits, owned attrs,
        mutators)). Empty class names disable a machine (fixture
        trees). GL-CONFIG validates every machine through this one
        list — adding a fourth machine is one entry here plus its
        config fields."""
        machines = [
            (
                "lifecycle",
                (
                    self.lifecycle_class,
                    self.lifecycle_release,
                    self.lifecycle_exits,
                    self.lifecycle_owned_attrs,
                    self.lifecycle_mutators,
                ),
            ),
            (
                "fleet_lifecycle",
                (
                    self.fleet_lifecycle_class,
                    self.fleet_lifecycle_release,
                    self.fleet_lifecycle_exits,
                    self.fleet_lifecycle_owned_attrs,
                    self.fleet_lifecycle_mutators,
                ),
            ),
            (
                "serve_lifecycle",
                (
                    self.serve_lifecycle_class,
                    self.serve_lifecycle_release,
                    self.serve_lifecycle_exits,
                    self.serve_lifecycle_owned_attrs,
                    self.serve_lifecycle_mutators,
                ),
            ),
            (
                "weightres_lifecycle",
                (
                    self.weightres_lifecycle_class,
                    self.weightres_lifecycle_release,
                    self.weightres_lifecycle_exits,
                    self.weightres_lifecycle_owned_attrs,
                    self.weightres_lifecycle_mutators,
                ),
            ),
            (
                "autoscale_lifecycle",
                (
                    self.autoscale_lifecycle_class,
                    self.autoscale_lifecycle_release,
                    self.autoscale_lifecycle_exits,
                    self.autoscale_lifecycle_owned_attrs,
                    self.autoscale_lifecycle_mutators,
                ),
            ),
            (
                "handoff_lifecycle",
                (
                    self.handoff_lifecycle_class,
                    self.handoff_lifecycle_release,
                    self.handoff_lifecycle_exits,
                    self.handoff_lifecycle_owned_attrs,
                    self.handoff_lifecycle_mutators,
                ),
            ),
        ]
        return [m for m in machines if m[1][0]]

    def lifecycle_machines(self) -> list[tuple[str, str, list, list, list]]:
        """The configured GL-LIFECYCLE state machines as (class,
        release, exits, owned attrs, mutators); empty class names
        disable a machine."""
        return [m for _, m in self.named_lifecycle_machines()]

    def acquire_release(self) -> dict[str, str]:
        out: dict[str, str] = {}
        for pair in self.refcount_pairs:
            acquire, _, release = pair.partition("=")
            if not release:
                raise ValueError(
                    f"refcount_pairs entry {pair!r} is not 'acquire=release'"
                )
            out[acquire.strip()] = release.strip()
        return out


_STRING = re.compile(r'^"((?:[^"\\]|\\.)*)"$')


def _parse_scalar(text: str, key: str):
    text = text.strip()
    m = _STRING.match(text)
    if m:
        return m.group(1).replace('\\"', '"').replace("\\\\", "\\")
    if text in ("true", "false"):
        return text == "true"
    if re.fullmatch(r"-?\d+", text):
        return int(text)
    raise ValueError(f"[tool.graftlint] {key}: unsupported value {text!r}")


def _parse_array(text: str, key: str) -> list:
    inner = text.strip()
    assert inner.startswith("[") and inner.endswith("]")
    items = []
    # Split on commas outside quotes — values are plain strings/ints.
    for piece in re.findall(r'"(?:[^"\\]|\\.)*"|[^,\[\]\s]+', inner[1:-1]):
        items.append(_parse_scalar(piece, key))
    return items


def _strip_comment(line: str) -> str:
    """Drop a ``#`` comment that is OUTSIDE any double-quoted string
    (valid TOML allows inline comments after values and whole comment
    lines inside multi-line arrays)."""
    out = []
    in_string = False
    escaped = False
    for ch in line:
        if escaped:
            out.append(ch)
            escaped = False
            continue
        if in_string and ch == "\\":
            out.append(ch)
            escaped = True
            continue
        if ch == '"':
            in_string = not in_string
        elif ch == "#" and not in_string:
            break
        out.append(ch)
    return "".join(out)


def read_graftlint_table(pyproject: Path) -> dict:
    """The ``[tool.graftlint]`` table as a plain dict (subset reader)."""
    raw: dict = {}
    if not pyproject.exists():
        return raw
    in_table = False
    pending_key: str | None = None
    pending_val = ""
    for line in pyproject.read_text(encoding="utf-8").splitlines():
        stripped = _strip_comment(line).strip()
        if pending_key is not None:
            pending_val += " " + stripped
            if pending_val.count("[") == pending_val.count("]"):
                raw[pending_key] = _parse_array(pending_val, pending_key)
                pending_key = None
            continue
        if stripped.startswith("["):
            in_table = stripped == "[tool.graftlint]"
            continue
        if not in_table or not stripped or stripped.startswith("#"):
            continue
        key, _, value = stripped.partition("=")
        key, value = key.strip(), value.strip()
        if value.startswith("["):
            if value.count("[") == value.count("]"):
                raw[key] = _parse_array(value, key)
            else:
                pending_key, pending_val = key, value
        else:
            raw[key] = _parse_scalar(value, key)
    return raw


def load_config(repo: Path) -> GraftlintConfig:
    cfg = GraftlintConfig()
    raw = read_graftlint_table(repo / "pyproject.toml")
    for key, value in raw.items():
        attr = key.replace("-", "_")
        if not hasattr(cfg, attr):
            raise ValueError(f"[tool.graftlint] unknown key {key!r}")
        setattr(cfg, attr, value)
    return cfg


def config_drift(repo: Path) -> list[str]:
    """Field-by-field drift between pyproject's ``[tool.graftlint]``
    table and the in-code defaults (which exist so fixture trees lint
    without a pyproject — they must never diverge from the committed
    table). THE shared drift guard: tools/lint_all.py runs it as a
    preflight stage and tests/test_tools.py pins it empty; per-module
    copies of the same check are retired."""
    import dataclasses

    cfg = load_config(repo)
    dflt = GraftlintConfig()
    out: list[str] = []
    for f in dataclasses.fields(cfg):
        have, want = getattr(cfg, f.name), getattr(dflt, f.name)
        if have != want:
            out.append(
                f"{f.name}: pyproject={have!r} != code default={want!r}"
            )
    return out
