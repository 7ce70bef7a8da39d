"""Test bootstrap.

Runs on CPU with a virtual 8-device mesh (SURVEY §4: the reference mocks its
transport seam and runs everything above it for real; our analogs are the
mock engine plus ``--xla_force_host_platform_device_count=8`` so sharding
code executes real collectives in one process). Env vars must be set before
jax initializes, hence at conftest import time.
"""

import os

# Tests are CPU-only by contract, whatever the invoking shell exports.
os.environ["JAX_PLATFORMS"] = "cpu"
# The whole suite runs with the lockdep sanitizer armed: every
# declared lock becomes a TrackedLock, the acquisition-order graph is
# live, and any inversion fails the test that caused it (the fixture
# below asserts zero violations at teardown). Must be set before the
# package imports — make_lock() reads it at lock construction.
os.environ.setdefault("ADVSPEC_LOCKDEP", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def _configure_jax_for_tests() -> None:
    """Mirror the CPU pin into jax.config and arm the persistent
    compile cache. Restricting jax_platforms leaves the "tpu" platform
    known to the lowering registry (Pallas registers its TPU rules at
    import), which the described-topology compile tests rely on."""
    # The cache directory follows configure_jax's one rule
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), so the
    # suite, its subprocess children (fleet workers, serve daemons, CLI
    # rounds) and a developer's own runs share entries. Cache keys
    # fingerprint the computation, so a code change can never serve a
    # stale binary.
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import jax

    # configure_jax's 1.0s floor is tuned for real-model programs; the
    # suite's tiny-model compiles mostly land under it, so cache them
    # all — the point here is aggregate wall across hundreds of tests.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


_configure_jax_for_tests()


# PR 32's test of its own benchmark entries pins them as the LAST of every
# list of BENCHMARK.json; the benchmark's contract has every later entry
# appended at the end. So from the next cell on (PR 36's) it fails on place
# alone, and the file is a benchmark file that only a `benchmark` PR may
# edit. Everything else it asserts runs, and passes, as
# tests/benchmark/test_perfbench_granite.py::test_pr32s_entries_hold_but_for_their_place.
# strict: once that test looks its entries up by name, this mark has to go.
_PINNED_LAST = (
    "tests/benchmark/test_perfbench_mistral4.py"
    "::test_the_new_entries_are_appended_and_say_what_the_cell_reports"
)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid == _PINNED_LAST:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins PR 32's entries as the last of BENCHMARK.json; later cells are "
                       "appended after them (PERF.md section 7, 'From PR 36' (ii))",
            ))


def _memory_maps() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to watch
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_executables_before_the_map_limit():
    """Every compiled XLA:CPU executable maps a handful of memory regions
    and stays mapped while a jit cache holds it. One process running the
    whole suite collects thousands of them and, three quarters of the way
    through, reaches the kernel's per-process limit (vm.max_map_count,
    65,530 by default): the next mmap fails inside XLA and the run dies
    with a segmentation fault. After a module that leaves the process
    past a third of that limit, drop jax's caches — the persistent
    compile cache gives the next module its programs back cheaply."""
    yield
    if _memory_maps() > 20_000:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True)
def _isolate_state(tmp_path, monkeypatch):
    """Point every persistence dir at tmp and reset engine singletons."""
    from adversarial_spec_tpu.debate import session, profiles
    from adversarial_spec_tpu.engine import registry, dispatch
    from adversarial_spec_tpu.resilience import breaker, faults, injector

    monkeypatch.setattr(session, "SESSIONS_DIR", tmp_path / "sessions")
    monkeypatch.setattr(session, "CHECKPOINTS_DIR", tmp_path / "checkpoints")
    monkeypatch.setattr(profiles, "PROFILES_DIR", tmp_path / "profiles")
    monkeypatch.setattr(
        profiles, "GLOBAL_CONFIG_PATH", tmp_path / "config.json"
    )
    monkeypatch.setattr(registry, "REGISTRY_PATH", tmp_path / "registry.json")
    # Resilience state is process-global by design (breakers must outlive
    # a round); between tests it must not leak. Chaos env vars from the
    # invoking shell must not reach the suite either.
    monkeypatch.delenv("ADVSPEC_CHAOS", raising=False)
    monkeypatch.delenv("ADVSPEC_CHAOS_SEED", raising=False)
    monkeypatch.delenv("ADVSPEC_BREAKER_THRESHOLD", raising=False)
    monkeypatch.delenv("ADVSPEC_BREAKER_COOLDOWN", raising=False)
    breaker.reset_default_registry()
    faults.reset()
    injector.reset()
    dispatch.clear_engine_cache()
    # Prefix-cache config/stats are process-global by design (the cache
    # outlives a round); tests must not leak a --no-prefix-cache or a
    # page cap into each other.
    from adversarial_spec_tpu.engine import prefix_cache

    prefix_cache.configure(enabled=True, max_pages=0)
    prefix_cache.reset_stats()
    # Tiered-KV config/stats are process-global by design (the tiers
    # live on persistent batchers); tests must not leak a store dir,
    # a host budget, or swap counts into each other. Tiering is pinned
    # OFF suite-wide (the PR 6 speculation-off precedent: per-insert
    # chain hashing and per-eviction demotion gathers in every batcher/
    # mock test are pure wall cost when the subject is orthogonal —
    # tier coverage of the same paths lives in tests/test_kv_tier.py,
    # which opts in explicitly, as do CLI tests of the env default).
    from adversarial_spec_tpu.engine import kvtier

    monkeypatch.setenv("ADVSPEC_KV_TIER", "0")
    monkeypatch.delenv("ADVSPEC_KV_HOST_MB", raising=False)
    monkeypatch.delenv("ADVSPEC_KV_STORE_DIR", raising=False)
    monkeypatch.delenv("ADVSPEC_KV_FLUSH_BLOCKS", raising=False)
    kvtier.configure(
        enabled=False,
        host_mb=kvtier.DEFAULT_HOST_MB,
        store_dir="",
        flush_blocks=0,
    )
    kvtier.reset_stats()
    # Weight-residency config/stats are process-global by design (the
    # ledger lives on each engine); tests must not leak a host budget,
    # swap counts, or — critically — an explicit HBM budget (the mock
    # engine's residency simulation arms only under
    # ADVSPEC_HBM_BUDGET_BYTES, keeping pre-residency mock event
    # streams byte-identical).
    from adversarial_spec_tpu.engine import weightres

    monkeypatch.delenv("ADVSPEC_WEIGHT_RES", raising=False)
    monkeypatch.delenv("ADVSPEC_WEIGHT_HOST_MB", raising=False)
    monkeypatch.delenv("ADVSPEC_HBM_BUDGET_BYTES", raising=False)
    weightres.configure(enabled=True, host_mb=weightres.DEFAULT_HOST_MB)
    weightres.reset_stats()
    # Fleet config/stats are process-global by design (the replica
    # topology outlives a round); tests must not leak an armed fleet,
    # spawned replicas, or routing counts into each other. Fleet OFF
    # is the product default — fleet coverage opts in explicitly in
    # tests/test_fleet.py (clear_engine_cache above already tears the
    # process fleet engine down).
    from adversarial_spec_tpu import fleet

    monkeypatch.delenv("ADVSPEC_FLEET", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_REPLICAS", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_TRANSPORT", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_AUTOSCALE", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_MIN", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_MAX", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_SCALE_COOLDOWN_S", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_SCALE_INTERVAL_S", raising=False)
    monkeypatch.delenv("ADVSPEC_REPLICA_KILL_AFTER", raising=False)
    monkeypatch.delenv("ADVSPEC_PREFILL_KILL_AFTER", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_PREFILL_REPLICAS", raising=False)
    monkeypatch.delenv("ADVSPEC_FLEET_HANDOFF_THRESHOLD", raising=False)
    fleet.configure(
        enabled=False,
        replicas=fleet.DEFAULT_REPLICAS,
        transport="inproc",
        autoscale=False,
        min_replicas=fleet.DEFAULT_MIN_REPLICAS,
        max_replicas=fleet.DEFAULT_MAX_REPLICAS,
        scale_cooldown_s=fleet.DEFAULT_SCALE_COOLDOWN_S,
        scale_interval_s=fleet.DEFAULT_SCALE_INTERVAL_S,
        prefill_replicas=fleet.DEFAULT_PREFILL_REPLICAS,
        handoff_threshold_tokens=fleet.DEFAULT_HANDOFF_THRESHOLD_TOKENS,
        min_prefill_replicas=fleet.DEFAULT_MIN_PREFILL_REPLICAS,
        max_prefill_replicas=fleet.DEFAULT_MAX_PREFILL_REPLICAS,
    )
    fleet.reset_stats()
    # Streaming config/stats are process-global by design (the CLI arms
    # them per round); tests must not leak a --no-stream / cancel
    # counts into each other. Defaults (stream + early-cancel on) are
    # the product defaults — streaming tests exercise both sides.
    from adversarial_spec_tpu.engine import streaming

    monkeypatch.delenv("ADVSPEC_STREAM", raising=False)
    monkeypatch.delenv("ADVSPEC_EARLY_CANCEL", raising=False)
    streaming.configure(enabled=True, early_cancel=True)
    streaming.reset_stats()
    # Serve-daemon state is process-global by design (the daemon arms
    # it once at startup); tests must not leak tightened admission
    # caps, quotas, counters, or — critically — an installed scheduler
    # gate (a leaked gate would route every later test's engine calls
    # through a dead scheduler).
    from adversarial_spec_tpu import serve
    from adversarial_spec_tpu.serve import gate as serve_gate

    for var in (
        "ADVSPEC_SERVE_QUEUE_DEPTH",
        "ADVSPEC_SERVE_BACKLOG_TOKENS",
        "ADVSPEC_SERVE_QUOTA_TOKENS",
        "ADVSPEC_SERVE_DRAIN_DEADLINE_S",
        "ADVSPEC_SERVE_TTFT_SLO_MS",
        "ADVSPEC_SERVE_SOCKET",
    ):
        monkeypatch.delenv(var, raising=False)
    serve_gate.uninstall()
    serve.configure(
        max_queue_depth=serve.DEFAULT_QUEUE_DEPTH,
        max_backlog_tokens=serve.DEFAULT_BACKLOG_TOKENS,
        tenant_quota_tokens=0,
        drain_deadline_s=serve.DEFAULT_DRAIN_DEADLINE_S,
        brownout_enter_fraction=serve.DEFAULT_BROWNOUT_ENTER_FRACTION,
        brownout_exit_fraction=serve.DEFAULT_BROWNOUT_EXIT_FRACTION,
        brownout_gamma=serve.DEFAULT_BROWNOUT_GAMMA,
        preempt_grace_s=0.0,
        interactive_ttft_slo_ms=0.0,
        max_dispatch_batch=4,
        max_debates_in_flight=32,
    )
    serve.reset_stats()
    # Observability state is process-global by design (the recorder and
    # metric handles outlive a round); tests must not leak an armed
    # events_out path, a shrunken ring, or recorded events.
    from adversarial_spec_tpu import obs

    monkeypatch.delenv("ADVSPEC_OBS", raising=False)
    monkeypatch.delenv("ADVSPEC_EVENTS_OUT", raising=False)
    monkeypatch.delenv("ADVSPEC_FLIGHT_RECORDER_SIZE", raising=False)
    monkeypatch.delenv("ADVSPEC_OBS_ARRIVALS", raising=False)
    obs.configure(
        enabled=True,
        recorder_size=obs.DEFAULT_RECORDER_SIZE,
        events_out="",
        dump_on_fault=True,
        arrivals=False,
    )
    obs.reset_stats()
    # Full retrace clear (reset() deliberately keeps compile baselines
    # for warm per-round accounting; tests want cold-start isolation).
    obs.retrace.clear()
    # Lockdep state is process-global by design (the order graph spans
    # every lock in the process); tests must not leak edges — or,
    # worse, a recorded violation — into each other.
    from adversarial_spec_tpu.resilience import lockdep

    lockdep.reset()
    yield
    leaked = lockdep.violations()
    assert not leaked, (
        "lock-order violation(s) recorded during this test:\n"
        + "\n\n".join(str(v) for v in leaked)
    )
    lockdep.reset()
    serve_gate.uninstall()
    serve.configure(
        max_queue_depth=serve.DEFAULT_QUEUE_DEPTH,
        max_backlog_tokens=serve.DEFAULT_BACKLOG_TOKENS,
        tenant_quota_tokens=0,
        drain_deadline_s=serve.DEFAULT_DRAIN_DEADLINE_S,
        preempt_grace_s=0.0,
        interactive_ttft_slo_ms=0.0,
        max_dispatch_batch=4,
    )
    serve.reset_stats()
    dispatch.clear_engine_cache()
    fleet.configure(
        enabled=False,
        replicas=fleet.DEFAULT_REPLICAS,
        transport="inproc",
        autoscale=False,
        min_replicas=fleet.DEFAULT_MIN_REPLICAS,
        max_replicas=fleet.DEFAULT_MAX_REPLICAS,
        scale_cooldown_s=fleet.DEFAULT_SCALE_COOLDOWN_S,
        scale_interval_s=fleet.DEFAULT_SCALE_INTERVAL_S,
        prefill_replicas=fleet.DEFAULT_PREFILL_REPLICAS,
        handoff_threshold_tokens=fleet.DEFAULT_HANDOFF_THRESHOLD_TOKENS,
        min_prefill_replicas=fleet.DEFAULT_MIN_PREFILL_REPLICAS,
        max_prefill_replicas=fleet.DEFAULT_MAX_PREFILL_REPLICAS,
    )
    fleet.reset_stats()
    breaker.reset_default_registry()
    prefix_cache.configure(enabled=True, max_pages=0)
    prefix_cache.reset_stats()
    kvtier.configure(
        enabled=False,
        host_mb=kvtier.DEFAULT_HOST_MB,
        store_dir="",
        flush_blocks=0,
    )
    kvtier.reset_stats()
    weightres.configure(enabled=True, host_mb=weightres.DEFAULT_HOST_MB)
    weightres.reset_stats()
    streaming.configure(enabled=True, early_cancel=True)
    streaming.reset_stats()
    obs.configure(
        enabled=True,
        recorder_size=obs.DEFAULT_RECORDER_SIZE,
        events_out="",
        dump_on_fault=True,
        arrivals=False,
    )
    obs.reset_stats()
    obs.retrace.clear()
    faults.reset()
    injector.reset()
