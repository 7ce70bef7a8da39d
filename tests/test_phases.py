"""The one timing primitive (``obs.phase``) and the names the benchmark
reads: the drive loop's phases partition an iteration, sit on the
profiler's clock beside the device's operations, and every key, jitted
program and kernel name a ``perfbench/metrics`` file refers to is one the
program produces today."""

import ast
import asyncio
import json
import re
import threading
from pathlib import Path

import jax
import pytest

from adversarial_spec_tpu import obs
from adversarial_spec_tpu import serve as serve_mod
from adversarial_spec_tpu.engine import generate as generate_mod
from adversarial_spec_tpu.engine import registry as registry_mod
from adversarial_spec_tpu.engine import scheduler as sched_mod
from adversarial_spec_tpu.engine import spec as spec_mod
from adversarial_spec_tpu.engine.scheduler import (
    ContinuousBatcher,
    SchedRequest,
)
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.models.transformer import init_params

ROOT = Path(__file__).resolve().parents[1]
METRICS = ROOT / "perfbench" / "metrics"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

DRIVE = [p for p in obs.PHASES if p.startswith("drive.")]
INNER = [p for p in DRIVE if p != "drive.iteration"]
NESTED = set(obs.PHASE_PARENT)  # an admission's and a completion's parts
# The metric files this PR brought: the ones that read a phase, a
# request stage or a batcher counter of the registry.
NEW_SERIES = re.compile(
    r"advspec_(phase|batcher_|prefill_wall|serve_ttft)"
)
NEW_FILES = sorted(
    f.name for f in METRICS.glob("*.json") if NEW_SERIES.search(f.read_text())
)
PATTERN_FILES = sorted(
    f.name for f in METRICS.glob("*.json") if '"pattern"' in f.read_text()
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("mistral", "tiny")
    return init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture()
def spec_on():
    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    spec_mod.configure(enabled=True)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)


def _drain(tiny, n=3, prompt=40, max_batch=2, new=12):
    """One ``run_all`` of ``n`` streamed requests, speculation on."""
    params, cfg = tiny
    b = ContinuousBatcher(
        params, cfg, max_batch=max_batch, capacity_tokens=2048,
        max_new_cap=max(16, new), eos_ids=[], greedy=True,
    )
    for i in range(n):
        b.submit(
            SchedRequest(
                req_id=i,
                prompt_ids=list(range(5, 5 + prompt + i)),
                max_new_tokens=new,
                on_tokens=lambda ids: True,
            )
        )
    return b.run_all()


def _profile(tiny, path, python_tracer_level=0, **drain):
    """A drain under the profiler (compiled outside it): its host events."""
    from perfbench.reduce import find_xplane, load_xplane

    _drain(tiny, **drain)
    obs.reset_stats()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = python_tracer_level
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        _drain(tiny, **drain)
    finally:
        jax.profiler.stop_trace()
    return load_xplane(find_xplane(str(path))).host


def _phase_sums() -> dict:
    return {
        name: (obs.hot.phase(name).sum, obs.hot.phase(name).count)
        for name in obs.PHASES
    }


class TestDriveLoopPhases:
    def test_phases_partition_the_iteration(self, tiny, spec_on):
        obs.configure(enabled=True)
        obs.reset_stats()
        results = _drain(tiny)
        assert [r.n_generated for r in results] == [12, 12, 12]
        snap = obs.metrics.snapshot()
        seen = {
            k.split('phase="', 1)[1].rstrip('"}')
            for k, v in snap.items()
            if k.startswith("advspec_phase_seconds{") and v["count"]
        }
        # a closed vocabulary: nothing observed outside it, and every
        # phase of the speculative streamed path observed; what nests in
        # those is an admission's and a completion's parts
        assert seen <= set(obs.PHASES)
        assert set(DRIVE) <= seen <= set(DRIVE) | NESTED
        sums = _phase_sums()
        iteration, n_iter = sums["drive.iteration"]
        # drive.iteration's own children: the drive.* phases, not what
        # nests in them
        inner = sum(sums[p][0] for p in INNER)
        assert n_iter >= 3
        assert inner <= iteration
        assert iteration - inner < 0.10 * iteration, (inner, iteration)
        # counted where the work happens
        assert snap["advspec_batcher_runs_total"] == 1
        assert snap["advspec_batcher_rows_total"] == 3
        assert snap["advspec_batcher_distinct_prompts_total"] == 3
        # a request's stages, one observation each
        assert snap["advspec_batcher_queue_wait_seconds"]["count"] == 3
        assert snap["advspec_prefill_wall_seconds"]["count"] == 3
        assert all(r.queue_wait_s >= 0.0 for r in results)
        # the third request waited for a slot: its wait holds a prefill
        assert results[2].queue_wait_s > results[0].queue_wait_s

    def test_sub_phases_take_no_more_than_their_parent(self, tiny, spec_on):
        obs.configure(enabled=True)
        obs.reset_stats()
        _drain(tiny, prompt=150)
        sums = _phase_sums()
        assert set(obs.PHASE_PARENT.values()) <= set(DRIVE)
        for parent in set(obs.PHASE_PARENT.values()):
            kids = [k for k, p in obs.PHASE_PARENT.items() if p == parent]
            assert sum(sums[k][0] for k in kids) <= sums[parent][0], parent
        # an admission's handoff and a completion, each once a request;
        # the third prompt is a hit admitted over its pages: no chunk
        for name in ("handoff.launch", "handoff.sync", "handoff.insert",
                     "handoff.books", "handoff.deliver", "collect.fetch",
                     "collect.deliver", "collect.release", "collect.books",
                     "admit.lookup", "admit.evict"):
            assert sums[name][1] == 3, name
        assert sums["prefill.chunk"][1] == 2

    def test_verify_only_iterations_open_no_sub_phase(
        self, tiny, spec_on, monkeypatch
    ):
        """Between one request's admission and its completion the drive
        loop runs verify steps only, and opens no phase of an admission
        or a completion: the new spans cost the step's path nothing."""
        opened: list[str] = []
        real = obs.phase

        def tapped(name):
            opened.append(name)
            return real(name)

        obs.configure(enabled=True)
        monkeypatch.setattr(obs, "phase", tapped)
        _drain(tiny, n=1, max_batch=1, new=40)
        iters: list[set] = []
        for name in opened:
            if name == "drive.iteration":
                iters.append(set())
            elif iters:
                iters[-1].add(name)
        touched = [i for i, names in enumerate(iters) if names & NESTED]
        assert len(iters) >= 10
        assert touched == [0, len(iters) - 1], touched

    def test_unknown_phase_is_refused(self):
        obs.configure(enabled=True)
        with pytest.raises(ValueError, match="unknown phase"):
            obs.phase("drive.sideways")

    def test_disabled_obs_times_nothing(self, tiny, spec_on):
        obs.configure(enabled=False)
        obs.reset_stats()
        try:
            assert obs.phase("drive.fetch") is obs.phase("drive.admit")
            results = _drain(tiny)
            assert len(results) == 3
            for key, value in obs.metrics.snapshot().items():
                if isinstance(value, dict):
                    assert value["count"] == 0, key
                else:
                    assert value == 0, key
            # what a result carries does not hang on the switch
            assert results[2].queue_wait_s > 0.0
        finally:
            obs.configure(enabled=True)

    def test_phases_sit_on_the_profilers_clock(self, tiny, spec_on, tmp_path):
        """The same run under the profiler: the annotation and the
        histogram take the same two clock readings, so the profile and
        the phase table agree, every phase lies in an iteration, and
        every sub-phase in its parent on the same thread."""
        obs.configure(enabled=True)
        host = _profile(tiny, tmp_path)
        sums = _phase_sums()
        by_name: dict = {}
        for thread, name, start, dur in host:
            if name.startswith("advspec."):
                by_name.setdefault(name[len("advspec."):], []).append(
                    (thread, start, start + dur)
                )
        nested = set(by_name) & NESTED
        assert nested
        for name in nested:
            assert len(by_name[name]) == sums[name][1], name
            parents = by_name[obs.PHASE_PARENT[name]]
            for thread, a, b in by_name[name]:
                assert any(
                    t == thread and a0 <= a and b <= b0
                    for t, a0, b0 in parents
                ), f"{name} outside every {obs.PHASE_PARENT[name]}"
        by_name = {k: v for k, v in by_name.items() if k in DRIVE}
        assert set(by_name) == set(DRIVE)
        iterations = by_name["drive.iteration"]
        assert len(iterations) == sums["drive.iteration"][1]
        assert len({t for t, _, _ in iterations}) == 1  # the drive thread
        for name in INNER:
            assert len(by_name[name]) == sums[name][1], name
            for thread, a, b in by_name[name]:
                assert any(
                    t == thread and a0 <= a and b <= b0
                    for t, a0, b0 in iterations
                ), f"{name} outside every drive.iteration"
        traced_iter = sum(b - a for _, a, b in iterations) / 1e9
        traced_inner = sum(
            b - a for n in INNER for _, a, b in by_name[n]
        ) / 1e9
        assert traced_iter == pytest.approx(
            sums["drive.iteration"][0], rel=0.05
        )
        assert traced_inner == pytest.approx(
            sum(sums[n][0] for n in INNER), rel=0.05
        )


# The host work the new spans are named for, by the Python tracer's frame
# (file, function), and the spans it may run in: one named for it, or, on
# a verify step's own path, the drive.* phase it ran in before.
NAMED_FOR = {
    ("prefix_cache.py", "insert"): {
        "handoff.insert", "collect.books", "admit.promote",
    },
    ("prefix_cache.py", "lookup_tiered"): {"admit.lookup"},
    ("scheduler.py", "_gauge_pool"): {"handoff.books", "collect.books"},
    ("scheduler.py", "_release_slot"): {"collect.release"},
    ("scheduler.py", "_fetch_page_kv"): {"admit.evict", "handoff.insert"},
    ("scheduler.py", "_deliver_stream"): {
        "handoff.deliver", "collect.deliver", "drive.stream",
    },
}


def test_host_work_lies_in_the_span_named_for_it(tiny, spec_on, tmp_path):
    """Under the Python tracer (the benchmark's traced run) every call of
    the functions whose idle time the profile used to name by its frame
    lies inside the span named for it: the spans can take the frames'
    place."""
    from adversarial_spec_tpu.engine import kvtier

    obs.configure(enabled=True)
    # the host tier, as the benchmark's cells run: the tiered lookup, and
    # the demotion's read warmed at the first handoff
    kvtier.configure(enabled=True, host_mb=16, store_dir="")
    host = _profile(tiny, tmp_path, python_tracer_level=1, prompt=150)
    spans = [
        (t, n[len("advspec."):], a, a + d)
        for t, n, a, d in host
        if n.startswith("advspec.")
    ]
    frame = re.compile(r"(\w+\.py):\d+ (\w+)$")
    ran = set()
    for thread, name, a, d in host:
        m = frame.search(name)
        if m is None or m.groups() not in NAMED_FOR:
            continue
        around = [
            (s1 - s0, span)
            for t, span, s0, s1 in spans
            if t == thread and s0 <= a and a + d <= s1
        ]
        assert around, f"{name}: in no span"
        innermost = min(around)[1]
        assert innermost in NAMED_FOR[m.groups()], (name, innermost)
        ran.add(m.groups())
    assert ran == set(NAMED_FOR)


# -- the names the benchmark's patterns lean on ------------------------------


def _jitted_programs() -> set[str]:
    """`jit_<__name__>` of every jitted program the serving path
    defines: what the profile's `XLA Modules` line calls them."""
    names = set()
    for mod in (sched_mod, generate_mod):
        for obj in vars(mod).values():
            if hasattr(obj, "lower") and hasattr(obj, "__name__"):
                names.add("jit_" + obj.__name__)
    return names


def _kernel_names() -> set[str]:
    """The explicit ``name=`` of every ``pallas_call`` under ops/."""
    names = set()
    ops = ROOT / "adversarial_spec_tpu" / "ops"
    for path in ops.glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (
                isinstance(node, ast.Call)
                and getattr(node.func, "attr", "") == "pallas_call"
            ):
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{path.name}: a pallas_call with no name="
                names.add(ast.literal_eval(kw["name"]))
    return names


def _patterns(node) -> list[str]:
    out = []
    if isinstance(node, dict):
        for k, v in node.items():
            if k in ("pattern", "within") and isinstance(v, str):
                out.append(v)
            else:
                out.extend(_patterns(v))
    return out


def test_every_pallas_call_is_named_after_its_entry_point():
    assert _kernel_names() == {
        "matmul_int8", "matmul_int4", "paged_decode_attention",
        "paged_decode_attention_mq", "decode_attention",
        "decode_attention_mq", "matmul_int8_grouped",
        "paged_latent_attention_mq", "ssm_span_read", "ssm_span_update",
        "paged_kv_write",
    }


@pytest.mark.parametrize("metric_file", PATTERN_FILES)
def test_trace_patterns_name_what_the_program_defines(metric_file):
    """A rename of a jitted step program or of a kernel fails here, not
    in the benchmark: every alternative of every `pattern` / `within` is
    a jitted program's name, the start of a kernel's, or one of XLA's own
    fusion names."""
    programs, kernels = _jitted_programs(), _kernel_names()
    spec = json.loads((METRICS / metric_file).read_text())
    patterns = _patterns(spec)
    assert patterns
    for pattern in patterns:
        for alt in pattern.lstrip("^").strip("()").split("|"):
            alt = alt.lstrip("^")
            if alt.startswith("jit_"):
                assert alt in programs, f"{metric_file}: no program {alt}"
            elif alt.endswith("fusion"):
                continue  # XLA's generic name for a fusion: nothing pins it
            else:
                assert any(k.startswith(alt) for k in kernels), (
                    f"{metric_file}: no kernel named {alt}*"
                )


def _scoped_op_names(lowered_text: str) -> list[str]:
    """The name-stack location of every matmul, convolution and custom
    call in a lowered program's debug text."""
    locs = dict(
        re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered_text, flags=re.M)
    )
    kinds = (
        "stablehlo.dot_general", "stablehlo.custom_call",
        "stablehlo.convolution",
    )
    out = []
    for line in lowered_text.splitlines():
        if any(k in line for k in kinds):
            m = re.search(r"loc\((#loc\d+)\)\s*$", line)
            out.append(locs.get(m.group(1), "") if m else "")
    return out


DENSE_SCOPES = {"attn", "mlp", "qmm", "head"}
MODEL_SCOPES = {
    "mistral": DENSE_SCOPES,
    "mistral4": DENSE_SCOPES
    | {"attn.latent", "moe.route", "moe.experts", "moe.shared"},
    # (ssm.conv and ssm.gate_norm hold no matmul: elementwise work alone)
    "granitemoehybrid": {"attn", "mlp", "head", "ssm", "ssm.scan"},
}


@pytest.mark.parametrize("family", MODEL_SCOPES)
def test_step_programs_run_under_declared_scopes(family, spec_on, monkeypatch):
    """Every matmul and custom call of the verify step and of the
    prefill chunk lies under one of the declared device scopes, and every
    scope a layer kind brings is met in both."""
    cfg = get_config(family, "tiny")
    tiny = init_params(jax.random.PRNGKey(0), cfg), cfg
    seen: dict = {}

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape")
            else x,
            tree,
        )

    for name in ("scheduler_spec_chunk", "prefill_chunk"):
        real = getattr(sched_mod, name)

        def tapped(*a, _real=real, _name=name, **k):
            seen.setdefault(_name, (_real, shapes(a), shapes(k)))
            return _real(*a, **k)

        monkeypatch.setattr(sched_mod, name, tapped)
    _drain(tiny)
    assert set(seen) == {"scheduler_spec_chunk", "prefill_chunk"}
    for name, (real, args, kwargs) in seen.items():
        text = real.lower(*args, **kwargs).as_text(debug_info=True)
        names = _scoped_op_names(text)
        assert len(names) >= 5, name
        for op in names:
            assert set(op.split("/")) & set(obs.DEVICE_SCOPES), (name, op)
        found = {s for op in names for s in op.split("/")}
        assert MODEL_SCOPES[family] <= found, (name, found)


# -- every key a new metric file reads is one the program produces -----------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One streamed debate of two opponents through `advspec serve` and
    the batcher at tiny size: the counters it leaves and what came back."""
    from adversarial_spec_tpu.engine import dispatch
    from adversarial_spec_tpu.serve import gate
    from adversarial_spec_tpu.serve.client import ServeClient
    from adversarial_spec_tpu.serve.daemon import ServeDaemon
    from perfbench.system import _flatten  # the harness's own `obs.<series>` keys

    tmp = tmp_path_factory.mktemp("served")
    mp = pytest.MonkeyPatch()
    mp.setattr(registry_mod, "REGISTRY_PATH", tmp / "registry.json")
    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    spec_mod.configure(enabled=True)
    registry_mod.save_registry_entry(
        registry_mod.ModelSpec(
            alias="paged-tiny", family="mistral", size="tiny", kv="paged",
            dtype="float32", mesh={"dp": 1, "tp": 1, "sp": 1},
        )
    )
    obs.configure(enabled=True)
    obs.reset_stats()
    serve_mod.configure(max_queue_depth=8, max_backlog_tokens=10**6)
    sock = str(tmp / "s.sock")
    ready = threading.Event()
    daemon = ServeDaemon(sock, sessions_dir=str(tmp / "sessions"))
    th = threading.Thread(
        target=lambda: asyncio.run(daemon.run(ready=ready)), daemon=True
    )
    th.start()
    assert ready.wait(10), "daemon did not come up"
    client = ServeClient(sock, timeout_s=300)
    try:
        rid = client.send(
            {
                "op": "debate", "tenant": "t0", "spec": "## Spec\nBody.\n",
                "models": ["tpu://paged-tiny", "tpu://paged-tiny"],
                "stream": True, "greedy": True, "max_new_tokens": 12,
                "return_token_ids": True,
            }
        )
        events = client.collect(rid, timeout_s=300)
        profile_error = client.call(
            {"op": "profile", "seconds": 0, "dir": str(tmp / "prof")}
        )
        out: dict = {}
        _flatten("obs", obs.metrics.snapshot(), out)
    finally:
        client.drain()
        client.close()
        th.join(timeout=30)
        gate.uninstall()
        dispatch.clear_engine_cache()
        spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)
        mp.undo()
    assert not th.is_alive()
    return {"counters": out, "events": events, "profile_error": profile_error}


@pytest.mark.parametrize("metric_file", NEW_FILES)
def test_new_metric_files_read_keys_the_program_produces(served, metric_file):
    params = json.loads((METRICS / metric_file).read_text())["params"]
    keys = list(params.get("num", [])) + list(params.get("den", []))
    keys += list(params.get("keys", []))
    if "key" in params:
        keys += [params["key"] + ".sum", params["key"] + ".count"]
    assert keys
    for key in keys:
        assert key in served["counters"], f"{metric_file}: no counter {key}"
    name = metric_file[: -len(".json")]
    entries = [
        m for m in BENCH["per_layer"]
        if m["name"] == name or m["name"].startswith(name + ".")
    ]
    assert entries and all(m.get("workloads") for m in entries)


def test_new_metric_files_are_the_issues_seventeen():
    assert len(NEW_FILES) == 17


def test_result_carries_timing_and_token_ids(served):
    events = served["events"]
    assert [e["event"] for e in events][-1] == "result"
    result = events[-1]
    assert result.get("error") is None
    streams = [e for e in events if e["event"] == "stream"]
    assert streams
    for index in (0, 1):
        r = result["results"][index]
        assert r["error"] is None
        timing = r["timing"]
        assert set(timing) == {
            "serve_queue_s", "batcher_queue_s", "prefill_s", "decode_s"
        }
        assert all(v >= 0.0 for v in timing.values())
        assert timing["prefill_s"] > 0.0 and timing["decode_s"] > 0.0
        assert len(r["token_ids"]) == r["output_tokens"] == 12
        assert len(r["prompt_token_ids"]) == r["input_tokens"]
        mine = [e for e in streams if e["index"] == index]
        counts = [e["n_tokens"] for e in mine]
        assert counts == sorted(counts) and counts[-1] == 12
    # the two opponents of one debate get the same prompt
    assert (
        result["results"][0]["prompt_token_ids"]
        == result["results"][1]["prompt_token_ids"]
    )
    c = served["counters"]
    assert c["obs.advspec_serve_ttft_seconds.count"] == 2
    assert c["obs.advspec_batcher_builds_total"] == 1
    assert c['obs.advspec_phase_seconds{phase="serve.dispatch"}.count'] == 1
    assert c['obs.advspec_phase_seconds{phase="engine.run_all"}.count'] == 1


def test_token_ids_and_n_tokens_are_off_by_default(tmp_path):
    from adversarial_spec_tpu.serve import protocol

    assert protocol.validate_request(
        {"op": "debate", "id": "c1", "tenant": "t", "spec": "s",
         "models": ["mock://agree"], "return_token_ids": True}
    ) == []
    assert protocol.validate_request(
        {"op": "debate", "id": "c1", "tenant": "t", "spec": "s",
         "models": ["mock://agree"], "return_token_ids": 1}
    )


def test_profile_op_validates_and_refuses_cleanly(served):
    from adversarial_spec_tpu.serve import protocol

    good = {"op": "profile", "id": "p1", "seconds": 2.5, "dir": "/tmp/x"}
    assert protocol.validate_request(good) == []
    for bad in (
        {**good, "seconds": 0},
        {**good, "seconds": 10**6},
        {**good, "seconds": True},
        {k: v for k, v in good.items() if k != "dir"},
    ):
        assert protocol.validate_request(bad), bad
    # over the wire: a malformed window is an error event, not a crash
    assert served["profile_error"]["event"] == "error"
    assert "seconds" in served["profile_error"]["message"]


def test_profile_op_writes_a_profile(tmp_path):
    """The op end to end against a live daemon: the reply names an
    .xplane.pb, and tools/trace_view.py reads the phases out of it."""
    from adversarial_spec_tpu.serve.client import ServeClient
    from adversarial_spec_tpu.serve.daemon import ServeDaemon
    from tools import trace_view

    sock = str(tmp_path / "s.sock")
    ready = threading.Event()
    daemon = ServeDaemon(sock, sessions_dir=str(tmp_path / "sessions"))
    th = threading.Thread(
        target=lambda: asyncio.run(daemon.run(ready=ready)), daemon=True
    )
    th.start()
    assert ready.wait(10)
    client = ServeClient(sock)
    try:
        reply = client.profile(0.2, str(tmp_path / "prof"))
        assert reply["event"] == "ok", reply
        assert reply["path"].endswith(".xplane.pb")
        assert Path(reply["path"]).exists()
    finally:
        client.drain()
        client.close()
        th.join(timeout=15)
    # no accelerator here, so no device plane: the tool says so (exit 1)
    assert trace_view.main(["--xplane", reply["path"]]) == 1


def test_idle_time_goes_to_the_innermost_phase():
    from tools.trace_view import NO_PHASE, idle_by_phase

    phases = [
        ("engine.run_all", -50, 300),
        ("drive.iteration", 0, 100),
        ("drive.dispatch", 10, 20),
        ("drive.fetch", 20, 90),
        ("drive.iteration", 100, 200),
    ]
    busy = [(-100, -60), (25, 85), (120, 180)]
    table = idle_by_phase(busy, phases)
    idle = {k: round(v["idle_s"] * 1e9) for k, v in table.items()}
    assert idle == {
        NO_PHASE: 10, "engine.run_all": 50, "drive.iteration": 40,
        "drive.dispatch": 10, "drive.fetch": 10,
    }
    assert sum(idle.values()) == (25 - -60) + (120 - 85)
    # where in the phase's stretch the device sat idle: before its first
    # operation there (launch), after its last (completion), or throughout
    fetch = {k: round(v * 1e9) for k, v in table["drive.fetch"].items()}
    assert (fetch["head"], fetch["between"], fetch["tail"]) == (5, 0, 5)
    assert round(table["drive.dispatch"]["whole"] * 1e9) == 10
