#!/usr/bin/env python3
"""chip_smoke.py — does the serving path still start on the chip?

Drives the system's main path once, through the entry points a user calls,
at the full width of Mistral-7B-v0.3 (random weights from a seed, int8, full
depth), and checks what comes out. The quickest proof that `advspec serve`
→ TpuEngine.chat → ContinuousBatcher → the paged Pallas kernels run on a
TPU v5e. Facts about one run, not benchmark numbers.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # only: tp=4 generate() vs one device

The last line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and the exit code 0 — only when every phase passed on a TPU. Any failure,
and any other platform, is a non-zero exit with no "ok": true anywhere.
`JAX_PLATFORMS=cpu python chip_smoke.py` is the rehearsal: every phase at
tiny size, then the verdict fails because the platform is not `tpu`.

One process owns a chip. The default run therefore stays off jax while it
launches the processes that need the chip ONE AT A TIME (a device listing,
the registry edit, the daemon, the follow-up CLI round), and only touches
jax itself for the last phase, after all of them have exited. `--chips 4`
starts no process at all.

Everything it writes (registry, sessions, socket, event files, reports)
lives under --out (default ./chip_smoke_out); children get HOME=<out>/home,
so nothing is read from or written to ~/.config or ~/.cache.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import signal
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ALIAS = "smoke-mistral"
CLI = [sys.executable, "-m", "adversarial_spec_tpu.cli"]

# Real size: Mistral-7B-v0.3 published widths, full depth, int8 weights,
# paged KV (what routes a one-device model to the ContinuousBatcher).
REAL = dict(size="7b", doc_bytes=4096, para_bytes=400, max_new=128)
# JAX_PLATFORMS=cpu rehearsal: same family, toy widths, short texts.
TINY = dict(size="tiny", doc_bytes=600, para_bytes=100, max_new=16)

# The batcher's jitted step programs (engine/scheduler.py): verify and
# plain decode, each alone and with an admission's prefill chunk fused in.
STEP_PROGRAMS = (
    "scheduler_spec_chunk",
    "fused_prefill_spec_chunk",
    "scheduler_decode_chunk",
    "fused_prefill_decode_chunk",
)

_WORDS = (
    "the service must shall may request response retry timeout queue worker "
    "tenant quota budget replica shard index cache page block token prefix "
    "session round debate opponent critique revision document section schema "
    "field record latency throughput backlog admission deadline failure "
    "recovery journal snapshot rollback version migration endpoint payload "
    "header signature key secret audit log metric alert threshold capacity"
).split()


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """Every check prints its verdict; the first failed one ends the run."""
    say(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise SmokeFailure(what)


def fact(name: str, value) -> None:
    say(f"  fact {name}: {json.dumps(value, sort_keys=True)}")


class Phases:
    """Wall clock per phase, printed as each ends (the driver's time
    limit is judged from these)."""

    def __init__(self) -> None:
        self.walls: dict[str, float] = {}
        self._t0 = time.monotonic()

    @contextlib.contextmanager
    def __call__(self, name: str):
        say(f"== phase {name}")
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.walls[name] = round(time.monotonic() - t0, 1)
            say(f"== phase {name}: {self.walls[name]} s")

    def total(self) -> float:
        return round(time.monotonic() - self._t0, 1)


# -- seeded text -----------------------------------------------------------


def make_doc(seed: int, n_bytes: int, title: str) -> str:
    """A spec-shaped document of ~n_bytes from a seed (one byte is one
    token for the synthetic checkpoints' byte tokenizer)."""
    rng = random.Random(seed)
    out = [f"# {title}\n"]
    size = len(out[0])
    section = 0
    while size < n_bytes:
        section += 1
        block = [f"\n## {section}. {' '.join(rng.sample(_WORDS, 3)).title()}\n"]
        for _ in range(rng.randint(3, 6)):
            words = [rng.choice(_WORDS) for _ in range(rng.randint(8, 16))]
            block.append(" ".join(words).capitalize() + ".\n")
        text = "".join(block)
        out.append(text)
        size += len(text)
    return "".join(out)[:n_bytes].rstrip() + "\n"


def make_paragraph(seed: int, n_bytes: int) -> str:
    return "\n## Addendum\n" + make_doc(seed, n_bytes, "x").split("\n", 1)[1]


# -- children --------------------------------------------------------------


def child_env(out: Path) -> dict:
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS", "").startswith("cpu"):
        # Rehearsal: tiny programs compile in under jax's one-second
        # floor for a persistent-cache entry; without entries the
        # cross-process cache check would have nothing to check.
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["HOME"] = str(out / "home")
    env["ADVSPEC_SESSIONS_DIR"] = str(out / "sessions")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    env["PYTHONUNBUFFERED"] = "1"
    return env


def run_child(args: list, out: Path, stdin: str | None = None, timeout=900):
    """One child to completion; a non-zero exit fails the smoke."""
    proc = subprocess.run(
        args,
        input=stdin,
        capture_output=True,
        text=True,
        env=child_env(out),
        cwd=str(out),
        timeout=timeout,
    )
    if proc.returncode != 0:
        say(proc.stderr[-4000:])
        raise SmokeFailure(f"{' '.join(map(str, args[:4]))}… exited {proc.returncode}")
    return proc


def cache_entries(cache_dir: str) -> int:
    p = Path(cache_dir)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


# -- result checks shared by the daemon rounds and the CLI round -----------


def check_results(label: str, results: list[dict], n: int) -> None:
    check(len(results) == n, f"{label}: {n} opponent result(s)")
    for i, r in enumerate(results):
        check(not r.get("error"), f"{label}[{i}]: no error ({r.get('error')})")
        check(
            r.get("output_tokens", 0) > 0,
            f"{label}[{i}]: output_tokens={r.get('output_tokens')} > 0, "
            f"input_tokens={r.get('input_tokens')}, "
            f"cached_tokens={r.get('cached_tokens')}",
        )


# -- the one-chip run ------------------------------------------------------


def one_chip(args, out: Path, phases: Phases) -> dict:
    from adversarial_spec_tpu.serve.client import ServeClient
    from adversarial_spec_tpu.serve.protocol import TERMINAL_EVENTS
    from adversarial_spec_tpu.utils.jaxenv import compile_cache_dir

    rehearsal = os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    size = TINY if rehearsal else REAL
    cache_dir = compile_cache_dir()
    fact("compile_cache_dir", cache_dir)
    fact("compile_cache_entries_before", cache_entries(cache_dir))

    with phases("preflight"):
        # `providers --json` is the user's device listing. A machine
        # whose jax finds no accelerator stops here (unless the caller
        # asked for the CPU rehearsal by name): nothing below falls back.
        proc = run_child(CLI + ["providers", "--json"], out)
        devices = json.loads(proc.stdout)["devices"]
        fact("devices", devices)
        check(
            devices["platform"] == "tpu" or rehearsal,
            f"jax platform is {devices['platform']!r} (tpu, or an explicit "
            "JAX_PLATFORMS=cpu rehearsal)",
        )

    with phases("register"):
        run_child(
            CLI
            + ["registry", "add-model", ALIAS, "--family", "mistral"]
            + ["--size", size["size"], "--quant", "int8", "--kv", "paged"],
            out,
        )
        reg_path = out / "home/.config/adversarial-spec-tpu/registry.json"
        entry = json.loads(reg_path.read_text())[ALIAS]
        fact("registry_file", str(reg_path))
        fact("registry_entry", entry)
        check(
            entry["checkpoint"] == "random"
            and entry["quant"] == "int8"
            and entry["kv"] == "paged"
            and entry["n_layers"] == 0,
            "registered mistral/" + size["size"] + ": random weights, int8, "
            "paged KV, published depth",
        )

    model = f"tpu://{ALIAS}"
    doc = make_doc(args.seed, size["doc_bytes"], "Webhook Delivery Service")
    doc_r2 = doc + make_paragraph(args.seed + 1, size["para_bytes"])
    doc_y = make_doc(args.seed + 2, size["doc_bytes"], "Tenant Quota Ledger")
    doc_z = make_doc(args.seed + 3, size["doc_bytes"], "Audit Log Pipeline")
    fact("document_bytes", {"round1": len(doc), "round2": len(doc_r2)})

    sock = out / "serve.sock"
    events_path = out / "daemon_events.jsonl"
    drain_path = out / "drain_report.json"
    daemon_log = open(out / "daemon.log", "w")
    daemon = subprocess.Popen(
        CLI
        + ["serve", "--socket", str(sock), "--events-out", str(events_path)]
        + ["--drain-report", str(drain_path)]
        + ["--flight-recorder-size", "400000"],
        stdout=daemon_log,
        stderr=subprocess.STDOUT,
        env=child_env(out),
        cwd=str(out),
    )
    try:
        with phases("daemon_start"):
            deadline = time.monotonic() + 120
            while not sock.exists():
                if daemon.poll() is not None or time.monotonic() > deadline:
                    raise SmokeFailure("the daemon's socket never appeared")
                time.sleep(0.2)
            client = ServeClient(str(sock), timeout_s=1000.0)
            check(client.ping().get("event") == "pong", "daemon answers ping")

        def debate(conn, label, spec, n, round_num, stream=False, tenant="t0"):
            rid = conn.submit_debate(
                spec,
                [model] * n,
                tenant=tenant,
                round_num=round_num,
                stream=stream,
                max_new_tokens=size["max_new"],
            )
            return label, rid, n

        def settle(label, final, n):
            check(
                final.get("event") == "result" and not final.get("error"),
                f"{label}: terminal event is a result "
                f"({final.get('event')}: {final.get('error') or final.get('message')})",
            )
            check_results(label, final["results"], n)
            fact(
                f"{label}.serve",
                {k: final[k] for k in ("wall_s", "ttft_s", "round")},
            )
            return final

        def collect(conn, label, rid, n):
            return settle(label, conn.collect(rid, timeout_s=1000.0)[-1], n)

        with phases("round1_cold"):
            # 4 opponents, one document: loads the model, compiles cold.
            collect(client, *debate(client, "round1", doc, 4, 1))

        with phases("round2_and_concurrent"):
            # Round 2 (streamed): the same document plus a paragraph, so
            # the prefix cache has round 1's pages to hit. Once its first
            # text arrives (it holds the engine now), a second client
            # submits two more debates on FRESH documents; they queue
            # behind it and the scheduler coalesces them into one 4-row
            # dispatch, where the second document's admission prefills
            # while the first one's rows decode — the fused step.
            label, rid, n = debate(client, "round2", doc_r2, 4, 2, stream=True)
            other = ServeClient(str(sock), timeout_s=1000.0)
            ev2, queued = [], []
            while not ev2 or ev2[-1].get("event") not in TERMINAL_EVENTS:
                ev2.append(client.recv(timeout_s=1000.0) or {"event": "error"})
                if ev2[-1].get("event") == "stream" and not queued:
                    queued = [
                        debate(other, "debateY", doc_y, 2, 1, tenant="t1"),
                        debate(other, "debateZ", doc_z, 2, 1, tenant="t2"),
                    ]
            n_stream = sum(1 for e in ev2 if e.get("event") == "stream")
            check(n_stream > 0, f"round2 streamed {n_stream} delivery event(s)")
            r2 = settle(label, ev2[-1], n)
            for pending in queued:
                collect(other, *pending)
            other.close()
            cached = [r["cached_tokens"] for r in r2["results"]]
            check(
                min(cached) > 0,
                f"round2 prefix-cache hit tokens per opponent {cached} > 0 "
                "(round 1's pages; every opponent, including the first)",
            )

        with phases("daemon_stats_and_drain"):
            stats = client.stats()
            probe = client.check()
            check(probe.get("ok") is True, f"allocator/tier invariants: {probe}")
            device = stats["device"]
            fact("daemon.device", device)
            fact("daemon.serve", stats["serve"])
            check(device is not None, "daemon reports the device that ran the model")
            client.close()
            daemon.send_signal(signal.SIGTERM)
            rc = daemon.wait(timeout=120)
            check(rc == 0, f"daemon drained and exited 0 on SIGTERM (rc={rc})")
            drain = json.loads(drain_path.read_text())
            fact(
                "drain_report",
                {
                    k: drain.get(k)
                    for k in (
                        "reason",
                        "clean_exit",
                        "inflight_at_exit",
                        "drained_units_at_deadline",
                    )
                },
            )
            check(
                drain.get("clean_exit") is True
                and drain.get("drained_units_at_deadline") == 0,
                "drain report: clean exit, nothing shed at the deadline",
            )
    except Exception:
        daemon_log.flush()
        say("---- daemon.log (tail) ----")
        say((out / "daemon.log").read_text(errors="replace")[-6000:])
        raise
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()
        daemon_log.close()

    with phases("daemon_events"):
        events = [
            json.loads(line)
            for line in events_path.read_text().splitlines()
            if line.strip()
        ]
        by_type: dict[str, list] = {}
        for e in events:
            by_type.setdefault(e["type"], []).append(e)
        steps: dict[str, int] = {}
        for e in by_type.get("step", []):
            steps[e["kind"]] = steps.get(e["kind"], 0) + 1
        fact("daemon.events_by_type", {k: len(v) for k, v in by_type.items()})
        fact("daemon.steps_by_kind", steps)
        check(
            steps.get("spec", 0) > 0,
            f"speculative verify steps ran in the batcher ({steps.get('spec', 0)})",
        )
        check(
            steps.get("fused_spec", 0) > 0,
            "an admission rode resident rows' verify step "
            f"(fused_spec steps: {steps.get('fused_spec', 0)})",
        )
        hits = [
            e for e in by_type.get("cache", []) if e["op"] == "lookup" and e["hit"]
        ]
        fact(
            "daemon.prefix_cache",
            {
                "lookups_hit": len(hits),
                "matched_tokens": sum(e["matched_tokens"] for e in hits),
            },
        )
        faults = by_type.get("fault", [])
        check(not faults, f"zero faults in the daemon ({faults[:2]})")
        check(
            not any(e.get("requeued") for e in faults),
            "zero requeues in the daemon",
        )
        opened = [e for e in by_type.get("breaker", []) if e["to"] == "open"]
        check(not opened, f"zero breaker opens ({opened[:2]})")
        compiles = by_type.get("compile", [])
        unexpected = [e for e in compiles if e["unexpected"]]
        fact(
            "daemon.compiles_by_program",
            {
                p: sum(1 for e in compiles if e["program"] == p)
                for p in sorted({e["program"] for e in compiles})
            },
        )
        check(not unexpected, f"zero unexpected recompiles ({unexpected[:2]})")
        check(
            stats["serve"]["shed_debates"] == 0
            and stats["serve"]["accepted_debates"] == 4
            and stats["serve"]["completed_debates"] == 4,
            "daemon accepted 4 debates, completed 4, shed none",
        )

    with phases("cli_followup"):
        # The L5 protocol's real shape: a fresh process per round. Same
        # document, same pool shape (4 opponents) — so every program it
        # needs is in the persistent compile cache the daemon filled.
        proc = run_child(
            CLI
            + ["critique", "--models", ",".join([model] * 4), "--json"]
            + ["--max-new-tokens", str(size["max_new"])]
            + ["--events-out", str(out / "cli_events.jsonl")]
            + ["--metrics-out", str(out / "cli_metrics.prom")],
            out,
            stdin=doc,
        )
        report = json.loads(proc.stdout)
        perf = report["perf"]
        check_results("cli", report["results"], 4)
        fact("cli.device", perf["device"])
        fact("cli.spec", perf["spec"])
        fact(
            "cli.prefix_cache",
            {k: perf["prefix_cache"][k] for k in ("hits", "lookups", "saved_tokens")},
        )
        check(perf["spec"]["spec_steps"] > 0, "cli: perf.spec steps > 0")
        check(
            not perf["resilience"]["faults"],
            f"cli: zero faults ({perf['resilience']['faults']})",
        )
        check(
            perf["obs"]["retrace"]["unexpected_recompiles"] == 0,
            "cli: zero unexpected recompiles",
        )
        cold = device["compile"]
        warm = perf["device"]["compile"]
        keys = ("backend_compile_s", "persistent_cache_hits", "persistent_cache_misses")
        fact(
            "compile_seconds",
            {
                "daemon": {k: cold[k] for k in keys},
                "cli_followup": {k: warm[k] for k in keys},
            },
        )
        check(
            warm["cache_dir"] == cold["cache_dir"] == cache_dir,
            f"one compile cache directory everywhere: {cache_dir}",
        )
        check(
            warm["persistent_cache_hits"] > 0,
            "cli: programs came from the persistent compile cache "
            f"({warm['persistent_cache_hits']} hits)",
        )
        if cold["persistent_cache_misses"] > cold["persistent_cache_hits"]:
            check(
                warm["backend_compile_s"] < 0.5 * cold["backend_compile_s"],
                f"cli compiled for {warm['backend_compile_s']} s against the "
                f"daemon's cold {cold['backend_compile_s']} s",
            )
        else:
            # The machine came with this repository's cache: the daemon
            # started warm too, and there is no cold compile to compare.
            check(
                warm["persistent_cache_misses"] <= cold["persistent_cache_misses"] + 2,
                "daemon started on a warm cache "
                f"({cold['persistent_cache_hits']} hits); the cli process "
                f"added {warm['persistent_cache_misses']} entries",
            )
        check(
            perf["device"]["platform"] == device["platform"]
            and perf["device"]["kind"] == device["kind"],
            "cli ran on the same device kind as the daemon",
        )

    with phases("kernel_census"):
        # Every child has exited, so this process may touch jax now.
        census = kernel_census(out, model, size, [doc, doc_y, doc_z])
        fact("tpu_custom_calls_by_program", census)
        want_kernels = device["platform"] == "tpu"
        for program in STEP_PROGRAMS:
            check(program in census, f"{program} ran in the census replay")
            if want_kernels:
                check(
                    census[program] > 0,
                    f"{program}: {census[program]} tpu_custom_call(s) — the "
                    "default-on kernels are in the compiled program",
                )
        if not want_kernels:
            say("  (not tpu: kernels are off by design, counts recorded only)")

    fact("compile_cache_entries_after", cache_entries(cache_dir))
    fact(
        "device_memory",
        {k: device["memory"].get(k) for k in ("peak_bytes_in_use", "bytes_limit")},
    )
    return {k: device[k] for k in ("platform", "kind", "count")}


def kernel_census(out: Path, model: str, size: dict, docs: list[str]) -> dict:
    """Which device kernels the step programs actually contain.

    The batcher picks its kernels from what it observes (platform, leaf
    types, shapes) and quietly takes the XLA path when a condition fails.
    This replays the smoke's traffic through the same TpuEngine in this
    process, notes the argument shapes of every step program the batcher
    dispatches, then lowers and compiles each one again from those shapes
    (a persistent-cache hit for everything the daemon already compiled)
    and counts `tpu_custom_call` in the compiled text. Nothing in the
    program is switched for this: the step functions are only wrapped,
    from out here, to see their arguments.
    """
    os.environ.update(child_env(out))  # registry path = Path.home()/…
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import jax

    from adversarial_spec_tpu.debate.core import RoundConfig, build_request
    from adversarial_spec_tpu.engine import scheduler as sched
    from adversarial_spec_tpu.engine import spec as spec_mod
    from adversarial_spec_tpu.engine.tpu import TpuEngine
    from adversarial_spec_tpu.engine.types import SamplingParams

    seen: dict[str, tuple] = {}
    originals = {name: getattr(sched, name) for name in STEP_PROGRAMS}

    def abstract(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x

    def watch(name):
        fn = originals[name]

        def wrapped(*a, **kw):
            if name not in seen:
                seen[name] = jax.tree.map(abstract, (a, kw))
            return fn(*a, **kw)

        return wrapped

    for name in STEP_PROGRAMS:
        setattr(sched, name, watch(name))
    try:
        engine = TpuEngine()
        sampling = SamplingParams(max_new_tokens=size["max_new"], seed=0)
        cfg = RoundConfig()
        # Two documents in one 4-row call: the second document's
        # admission finds rows already decoding and rides their step.
        # The second pass (speculation off: the plain decode programs)
        # needs a document the prefix cache has not seen, or nothing
        # would be left to prefill.
        for speculative, second in ((True, docs[1]), (False, docs[2])):
            spec_mod.configure(enabled=speculative)
            batch = [
                build_request(model, d, 1, cfg) for d in (docs[0],) * 2 + (second,) * 2
            ]
            for comp in engine.chat(batch, sampling):
                if comp.error:
                    raise SmokeFailure(f"census replay: {comp.error}")
    finally:
        for name, fn in originals.items():
            setattr(sched, name, fn)
    census = {}
    for name, (a, kw) in seen.items():
        text = originals[name].lower(*a, **kw).compile().as_text()
        census[name] = text.count("tpu_custom_call")
    return census


# -- the four-chip run -----------------------------------------------------


def four_chips(args, out: Path, phases: Phases) -> dict:
    """Only the sharded path and what it is compared with: the same seeded
    Mistral-7B-width model in bf16, depth cut, greedy, through
    TpuEngine.chat on mesh tp=4 and on a one-device submesh, one process."""
    os.environ.update(child_env(out))
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adversarial_spec_tpu.debate.core import RoundConfig, build_request
    from adversarial_spec_tpu.engine.registry import ModelSpec, save_registry_entry
    from adversarial_spec_tpu.engine.tokenizer import apply_chat_template
    from adversarial_spec_tpu.engine.tpu import TpuEngine, per_chip_param_bytes
    from adversarial_spec_tpu.engine.types import SamplingParams
    from adversarial_spec_tpu.engine.generate import generate, prefill_chunk
    from adversarial_spec_tpu.models.transformer import init_cache

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    fact("devices", device)
    check(len(devs) == 4, f"four devices ({len(devs)})")
    rehearsal = device["platform"] == "cpu"
    # 16 of 32 layers: 7.5 GB of bf16, what one 16 GB chip holds beside
    # the quarter of the sharded copy that shares device 0 with it.
    size, depth = ("tiny-tp4", 0) if rehearsal else ("7b", 16)
    n_new = 16 if rehearsal else 32
    doc = make_doc(args.seed, 600 if rehearsal else 1500, "Webhook Delivery Service")
    common = dict(family="mistral", size=size, n_layers=depth, dtype="bfloat16")
    # An empty mesh spec would put leftover devices on dp: pin every axis.
    save_registry_entry(ModelSpec(alias="smoke-tp4", mesh={"dp": 1, "tp": 4, "sp": 1}, **common))
    save_registry_entry(ModelSpec(alias="smoke-one", mesh={"dp": 1, "tp": 1, "sp": 1}, **common))
    fact("model", {**common, "n_layers": depth or "published"})

    engine = TpuEngine()
    sampling = SamplingParams(max_new_tokens=n_new, greedy=True, seed=0)
    req = lambda alias: build_request(f"tpu://{alias}", doc, 1, RoundConfig())  # noqa: E731

    def in_use():
        return [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devs]

    with phases("tp4"):
        before = in_use()
        [sharded] = engine.chat([req("smoke-tp4")], sampling)
        check(not sharded.error, f"tp=4 chat: no error ({sharded.error})")
        check(sharded.usage.output_tokens > 0, "tp=4 chat: output tokens > 0")
        lm4 = engine._models["smoke-tp4"]
        leaves = jax.tree.leaves(lm4.params)
        total = sum(x.nbytes for x in leaves)
        replicated = sum(x.nbytes for x in leaves if x.sharding.is_fully_replicated)
        per_chip = per_chip_param_bytes(lm4.params)
        held = [a - b for a, b in zip(in_use(), before)]
        fact(
            "param_bytes",
            {
                "total": total,
                "replicated": replicated,
                "per_chip": per_chip,
                "per_chip_share": round(per_chip / total, 4),
                "bytes_in_use_delta": held,
            },
        )
        # Embedding and norms replicate; every matmul weight splits 4 ways.
        check(
            per_chip - replicated == (total - replicated) // 4,
            "every sharded weight puts exactly a quarter of its bytes on each chip",
        )
        check(
            rehearsal or per_chip <= 0.30 * total,
            f"each chip holds about a quarter of the parameter bytes "
            f"({per_chip / total:.3f}"
            + ("; toy widths replicate a larger share)" if rehearsal else ")"),
        )
        if any(held):  # the CPU backend keeps no memory statistics
            check(
                all(0.9 * per_chip <= h <= 0.45 * total for h in held),
                f"every device's bytes_in_use grew by about its share: {held}",
            )

    with phases("one_device"):
        [single] = engine.chat([req("smoke-one")], sampling)
        check(not single.error, f"one-device chat: no error ({single.error})")
        lm1 = engine._models["smoke-one"]
        check(lm1.mesh.size == 1 and lm4.mesh.size == 4, "meshes: 1 and 4 devices")

    with phases("compare"):
        r = req("smoke-one")
        text = apply_chat_template("mistral", r.system, r.user, False)
        ids = lm1.tokenizer.encode(text)[:512]
        tokens = jnp.asarray([ids], jnp.int32)
        pads = jnp.zeros((1,), jnp.int32)

        def first_step(lm):
            """The engine's own jitted prefill step over the prompt's
            first 512 tokens: last-position logits + the compiled text."""
            with lm.mesh:
                cache = init_cache(lm.cfg, 1, len(ids), dtype=jnp.bfloat16)
                step = (lm.params, lm.cfg, tokens, pads, cache, jnp.int32(0))
                hlo = prefill_chunk.lower(*step).compile().as_text()
                logits = np.asarray(prefill_chunk(*step)[1], np.float32)
            return logits, hlo

        logits4, hlo4 = first_step(lm4)
        logits1, _ = first_step(lm1)
        collectives = {
            op: hlo4.count(op)
            for op in ("all-reduce", "all-gather", "reduce-scatter", "collective-permute")
        }
        fact("tp4.collectives_in_compiled_step", collectives)
        check(sum(collectives.values()) > 0, "the tp=4 step contains collectives")
        check(
            np.isfinite(logits4).all() and logits4.shape == (1, lm4.cfg.vocab_size),
            f"tp=4 logits finite, shape {logits4.shape}",
        )
        diff = float(np.abs(logits4 - logits1).max())
        scale = float(np.abs(logits1).max())
        fact("first_step_logits", {"max_abs_diff": diff, "max_abs": scale})
        # bf16 keeps 8 bits of mantissa; tp=4 sums each matmul's partial
        # products in another order, layer after layer.
        check(diff <= 0.05 * scale, "first-step logits agree within 5% of max |logit| (bf16)")
        # Token ids (chat returns text, and a random 32k-vocabulary model
        # mostly emits ids the byte tokenizer cannot print): the call
        # TpuEngine.chat makes, made again on the programs it compiled.
        full_ids = lm1.tokenizer.encode(text)

        def greedy_ids(lm):
            with lm.mesh:
                res = generate(
                    lm.params,
                    lm.cfg,
                    [full_ids],
                    max_new_tokens=n_new,
                    eos_ids=list(lm.tokenizer.eos_ids),
                    pad_id=lm.tokenizer.pad_id,
                    greedy=True,
                    seed=0,
                    mesh=lm.mesh,
                )
            return [int(t) for t in res.tokens[0, : int(res.n_generated[0])]]

        a, b = greedy_ids(lm4), greedy_ids(lm1)
        n = min(len(a), len(b))
        agree = next((i for i in range(n) if a[i] != b[i]), n)
        fact(
            "greedy_token_agreement",
            {"leading_equal_tokens": agree, "of": n, "tp4": a[:8], "one_device": b[:8]},
        )
        check(n > 0 and agree > 0, "tp=4 and one device agree on the first greedy token")
    return device


# -- main ------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chip_smoke_out")
    args = ap.parse_args()

    out = Path(args.out).resolve()
    for sub in ("home", "sessions"):
        (out / sub).mkdir(parents=True, exist_ok=True)
    for stale in ("serve.sock", "home/.config/adversarial-spec-tpu/registry.json"):
        (out / stale).unlink(missing_ok=True)
    sys.path.insert(0, str(HERE))
    phases = Phases()
    say(f"chip_smoke: chips={args.chips} seed={args.seed} out={out}")
    failure = None
    try:
        run = four_chips if args.chips == 4 else one_chip
        device = run(args, out, phases)
        check(
            device["platform"] == "tpu",
            f"the model ran on a TPU (platform {device['platform']!r})",
        )
        check(device["count"] == args.chips, f"{args.chips} chip(s) ({device['count']})")
    except (SmokeFailure, subprocess.TimeoutExpired, TimeoutError, OSError, ImportError) as e:
        failure = e
    fact("phase_walls_s", phases.walls)
    fact("total_wall_s", phases.total())
    if failure is not None:
        say(f"chip_smoke: FAILED: {type(failure).__name__}: {failure}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
