#!/usr/bin/env python3
"""One run of one cell of the benchmark (BENCHMARK.json at the root).

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (not measured, reported as `setup_s`): the `advspec serve` daemon
starts inside this process, the cell's model is loaded and every program
the cell's traffic needs is compiled or taken from the compile cache by the
mix's warm-up debates. Then the window: the mix's closed-loop clients for
`--seconds` seconds (a traced run measures the mix's `trace_seconds`, under
the profiler). Then, with the program's state freed, the plain reference
checks a sample of what the window served. The last line of stdout is the
result; a run that finds no TPU, or fewer chips than the cell asks for,
prints none and exits with a code other than 0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

_T_IMPORT = time.monotonic()


def _process_age_s() -> float:
    """Seconds this process had lived when this module was loaded."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_IMPORT = _process_age_s()

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import manifest, stats, traffic  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_BAD_CELL = 2
EXIT_NO_PROGRAM = 4


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Phases:
    """How set-up splits, on the host's clock."""

    def __init__(self) -> None:
        self.walls: dict[str, float] = {}
        self._last = _T_IMPORT

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.walls[name] = self.walls.get(name, 0.0) + now - self._last
        self._last = now


def run_cell(cell, seed: int, seconds: float, trace: bool, *, require_tpu: bool = True,
             control: bool = False):
    """One run. Returns (exit code, result dict or None)."""
    from perfbench import system as system_mod

    phases = Phases()
    run_dir = system_mod.prepare_run_dir(ROOT)
    try:
        device = system_mod.devices()
    except ImportError as e:
        say(f"perfbench: the program is not in this checkout ({e}). No result.")
        return EXIT_NO_PROGRAM, None
    phases.mark("import_and_devices")
    say(f"perfbench: cell={cell.name} seed={seed} seconds={seconds} trace={int(trace)} device={device}")
    on_tpu = device["platform"] == "tpu"
    if require_tpu and (not on_tpu or device["count"] < cell.chips):
        say(
            f"perfbench: needs {cell.chips} TPU chip(s); jax found "
            f"{device['count']} device(s) of platform {device['platform']!r}. No result."
        )
        return EXIT_NO_CHIP, None

    from perfbench import shapes

    peaks = shapes.peaks_for(device["kind"]) if on_tpu else None
    mix = cell.traffic
    plans = traffic.plan(mix, seed)
    rows = system_mod.dispatch_rows()
    primer = traffic.primer(mix, seed, rows)
    window_s = min(seconds, float(mix.get("trace_seconds", seconds))) if trace else seconds

    sut = system_mod.System(run_dir, cell.config["serving"])
    sut.start()
    phases.mark("daemon_start")
    from perfbench.loadgen import ClosedLoop

    loop = ClosedLoop(sut, primer, plans)
    try:
        c_boot = system_mod.counters(sut.tap)
        loop.start()
        loop.wait_warm()
        phases.mark("warm_up_traffic")
        c0 = system_mod.counters(sut.tap)
        warm = [loop.primer_record] + [r for recs in loop.records for r in recs if r.debate.warmup]
        bad = [r for r in warm if r is None or not r.ok]
        if bad or loop.errors:
            say(f"perfbench: warm-up failed: {[r and r.final for r in bad][:2]} {loop.errors}")
            return 1, None
        load_s = c0.get("obs.advspec_model_load_seconds.sum", 0.0)
        compile_s = c0.get("device.compile.backend_compile_s", 0.0) - c_boot.get(
            "device.compile.backend_compile_s", 0.0
        )
        trace_dir = str(run_dir / "trace")
        if trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1  # idle gaps are named by the host's Python frame
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            phases.mark("trace_start")
        t0 = time.monotonic()
        setup_s = _AGE_AT_IMPORT + (t0 - _T_IMPORT)
        say(f"perfbench: window opens, set-up took {setup_s:.3f} s")
        time.sleep(max(0.0, t0 + window_s - time.monotonic()))
        t1 = time.monotonic()
        say("perfbench: window closes")
        c1 = system_mod.counters(sut.tap)
        if trace:
            jax.profiler.stop_trace()
        loop.stop_and_wait()
        t_done = time.monotonic()
        c2 = system_mod.counters(sut.tap)
        memory_peak = system_mod.memory_peak_bytes()
        tree_bytes = sut.param_bytes()
    finally:
        loop.close()
        stop = sut.stop()
    say(f"perfbench: daemon stopped: {stop}")
    # For whoever writes a metric's file: the counters' names, as this run read them.
    (run_dir / "counters.json").write_text(
        json.dumps({"window_start": c0, "window_end": c1, "all_done": c2}, indent=1, sort_keys=True)
    )

    ws = stats.window_stats(loop.records, sut.tap.requests, t0, t1, cell.chips)
    if loop.errors:
        ws.notes.extend(loop.errors)

    # -- the lines before the last: what a reader has to see ----------------
    split = dict(phases.walls)
    say(
        "setup_split_s: "
        + json.dumps(
            {
                **{k: round(v, 3) for k, v in split.items()},
                "of_warm_up.model_load": round(load_s, 3),
                "of_warm_up.backend_compile": round(compile_s, 3),
                "setup_s": round(setup_s, 3),
            }
        )
    )
    say(
        "compile_cache: "
        + json.dumps(
            {k.split(".", 2)[2]: v for k, v in c0.items() if k.startswith("device.compile.")}
        )
    )
    compiles = _compiles(c0, c1)
    say(f"window: {window_s:.3f} s asked, {t1 - t0:.3f} s held, in-flight work ended {t_done - t1:.3f} s after the close")
    say(f"counts: {json.dumps(ws.counts)}")
    say(f"cached_prompt_tokens_per_debate: {ws.cached_by_debate[:12]}")
    say(f"samples: {json.dumps(ws.samples)}")
    if compiles:
        say(f"WARNING compiles_in_window={compiles}: set-up leaked into the window; this run's numbers are not steady-state")
    if ws.counts["ended_early"]:
        say(f"NOTE ended_early={ws.counts['ended_early']} replies ended before max_new_tokens (end-of-sequence token)")
    if ws.counts["failed"]:
        say(f"WARNING failed={ws.counts['failed']} of {ws.counts['attempted']} requests failed, were shed or timed out")
    if ws.counts["stream_events_unmatched"]:
        say(f"NOTE stream_events_unmatched={ws.counts['stream_events_unmatched']}: those replies' events counted as one token each")
    expect = cell.arch.weight_bytes(cell.config, cell.config["serving"].get("quant", ""))
    say(f"weight_bytes: tree={json.dumps(_tree_summary(tree_bytes))} shapes={json.dumps(expect)}")
    for note in ws.notes:
        say(f"NOTE {note}")
    say(f"NOTE {_row_steps_note(ws, c0, c1)}")

    # -- metrics --------------------------------------------------------------
    client = dict(ws.client)
    client["setup_s"] = setup_s
    metrics: dict = {}
    device_out = {**device, "memory_peak_bytes": memory_peak}
    breakdown = None
    if not trace:
        for m in cell.end_to_end:
            value = client.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from perfbench import reduce as rd
        from perfbench import reducers

        path = rd.find_xplane(trace_dir)
        tr = rd.load_xplane(path) if path else None
        t_read = time.monotonic()
        if tr is not None and tr.devices:
            busy = rd.busy_seconds(tr)
            span = rd.window_of(tr)
            traced_s = (span[1] - span[0]) / 1e9 if span else None
            if busy and traced_s:
                device_out["busy_s"] = busy
                device_out["window_s"] = traced_s
            breakdown = {"device_ops": rd.top_ops(tr), "idle_gaps": rd.idle_gaps(tr)}
            _dump_trace_summary(tr, run_dir)
        else:
            traced_s = None
            say("WARNING no device plane in the trace: no operation ran on a TPU under the profiler")
        reading = reducers.Reading(
            window_s=traced_s or (t1 - t0),
            counters_start=c0,
            counters_end=c1,
            client=client,
            token_contexts=ws.token_contexts,
            row_step_contexts=ws.row_step_contexts,
            prefill_spans=ws.prefill_spans,
            rows=rows,
            config=cell.config,
            arch=cell.arch,
            quant=cell.config["serving"].get("quant", ""),
            peaks=peaks,
            trace=tr if (tr is not None and tr.devices) else None,
        )
        for m in cell.per_layer:
            value = reducers.read_metric(m, reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for note in reading.notes:
            say(f"NOTE {note}")
        say(f"trace: read and reduced in {time.monotonic() - t_read:.1f} s from {path}")

    # -- the comparison ---------------------------------------------------------
    sut.free_device_state()
    compared, check_lines, extra = check_outputs(cell, ws, seed, control=control)
    compared["requests_not_served_by_batcher"] = {
        "value": ws.counts["not_served_by_batcher"], "limit": 0,
    }
    compared["platform_is_tpu"] = {"value": 0 if on_tpu else 1, "limit": 0}
    from perfbench import correct as correct_mod

    ok = correct_mod.verdict(compared) and bool(ws.finished)
    for line in check_lines:
        say(line)
    say("compared (value <= limit): " + json.dumps(compared))
    result = {
        "correct": ok,
        "attempted": ws.counts["attempted"],
        "failed": ws.counts["failed"],
        "metrics": metrics,
        "device": device_out,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["ended_early"] = ws.counts["ended_early"]
    result["compiles_in_window"] = compiles
    result.update(extra)
    result["compared"] = compared
    return 0, result


def _compiles(c0: dict, c1: dict) -> int:
    """Programs that were first needed inside the window: compiled there,
    or loaded there from the persistent cache."""
    keys = ("device.compile.backend_compiles", "device.compile.persistent_cache_hits")
    return int(sum(c1.get(k, 0) - c0.get(k, 0) for k in keys))


def _row_steps_note(ws, c0: dict, c1: dict) -> str:
    """The deliveries that the decode work's keys and values are counted by,
    beside the program's own count of the same thing (`spec.spec_steps`: +1
    a live row a verify program). The two clocks differ at the window's
    two edges by a step or two of every row. No reader depends on this."""
    own = c1.get("spec.spec_steps", 0) - c0.get("spec.spec_steps", 0)
    per_step, per_token = sum(ws.row_step_contexts), sum(ws.token_contexts)
    ratio = f"{per_token / per_step:.4f}" if per_step else "n/a"
    return (
        f"row_steps: {ws.counts['row_steps']} verify-step deliveries counted in the window "
        f"({ws.counts['handoff_deliveries']} handoff deliveries of a first token left out) "
        f"against spec.spec_steps close - open = {own}; positions of keys and values summed "
        f"once a delivery {per_step}, once a token {per_token} (x{ratio}: what a count per "
        f"emitted token would charge)"
    )


def _tree_summary(tree_bytes: dict) -> dict:
    layers = {k: v for k, v in tree_bytes.items() if k.startswith("layers.")}
    mm = sum(v for k, v in layers.items() if k.split(".")[1].startswith("w"))
    out = {k: v for k, v in tree_bytes.items() if not k.startswith("layers.")}
    out["layers_matmul"] = mm
    out["layers_small"] = sum(layers.values()) - mm
    out["total"] = sum(tree_bytes.values())
    return out


def _dump_trace_summary(tr, run_dir: Path) -> None:
    """Names and totals of the trace's lines, for a reader who has to write
    a metric's name pattern (kept in the run's directory, never committed)."""
    summary = {}
    for plane, lines in tr.devices.items():
        for line, evs in lines.items():
            total: dict = {}
            for name, _, d in evs:
                n, s = total.get(name, (0, 0.0))
                total[name] = (n + 1, s + d / 1e9)
            top = sorted(total.items(), key=lambda kv: -kv[1][1])[:60]
            summary[f"{plane}|{line}"] = [[k, n, round(s, 6)] for k, (n, s) in top]
    (run_dir / "trace_summary.json").write_text(json.dumps(summary, indent=1))
    # A cut of a tenth of a second, one second in: how data/small_trace.json was recorded.
    from perfbench import reduce as rd

    span = rd.window_of(tr)
    if span:
        cut = rd.trim(tr, span[0] + 1.0e9, span[0] + 1.12e9)
        cut.host = [e for e in cut.host if e[3] >= 20_000.0]
        (run_dir / "trace_small.json").write_text(json.dumps(cut.to_json()))


def check_outputs(cell, ws, seed: int, *, control: bool):
    """The architecture's plain reference (`cell.arch`) over a sample of
    what the window finished."""
    from perfbench import correct as correct_mod

    t_start = time.monotonic()
    lines = []
    by_debate: dict = {}
    for f in ws.finished:
        by_debate.setdefault(f.debate_key, []).append(f)
    chosen = correct_mod.pick_debates(
        [correct_mod.FinishedDebate(k, v) for k, v in sorted(by_debate.items())],
        int(cell.traffic.get("check_debates", 3)),
        seed,
    )
    weights_seed = 0  # the program's own for `checkpoint: random`; its registry has no option
    unique: dict = {}
    for d in chosen:
        for r in d.reqs:
            if r.tokens:
                unique.setdefault((tuple(r.prompt_ids), tuple(r.tokens)), None)
    logits: dict = {}
    low_logits: dict = {}
    weights = cell.arch.make_weights(cell.config, weights_seed, bits=8) if unique else None
    t_weights = time.monotonic()
    for key in unique:
        logits[key] = correct_mod.served_logits(
            cell.arch, cell.config, weights, list(key[0]), list(key[1])
        )
    del weights
    if control and unique:
        # one set of weights on the chip at a time
        low = cell.arch.make_weights(cell.config, weights_seed, bits=4)
        for key in unique:
            low_logits[key] = correct_mod.served_logits(
                cell.arch, cell.config, low, list(key[0]), list(key[1])
            )
        del low
    n_passes = len(unique)
    gap_max, n_tokens, n_match = 0.0, 0, 0
    control_gap = None
    for d in chosen:
        for r in d.reqs:
            key = (tuple(r.prompt_ids), tuple(r.tokens))
            if key not in logits:
                continue
            res = correct_mod.compare_request(logits[key], r.tokens, low_logits.get(key))
            gap_max = max(gap_max, res["gap_max"])
            n_tokens += res["n"]
            n_match += res["match"]
            if "control_gap_max" in res:
                control_gap = max(control_gap or 0.0, res["control_gap_max"])
    lines.append(
        f"check: {len(chosen)} debates, {n_tokens} served tokens over {n_passes} reference passes, "
        f"{n_match} equal the reference's best token; weights {t_weights - t_start:.1f} s, "
        f"passes {time.monotonic() - t_weights:.1f} s"
    )
    compared = {
        "served_token_gap_over_std_max": {
            "value": gap_max if n_tokens else 1e9,  # nothing to compare is not correct
            "limit": cell.limits["served_token_gap_over_std_max"]["limit"],
        }
    }
    extra = {}
    if control_gap is not None:
        # The control stands in the program's place: the same number (the
        # widest gap over the sample) has to fail the same limit.
        limit = compared["served_token_gap_over_std_max"]["limit"]
        extra["control"] = {
            "served_token_gap_over_std_max": control_gap,
            "correct": control_gap <= limit,
        }
        lines.append(f"control (the reference with int4 weights): {json.dumps(extra['control'])}")
    return compared, lines, extra


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--control", type=int, choices=(0, 1), default=0,
        help="also read the control (the reference with int4 weights) over the same "
        "requests: how a limit's upper reading is taken, never part of a benchmark run",
    )
    args = ap.parse_args(argv)
    try:
        cell = manifest.load_cell(args.workload, ROOT, HERE)
    except (manifest.ManifestError, KeyError) as e:
        say(f"perfbench: {e}")
        return EXIT_BAD_CELL
    rc, result = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), control=bool(args.control)
    )
    if result is None:
        return rc or 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Daemon threads of the program (its debate pool, the profiler) must
    # not hold the process once the result is out.
    os._exit(code)
