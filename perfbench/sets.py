#!/usr/bin/env python3
"""Runs of one cell, one after another, each a process of its own, with the
result lines gathered into a file: how the spreads behind the bounds in
BENCHMARK.json were measured (PERF.md), and how a later benchmark PR
measures them again. Touches no jax itself: a chip belongs to one process.

    python3 perfbench/sets.py --workload <cell> --seeds 11,12,13 --seconds 51 \\
        [--trace 0|1] [--out chiprun_out/<name>.jsonl] [--control 1]
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--keep", default="", help="directory to copy each run's .perfbench_run notes into")
    args = ap.parse_args()
    out = Path(args.out) if args.out else None
    if out:
        out.parent.mkdir(parents=True, exist_ok=True)
    rc_all = 0
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        cmd = [sys.executable, str(ROOT / "perfbench/run.py"), "--workload", args.workload,
               "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace),
               "--control", str(args.control)]
        t0 = time.monotonic()
        proc = subprocess.run(cmd, cwd=str(ROOT), capture_output=True, text=True)
        wall = time.monotonic() - t0
        last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        try:
            result = json.loads(last)
        except json.JSONDecodeError:
            result = None
        row = {"workload": args.workload, "seed": seed, "seconds": args.seconds,
               "trace": args.trace, "rc": proc.returncode, "wall_s": round(wall, 1),
               "result": result}
        notes = [ln for ln in proc.stderr.splitlines()
                 if ln.startswith(("setup_split_s", "compile_cache", "window:", "counts:",
                                   "cached_prompt", "samples:", "WARNING", "NOTE", "check:", "control",
                                   "compared", "weight_bytes", "trace:", "perfbench:"))]
        # whatever the program said inside the window (a compile's log line, a warning)
        lines = proc.stderr.splitlines()
        opens = next((i for i, ln in enumerate(lines) if "window opens" in ln), None)
        closes = next((i for i, ln in enumerate(lines) if "window closes" in ln), None)
        if opens is not None and closes is not None:
            notes += [f"IN WINDOW: {ln[:300]}" for ln in lines[opens + 1 : closes][:40]]
        row["notes"] = notes
        print(json.dumps(row), flush=True)
        if out:
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")
        if proc.returncode != 0 or result is None:
            rc_all = 1
            print(proc.stderr[-6000:], file=sys.stderr, flush=True)
        if args.keep:
            keep = Path(args.keep) / f"{args.workload}.{seed}.t{args.trace}"
            keep.mkdir(parents=True, exist_ok=True)
            for name in ("trace_summary.json", "trace_small.json", "counters.json"):
                src = ROOT / ".perfbench_run" / name
                if src.exists():
                    (keep / name).write_text(src.read_text())
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
