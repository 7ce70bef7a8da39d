#!/usr/bin/env python3
"""The whole command at the tiny presets, on whatever jax finds (the CPU
here): every phase of a run, the reference's comparison with it, and then
the verdict, which is not correct because the platform is not a TPU.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py --workload <cell> --seed 1 --seconds 3 --trace 0

Prints the result line a chip run would print and exits 1. A rehearsal's
numbers are counts and CPU times: never a device metric.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# Tiny programs compile in under jax's one-second floor for a cache entry.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

from perfbench import manifest, run  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload, rehearsal=True)
    rc, result = run.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), require_tpu=False,
        control=bool(args.control),
    )
    if result is None:
        return rc or 1
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
