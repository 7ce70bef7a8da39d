#!/usr/bin/env python3
"""The second control of `perfbench/limits/granite-4.0-h-micro-bf16.json`:
a whole run of a granite cell whose control is the plain reference with the
RECURRENT STATE held in bfloat16 (the weights as served), where
`perfbench/run.py --control 1` lowers the weights (int8 per output channel)
and keeps the state in float32. Not a test file and never part of a
benchmark run: how that limit's third reading is taken.

    python3 tests/benchmark/granite_controls.py --workload granite-4.0-h-micro-bf16.critique --seed 1 --seconds 51
    JAX_PLATFORMS=cpu python3 tests/benchmark/granite_controls.py --rehearsal 1 --workload ... --seconds 2

`run.py` asks the architecture's module for `bits=4` when it makes the
control's weights; here that request is answered with the served weights
and `state_dtype="bfloat16"` (`architectures/granitemoehybrid.py`), so the
result line's `control` group is the bfloat16-state control's reading."""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import manifest, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload, rehearsal=bool(args.rehearsal))
    served = cell.arch.make_weights

    def state_lowered(cfg, seed, bits=8):
        return served(cfg, seed, bits=8, state_dtype="bfloat16" if bits == 4 else "float32")

    cell.arch.make_weights = state_lowered
    rc, result = run.run_cell(
        cell, args.seed, args.seconds, False, require_tpu=not args.rehearsal, control=True
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
