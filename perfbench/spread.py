#!/usr/bin/env python3
"""The spread of each metric over the runs that perfbench/sets.py gathered:
the distance between the first and third quartile (Python's
`statistics.quantiles(values, n=4)`) as a share of the median, per file
(one file is one set), and the widest over the files.

    python3 perfbench/spread.py chiprun_out/setA.jsonl chiprun_out/setB.jsonl
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> int:
    widest: dict = {}
    for path in paths:
        rows = [json.loads(line) for line in open(path) if line.strip()]
        by_cell: dict = {}
        for r in rows:
            if r.get("result"):
                by_cell.setdefault((r["workload"], r["trace"]), []).append(r)
        for (cell, trace), rs in sorted(by_cell.items()):
            print(f"{path}: {cell} trace={trace}: {len(rs)} runs, "
                  f"correct {[r['result']['correct'] for r in rs].count(True)}/{len(rs)}")
            names = sorted({k for r in rs for k in r["result"]["metrics"]})
            for name in names:
                vals = [r["result"]["metrics"][name]["value"] for r in rs
                        if name in r["result"]["metrics"]]
                line = f"  {name}: " + " ".join(f"{v:.6g}" for v in vals)
                if len(vals) >= 2 and statistics.median(vals):  # a count that reads 0 has no share
                    sp = spread(vals)
                    line += f" | median {statistics.median(vals):.6g} spread {sp:.5f}"
                    key = (cell, trace, name)
                    widest[key] = max(widest.get(key, 0.0), sp)
                print(line)
            gaps = [r["result"]["compared"]["served_token_gap_over_std_max"]["value"] for r in rs]
            print("  served_token_gap_over_std_max: " + " ".join(f"{g:.4g}" for g in gaps))
    print("widest spread per metric over the files:")
    for (cell, trace, name), sp in sorted(widest.items()):
        print(f"  {cell} trace={trace} {name}: {sp:.5f}  -> five times: {5 * sp:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
