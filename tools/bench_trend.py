"""Aggregate every committed ``BENCH_*.json`` into one perf-trajectory
table.

Eight PRs in, the bench record is scattered across per-mode files
(``BENCH_prefix.json``, ``BENCH_obs.json``, …) and per-run driver
wrappers (``BENCH_r01.json``'s ``{n, cmd, rc, tail, parsed}``) that
nobody joins — this tool is the join: one row per file with the mode,
headline metric, value/unit, platform, and budget verdict, so a
reviewer reads the whole perf trajectory at a glance and a regression
(or a silently invalid bench file) can't hide in a file nobody opens.

Every file is SCHEMA-VALIDATED first: metric-style payloads must carry
``metric``/``value``/``unit``/``platform`` with the right types; run
wrappers must carry ``n``/``cmd``/``rc`` and, when the wrapped run
succeeded, a ``parsed`` metric payload. A violation is a nonzero exit —
``tools/lint_all.py --full`` runs this, so a malformed bench file fails
the preflight gate instead of silently dropping out of the record.

Usage:
    python tools/bench_trend.py            # table over repo-root BENCH_*
    python tools/bench_trend.py --json     # machine-readable rows
    python tools/bench_trend.py --dir D    # another directory

Exit codes: 0 = all files valid; 1 = schema violations; 2 = no bench
files found / unreadable directory.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Metric-style payload contract (bench.py's output schema): field ->
# required type(s). ``vs_baseline`` may be None (budget pins).
_METRIC_REQUIRED: dict[str, tuple[type, ...]] = {
    "metric": (str,),
    "value": (int, float),
    "unit": (str,),
    "platform": (str,),
}
# Run-wrapper contract (the driver's BENCH_r*/MULTICHIP_r* round files).
_WRAPPER_REQUIRED: dict[str, tuple[type, ...]] = {
    "n": (int,),
    "cmd": (str,),
    "rc": (int,),
}
# BENCH_serve.json additionally pins the serving trajectory: the shed
# fraction at the overload point, the brownout transition count, and
# the capacity point the admission caps are sized against — a serve
# bench that silently dropped one of these would hide a capacity
# regression behind a still-valid headline metric.
_SERVE_REQUIRED: dict[str, tuple[type, ...]] = {
    "shed_fraction": (int, float),
    "brownout_transitions": (int,),
    "capacity": (dict,),
}
# BENCH_residency.json additionally pins the weight-paging trajectory:
# total weight-load seconds resident-vs-thrash (the >=2x headline), the
# swap-overlap fraction (promotions that rode another model's decode),
# byte-identical transcripts across arms, and zero unexpected
# recompiles on re-promotion — a residency bench silently dropping one
# of these would hide a paging regression behind a valid headline.
_RESIDENCY_REQUIRED: dict[str, tuple[type, ...]] = {
    "load_wall_resident_s": (int, float),
    "load_wall_thrash_s": (int, float),
    "swap_overlap_fraction": (int, float),
    "transcripts_byte_identical": (dict,),
    "unexpected_recompiles": (int,),
}
# BENCH_elastic.json additionally pins the elasticity trajectory: the
# accepted-debate throughput of both load-step arms (the >1x headline
# must stay decomposable), interactive p99 TTFT per arm (growth must
# not trade admission for latency collapse), byte-identical transcripts
# across the planned scale-in, and zero duplicated completions — an
# elastic bench silently dropping one of these would hide a membership-
# change regression behind a valid headline ratio.
_ELASTIC_REQUIRED: dict[str, tuple[type, ...]] = {
    "accepted_throughput_elastic": (int, float),
    "accepted_throughput_fixed": (int, float),
    "ttft_p99_s": (dict,),
    "transcripts_byte_identical": (dict,),
    "duplicated_completions": (int,),
}
# BENCH_disagg.json additionally pins the disaggregation trajectory:
# decode-side p99 TTFT per arm (the headline speedup must stay
# decomposable), accepted-debate throughput per arm, the cross-replica
# KV handoff hit fraction (a disagg bench whose handoffs silently all
# degraded to local prefill would report a meaningless TTFT win),
# byte-identical transcripts disagg-vs-symmetric, zero duplicated
# completions, and zero decode-side unexpected recompiles.
_DISAGG_REQUIRED: dict[str, tuple[type, ...]] = {
    "ttft_p99_s": (dict,),
    "accepted_debates_per_s": (dict,),
    "handoff_hit_fraction": (int, float),
    "handoff": (dict,),
    "transcripts_byte_identical": (dict,),
    "duplicated_completions": (int,),
    "unexpected_recompiles": (int,),
}
# BENCH_kernels.json additionally pins the fused-kernel contract: the
# numeric parity of each fused kernel against its XLA reference, the
# per-arm decode throughput the headline ratio decomposes into,
# byte-identical transcripts fused-on vs fused-off, and zero unexpected
# recompiles through the batcher with both kernels live — a kernels
# bench silently dropping one of these would hide a numerics or
# retrace regression behind a valid speedup headline.
_KERNELS_REQUIRED: dict[str, tuple[type, ...]] = {
    "parity": (dict,),
    "tokens_per_s": (dict,),
    "transcripts_byte_identical": (dict,),
    "unexpected_recompiles": (int,),
}
# BENCH_capacity.json additionally pins the capacity frontier
# (tools/load_replay.py): the per-arm frontier dict (>=2 knob arms,
# each with a numeric debates/s at SLO) and the SLO it was measured
# against. A frontier whose headline drops >10% vs the committed value
# (vs_baseline < 0.9) is a capacity REGRESSION — it fails the gate
# even though the file is otherwise schema-valid.
_CAPACITY_REQUIRED: dict[str, tuple[type, ...]] = {
    "frontier": (dict,),
    "slo": (dict,),
}


def _check_fields(
    payload: dict, required: dict[str, tuple[type, ...]], label: str
) -> list[str]:
    problems = []
    for name, types in required.items():
        if name not in payload:
            problems.append(f"{label}: missing field {name!r}")
        elif not isinstance(payload[name], types) or isinstance(
            payload[name], bool
        ):
            problems.append(
                f"{label}: field {name!r} expected "
                f"{'/'.join(t.__name__ for t in types)}, got "
                f"{type(payload[name]).__name__}"
            )
    return problems


def validate_bench_file(path: Path) -> tuple[dict | None, list[str]]:
    """Validate one BENCH file; returns (trend row, problems). The row
    is None when the file is too malformed to summarize."""
    try:
        payload = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        return None, [f"{path.name}: unreadable ({e})"]
    if not isinstance(payload, dict):
        return None, [f"{path.name}: not a JSON object"]
    mode = path.stem.split("_", 1)[1] if "_" in path.stem else path.stem

    if "metric" in payload or "parsed" not in payload and "n" not in payload:
        # Metric-style: the payload IS the headline.
        problems = _check_fields(payload, _METRIC_REQUIRED, path.name)
        if mode == "serve":
            problems.extend(
                _check_fields(payload, _SERVE_REQUIRED, path.name)
            )
        if mode == "residency":
            problems.extend(
                _check_fields(payload, _RESIDENCY_REQUIRED, path.name)
            )
            ident = payload.get("transcripts_byte_identical")
            if isinstance(ident, dict) and not all(ident.values()):
                problems.append(
                    f"{path.name}: transcripts_byte_identical has a "
                    f"false arm: {ident}"
                )
        if mode == "elastic":
            problems.extend(
                _check_fields(payload, _ELASTIC_REQUIRED, path.name)
            )
            ident = payload.get("transcripts_byte_identical")
            if isinstance(ident, dict) and not all(ident.values()):
                problems.append(
                    f"{path.name}: transcripts_byte_identical has a "
                    f"false arm: {ident}"
                )
            if payload.get("duplicated_completions"):
                problems.append(
                    f"{path.name}: duplicated_completions must be 0, "
                    f"got {payload['duplicated_completions']}"
                )
        if mode == "disagg":
            problems.extend(
                _check_fields(payload, _DISAGG_REQUIRED, path.name)
            )
            ident = payload.get("transcripts_byte_identical")
            if isinstance(ident, dict) and not all(ident.values()):
                problems.append(
                    f"{path.name}: transcripts_byte_identical has a "
                    f"false arm: {ident}"
                )
            for gate in ("duplicated_completions", "unexpected_recompiles"):
                if payload.get(gate):
                    problems.append(
                        f"{path.name}: {gate} must be 0, "
                        f"got {payload[gate]}"
                    )
        if mode == "capacity":
            problems.extend(
                _check_fields(payload, _CAPACITY_REQUIRED, path.name)
            )
            frontier = payload.get("frontier")
            if isinstance(frontier, dict):
                if len(frontier) < 2:
                    problems.append(
                        f"{path.name}: frontier needs >=2 knob arms, "
                        f"got {len(frontier)}"
                    )
                for arm, entry in frontier.items():
                    dps = (
                        entry.get("debates_per_s")
                        if isinstance(entry, dict)
                        else None
                    )
                    if not isinstance(dps, (int, float)) or isinstance(
                        dps, bool
                    ):
                        problems.append(
                            f"{path.name}: frontier arm {arm!r} missing "
                            f"numeric debates_per_s"
                        )
            vs = payload.get("vs_baseline")
            if (
                isinstance(vs, (int, float))
                and not isinstance(vs, bool)
                and vs < 0.9
            ):
                problems.append(
                    f"{path.name}: capacity frontier dropped >10% vs "
                    f"the committed value (vs_baseline={vs})"
                )
        if mode == "kernels":
            problems.extend(
                _check_fields(payload, _KERNELS_REQUIRED, path.name)
            )
            for gate in ("parity", "transcripts_byte_identical"):
                vals = payload.get(gate)
                if isinstance(vals, dict) and not all(vals.values()):
                    problems.append(
                        f"{path.name}: {gate} has a false arm: {vals}"
                    )
            if payload.get("unexpected_recompiles"):
                problems.append(
                    f"{path.name}: unexpected_recompiles must be 0, "
                    f"got {payload['unexpected_recompiles']}"
                )
        if problems:
            return None, problems
        row = {
            "file": path.name,
            "mode": mode,
            "metric": payload["metric"],
            "value": payload["value"],
            "unit": payload["unit"],
            "platform": payload["platform"],
            "within_budget": payload.get("within_budget"),
            "vs_baseline": payload.get("vs_baseline"),
        }
        if mode == "serve":
            row["shed_fraction"] = payload["shed_fraction"]
            row["brownout_transitions"] = payload["brownout_transitions"]
        return row, []

    # Run wrapper: the headline lives in ``parsed``. Any parsed
    # payload PRESENT must schema-validate (a failed run may still
    # carry one, and its fields flow into the table); rc 0 with no
    # parsed payload is a wrapper bug.
    problems = _check_fields(payload, _WRAPPER_REQUIRED, path.name)
    parsed = payload.get("parsed")
    if payload.get("rc") == 0 and not isinstance(parsed, dict):
        problems.append(f"{path.name}: rc 0 but no parsed metric payload")
    if isinstance(parsed, dict):
        problems.extend(
            _check_fields(parsed, _METRIC_REQUIRED, f"{path.name}:parsed")
        )
    if problems:
        return None, problems
    row = {
        "file": path.name,
        "mode": mode,
        "metric": None,
        "value": None,
        "unit": None,
        "platform": None,
        "within_budget": None,
        "vs_baseline": None,
        "rc": payload["rc"],
    }
    if isinstance(parsed, dict):
        row.update(
            metric=parsed.get("metric"),
            value=parsed.get("value"),
            unit=parsed.get("unit"),
            platform=parsed.get("platform"),
            within_budget=parsed.get("within_budget"),
            vs_baseline=parsed.get("vs_baseline"),
        )
    return row, []


def collect(bench_dir: Path) -> tuple[list[dict], list[str]]:
    rows: list[dict] = []
    problems: list[str] = []
    for path in sorted(bench_dir.glob("BENCH_*.json")):
        row, file_problems = validate_bench_file(path)
        problems.extend(file_problems)
        if row is not None:
            rows.append(row)
    return rows, problems


def render_table(rows: list[dict]) -> str:
    if not rows:
        return "(no bench files)"
    header = ("file", "mode", "metric", "value", "unit", "platform", "ok")
    body = []
    for r in rows:
        # Defensive on optional fields: within_budget/vs_baseline are
        # not schema-required, so render survives any JSON value there.
        ok = r.get("within_budget")
        body.append(
            (
                r["file"],
                r["mode"],
                str(r["metric"] or "-"),
                (
                    f"{r['value']:g}"
                    if isinstance(r["value"], (int, float))
                    and not isinstance(r["value"], bool)
                    else "-"
                ),
                str(r["unit"] or "-")[:34],
                str(r["platform"] or "-"),
                "yes" if ok is True else ("BREACH" if ok is False else "-"),
            )
        )
    widths = [
        max(len(row[i]) for row in [header] + body)
        for i in range(len(header))
    ]
    lines = [
        "  ".join(h.ljust(w) for h, w in zip(header, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.ljust(w) for c, w in zip(row, widths)) for row in body]
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--dir",
        default=str(REPO),
        help="directory holding BENCH_*.json (default: repo root)",
    )
    ap.add_argument(
        "--json", action="store_true", help="machine-readable rows"
    )
    args = ap.parse_args(argv)
    bench_dir = Path(args.dir)
    if not bench_dir.is_dir():
        print(f"bench_trend: no such directory {bench_dir}", file=sys.stderr)
        return 2
    rows, problems = collect(bench_dir)
    if not rows and not problems:
        print(f"bench_trend: no BENCH_*.json in {bench_dir}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({"rows": rows, "problems": problems}, indent=2))
    else:
        print(render_table(rows))
    for p in problems:
        print(f"bench_trend: {p}", file=sys.stderr)
    if problems:
        print(
            f"bench_trend: {len(problems)} schema violation(s)",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
