"""Unified lint/QA runner with ONE exit code — the preflight gate to
run before spending chip time.

Stages (each prints its own verdict; the runner exits nonzero if ANY
stage failed):

1. **graftlint** — the full rule set over the repo (tools/graftlint),
   plus its self-test (every registered rule must fire on its fixture:
   a silently dead rule is worse than no rule).
2. **mutmut-config sanity** — the mutation-skip config both mutmut and
   tools/mutation_run.py consume must stay importable and structurally
   sound (non-empty marker tuples, tests + graftlint fixtures excluded
   from mutation targets).
2b. **journal schema self-check** — the crash-safe round journal's
   record schema (debate/journal.py RECORD_FIELDS): every record type
   has a validating example and the validator provably fires on broken
   records — a resume that silently misreads its journal is a lost
   round.
3. **bench-trend** (``--full`` only) — every committed BENCH_*.json
   must schema-validate and join into the perf-trajectory table
   (tools/bench_trend.py): a malformed bench file fails the gate
   instead of silently dropping out of the record.
4. **replay-smoke** (``--full`` only) — a tiny seeded
   tools/load_replay.py sweep on the mock daemon must emit a
   BENCH_capacity.json payload that bench_trend's capacity schema
   accepts (>=2 knob arms, numeric frontier): the load harness and
   the capacity gate can never drift apart unnoticed.

Usage:
    python tools/lint_all.py            # graftlint + mutmut sanity
    python tools/lint_all.py --changed  # lint only files changed vs main
    python tools/lint_all.py --full     # + bench trend + replay smoke
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def lintable(names: list[str], repo: Path = REPO) -> list[str]:
    """Repo-relative names filtered to existing .py files under the
    lint roots (pure — the testable half of --changed)."""
    from tools.graftlint.core import DEFAULT_ROOTS

    roots = tuple(
        r if r.endswith(".py") else r + "/" for r in DEFAULT_ROOTS
    )
    out = []
    for name in names:
        if not name.endswith(".py"):
            continue
        if not any(name == r or name.startswith(r) for r in roots):
            continue
        if (repo / name).is_file():
            out.append(name)
    return sorted(set(out))


def changed_py_files(repo: Path = REPO, base: str = "main") -> list[str] | None:
    """Lintable files changed vs ``base`` (committed + worktree +
    untracked); None when git cannot answer (fall back to a full lint)."""
    names: list[str] = []
    try:
        for args in (
            ["git", "diff", "--name-only", base],
            ["git", "ls-files", "--others", "--exclude-standard"],
        ):
            r = subprocess.run(
                args, cwd=repo, capture_output=True, text=True, timeout=30
            )
            if r.returncode != 0:
                return None
            names += r.stdout.splitlines()
    except (OSError, subprocess.SubprocessError):
        return None
    return lintable(names, repo)


def _stage_graftlint(paths: list[str] | None = None) -> bool:
    from tools.graftlint import core

    failures = core.self_test()
    for f in failures:
        print(f"lint_all: graftlint self-test: {f}", file=sys.stderr)
    if paths is not None and not paths:
        # --changed with nothing changed: the self-test above is the
        # whole lint stage.
        ok = not failures
        print(
            f"lint_all: graftlint {'OK' if ok else 'FAILED'} "
            "(0 changed files)",
            file=sys.stderr,
        )
        return ok
    try:
        result = core.run(
            [str(REPO / p) for p in paths] if paths else None
        )
    except (SyntaxError, ValueError) as e:
        print(f"lint_all: graftlint: {e}", file=sys.stderr)
        print("lint_all: graftlint FAILED", file=sys.stderr)
        return False
    for finding in result.findings:
        print(finding.render())
    ok = not failures and result.exit_code == 0
    slowest = sorted(
        result.rule_seconds.items(), key=lambda kv: -kv[1]
    )[:3]
    timing = ", ".join(f"{r} {s:.2f}s" for r, s in slowest)
    print(
        f"lint_all: graftlint {'OK' if ok else 'FAILED'} "
        f"({len(result.findings)} finding(s), "
        f"{len(failures)} dead rule(s), {result.n_files} files; "
        f"slowest rules: {timing})",
        file=sys.stderr,
    )
    return ok


def _stage_graftlint_config() -> bool:
    """THE pyproject-vs-code-defaults drift guard (hoisted here from
    per-module test pins): the [tool.graftlint] table and the in-code
    defaults must be the same config — the defaults exist so fixture
    trees lint without a pyproject, not as a second opinion."""
    from tools.graftlint.config import config_drift

    try:
        drift = config_drift(REPO)
    except ValueError as e:
        print(f"lint_all: graftlint-config: {e}", file=sys.stderr)
        drift = ["<unreadable table>"]
    for d in drift:
        print(f"lint_all: graftlint-config: drift: {d}", file=sys.stderr)
    ok = not drift
    print(
        f"lint_all: graftlint-config {'OK' if ok else 'FAILED'}",
        file=sys.stderr,
    )
    return ok


def _stage_lockdep_selftest() -> bool:
    """Prove the runtime lockdep sanitizer is live, mirroring graftlint
    ``--self-test``: a synthetic two-lock inversion must be detected
    and must name both stacks. A sanitizer that silently stopped
    detecting would make every 'zero violations' green a lie."""
    from adversarial_spec_tpu.resilience import lockdep

    problems = lockdep.self_test()
    for p in problems:
        print(f"lint_all: lockdep-selftest: {p}", file=sys.stderr)
    ok = not problems
    print(
        f"lint_all: lockdep-selftest {'OK' if ok else 'FAILED'}",
        file=sys.stderr,
    )
    return ok


def _stage_mutmut_sanity() -> bool:
    ok = True

    def fail(msg: str) -> None:
        nonlocal ok
        ok = False
        print(f"lint_all: mutmut-config: {msg}", file=sys.stderr)

    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "mutmut_config", REPO / "mutmut_config.py"
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    except Exception as e:
        fail(f"import failed: {e}")
        print("lint_all: mutmut-config FAILED", file=sys.stderr)
        return False
    for name in ("_SKIP_LINE_MARKERS", "_SKIP_PATH_FRAGMENTS"):
        val = getattr(module, name, None)
        if not (
            isinstance(val, tuple)
            and val
            and all(isinstance(m, str) and m for m in val)
        ):
            fail(f"{name} must be a non-empty tuple of strings")
    if not callable(getattr(module, "pre_mutation", None)):
        fail("pre_mutation hook missing")
    frags = getattr(module, "_SKIP_PATH_FRAGMENTS", ())
    for required in ("/tests/", "/tools/graftlint/"):
        if required not in frags:
            fail(f"_SKIP_PATH_FRAGMENTS must exclude {required!r}")
    # mutation_run must agree (it imports the same markers by path) and
    # must never target the self-test fixture package.
    from tools.mutation_run import DEFAULT_TARGETS, SKIP_LINE_MARKERS

    if SKIP_LINE_MARKERS != module._SKIP_LINE_MARKERS:
        fail("mutation_run.SKIP_LINE_MARKERS diverged from mutmut_config")
    for target in DEFAULT_TARGETS:
        if "tools/graftlint" in target:
            fail(f"graftlint fixtures are a mutation target: {target}")
    print(
        f"lint_all: mutmut-config {'OK' if ok else 'FAILED'}",
        file=sys.stderr,
    )
    return ok


def _stage_journal_schema() -> bool:
    try:
        from adversarial_spec_tpu.debate import journal
    except Exception as e:
        print(f"lint_all: journal-schema: import failed: {e}", file=sys.stderr)
        print("lint_all: journal-schema FAILED", file=sys.stderr)
        return False
    problems = journal.self_check()
    for p in problems:
        print(f"lint_all: journal-schema: {p}", file=sys.stderr)
    ok = not problems
    print(
        f"lint_all: journal-schema {'OK' if ok else 'FAILED'} "
        f"({len(journal.RECORD_TYPES)} record type(s))",
        file=sys.stderr,
    )
    return ok


def _stage_bench_trend() -> bool:
    from tools.bench_trend import collect

    rows, problems = collect(REPO)
    for p in problems:
        print(f"lint_all: bench-trend: {p}", file=sys.stderr)
    ok = not problems and bool(rows)
    if not rows:
        print("lint_all: bench-trend: no BENCH_*.json found", file=sys.stderr)
    print(
        f"lint_all: bench-trend {'OK' if ok else 'FAILED'} "
        f"({len(rows)} bench file(s))",
        file=sys.stderr,
    )
    return ok


def _stage_replay_smoke() -> bool:
    """A tiny seeded load_replay sweep must produce a schema-valid
    capacity payload (tools/bench_trend.py's capacity contract) — the
    replay harness and the frontier gate can never drift apart
    unnoticed."""
    import json
    import tempfile

    from tools.bench_trend import validate_bench_file

    ok = True
    with tempfile.TemporaryDirectory(prefix="advspec-replay-smoke-") as td:
        out = Path(td) / "BENCH_capacity.json"
        r = subprocess.run(
            [
                sys.executable,
                str(REPO / "tools" / "load_replay.py"),
                "--smoke",
                "--bench-out",
                str(out),
            ],
            cwd=REPO,
            capture_output=True,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if r.returncode != 0 or not out.is_file():
            print(
                f"lint_all: replay-smoke: load_replay exited "
                f"{r.returncode}: {r.stderr[-400:]}",
                file=sys.stderr,
            )
            ok = False
        else:
            row, problems = validate_bench_file(out)
            for p in problems:
                print(f"lint_all: replay-smoke: {p}", file=sys.stderr)
            payload = json.loads(out.read_text(encoding="utf-8"))
            arms = payload.get("frontier", {})
            if len(arms) < 2:
                print(
                    f"lint_all: replay-smoke: expected >=2 knob arms, "
                    f"got {len(arms)}",
                    file=sys.stderr,
                )
                ok = False
            ok = ok and not problems and row is not None
    print(
        f"lint_all: replay-smoke {'OK' if ok else 'FAILED'}",
        file=sys.stderr,
    )
    return ok


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--full",
        action="store_true",
        help="also run the (slow) bench-trend and replay-smoke stages",
    )
    ap.add_argument(
        "--changed",
        action="store_true",
        help="lint only files changed vs --base (the fast "
        "preflight); falls back to a full lint when git cannot "
        "answer. Absence-proving checks (GL-CONFIG) skip on a subset.",
    )
    ap.add_argument(
        "--base",
        default="main",
        help="base ref for --changed (default: main)",
    )
    args = ap.parse_args(argv)
    paths: list[str] | None = None
    if args.changed:
        paths = changed_py_files(REPO, args.base)
        if paths is None:
            print(
                "lint_all: --changed: git unavailable, full lint",
                file=sys.stderr,
            )
        elif not paths:
            print(
                f"lint_all: --changed: no lintable files changed vs "
                f"{args.base}; graftlint self-test + config stages only",
                file=sys.stderr,
            )
        else:
            print(
                f"lint_all: --changed: {len(paths)} file(s) vs "
                f"{args.base}",
                file=sys.stderr,
            )
    ok = _stage_graftlint(paths)
    ok = _stage_graftlint_config() and ok
    ok = _stage_lockdep_selftest() and ok
    ok = _stage_mutmut_sanity() and ok
    ok = _stage_journal_schema() and ok
    if args.full:
        ok = _stage_bench_trend() and ok
        ok = _stage_replay_smoke() and ok
    print(
        f"lint_all: {'ALL OK' if ok else 'FAILURES'}",
        file=sys.stderr,
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
