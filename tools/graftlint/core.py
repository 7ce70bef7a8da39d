"""Rule registry, suppression/baseline machinery, and the lint driver.

Execution model (multi-pass):

1. collect files under the requested roots;
2. **index pass** — parse every file once into ``ModuleInfo``
   (tools/graftlint/index.py);
3. **rule passes** — each selected rule walks the index and reports
   findings through ``Context.report``;
4. **filter pass** — inline suppressions (reason mandatory) and the
   committed baseline partition raw findings into reported / suppressed
   / baselined; malformed suppressions become GL-SUPPRESS findings.
"""

from __future__ import annotations

import ast
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

from tools.graftlint.config import GraftlintConfig, load_config
from tools.graftlint.index import ModuleInfo, build_index, modname_for

REPO = Path(__file__).resolve().parent.parent.parent
BASELINE_PATH = Path(__file__).resolve().parent / "baseline.json"
BASELINE_VERSION = 1
JSON_VERSION = 1

DEFAULT_ROOTS = (
    "adversarial_spec_tpu",
    "tools",
    "tests",
    "bench.py",
    "__graft_entry__.py",
    "chip_smoke.py",
)


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    message: str

    def fingerprint(self) -> tuple[str, str, str]:
        """Line-number-free identity used for baseline matching: survives
        unrelated edits shifting the file."""
        return (self.rule, self.path, self.message)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }


class Rule:
    """One registered check. Subclasses set the class attributes and
    implement ``check``; ``fixtures`` maps relative paths to source for
    a minimal tree on which the rule MUST fire (the self-test gate —
    a rule that cannot fail is not a rule)."""

    id: str = ""
    title: str = ""
    rationale: str = ""
    fixtures: dict[str, str] = {}
    # Config overrides the self-test applies when linting the fixture
    # (e.g. pointing refcount_modules at the fixture tree's modules).
    fixture_config: dict = {}

    def check(self, ctx: "Context") -> None:
        raise NotImplementedError


_REGISTRY: dict[str, Rule] = {}


def register(cls: type[Rule]) -> type[Rule]:
    if not cls.id or not re.fullmatch(r"GL-[A-Z]+(-[A-Z]+)*", cls.id):
        raise ValueError(f"rule id {cls.id!r} must match GL-[A-Z]+(-[A-Z]+)*")
    if cls.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {cls.id}")
    _REGISTRY[cls.id] = cls()
    return cls


def all_rules() -> dict[str, Rule]:
    return dict(_REGISTRY)


def get_rule(rule_id: str) -> Rule:
    return _REGISTRY[rule_id]


class Context:
    """Everything a rule pass sees: repo root, config, module index.
    ``full_run`` is True when the default roots (the whole repo) are
    being linted — rules that prove absence over the package
    (GL-CONFIG's stale-entry check) only run then; a ``--changed``
    subset cannot prove anything absent."""

    def __init__(
        self,
        repo: Path,
        cfg: GraftlintConfig,
        index: dict[str, ModuleInfo],
        full_run: bool = True,
    ):
        self.repo = repo
        self.cfg = cfg
        self.index = index
        self.full_run = full_run
        self.findings: list[Finding] = []
        self.n_checked_calls = 0  # GL-ARITY call sites verified
        # Rule-published structured output surfaced in --json (e.g.
        # GL-LOCK-ORDER's discovered lock hierarchy). Keyed by a short
        # artifact name; values must be JSON-serializable.
        self.artifacts: dict[str, object] = {}

    def report(
        self, rule_id: str, path: Path, lineno: int, message: str
    ) -> None:
        try:
            rel = path.relative_to(self.repo).as_posix()
        except ValueError:
            rel = path.as_posix()
        self.findings.append(Finding(rule_id, rel, lineno, message))

    def module(self, modname: str) -> ModuleInfo | None:
        return self.index.get(modname)


# ------------------------------------------------------------ suppression

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=(?P<ids>[A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
    r"(?P<reason>\s+--\s+\S.*)?\s*$"
)


@dataclass
class Suppression:
    path: str
    comment_line: int
    target_line: int  # the code line the suppression covers
    ids: tuple[str, ...]
    reason: str  # "" when missing (invalid — rejected)
    used: bool = False


def parse_suppressions(path: Path, repo: Path) -> list[Suppression]:
    """Inline ``# graftlint: disable=ID[,ID...] -- reason`` comments.

    Tokenized, not grepped: only genuine COMMENT tokens count, so a
    fixture string or docstring quoting the marker never becomes a live
    suppression. A trailing comment covers its own line; a standalone
    comment line covers the next code line.
    """
    import io
    import tokenize

    rel = path.relative_to(repo).as_posix()
    source = path.read_text(encoding="utf-8")
    lines = source.splitlines()
    out: list[Suppression] = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except tokenize.TokenError:
        return out
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        i = tok.start[0]
        ids = tuple(s.strip() for s in m.group("ids").split(","))
        reason = (m.group("reason") or "").strip()
        reason = reason[2:].strip() if reason.startswith("--") else ""
        target = i
        if lines[i - 1].strip().startswith("#"):
            # Standalone comment: applies to the next code line.
            for j in range(i, len(lines)):
                nxt = lines[j].strip()
                if nxt and not nxt.startswith("#"):
                    target = j + 1
                    break
        out.append(
            Suppression(
                path=rel,
                comment_line=i,
                target_line=target,
                ids=ids,
                reason=reason,
            )
        )
    return out


# --------------------------------------------------------------- baseline


def load_baseline(path: Path) -> list[tuple[str, str, str]]:
    if not path.exists():
        return []
    data = json.loads(path.read_text(encoding="utf-8"))
    if data.get("version") != BASELINE_VERSION:
        raise ValueError(
            f"baseline {path} has version {data.get('version')!r}; "
            f"expected {BASELINE_VERSION}"
        )
    return [
        (e["rule"], e["path"], e["message"]) for e in data.get("entries", [])
    ]


def write_baseline(path: Path, findings: list[Finding]) -> None:
    entries = [
        {"rule": f.rule, "path": f.path, "message": f.message}
        for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule))
    ]
    path.write_text(
        json.dumps({"version": BASELINE_VERSION, "entries": entries}, indent=1)
        + "\n",
        encoding="utf-8",
    )


# ----------------------------------------------------------------- driver


@dataclass
class LintResult:
    findings: list[Finding]  # what the caller should act on
    suppressed: list[Finding] = field(default_factory=list)
    baselined: list[Finding] = field(default_factory=list)
    n_files: int = 0
    n_checked_calls: int = 0
    rules_run: tuple[str, ...] = ()
    # Per-rule wall seconds: slow passes must be visible as the rule
    # set grows (interprocedural passes are not free).
    rule_seconds: dict[str, float] = field(default_factory=dict)
    # Structured rule output (Context.artifacts) — e.g. the canonical
    # lock hierarchy GL-LOCK-ORDER discovered.
    artifacts: dict[str, object] = field(default_factory=dict)

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def to_json(self) -> dict:
        by_rule: dict[str, int] = {}
        for f in self.findings:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        return {
            "version": JSON_VERSION,
            "rules": sorted(self.rules_run),
            "findings": [f.to_dict() for f in self.findings],
            "counts": {
                "total": len(self.findings),
                "suppressed": len(self.suppressed),
                "baselined": len(self.baselined),
                "by_rule": dict(sorted(by_rule.items())),
            },
            "files": self.n_files,
            "checked_calls": self.n_checked_calls,
            "rule_seconds": {
                r: round(s, 4)
                for r, s in sorted(self.rule_seconds.items())
            },
            "artifacts": dict(sorted(self.artifacts.items())),
        }


def collect_files(roots: list[Path]) -> list[Path]:
    files: list[Path] = []
    for r in roots:
        if r.is_dir():
            files += sorted(r.rglob("*.py"))
        elif r.suffix == ".py" and r.exists():
            files.append(r)
    return files


def run(
    paths: list[str] | None = None,
    *,
    repo: Path = REPO,
    rules: list[str] | None = None,
    cfg: GraftlintConfig | None = None,
    baseline: Path | None = BASELINE_PATH,
    full: bool | None = None,
) -> LintResult:
    """Lint ``paths`` (repo-default roots when empty) with the selected
    rules (all when None). Raises SyntaxError on unparsable files.
    ``full`` marks a whole-repo run (default: True iff ``paths`` is
    empty) — absence-proving rules (GL-CONFIG) only run then."""
    import time

    cfg = cfg or load_config(repo)
    roots = (
        [Path(p).resolve() for p in paths]
        if paths
        else [repo / r for r in DEFAULT_ROOTS]
    )
    files = collect_files(roots)
    index = build_index(files, repo, set(cfg.sig_preserving_decorators))
    ctx = Context(repo, cfg, index, full_run=not paths if full is None else full)

    selected = rules if rules is not None else sorted(_REGISTRY)
    unknown = [r for r in selected if r not in _REGISTRY]
    if unknown:
        raise KeyError(f"unknown rule id(s): {', '.join(unknown)}")
    rule_seconds: dict[str, float] = {}
    for rule_id in selected:
        t0 = time.perf_counter()
        _REGISTRY[rule_id].check(ctx)
        rule_seconds[rule_id] = time.perf_counter() - t0

    # Dedup (several taint hits can land on one line), drop findings for
    # unselected ids (shared passes may emit siblings), and sort.
    raw = sorted(
        {f for f in ctx.findings if f.rule in selected},
        key=lambda f: (f.path, f.line, f.rule),
    )

    suppressions: dict[str, list[Suppression]] = {}
    for f in files:
        rel = f.relative_to(repo).as_posix()
        suppressions[rel] = parse_suppressions(f, repo)

    reported: list[Finding] = []
    suppressed: list[Finding] = []
    for finding in raw:
        supp = None
        for s in suppressions.get(finding.path, ()):
            if finding.rule in s.ids and finding.line in (
                s.target_line,
                s.comment_line,
            ):
                supp = s
                break
        if supp is not None and supp.reason:
            supp.used = True
            suppressed.append(finding)
        else:
            reported.append(finding)

    # Suppression hygiene is itself a rule (GL-SUPPRESS): a reasonless
    # disable never suppresses, unknown ids are flagged so typos can't
    # silently disarm a rule, and a reasoned suppression that matched
    # nothing is STALE — its finding was fixed, the mute lingers.
    if rules is None or "GL-SUPPRESS" in selected:
        selected_set = set(selected)
        for file_supps in suppressions.values():
            for s in file_supps:
                if not s.reason:
                    reported.append(
                        Finding(
                            "GL-SUPPRESS",
                            s.path,
                            s.comment_line,
                            "suppression missing mandatory reason "
                            "(use: # graftlint: disable=<id> -- <reason>)",
                        )
                    )
                for rid in s.ids:
                    if rid not in _REGISTRY:
                        reported.append(
                            Finding(
                                "GL-SUPPRESS",
                                s.path,
                                s.comment_line,
                                f"suppression names unknown rule {rid!r}",
                            )
                        )
                # Stale check only when every suppressed rule actually
                # ran this invocation (a --rule subset must not call
                # the others' suppressions stale) AND the lint covered
                # the full roots — on a --changed path subset the taint
                # engine may lack the cross-module context that derives
                # a suppression's finding, and "no finding matched" on
                # a subset proves nothing (the GL-CONFIG rule's gate,
                # applied to suppressions).
                if (
                    s.reason
                    and not s.used
                    and ctx.full_run
                    and all(rid in selected_set for rid in s.ids)
                ):
                    reported.append(
                        Finding(
                            "GL-SUPPRESS",
                            s.path,
                            s.comment_line,
                            f"stale suppression ({', '.join(s.ids)}): "
                            "no finding matched it — the issue was "
                            "fixed or moved; delete the comment",
                        )
                    )

    baselined: list[Finding] = []
    if baseline is not None:
        known = set(load_baseline(baseline))
        still: list[Finding] = []
        for finding in reported:
            if finding.fingerprint() in known:
                baselined.append(finding)
            else:
                still.append(finding)
        reported = still

    reported.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(
        findings=reported,
        suppressed=suppressed,
        baselined=baselined,
        n_files=len(files),
        n_checked_calls=ctx.n_checked_calls,
        rules_run=tuple(selected),
        rule_seconds=rule_seconds,
        artifacts=dict(ctx.artifacts),
    )


def lint_sources(
    sources: dict[str, str],
    *,
    rules: list[str],
    cfg: GraftlintConfig | None = None,
    tmpdir: Path | None = None,
) -> list[Finding]:
    """Lint an in-memory tree (fixture helper for self-test + tests):
    writes ``sources`` under a temp repo root and runs the selected
    rules with no baseline."""
    import tempfile

    cfg = cfg or GraftlintConfig()
    own = tmpdir is None
    root = Path(tempfile.mkdtemp(prefix="graftlint-")) if own else tmpdir
    try:
        for rel, src in sources.items():
            dest = root / rel
            dest.parent.mkdir(parents=True, exist_ok=True)
            if (
                dest.parent != root
                and not (dest.parent / "__init__.py").exists()
            ):
                (dest.parent / "__init__.py").write_text("")
            dest.write_text(src, encoding="utf-8")
        result = run(
            [str(root)],
            repo=root,
            rules=rules,
            cfg=cfg,
            baseline=None,
            full=True,  # a fixture tree is its own whole repo
        )
        return result.findings
    finally:
        if own:
            import shutil

            shutil.rmtree(root, ignore_errors=True)


def self_test(rule_ids: list[str] | None = None) -> list[str]:
    """Prove every selected rule fires on its embedded fixture. Returns
    a list of failure messages (empty = all rules live)."""
    unknown = [r for r in (rule_ids or ()) if r not in _REGISTRY]
    if unknown:
        raise KeyError(f"unknown rule id(s): {', '.join(unknown)}")
    failures: list[str] = []
    for rule_id in sorted(rule_ids or _REGISTRY):
        rule = _REGISTRY[rule_id]
        if not rule.fixtures:
            failures.append(f"{rule_id}: no must-fail fixture embedded")
            continue
        cfg = GraftlintConfig(**rule.fixture_config)
        findings = lint_sources(
            dict(rule.fixtures), rules=[rule_id], cfg=cfg
        )
        if not any(f.rule == rule_id for f in findings):
            failures.append(
                f"{rule_id}: fixture produced no {rule_id} finding "
                f"(got: {[f.render() for f in findings]})"
            )
    return failures


def resolve_module_path(ctx: Context, path: Path) -> str:
    return modname_for(path, ctx.repo)
