"""Repo tooling: bench.py's in-process device contract, the graftlint
static-analysis framework, and the mutation runner's generation
invariants."""

import json
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))


class TestBench:
    """bench.py runs every mode in-process on the platform jax gives it:
    no device probe, no measuring child, no CPU fallback."""

    def test_no_probe_child_or_fallback(self):
        import bench

        for name in (
            "_probe_tpu",
            "_run_tpu_in_child",
            "_run_cpu_fallback",
            "_harvested_tuning",
        ):
            assert not hasattr(bench, name), name
        src = (REPO_ROOT / "bench.py").read_text()
        for gone in ("--_tpu-child", "BENCH_FORCE_CPU", "BENCH_TPU_TIMEOUT_S"):
            assert gone not in src, gone

    def test_failing_runner_is_a_nonzero_exit(self, monkeypatch, capsys):
        """A runner that raises must fail the command — never a payload
        from some other device."""
        import bench

        def boom(platform):
            raise RuntimeError("device fell over")

        monkeypatch.setattr(bench, "_run_bench", boom)
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        with pytest.raises(RuntimeError, match="device fell over"):
            bench.main()
        assert capsys.readouterr().out == ""

    def test_payload_names_the_device(self, monkeypatch, capsys):
        import jax

        import bench

        monkeypatch.setattr(
            bench,
            "_run_bench",
            lambda platform: {"metric": "m", "value": 1, "platform": "?"},
        )
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        assert bench.main() == 0
        out = json.loads(capsys.readouterr().out)
        dev = jax.devices()[0]
        assert out["platform"] == dev.platform == "cpu"
        assert out["device_kind"] == dev.device_kind
        assert out["device_count"] == len(jax.devices())

    @pytest.mark.slow
    def test_round_loop_mode_runs(self):
        """The config-4-shaped bench mode produces a complete record
        (driver-facing surface; pinned so the mode can't rot)."""
        import bench

        out = bench._run_round_loop("cpu")
        assert out["rounds"] == 5
        assert out["decode_tokens_total"] == 5 * 4 * 256
        assert out["value"] > 0
        assert out["vs_baseline"] is None  # cpu: no north-star ratio


class TestGraftlint:
    """tools/graftlint — the rule-registry static-analysis framework
    (docs/static_analysis.md). The compat entrypoint tools/astlint.py
    remains the executed typecheck gate."""

    ALL_RULES = {
        "GL-IMPORT",
        "GL-ATTR",
        "GL-ARITY",
        "GL-SYNC",
        "GL-TRACE",
        "GL-RETRACE",
        "GL-REFCOUNT",
        "GL-SUPPRESS",
        "GL-COMMIT",
        "GL-DONATE",
        "GL-ATOMIC",
        "GL-LIFECYCLE",
        "GL-CONFIG",
        "GL-LOCK-GUARD",
        "GL-LOCK-ORDER",
        "GL-LOCK-BLOCKING",
    }

    def test_repo_is_clean(self):
        """The package + tools + tests + entry scripts lint clean under
        EVERY registered rule (the executed typecheck gate, now with
        the serving-discipline rules on top) — and no
        grandfathered debt: the committed baseline must be empty."""
        import subprocess

        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint"],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        # The gate must actually be checking something.
        assert "call sites arity-checked" in r.stderr
        checked = int(r.stderr.rsplit("(", 1)[1].split()[0])
        assert checked > 400
        baseline = json.loads(
            (REPO_ROOT / "tools" / "graftlint" / "baseline.json").read_text()
        )
        assert baseline["entries"] == []

    def test_astlint_compat_entrypoint(self):
        """tools/astlint.py still runs, still exits 0 on the repo, and
        still prints the legacy summary line."""
        import subprocess

        r = subprocess.run(
            [sys.executable, str(REPO_ROOT / "tools" / "astlint.py")],
            capture_output=True,
            text=True,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "astlint: 0 finding(s)" in r.stderr
        assert "call sites arity-checked" in r.stderr

    def test_registry_and_selection(self):
        from tools.graftlint import all_rules, core

        rules = all_rules()
        assert set(rules) == self.ALL_RULES
        for rule in rules.values():
            assert rule.title and rule.rationale and rule.fixtures
        with pytest.raises(KeyError):
            core.run(rules=["GL-NOPE"])

    def test_self_test_every_rule_fires_on_its_fixture(self):
        """The self-test harness proves each registered rule can fail —
        a gate that cannot fail is not a gate."""
        from tools.graftlint import core

        assert core.self_test() == []

    def test_sync_fires_when_allowlist_entry_removed(self):
        """GL-SYNC is doing the exempting: the real batcher DOES
        blanket-sync inside its allowlisted methods, so an emptied
        allowlist must produce findings on them — and the committed
        allowlist none (test_repo_is_clean covers that end to end)."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        src = (
            REPO_ROOT / "adversarial_spec_tpu" / "engine" / "scheduler.py"
        ).read_text()
        findings = lint_sources(
            {"pkg/sched.py": src},
            rules=["GL-SYNC"],
            cfg=GraftlintConfig(sync_allowlist=[]),
        )
        msgs = [f.message for f in findings]
        assert msgs, "emptied allowlist produced no findings"
        # The allowlist names _advance_admission alone: emptied, every
        # blanket sync the rule finds is in that one method.
        blanket = [m for m in msgs if "block_until_ready" in m]
        assert blanket and all("_advance_admission" in m for m in blanket)

    def test_sync_fires_when_any_suppression_removed(self):
        """Acceptance pin: every inline GL-SYNC suppression in
        scheduler.py is load-bearing — removing any ONE of them makes
        the rule fire on exactly that site (none is decorative)."""
        from tools.graftlint.core import lint_sources

        path = (
            REPO_ROOT / "adversarial_spec_tpu" / "engine" / "scheduler.py"
        )
        lines = path.read_text().splitlines(keepends=True)
        supp = [
            i
            for i, line in enumerate(lines)
            if "# graftlint: disable=GL-SYNC" in line
        ]
        assert len(supp) >= 8, "scheduler lost its sanctioned-site map"
        # Fully suppressed as committed:
        assert (
            lint_sources({"pkg/sched.py": "".join(lines)}, rules=["GL-SYNC"])
            == []
        )
        for i in supp:
            mutated = "".join(
                line for j, line in enumerate(lines) if j != i
            )
            findings = lint_sources(
                {"pkg/sched.py": mutated}, rules=["GL-SYNC"]
            )
            assert findings, (
                f"removing the suppression on line {i + 1} produced no "
                "GL-SYNC finding — dead suppression"
            )

    def test_refcount_fires_on_acquire_without_release(self):
        """Acceptance pin: an acquire that can leak on a raise path is a
        finding; the guarded idiom and ownership-transfer-with-finally
        are not."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(refcount_modules=["pkg.alloc_user"])
        leaky = (
            "def admit(alloc, seq, tokens):\n"
            "    alloc.new_sequence(seq)\n"
            "    alloc.extend(seq, len(tokens))  # can raise: leaks seq\n"
            "    return seq\n"
            "\n"
            "def admit_guarded(alloc, seq, tokens):\n"
            "    alloc.new_sequence(seq)\n"
            "    try:\n"
            "        alloc.extend(seq, len(tokens))\n"
            "    except Exception:\n"
            "        alloc.free_sequence(seq)\n"
            "        raise\n"
            "    return seq\n"
            "\n"
            "def share(alloc, seq, pages, n):\n"
            "    try:\n"
            "        alloc.adopt(seq, pages, n)\n"
            "    finally:\n"
            "        alloc.free_sequence(seq)\n"
        )
        findings = lint_sources(
            {"pkg/alloc_user.py": leaky}, rules=["GL-REFCOUNT"], cfg=cfg
        )
        assert len(findings) == 1
        assert findings[0].line == 2
        assert "new_sequence() in admit" in findings[0].message

    def test_refcount_unrelated_guard_is_no_protection(self):
        """An acquire is protected only by its OWN guard — inside the
        try body, or the try opening as the immediately next statement.
        A later sibling guard (for a different sequence) leaves a leak
        window and must not mask the finding."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(refcount_modules=["pkg.m"])
        src = (
            "def f(alloc, a, b, n):\n"
            "    alloc.new_sequence(a)\n"
            "    alloc.extend(a, n)  # raise here leaks a\n"
            "    alloc.new_sequence(b)\n"
            "    try:\n"
            "        alloc.extend(b, n)\n"
            "    except Exception:\n"
            "        alloc.free_sequence(b)\n"
            "        raise\n"
        )
        findings = lint_sources(
            {"pkg/m.py": src}, rules=["GL-REFCOUNT"], cfg=cfg
        )
        assert [f.line for f in findings] == [2]

    def test_refcount_compound_statement_leak_window(self):
        """An acquire nested in a compound statement is protected by
        the compound's next-sibling guard ONLY in tail position: a
        risky statement after the acquire inside the compound is a leak
        window, and a loop body is never tail (later iterations
        intervene)."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(refcount_modules=["pkg.m"])
        guard = (
            "    try:\n"
            "        alloc.extend(seq, 1)\n"
            "    except Exception:\n"
            "        alloc.free_sequence(seq)\n"
            "        raise\n"
        )
        risky = (
            "def f(alloc, seq, tokens):\n"
            "    if tokens:\n"
            "        alloc.new_sequence(seq)\n"
            "        do_risky(tokens)\n" + guard
        )
        findings = lint_sources(
            {"pkg/m.py": risky}, rules=["GL-REFCOUNT"], cfg=cfg
        )
        assert [f.line for f in findings] == [3]
        tail = (
            "def f(alloc, seq, tokens):\n"
            "    if tokens:\n"
            "        alloc.new_sequence(seq)\n" + guard
        )
        assert (
            lint_sources({"pkg/m.py": tail}, rules=["GL-REFCOUNT"], cfg=cfg)
            == []
        )
        loop = (
            "def f(alloc, seq, pages):\n"
            "    for p in pages:\n"
            "        alloc.cache_ref(p)\n"
            "    try:\n"
            "        commit()\n"
            "    except Exception:\n"
            "        alloc.cache_unref(p)\n"
            "        raise\n"
        )
        findings = lint_sources(
            {"pkg/m.py": loop}, rules=["GL-REFCOUNT"], cfg=cfg
        )
        assert [f.line for f in findings] == [3]

    def test_syntax_error_names_the_file(self, tmp_path):
        from tools.graftlint import core
        from tools.graftlint.config import GraftlintConfig

        (tmp_path / "broken.py").write_text("def f(:\n")
        with pytest.raises(SyntaxError, match="broken"):
            core.run(
                [str(tmp_path)],
                repo=tmp_path,
                rules=["GL-IMPORT"],
                cfg=GraftlintConfig(),
                baseline=None,
            )

    def test_config_reader_tolerates_toml_comments(self, tmp_path):
        """Inline comments after values and comment lines inside
        multi-line arrays are valid TOML and must parse, not crash."""
        from tools.graftlint.config import read_graftlint_table

        p = tmp_path / "pyproject.toml"
        p.write_text(
            "[tool.graftlint]\n"
            'sync_class = "ContinuousBatcher"  # the batcher\n'
            "sync_allowlist = [\n"
            "    # keep in sync with docs\n"
            '    "_advance_admission",\n'
            '    "_drive_legacy",  # escape hatch\n'
            "]\n"
        )
        table = read_graftlint_table(p)
        assert table["sync_class"] == "ContinuousBatcher"
        assert table["sync_allowlist"] == [
            "_advance_admission",
            "_drive_legacy",
        ]

    def test_retrace_nested_def_does_not_poison_outer_scope(self):
        """A nested function's local assignment must not degrade a
        same-named outer local to 'dynamic' (scopes are separate)."""
        from tools.graftlint.core import lint_sources

        src = (
            "from functools import partial\n"
            "import jax\n"
            "def _impl(x, *, chunk):\n"
            "    return x\n"
            "step = partial(jax.jit, static_argnames=('chunk',))(_impl)\n"
            "def drive(x, ys):\n"
            "    n = 256\n"
            "    def helper(zs):\n"
            "        n = len(zs)\n"
            "        return n\n"
            "    return step(x, chunk=n)\n"
        )
        assert lint_sources({"pkg/c.py": src}, rules=["GL-RETRACE"]) == []

    def test_stale_suppression_is_flagged(self):
        """A reasoned suppression whose finding was fixed is reported
        stale (only when every suppressed rule actually ran)."""
        from tools.graftlint.core import lint_sources

        src = "import os  # graftlint: disable=GL-SYNC -- was needed\n"
        findings = lint_sources(
            {"pkg/x.py": src}, rules=["GL-SYNC", "GL-SUPPRESS"]
        )
        assert any("stale suppression" in f.message for f in findings)
        # A --rule subset that does NOT run the suppressed rule must
        # not call its suppressions stale.
        findings = lint_sources({"pkg/x.py": src}, rules=["GL-SUPPRESS"])
        assert findings == []

    def test_trace_rule_fires_through_the_jit_closure(self):
        """GL-TRACE reaches bodies only *called* from a jit root: the
        impure call sits in a helper, the jit wrapping is on the
        caller (the fused-program pattern)."""
        from tools.graftlint.core import lint_sources

        src = (
            "import time\n"
            "from functools import partial\n"
            "import jax\n"
            "\n"
            "def helper(x):\n"
            "    return x + time.monotonic()\n"
            "\n"
            "@partial(jax.jit, static_argnames=('n',))\n"
            "def step(x, *, n):\n"
            "    return helper(x)\n"
        )
        findings = lint_sources({"pkg/traced.py": src}, rules=["GL-TRACE"])
        assert len(findings) == 1
        assert "time.monotonic" in findings[0].message
        assert "helper" in findings[0].message

    def test_trace_roots_cover_spec_verify_programs(self):
        """GL-TRACE's discovered roots must include the speculative
        verify programs (ISSUE 6): both the standalone and the fused
        draft+verify chunk are jit roots whose transitive bodies the
        rule walks."""
        from pathlib import Path

        from tools.graftlint.config import load_config
        from tools.graftlint.core import (
            DEFAULT_ROOTS,
            Context,
            build_index,
            collect_files,
        )
        from tools.graftlint.rules.trace import traced_functions

        repo = REPO_ROOT
        cfg = load_config(repo)
        files = collect_files([Path(repo) / r for r in DEFAULT_ROOTS])
        index = build_index(
            files, repo, set(cfg.sig_preserving_decorators)
        )
        ctx = Context(repo, cfg, index)
        roots = {
            fn for (mod, fn) in traced_functions(ctx)
            if mod.endswith("engine.scheduler")
        }
        assert "_spec_chunk_impl" in roots
        assert "fused_prefill_spec_chunk" in roots

    def test_retrace_rule_static_and_traced_args(self):
        from tools.graftlint.core import lint_sources

        src = (
            "from functools import partial\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "\n"
            "def bucket_length(n):\n"
            "    return max(128, 1 << (n - 1).bit_length())\n"
            "\n"
            "def _impl(x, n, *, chunk):\n"
            "    return x\n"
            "\n"
            "step = partial(jax.jit, static_argnames=('chunk',))(_impl)\n"
            "\n"
            "def drive(x, xs):\n"
            "    step(x, jnp.int32(0), chunk=256)\n"
            "    step(x, jnp.int32(0), chunk=bucket_length(len(xs)))\n"
            "    step(x, jnp.int32(0), chunk=len(xs))\n"
            "    step(x, len(xs), chunk=256)\n"
        )
        findings = lint_sources({"pkg/calls.py": src}, rules=["GL-RETRACE"])
        assert len(findings) == 2
        by_line = {f.line: f.message for f in findings}
        assert "dynamic Python scalar to a static arg" in by_line[16]
        assert "bare host scalar to a traced arg" in by_line[17]

    def test_suppression_requires_reason(self):
        """A reasoned inline disable suppresses; a reasonless one is
        rejected — the underlying finding survives AND the malformed
        suppression is itself a GL-SUPPRESS finding."""
        from tools.graftlint.core import lint_sources

        body = (
            "import jax\n"
            "class ContinuousBatcher:\n"
            "    def hot(self):\n"
            "        jax.block_until_ready(self.active){}\n"
        )
        reasoned = body.format(
            "  # graftlint: disable=GL-SYNC -- test fixture"
        )
        assert (
            lint_sources({"p/s.py": reasoned}, rules=["GL-SYNC"]) == []
        )
        reasonless = body.format("  # graftlint: disable=GL-SYNC")
        findings = lint_sources(
            {"p/s.py": reasonless}, rules=["GL-SYNC", "GL-SUPPRESS"]
        )
        rules = {f.rule for f in findings}
        assert rules == {"GL-SYNC", "GL-SUPPRESS"}
        assert any("missing mandatory reason" in f.message for f in findings)
        # A typo'd rule id is flagged too (a silently disarmed check).
        typod = body.format(
            "  # graftlint: disable=GL-SNC -- reason given"
        )
        findings = lint_sources(
            {"p/s.py": typod}, rules=["GL-SYNC", "GL-SUPPRESS"]
        )
        assert any("unknown rule" in f.message for f in findings)

    def test_baseline_round_trip(self, tmp_path):
        """write_baseline grandfathers current findings; a re-run
        against that baseline is clean; a NEW finding still fires."""
        from tools.graftlint import core
        from tools.graftlint.config import GraftlintConfig

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "base.py").write_text("def real_thing():\n    return 1\n")
        (pkg / "old.py").write_text(
            "from pkg.base import missing_thing\n"
        )
        cfg = GraftlintConfig()
        baseline = tmp_path / "baseline.json"
        first = core.run(
            [str(pkg)], repo=tmp_path, rules=["GL-IMPORT"], cfg=cfg,
            baseline=None,
        )
        assert len(first.findings) == 1
        core.write_baseline(baseline, first.findings)
        second = core.run(
            [str(pkg)], repo=tmp_path, rules=["GL-IMPORT"], cfg=cfg,
            baseline=baseline,
        )
        assert second.findings == []
        assert len(second.baselined) == 1
        # New debt is not grandfathered.
        (pkg / "new.py").write_text("from pkg.base import also_missing\n")
        third = core.run(
            [str(pkg)], repo=tmp_path, rules=["GL-IMPORT"], cfg=cfg,
            baseline=baseline,
        )
        assert len(third.findings) == 1
        assert "also_missing" in third.findings[0].message

    def test_json_schema_stability(self):
        """The --json payload shape is a driver-facing surface: pin it."""
        import subprocess

        r = subprocess.run(
            [
                sys.executable,
                "-m",
                "tools.graftlint",
                "--json",
                "--rule",
                "GL-IMPORT",
                str(REPO_ROOT / "tools" / "graftlint"),
            ],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert set(payload) == {
            "version",
            "rules",
            "findings",
            "counts",
            "files",
            "checked_calls",
            "rule_seconds",
            "artifacts",
        }
        assert payload["version"] == 1
        assert payload["rules"] == ["GL-IMPORT"]
        # Rule-emitted artifacts (GL-LOCK-ORDER's lock_order/lock_edges)
        # only appear when their rule is selected.
        assert payload["artifacts"] == {}
        assert set(payload["counts"]) == {
            "total",
            "suppressed",
            "baselined",
            "by_rule",
        }
        # Per-rule wall timing: every selected rule reports a
        # non-negative float (slow passes visible as the set grows).
        assert set(payload["rule_seconds"]) == {"GL-IMPORT"}
        assert payload["rule_seconds"]["GL-IMPORT"] >= 0.0

    def test_detects_seeded_error_classes(self):
        """Every legacy astlint error class fires on a synthetic
        package — proof the ported gate can still fail."""
        from tools.graftlint.core import lint_sources

        sources = {
            "pkg/good.py": "def takes_two(a, b, *, c=0):\n    return a\n",
            "pkg/bad.py": (
                "from pkg.good import takes_two, absent\n"
                "from pkg import good\n"
                "takes_two(1)\n"
                "takes_two(1, 2, 3)\n"
                "takes_two(1, 2, zz=9)\n"
                "x = good.nothing_here\n"
                # A keyword hitting an OPTIONAL positional must not mask
                # the missing required one (f(b=2) on f(a, b=1) raises).
                "def opt(a, b=1):\n    return a\n"
                "opt(b=2)\n"
                # A parameter shadowing a module function must NOT be
                # arity-checked against the module function.
                "def uses(takes_two):\n    return takes_two(1, 2, 3, 4)\n"
            ),
            "pkg/sub/leaf.py": "def leaf_fn(x):\n    return x\n",
            # Relative import from a nested-package __init__: level 1 is
            # the package itself; a bad name must be flagged there too.
            "pkg/sub/__init__.py": (
                "from .leaf import leaf_fn, leaf_missing\n"
            ),
        }
        findings = lint_sources(
            sources, rules=["GL-IMPORT", "GL-ATTR", "GL-ARITY"]
        )
        text = "\n".join(f.message for f in findings)
        assert "'absent' is not defined" in text
        assert "missing required args" in text
        assert "takes 2 positional args but 3 given" in text
        assert "unexpected keyword 'zz'" in text
        assert "no attribute 'nothing_here'" in text
        # opt(b=2): the optional-positional keyword can't stand in for
        # the missing required 'a'.
        assert "opt() missing required args" in text
        # Shadowed name: no finding may point at the `uses` body.
        assert "takes 2 positional args but 4 given" not in text
        # Nested __init__ relative import resolves to pkg.sub.leaf.
        assert "'leaf_missing' is not defined in pkg.sub.leaf" in text

    def test_shadowed_names_one_level_flow(self):
        """Regression for the _shadowed_names fix: the docstring always
        promised params PLUS local assignment/for/with/except targets,
        but the pre-graftlint code only collected params — a local
        rebind then false-positived against the module function."""
        from tools.graftlint.core import lint_sources

        sources = {
            "pkg/good.py": "def takes_two(a, b):\n    return a\n",
            "pkg/bad.py": (
                "from pkg.good import takes_two\n"
                "def make():\n    return None\n"
                # Local ASSIGNMENT rebind: must not be arity-checked.
                "def via_assign():\n"
                "    takes_two = make()\n"
                "    return takes_two(1, 2, 3, 4)\n"
                # for-target rebind.
                "def via_for(xs):\n"
                "    for takes_two in xs:\n"
                "        takes_two(1, 2, 3, 4)\n"
                # with-target rebind.
                "def via_with(cm):\n"
                "    with cm as takes_two:\n"
                "        return takes_two(1, 2, 3, 4)\n"
                # except-target rebind.
                "def via_except():\n"
                "    try:\n"
                "        return takes_two(1, 2)\n"  # real call: checked
                "    except ValueError as takes_two:\n"
                "        return takes_two\n"
                # AFTER the scoped functions, module-level resolution
                # must be restored: this bad call must still be caught.
                "takes_two(1, 2, 3, 4)\n"
            ),
        }
        findings = lint_sources(sources, rules=["GL-ARITY"])
        assert len(findings) == 1
        assert findings[0].line > 15, "local rebind was arity-checked"
        assert "takes 2 positional args but 4 given" in findings[0].message

    def test_config_drift_guard_empty(self):
        """THE pyproject-vs-code-defaults drift guard, shared with the
        tools/lint_all.py graftlint-config stage (hoisted there from
        scattered per-check pins): the [tool.graftlint] table and the
        in-code defaults are the same config, field by field."""
        from tools.graftlint.config import config_drift

        assert config_drift(REPO_ROOT) == []

    # One shared parametrized pin for the per-module process-config
    # defaults (spec / prefix_cache / kvtier / streaming used to each
    # pin their own): the DATACLASS defaults — what a
    # fresh process arms before any CLI/env override — are part of the
    # serving contract (docs/perf.md's default-on claims) and must not
    # drift silently when a module is touched.
    @pytest.mark.parametrize(
        "modname, cls, knob, expected",
        [
            ("engine.spec", "SpecConfig", "enabled", True),
            ("engine.spec", "SpecConfig", "gamma", 8),
            ("engine.prefix_cache", "PrefixCacheConfig", "enabled", True),
            ("engine.prefix_cache", "PrefixCacheConfig", "max_pages", 0),
            ("engine.kvtier", "TierConfig", "enabled", True),
            ("engine.kvtier", "TierConfig", "store_dir", ""),
            ("engine.streaming", "StreamConfig", "enabled", True),
            ("engine.streaming", "StreamConfig", "early_cancel", True),
        ],
    )
    def test_module_config_default_pins(self, modname, cls, knob, expected):
        import importlib

        mod = importlib.import_module(f"adversarial_spec_tpu.{modname}")
        fresh = getattr(mod, cls)()  # defaults, not the armed instance
        assert getattr(fresh, knob) == expected

    # -- graftlint v2: interprocedural dataflow + new rule families ----

    def test_sync_taint_survives_helper_extraction(self):
        """The v2 headline: extracting a batcher fetch into a helper
        (method or same-module function) must not launder device taint
        — and a helper fed only host values must stay clean."""
        from tools.graftlint.core import lint_sources

        sources = {
            "pkg/sched.py": (
                "import numpy as np\n"
                "\n"
                "def fetch_rows(buf):\n"
                "    return np.asarray(buf)\n"
                "\n"
                "class ContinuousBatcher:\n"
                "    def _host_helper(self, counts):\n"
                "        return np.asarray(counts)\n"
                "    def _drive(self):\n"
                "        rows = fetch_rows(self.out_buf)\n"
                "        host = [1, 2, 3]\n"
                "        ok = self._host_helper(host)\n"
                "        return rows, ok\n"
            ),
        }
        findings = lint_sources(sources, rules=["GL-SYNC"])
        msgs = [f.render() for f in findings]
        assert any(
            "helper fetch_rows" in m and ":4:" in m for m in msgs
        ), msgs
        # The host-fed helper must NOT fire (conservative at unknown /
        # host provenance).
        assert not any("_host_helper" in m for m in msgs), msgs

    def test_sync_taint_through_summaries_and_locals(self):
        """Derived taint: a method whose return derives from device
        attrs taints its callers' locals; assignment chains keep it."""
        from tools.graftlint.core import lint_sources

        sources = {
            "pkg/sched.py": (
                "import jax.numpy as jnp\n"
                "import numpy as np\n"
                "\n"
                "class ContinuousBatcher:\n"
                "    def _counts(self):\n"
                "        return jnp.stack([self.n_emitted])\n"
                "    def _drive(self):\n"
                "        counts = self._counts()\n"
                "        snapshot = counts\n"
                "        return int(snapshot[0])\n"
            ),
        }
        findings = lint_sources(sources, rules=["GL-SYNC"])
        assert len(findings) == 1 and "int() on a device value" in (
            findings[0].message
        ), [f.render() for f in findings]

    def test_commit_rule_flags_uncommitted_creation_only(self):
        """GL-COMMIT: a bare creator reaching a persistent attr or a
        holder keyword (directly or through a local) fires; wrapped
        creations and DERIVED state (.at[].set, zeros_like) stay
        clean."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(
            commit_classes=["Batcher"],
            commit_attrs=["active", "cache"],
            commit_holders=["Admission"],
        )
        sources = {
            "pkg/b.py": (
                "import jax.numpy as jnp\n"
                "\n"
                "def init_cache(n):\n"
                "    return {}\n"
                "\n"
                "class Admission:\n"
                "    cache: dict = None\n"
                "\n"
                "class Batcher:\n"
                "    def __init__(self, B):\n"
                "        self.active = jnp.zeros((B,), bool)\n"
                "        self.other = jnp.zeros((B,))\n"
                "    def _commit(self, x):\n"
                "        return x\n"
                "    def admit(self):\n"
                "        ok = self._commit(init_cache(4))\n"
                "        bad = init_cache(4)\n"
                "        a1 = Admission(cache=ok)\n"
                "        a2 = Admission(cache=bad)\n"
                "        a3 = Admission(cache=init_cache(4))\n"
                "        self.active = self.active.at[0].set(False)\n"
                "        self.active = jnp.zeros_like(self.active)\n"
                "        return a1, a2, a3\n"
            ),
        }
        findings = lint_sources(sources, rules=["GL-COMMIT"], cfg=cfg)
        lines = sorted(f.line for f in findings)
        # __init__ self.active (11), a2's local flow (19), a3's direct
        # creator keyword (20) — and nothing else: self.other is not a
        # configured attr, ok is wrapped, derived state is derived.
        assert lines == [11, 19, 20], [f.render() for f in findings]

    def test_commit_rule_is_flow_ordered_on_rebinds(self):
        """Review regression: the local-flow env must be per program
        point, not the function's FINAL bindings — a local rebound
        AFTER a holder use must neither poison an earlier committed
        use (false positive) nor launder an earlier uncommitted one
        (false negative)."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(
            commit_classes=["Batcher"],
            commit_attrs=["cache"],
            commit_holders=["Admission"],
        )
        sources = {
            "pkg/b.py": (
                "def init_cache(n):\n"
                "    return {}\n"
                "\n"
                "class Admission:\n"
                "    cache: dict = None\n"
                "\n"
                "class Batcher:\n"
                "    def _commit(self, x):\n"
                "        return x\n"
                "    def good_then_rebound(self):\n"
                "        c = self._commit(init_cache(4))\n"
                "        a = Admission(cache=c)\n"
                "        c = init_cache(4)\n"
                "        return a, self._commit(c)\n"
                "    def bad_then_laundered(self):\n"
                "        c = init_cache(4)\n"
                "        a = Admission(cache=c)\n"
                "        c = self._commit(init_cache(4))\n"
                "        return a, c\n"
            ),
        }
        findings = lint_sources(sources, rules=["GL-COMMIT"], cfg=cfg)
        assert [f.line for f in findings] == [17], [
            f.render() for f in findings
        ]

    def test_donate_rule_escape_positions_and_snapshots(self):
        """GL-DONATE: a raw alias stored in the dispatch loop fires; a
        jnp.copy snapshot, the rebind idiom, a post-loop return, and
        the staged-args splat are all clean."""
        from tools.graftlint.core import lint_sources

        src = (
            "from functools import partial\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "\n"
            "def _impl(pool, out_buf):\n"
            "    return pool, out_buf\n"
            "\n"
            "step = partial(jax.jit, donate_argnames=('pool', 'out_buf'))"
            "(_impl)\n"
            "\n"
            "def drive(pool, out_buf, n):\n"
            "    entries = []\n"
            "    for _ in range(n):\n"
            "        entries.append((out_buf,))\n"
            "        snap = (jnp.copy(out_buf),)\n"
            "        pool, out_buf = step(pool, out_buf)\n"
            "        args = (pool, out_buf)\n"
            "        pool, out_buf = step(*args)\n"
            "    return out_buf\n"
        )
        findings = lint_sources({"pkg/d.py": src}, rules=["GL-DONATE"])
        assert [f.line for f in findings] == [13], [
            f.render() for f in findings
        ]
        assert "container literal" in findings[0].message

    def test_donate_rule_interprocedural_method_summary(self):
        """A method that donates self.X marks ITS callers' escapes: the
        PR 9 shape — dispatch in one method, raw alias stored in the
        drive loop of another."""
        from tools.graftlint.core import lint_sources

        src = (
            "from functools import partial\n"
            "import jax\n"
            "\n"
            "def _impl(out_buf):\n"
            "    return out_buf\n"
            "\n"
            "step = partial(jax.jit, donate_argnames=('out_buf',))(_impl)\n"
            "\n"
            "class Batcher:\n"
            "    def _dispatch(self):\n"
            "        self.out_buf = step(self.out_buf)\n"
            "    def _drive(self, n):\n"
            "        inflight = []\n"
            "        while n:\n"
            "            self._dispatch()\n"
            "            inflight.append((self.out_buf,))\n"
            "            n -= 1\n"
            "        return inflight\n"
        )
        findings = lint_sources({"pkg/d.py": src}, rules=["GL-DONATE"])
        assert [f.line for f in findings] == [16], [
            f.render() for f in findings
        ]
        assert "self.out_buf" in findings[0].message

    def test_atomic_rule_scope_and_allowlist(self):
        """GL-ATOMIC: write-mode opens / write_text inside the package
        fire unless the enclosing function is a sanctioned
        implementation; reads and out-of-package writes are free."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(
            package="pkg", atomic_funcs=["pkg.io:atomic_write"]
        )
        sources = {
            "pkg/io.py": (
                "import os\n"
                "\n"
                "def atomic_write(path, data):\n"
                "    with open(path + '.tmp', 'w') as f:\n"
                "        f.write(data)\n"
                "    os.replace(path + '.tmp', path)\n"
                "\n"
                "def torn_write(path, data):\n"
                "    with open(path, 'w') as f:\n"
                "        f.write(data)\n"
                "\n"
                "def reader(path):\n"
                "    return open(path).read()\n"
            ),
            "elsewhere/scratch.py": (
                "def dump(path, data):\n"
                "    open(path, 'w').write(data)\n"
            ),
        }
        findings = lint_sources(sources, rules=["GL-ATOMIC"], cfg=cfg)
        assert [f.line for f in findings] == [9], [
            f.render() for f in findings
        ]
        assert "torn_write" in findings[0].message

    def test_lifecycle_rule_exit_reachability_and_side_writes(self):
        """GL-LIFECYCLE: an exit path that never reaches the shared
        surgery fires, a hand-rolled ownership write outside the
        surgery fires, and the sanctioned paths stay clean."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg = GraftlintConfig(
            lifecycle_class="Batcher",
            lifecycle_release="_release_slot",
            lifecycle_exits=["_finish_slot", "_cancel_slot"],
            lifecycle_owned_attrs=["_slot_req", "_slot_seq"],
            lifecycle_mutators=["_finish_admission"],
        )
        sources = {
            "pkg/sched.py": (
                "class Batcher:\n"
                "    def __init__(self, B):\n"
                "        self._slot_req = [None] * B\n"
                "    def _finish_admission(self, slot, req):\n"
                "        self._slot_req[slot] = req\n"
                "    def _release_slot(self, slot):\n"
                "        self._slot_req[slot] = None\n"
                "        self._slot_seq[slot] = None\n"
                "    def _finish_slot(self, slot):\n"
                "        self._release_slot(slot)\n"
                "    def _cancel_slot(self, slot):\n"
                "        self._slot_req[slot] = None\n"
            ),
        }
        findings = lint_sources(
            sources, rules=["GL-LIFECYCLE"], cfg=cfg
        )
        msgs = [f.render() for f in findings]
        assert len(findings) == 2, msgs
        assert any(
            "never reaches the shared release surgery" in m for m in msgs
        )
        assert any("self._slot_req written" in m for m in msgs)

    def test_config_rule_stale_entries(self):
        """GL-CONFIG (stale-allowlist detection): a table entry that
        matches nothing in the indexed package is a finding; live
        entries are not; a path-subset run proves nothing and skips."""
        from tools.graftlint.config import GraftlintConfig
        from tools.graftlint.core import lint_sources

        cfg_kwargs = dict(
            package="pkg",
            sync_class="Batcher",
            sync_allowlist=["_live", "_ghost"],
            sync_device_attrs=["active"],
            sync_device_names=[],
            refcount_modules=[],
            refcount_pairs=[],
            retrace_bucketers=[],
            commit_classes=[],
            commit_attrs=[],
            commit_holders=[],
            atomic_funcs=[],
            lifecycle_class="Batcher",
            lifecycle_release="_live",
            lifecycle_exits=[],
            lifecycle_owned_attrs=[],
            lifecycle_mutators=[],
            fleet_lifecycle_class="",  # fixture has no fleet machine
            serve_lifecycle_class="",  # fixture has no serve machine
            weightres_lifecycle_class="",  # nor a weight-ledger machine
            autoscale_lifecycle_class="",  # nor an autoscaler machine
            handoff_lifecycle_class="",  # nor a handoff ledger
            lock_guards=[],  # nor any declared locks
            lock_thread_entries=[],
        )
        sources = {
            "pkg/sched.py": (
                "class Batcher:\n"
                "    def _live(self):\n"
                "        return self.active\n"
            ),
        }
        findings = lint_sources(
            sources,
            rules=["GL-CONFIG"],
            cfg=GraftlintConfig(**cfg_kwargs),
        )
        msgs = [f.message for f in findings]
        assert len(findings) == 1, msgs
        assert "'_ghost'" in msgs[0] and "sync_allowlist" in msgs[0]

    def test_changed_mode_filter(self):
        """lint_all's --changed filter keeps only existing .py files
        under the lint roots."""
        from tools.lint_all import lintable

        names = [
            "adversarial_spec_tpu/engine/scheduler.py",
            "tools/lint_all.py",
            "bench.py",
            "docs/static_analysis.md",  # not .py
            "adversarial_spec_tpu/engine/ghost.py",  # doesn't exist
            "somewhere_else/module.py",  # outside the roots
        ]
        assert lintable(names, REPO_ROOT) == [
            "adversarial_spec_tpu/engine/scheduler.py",
            "bench.py",
            "tools/lint_all.py",
        ]

    # -- regression-class pins: the two historical bugs, permanently --

    def _scheduler_src(self):
        return (
            REPO_ROOT / "adversarial_spec_tpu" / "engine" / "scheduler.py"
        ).read_text()

    def test_commit_regression_pin(self):
        """Deleting the ``self._commit`` wrapper (the PR 5/6 double-
        compile bugs, scheduler.py `_commit`) makes GL-COMMIT fire on
        the real codebase — and the committed source is clean."""
        from tools.graftlint.core import lint_sources

        src = self._scheduler_src()
        path = "adversarial_spec_tpu/engine/scheduler.py"
        assert (
            lint_sources({path: src}, rules=["GL-COMMIT"]) == []
        ), "committed scheduler must be GL-COMMIT clean"
        assert "self._commit(" in src
        mutated = src.replace("self._commit(", "(")
        findings = lint_sources({path: mutated}, rules=["GL-COMMIT"])
        assert findings, (
            "removing the _commit wrapper produced no GL-COMMIT "
            "finding — the double-compile class is unguarded"
        )
        # Both historical sites are caught: the admission cache
        # (holder keyword, PR 5) and batcher row state (PR 6).
        msgs = " ".join(f.message for f in findings)
        assert "cache" in msgs and "self." in msgs

    def test_donate_regression_pin(self):
        """Deleting the ``jnp.copy`` snapshot (the PR 9 donated-buffer
        bug, scheduler.py streaming entry) makes GL-DONATE fire on the
        real codebase — and the committed source is clean."""
        from tools.graftlint.core import lint_sources

        src = self._scheduler_src()
        path = "adversarial_spec_tpu/engine/scheduler.py"
        assert (
            lint_sources({path: src}, rules=["GL-DONATE"]) == []
        ), "committed scheduler must be GL-DONATE clean"
        needle = "jnp.copy(self.out_buf) if streaming else None"
        assert needle in src
        mutated = src.replace(needle, "self.out_buf if streaming else None")
        findings = lint_sources({path: mutated}, rules=["GL-DONATE"])
        assert findings, (
            "removing the jnp.copy snapshot produced no GL-DONATE "
            "finding — the use-after-donate class is unguarded"
        )
        assert any("self.out_buf" in f.message for f in findings)


class TestObsDump:
    """tools/obs_dump.py — offline validator/pretty-printer for flight-
    recorder JSONL (the triage half of the observability subsystem)."""

    def _dump(self, tmp_path, events):
        import json

        p = tmp_path / "ev.jsonl"
        p.write_text(
            "".join(json.dumps(e) + "\n" for e in events), encoding="utf-8"
        )
        return str(p)

    def _recorder_dump(self, tmp_path):
        from adversarial_spec_tpu.obs import (
            FaultEvent,
            FlightRecorder,
            RequestEvent,
            StepEvent,
        )

        r = FlightRecorder(size=64)
        r.append(RequestEvent(req_id=0, state="queued", tokens=8))
        r.append(RequestEvent(req_id=0, state="admitted", slot=1, tokens=8))
        r.append(
            StepEvent(kind="fused", n_live=2, admission_slot=1,
                      prefill_tokens=64, decode_chunk=4, pipeline_depth=2)
        )
        r.append(StepEvent(kind="decode", n_live=2, decode_chunk=4,
                           sync_reason="depth_fetch"))
        r.append(
            FaultEvent(seam="scheduler_chunk", kind="oom", slot=1,
                       req_id=0, pages_freed=3)
        )
        r.append(RequestEvent(req_id=0, state="evicted", slot=1))
        p = tmp_path / "real.jsonl"
        r.dump_jsonl(str(p))
        return str(p)

    def test_real_recorder_dump_validates_exit_0(self, tmp_path, capsys):
        from tools.obs_dump import main

        path = self._recorder_dump(tmp_path)
        assert main([path, "--timeline", "--requests"]) == 0
        out = capsys.readouterr().out
        assert "6 event(s)" in out
        assert "oom at scheduler_chunk" in out
        assert "3 page(s) freed" in out

    def test_occupancy_timeline_renders_bars_and_annotations(
        self, tmp_path, capsys
    ):
        from tools.obs_dump import load_events, occupancy_timeline

        events, errors = load_events(self._recorder_dump(tmp_path))
        assert errors == []
        text = occupancy_timeline(events)
        assert "#" in text  # fused glyph at full occupancy
        assert "adm@1+64tok" in text
        assert "depth=2" in text
        assert "sync=depth_fetch" in text

    def test_schema_violations_exit_1_and_are_listed(self, tmp_path, capsys):
        from tools.obs_dump import main

        path = self._dump(
            tmp_path,
            [
                {"seq": 1, "type": "nope"},
                {"seq": 2, "type": "request", "req_id": "zero",
                 "state": "queued", "slot": -1, "tokens": 0,
                 "cached_tokens": 0},
                {"seq": 3, "type": "step", "kind": "decode", "n_live": 0,
                 "admission_slot": -1, "prefill_tokens": 0,
                 "decode_chunk": 0, "pipeline_depth": 0,
                 "sync_reason": ""},
            ],
        )
        assert main([path]) == 1
        err = capsys.readouterr().err
        assert "unknown event type 'nope'" in err
        assert "req_id" in err
        assert "schema violation" in err

    def test_unreadable_input_exits_2(self, tmp_path):
        from tools.obs_dump import main

        assert main([str(tmp_path / "missing.jsonl")]) == 2

    def test_unexpected_recompiles_warn_in_summary(self, tmp_path, capsys):
        from tools.obs_dump import main

        path = self._dump(
            tmp_path,
            [
                {"seq": 1, "type": "compile", "program": "decode",
                 "key": "(4,)", "n_compiles": 2, "unexpected": True,
                 "trace_id": "", "span_id": ""},
            ],
        )
        assert main([path]) == 0
        assert "unexpected jit recompile" in capsys.readouterr().out

    def test_schemas_track_the_dataclasses(self):
        """EVENT_FIELDS derives from the dataclasses — a new event field
        is validated automatically, never silently ignored."""
        import dataclasses

        from adversarial_spec_tpu.obs import EVENT_FIELDS
        from adversarial_spec_tpu.obs.events import EVENT_TYPES

        for cls in EVENT_TYPES:
            assert set(EVENT_FIELDS[cls.TYPE]) == {
                f.name for f in dataclasses.fields(cls)
            }
        # Trace ids are part of EVERY event's schema, and the span
        # event is in the vocabulary.
        for cls in EVENT_TYPES:
            assert "trace_id" in EVENT_FIELDS[cls.TYPE]
            assert "span_id" in EVENT_FIELDS[cls.TYPE]
        assert "span" in EVENT_FIELDS

    def _traced_dump(self, tmp_path):
        from adversarial_spec_tpu.obs import (
            FlightRecorder,
            RequestEvent,
            SpanEvent,
            StepEvent,
        )

        r = FlightRecorder(size=64)
        r.append(
            SpanEvent(name="request", phase="begin", req_id=0,
                      trace_id="tr-001-01", span_id="tr-001-01/s00")
        )
        r.append(
            RequestEvent(req_id=0, state="queued", tokens=8,
                         trace_id="tr-001-01", span_id="tr-001-01/s00")
        )
        r.append(
            StepEvent(kind="decode", n_live=1, decode_chunk=4,
                      trace_id="tr-001-01")
        )
        r.append(
            SpanEvent(name="prefill", phase="end", req_id=0, slot=1,
                      wall_s=0.25, trace_id="tr-001-01",
                      span_id="tr-001-01/s00")
        )
        r.append(
            StepEvent(kind="decode", n_live=1, decode_chunk=4,
                      trace_id="tr-002-01")
        )
        p = tmp_path / "traced.jsonl"
        r.dump_jsonl(str(p))
        return str(p)

    def test_trace_filter_scopes_the_views(self, tmp_path, capsys):
        from tools.obs_dump import main

        path = self._traced_dump(tmp_path)
        assert main([path, "--trace", "tr-001-01"]) == 0
        out = capsys.readouterr().out
        assert "4 event(s)" in out  # the tr-002-01 step is filtered
        assert main([path, "--trace", "tr-002-01"]) == 0
        assert "1 event(s)" in capsys.readouterr().out

    def test_span_rows_render_in_timeline_and_request_log(
        self, tmp_path, capsys
    ):
        from tools.obs_dump import main

        path = self._traced_dump(tmp_path)
        assert main([path, "--timeline", "--requests"]) == 0
        out = capsys.readouterr().out
        assert "request:begin" in out
        assert "prefill:end" in out
        assert "0.2500s" in out  # end rows carry the stage wall
        assert "span begin" in out  # legend documents the glyphs
        assert "span=tr-001-01/s00" in out  # request log row suffix


class TestTraceView:
    """tools/trace_view.py — per-request waterfalls + the CHECKED
    stage-wall decomposition (deeper coverage incl. corruption rides
    tests/test_trace.py with real scheduler/mock streams)."""

    def _write(self, tmp_path, events):
        import json

        p = tmp_path / "ev.jsonl"
        p.write_text(
            "".join(json.dumps(e) + "\n" for e in events),
            encoding="utf-8",
        )
        return str(p)

    def _span(self, seq, name, phase, wall=0.0, sid="tr-001-01/s00"):
        return {
            "seq": seq, "type": "span", "name": name, "phase": phase,
            "req_id": 0, "slot": 0, "wall_s": wall,
            "trace_id": "tr-001-01", "span_id": sid,
        }

    def test_consistent_stream_renders_and_exits_0(self, tmp_path, capsys):
        from tools.trace_view import main

        path = self._write(
            tmp_path,
            [
                self._span(1, "request", "begin"),
                self._span(2, "queued", "end", 0.01),
                self._span(3, "prefill", "end", 0.25),
                self._span(4, "decode", "end", 0.75),
                self._span(5, "request", "end", 1.0),
            ],
        )
        assert main([path]) == 0
        out = capsys.readouterr().out
        assert "service 1.0000s" in out
        assert "critical path: tr-001-01/s00" in out
        assert "dominant stage: decode" in out

    def test_sum_violation_exits_1(self, tmp_path, capsys):
        from tools.trace_view import main

        path = self._write(
            tmp_path,
            [
                self._span(1, "prefill", "end", 0.25),
                self._span(2, "decode", "end", 0.25),
                self._span(3, "request", "end", 1.0),
            ],
        )
        assert main([path]) == 1
        assert "DECOMPOSITION VIOLATION" in capsys.readouterr().err

    def test_open_requests_are_rendered_not_checked(self, tmp_path):
        """A request evicted mid-flight (no decode end) waterfalls as
        'open' but cannot fail the sum check — there is nothing to
        check yet."""
        from tools.trace_view import main

        path = self._write(
            tmp_path,
            [
                self._span(1, "request", "begin"),
                self._span(2, "prefill", "end", 0.25),
            ],
        )
        assert main([path]) == 0

    def test_trace_scoping_and_json_mode(self, tmp_path, capsys):
        import json as json_mod

        from tools.trace_view import main

        path = self._write(
            tmp_path,
            [
                self._span(1, "prefill", "end", 0.5),
                self._span(2, "decode", "end", 0.5),
                self._span(3, "request", "end", 1.0),
                self._span(
                    4, "request", "end", 9.0, sid="tr-002-01/s00"
                )
                | {"trace_id": "tr-002-01"},
            ],
        )
        assert main([path, "--trace", "tr-001-01", "--json"]) == 0
        data = json_mod.loads(capsys.readouterr().out)
        assert set(data["requests"]) == {"tr-001-01/s00"}
        assert data["check_problems"] == []

    def test_unreadable_input_exits_2(self, tmp_path):
        from tools.trace_view import main

        assert main([str(tmp_path / "missing.jsonl")]) == 2


class TestBenchTrend:
    """tools/bench_trend.py — the BENCH_*.json join + schema gate."""

    def _metric_file(self, tmp_path, name="BENCH_demo.json", **over):
        import json

        payload = {
            "metric": "demo_metric", "value": 1.5, "unit": "x",
            "platform": "cpu", "within_budget": True,
        }
        payload.update(over)
        for k, v in list(payload.items()):
            if v is None:
                del payload[k]
        (tmp_path / name).write_text(json.dumps(payload))
        return payload

    def test_joins_metric_and_ladder_files(self, tmp_path, capsys):
        import json

        from tools.bench_trend import main

        self._metric_file(tmp_path)
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps(
                {
                    "n": 1, "cmd": "python bench.py", "rc": 0,
                    "tail": "…",
                    "parsed": {
                        "metric": "tok_per_sec", "value": 497.9,
                        "unit": "tok/s", "platform": "tpu",
                    },
                }
            )
        )
        assert main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "demo_metric" in out and "tok_per_sec" in out
        assert "497.9" in out
        assert main(["--dir", str(tmp_path), "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert [r["mode"] for r in data["rows"]] == ["demo", "r01"]
        assert data["problems"] == []

    def test_schema_violation_fails_the_gate(self, tmp_path, capsys):
        from tools.bench_trend import main

        self._metric_file(
            tmp_path, name="BENCH_bad.json", value="fast"
        )
        assert main(["--dir", str(tmp_path)]) == 1
        err = capsys.readouterr().err
        assert "BENCH_bad.json" in err and "value" in err

    def test_successful_ladder_run_requires_parsed_payload(
        self, tmp_path, capsys
    ):
        import json

        from tools.bench_trend import main

        (tmp_path / "BENCH_r09.json").write_text(
            json.dumps({"n": 9, "cmd": "x", "rc": 0, "tail": ""})
        )
        assert main(["--dir", str(tmp_path)]) == 1
        assert "no parsed metric payload" in capsys.readouterr().err
        # A FAILED ladder run legitimately has no payload.
        (tmp_path / "BENCH_r09.json").write_text(
            json.dumps({"n": 9, "cmd": "x", "rc": 1, "tail": "boom"})
        )
        assert main(["--dir", str(tmp_path)]) == 0
        capsys.readouterr()
        # ...but a parsed payload PRESENT on a failed run must still
        # schema-validate: malformed fields are a gate failure, not a
        # crash in the renderer.
        (tmp_path / "BENCH_r09.json").write_text(
            json.dumps(
                {
                    "n": 9, "cmd": "x", "rc": 1, "tail": "boom",
                    "parsed": {
                        "metric": "m", "value": "fast", "unit": "x",
                        "platform": "cpu",
                    },
                }
            )
        )
        assert main(["--dir", str(tmp_path)]) == 1
        assert "value" in capsys.readouterr().err

    def test_committed_bench_record_is_valid(self):
        """The repo's own BENCH_* files pass the gate (this is what
        lint_all --full runs)."""
        from pathlib import Path

        from tools.bench_trend import collect

        rows, problems = collect(Path(__file__).resolve().parent.parent)
        assert problems == []
        assert len(rows) >= 8
        modes = {r["mode"] for r in rows}
        assert {"obs", "prefix", "spec", "tier"} <= modes
        assert "interleave" not in modes  # went with the legacy loop
        obs_row = next(r for r in rows if r["mode"] == "obs")
        assert obs_row["within_budget"] is True

    def test_empty_and_missing_dirs_exit_2(self, tmp_path):
        from tools.bench_trend import main

        assert main(["--dir", str(tmp_path)]) == 2
        assert main(["--dir", str(tmp_path / "nope")]) == 2


class TestMutationRun:
    """tools/mutation_run.py — mutant generation invariants (the full
    subprocess sweep runs via `python tools/mutation_run.py`; its score
    is recorded in PARITY.md, row 8)."""

    def test_every_site_yields_a_distinct_compiling_mutant(self):
        from tools.mutation_run import enumerate_mutants, make_mutant

        src = (
            "def f(a, b):\n"
            "    if a == b and a > 0:\n"
            "        return a + 1\n"
            "    return not b\n"
            "FLAG = True\n"
            "NAME = 'proto'\n"
        )
        import ast as _ast

        sites = enumerate_mutants(src)
        assert len(sites) >= 7  # ==, and, >, 0, +, 1, not, return, ...
        unparsed_original = _ast.unparse(_ast.parse(src))
        seen = set()
        for i in range(len(sites)):
            mutated, desc = make_mutant(src, i)
            compile(mutated, "<m>", "exec")
            # Same normalized form ⇒ the mutator applied nothing.
            assert mutated != unparsed_original
            seen.add(mutated)
        # Each site produces a unique mutant (collector/mutator aligned).
        assert len(seen) == len(sites)

    def test_docstrings_and_marked_lines_skipped(self):
        from tools.mutation_run import enumerate_mutants

        src = (
            '"""module docstring"""\n'
            "def f():\n"
            '    """doc"""\n'
            '    print("log line", 123)\n'
            "    return None\n"
        )
        # docstrings skipped, print( line skipped, bare return None
        # not a site:
        assert enumerate_mutants(src) == []

    def test_mutants_change_behavior(self):
        from tools.mutation_run import enumerate_mutants, make_mutant

        src = "def f(a):\n    return a == 3\n"
        sites = enumerate_mutants(src)
        outs = set()
        for i in range(len(sites)):
            mutated, _ = make_mutant(src, i)
            ns: dict = {}
            exec(compile(mutated, "<m>", "exec"), ns)
            outs.add((ns["f"](3), ns["f"](4)))
        base = (True, False)
        assert base not in outs  # every mutant diverges on some input
