"""utils/jaxenv.py: one compile cache that can be placed from outside, no
hidden fallbacks at start-up, and the process's device report."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

_PROBE = """
import json, sys
sys.path.insert(0, {repo!r})
from adversarial_spec_tpu.utils import jaxenv
before = jaxenv.device_report()
jaxenv.configure_jax()
import jax
print(json.dumps({{
    "before": before,
    "config_dir": getattr(jax.config, "jax_compilation_" + "cache_dir"),
    "reported_dir": jaxenv.compile_cache_dir(),
    "report": jaxenv.device_report(),
}}))
"""


def _probe(cwd, **env_overrides) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE.format(repo=str(REPO_ROOT))],
        cwd=str(cwd),
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_is_used_untouched(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and
    configure_jax sets no other directory."""
    placed = str(tmp_path / "placed-cache")
    got = _probe(tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    assert got["config_dir"] == placed
    assert got["reported_dir"] == placed
    assert got["report"]["compile"]["cache_dir"] == placed


def test_default_cache_dir_is_one_path_inside_the_checkout(tmp_path):
    """Unset: the same in-checkout directory whatever the working
    directory and the home directory are."""
    a = _probe(tmp_path, HOME=str(tmp_path / "home-a"))
    b = _probe(REPO_ROOT / "tests", HOME=str(tmp_path / "home-b"))
    assert a["config_dir"] == b["config_dir"] == str(REPO_ROOT / ".jax_cache")
    assert a["reported_dir"] == a["config_dir"]


def test_default_cache_dir_is_ignored_by_git():
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_the_cache_dir_is_configured_at_one_site():
    """Grepping the code for jax's cache-directory option shows one
    site (the needle is spelled in two halves so this file is not one)."""
    needle = "compilation_" + "cache_dir"
    sites = [
        str(p.relative_to(REPO_ROOT))
        for root in ("adversarial_spec_tpu", "tools", "tests")
        for p in (REPO_ROOT / root).rglob("*.py")
        if needle in p.read_text()
    ] + [
        name
        for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py")
        if needle in (REPO_ROOT / name).read_text()
    ]
    assert sites == ["adversarial_spec_tpu/utils/jaxenv.py"]


def test_device_report_names_the_device_and_counts_compiles(tmp_path):
    got = _probe(tmp_path)
    assert got["before"] is None  # never configured: jax not imported
    report = got["report"]
    assert report["platform"] == "cpu" and report["count"] >= 1
    assert isinstance(report["kind"], str) and report["kind"]
    assert set(report["compile"]) == {
        "backend_compiles", "backend_compile_s",
        "persistent_cache_hits", "persistent_cache_misses", "cache_dir",
    }


def test_no_start_up_fallbacks_in_the_package():
    """No try/except around the jax import or its options, no branch on
    what this jax happens to have, no deprecated shard_map import."""
    src = (REPO_ROOT / "adversarial_spec_tpu/utils/jaxenv.py").read_text()
    assert "except" not in src
    offenders = []
    for p in (REPO_ROOT / "adversarial_spec_tpu").rglob("*.py"):
        text = p.read_text()
        if re.search(r"hasattr\(jax\b|jax\.experimental\.shard_map", text):
            offenders.append(str(p.relative_to(REPO_ROOT)))
    assert offenders == []


def test_an_accelerator_without_a_memory_limit_is_an_error(monkeypatch):
    """hbm_budget_bytes: the CPU (tests) gets a stand-in limit; any other
    platform that reports none raises instead of assuming 16 GiB."""
    import jax
    import pytest

    from adversarial_spec_tpu.engine import tpu as tpu_mod

    monkeypatch.delenv("ADVSPEC_HBM_BUDGET_BYTES", raising=False)
    assert tpu_mod.hbm_budget_bytes() == int(16 * (1 << 30) * 0.75)

    class Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "devices", lambda: [Chip(None)])
    with pytest.raises(RuntimeError, match="reports no memory limit"):
        tpu_mod.hbm_budget_bytes()
    monkeypatch.setattr(jax, "devices", lambda: [Chip({"bytes_limit": 1000})])
    assert tpu_mod.hbm_budget_bytes() == 750
