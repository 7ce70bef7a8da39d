"""chip_smoke.py's contract, rehearsed where there is no chip: it runs every
phase at tiny size on the CPU, and then refuses to call that a pass."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))


def _run(args, cwd=REPO_ROOT, env=None, timeout=600):
    # Not the suite's 8 virtual devices: an empty registry mesh puts
    # leftover devices on dp, and a dp=8 model leaves the batcher.
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full_env = {**base, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(
        [sys.executable, *args],
        cwd=str(cwd),
        env=full_env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def _facts(stdout: str) -> dict:
    out = {}
    for line in stdout.splitlines():
        if line.startswith("  fact "):
            name, _, value = line[len("  fact ") :].partition(": ")
            out[name] = json.loads(value)
    return out


def _failed_checks(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.startswith("  [FAIL]")]


class TestRehearsal:
    def test_every_phase_passes_then_the_verdict_fails(self, tmp_path):
        """JAX_PLATFORMS=cpu: daemon rounds, streamed round 2 with
        prefix hits, coalesced concurrent debates riding a fused step,
        SIGTERM drain, follow-up CLI process on the same compile cache,
        kernel census — all pass; only the platform check fails, the
        exit code is non-zero, and no line says ok."""
        proc = _run(["chip_smoke.py", "--out", str(tmp_path / "out")])
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert _failed_checks(proc.stdout) == [
            "  [FAIL] the model ran on a TPU (platform 'cpu')"
        ], proc.stdout[-3000:] + proc.stderr[-2000:]
        facts = _facts(proc.stdout)
        assert set(facts["phase_walls_s"]) >= {
            "preflight", "register", "daemon_start", "round1_cold",
            "round2_and_concurrent", "daemon_stats_and_drain",
            "daemon_events", "cli_followup", "kernel_census",
        }
        assert facts["daemon.steps_by_kind"]["fused_spec"] > 0
        assert facts["daemon.steps_by_kind"]["spec"] > 0
        assert facts["daemon.device"]["platform"] == "cpu"
        # The registry lives under --out, not under the real ~/.config.
        assert facts["registry_file"].startswith(str(tmp_path / "out"))
        assert facts["registry_entry"]["kv"] == "paged"
        # One cache directory, in the checkout, hit across processes.
        assert facts["compile_cache_dir"] == str(REPO_ROOT / ".jax_cache")
        followup = facts["compile_seconds"]["cli_followup"]
        assert followup["persistent_cache_hits"] > 0
        import chip_smoke

        assert set(facts["tpu_custom_calls_by_program"]) == set(
            chip_smoke.STEP_PROGRAMS
        )
        # Every process it started is gone.
        assert not (tmp_path / "out" / "serve.sock").exists()

    def test_chips_4_shards_parameters_four_ways(self, tmp_path):
        """--chips 4 on four virtual CPU devices: only the sharded path
        and its one-device comparison run; every sharded weight puts a
        quarter of its bytes on each device; the step has collectives."""
        proc = _run(
            ["chip_smoke.py", "--chips", "4", "--out", str(tmp_path / "out")],
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        )
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert _failed_checks(proc.stdout) == [
            "  [FAIL] the model ran on a TPU (platform 'cpu')"
        ], proc.stdout[-3000:] + proc.stderr[-2000:]
        facts = _facts(proc.stdout)
        assert set(facts["phase_walls_s"]) == {"tp4", "one_device", "compare"}
        assert facts["devices"]["count"] == 4
        pb = facts["param_bytes"]
        assert pb["per_chip"] - pb["replicated"] == (
            pb["total"] - pb["replicated"]
        ) // 4
        assert sum(facts["tp4.collectives_in_compiled_step"].values()) > 0
        agree = facts["greedy_token_agreement"]
        assert agree["leading_equal_tokens"] > 0

    def test_chips_4_needs_four_devices(self, tmp_path):
        proc = _run(
            ["chip_smoke.py", "--chips", "4", "--out", str(tmp_path / "out")],
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        )
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert _failed_checks(proc.stdout) == ["  [FAIL] four devices (2)"]


class TestAlone:
    def test_fails_without_the_program(self, tmp_path):
        """In a directory that holds chip_smoke.py and nothing else of
        the repo it exits non-zero and prints no result."""
        shutil.copy(REPO_ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"],
            cwd=str(tmp_path),
            env={**env, "JAX_PLATFORMS": "cpu"},
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


class TestSeededText:
    def test_documents_come_from_the_seed(self):
        import chip_smoke

        a = chip_smoke.make_doc(0, 4096, "T")
        assert a == chip_smoke.make_doc(0, 4096, "T")
        assert a != chip_smoke.make_doc(1, 4096, "T")
        assert 4000 <= len(a) <= 4097

    def test_stays_off_jax_until_its_children_are_done(self):
        """One process owns a chip: importing the script (and its module
        level) must not import jax — only the last phase and --chips 4,
        which start no children, do."""
        code = (
            "import sys, chip_smoke; "
            "sys.exit(1 if 'jax' in sys.modules else 0)"
        )
        proc = _run(["-c", code])
        assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize(
    "gone",
    [
        "tpu_ladder.py", "tpu_session.sh", "tools/crossover_report.py",
        "tpu_results", "NOTES.md", "VERDICT.md", "CHANGELOG.md",
    ],
)
def test_superseded_launchers_and_records_are_gone(gone):
    assert not (REPO_ROOT / gone).exists()
