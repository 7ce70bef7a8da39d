"""The whole command, rehearsed on the CPU at the tiny presets: every cell's
last line parses and carries the contract's keys, the reference agrees with
what the program served, the control and a planted fault come out as not
correct, and the command itself refuses to give a result without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one device, as on a one-chip machine: not the suite's eight virtual ones
    env.pop("XLA_FLAGS", None)
    env.pop("ADVSPEC_LOCKDEP", None)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return env


def _run(script, *args, cwd=ROOT, timeout=600, **env):
    return subprocess.run(
        [sys.executable, str(script), *map(str, args)],
        cwd=str(cwd), env={**_env(), **env}, capture_output=True, text=True, timeout=timeout,
    )


def _result(proc):
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_every_cell(cell):
    control = cell == CELLS[0]
    proc = _run(ROOT / "perfbench/rehearse.py", "--workload", cell, "--seed", 2_147_483_777,
                "--seconds", 2, "--trace", 0, "--control", int(control))
    res = _result(proc)
    assert CONTRACT_KEYS <= set(res), res.keys()
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert "memory_peak_bytes" in res["device"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # exactly the cell's end-to-end metrics, each a number with its unit
    want = {m["name"]: m["unit"] for m in BENCH["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(isinstance(v["value"], float) and v["value"] > 0 for v in res["metrics"].values())
    # the reference agrees with what the program served, at tiny size
    cmp_ = res["compared"]
    gap = cmp_["served_token_gap_over_std_max"]
    assert gap["value"] <= gap["limit"]
    assert cmp_["requests_not_served_by_batcher"]["value"] == 0
    # ... and the verdict fails only because the platform is not a TPU
    assert cmp_["platform_is_tpu"]["value"] == 1
    assert res["correct"] is False and proc.returncode == 1
    # each number compared is on the last lines of stderr, beside its limit
    assert "compared (value <= limit)" in proc.stderr.strip().splitlines()[-1]
    assert res["compiles_in_window"] == 0, "set-up leaked into the window"
    if control:
        # the reference in the precision below, in the program's place, fails the same limit
        assert res["control"]["correct"] is False
        assert res["control"]["served_token_gap_over_std_max"] > max(3 * gap["value"], gap["limit"])


def test_traced_rehearsal_reports_per_layer_metrics_only():
    cell = CELLS[-1]
    proc = _run(ROOT / "perfbench/rehearse.py", "--workload", cell, "--seed", 5,
                "--seconds", 2, "--trace", 1)
    res = _result(proc)
    names = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m or cell in m["workloads"]}
    assert set(res["metrics"]) <= names and res["metrics"]
    assert not set(res["metrics"]) & {m["name"] for m in BENCH["end_to_end"]}
    # no TPU under the profiler: every device metric is left out, none reads 0
    device_metrics = {m["name"] for m in BENCH["per_layer"] if m["source"] == "device_trace"}
    assert not set(res["metrics"]) & device_metrics
    assert "busy_s" not in res["device"]
    assert res["metrics"]["device.compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", ["alter_token", "state_unchanged", "rows_read_row_0"])
def test_a_fault_planted_under_the_timed_path_is_not_correct(fault):
    proc = _run(Path(__file__).with_name("fault_rehearsal.py"), "--workload", CELLS[0],
                "--seed", 11, "--seconds", 2, "--trace", 0, PERFBENCH_FAULT=fault)
    res = _result(proc)
    gap = res["compared"]["served_token_gap_over_std_max"]
    # everything else looks sound: served by the batcher, nothing failed
    assert res["failed"] == 0 and res["compared"]["requests_not_served_by_batcher"]["value"] == 0
    assert gap["value"] > gap["limit"], gap
    assert res["correct"] is False


def test_the_command_gives_no_result_without_a_tpu():
    proc = _run(ROOT / "perfbench/run.py", "--workload", CELLS[0], "--seed", 1,
                "--seconds", 1, "--trace", 0)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "No result" in proc.stderr


def test_the_command_gives_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path / "perfbench/run.py", "--workload", CELLS[0], "--seed", 1,
                "--seconds", 1, "--trace", 0, cwd=tmp_path)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""


def test_an_unknown_cell_is_refused():
    proc = _run(ROOT / "perfbench/run.py", "--workload", "no-such.cell", "--seed", 1,
                "--seconds", 1, "--trace", 0)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
