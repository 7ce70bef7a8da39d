"""BENCHMARK.json against the limits the driver checks before any run."""

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    # the command names no file outside `paths`
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")


def test_configs_and_cells():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        # no width may be reduced
        for k in c["reduced"]:
            assert not re.search(r"(hidden|intermediate|_dim$|_rank$|head_dim|experts_per_tok)", k)
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in names
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert {w["config"] for w in BENCH["workloads"]} == set(names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(cells) // 4)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = BENCH["end_to_end"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128
    names = [m["name"] for m in e2e + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and _line(m["layer"])
        layers.add(m["layer"])
        # every cell that reads it reports the end-to-end metric it moves
        assert m["moves"] in e2e_cells
        assert set(m.get("workloads", e2e_cells[m["moves"]])) <= e2e_cells[m["moves"]]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric, and a per-layer metric
    for cell in cells:
        assert sum(cell in c for c in e2e_cells.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in BENCH["per_layer"])
    # a kernel's roofline that moves a metric stands beside the whole step's mfu moving the same
    moved_by_roofline = {m["moves"] for m in BENCH["per_layer"] if m["name"].endswith("_roofline")}
    moved_by_mfu = {m["moves"] for m in BENCH["per_layer"] if "mfu" in m["name"]}
    assert moved_by_roofline <= moved_by_mfu


def test_files_under_paths_are_named_from_a_names_characters():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(f.relative_to(ROOT))), f
