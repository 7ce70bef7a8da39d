"""A configuration, a traffic mix, a cell and a per-layer metric are each
added by adding files and one entry: no file that is there is edited, and
no code knows a name."""

import json
import shutil
from pathlib import Path

from perfbench import manifest, reducers, traffic

ROOT = Path(__file__).resolve().parents[2]


def test_a_throw_away_config_mix_cell_and_metric_are_data_only(tmp_path):
    # a copy of the benchmark's data (the harness's code stays where it is)
    for sub in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(ROOT / "perfbench" / sub, tmp_path / "perfbench" / sub)
    before = {
        p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*") if p.is_file()
    }
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    # new files: a configuration, a mix, a metric
    cfg = json.loads((ROOT / "perfbench/configs/mistral-7b-int8.json").read_text())
    cfg.update(name="toy-dense", hidden_size=1024, num_hidden_layers=6, vocab_size=4096)
    (tmp_path / "perfbench/configs/toy-dense.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "perfbench/traffic/fresh-doc.json").read_text())
    mix.update(name="three-short", clients=3, opponents=2, max_new_tokens=8)
    mix["document"]["bytes"] = 777
    (tmp_path / "perfbench/traffic/three-short.json").write_text(json.dumps(mix))
    reader = {
        "reducer": "counter_ratio",
        "params": {"num": ["stream.streamed_tokens"], "den": ["stream.requests_streamed"]},
    }
    (tmp_path / "perfbench/metrics/toy.streamed_per_request.json").write_text(json.dumps(reader))

    # one entry each, appended
    bench["configs"].append({"name": "toy-dense", "source": "https://example.org/toy",
                             "file": "perfbench/configs/toy-dense.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "toy-dense.three-short", "config": "toy-dense",
                               "traffic": "three-short", "chips": 1, "why": "t"})
    for name in ("toy.streamed_per_request", "toy.streamed_per_request.again"):
        # the second reads through the first's file, as `device.idle_share.fresh` does
        bench["per_layer"].append({"name": name, "unit": "tokens", "better": "higher",
                                   "source": "program_counter", "layer": "batcher",
                                   "moves": "ttft_mean_ms", "workloads": ["toy-dense.three-short"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "round_p50_s"):
            m["workloads"].append("toy-dense.three-short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = manifest.load_cell("toy-dense.three-short", tmp_path, tmp_path / "perfbench")
    assert cell.config["hidden_size"] == 1024 and cell.traffic["clients"] == 3
    assert {m["name"] for m in cell.end_to_end} == {"ttft_mean_ms", "round_p50_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"toy.streamed_per_request", "toy.streamed_per_request.again"} <= names
    assert "device.compiles_in_window" in names
    assert "kernel.paged_attn_roofline" not in names  # lists other cells

    # the one generator reads the new mix, the fixed readers read the new metric
    plans = traffic.plan(cell.traffic, 12345, n_debates=3)
    assert [len(p) for p in plans] == [3, 3, 3] and len(plans[0][0].spec) == 777
    reading = reducers.Reading(
        window_s=1.0,
        counters_start={"stream.streamed_tokens": 10, "stream.requests_streamed": 2},
        counters_end={"stream.streamed_tokens": 58, "stream.requests_streamed": 8},
        client={}, token_contexts=[], row_step_contexts=[], prefill_spans=[], rows=2,
        config=cell.config, quant="int8", peaks=None,
    )
    for spec in cell.per_layer:
        if spec["name"].startswith("toy."):
            assert reducers.read_metric(spec, reading) == 8.0
    # the shape functions take the new configuration's sizes as data
    from perfbench import shapes

    assert shapes.kv_bytes_per_token(cell.config) == 2 * 6 * 8 * 128 * 2

    # nothing that was there changed
    for p, data in before.items():
        assert p.read_bytes() == data
    # and an old cell still loads from the grown manifest
    old = manifest.load_cell(bench["workloads"][0]["name"], tmp_path, tmp_path / "perfbench")
    assert "toy.streamed_per_request" not in {m["name"] for m in old.per_layer}


def test_every_manifest_entry_has_its_files_and_they_agree():
    bench = manifest.load_manifest(ROOT)
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"], ROOT)
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    on_disk = {p.stem for p in (ROOT / "perfbench/metrics").glob("*.json")}
    read = {manifest.metric_file(ROOT / "perfbench", m["name"]).stem for m in bench["per_layer"]}
    assert read == on_disk  # every metric finds a reader, and no reader lies unused
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def test_a_reader_that_finds_nothing_returns_nothing():
    reading = reducers.Reading(
        window_s=1.0, counters_start={}, counters_end={}, client={}, token_contexts=[],
        row_step_contexts=[], prefill_spans=[], rows=4, config={}, quant="int8", peaks=None,
        trace=None,
    )
    bench = manifest.load_manifest(ROOT)
    for w in bench["workloads"]:
        for m in manifest.load_cell(w["name"], ROOT).per_layer:
            assert reducers.read_metric(m, reading) is None, m["name"]
