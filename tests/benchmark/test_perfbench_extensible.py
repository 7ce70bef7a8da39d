"""A configuration, a traffic mix, a cell, a per-layer metric and an
architecture are each added by adding files and one entry: no file that is
there is edited, and no code knows a name."""

import json
import re
import shutil
from pathlib import Path

import pytest

from perfbench import manifest, reducers, traffic
from perfbench import reduce as rd

ROOT = Path(__file__).resolve().parents[2]
DATA_DIRS = ("configs", "traffic", "metrics", "limits", "architectures")


def _copy_of_the_benchmarks_data(tmp_path):
    """The benchmark's data in a temporary bench directory (the harness's
    code stays where it is), and every file of it as it was."""
    for sub in DATA_DIRS:
        shutil.copytree(ROOT / "perfbench" / sub, tmp_path / "perfbench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return {p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*") if p.is_file()}


def test_a_throw_away_config_mix_cell_and_metric_are_data_only(tmp_path):
    before = _copy_of_the_benchmarks_data(tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    # new files: a configuration with its limits, a mix, a metric
    cfg = json.loads((ROOT / "perfbench/configs/mistral-7b-int8.json").read_text())
    cfg.update(name="toy-dense", hidden_size=1024, num_hidden_layers=6, vocab_size=4096)
    (tmp_path / "perfbench/configs/toy-dense.json").write_text(json.dumps(cfg))
    shutil.copy(tmp_path / "perfbench/limits/mistral-7b-int8.json",
                tmp_path / "perfbench/limits/toy-dense.json")
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
        config=cell.config, arch=cell.arch, quant="int8", peaks=None,
    )
    for spec in cell.per_layer:
        if spec["name"].startswith("toy."):
            assert reducers.read_metric(spec, reading) == 8.0
    # the shape functions take the new configuration's sizes as data, through the
    # architecture that its `model_type` names
    from perfbench import shapes

    assert shapes.kv_bytes_per_token(cell.config) == 2 * 6 * 8 * 128 * 2
    assert cell.arch.weight_bytes(cell.config, "int8") == shapes.weight_bytes(cell.config, "int8")
    assert cell.limits["served_token_gap_over_std_max"]["limit"] == 0.35

    # nothing that was there changed
    for p, data in before.items():
        assert p.read_bytes() == data
    # and an old cell still loads from the grown manifest
    old = manifest.load_cell(bench["workloads"][0]["name"], tmp_path, tmp_path / "perfbench")
    assert "toy.streamed_per_request" not in {m["name"] for m in old.per_layer}


TOY_EXPERTS = '''"""A throw-away architecture: 64 routed experts, fixed logits."""
import numpy as np

from perfbench.architectures import dense


def _best(position, bits):
    return 10 + (position + (0 if bits == 8 else 1)) % 5


def make_weights(cfg, seed, bits):
    return {"bits": bits}


def logits_for(cfg, weights, ids, first):
    n = len(ids) - first
    out = np.zeros((n, cfg["vocab_size"]), np.float32)
    out[np.arange(n), [_best(first + i, weights["bits"]) for i in range(n)]] = 1.0
    return out


def weight_bytes(cfg, quant):
    experts = cfg["n_routed_experts"] * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    out = {"experts": cfg["num_hidden_layers"] * experts, "embed": 2 * cfg["vocab_size"] * cfg["hidden_size"]}
    out["total"] = sum(out.values())
    return out


def work(kind, r, n_steps):
    if kind == "toy_experts":
        if not n_steps:
            return None
        return {"bytes": int(n_steps) * weight_bytes(r.config, r.quant)["experts"], "flops": 0}
    return dense.work(kind, r, n_steps)
'''


def test_a_throw_away_architecture_is_files_only(tmp_path):
    """What the next `model_config` PR does: an architecture's module, a
    configuration whose keys the dense functions do not know, its limits, a
    metric file that names a work only the new module knows, and entries."""
    from perfbench import run, stats

    before = _copy_of_the_benchmarks_data(tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    tmp = tmp_path / "perfbench"

    (tmp / "architectures/toy_experts.py").write_text(TOY_EXPERTS)
    cfg = {
        "name": "toy-experts", "source": "https://example.org/toy-experts", "model_type": "toy_experts",
        "hidden_size": 512, "moe_intermediate_size": 256, "n_routed_experts": 64,
        "num_experts_per_tok": 4, "kv_lora_rank": 64, "num_hidden_layers": 3, "vocab_size": 32,
        "reduced": [], "serving": {"family": "toy", "size": "tiny", "n_experts_held": 16},
    }
    (tmp / "configs/toy-experts.json").write_text(json.dumps(cfg))
    (tmp / "limits/toy-experts.json").write_text(
        json.dumps({"served_token_gap_over_std_max": {"limit": 0.5}}))
    (tmp / "metrics/kernel.toy_experts_roofline.json").write_text(json.dumps({
        "reducer": "least_time_share",
        "params": {"work": "toy_experts",
                   "steps": {"line": rd.MODULES_LINE, "pattern": "jit__spec_chunk_impl"},
                   "over": {"pattern": "^matmul_int8", "within": "jit__spec_chunk_impl"}},
    }))
    bench["configs"].append({"name": "toy-experts", "source": cfg["source"],
                             "file": "perfbench/configs/toy-experts.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "toy-experts.critique", "config": "toy-experts",
                               "traffic": "critique", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "kernel.toy_experts_roofline", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "kernel",
                               "moves": "out_tokens_per_s", "workloads": ["toy-experts.critique"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("out_tokens_per_s", "itl_p95_ms"):
            m["workloads"].append("toy-experts.critique")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # the cell loads, and carries the module its model_type names
    cell = manifest.load_cell("toy-experts.critique", tmp_path, tmp)
    assert Path(cell.arch.__file__) == tmp / "architectures/toy_experts.py"
    assert cell.limits["served_token_gap_over_std_max"]["limit"] == 0.5
    experts = 3 * 64 * 3 * 512 * 256
    assert cell.arch.weight_bytes(cell.config, "int8") == {
        "experts": experts, "embed": 2 * 32 * 512, "total": experts + 2 * 32 * 512}

    # the metric reads a share from the new module's count, over the recorded trace
    tr = rd.load_json(str(ROOT / "perfbench/data/small_trace.json"))
    reading = reducers.Reading(
        window_s=0.09, counters_start={}, counters_end={}, client={}, token_contexts=[],
        row_step_contexts=[], prefill_spans=[], rows=4, config=cell.config, arch=cell.arch,
        quant="int8", peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, trace=tr,
    )
    spec = next(m for m in cell.per_layer if m["name"] == "kernel.toy_experts_roofline")
    spent = rd.summed_seconds(rd.select(tr, "^matmul_int8", within="jit__spec_chunk_impl"))
    # one verify step in the recorded trace: the experts' bytes once
    assert reducers.read_metric(spec, reading) == pytest.approx(100 * (experts / 819e9) / spent)
    assert reading.notes[-1].startswith("toy_experts: least ")
    # the four dense names go on to `dense`; a name no module knows is an error
    assert cell.arch.work("prefill", reading, None) is None
    with pytest.raises(KeyError):
        cell.arch.work("no_such_work", reading, 1)

    # check_outputs compares what was served against the new module's logits
    prompt = [1, 5, 6, 7]
    sound = stats.Finished("a", prompt, [10 + (3 + i) % 5 for i in range(6)], (0, 0))
    ws = stats.WindowStats(finished=[sound])
    compared, lines, extra = run.check_outputs(cell, ws, 3, control=True)
    assert compared["served_token_gap_over_std_max"] == {"value": 0.0, "limit": 0.5}
    assert "6 served tokens over 1 reference passes, 6 equal" in lines[0]
    assert extra["control"]["correct"] is False  # the module's bits=4 puts another token first
    altered = stats.Finished("b", prompt, [11] + sound.tokens[1:], (0, 1))
    compared, _, _ = run.check_outputs(cell, stats.WindowStats(finished=[altered]), 3, control=False)
    gap = compared["served_token_gap_over_std_max"]
    assert gap["value"] > gap["limit"]

    # a model_type with no file, or none at all, is a bad cell, and the path is named
    no_type = {k: v for k, v in cfg.items() if k != "model_type"}
    for bad, said in (({**cfg, "model_type": "no_such_arch"}, "architectures/no_such_arch.py"),
                      (no_type, "states no model_type")):
        (tmp / "configs/toy-experts.json").write_text(json.dumps(bad))
        with pytest.raises(manifest.ManifestError, match=said):
            manifest.load_cell("toy-experts.critique", tmp_path, tmp)
    # a file that lacks one of the four things is refused too
    (tmp / "architectures/half.py").write_text("def work(kind, r, n_steps):\n    return None\n")
    with pytest.raises(manifest.ManifestError, match="lacks make_weights, logits_for, weight_bytes"):
        manifest.load_architecture(tmp, {"name": "h", "model_type": "half"})

    # every file that was there is byte for byte what it was, and an old cell still loads
    for p, data in before.items():
        assert p.read_bytes() == data
    old = manifest.load_cell(bench["workloads"][0]["name"], tmp_path, tmp)
    assert Path(old.arch.__file__) == tmp / "architectures/mistral.py"
    assert "kernel.toy_experts_roofline" not in {m["name"] for m in old.per_layer}


def test_every_manifest_entry_has_its_files_and_they_agree():
    bench = manifest.load_manifest(ROOT)
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"], ROOT)
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    on_disk = {p.stem for p in (ROOT / "perfbench/metrics").glob("*.json")}
    read = {manifest.metric_file(ROOT / "perfbench", m["name"]).stem for m in bench["per_layer"]}
    assert read == on_disk  # every metric finds a reader, and no reader lies unused
    model_types = set()
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "perfbench/limits" / f"{c['name']}.json").is_file()
        # its architecture has its file, and the file gives what the harness asks of it
        arch = manifest.load_architecture(ROOT / "perfbench", cfg)
        assert Path(arch.__file__).stem == cfg["model_type"]
        model_types.add(cfg["model_type"])
    # no file under architectures/ is read by none: by a configuration's model_type, or by
    # a file that one reads
    arch_dir = ROOT / "perfbench/architectures"
    read, todo = set(), sorted(model_types)
    while todo:
        stem = todo.pop()
        if stem in read:
            continue
        read.add(stem)
        text = (arch_dir / f"{stem}.py").read_text()
        todo += re.findall(r"perfbench\.architectures\.(\w+)", text)
        todo += re.findall(r"from perfbench\.architectures import (\w+)", text)
    assert read == {p.stem for p in arch_dir.glob("*.py")}


def test_a_reader_that_finds_nothing_returns_nothing():
    reading = reducers.Reading(
        window_s=1.0, counters_start={}, counters_end={}, client={}, token_contexts=[],
        row_step_contexts=[], prefill_spans=[], rows=4, config={}, arch=None, quant="int8",
        peaks=None, trace=None,
    )
    bench = manifest.load_manifest(ROOT)
    for w in bench["workloads"]:
        for m in manifest.load_cell(w["name"], ROOT).per_layer:
            assert reducers.read_metric(m, reading) is None, m["name"]
