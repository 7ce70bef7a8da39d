"""The two configurations' architecture is today's behaviour moved, not
rewritten: `architectures/dense.py` returns what the shape functions return,
its reference is `perfbench/reference.py`, and the registry entry written
for each configuration is the one that was written before the move."""

import json
from pathlib import Path

import pytest

from perfbench import manifest, reducers, reference, shapes, system
from perfbench import reduce as rd
from perfbench.architectures import dense

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"
CONFIGS = ["mistral-7b-int8", "qwen2-7b-int8"]
N_STEPS = 3

# what `reducers._work` called for each name, until the names became the architecture's
TODAY = {
    "decode": lambda r: shapes.decode_work(
        r.config, r.quant, N_STEPS, r.token_contexts, r.row_step_contexts),
    "prefill": lambda r: shapes.prefill_work(r.config, r.prefill_spans),
    "paged_attention": lambda r: shapes.paged_attention_work(
        r.config, r.token_contexts, r.row_step_contexts),
    "qmm": lambda r: shapes.qmm_work(r.config, r.quant, N_STEPS, r.rows),
}

# the entries `system.write_registry` wrote at the parent commit, copied out
ENTRIES = {
    "mistral-7b-int8": {
        "alias": "bench-model", "family": "mistral", "checkpoint": "random", "tokenizer": "",
        "size": "7b", "dtype": "bfloat16", "mesh": {"dp": 1, "tp": 1, "sp": 1}, "max_seq_len": 0,
        "n_layers": 0, "quant": "int8", "kv": "paged", "kv_dtype": "",
    },
    "qwen2-7b-int8": {
        "alias": "bench-model", "family": "qwen2", "checkpoint": "random", "tokenizer": "",
        "size": "7b", "dtype": "bfloat16", "mesh": {"dp": 1, "tp": 1, "sp": 1},
        "max_seq_len": 32768, "n_layers": 0, "quant": "int8", "kv": "paged", "kv_dtype": "",
    },
}


def _cell(config):
    bench = manifest.load_manifest(ROOT)
    name = next(w["name"] for w in bench["workloads"] if w["config"] == config)
    return manifest.load_cell(name, ROOT)


def _reading(cell):
    """One fixed reading over the recorded trace: 3 steps of 4 rows, two
    tokens a row a step, and two prompts prefilled (one from a cached prefix)."""
    tr = rd.load_json(str(BENCH / "data/small_trace.json"))
    row_steps = [5310, 5310, 5330, 5330, 5312, 5312, 5332, 5332, 5314, 5314, 5334, 5334]
    return reducers.Reading(
        window_s=0.09, counters_start={}, counters_end={}, client={},
        token_contexts=[c - k for c in row_steps for k in (2, 1)], row_step_contexts=row_steps,
        prefill_spans=[(0, 5308), (4096, 5308)], rows=4, config=cell.config, arch=cell.arch,
        quant=cell.config["serving"]["quant"], peaks=shapes.peaks_for("TPU v5 lite"), trace=tr,
    )


@pytest.mark.parametrize("kind", list(TODAY))
@pytest.mark.parametrize("config", CONFIGS)
def test_dense_work_is_the_shape_function(config, kind):
    cell = _cell(config)
    r = _reading(cell)
    want = TODAY[kind](r)
    assert want["bytes"] > 0 or want["flops"] > 0
    assert dense.work(kind, r, N_STEPS) == want
    # ... and so is the module that `load_cell` found by the configuration's model_type
    assert cell.arch.work(kind, r, float(N_STEPS)) == want


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_work_with_nothing_to_count_is_none_and_an_unknown_name_an_error(config):
    cell = _cell(config)
    r = _reading(cell)
    assert dense.work("decode", r, None) is None and dense.work("qmm", r, 0) is None
    r.token_contexts, r.row_step_contexts, r.prefill_spans = [], [], []
    assert dense.work("decode", r, N_STEPS) is None
    assert dense.work("paged_attention", r, N_STEPS) is None
    assert dense.work("prefill", r, N_STEPS) is None
    with pytest.raises(KeyError, match="moe_experts"):
        cell.arch.work("moe_experts", r, N_STEPS)


@pytest.mark.parametrize("config", CONFIGS)
def test_the_reference_and_the_weight_bytes_are_the_ones_that_stood(config):
    arch = _cell(config).arch
    assert Path(arch.__file__).name == json.loads(
        (BENCH / "configs" / f"{config}.json").read_text())["model_type"] + ".py"
    assert arch.weight_bytes is shapes.weight_bytes
    assert arch.make_weights is reference.make_weights
    assert arch.logits_for is reference.logits_for
    assert arch.work is dense.work


@pytest.mark.parametrize("config", CONFIGS)
def test_a_metric_read_through_the_cells_module_reads_what_the_shape_function_gives(config):
    cell = _cell(config)
    r = _reading(cell)
    spec = next(m for m in cell.per_layer if m["name"] == "kernel.paged_attn_roofline")
    spent = rd.summed_seconds(rd.select(
        r.trace, spec["params"]["over"]["pattern"], within=spec["params"]["over"]["within"]))
    least = sum(r.row_step_contexts) * shapes.kv_bytes_per_token(cell.config) / 819e9
    assert reducers.read_metric(spec, r) == pytest.approx(100 * least / spent)
    assert r.notes == [f"paged_attention: least {least:.4f} s (bytes-bound) over {spent:.4f} s"]


@pytest.mark.parametrize("config", CONFIGS)
def test_the_registry_entry_is_the_one_written_before(config, tmp_path):
    serving = json.loads((BENCH / "configs" / f"{config}.json").read_text())["serving"]
    entry = system.write_registry(tmp_path, serving)
    assert entry == ENTRIES[config]
    written = json.loads(
        (tmp_path / "home/.config/adversarial-spec-tpu/registry.json").read_text())
    assert written == {"bench-model": ENTRIES[config]}


def test_every_key_of_the_serving_group_reaches_the_registry_entry(tmp_path):
    serving = {"family": "mistral", "size": "7b", "quant": "int8",
               "mesh": {"dp": 1, "tp": 4, "sp": 1}, "n_experts_held": 32}
    entry = system.write_registry(tmp_path, serving)
    assert entry["mesh"] == {"dp": 1, "tp": 4, "sp": 1} and entry["n_experts_held"] == 32
    assert entry["kv"] == "paged" and entry["checkpoint"] == "random"
    with pytest.raises(KeyError):
        system.write_registry(tmp_path, {"size": "7b"})  # family and size have no default
