"""`perfbench/architectures/afmoe.py` and its cell: the configuration against
the catalog's row and the program's preset, the entries in BENCHMARK.json
(looked up by name), the weight bytes and the work against hand counts at
the published sizes, the two new readers, the rehearsal of the cell with
its control, and the planted faults at tiny size.

What the comparison with the reference ought to catch of the planted faults
and does not at tiny size is marked `xfail` (not strict: a seed may catch
it): blind spots of a comparison by served tokens, not behaviour to keep.
`perfbench/limits/trinity-large-int8-ep8.json` has each fault's reading at
the cell's size on the chip; tests/test_afmoe.py fails every one by logits."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import manifest, reducers  # noqa: E402

CONFIG = "trinity-large-int8-ep8"
CELL = CONFIG + ".critique-long"

# By hand, int8 per output channel (a byte a parameter + a float32 scale a column):
ATTN = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072  # Wq, Wg; Wk, Wv; Wo
ATTN_BYTES = ATTN + 4 * (2 * 6144 + 2 * 1024 + 3072)
EXPERT = 3 * 3072 * 3072
EXPERT_BYTES = EXPERT + 4 * 3 * 3072
DENSE_FFN = 3 * 3072 * 12288
DENSE_FFN_BYTES = DENSE_FFN + 4 * (2 * 12288 + 3072)
ROUTER_BYTES = 2 * 3072 * 256  # bfloat16
NORMS = 2 * (4 * 3072 + 2 * 128)  # four sandwich norms, two head norms, bfloat16
KV_TOKEN_LAYER = 2 * 8 * 128 * 2  # keys and values, 8 heads of 128, bfloat16


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """The harness and its data in a directory of their own: a run keeps
    its run directory beside `perfbench/`, and another worker of the suite
    may be rehearsing a cell from the checkout at the same time."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests/benchmark", root / "tests/benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


@pytest.fixture(scope="module")
def cell(bench_copy):
    return manifest.load_cell(CELL, bench_copy)


def _reading(cell, **kw):
    base = dict(
        config=cell.config, quant="int8", notes=[], rows=4, prefill_spans=[],
        token_contexts=[13600, 13601, 3000], row_step_contexts=[13601, 3000],
        counters_start={}, counters_end={},
    )
    base.update(kw)
    return SimpleNamespace(**base)


def test_the_configuration_is_the_catalogs_row_but_for_the_cut(cell, bench_copy):
    cfg = cell.config
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 3072, "intermediate_size": 12288, "load_balance_coeff": 5e-05,
        "max_position_embeddings": 262144, "model_type": "afmoe",
        "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 48, "num_dense_layers": 6, "num_expert_groups": 1,
        "num_experts_per_tok": 4, "num_hidden_layers": 60, "num_key_value_heads": 8,
        "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
        "score_func": "sigmoid", "sliding_window": 4096, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 15
    # the cut: the depth this chip runs, the experts and the vocabulary rows it holds
    assert cfg["reduced"] == ["serving.n_layers", "num_experts", "vocab_size"]
    assert (cfg["num_experts"], cfg["vocab_size"]) == (32, 25024)
    dep = cfg["deployment"]
    assert (dep["num_experts_published"], dep["vocab_size_published"]) == (256, 200192)
    assert dep["vocab_size_published"] == 8 * cfg["vocab_size"]
    assert (dep["slice"], dep["chips_per_layer"], dep["pipeline_stages"]) == ("v5e-64", 8, 8)
    assert dep["layers_here"] == [0, 8, 9, 10, 11, 12, 13, 14, 15]
    assert cfg["serving"] == {
        "family": "afmoe", "size": "trinity-large", "quant": "int8", "kv": "paged",
        "dtype": "bfloat16", "max_seq_len": 32768, "n_layers": 9,
        "experts_held": [0, 32], "vocab_rows": 25024,
    }
    assert {"attention_gate", "qk_norm", "nope_on_full_layers", "router_bias", "tokenizer",
            "weights", "max_seq_len"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.load_manifest(bench_copy)["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json" and CONFIG == cfg["name"]
    # no width is cut, and none is named in `reduced`
    assert not any(re.search(r"hidden|intermediate|_dim|_rank|head_dim|per_tok", k)
                   for k in cfg["reduced"])


def test_the_programs_preset_is_the_same_model_and_the_same_cut(cell):
    from adversarial_spec_tpu.models.config import get_config

    cfg, s = cell.config, cell.arch.sizes(cell.config)
    serving = cfg["serving"]
    m = get_config(
        serving["family"], serving["size"], serving["max_seq_len"], serving["n_layers"],
        serving["experts_held"], serving["vocab_rows"],
    )
    assert (m.dim, m.n_heads, m.n_kv_heads, m.head_dim, m.ffn_dim, m.vocab_size) == (
        s["D"], s["H"], s["KV"], s["hd"], s["F"], s["V"])
    assert (m.prefix.ffn_dim, m.gated.window, m.rope_theta, m.rms_eps) == (
        s["F_dense"], s["window"], s["theta"], s["eps"])
    ex = m.experts
    assert (ex.n_routed, ex.first_held, ex.n_held, ex.top_k, ex.expert_dim, ex.n_shared) == (
        s["n_routed"], s["first"], s["held"], s["top_k"], s["F"], 1)
    assert (ex.scoring, ex.norm_topk, ex.routed_scaling, ex.bias_std) == (
        cfg["score_func"], s["route_norm"], s["route_scale"], s["bias_std"])
    assert m.scale_embeddings and m.post_norms and not m.tied_embeddings
    # `deployment.layers_here` and the registry's n_layers = 9 are the same stack
    assert [(w > 0, i < m.n_leading) for i, w in enumerate(m.layer_windows)] == list(s["layers"])
    assert [k == "swa" for k in m.layer_mixers] == [w for w, _ in s["layers"]]
    # the preset itself states the source's values, uncut
    whole = get_config("afmoe", "trinity-large")
    assert (whole.n_layers, whole.n_leading, whole.vocab_size, whole.max_seq_len) == (
        cfg["num_hidden_layers"], cfg["num_dense_layers"],
        cfg["deployment"]["vocab_size_published"], cfg["max_position_embeddings"])
    assert [("sliding_attention" if k == "swa" else "full_attention")
            for k in whole.layer_mixers] == cfg["layer_types"]


def test_the_entries_are_found_by_name_and_say_what_the_cell_reports(bench_copy):
    bench = manifest.load_manifest(bench_copy)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "host-bound" in entry["why"]
    assert entry == {**entry, "config": CONFIG, "traffic": "critique-long", "chips": 1}
    assert sum(c["name"] == CONFIG for c in bench["configs"]) == 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m.get("workloads", []).count(CELL) <= 1
    reported = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert all(m["moves"] in reported for m in mine.values())
    new = {n for n, m in mine.items() if m["workloads"][0] == CELL}  # the lists this PR began
    assert new == {"attn.window_read_share", "kv.window_dead_share"}
    for name in new:
        assert (mine[name]["source"], mine[name]["better"], mine[name]["unit"]) == (
            "program_counter", "lower", "%")
        assert manifest.metric_file(bench_copy / "perfbench", name).stem == name
    # the whole step's share and the shared kernels' rooflines stand beside them
    assert {"step.decode_mfu.itl", "step.decode_dev_ms", "batcher.tokens_per_step.itl",
            "batcher.iteration_ms", "batcher.host_step_mean_ms", "device.idle_share.itl",
            "device.peak_bytes_share.itl", "batcher.exposed_host_ms.itl", "client.itl_p50_ms",
            "client.delivery_gap_p95_ms", "batcher.rows_per_run", "batcher.sequences_per_run",
            "batcher.rebuilds_in_window", "kernel.paged_attn_roofline.itl",
            "kernel.moe_experts_roofline", "moe.tokens_per_active_expert",
            "moe.active_expert_share", "moe.imbalance"} == set(mine) - new
    mix = json.loads((bench_copy / "perfbench/traffic/critique-long.json").read_text())
    short = json.loads((bench_copy / "perfbench/traffic/critique.json").read_text())
    assert mix["document"]["bytes"] == 3 * short["document"]["bytes"] == 12288
    for key in ("clients", "opponents", "max_new_tokens", "warmup_debates", "trace_seconds",
                "check_debates", "rehearsal"):
        assert mix[key] == short[key], key


def test_weight_bytes_by_hand(cell):
    wb = cell.arch.weight_bytes(cell.config, "int8")
    assert wb["leading"] == ATTN_BYTES + DENSE_FFN_BYTES + NORMS
    assert wb["layers_matmul"] == 8 * (
        ATTN_BYTES + EXPERT_BYTES + ROUTER_BYTES + 32 * EXPERT_BYTES)
    assert wb["layers_small"] == 8 * (NORMS + 4 * 256)
    assert wb["embed"] == 2 * 25024 * 3072
    assert wb["lm_head"] == 3072 * 25024 + 4 * 25024
    # the issue's table: attention 62.9 M, an expert 28.3 M, a dense layer 176 M,
    # a routed layer 998 M parameters
    assert round(ATTN / 1e6, 1) == 62.9 and round(EXPERT / 1e6, 1) == 28.3
    assert round((ATTN + DENSE_FFN) / 1e6) == 176
    assert round((ATTN + 33 * EXPERT + 3072 * 256) / 1e6) == 998
    assert wb["total"] == 8_407_740_672  # 8.41 GB: half of the chip's 16.91


def test_weight_bytes_are_the_programs_tree_at_tiny_size(bench_copy):
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models import transformer as tf
    from adversarial_spec_tpu.models.config import get_config
    from adversarial_spec_tpu.ops import quant

    tiny = manifest.load_cell(CELL, bench_copy, rehearsal=True)
    serving = tiny.config["serving"]
    cfg = get_config("afmoe", "tiny", experts_held=serving["experts_held"],
                     vocab_rows=serving["vocab_rows"])
    shapes = jax.eval_shape(
        lambda: quant.quantize_params(
            tf.init_params(jax.random.key(0), cfg, jnp.bfloat16, expert_quant="int8")
        )
    )
    tree = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert tiny.arch.weight_bytes(tiny.config, "int8")["total"] == tree


def test_work_terms_by_hand(cell):
    work = cell.arch.work
    s = cell.arch.sizes(cell.config)
    # one windowed layer covers min(context, 4096), one global layer all of it
    assert KV_TOKEN_LAYER == 4096
    assert cell.arch.tokens_in_bounds(s, 13601) == 7 * 4096 + 2 * 13601
    assert cell.arch.tokens_in_bounds(s, 3000) == 9 * 3000
    r = _reading(cell)
    attn = work("paged_attention", r, None)
    in_bounds = (7 * 4096 + 2 * 13601) + 9 * 3000
    assert attn["bytes"] == in_bounds * KV_TOKEN_LAYER
    assert attn["flops"] == 4 * 48 * 128 * (
        (7 * 4096 + 2 * 13600) + (7 * 4096 + 2 * 13601) + 9 * 3000)
    # no routing counters: nothing to count, not zero
    assert work("moe_experts", r, None) is None and work("decode", r, 2.0) is None
    key = 'obs.advspec_moe_{}_total{{positions="emitted",program="decode"}}'
    counted = _reading(
        cell,
        counters_start={key.format("pairs"): 10, key.format("active_experts"): 4},
        counters_end={key.format("pairs"): 110, key.format("active_experts"): 44},
    )
    experts = work("moe_experts", counted, None)
    assert experts == {"bytes": 40 * EXPERT_BYTES, "flops": 100 * 2 * EXPERT}
    dec = work("decode", counted, 2.0)
    wb = cell.arch.weight_bytes(cell.config, "int8")
    per_step = wb["total"] - wb["embed"] - 8 * 32 * EXPERT_BYTES
    assert dec["bytes"] == (
        2 * per_step + experts["bytes"] + attn["bytes"] + 3 * (2 * 3072 + 9 * KV_TOKEN_LAYER)
    )
    dense_params = 9 * ATTN + DENSE_FFN + 8 * (EXPERT + 3072 * 256)
    assert dec["flops"] == (
        3 * 2 * (dense_params + 3072 * 25024) + experts["flops"] + attn["flops"]
    )
    assert work("paged_attention", _reading(cell, row_step_contexts=[]), None) is None
    with pytest.raises(KeyError):
        work("qmm", r, 2.0)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters(cell):
    """On the parent the registry has no `advspec_attn_kv_tokens_total` and
    neither gauge: the readers return nothing, they do not raise."""
    specs = {m["name"]: m for m in cell.per_layer}

    def reading(start, end):
        return reducers.Reading(
            window_s=12.0, counters_start=start, counters_end=end, client={},
            token_contexts=[], row_step_contexts=[], prefill_spans=[], rows=4,
            config=cell.config, arch=cell.arch, quant="int8", peaks=None,
        )

    for name in ("attn.window_read_share", "kv.window_dead_share"):
        assert reducers.read_metric(specs[name], reading({}, {"spec.spec_steps": 5.0})) is None
    tokens = 'obs.advspec_attn_kv_tokens_total{{bounds="{}",layers="{}"}}'
    full = reading(
        {tokens.format("in", "window"): 7 * 4096, tokens.format("all", "window"): 7 * 13500,
         tokens.format("all", "full"): 2 * 13500},
        {tokens.format("in", "window"): 3 * 7 * 4096, tokens.format("all", "window"): 3 * 7 * 13500,
         tokens.format("all", "full"): 3 * 2 * 13500,
         "obs.advspec_kv_window_dead_bytes": 1.0e9, "obs.advspec_kv_held_bytes": 2.0e9},
    )
    assert reducers.read_metric(specs["attn.window_read_share"], full) == pytest.approx(
        100 * (7 * 4096 + 2 * 13500) / (9 * 13500))
    assert reducers.read_metric(specs["kv.window_dead_share"], full) == pytest.approx(50.0)


# -- the rehearsal, its control and the planted faults ----------------------------


def _run(bench_copy, script, *args, nice=0, **env_more):
    """`nice`: the run yields the CPU to the suite's other workers (a
    planted fault's run needs no speed: the standing 2 s rehearsals of
    other files, which have to catch a request in their window, do)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device, as on a one-chip machine
    env.pop("ADVSPEC_LOCKDEP", None)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # the harness and its data from the copy, the program from the checkout
    env["PYTHONPATH"] = os.pathsep.join([str(bench_copy), str(ROOT), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env.update(env_more)
    proc = subprocess.run(
        [sys.executable, str(bench_copy / script), "--workload", CELL, *map(str, args)],
        cwd=str(bench_copy), env=env, capture_output=True, text=True, timeout=900,
        preexec_fn=(lambda: os.nice(nice)) if nice else None,
    )
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return proc, json.loads(lines[-1])


def _sound(res, proc):
    assert res["attempted"] > 0 and res["failed"] == 0
    cmp_ = res["compared"]
    gap = cmp_["served_token_gap_over_std_max"]
    assert gap["value"] <= gap["limit"]
    assert cmp_["requests_not_served_by_batcher"]["value"] == 0
    assert cmp_["platform_is_tpu"]["value"] == 1  # the verdict fails on the platform alone
    assert res["correct"] is False and proc.returncode == 1
    assert res["compiles_in_window"] == 0, "set-up leaked into the window"
    return gap


@pytest.fixture(scope="module")
def control_run(bench_copy):
    """One traced rehearsal of the cell with the int4 control read beside
    it (the untraced round, with the contract's keys and the cell's two
    end-to-end metrics, is `test_perfbench_rehearsal.py`'s, which takes
    every cell of BENCHMARK.json as a case)."""
    return _run(bench_copy, "perfbench/rehearse.py", "--seed", 5, "--seconds", 2,
                "--trace", 1, "--control", 1)


def test_rehearsal_of_the_cell(control_run):
    proc, res = control_run
    gap = _sound(res, proc)
    # the control moves the served tokens' gap, if not past the cell's limit
    assert res["control"]["served_token_gap_over_std_max"] > 3 * gap["value"]


_UNSEEN = ("the served tokens' gap does not see it at tiny size, over replies of 8 tokens "
           "(tests/test_afmoe.py fails it by logits; perfbench/limits/trinity-large-int8-ep8.json "
           "has its reading at the cell's size on the chip)")


@pytest.mark.xfail(strict=False, reason=_UNSEEN)
def test_the_control_fails_the_limit(control_run):
    """The reference in the precision below, in the program's place, has
    to come out as not correct by the cell's limit (it does on the chip:
    the limits file; at tiny size it reads 0.45)."""
    _, res = control_run
    assert res["control"]["correct"] is False


def test_traced_rehearsal_of_the_cell_reads_the_window_counters(bench_copy, control_run):
    """The two new counters come out on the CPU as numbers (they are the
    host's counts, no device is asked), every device metric is left out."""
    proc, res = control_run
    bench = manifest.load_manifest(bench_copy)
    names = {m["name"] for m in bench["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    assert set(res["metrics"]) <= names
    assert not set(res["metrics"]) & {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    assert res["metrics"]["device.compiles_in_window"]["value"] == 0
    assert res["metrics"]["batcher.rebuilds_in_window"]["value"] == 0
    assert res["metrics"]["batcher.rows_per_run"]["value"] == 4
    # 1.5k-token prompts under a window of 128, two windowed layers of four:
    # (2 x 128 + 2 c) / 4 c at c ~ 1,520 is 54%; the pool holds the same
    # prompts' pages, dead in the windowed half but for their last 128 tokens
    assert 52 < res["metrics"]["attn.window_read_share"]["value"] < 57
    assert 10 < res["metrics"]["kv.window_dead_share"]["value"] < 50
    assert 0 < res["metrics"]["moe.active_expert_share"]["value"] <= 100
    tree = re.search(r'weight_bytes: tree=(\{.*?\}) shapes=(\{.*?\})', proc.stderr)
    assert json.loads(tree.group(1)) == json.loads(tree.group(2))


@pytest.mark.parametrize("fault", [
    "window_sees_all",
    *(pytest.param(f, marks=pytest.mark.xfail(strict=False, reason=_UNSEEN))
      for f in ("full_rotates", "no_gate", "no_bias", "drop_expert")),
])
def test_a_fault_planted_in_the_layer_is_not_correct(bench_copy, fault):
    """The daemon streams and finishes as ever under every one, and the
    comparison with the reference says not correct."""
    proc, res = _run(bench_copy, "tests/benchmark/fault_rehearsal_trinity.py", "--seed", 11,
                     "--seconds", 2, "--trace", 0, nice=10, PERFBENCH_FAULT=fault)
    gap = res["compared"]["served_token_gap_over_std_max"]
    assert res["failed"] == 0 and res["compared"]["requests_not_served_by_batcher"]["value"] == 0
    assert gap["value"] > gap["limit"], gap
    assert res["correct"] is False
