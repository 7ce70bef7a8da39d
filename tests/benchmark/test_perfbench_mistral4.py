"""`perfbench/architectures/mistral4.py`: its counts against hand counts at the
published sizes, its weight bytes against the program's tree at tiny size,
the entries of its configuration and cell in BENCHMARK.json, the rehearsal
of the cell with the control, and a fault planted under the routed layer
that must come out as not correct."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import manifest  # noqa: E402

CONFIG = "mistral-small-4-int8-ep4"
CELL = CONFIG + ".critique"
PAIRS = 'obs.advspec_moe_pairs_total{positions="emitted",program="decode"}'
READS = 'obs.advspec_moe_active_experts_total{positions="emitted",program="decode"}'


def _load(rehearsal=False):
    return manifest.load_cell(CELL, rehearsal=rehearsal)


# One layer's dense int8 matrices, by hand (values + 4 bytes a column):
DENSE_LAYER = (
    (4096 * 1024 + 4 * 1024)  # wq_a
    + (1024 * 4096 + 4 * 4096)  # wq_b: 32 heads x (64 + 64)
    + (4096 * 320 + 4 * 320)  # wkv_a: 256 + 64
    + (256 * 6144 + 4 * 6144)  # wkv_b: 32 heads x (64 + 128)
    + (4096 * 4096 + 4 * 4096)  # wo
    + 2 * (4096 * 2048 + 4 * 2048)  # the shared expert's gate and up
    + (2048 * 4096 + 4 * 4096)  # ... and down
)
ROUTER = 4096 * 128 * 2  # bfloat16, over all 128 published experts
EXPERT = 2 * (4096 * 2048 + 4 * 2048) + (2048 * 4096 + 4 * 4096)
NORMS = 2 * (4096 + 4096 + 1024 + 256)  # attn, ffn, q, kv; bfloat16
HEAD = 4096 * 32768 + 4 * 32768
LATENT = 2 * 9 * (256 + 64)  # c_kv and k_r, bfloat16, 9 layers: no padding


@pytest.fixture(scope="module")
def cell():
    return _load()


def _reading(cell, **kw):
    base = dict(
        config=cell.config, quant="int8", notes=[], rows=4, prefill_spans=[],
        token_contexts=[5400, 5401, 5500], row_step_contexts=[5401, 5500],
        counters_start={PAIRS: 100, READS: 40}, counters_end={PAIRS: 127, READS: 59},
    )
    base.update(kw)
    return SimpleNamespace(**base)


def test_the_configuration_is_the_catalogs_row_cut_as_stated(cell):
    cfg = cell.config
    assert DENSE_LAYER == 53_310_720 and EXPERT == 25_198_592 and LATENT == 5760
    # the depth as run is the serving group's (one pipeline stage of four); the
    # top-level key stays the published depth, and `reduced` names the cut by
    # the key that holds it (the configuration's file says why, under `deployment`)
    assert cfg["reduced"] == ["serving.n_layers", "n_routed_experts", "vocab_size"]
    serving = cfg["serving"]
    assert (cfg["num_hidden_layers"], serving["n_layers"]) == (36, 9)
    assert (cfg["n_routed_experts"], cfg["vocab_size"]) == (32, 32768)
    assert cell.arch.sizes(cfg)["L"] == 9
    dep = cfg["deployment"]
    assert (dep["n_routed_experts_published"], dep["vocab_size_published"]) == (128, 131072)
    assert dep["pipeline_stages"] * dep["chips_per_layer"] == 16
    assert dep["pipeline_stages"] * serving["n_layers"] == cfg["num_hidden_layers"]
    # floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert serving["n_layers"] >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= dep["vocab_size_published"]
    assert serving["experts_held"] == [0, 32] and serving["vocab_rows"] == 32768
    assert serving["family"] == "mistral4"
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json" and CONFIG == cfg["name"]
    # the program's preset is the same model
    from adversarial_spec_tpu.models.config import get_config

    m = get_config("mistral4", "small-119b", n_layers=9, experts_held=(0, 32), vocab_rows=32768)
    la, ex = m.latent, m.experts
    assert (m.dim, m.n_heads, la.q_rank, la.kv_rank, la.nope_dim, la.rope_dim, la.v_dim) == (
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    assert (ex.n_routed, ex.top_k, ex.expert_dim, ex.n_shared, ex.held) == (
        128, cfg["num_experts_per_tok"], cfg["moe_intermediate_size"], cfg["n_shared_experts"], (0, 32))
    rp = cfg["rope_parameters"]
    y = la.yarn
    assert (y.factor, y.original_max, y.beta_fast, y.beta_slow, y.query_scaling_beta) == (
        rp["factor"], rp["original_max_position_embeddings"], rp["beta_fast"], rp["beta_slow"],
        rp["llama_4_scaling_beta"])
    assert m.rms_eps == cfg["rms_norm_eps"] and m.rope_theta == rp["rope_theta"]


def test_the_new_entries_are_appended_and_say_what_the_cell_reports():
    """`test_perfbench_manifest.py` holds the whole manifest to its rules;
    here: the configuration and the cell stand last, the cell's name is the
    last of every list it was appended to, and the cell reports the gap's
    tail and set-up, the whole step's share and both new kernels' rooflines.
    Not the token rate: it rides this model's bursty acceptance of drafts
    and spread by 4.6-6.8% over six seeds, against the 2.5% a new cell is
    admitted at (PERF.md section 6, PR 32); `batcher.tokens_per_step.itl`
    and `batcher.iteration_ms` show its two factors in every traced run."""
    bench = manifest.load_manifest()
    cfg = bench["configs"][-1]
    assert cfg["name"] == CONFIG and bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "config": CONFIG,
                                      "traffic": "critique", "chips": 1}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
    reported = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert all(m["moves"] in reported for m in mine.values())
    new = {n for n, m in mine.items() if m["workloads"] == [CELL]}
    assert new == {"kernel.latent_attn_roofline", "kernel.moe_experts_roofline",
                   "moe.tokens_per_active_expert", "moe.active_expert_share", "moe.imbalance",
                   "step.decode_mfu.itl", "batcher.tokens_per_step.itl",
                   "batcher.exposed_host_ms.itl", "device.idle_share.itl",
                   "device.peak_bytes_share.itl"}
    # a name with a further dotted part reads through the standing metric's file
    for name in new:
        if name.endswith(".itl"):
            assert manifest.metric_file(ROOT / "perfbench", name).stem == name[: -len(".itl")]
    assert {"step.decode_dev_ms", "batcher.iteration_ms", "client.delivery_gap_p95_ms"} <= set(mine)
    assert not {"kernel.paged_attn_roofline", "step.weight_path_roofline"} & set(mine)
    # no width is reduced, by the contract's own list of what a width is
    assert not any(w in k for k in cfg["reduced"]
                   for w in ("hidden_size", "intermediate", "_dim", "_rank", "experts_per_tok"))
    file_cfg = json.loads((ROOT / cfg["file"]).read_text())
    # the published numbers of the catalog row, key by key, but the reduced
    published = {
        "hidden_size": 4096, "intermediate_size": 12288, "kv_lora_rank": 256, "q_lora_rank": 1024,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "qk_head_dim": 128, "v_head_dim": 128,
        "head_dim": 128, "moe_intermediate_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_experts_per_tok": 4, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "first_k_dense_replace": 0, "max_position_embeddings": 1048576,
        "rms_norm_eps": 1e-06, "routed_scaling_factor": 1, "num_hidden_layers": 36,
    }
    assert {k: file_cfg[k] for k in published} == published
    assert file_cfg["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn",
    }


def test_weight_bytes_by_hand(cell):
    wb = cell.arch.weight_bytes(cell.config, "int8")
    assert wb["layers_matmul"] == 9 * (DENSE_LAYER + ROUTER + 32 * EXPERT) == 7_746_428_160
    assert wb["layers_small"] == 9 * NORMS
    assert wb["embed"] == 2 * 32768 * 4096 and wb["lm_head"] == HEAD
    # what the chip's tree held (my chip run, PR 32): 8,149,391,104 B, 48% of 16.91 GB
    assert wb["total"] == 8_149_391_104


def test_work_terms_by_hand(cell):
    work = cell.arch.work
    r = _reading(cell)
    pairs, reads = 27, 19
    lat = work("latent_attention", r, None)
    assert lat["bytes"] == (5401 + 5500) * LATENT
    per_position = 2 * 32 * (2 * 256 + 64) * 9  # scores on [c_kv | k_r], values on c_kv
    assert lat["flops"] == (5400 + 5401 + 5500) * per_position
    moe = work("moe_experts", r, None)
    assert moe["bytes"] == reads * EXPERT
    assert moe["flops"] == pairs * 2 * 3 * 4096 * 2048
    dec = work("decode", r, 2.0)
    per_step = 9 * (DENSE_LAYER + ROUTER) + 9 * NORMS + 2 * 4096 + HEAD
    assert dec["bytes"] == (
        2 * per_step + reads * EXPERT + (5401 + 5500) * LATENT + 3 * (2 * 4096 + LATENT)
    )
    dense_params = (
        4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096 + 3 * 4096 * 2048
        + 4096 * 128
    )
    assert dec["flops"] == (
        3 * 2 * (9 * dense_params + 4096 * 32768) + moe["flops"] + lat["flops"]
    )
    # a note beside the count: pairs against positions x layers x 4 x 32/128
    assert any(n.startswith("moe: 27 ") and "= 27" in n for n in r.notes)
    pre = work("prefill", _reading(cell, prefill_spans=[(5248, 5308)]), None)
    assert pre["tokens"] == 60 and pre["flops"] > 60 * 2 * 9 * dense_params
    with pytest.raises(KeyError):
        work("paged_attention", r, None)


def test_work_returns_nothing_where_the_program_has_no_counters(cell):
    """On a program without routing counters (the parent) the readers find
    nothing and the metrics are left out; nothing raises."""
    r = _reading(cell, counters_start={}, counters_end={})
    assert cell.arch.work("decode", r, 2.0) is None
    assert cell.arch.work("moe_experts", r, None) is None
    assert cell.arch.work("latent_attention", _reading(cell, row_step_contexts=[]), None) is None


def test_weight_bytes_equal_the_programs_tree_at_tiny_size():
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models.config import get_config
    from adversarial_spec_tpu.models.transformer import init_params
    from adversarial_spec_tpu.ops.quant import quantize_params

    cell = _load(rehearsal=True)
    s = cell.config["serving"]
    cfg = get_config("mistral4", s["size"], experts_held=s["experts_held"], vocab_rows=s["vocab_rows"])
    tree = quantize_params(
        init_params(jax.random.key(0), cfg, dtype=jnp.bfloat16, expert_quant="int8")
    )
    size = lambda node: sum(x.nbytes for x in jax.tree.leaves(node))  # noqa: E731
    wb = cell.arch.weight_bytes(cell.config, "int8")
    layers = tree["layers"]
    assert sum(size(v) for k, v in layers.items() if k.startswith("w")) == wb["layers_matmul"]
    assert sum(size(v) for k, v in layers.items() if not k.startswith("w")) == wb["layers_small"]
    assert size(tree["embed"]) == wb["embed"] and size(tree["lm_head"]) == wb["lm_head"]
    assert size(tree) == wb["total"]


@pytest.mark.parametrize("fault", [None, "an_experts_output_dropped"])
def test_an_experts_output_dropped_is_not_correct(fault):
    """The comparison that decides `correct` (`correct.compare_request`,
    `correct.verdict`, the cell's own limit) over what the program would
    serve greedily at tiny size, position by position over random tokens:
    sound, it serves the reference's best token everywhere; with the held
    expert that got the most tokens left out of every routed layer
    (`moe.group_pairs` gives its pairs no row), it is not correct. Not
    through the daemon: the tiny model's rehearsal traffic decodes to one
    repeated token whatever the experts add, so only the arithmetic tests
    can see the routed layer at this size (PERF.md, Open questions)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adversarial_spec_tpu.models import moe
    from adversarial_spec_tpu.models import transformer as tf
    from adversarial_spec_tpu.models.config import get_config
    from adversarial_spec_tpu.ops.quant import quantize_params
    from perfbench import correct

    cell = _load(rehearsal=True)
    s = cell.config["serving"]
    cfg = get_config("mistral4", s["size"], experts_held=s["experts_held"], vocab_rows=s["vocab_rows"])
    params = quantize_params(tf.init_params(jax.random.key(0), cfg, dtype=jnp.bfloat16))
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params
    )
    sound = moe.group_pairs

    def dropped(idx, ex, bm):
        dest, tile_group, n_live, counts, M = sound(idx, ex, bm)
        busiest = ex.first_held + jnp.argmax(counts)
        return jnp.where(idx == busiest, M, dest), tile_group, n_live, counts, M

    T = 104
    tokens = jax.random.randint(jax.random.key(12), (1, T), 3, 259)
    if fault:
        moe.group_pairs = dropped
    try:
        logits, _ = tf.forward(
            params, cfg, tokens, jnp.arange(T)[None],
            tf.init_cache(cfg, 1, T, dtype=jnp.float32), jnp.int32(0), jnp.ones((1, T), bool),
        )
    finally:
        moe.group_pairs = sound
    served = [int(t) for t in np.asarray(logits[0]).argmax(-1)]
    ids = [int(t) for t in tokens[0]]
    weights = cell.arch.make_weights(cell.config, 0, bits=8)
    ref_logits = cell.arch.logits_for(cell.config, weights, ids, 0)
    res = correct.compare_request(ref_logits, served)
    limit = cell.limits["served_token_gap_over_std_max"]["limit"]
    ok = correct.verdict({"gap": {"value": res["gap_max"], "limit": limit}})
    if fault:
        assert not ok and res["match"] < 0.75 * T and res["gap_max"] > 2 * limit, res
    else:
        assert ok and res["match"] == T and res["gap_max"] == 0.0, res


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """The harness and its data in a directory of their own: a run keeps its
    run directory beside `perfbench/`, and another worker of the suite may be
    rehearsing a cell from the checkout at the same time."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


def _rehearse(bench_copy, *args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one device, as on a one-chip machine: not the suite's eight virtual ones
    env.pop("XLA_FLAGS", None)
    env.pop("ADVSPEC_LOCKDEP", None)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # the harness and its data from the copy, the program from the checkout
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, str(bench_copy / "perfbench/rehearse.py"), "--workload", CELL,
         *map(str, args)],
        cwd=str(bench_copy), env=env, capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return proc, json.loads(lines[-1])


def test_rehearsal_of_the_cell_with_the_control(bench_copy):
    """What `test_perfbench_rehearsal.py::test_rehearsal_of_every_cell` holds
    every cell of BENCHMARK.json to, and the int4 control beside it."""
    proc, res = _rehearse(bench_copy, "--seed", 2_147_483_777, "--seconds", 2, "--trace", 0,
                          "--control", 1)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {k: v["unit"] for k, v in res["metrics"].items()} == {
        "itl_p95_ms": "ms", "setup_s": "s"}
    assert all(isinstance(v["value"], float) and v["value"] > 0 for v in res["metrics"].values())
    cmp_ = res["compared"]
    gap = cmp_["served_token_gap_over_std_max"]
    assert gap["value"] <= gap["limit"]
    assert cmp_["requests_not_served_by_batcher"]["value"] == 0
    assert cmp_["platform_is_tpu"]["value"] == 1  # the verdict fails on the platform alone
    assert res["correct"] is False and proc.returncode == 1
    assert res["compiles_in_window"] == 0, "set-up leaked into the window"
    assert res["control"]["correct"] is False
    assert res["control"]["served_token_gap_over_std_max"] > max(3 * gap["value"], gap["limit"])


def test_traced_rehearsal_of_the_cell_reads_the_routing_counters(bench_copy):
    _, res = _rehearse(bench_copy, "--seed", 5, "--seconds", 2, "--trace", 1)
    bench = manifest.load_manifest()
    names = {m["name"] for m in bench["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    assert set(res["metrics"]) <= names
    # no TPU under the profiler: every device metric is left out, none reads 0
    assert not set(res["metrics"]) & {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    assert res["metrics"]["device.compiles_in_window"]["value"] == 0
    assert res["metrics"]["batcher.rebuilds_in_window"]["value"] == 0
    # the step's routing counters, fetched with its counts (tiny: 2 of 4 experts held, top 2 of 4)
    assert 0 < res["metrics"]["moe.active_expert_share"]["value"] <= 100
    assert res["metrics"]["moe.tokens_per_active_expert"]["value"] >= 1
    assert res["metrics"]["moe.imbalance"]["value"] >= 1
