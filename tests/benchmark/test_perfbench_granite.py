"""`perfbench/architectures/granitemoehybrid.py` and its cell: the counts
against hand counts at the published sizes, the weight bytes against the
program's tree, the entries in BENCHMARK.json, the rehearsal of the cell
with both controls, and the planted faults.

What the comparison with the reference ought to catch on this
configuration and does not (both controls, and in the tiny rehearsal a hit
that restores no snapshot and rows that read row 0's pages) is marked
`xfail`: the comparison's blind spots (PERF.md section 7, "From PR 36"),
not behaviour to keep."""

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

CONFIG = "granite-4.0-h-micro-bf16"
CELL = CONFIG + ".critique"

# By hand, bfloat16 (2 B a parameter):
MAMBA_LAYER = 2048 * 8512 + 4096 * 2048  # in_proj (4096 gate + 4352 conv + 64 dt), out_proj
ATTN_LAYER = 2 * 2048 * 2048 + 2 * 2048 * 512  # q, o; k, v (8 KV heads of 64)
MLP = 3 * 2048 * 8192
EMBED = 100352 * 2048
MAMBA_SMALL = 5 * 4352 + 4096 + 3 * 64  # conv taps and bias, gated norm; dt_bias, A_log, D
STATE_ROW = 36 * (4 * 4096 * 128 + 2 * 3 * 4352)  # float32 states, bfloat16 conv windows
KV_TOKEN = 2 * 4 * 8 * 64 * 2  # keys and values, 4 layers, 8 heads of 64, bfloat16


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
        config=cell.config, quant="", notes=[], rows=4, prefill_spans=[],
        token_contexts=[5400, 5401, 5500], row_step_contexts=[5401, 5500],
        counters_start={}, counters_end={},
    )
    base.update(kw)
    return SimpleNamespace(**base)


def test_the_configuration_is_the_catalogs_row_uncut(cell, bench_copy):
    cfg = cell.config
    assert cfg["reduced"] == [] and cfg["model_type"] == "granitemoehybrid"
    published = {
        "hidden_size": 2048, "intermediate_size": 8192, "shared_intermediate_size": 8192,
        "num_hidden_layers": 40, "num_attention_heads": 32, "num_key_value_heads": 8,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 256, "vocab_size": 100352,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 8, "rms_norm_eps": 1e-05, "max_position_embeddings": 131072,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "num_local_experts": 0, "num_experts_per_tok": 0,
    }
    assert {k: cfg[k] for k in published} == published
    assert [i for i, k in enumerate(cfg["layer_types"]) if k == "attention"] == [5, 15, 25, 35]
    assert cfg["serving"] == {"family": "granitemoehybrid", "size": "h-micro", "quant": "",
                              "kv": "paged", "dtype": "bfloat16", "max_seq_len": 0}
    assert {"state_dtype", "ssm_constants", "max_seq_len", "tokenizer", "weights", "head"} <= set(
        cfg["assumed"])
    entry = next(c for c in manifest.load_manifest(bench_copy)["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json" and CONFIG == cfg["name"]
    # the program's preset is the same model
    from adversarial_spec_tpu.models.config import get_config

    m = get_config("granitemoehybrid", "h-micro")
    s = cell.arch.sizes(cfg)
    assert (m.dim, m.n_layers, m.n_heads, m.n_kv_heads, m.head_dim, m.ffn_dim, m.vocab_size) == (
        s["D"], s["L"], s["heads"], s["kv_heads"], s["hd"], s["F"], s["V"])
    assert (m.ssm.n_heads, m.ssm.head_dim, m.ssm.state_dim, m.ssm.conv_width, m.ssm.chunk) == (
        s["H"], s["P"], s["N"], s["K"], cfg["mamba_chunk_size"])
    assert (m.ssm.inner_dim, m.ssm.conv_dim, m.ssm.in_dim) == (s["inner"], s["conv"], s["in"])
    assert [("mamba" if k[0] == "ssm" else "attention") for k in m.layer_kinds] * 4 == list(s["kinds"])
    assert (m.embedding_multiplier, m.residual_multiplier, m.attn_scale, m.logits_scaling) == (
        s["emb"], s["res"], s["att"], s["logit"])
    assert m.rms_eps == s["eps"] and m.tied_embeddings and not m.rope


def test_the_entries_say_what_the_cell_reports(bench_copy):
    """The configuration's and the cell's entries, looked up by name (a
    later PR appends after them). Not the token rate: it spread by 3.05%
    and 1.06% over the two sets of six seeds, against the 2.5% a new cell
    is admitted at (PERF.md section 6, PR 36)."""
    bench = manifest.load_manifest(bench_copy)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "0 of 8 drafts" in entry["why"]
    assert entry == {**entry, "config": CONFIG, "traffic": "critique", "chips": 1}
    assert sum(c["name"] == CONFIG for c in bench["configs"]) == 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m.get("workloads", []).count(CELL) <= 1
    reported = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert all(m["moves"] in reported for m in mine.values())
    new = {n for n, m in mine.items() if m["workloads"][0] == CELL}  # the lists this PR began
    assert new == {"kernel.ssm_state_roofline", "kernel.paged_attn_roofline.itl",
                   "prefix.state_resume_share", "ssm.snapshot_bytes_share"}
    # the whole step's share stands beside the kernels' rooflines
    assert {"step.decode_mfu.itl", "step.decode_dev_ms", "batcher.tokens_per_step.itl",
            "batcher.iteration_ms", "device.idle_share.itl", "device.peak_bytes_share.itl"} <= set(mine)
    assert manifest.metric_file(bench_copy / "perfbench", "kernel.paged_attn_roofline.itl").stem == (
        "kernel.paged_attn_roofline")
    for name in ("kernel.ssm_state_roofline", "prefix.state_resume_share", "ssm.snapshot_bytes_share"):
        assert manifest.metric_file(bench_copy / "perfbench", name).stem == name


def test_pr32s_entries_hold_but_for_their_place(monkeypatch):
    """`test_perfbench_mistral4.py::test_the_new_entries_are_appended_and_say_what_the_cell_reports`
    (a standing file) pins PR 32's configuration and cell as the LAST of
    every list, and the contract has every later entry appended at the end:
    with this cell in BENCHMARK.json it fails on place alone, and
    `tests/conftest.py` marks it `xfail` for that reason. Everything else it
    asserts is held here: the same function, over the manifest cut back to
    what it was when PR 32 appended to it (whatever came after is dropped,
    so a later cell does not fail this test in its turn)."""
    from tests.benchmark import test_perfbench_mistral4 as pr32

    def upto(items, last):
        return items[: items.index(last) + 1]

    bench = manifest.load_manifest()
    bench["configs"] = upto(bench["configs"], next(c for c in bench["configs"] if c["name"] == pr32.CONFIG))
    bench["workloads"] = upto(bench["workloads"], next(w for w in bench["workloads"] if w["name"] == pr32.CELL))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if pr32.CELL in m.get("workloads", []):
            m["workloads"] = upto(m["workloads"], pr32.CELL)
    monkeypatch.setattr(pr32.manifest, "load_manifest", lambda *a: bench)
    pr32.test_the_new_entries_are_appended_and_say_what_the_cell_reports()


def test_weight_bytes_by_hand(cell):
    wb = cell.arch.weight_bytes(cell.config, "")
    assert wb["layers_matmul"] == 2 * (36 * MAMBA_LAYER + 4 * ATTN_LAYER + 40 * MLP)
    assert wb["layers_small"] == 2 * 2 * 40 * 2048 + 36 * (2 * 5 * 4352 + 2 * 4096 + 3 * 4 * 64)
    assert wb["embed"] == wb["lm_head_t"] == 2 * EMBED
    # the issue's arithmetic: 76.18 M a state-space layer, 60.82 M an attention layer
    assert round((MAMBA_LAYER + MLP + MAMBA_SMALL) / 1e6, 2) == 76.18
    assert round((ATTN_LAYER + MLP) / 1e6, 2) == 60.82
    assert wb["published_params"] == 3_191_403_008  # 6.38 GB in bfloat16
    # what the chip's tree held (my chip runs, PR 36): 6,793,847,808 B with the head's copy
    assert wb["total"] == 6_793_847_808
    with pytest.raises(NotImplementedError):
        cell.arch.weight_bytes(cell.config, "int8")


def test_weight_bytes_are_the_programs_tree_at_tiny_size(bench_copy):
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models import transformer as tf
    from adversarial_spec_tpu.models.config import get_config

    tiny = manifest.load_cell(CELL, bench_copy, rehearsal=True)
    shapes = jax.eval_shape(
        lambda: tf.init_params(jax.random.key(0), get_config("granitemoehybrid", "tiny"), jnp.bfloat16)
    )
    tree = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert tiny.arch.weight_bytes(tiny.config, "")["total"] == tree


def test_work_terms_by_hand(cell):
    work = cell.arch.work
    r = _reading(cell)
    assert STATE_ROW == 76_437_504 and KV_TOKEN == 8192
    state = work("ssm_state", r, None)
    assert state["bytes"] == 2 * 2 * STATE_ROW  # two rows' steps, each once read and once written
    assert state["flops"] == 3 * 36 * 6 * 4096 * 128
    attn = work("paged_attention", r, None)
    assert attn["bytes"] == (5401 + 5500) * KV_TOKEN
    assert attn["flops"] == (5400 + 5401 + 5500) * 4 * 32 * 64 * 4
    dec = work("decode", r, 2.0)
    wb = cell.arch.weight_bytes(cell.config, "")
    assert dec["bytes"] == (
        2 * (wb["total"] - wb["embed"]) + state["bytes"] + attn["bytes"] + 3 * (2 * 2048 + KV_TOKEN)
    )
    matmul_params = 36 * MAMBA_LAYER + 4 * ATTN_LAYER + 40 * MLP
    assert dec["flops"] == 3 * 2 * (matmul_params + EMBED) + state["flops"] + attn["flops"]
    pre = work("prefill", _reading(cell, prefill_spans=[(5120, 5308)]), None)
    assert pre["tokens"] == 188 and pre["flops"] > 188 * 2 * matmul_params
    assert work("decode", _reading(cell, token_contexts=[]), 2.0) is None
    assert work("ssm_state", _reading(cell, row_step_contexts=[]), None) is None
    with pytest.raises(KeyError):
        work("qmm", r, 2.0)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters(cell):
    """On the parent the statistics have no `resumed_tokens` and no
    `snapshot_bytes`: the readers return nothing, they do not raise."""
    specs = {m["name"]: m for m in cell.per_layer}
    empty = reducers.Reading(
        window_s=12.0, counters_start={}, counters_end={"device.memory.bytes_limit": 1.0},
        client={}, token_contexts=[], row_step_contexts=[], prefill_spans=[], rows=4,
        config=cell.config, arch=cell.arch, quant="", peaks=None,
    )
    for name in ("prefix.state_resume_share", "ssm.snapshot_bytes_share", "kernel.ssm_state_roofline"):
        assert reducers.read_metric(specs[name], empty) is None
    full = reducers.Reading(
        window_s=12.0, counters_start={"prefix.resumed_tokens": 100, "prefix.matched_tokens": 104},
        counters_end={"prefix.resumed_tokens": 10340, "prefix.matched_tokens": 10536,
                      "prefix.snapshot_bytes": 3.2e9, "device.memory.bytes_limit": 16e9},
        client={}, token_contexts=[], row_step_contexts=[], prefill_spans=[], rows=4,
        config=cell.config, arch=cell.arch, quant="", peaks=None,
    )
    assert reducers.read_metric(specs["prefix.state_resume_share"], full) == pytest.approx(98.16, abs=0.01)
    assert reducers.read_metric(specs["ssm.snapshot_bytes_share"], full) == pytest.approx(20.0)


# -- the rehearsal, its controls and the planted faults ---------------------------


def _run(bench_copy, script, *args, **env_more):
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
    )
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    equal = re.search(r"check: .* (\d+) served tokens .* (\d+) equal the reference", proc.stderr)
    return proc, json.loads(lines[-1]), (int(equal.group(2)), int(equal.group(1))) if equal else None


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


@pytest.fixture(scope="module", params=["int8_weights", "bfloat16_state"])
def control_run(bench_copy, request):
    """One rehearsal of the cell with a control read beside it: the
    reference with int8 weights (`run.py --control 1`), or the reference
    with the recurrent state held in bfloat16 (`granite_controls.py`)."""
    if request.param == "int8_weights":
        return _run(bench_copy, "perfbench/rehearse.py", "--seed", 2_147_483_777,
                    "--seconds", 2, "--trace", 0, "--control", 1)[:2]
    return _run(bench_copy, "tests/benchmark/granite_controls.py", "--rehearsal", 1,
                "--seed", 2_147_483_777, "--seconds", 2)[:2]


def test_rehearsal_of_the_cell(control_run):
    """What `test_perfbench_rehearsal.py::test_rehearsal_of_every_cell`
    holds every cell of BENCHMARK.json to."""
    proc, res = control_run
    _sound(res, proc)
    assert {k: v["unit"] for k, v in res["metrics"].items()} == {"itl_p95_ms": "ms", "setup_s": "s"}
    assert res["control"]["served_token_gap_over_std_max"] >= 0


@pytest.mark.xfail(strict=False, reason="neither control can be told from the sound program by the "
                   "served tokens' gap, here or on the chip (perfbench/limits/granite-4.0-h-micro-bf16.json): "
                   "a blind spot of the comparison (PERF.md section 7, 'From PR 36')")
def test_the_control_fails_the_limit(control_run):
    """The reference in the precision below, in the program's place, has
    to come out as not correct by the cell's limit."""
    _, res = control_run
    assert res["control"]["correct"] is False


def test_traced_rehearsal_of_the_cell_reads_the_state_counters(bench_copy):
    proc, res, _ = _run(bench_copy, "perfbench/rehearse.py", "--seed", 5, "--seconds", 2, "--trace", 1)
    bench = manifest.load_manifest(bench_copy)
    names = {m["name"] for m in bench["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    assert set(res["metrics"]) <= names
    # no TPU under the profiler: every device metric is left out, none reads 0
    assert not set(res["metrics"]) & {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    assert res["metrics"]["device.compiles_in_window"]["value"] == 0
    assert res["metrics"]["batcher.rebuilds_in_window"]["value"] == 0
    # every admission of the window resumed from a snapshot under its match
    assert 50 < res["metrics"]["prefix.state_resume_share"]["value"] <= 100
    assert res["metrics"]["batcher.tokens_per_step.itl"]["value"] >= 1
    tree = re.search(r'weight_bytes: tree=(\{.*?\}) shapes=(\{.*?\})', proc.stderr)
    assert json.loads(tree.group(1))["total"] == json.loads(tree.group(2))["total"]


_UNSEEN = "the served tokens' gap does not see it on this configuration (PERF.md section 7, 'From PR 36' (iv))"


@pytest.mark.parametrize(
    "fault", [
        "keep_rejected_state",
        pytest.param("restore_no_snapshot", marks=pytest.mark.xfail(strict=False, reason=_UNSEEN)),
        "alter_token",
        "state_unchanged",
        pytest.param("rows_read_row_0", marks=pytest.mark.xfail(strict=False, reason=_UNSEEN)),
    ])
def test_a_fault_planted_under_the_timed_path_is_not_correct(bench_copy, fault):
    """The daemon streams and finishes as ever under every one, and the
    comparison has to say not correct. It does for a verify step that
    keeps a rejected draft's state, an altered token and keys and values
    that never reach their pages. It does not for:

    - a hit that starts from an empty state instead of its snapshot, AT
      THIS TINY SIZE (in one run 60 of 64 served tokens were still the
      reference's best, the widest gap 0.09; in another all 32): the tiny
      state forgets within a hundred tokens or so and the cell's deltas are
      ~190 tokens long (tests/test_ssm.py holds the restore itself, on a
      short delta and on the logits). At the published width on the chip
      the same fault reads 0.731 and is not correct (PERF.md section 6);
    - rows that read row 0's pages (gap 0.0): the 4 attention layers have
      no position embedding and a softmax scale of 1/64, so over random
      keys every row attends almost evenly and gets the same mean of values
      from either document (0.231 on the chip: unseen there too;
      tests/test_ssm.py holds the paged attention by its logits)."""
    script = ("tests/benchmark/fault_rehearsal_granite.py"
              if fault in ("keep_rejected_state", "restore_no_snapshot")
              else "tests/benchmark/fault_rehearsal.py")
    proc, res, equal = _run(bench_copy, script, "--seed", 11, "--seconds", 2, "--trace", 0,
                            PERFBENCH_FAULT=fault)
    assert res["attempted"] > 0 and res["failed"] == 0
    gap = res["compared"]["served_token_gap_over_std_max"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"], (fault, gap, equal)
