"""The trace reduction: on a trace small enough to work out by hand, and on
the small recorded trace kept with the benchmark."""

import json
from pathlib import Path

import pytest

from perfbench import reduce as rd
from perfbench import reducers
from perfbench.architectures import dense

BENCH = Path(__file__).resolve().parents[2] / "perfbench"
US = 1000.0  # the trace's times are nanoseconds


def _hand_trace():
    """Two compiled programs on one device: `jit_step` (10..60 us and
    100..150 us) holding a kernel `attn` (20 us each) and a `fusion`, and
    `jit_prefill` (200..240 us). The device idles 60..100 and 150..200."""
    ops = [
        ("attn.1", 10 * US, 20 * US), ("fusion.7", 30 * US, 30 * US),
        ("attn.1", 100 * US, 20 * US), ("fusion.7", 120 * US, 30 * US),
        ("fusion.9", 200 * US, 40 * US),
    ]
    modules = [
        ("jit_step(123)", 10 * US, 50 * US), ("jit_step(123)", 100 * US, 50 * US),
        ("jit_prefill(9)", 200 * US, 40 * US),
    ]
    host = [
        ("python3", "$sched.py:10 drive", 0.0, 250 * US),
        ("python3", "$cache.py:5 insert", 62 * US, 36 * US),   # covers the first gap
        ("python3", "$sched.py:99 prepare", 150 * US, 30 * US),  # most of the second
        ("python3", "$sched.py:120 other", 185 * US, 10 * US),
    ]
    return rd.Trace(
        devices={"/device:TPU:0": {rd.OPS_LINE: ops, rd.MODULES_LINE: modules}}, host=host
    )


def test_busy_idle_and_window_by_hand():
    tr = _hand_trace()
    assert rd.busy_intervals(tr, "/device:TPU:0") == [
        (10 * US, 60 * US), (100 * US, 150 * US), (200 * US, 240 * US)]
    assert rd.busy_seconds(tr) == pytest.approx(140e-6)
    assert rd.window_of(tr) == (10 * US, 240 * US)
    assert rd.idle_share(tr) == pytest.approx(100 * (1 - 140 / 230))
    assert rd.idle_share(tr, window_s=280e-6) == pytest.approx(50.0)


def test_overlapping_operations_are_counted_once():
    tr = rd.Trace(devices={"/device:TPU:0": {rd.OPS_LINE: [
        ("a", 0.0, 10 * US), ("b", 5 * US, 10 * US), ("c", 30 * US, 5 * US)]}})
    assert rd.busy_seconds(tr) == pytest.approx(20e-6)


def test_operations_and_programs_by_pattern():
    tr = _hand_trace()
    attn = rd.select(tr, r"^attn")
    assert rd.count(attn) == 2 and rd.summed_seconds(attn) == pytest.approx(40e-6)
    steps = rd.select(tr, r"jit_step", line=rd.MODULES_LINE)
    assert rd.count(steps) == 2 and rd.summed_seconds(steps) == pytest.approx(100e-6)
    # an operation inside a compiled program: fusions within jit_step, not the prefill's
    inside = rd.select(tr, r"^fusion", within=r"jit_step")
    assert [e[0] for e in inside[0]] == ["fusion.7", "fusion.7"]
    assert rd.select(tr, r"nothing_like_it") == [[]]
    assert rd.top_ops(tr, n=2) == [["fusion.7", pytest.approx(60e-6)], ["attn.1", pytest.approx(40e-6)]]


def test_idle_gaps_go_to_the_deepest_host_frame_that_covers_them():
    gaps = dict(rd.idle_gaps(_hand_trace()))
    # 60..100: `insert` is open for 36 of its 40 us (and `drive`, the outer frame, for all)
    assert gaps["python3:_cache.py:5_insert"] == pytest.approx(40e-6)
    # 150..200: `prepare` covers 30 us of it, `other` only 10: not half
    assert gaps["python3:_sched.py:99_prepare"] == pytest.approx(50e-6)
    assert sum(gaps.values()) == pytest.approx(90e-6)


def test_a_thread_that_waits_through_a_gap_is_left_out_with_its_frames():
    tr = _hand_trace()
    # a client thread sits in recv under its own short frame through the first gap
    tr.host += [
        ("1/python3", "$loadgen.py:68 run_debate", 61 * US, 38 * US),
        ("1/python3", "$<unknown> recv", 61 * US, 38 * US),
    ]
    tr.host.sort(key=lambda e: e[2])
    gaps = dict(rd.idle_gaps(tr))
    assert gaps["python3:_cache.py:5_insert"] == pytest.approx(40e-6)
    assert not any("run_debate" in k or "recv" in k for k in gaps)


def test_devices_are_averaged_not_summed():
    tr = _hand_trace()
    tr.devices["/device:TPU:1"] = tr.devices["/device:TPU:0"]
    assert rd.busy_seconds(tr) == pytest.approx(140e-6)
    assert rd.top_ops(tr, n=1)[0][1] == pytest.approx(60e-6)


def test_readers_on_the_hand_trace():
    tr = _hand_trace()
    cfg = json.loads((BENCH / "configs/mistral-7b-int8.json").read_text())
    reading = reducers.Reading(
        window_s=230e-6, counters_start={}, counters_end={}, client={},
        token_contexts=[100] * 8, row_step_contexts=[100] * 8, prefill_spans=[], rows=4,
        config=cfg, arch=dense, quant="int8", peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=tr,
    )
    assert reducers.trace_mean_ms(reading, {"line": rd.MODULES_LINE, "pattern": "jit_step"}) == pytest.approx(0.05)
    assert reducers.trace_idle_share(reading, {}) == pytest.approx(100 * (1 - 140 / 230))
    share = reducers.least_time_share(
        reading, {"work": "paged_attention", "over": {"pattern": "^attn", "within": "jit_step"}})
    assert share == pytest.approx(100 * (800 * 131072 / 819e9) / 40e-6)
    # no such kernel in the trace: nothing to read, never a zero
    assert reducers.least_time_share(
        reading, {"work": "paged_attention", "over": {"pattern": "^gone"}}) is None


def test_a_kernel_that_streams_its_rows_kv_at_the_peak_reads_100_at_two_tokens_a_step():
    """Two steps of 4 rows, 1,000 positions each, 2 tokens a row a step. The
    kernel takes exactly the time the chip needs to read the 8 row-steps'
    keys and values once: 100%. Counted once an emitted token the same
    kernel would read 200%, which no chip can do."""
    cfg = json.loads((BENCH / "configs/mistral-7b-int8.json").read_text())
    kv_seconds = 8 * 1000 * 131072 / 819e9
    each = kv_seconds / 2 * 1e9  # nanoseconds of one step's kernel
    tr = rd.Trace(devices={"/device:TPU:0": {
        rd.OPS_LINE: [("attn.1", 0.0, each), ("attn.1", 2 * each, each)],
        rd.MODULES_LINE: [("jit_step(1)", 0.0, each), ("jit_step(1)", 2 * each, each)],
    }})
    reading = reducers.Reading(
        window_s=3 * each / 1e9, counters_start={}, counters_end={}, client={},
        token_contexts=[998, 999] * 8, row_step_contexts=[1000] * 8, prefill_spans=[], rows=4,
        config=cfg, arch=dense, quant="int8", peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=tr,
    )
    params = {"work": "paged_attention", "over": {"pattern": "^attn", "within": "jit_step"}}
    assert reducers.least_time_share(reading, params) == pytest.approx(100.0)
    per_token = sum(reading.token_contexts) * 131072 / 819e9
    assert 100 * per_token / kv_seconds == pytest.approx(200.0, rel=2e-3)
    # no verify step delivered in the window: nothing to read, never a zero
    reading.row_step_contexts = []
    assert reducers.least_time_share(reading, params) is None


def test_trim_and_json_round_trip():
    tr = _hand_trace()
    cut = rd.trim(tr, 90 * US, 160 * US)
    assert [e[0] for e in cut.devices["/device:TPU:0"][rd.OPS_LINE]] == ["attn.1", "fusion.7"]
    again = rd.Trace.from_json(json.loads(json.dumps(cut.to_json())))
    assert again.devices == cut.devices and again.host == cut.host


# -- the small recorded trace ------------------------------------------------
# perfbench/data/small_trace.json: a cut of a real trace (my chip run, PR 26:
# qwen2-7b-int8.critique, seed 2147483415, TPU v5 lite), 90 ms long: three small
# programs of the batcher's host loop, then one verify step (its scan's
# `while` whole, its inner operations up to where the cut ends).


def _recorded():
    return rd.load_json(str(BENCH / "data/small_trace.json"))


def test_recorded_trace_busy_idle_and_programs():
    tr = _recorded()
    assert list(tr.devices) == ["/device:TPU:0"]
    mods = tr.devices["/device:TPU:0"][rd.MODULES_LINE]
    assert [m[0].split("(")[0] for m in mods] == [
        "jit__squeeze", "jit__threefry_split", "jit__unstack", "jit__spec_chunk_impl"]
    assert rd.busy_seconds(tr) == pytest.approx(0.088182485, rel=1e-6)
    a, b = rd.window_of(tr)
    assert (b - a) / 1e9 == pytest.approx(0.09029463, rel=1e-6)
    assert rd.idle_share(tr) == pytest.approx(2.33917, rel=1e-5)
    # the scan's loop holds its children: it is busy time, not a top operation
    assert all(not rd.CONTAINER.match(name) for name, _ in rd.top_ops(tr, 10))
    assert rd.top_ops(tr, 1)[0] == ["paged_decode_attention_mq.29", pytest.approx(0.006568976)]


def test_recorded_trace_kernels_by_pattern_inside_the_verify_program():
    tr = _recorded()
    paged = rd.select(tr, r"^paged_decode_attention", within=r"^jit__spec_chunk_impl")
    assert rd.count(paged) == 14 and rd.summed_seconds(paged) == pytest.approx(0.022991258)
    qmm = rd.select(tr, r"^matmul_int8")
    assert rd.count(qmm) == 96 and rd.summed_seconds(qmm) == pytest.approx(0.003707142)
    staged = rd.select(tr, r"^(matmul_int8|dynamic-slice_bitcast_fusion|slice_bitcast_fusion)",
                       within=r"^jit__spec_chunk_impl")
    assert rd.summed_seconds(staged) == pytest.approx(0.020983564, rel=1e-6)
    # an operation that takes the kernel's result as an operand does not match its pattern
    assert all(e[0].startswith("paged_decode_attention") for e in paged[0])
    steps = rd.select(tr, r"^jit__spec_chunk_impl", line=rd.MODULES_LINE)
    assert rd.count(steps) == 1 and rd.summed_seconds(steps) == pytest.approx(0.089063852)


def test_recorded_trace_idle_gaps_name_host_frames_not_waiting_threads():
    tr = _recorded()
    # two Python threads share the line name: the line's index tells them apart
    assert {"8/python3", "9/python3"} <= {e[0] for e in tr.host}
    gaps = rd.idle_gaps(tr, n=5)
    names = [n for n, _ in gaps]
    assert names[:3] == ["python3:_core.py:630_bind", "python3:PjitFunction_convert_element_type_",
                         "python3:_scheduler.py:2856__prepare_spec_step"]
    assert gaps[0][1] == pytest.approx(0.000964133, rel=1e-4)
    assert not any(rd.WAITING.search(n) for n in names)


def test_instruction_text_is_reduced_to_the_operations_own_name():
    text = ("%fusion.5 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %paged_decode_attention_mq.30), "
            "kind=kLoop")
    assert rd.op_name(text) == "fusion.5"
    assert rd.op_name("jit_step(123)") == "jit_step(123)"
