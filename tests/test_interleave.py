"""Drive-loop telemetry tests (engine/interleave.py).

The device-side behavior (fused dispatches, token parity) is pinned in
tests/test_scheduler.py; this file covers the process-wide accounting
contract:

- ``stalled_prefill_s + overlapped_prefill_s == prefill_time_s`` holds
  EXACTLY (the mock engine's synthetic seconds are tokens/1024 — exact
  binary fractions — so the pin is ``==``, not approx);
- the mock engine attributes request 0 of a chat batch as stalled and
  later requests as overlapped, deterministically on CPU;
- the CLI's ``--json`` carries the ``perf.interleave`` block, and the
  drive loop's deleted switches are argparse errors.
"""

import io
import json

import pytest

from adversarial_spec_tpu.engine import interleave as interleave_mod


@pytest.fixture(autouse=True)
def _spec_off_module(monkeypatch):
    """Speculation is default-on and only multiplies the jit programs
    every batcher/engine this module compiles; its subject is
    orthogonal. Spec-on coverage (incl. SpecEvents, spec chaos fuzz,
    and the obs families) lives in tests/test_spec_batcher.py."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    monkeypatch.setenv("ADVSPEC_SPECULATIVE", "0")
    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)



@pytest.fixture(autouse=True)
def _fresh_interleave_state():
    interleave_mod.reset_stats()
    yield
    interleave_mod.reset_stats()


class TestInterleaveModule:
    def test_snapshot_sum_invariant(self):
        s = interleave_mod.stats
        s.record_prefill_time(0.25, overlapped=False)
        s.record_prefill_time(0.5, overlapped=True)
        s.record_prefill_time(0.125, overlapped=True)
        snap = interleave_mod.snapshot()
        assert snap["stalled_prefill_s"] == 0.25
        assert snap["overlapped_prefill_s"] == 0.625
        assert snap["prefill_time_s"] == (
            snap["stalled_prefill_s"] + snap["overlapped_prefill_s"]
        )

    def test_reset_zeroes_in_place(self):
        s = interleave_mod.stats
        s.record_step(fused=True)
        s.record_prefill_time(1.0, overlapped=True)
        ref = interleave_mod.stats  # engines hold the object itself
        interleave_mod.reset_stats()
        assert ref.fused_steps == 0 and ref.overlapped_prefill_s == 0.0


class TestMockEngineOverlapAccounting:
    def _chat(self, n_requests):
        from adversarial_spec_tpu.engine.mock import MockEngine
        from adversarial_spec_tpu.engine.types import (
            ChatRequest,
            SamplingParams,
        )

        reqs = [
            ChatRequest(
                model="mock://critic",
                system="sys " * 40,
                user=f"opponent {i} " * 50,
            )
            for i in range(n_requests)
        ]
        return MockEngine().chat(reqs, SamplingParams())

    def test_first_request_stalled_rest_overlapped(self):
        self._chat(3)
        snap = interleave_mod.snapshot()
        # Request 0 prefilled into an empty batch; 1 and 2 rode it.
        assert snap["prefill_steps"] == 1
        assert snap["fused_steps"] == 2
        assert snap["stalled_prefill_s"] > 0
        assert snap["overlapped_prefill_s"] > 0
        # Exact, not approximate: synthetic seconds are tokens/1024.
        assert snap["prefill_time_s"] == (
            snap["stalled_prefill_s"] + snap["overlapped_prefill_s"]
        )

    def test_single_request_has_nothing_to_overlap(self):
        self._chat(1)
        snap = interleave_mod.snapshot()
        assert snap["overlapped_prefill_s"] == 0.0
        assert snap["stalled_prefill_s"] > 0


class TestCliInterleaveFlags:
    SPEC = "# S\n" + "body line\n" * 50

    def _run(self, argv, monkeypatch, capsys):
        from adversarial_spec_tpu import cli

        monkeypatch.setattr("sys.stdin", io.StringIO(self.SPEC))
        code = cli.main(argv)
        out, err = capsys.readouterr()
        return code, json.loads(out), err

    def test_json_carries_interleave_section(self, monkeypatch, capsys):
        """A mock round with TWO opponents in one chat batch: one
        stalled + one overlapped prefill, and the sum invariant holds in
        the reported JSON — deterministically on CPU."""
        code, data, _ = self._run(
            [
                "critique", "--models", "mock://critic,mock://agree",
                "--json",
            ],
            monkeypatch, capsys,
        )
        assert code == 0
        snap = data["perf"]["interleave"]
        # Counters only: the loop has no switch to report.
        assert "enabled" not in snap and "pipeline_depth" not in snap
        assert snap["prefill_steps"] == 1
        assert snap["fused_steps"] == 1
        assert snap["overlapped_prefill_s"] > 0
        assert snap["stalled_prefill_s"] + snap["overlapped_prefill_s"] == (
            snap["prefill_time_s"]
        )

    @pytest.mark.parametrize(
        "flag", [["--no-interleave"], ["--pipeline-depth", "1"]]
    )
    def test_drive_loop_flags_are_gone(self, monkeypatch, capsys, flag):
        """One drive loop: its two switches are argparse errors."""
        from adversarial_spec_tpu import cli

        monkeypatch.setattr("sys.stdin", io.StringIO(self.SPEC))
        with pytest.raises(SystemExit) as e:
            cli.main(
                [
                    "critique", "--models", "mock://critic,mock://agree",
                    "--json", *flag,
                ]
            )
        assert e.value.code == 2
        assert "unrecognized arguments" in capsys.readouterr().err
