"""Durability tests — crash-safe round journal, mid-round resume,
per-request watchdog deadlines, hedged re-admission, and the
kill-chaos recovery contract (docs/resilience.md "Durability and
recovery").

The headline coverage: a real subprocess round SIGKILLed the moment
its 2nd opponent's journal record becomes durable, resumed in-process
— only unfinished opponents re-issue, journal-served transcripts are
byte-identical to an uninterrupted run, and the mock engine's
allocator invariants are clean post-recovery.
"""

import io
import json
import os
import signal
import subprocess
import sys
from pathlib import Path

import pytest

from adversarial_spec_tpu.debate import core
from adversarial_spec_tpu.debate import journal as journal_mod
from adversarial_spec_tpu.debate import session as session_mod
from adversarial_spec_tpu.debate.core import RoundConfig, run_round
from adversarial_spec_tpu.debate.journal import (
    JOURNAL_VERSION,
    RoundJournal,
    completion_from_record,
    spec_sha,
    validate_record,
)
from adversarial_spec_tpu.debate.session import (
    CorruptSessionState,
    SessionState,
    save_checkpoint,
)
from adversarial_spec_tpu.debate.usage import Usage
from adversarial_spec_tpu.engine.types import Completion, SamplingParams
from adversarial_spec_tpu.resilience import breaker as breaker_mod
from adversarial_spec_tpu.resilience import faults as faults_mod
from adversarial_spec_tpu.resilience import injector as injector_mod
from adversarial_spec_tpu.resilience.faults import FaultKind
from adversarial_spec_tpu.resilience.injector import FaultInjector, FaultRule

REPO = Path(__file__).resolve().parent.parent

SPEC = "# Cache Service\n\nA read-through cache with bounded staleness."


@pytest.fixture(autouse=True)
def _spec_off(monkeypatch):
    """This module pins journal/watchdog/recovery semantics; speculation
    is default-on and would only multiply the jit programs the watchdog
    batchers compile (the PR 6 suite-budget precedent). The one
    spec-on watchdog case opts back in explicitly."""
    from adversarial_spec_tpu.engine import spec as spec_mod

    prev = spec_mod.config()
    prev_enabled, prev_gamma = prev.enabled, prev.gamma
    monkeypatch.setenv("ADVSPEC_SPECULATIVE", "0")
    spec_mod.configure(enabled=False)
    yield
    spec_mod.configure(enabled=prev_enabled, gamma=prev_gamma)


def _completion(text="1. Critique.\n", out_tokens=12) -> Completion:
    return Completion(text=text, usage=Usage(output_tokens=out_tokens))


class TestJournalUnit:
    def test_append_replay_roundtrip(self):
        j = RoundJournal("t1")
        assert j.ensure_round_start(1, SPEC, ["m1", "m2"], {"doc_type": "t"})
        j.log_completion(1, 0, "m1", _completion("alpha"), 0.25)
        j.log_completion(1, 1, "m2", _completion("beta", 7), 0.5)
        served = j.replay(1, SPEC, ["m1", "m2"])
        assert sorted(served) == [0, 1]
        comp, latency = completion_from_record(served[1])
        assert comp.text == "beta"
        assert comp.usage.output_tokens == 7
        assert latency == 0.5

    def test_replay_guards_spec_hash(self):
        j = RoundJournal("t2")
        j.ensure_round_start(1, SPEC, ["m1"], {})
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        assert j.replay(1, SPEC + " REVISED", ["m1"]) == {}
        assert j.replay(2, SPEC, ["m1"]) == {}

    def test_replay_guards_model_identity(self):
        j = RoundJournal("t3")
        j.ensure_round_start(1, SPEC, ["m1", "m2"], {})
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        served = j.replay(1, SPEC, ["OTHER", "m2"])
        assert served == {}  # the model SET changed: clean full refusal

    def test_permuted_pool_serves_each_completion_to_its_model(self):
        """A resume whose opponent-pool ORDER changed (same models,
        permuted) still serves every completion — re-homed to its
        model's new index, decided by the per-index model match."""
        j = RoundJournal("t3p")
        models = ["m1", "m2", "m3"]
        j.ensure_round_start(1, SPEC, models, {})
        for i, m in enumerate(models):
            j.log_completion(1, i, m, _completion(f"text-{m}"), 0.1)
        permuted = ["m3", "m1", "m2"]
        served = j.replay(1, SPEC, permuted)
        assert sorted(served) == [0, 1, 2]
        for new_idx, model in enumerate(permuted):
            comp, _ = completion_from_record(served[new_idx])
            assert comp.text == f"text-{model}"  # the RIGHT model's text

    def test_permuted_pool_partial_records_rehome_too(self):
        """Only some opponents completed before the crash: the ones
        that did re-home; the rest re-issue at their new indices."""
        j = RoundJournal("t3q")
        j.ensure_round_start(1, SPEC, ["m1", "m2", "m3"], {})
        j.log_completion(1, 0, "m1", _completion("text-m1"), 0.1)
        j.log_completion(1, 2, "m3", _completion("text-m3"), 0.1)
        served = j.replay(1, SPEC, ["m2", "m3", "m1"])
        assert sorted(served) == [1, 2]  # m3 at 1, m1 at 2; m2 re-issues
        assert completion_from_record(served[1])[0].text == "text-m3"
        assert completion_from_record(served[2])[0].text == "text-m1"

    def test_duplicate_model_ids_keep_the_strict_index_match(self):
        """Duplicated ids make re-homing ambiguous: only records whose
        recorded index still names their model replay."""
        j = RoundJournal("t3r")
        j.ensure_round_start(1, SPEC, ["dup", "dup", "m3"], {})
        j.log_completion(1, 0, "dup", _completion("a"), 0.1)
        j.log_completion(1, 1, "dup", _completion("b"), 0.1)
        j.log_completion(1, 2, "m3", _completion("c"), 0.1)
        served = j.replay(1, SPEC, ["dup", "m3", "dup"])
        # dup@0 matches in place; m3 re-homes to 1; the second dup is
        # ambiguous (count != 1) and re-issues.
        assert sorted(served) == [0, 1]
        assert completion_from_record(served[0])[0].text == "a"
        assert completion_from_record(served[1])[0].text == "c"

    def test_changed_model_set_refuses_replay_cleanly(self):
        """A grown/shrunk/substituted pool invalidates the ROUND's
        records wholesale — no crash, no half-replay."""
        j = RoundJournal("t3s")
        j.ensure_round_start(1, SPEC, ["m1", "m2"], {})
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        j.log_completion(1, 1, "m2", _completion(), 0.1)
        assert j.replay(1, SPEC, ["m1", "m2", "m3"]) == {}  # grown
        assert j.replay(1, SPEC, ["m1"]) == {}  # shrunk
        assert j.replay(1, SPEC, ["m1", "mX"]) == {}  # substituted
        # The unchanged pool (any order) still replays everything.
        assert sorted(j.replay(1, SPEC, ["m2", "m1"])) == [0, 1]

    def test_torn_tail_tolerated(self):
        j = RoundJournal("t4")
        j.ensure_round_start(1, SPEC, ["m1"], {})
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        # A crash mid-append leaves a half-written final line.
        with open(j.path, "a") as f:
            f.write('{"v": 1, "type": "completio')
        records, skipped = j.read()
        assert [r["type"] for r in records] == ["round_start", "completion"]
        assert skipped == 1
        assert sorted(j.replay(1, SPEC, ["m1"])) == [0]

    def test_records_after_torn_tail_stay_replayable(self):
        """A realistic tear (half-written line, NO trailing newline)
        must not cost the records appended after it: the next append
        heals the tear with a leading newline, the reader skips the
        confined garbage alone, and a SECOND crash in the same round
        still replays every post-tear completion — durability does not
        silently stop at the first crash."""
        j = RoundJournal("t-torn-multi")
        j.ensure_round_start(1, SPEC, ["m1", "m2"], {})
        j.log_completion(1, 0, "m1", _completion("alpha"), 0.1)
        with open(j.path, "a") as f:
            f.write('{"v": 1, "type": "completio')  # crash: no newline
        # The resumed process re-issues the missing opponent and its
        # completion must become durable DESPITE the tear before it.
        j2 = RoundJournal("t-torn-multi")
        j2.log_completion(1, 1, "m2", _completion("beta"), 0.1)
        j2.log_round_commit(1, all_agreed=False)
        records, skipped = j.read()
        assert [r["type"] for r in records] == [
            "round_start",
            "completion",
            "completion",
            "round_commit",
        ]
        assert skipped == 1  # exactly the confined torn line
        served = j.replay(1, SPEC, ["m1", "m2"])
        assert sorted(served) == [0, 1]
        assert served[1]["text"] == "beta"

    def test_foreign_versions_interleaved_mid_stream(self):
        """Foreign-version records INTERLEAVED between valid ones are
        each skipped alone — unlike a tear, a complete append from a
        future writer does not invalidate what follows it."""
        j = RoundJournal("t-foreign-mid")
        j.ensure_round_start(1, SPEC, ["m1", "m2", "m3"], {})
        foreign = (
            json.dumps(
                {"v": JOURNAL_VERSION + 1, "type": "future", "x": 1}
            )
            + "\n"
        )
        j.log_completion(1, 0, "m1", _completion("a"), 0.1)
        with open(j.path, "a") as f:
            f.write(foreign)
        j.log_completion(1, 1, "m2", _completion("b"), 0.1)
        with open(j.path, "a") as f:
            f.write(foreign)
        j.log_completion(1, 2, "m3", _completion("c"), 0.1)
        records, skipped = j.read()
        assert skipped == 2
        assert [r["type"] for r in records] == [
            "round_start",
            "completion",
            "completion",
            "completion",
        ]
        served = j.replay(1, SPEC, ["m1", "m2", "m3"])
        assert sorted(served) == [0, 1, 2]

    def test_round_commit_torn_at_fsync_boundary(self):
        """A round_commit torn exactly at the fsync boundary (the line
        half-written, no newline durable) never became a commit: the
        reader discards it, the round's completions stay replayable,
        and a resume of the SAME round appends no new marker — it
        re-synthesizes from the journal and re-commits."""
        j = RoundJournal("t-commit-torn")
        j.ensure_round_start(1, SPEC, ["m1"], {})
        j.log_completion(1, 0, "m1", _completion("alpha"), 0.1)
        full = json.dumps(
            {"v": JOURNAL_VERSION, "type": "round_commit", "round": 1,
             "all_agreed": True}
        )
        with open(j.path, "a") as f:
            f.write(full[: len(full) // 2])  # crash mid-write, no \n
        records, skipped = j.read()
        assert [r["type"] for r in records] == [
            "round_start",
            "completion",
        ]
        assert skipped == 1
        # The resume path: same round, same spec — marker already
        # durable (no fresh truncation), completion served from the
        # journal with zero engine work, and the re-commit LANDS: the
        # append heals the newline-less tear first, so the new commit
        # sits on its own line instead of fusing into the garbage.
        j2 = RoundJournal("t-commit-torn")
        assert not j2.ensure_round_start(1, SPEC, ["m1"], {})
        served = j2.replay(1, SPEC, ["m1"])
        assert sorted(served) == [0]
        j2.log_round_commit(1, all_agreed=True)
        records, skipped = j2.read()
        assert [r["type"] for r in records] == [
            "round_start",
            "completion",
            "round_commit",
        ]
        assert records[-1]["all_agreed"] is True
        assert skipped == 1  # the confined torn half-commit

    def test_foreign_version_skipped_not_fatal(self):
        j = RoundJournal("t5")
        j.ensure_round_start(1, SPEC, ["m1"], {})
        with open(j.path, "a") as f:
            f.write(
                json.dumps(
                    {"v": JOURNAL_VERSION + 1, "type": "future", "x": 1}
                )
                + "\n"
            )
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        records, skipped = j.read()
        assert skipped == 1
        assert [r["type"] for r in records] == ["round_start", "completion"]

    def test_partial_records_never_served(self):
        j = RoundJournal("t6")
        j.ensure_round_start(1, SPEC, ["m1"], {})
        j.log_partial(
            1, 0, "m1", Completion(text="parti", error="DEADLINE_EXCEEDED")
        )
        assert j.replay(1, SPEC, ["m1"]) == {}
        records, _ = j.read()
        assert records[-1]["type"] == "partial"
        assert records[-1]["error"] == "DEADLINE_EXCEEDED"

    def test_round_start_idempotent_then_truncates_next_round(self):
        j = RoundJournal("t7")
        assert j.ensure_round_start(1, SPEC, ["m1"], {})
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        # Resume of the SAME round: no new marker, completions survive.
        assert not j.ensure_round_start(1, SPEC, ["m1"], {})
        assert sorted(j.replay(1, SPEC, ["m1"])) == [0]
        j.log_round_commit(1, all_agreed=False)
        # A NEW round truncates: the committed round's records are dead
        # weight (history lives on SessionState).
        assert j.ensure_round_start(2, "spec v2", ["m1"], {})
        records, _ = j.read()
        assert [r["type"] for r in records] == ["round_start"]
        assert records[0]["round"] == 2

    def test_multi_crash_accumulates_completions(self):
        j = RoundJournal("t8")
        j.ensure_round_start(1, SPEC, ["m1", "m2", "m3"], {})
        j.log_completion(1, 0, "m1", _completion("a"), 0.1)
        # Second process, same round: marker skipped, records append.
        j2 = RoundJournal("t8")
        j2.ensure_round_start(1, SPEC, ["m1", "m2", "m3"], {})
        j2.log_completion(1, 1, "m2", _completion("b"), 0.1)
        assert sorted(j2.replay(1, SPEC, ["m1", "m2", "m3"])) == [0, 1]

    def test_self_check_clean_and_validator_fires(self):
        assert journal_mod.self_check() == []
        good = {
            "v": JOURNAL_VERSION,
            "type": "round_commit",
            "round": 1,
            "all_agreed": True,
        }
        assert validate_record(good) == []
        assert validate_record({**good, "round": "one"})
        assert validate_record({**good, "v": 99})
        assert validate_record({**good, "mystery": 1})

    def test_fsync_events_and_metrics_emitted(self):
        from adversarial_spec_tpu import obs

        j = RoundJournal("t9")
        j.ensure_round_start(1, SPEC, ["m1"], {})
        j.log_completion(1, 0, "m1", _completion(), 0.1)
        kinds = [
            (e["op"], e["rtype"])
            for e in obs.recorder.events()
            if e["type"] == "journal"
        ]
        assert ("append", "round_start") in kinds
        assert ("append", "completion") in kinds
        snap = obs.metrics.snapshot()
        assert (
            snap.get('advspec_journal_records_total{type="completion"}', 0)
            == 1
        )
        assert snap["advspec_journal_fsync_seconds"]["count"] >= 2

    def test_journal_event_schema_validates(self):
        from adversarial_spec_tpu.obs import (
            JournalEvent,
            RecoveryEvent,
            validate_event,
        )
        from adversarial_spec_tpu.obs.events import event_to_dict

        for ev in (
            JournalEvent(op="append", rtype="completion", round_num=1),
            RecoveryEvent(round_num=1, served=2, reissued=2),
        ):
            obj = json.loads(json.dumps(event_to_dict(1, ev)))
            assert validate_event(obj) == []


class TestSessionDurability:
    def test_save_crash_window_old_file_intact_no_orphan(self, monkeypatch):
        st = SessionState(session_id="cw", spec="v1")
        path = st.save()
        before = path.read_text()
        monkeypatch.setattr(
            "os.replace",
            lambda *a: (_ for _ in ()).throw(
                OSError("crash inside the rename window")
            ),
        )
        st.spec = "v2"
        with pytest.raises(OSError):
            st.save()
        monkeypatch.undo()
        assert path.read_text() == before  # --resume still has a round
        assert not list(path.parent.glob("*.tmp"))  # no orphan tmp

    def test_checkpoint_crash_window(self, monkeypatch, tmp_path):
        path = save_checkpoint("v1", 1, "ck", checkpoints_dir=tmp_path)
        monkeypatch.setattr(
            "os.replace",
            lambda *a: (_ for _ in ()).throw(OSError("crash")),
        )
        with pytest.raises(OSError):
            save_checkpoint("v2", 1, "ck", checkpoints_dir=tmp_path)
        monkeypatch.undo()
        assert path.read_text() == "v1"
        assert not list(tmp_path.glob("*.tmp"))

    def test_load_corrupt_quarantines_with_clear_error(self):
        st = SessionState(session_id="corr", spec="v1")
        path = st.save()
        path.write_text('{"session_id": "corr", "spec": "v1", "rou')
        with pytest.raises(CorruptSessionState) as ei:
            SessionState.load("corr")
        msg = str(ei.value)
        assert str(path) in msg
        assert "quarantined" in msg
        assert "--session corr" in msg  # names the recovery option
        assert not path.exists()
        quarantine = path.with_name(path.name + ".corrupt")
        assert quarantine.exists()
        # The quarantined file does not shadow future sessions.
        assert SessionState.list_sessions() == []

    @pytest.mark.parametrize(
        "payload",
        [b'["valid", "json", "wrong", "shape"]', b"\xff\xfe garbage \x80"],
        ids=["non-object-json", "non-utf8-bytes"],
    )
    def test_load_quarantines_every_corruption_shape(self, payload):
        # Corruption is not always a JSONDecodeError: bad storage can
        # leave non-UTF-8 bytes, and a rewritten file can be valid JSON
        # of the wrong shape — all must quarantine, none may escape as
        # a raw stack trace.
        st = SessionState(session_id="corr2", spec="v1")
        path = st.save()
        path.write_bytes(payload)
        with pytest.raises(CorruptSessionState) as ei:
            SessionState.load("corr2")
        assert "quarantined" in str(ei.value)
        assert not path.exists()
        assert path.with_name(path.name + ".corrupt").exists()

    def test_cli_corrupt_resume_is_validation_error(
        self, monkeypatch, capsys
    ):
        from adversarial_spec_tpu import cli

        st = SessionState(session_id="cx", spec="v1")
        path = st.save()
        path.write_text("{torn")
        code = cli.main(["critique", "--resume", "cx"])
        _, err = capsys.readouterr()
        assert code == cli.EXIT_VALIDATION
        assert "quarantined" in err


class TestRunRoundJournal:
    def test_round_journals_start_completions(self):
        j = RoundJournal("rr1")
        cfg = RoundConfig(journal=j)
        result = run_round(SPEC, ["mock://critic?j=1", "mock://agree"], cfg=cfg)
        assert all(r.ok for r in result.responses)
        records, skipped = j.read()
        assert skipped == 0
        assert [r["type"] for r in records] == [
            "round_start",
            "completion",
            "completion",
        ]
        assert records[0]["spec_sha"] == spec_sha(SPEC)
        assert records[1]["text"] == result.responses[0].critique

    def test_resume_serves_from_journal_with_zero_engine_calls(self):
        from adversarial_spec_tpu.engine.dispatch import get_engine

        models = ["mock://critic?j=2", "mock://critic?j=3"]
        r1 = run_round(SPEC, models, cfg=RoundConfig(journal=RoundJournal("rr2")))
        engine = get_engine(models[0])
        calls_before = dict(engine._calls)
        r2 = run_round(SPEC, models, cfg=RoundConfig(journal=RoundJournal("rr2")))
        # Byte-identical service with ZERO engine work re-paid.
        assert [r.critique for r in r2.responses] == [
            r.critique for r in r1.responses
        ]
        assert engine._calls == calls_before
        assert r2.tracer.counters.get("journal.served") == 2
        assert r2.tracer.counters.get("attempts." + models[0]) is None

    def test_partial_resume_reissues_only_missing(self):
        models = ["mock://critic?j=4", "mock://critic?j=5"]
        # Simulate the crashed process: only opponent 0's record durable.
        j = RoundJournal("rr3")
        j.ensure_round_start(1, SPEC, models, {})
        j.log_completion(1, 0, models[0], _completion("from-journal"), 0.1)
        result = run_round(SPEC, models, cfg=RoundConfig(journal=RoundJournal("rr3")))
        assert result.responses[0].critique == "from-journal"
        assert result.responses[1].ok
        assert result.tracer.counters.get("journal.served") == 1
        assert result.tracer.counters.get(f"attempts.{models[1]}") == 1
        # The re-issued opponent's completion is journaled too: a second
        # crash-resume now serves BOTH.
        served = RoundJournal("rr3").replay(1, SPEC, models)
        assert sorted(served) == [0, 1]

    def test_recovery_event_reports_read_stats(self):
        from adversarial_spec_tpu import obs

        models = ["mock://critic?j=9", "mock://critic?j=10"]
        j = RoundJournal("rrev")
        j.ensure_round_start(1, SPEC, models, {})
        j.log_completion(1, 0, models[0], _completion(), 0.1)
        with open(j.path, "a") as f:
            f.write('{"v": 1, "type": "completio')  # torn tail
        run_round(SPEC, models, cfg=RoundConfig(journal=RoundJournal("rrev")))
        ev = [e for e in obs.recorder.events() if e["type"] == "recovery"]
        assert ev and ev[-1]["served"] == 1 and ev[-1]["reissued"] == 1
        # records = every readable journal record, skipped = the torn
        # line — the two fields exist to show data was discarded.
        assert ev[-1]["records"] == 2
        assert ev[-1]["skipped"] == 1

    def test_breaker_open_still_skips_on_journal_resume(self):
        """Satellite: an open circuit persisted on SessionState.breakers
        must keep skipping the failing model when the round is resumed
        from the journal — recovery must not grant a broken model a
        fresh retry ladder."""
        good, bad = "mock://critic?j=6", "mock://error"
        j = RoundJournal("rr4")
        j.ensure_round_start(1, SPEC, [good, bad], {})
        j.log_completion(1, 0, good, _completion("durable"), 0.1)
        reg = breaker_mod.BreakerRegistry(threshold=1, cooldown_s=300.0)
        reg.restore(
            {
                bad: {
                    "state": "open",
                    "failures": 3,
                    "cooldown_remaining": 300.0,
                    "last_fault": "bug",
                }
            }
        )
        result = run_round(
            SPEC,
            [good, bad],
            cfg=RoundConfig(journal=RoundJournal("rr4"), breakers=reg),
        )
        assert result.responses[0].critique == "durable"
        assert "circuit open" in result.responses[1].error
        # ZERO engine attempts anywhere: one served, one breaker-skipped.
        assert not [
            k for k in result.tracer.counters if k.startswith("attempts.")
        ]

    def test_journal_failure_contained_round_survives(self):
        # Every append faults at the crash seam: the round must resolve
        # every opponent cleanly anyway (durability lost, service kept).
        injector_mod.install(
            FaultInjector([FaultRule(kind=FaultKind.BUG, seam="crash")])
        )
        try:
            result = run_round(
                SPEC,
                ["mock://critic?j=7"],
                cfg=RoundConfig(journal=RoundJournal("rr5")),
            )
        finally:
            injector_mod.install(None)
        assert result.responses[0].ok
        assert faults_mod.snapshot().get("crash.bug", 0) >= 1
        assert RoundJournal("rr5").replay(1, SPEC, ["mock://critic?j=7"]) == {}

    @pytest.mark.chaos
    def test_crash_seam_fuzz_no_response_lost(self):
        """Random faults at the journal-append seam mid-round: every
        opponent still resolves (no response lost), and whatever subset
        of records became durable is readable and replayable."""
        import random

        models = ["mock://critic?f=1", "mock://critic?f=2", "mock://agree"]
        for seed in (0, 1, 2):
            rng = random.Random(seed)
            rules = [
                FaultRule(
                    kind=rng.choice(list(FaultKind)), seam="crash", p=0.5
                )
            ]
            injector_mod.install(FaultInjector(rules, seed=seed))
            try:
                result = run_round(
                    SPEC,
                    models,
                    cfg=RoundConfig(journal=RoundJournal(f"fz{seed}")),
                )
            finally:
                injector_mod.install(None)
            assert len(result.responses) == len(models), f"seed {seed}"
            assert all(r.ok for r in result.responses), f"seed {seed}"
            served = RoundJournal(f"fz{seed}").replay(1, SPEC, models)
            for i, rec in served.items():
                comp, _ = completion_from_record(rec)
                assert comp.text == result.responses[i].critique


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from adversarial_spec_tpu.models import transformer as T
    from adversarial_spec_tpu.models.config import get_config

    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    return cfg, params


class TestWatchdogDeadline:
    """Per-request watchdog (SchedRequest.deadline_s): one hung/slow
    request evicts as TIMEOUT through the shared _release_slot surgery
    while co-residents keep decoding."""

    def _batcher(self, tiny_model, **kw):
        from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher

        cfg, params = tiny_model
        kw.setdefault("max_batch", 2)
        kw.setdefault("max_new_cap", 64)
        kw.setdefault("chunk", 4)
        return ContinuousBatcher(params, cfg, **kw)

    @pytest.mark.parametrize("speculative", [True, False])
    def test_deadline_evicts_only_the_expired_slot(
        self, tiny_model, speculative
    ):
        """Both retirements of the drive loop: the verify step's counts
        fetch and the plain step's double buffer."""
        from adversarial_spec_tpu.engine.scheduler import SchedRequest

        b = self._batcher(tiny_model, speculative=speculative)
        total_pages = b.allocator.free_pages
        deliveries = []
        b.submit(
            SchedRequest(
                req_id=0,
                prompt_ids=[1, 2, 3, 4] * 8,
                max_new_tokens=64,
                deadline_s=0.05,
                on_tokens=lambda t: deliveries.append(len(t)) or True,
            )
        )
        b.submit(
            SchedRequest(
                req_id=1, prompt_ids=[5, 6, 7] * 8, max_new_tokens=8
            )
        )
        res = {r.req_id: r for r in b.run_all()}
        # The expired slot: TIMEOUT fault, partial tokens, no requeue.
        assert res[0].fault_kind == "timeout"
        assert "watchdog deadline" in res[0].error
        assert res[0].n_generated < 64
        # The co-resident is untouched and the pool is whole again.
        assert res[1].error is None and res[1].n_generated == 8
        b.allocator.check_invariants()
        assert b.allocator.free_pages == total_pages
        # Partial text reached the stream consumer before the evict.
        if res[0].n_generated:
            assert deliveries[-1] == res[0].n_generated
        else:
            assert not deliveries

    def test_queued_request_past_deadline_resolves(self, tiny_model):
        from adversarial_spec_tpu.engine.scheduler import SchedRequest

        b = self._batcher(tiny_model, max_batch=1, max_new_cap=32)
        b.submit(
            SchedRequest(req_id=0, prompt_ids=[1, 2, 3, 4], max_new_tokens=32)
        )
        b.submit(
            SchedRequest(
                req_id=1,
                prompt_ids=[5, 6, 7, 8],
                max_new_tokens=32,
                deadline_s=1e-6,
            )
        )
        res = {r.req_id: r for r in b.run_all()}
        assert res[0].error is None and res[0].n_generated == 32
        assert res[1].fault_kind == "timeout" and res[1].n_generated == 0
        b.allocator.check_invariants()

    def test_watchdog_fault_event_no_requeue(self, tiny_model):
        from adversarial_spec_tpu import obs
        from adversarial_spec_tpu.engine.scheduler import SchedRequest

        b = self._batcher(tiny_model)
        b.submit(
            SchedRequest(
                req_id=0,
                prompt_ids=[1, 2, 3, 4] * 4,
                max_new_tokens=64,
                deadline_s=1e-4,
            )
        )
        b.run_all()
        faults = [
            e for e in obs.recorder.events() if e["type"] == "fault"
        ]
        mine = [e for e in faults if e["seam"] == "watchdog"]
        assert mine and mine[-1]["kind"] == "timeout"
        # The budget is spent: no batcher-level requeue — the hedge is
        # the debate layer's decision.
        assert mine[-1]["requeued"] is False
        assert faults_mod.snapshot().get("watchdog.timeout", 0) >= 1

    def test_deadline_under_speculation(self, tiny_model, monkeypatch):
        from adversarial_spec_tpu.engine import spec as spec_mod
        from adversarial_spec_tpu.engine.scheduler import SchedRequest

        monkeypatch.setenv("ADVSPEC_SPECULATIVE", "1")
        spec_mod.configure(enabled=True, gamma=4)
        b = self._batcher(tiny_model, speculative=True, gamma=4)
        b.submit(
            SchedRequest(
                req_id=0,
                prompt_ids=[1, 2, 3, 4] * 8,
                max_new_tokens=64,
                deadline_s=0.05,
            )
        )
        res = b.run_all()
        assert res[0].fault_kind == "timeout"
        b.allocator.check_invariants()


class _HedgeEngine:
    """Engine fake: every request times out `fail_n` times at the
    watchdog, then succeeds. Records each call's request deadline."""

    def __init__(self, fail_n=1):
        self.fail_n = fail_n
        self.calls = []

    def chat(self, batch, params):
        self.calls.append((len(batch), params.request_deadline_s))
        if len(self.calls) <= self.fail_n:
            return [
                Completion(
                    text="1. partial cri",
                    error=(
                        "DEADLINE_EXCEEDED: per-request watchdog deadline "
                        "0.4s expired (mid-decode, req 0)"
                    ),
                    transient=True,
                )
                for _ in batch
            ]
        return [Completion(text="1. full critique") for _ in batch]

    def validate(self, model):
        return None


class TestHedgedReadmission:
    def _cfg(self, **kw):
        cfg = RoundConfig(
            sampling=SamplingParams(request_deadline_s=0.4),
            breakers=breaker_mod.BreakerRegistry(
                threshold=kw.pop("threshold", 3), cooldown_s=300.0
            ),
            **kw,
        )
        cfg.sleep = lambda s: None
        return cfg

    def test_single_hedge_with_tightened_budget(self, monkeypatch):
        eng = _HedgeEngine(fail_n=1)
        monkeypatch.setattr(core, "get_engine", lambda m: eng)
        result = run_round(SPEC, ["fake://m"], cfg=self._cfg())
        assert result.responses[0].ok
        assert result.responses[0].critique == "1. full critique"
        # Exactly one hedge, on HEDGE_BUDGET_FACTOR of the deadline.
        assert eng.calls == [(1, 0.4), (1, 0.4 * core.HEDGE_BUDGET_FACTOR)]
        assert result.tracer.counters.get("hedge.fake://m") == 1
        assert result.tracer.counters.get("attempts.fake://m") == 2

    def test_hedge_loses_keeps_original_partial_no_third_attempt(
        self, monkeypatch
    ):
        eng = _HedgeEngine(fail_n=99)
        monkeypatch.setattr(core, "get_engine", lambda m: eng)
        result = run_round(SPEC, ["fake://m"], cfg=self._cfg())
        assert len(eng.calls) == 2  # never a third
        assert "watchdog deadline" in result.responses[0].error

    def test_breaker_open_vetoes_the_hedge(self, monkeypatch):
        eng = _HedgeEngine(fail_n=99)
        monkeypatch.setattr(core, "get_engine", lambda m: eng)
        # threshold=1: the first watchdog timeout opens the circuit, so
        # the hedge must not fire at all.
        result = run_round(SPEC, ["fake://m"], cfg=self._cfg(threshold=1))
        assert len(eng.calls) == 1
        assert "watchdog deadline" in result.responses[0].error

    def test_timeout_without_deadline_takes_normal_retries(
        self, monkeypatch
    ):
        eng = _HedgeEngine(fail_n=99)
        monkeypatch.setattr(core, "get_engine", lambda m: eng)
        cfg = self._cfg()
        cfg.sampling = SamplingParams()  # request_deadline_s = 0
        result = run_round(SPEC, ["fake://m"], cfg=cfg)
        # Transient timeout without a watchdog armed: the classic
        # 3-attempt ladder, full budget each time, and the LAST
        # attempt's error is the surfaced one.
        assert [c[1] for c in eng.calls] == [0.0, 0.0, 0.0]
        assert "DEADLINE_EXCEEDED" in result.responses[0].error

    def test_deadline_evicted_partial_is_journaled(self, monkeypatch):
        eng = _HedgeEngine(fail_n=99)
        monkeypatch.setattr(core, "get_engine", lambda m: eng)
        cfg = self._cfg(journal=RoundJournal("hj"))
        run_round(SPEC, ["fake://m"], cfg=cfg)
        records, _ = RoundJournal("hj").read()
        partials = [r for r in records if r["type"] == "partial"]
        assert partials and partials[-1]["text"] == "1. partial cri"
        assert "DEADLINE_EXCEEDED" in partials[-1]["error"]


class TestKillRecoverySmoke:
    """The tier-1 kill-chaos smoke: a REAL subprocess round SIGKILLed
    the moment the 2nd opponent's record becomes durable, then resumed
    in-process (so the mock engine's allocator is reachable for the
    post-recovery invariants check)."""

    MODELS = [f"mock://critic?k={n}" for n in range(1, 5)]

    def test_sigkill_mid_round_then_resume(
        self, monkeypatch, capsys, tmp_path
    ):
        from adversarial_spec_tpu import cli
        from adversarial_spec_tpu.engine.dispatch import get_engine

        sessions = tmp_path / "sessions"
        env = {
            **os.environ,
            "PYTHONPATH": str(REPO),
            "JAX_PLATFORMS": "cpu",
            "ADVSPEC_SESSIONS_DIR": str(sessions),
            "ADVSPEC_JOURNAL_KILL_AFTER": "2",
        }
        victim = subprocess.run(
            [
                sys.executable,
                "-m",
                "adversarial_spec_tpu.cli",
                "critique",
                "--session",
                "ks",
                "--models",
                ",".join(self.MODELS),
                "--json",
            ],
            input=SPEC,
            text=True,
            capture_output=True,
            # tmp cwd: the CLI writes cwd-relative spec checkpoints,
            # which must not litter the repo (PYTHONPATH in env makes
            # the package importable from anywhere).
            cwd=tmp_path,
            env=env,
        )
        assert victim.returncode == -signal.SIGKILL, victim.stderr[-300:]
        journal = RoundJournal("ks", journal_dir=sessions)
        records, skipped = journal.read()
        assert skipped == 0
        assert [r["type"] for r in records] == [
            "round_start",
            "completion",
            "completion",
        ]

        # Resume in-process.
        monkeypatch.setattr(session_mod, "SESSIONS_DIR", sessions)
        code = cli.main(["critique", "--resume", "ks", "--json"])
        out, err = capsys.readouterr()
        assert code == 0
        assert "2 opponent(s) served from the round journal" in err
        data = json.loads(out)
        counters = data["perf"]["counters"]
        # Only unfinished opponents re-issue — no duplicated work.
        assert counters.get("debate/journal.served") == 2
        for i, model in enumerate(self.MODELS):
            want = 0 if i < 2 else 1
            assert counters.get(f"debate/attempts.{model}", 0) == want, model
        # Byte-identical to an uninterrupted run of the same round.
        reference = run_round(SPEC, list(self.MODELS), round_num=1)
        for i in range(len(self.MODELS)):
            assert (
                data["results"][i]["response"]
                == reference.responses[i].critique
            ), f"opponent {i}"
        # check_invariants clean post-recovery, and the round committed.
        engine = get_engine(self.MODELS[0])
        if engine._allocator is not None:
            engine._allocator.check_invariants()
        records, _ = journal.read()
        assert records[-1]["type"] == "round_commit"
        # No faults surfaced anywhere in the recovery round.
        assert data["perf"]["resilience"]["faults"] == {}


class TestCliJournalFlags:
    def _run(self, argv, monkeypatch, capsys, stdin=SPEC):
        from adversarial_spec_tpu import cli

        if stdin is not None:
            monkeypatch.setattr("sys.stdin", io.StringIO(stdin))
        code = cli.main(argv)
        out, err = capsys.readouterr()
        return code, out, err

    def test_journal_default_on_with_session(self, monkeypatch, capsys):
        code, _, _ = self._run(
            ["critique", "--models", "mock://critic", "--session", "cj"],
            monkeypatch,
            capsys,
        )
        assert code == 0
        assert RoundJournal("cj").path.is_file()
        records, _ = RoundJournal("cj").read()
        assert records[-1]["type"] == "round_commit"

    def test_no_journal_flag(self, monkeypatch, capsys):
        code, _, _ = self._run(
            [
                "critique",
                "--models",
                "mock://critic",
                "--session",
                "cj2",
                "--no-journal",
            ],
            monkeypatch,
            capsys,
        )
        assert code == 0
        assert not RoundJournal("cj2").path.exists()

    def test_env_default_off(self, monkeypatch, capsys):
        monkeypatch.setenv("ADVSPEC_JOURNAL", "0")
        code, _, _ = self._run(
            ["critique", "--models", "mock://critic", "--session", "cj3"],
            monkeypatch,
            capsys,
        )
        assert code == 0
        assert not RoundJournal("cj3").path.exists()

    def test_no_journal_without_session(self, monkeypatch, capsys):
        code, _, _ = self._run(
            ["critique", "--models", "mock://critic"], monkeypatch, capsys
        )
        assert code == 0
        # No session id = nothing to key the journal on.
        assert not list(Path(session_mod.SESSIONS_DIR).glob("*.journal.jsonl"))

    def test_request_deadline_flag_and_env(self, monkeypatch):
        from adversarial_spec_tpu import cli

        parser = cli.create_parser()
        args = parser.parse_args(
            ["critique", "--request-deadline-s", "2.5"]
        )
        assert cli._sampling_from_args(args).request_deadline_s == 2.5
        args = parser.parse_args(["critique"])
        assert cli._sampling_from_args(args).request_deadline_s == 0.0
        monkeypatch.setenv("ADVSPEC_REQUEST_DEADLINE_S", "7.5")
        assert cli._sampling_from_args(args).request_deadline_s == 7.5
        # Flag beats env.
        args = parser.parse_args(["critique", "--request-deadline-s", "1"])
        assert cli._sampling_from_args(args).request_deadline_s == 1.0


class TestBenchRecoverSchema:
    def test_bench_recover_json_schema_and_budget(self):
        from tools.bench_trend import collect

        rows, problems = collect(REPO)
        assert not [p for p in problems if "recover" in p], problems
        assert any(r["file"] == "BENCH_recover.json" for r in rows)
        payload = json.loads((REPO / "BENCH_recover.json").read_text())
        assert payload["metric"] == "recover_tokens_salvaged_fraction"
        assert payload["value"] >= 0.5
        assert payload["within_budget"] is True
        assert payload["victim_sigkilled"] is True
        assert payload["transcripts_byte_identical"] is True
