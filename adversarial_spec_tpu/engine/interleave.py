"""Drive-loop telemetry (host side).

The ContinuousBatcher's drive loop (engine/scheduler.py ``_drive``)
issues, each iteration, ONE device program that advances the in-flight
admission's prompt chunk AND every resident row's decode (or verify)
step together (Sarathi-style piggybacked chunked prefill), and syncs
the host only at admission handoff, the speculative counts fetch, the
double buffer's depth bound, slot completion, and fault/timeout
decision points.

This module holds the process-wide counters both engines (TPU scheduler
and the mock's deterministic CPU accounting) record into, à la
``resilience.faults`` / ``prefix_cache``:

- ``stalled_prefill_s``: admission prefill wall-clock the batch actually
  waited on (standalone chunks with nothing to overlap, and the
  admission-handoff scatter+sample).
- ``overlapped_prefill_s``: prefill wall-clock attributed to chunks that
  rode inside a fused step — decode was running anyway, so this time was
  hidden under it.

``prefill_time_s`` is BY CONSTRUCTION the sum of the two buckets (the
snapshot computes it), so ``stalled + overlapped == prefill`` holds
exactly — the invariant tier-1 pins on the mock engine's deterministic
numbers. Deliberately imports no jax: the mock engine uses it on CPU.

The reset/as_dict mechanics live in ``engine/procconfig.py``
(``StatsBase``, shared with ``spec``, ``prefix_cache``, ``kvtier``);
there is nothing to configure here.
"""

from __future__ import annotations

from dataclasses import dataclass

from adversarial_spec_tpu.engine import procconfig


@dataclass
class InterleaveStats(procconfig.StatsBase):
    """Process-wide counters, aggregated across every batcher (and the
    mock engine's accounting). ``reset`` zeroes in place so engines
    holding a reference keep counting into the same object."""

    fused_steps: int = 0  # dispatches carrying prefill AND decode
    decode_steps: int = 0  # decode-only dispatches
    prefill_steps: int = 0  # standalone (stalled) prefill chunks
    sync_points: int = 0  # sanctioned host syncs (handoff/fault/timeout)
    stalled_prefill_s: float = 0.0
    overlapped_prefill_s: float = 0.0

    def record_step(self, *, fused: bool, prefill_only: bool = False) -> None:
        if fused:
            self.fused_steps += 1
        elif prefill_only:
            self.prefill_steps += 1
        else:
            self.decode_steps += 1

    def record_prefill_time(self, seconds: float, *, overlapped: bool) -> None:
        if overlapped:
            self.overlapped_prefill_s += seconds
        else:
            self.stalled_prefill_s += seconds

    def record_sync(self) -> None:
        self.sync_points += 1

    def snapshot(self) -> dict:
        out = self.as_dict()
        # The invariant the telemetry promises: total prefill time IS
        # the two buckets — there is no third place prefill time can
        # hide. Computed here (NOT rounded: rounding the addends would
        # break the exact ``stalled + overlapped == prefill`` pin).
        out["prefill_time_s"] = (
            self.stalled_prefill_s + self.overlapped_prefill_s
        )
        return out


stats = InterleaveStats()


def reset_stats() -> None:
    stats.reset()


def snapshot() -> dict:
    """The ``perf.interleave`` payload."""
    return stats.snapshot()
