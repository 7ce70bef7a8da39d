"""Streaming-token config and telemetry (process-wide, host side).

The engine seam streams per-request tokens to a HOST-SIDE consumer
(engine/types.py ``StreamConsumer``): the ContinuousBatcher delivers
each request's tokens-so-far at the drive loop's existing fetch points
(the pipelined loop's async entry fetch, the speculative path's
per-step counts sync, admission handoff, slot completion — no new
sanctioned sync points), and a consumer returning ``False`` cancels
the request mid-decode: its spans close with a ``cancelled`` phase,
the computed KV's full pages are salvaged into the prefix cache, its
pages and slot free through the same reference-drop surgery fault
eviction uses, and the freed capacity re-admits queued work
immediately (docs/streaming.md).

The debate layer's early-convergence consumer (debate/core.py) is the
first user: an opponent's critique is only needed until ``[AGREE]``
(or a section marker — parsing.EARLY_CANCEL_MARKERS) appears, so
everything decoded past the marker is waste the matched-ceiling debate
study (PAPERS.md) says buys nothing — round COUNT, not round length,
drives quality. This module is the switchboard both engines (batcher
and the mock's deterministic CPU accounting) consult and record into,
following the ``interleave`` / ``spec`` / ``prefix_cache`` pattern:

- **config**: ``enabled`` (CLI ``--stream/--no-stream``, env
  ``ADVSPEC_STREAM``, default on) gates token delivery;
  ``early_cancel`` (CLI ``--early-cancel/--no-early-cancel``, env
  ``ADVSPEC_EARLY_CANCEL``, default on) additionally arms the debate
  layer's marker-driven cancellation. Stream off = the blocking path,
  byte-identical end to end; stream on = transcripts byte-identical
  UP TO each cancellation point (greedy decode is deterministic and
  cancellation only truncates).
- **stats**: per-round streaming counters; ``snapshot()`` is the CLI's
  ``perf.stream`` payload. ``saved_fraction`` is the headline the
  cancel bench pins: tokens the round did NOT decode over the tokens
  it would have decoded without cancellation.

Deliberately imports no jax: the mock engine uses it on CPU. The
config/stats mechanics live in ``engine/procconfig.py``.
"""

from __future__ import annotations

import inspect
import os
from dataclasses import dataclass

from adversarial_spec_tpu.engine import procconfig


def env_enabled() -> bool:
    """The process default for the master switch (``ADVSPEC_STREAM``)."""
    return os.environ.get("ADVSPEC_STREAM", "1") != "0"


def env_early_cancel() -> bool:
    """The process default for marker-driven cancellation
    (``ADVSPEC_EARLY_CANCEL``)."""
    return os.environ.get("ADVSPEC_EARLY_CANCEL", "1") != "0"


@dataclass
class StreamConfig:
    """Process-wide knobs, set once per CLI round (or by tests)."""

    enabled: bool = True
    early_cancel: bool = True


@dataclass
class StreamStats(procconfig.StatsBase):
    """Process-wide streaming counters, aggregated across every batcher
    drain (and the mock engine's deterministic accounting).

    ``streamed_tokens`` counts tokens DELIVERED through consumers (a
    cancelled request contributes only its emitted prefix), so
    ``tokens_saved / (streamed_tokens + tokens_saved)`` — the snapshot's
    ``saved_fraction`` — is the fraction of the round's streamed decode
    the cancellations avoided paying for.

    ``tokens_saved`` semantics per engine: the REAL batcher records the
    budget remainder (``max_new_tokens − emitted``) — the reserved
    decode capacity the cancel returned to the pool, an UPPER bound on
    the decode actually avoided, since where EOS would have landed is
    unknowable once decoding stops. The MOCK engine scripts its own
    reply, so it records the exact remainder of the reply the consumer
    never read; its ``saved_fraction`` (the cancel bench's headline) is
    therefore exact, not an upper bound.
    """

    requests_streamed: int = 0
    deliveries: int = 0  # consumer callbacks that carried new tokens
    streamed_tokens: int = 0  # tokens delivered through consumers
    cancels: int = 0
    cancelled_emitted_tokens: int = 0  # tokens emitted before each cancel
    tokens_saved: int = 0  # budget tokens never decoded thanks to cancel

    def record_request(self) -> None:
        self.requests_streamed += 1

    def record_delivery(self, n_tokens: int) -> None:
        self.deliveries += 1
        self.streamed_tokens += n_tokens

    def record_cancel(self, emitted: int, saved: int) -> None:
        self.cancels += 1
        self.cancelled_emitted_tokens += emitted
        self.tokens_saved += saved

    def snapshot(self) -> dict:
        out = self.as_dict()
        denom = self.streamed_tokens + self.tokens_saved
        out["saved_fraction"] = (
            round(self.tokens_saved / denom, 4) if denom else 0.0
        )
        return out


_state = procconfig.ProcState(
    StreamConfig(enabled=env_enabled(), early_cancel=env_early_cancel()),
    StreamStats(),
)
_config = _state.config
stats = _state.stats


def config() -> StreamConfig:
    return _state.config


def configure(
    enabled: bool | None = None, early_cancel: bool | None = None
) -> StreamConfig:
    return _state.configure(enabled=enabled, early_cancel=early_cancel)


def reset_stats() -> None:
    _state.reset_stats()


def snapshot() -> dict:
    """Stats + config, the ``perf.stream`` payload."""
    return _state.snapshot()


def armed() -> bool:
    """True when the debate layer should build early-cancel consumers:
    streaming AND marker cancellation both enabled."""
    return _state.config.enabled and _state.config.early_cancel


def consumer_supported(engine) -> bool:
    """True when the engine's ``chat`` accepts the streaming
    ``consumer`` kwarg (the Engine protocol's streaming extension).
    Inspected rather than assumed so test fakes and out-of-tree engines
    with the original 2-argument signature keep working unmodified —
    they simply serve the blocking path."""
    try:
        return "consumer" in inspect.signature(engine.chat).parameters
    except (TypeError, ValueError):
        return False


def wants_n_tokens(consumer) -> bool:
    """True when a stream consumer asked to be called as
    ``consumer(row, text, n_tokens)``: the count of token ids behind
    ``text``, which only the engine knows (a ``wants_n_tokens = True``
    attribute on the callable; the serve gate sets it). Every other
    consumer keeps the two-argument call."""
    return bool(getattr(consumer, "wants_n_tokens", False))
