"""Closed-loop debate clients on the daemon's socket, and their records.

One thread per client of the mix, each with a `ServeClient` connection of
its own: it sends a greedy, streamed debate, reads the stream until the
terminal event, stamps every event with the host clock as it arrives, and
sends the next. The threads block in `recv` nearly all the time; `late_ms`
(result in hand -> next submit on the wire) shows whether they kept up.

The loops run from set-up straight through the window: the primer goes
first, the clients join while it holds the engine, and the window opens
once every client has its warm-up debates behind it, on a system that is
already in its steady state.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from perfbench.traffic import Debate


@dataclass
class DebateRecord:
    debate: Debate
    t_submit: float
    t_result: float | None = None
    # per opponent index: arrival times of its stream events
    stream_times: dict[int, list[float]] = field(default_factory=dict)
    final: dict | None = None  # the terminal event
    late_ms: float | None = None  # previous result -> this submit

    @property
    def ok(self) -> bool:
        f = self.final
        return (
            f is not None
            and f.get("event") == "result"
            and not f.get("error")
            and len(f.get("results", [])) == self.debate.opponents
            and not any(r.get("error") for r in f["results"])
        )


def _submit(client, model: str, d: Debate) -> str:
    """The `debate` op as `ServeClient.submit_debate` builds it, with
    `greedy` set, which that call has no argument for."""
    return client.send({
        "op": "debate",
        "tenant": d.tenant,
        "tier": "interactive",
        "spec": d.spec,
        "models": [model] * d.opponents,
        "round": d.round_num,
        "max_new_tokens": d.max_new_tokens,
        "greedy": True,
        "stream": True,
    })


def run_debate(client, model: str, d: Debate, timeout_s: float, t_prev=None,
               on_first_token=None) -> DebateRecord:
    """One debate to its terminal event, every event stamped on arrival."""
    from adversarial_spec_tpu.serve.protocol import TERMINAL_EVENTS

    t_submit = time.monotonic()
    rid = _submit(client, model, d)
    rec = DebateRecord(debate=d, t_submit=t_submit)
    if t_prev is not None:
        rec.late_ms = (t_submit - t_prev) * 1000.0
    deadline = t_submit + timeout_s
    while True:
        ev = client.recv(timeout_s=max(0.1, deadline - time.monotonic()))
        now = time.monotonic()
        if ev is None:
            rec.final = {"event": "error", "error": "daemon closed the connection"}
            break
        if ev.get("id") != rid:
            continue  # broadcasts (draining)
        kind = ev.get("event")
        if kind == "stream":
            if on_first_token is not None and not rec.stream_times:
                on_first_token()
            rec.stream_times.setdefault(int(ev["index"]), []).append(now)
        elif kind in TERMINAL_EVENTS:
            rec.final = ev
            rec.t_result = now
            break
    return rec


class ClosedLoop:
    """The mix's primer, then its clients, each sending its plan's debates
    one after another until `stop_and_wait`."""

    def __init__(self, system, primer: Debate, plans: list[list[Debate]], timeout_s: float = 300.0):
        self.system = system
        self.primer = primer
        self.plans = plans
        self.timeout_s = timeout_s
        self.primer_record: DebateRecord | None = None
        self.records: list[list[DebateRecord]] = [[] for _ in plans]
        self.errors: list[str] = []
        self._stop = threading.Event()
        self._primer_running = threading.Event()
        self._clients = [system.client(timeout_s=timeout_s) for _ in range(len(plans) + 1)]
        self._threads = [threading.Thread(target=self._prime, daemon=True)] + [
            threading.Thread(target=self._loop, args=(c,), daemon=True)
            for c in range(len(plans))
        ]

    def start(self) -> None:
        """The primer, and once its first token shows that it holds the
        engine, every client."""
        self._threads[0].start()
        self._primer_running.wait(self.timeout_s)
        for th in self._threads[1:]:
            th.start()

    def wait_warm(self) -> None:
        """Until every client has finished its warm-up debates (or has ended)."""
        def warm(c: int) -> bool:
            want = sum(d.warmup for d in self.plans[c])
            return len(self.records[c]) >= want or not self._threads[c + 1].is_alive()

        self._threads[0].join()
        while not all(warm(c) for c in range(len(self.plans))):
            time.sleep(0.005)

    def stop_and_wait(self) -> None:
        """No new debate goes out; the ones in flight run to their end."""
        self._stop.set()
        for th in self._threads:
            th.join()

    def close(self) -> None:
        for c in self._clients:
            c.close()

    def _prime(self) -> None:
        try:
            self.primer_record = run_debate(
                self._clients[-1], self.system.model, self.primer, self.timeout_s,
                on_first_token=self._primer_running.set,
            )
        except Exception as e:
            self.errors.append(f"primer: {type(e).__name__}: {e}")
        finally:
            self._primer_running.set()

    def _loop(self, c: int) -> None:
        t_prev = None
        try:
            for d in self.plans[c]:
                if self._stop.is_set():
                    break
                rec = run_debate(self._clients[c], self.system.model, d, self.timeout_s, t_prev=t_prev)
                t_prev = rec.t_result
                self.records[c].append(rec)
                if rec.t_result is None:
                    break
        except Exception as e:  # a broken client ends its loop, visibly
            self.errors.append(f"client {c}: {type(e).__name__}: {e}")
