"""The one traffic generator: a mix's parameters and a seed give a plan.

A plan is, for every client of the mix, the list of debates that client
sends one after another (a closed loop: the next goes out when the last
came back). The amount of work is fixed by the mix alone: every length is
in bytes, one byte is one token of the synthetic checkpoints, and the seed
decides nothing but the bytes. So two seeds give two plans of the same
debates, requests, prompt lengths and reply budgets in the same order.

Every client has documents of its own, and a debate has fewer opponents
than a dispatch has rows (the daemon's `max_dispatch_batch`), so the daemon
coalesces two clients' debates into one dispatch and the rows that decode
together hold different sequences. The `primer` is one debate of a whole
dispatch's rows on a document of its own, sent first: it holds the engine
while the clients join, so that every later dispatch is full.

`make_doc` is a copy of chip_smoke.py's seeded spec-shaped text, padded to
the exact length (the original strips trailing space, so its length moves
with the seed by a few bytes).
"""

from __future__ import annotations

import random
from dataclasses import dataclass

_WORDS = (
    "the service must shall may request response retry timeout queue worker "
    "tenant quota budget replica shard index cache page block token prefix "
    "session round debate opponent critique revision document section schema "
    "field record latency throughput backlog admission deadline failure "
    "recovery journal snapshot rollback version migration endpoint payload "
    "header signature key secret audit log metric alert threshold capacity"
).split()

# More debates than any run reaches: a client never runs out of plan.
PLAN_DEBATES = 64


def _exact(text: str, n_bytes: int) -> str:
    """`text` cut or padded to exactly n_bytes of ASCII, ending in a newline."""
    body = text[: n_bytes - 1].rstrip()
    return body + "." * (n_bytes - 1 - len(body)) + "\n"


def make_doc(rng: random.Random, n_bytes: int, title: str) -> str:
    out = [f"# {title}\n"]
    size = len(out[0])
    section = 0
    while size < n_bytes:
        section += 1
        block = [f"\n## {section}. {' '.join(rng.sample(_WORDS, 3)).title()}\n"]
        for _ in range(rng.randint(3, 6)):
            words = [rng.choice(_WORDS) for _ in range(rng.randint(8, 16))]
            block.append(" ".join(words).capitalize() + ".\n")
        text = "".join(block)
        out.append(text)
        size += len(text)
    return _exact("".join(out), n_bytes)


# "Debate round 9" and "round 10" differ by a token: rounds start here, so
# that a session's rounds keep one width and every round is the same work.
ROUND_BASE = 10


@dataclass(frozen=True)
class Debate:
    client: int
    index: int  # 0, 1, 2, ... within its client
    tenant: str
    round_num: int
    spec: str
    opponents: int
    max_new_tokens: int
    warmup: bool  # sent in set-up, before the window opens


def _debate(mix: dict, client: int, index: int, tenant: str, spec: str, *,
            opponents: int, warmup: bool, fresh: bool) -> Debate:
    return Debate(
        client=client,
        index=index,
        tenant=tenant,
        round_num=ROUND_BASE if fresh else ROUND_BASE + index,
        spec=spec,
        opponents=opponents,
        max_new_tokens=int(mix["max_new_tokens"]),
        warmup=warmup,
    )


def plan(mix: dict, seed: int, n_debates: int = PLAN_DEBATES) -> list[list[Debate]]:
    """Per client, its debates in the order it sends them."""
    doc_bytes = int(mix["document"]["bytes"])
    fresh = bool(mix["document"]["fresh_per_debate"])
    warm = int(mix["warmup_debates"])
    clients = []
    for c in range(int(mix["clients"])):
        debates = []
        for i in range(n_debates):
            k = i if fresh else 0  # a session keeps its document
            spec = make_doc(random.Random(f"doc:{seed}:{c}:{k}"), doc_bytes, f"Spec {c}-{k}")
            debates.append(
                _debate(mix, c, i, f"t{c}", spec, opponents=int(mix["opponents"]),
                        warmup=i < warm, fresh=fresh)
            )
        clients.append(debates)
    return clients


def primer(mix: dict, seed: int, rows: int) -> Debate:
    """The debate that is sent before any client's: a full dispatch of `rows`."""
    spec = make_doc(random.Random(f"primer:{seed}"), int(mix["document"]["bytes"]), "Primer")
    return _debate(mix, -1, 0, "primer", spec, opponents=rows,
                   warmup=True, fresh=True)


def shape_of(plans: list[list[Debate]]) -> list[list[tuple]]:
    """What a plan asks of the system, without its bytes: the seed-invariant part."""
    return [
        [
            (d.round_num, len(d.spec.encode()), d.opponents, d.max_new_tokens, d.warmup)
            for d in debates
        ]
        for debates in plans
    ]
