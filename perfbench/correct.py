"""The comparison that decides `correct`.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed, the longest always
in it) is put to the plain reference: one pass over each prompt with the
tokens that were served, and at every served position the gap by which the
served token's logit lies below the reference's best, in units of that
position's standard deviation of the reference's logits (random weights
give every configuration another logit scale; the ratio has none). The
number compared is the widest such gap. The traffic is greedy, so a sound
program serves the reference's own best token, or one that rounding put
level with it.

The limit is data: `limits/<configuration>.json`, with the readings it was
set from (PERF.md gives them too); `manifest.load_cell` reads it with the
cell's other files.
"""

from __future__ import annotations

import random
from dataclasses import dataclass

import numpy as np


@dataclass
class FinishedDebate:
    """One finished debate of the window: its opponents' requests."""

    key: tuple
    reqs: list

    @property
    def length(self) -> int:
        return max(r.length for r in self.reqs)


def pick_debates(finished: list, n: int, seed: int) -> list:
    """`n` of the finished debates, drawn from the seed: the longest (prompt
    and reply) always, then client by client in turn, so that the sample
    holds every place a debate can take among a dispatch's rows (a client
    keeps its place from dispatch to dispatch). `finished` items have a
    `.length` and a `.key` of (client, index)."""
    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda i: finished[i].length)
    by_client: dict = {}
    for i, f in enumerate(finished):
        if i != longest:
            by_client.setdefault(f.key[0], []).append(i)
    rng = random.Random(f"check:{seed}")
    for idxs in by_client.values():
        rng.shuffle(idxs)
    clients = sorted(by_client)
    # the turn starts after the longest one's client
    start = next((k + 1 for k, c in enumerate(clients) if c == finished[longest].key[0]), 0)
    order = clients[start:] + clients[:start]
    picked = [longest]
    while len(picked) < n and any(by_client.values()):
        for c in order:
            if by_client[c] and len(picked) < n:
                picked.append(by_client[c].pop())
    return [finished[i] for i in picked]


def gaps(logits: np.ndarray, tokens: list[int]) -> np.ndarray:
    """Per position, (best logit - the token's logit) / std of the logits."""
    logits = np.asarray(logits, np.float64)
    idx = np.arange(len(tokens))
    best = logits.max(axis=-1)
    std = logits.std(axis=-1)
    return (best - logits[idx, np.asarray(tokens)]) / np.maximum(std, 1e-12)


def served_logits(arch, cfg: dict, weights, prompt: list[int], served: list[int]) -> np.ndarray:
    """One pass of the architecture's plain reference (`arch.logits_for`)
    over prompt + served tokens: the logits that chose each served token,
    [len(served), vocabulary]."""
    ids = list(prompt) + list(served[:-1])
    return arch.logits_for(cfg, weights, ids, len(prompt) - 1)


def compare_request(logits: np.ndarray, served: list[int], low_logits: np.ndarray | None = None) -> dict:
    """The served tokens against the reference's logits. With `low_logits`
    (the reference in the precision below, over the same prompt and
    tokens), also the gap of the token the lower precision puts first at
    each position: the control need not decode."""
    best = logits.argmax(axis=-1)
    out = {
        "n": len(served),
        "gap_max": float(gaps(logits, served).max()),
        "match": int((best == np.asarray(served)).sum()),
    }
    if low_logits is not None:
        low_best = low_logits.argmax(axis=-1)
        out["control_gap_max"] = float(gaps(logits, list(low_best)).max())
        out["control_match"] = int((low_best == best).sum())
    return out


def verdict(compared: dict) -> bool:
    """True when every number compared is within its limit (lower is better
    for all of them) and every count that must be zero is."""
    return all(v["value"] <= v["limit"] for v in compared.values())
