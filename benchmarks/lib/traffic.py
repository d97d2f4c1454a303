"""The one traffic generator.  A traffic mix is a data file under
``benchmarks/traffic/``; this module turns it and ``--seed`` into inputs.

A closed loop's request SIZES are a fixed multiset, the deck, that the
traffic file alone decides: every seed does the same work.  ``--seed``
decides the ORDER in which the deck is issued, the token ids and (in the
driver) the weights.  Sizes drawn from ``--seed`` would make two seeds two
workloads; the order has to come from it, or every run would replay one
schedule and a tail would be a property of that one order as much as of
the system (in a closed loop the order decides which requests finish in
the same round and queue at admission together).
"""

from __future__ import annotations

import itertools
import json
import os

import numpy as np

__all__ = [
    "load", "request_deck", "request_size", "stratified_counts",
    "prompt_tokens",
]

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path, encoding="utf-8") as f:
        traffic = json.load(f)
    if "kind" not in traffic:
        raise ValueError(f"{path}: a traffic file names its driver by 'kind'")
    return traffic


def stratified_counts(weights, total: int) -> list:
    """How many of ``total`` cards each weight gets: exact where
    ``weight * total`` is whole, else largest remainders first."""
    raw = [w * total / sum(weights) for w in weights]
    counts = [int(r + 1e-9) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: -(raw[i] - counts[i]))
    for i in order[: total - sum(counts)]:
        counts[i] += 1
    return counts


def request_deck(traffic: dict, seed: int) -> dict:
    """The closed loop's request sizes, ``(prompt_len, max_new_tokens)``,
    in the order requests are issued: ``{"opening": [...], "cards":
    [...]}`` (see :func:`request_size`).

    ``cards`` holds every (prompt length, output length) pair in the
    product of the two weightings, ``deck`` cards in all, shuffled by
    ``seed``.  ``opening`` is one of each pair (shuffled too): the loop's
    first requests meet every shape the cell will ever use, so whatever
    compiles does so in the warm-up rounds."""
    pairs = list(itertools.product(
        zip(traffic["prompt_lens"], traffic["prompt_weights"]),
        zip(traffic["max_new"], traffic["max_new_weights"]),
    ))
    counts = stratified_counts(
        [pw * mw for (_, pw), (_, mw) in pairs], int(traffic["deck"])
    )
    cards = [
        (int(p), int(m))
        for ((p, _), (m, _)), c in zip(pairs, counts) for _ in range(c)
    ]
    rng = np.random.default_rng([int(seed), 0xDEC4])
    opening = [(int(p), int(m)) for (p, _), (m, _) in pairs]
    rng.shuffle(opening)
    rng.shuffle(cards)
    return {"opening": opening, "cards": cards}


def request_size(deck: dict, n: int) -> tuple:
    """Sizes of the ``n``-th request issued: the opening once, then the
    cards round and round."""
    opening, cards = deck["opening"], deck["cards"]
    if n < len(opening):
        return opening[n]
    return cards[(n - len(opening)) % len(cards)]


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``rid``: uniform token ids from the seed."""
    rng = np.random.default_rng([int(seed), 0x9207, int(rid)])
    return rng.integers(0, vocab, (length,)).astype(np.int32)
