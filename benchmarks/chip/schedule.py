"""One generator for every engine traffic mix: the mix's JSON parameters
and the seed in, closed batches of requests out.

Steadiness over seeds: a seed changes the order of the work and the prompt
tokens, never the sizes. Each batch cycles through the mix's fixed rounds
(multisets of prompt lengths, one length per slot), so every seed prefills
and decodes the same lengths in another order.
"""
from __future__ import annotations

import random

import numpy as np


def _shuffled(items: list, seed: int, stream: str) -> list:
    items = list(items)
    random.Random(f"{seed}/{stream}").shuffle(items)
    return items


def eval_batches(traffic: dict, vocab: int, seed: int, n_batches: int,
                 stream: str = "win") -> list[list[dict]]:
    """Closed batches of requests for an engine cell. Each batch cycles
    through the mix's fixed rounds (multisets of prompt lengths, one per
    slot); the seed orders each round and draws the prompt tokens."""
    rounds = traffic["rounds"]
    per_batch = int(traffic["batch"])
    slots = len(rounds[0])
    out = []
    for b in range(n_batches):
        reqs = []
        for r in range(per_batch // slots):
            lens = _shuffled(rounds[r % len(rounds)], seed,
                             f"{stream}/{b}/{r}")
            for j, n in enumerate(lens):
                rs = np.random.default_rng(
                    [int(seed) % 2**63, b, r, j, 1 if stream == "win" else 2])
                reqs.append({"prompt": rs.integers(0, vocab, n,
                                                   dtype=np.int32),
                             "max_new_tokens": int(traffic["new_tokens"])})
        out.append(reqs)
    return out
