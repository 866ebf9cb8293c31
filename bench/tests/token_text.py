"""Synthetic token traffic for the tiny language model: each data shard
draws its sequences from a Zipf-like unigram law of its own (a
permutation of the vocabulary from the seed), labels the next token.

Settings (the traffic file's ``data``): ``samples_per_device``
sequences per shard, ``zipf`` the law's exponent. The sequence length
and vocabulary are the model's (``seq``, ``vocab``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: a CPU rehearsal's data
TINY = {"samples_per_device": 8}


def make(traffic: Dict, model: Dict, shards: int, seed: int) -> Dict:
    """``xs`` (shards, N, seq) int32 ids and ``ys`` (shards, N, seq) the
    ids that follow them; a pure function of ``seed``."""
    v, s = model["vocab"], model["seq"]
    n = traffic["samples_per_device"]
    rng = np.random.default_rng(seed)
    law = 1.0 / np.arange(1, v + 1) ** traffic["zipf"]
    law /= law.sum()
    seqs = np.stack([rng.permutation(v)[rng.choice(v, (n, s + 1), p=law)]
                     for _ in range(shards)]).astype(np.int32)
    return {"xs": seqs[..., :-1], "ys": seqs[..., 1:]}
