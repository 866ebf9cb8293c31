"""Synthetic FEMNIST traffic: class-conditional images split non-IID
over the federation's data shards, made from the run's seed.

The generator is copied from the program's own (``make_synthetic_images``,
``dirichlet_partition`` and ``build_fl_data`` in ``repro.data.federated``,
with the image settings of ``benchmarks.common.make_data``), so that a
change to the program cannot move the benchmark's inputs. Every number
it uses comes from the traffic file (``bench/traffic/<mix>.json``):

- ``train_samples``, ``test_samples``: images drawn per split;
- ``samples_per_device``: the rows of each data shard;
- ``alpha``: the Dirichlet concentration of the label split;
- ``noise``: the pixel noise around each class mean.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

#: a CPU rehearsal's data: a few samples per shard
TINY = {"train_samples": 128, "test_samples": 16, "samples_per_device": 16}
#: class means are fixed, so train and test draws share one task
MEANS_SEED = 1234


def synthetic_images(num: int, hw: int, channels: int, classes: int, *,
                     seed: int, noise: float):
    """(num, hw, hw, channels) images around fixed class means."""
    means = np.random.default_rng(MEANS_SEED).normal(
        size=(classes, hw, hw, channels)).astype(np.float32)
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, num)
    x = means[y] + rng.normal(size=(num, hw, hw, channels)) * noise
    return x.astype(np.float32), y.astype(np.int32)


def dirichlet_split(y: np.ndarray, shards: int, alpha: float,
                    seed: int) -> List[np.ndarray]:
    """Per-class Dirichlet(alpha) split of sample indices over shards."""
    rng = np.random.default_rng(seed)
    out: List[List[int]] = [[] for _ in range(shards)]
    for c in np.unique(y):
        idx = np.nonzero(y == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * shards)
        cuts = (np.cumsum(props)[:-1] * len(idx)).astype(int)
        for dev, part in enumerate(np.split(idx, cuts)):
            out[dev].extend(part.tolist())
    return [np.asarray(sorted(ix), np.int64) for ix in out]


def stack_shards(x, y, parts, per_shard: int):
    """(shards, per_shard, ...) arrays; short shards are topped up by a
    fixed draw with replacement, empty ones from the whole set."""
    rng = np.random.default_rng(0)
    xs, ys = [], []
    for p in parts:
        if len(p) >= per_shard:
            sel = p[:per_shard]
        elif len(p):
            sel = rng.choice(p, size=per_shard, replace=True)
        else:
            sel = rng.integers(0, len(y), per_shard)
        xs.append(x[sel])
        ys.append(y[sel])
    return np.stack(xs), np.stack(ys)


def make(traffic: Dict, model: Dict, shards: int, seed: int) -> Dict:
    """The federation's data: ``xs`` (shards, N, H, W, C) and ``ys``
    (shards, N) per-shard training rows, ``test_x``/``test_y`` a common
    test split. All of it is a pure function of ``seed``."""
    hw, ch, classes = model["image"], model["channels"], model["classes"]
    noise = traffic["noise"]
    x, y = synthetic_images(traffic["train_samples"], hw, ch, classes,
                            seed=seed, noise=noise)
    tx, ty = synthetic_images(traffic["test_samples"], hw, ch, classes,
                              seed=seed + 1, noise=noise)
    parts = dirichlet_split(y, shards, traffic["alpha"], seed)
    xs, ys = stack_shards(x, y, parts, traffic["samples_per_device"])
    return {"xs": xs, "ys": ys, "test_x": tx, "test_y": ty}
