"""A tiny causal language model, for the tests that a next-token model
goes through the benchmark's reference, controls and CPU rehearsal with
the model contract of ``bench/models/__init__.py`` alone.

Token embedding (vocab, dim) -> one-head causal self-attention with a
residual -> vocab head (dim, vocab); logits ``(batch, seq, vocab)`` from
int32 ids ``(batch, seq)``. The program under test has no such model:
``system`` binds the plain forward pass itself.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.models.femnist_cnn import BF16_3X, product  # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST
#: a CPU rehearsal's sizes: a vocabulary past 256, which bfloat16 ids
#: could not hold, and short sequences
TINY = {"vocab": 300, "seq": 8}


def system(model: Dict):
    def init(key):
        return ref_init(key, model)
    return init, ref_apply


def forward_flops(model: Dict) -> int:
    """Per sequence: q, k, v, o projections, scores and weighted sum over
    all seq x seq pairs, and the vocab head."""
    s, d, v = model["seq"], model["dim"], model["vocab"]
    return s * 4 * d * d * 2 + 2 * s * s * d * 2 + s * d * v * 2


def param_count(model: Dict) -> int:
    d, v = model["dim"], model["vocab"]
    return v * d + 4 * d * d + d * v


def ref_init(key, model: Dict, dtype=jnp.float32):
    d, v = model["dim"], model["vocab"]
    ks = jax.random.split(key, 6)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)
    return {"embed": normal(ks[0], (v, d), 1),
            "wq": normal(ks[1], (d, d), d), "wk": normal(ks[2], (d, d), d),
            "wv": normal(ks[3], (d, d), d), "wo": normal(ks[4], (d, d), d),
            "head": normal(ks[5], (d, v), d)}


def _scores(q, k, precision):
    return jnp.einsum("bsd,btd->bst", q, k, precision=precision)


def _mix(a, v, precision):
    return jnp.einsum("bst,btd->bsd", a, v, precision=precision)


def ref_apply(p, x, precision=HIGHEST):
    """Logits ``(batch, seq, vocab)`` of int32 ids ``x`` ``(batch, seq)``."""
    h = p["embed"][x]
    dt = h.dtype

    def lin(a, w):
        return product(jnp.dot, a, w, precision).astype(dt)
    q, k, v = lin(h, p["wq"]), lin(h, p["wk"]), lin(h, p["wv"])
    s = product(_scores, q, k, precision).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    seq = x.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1).astype(dt)
    h = h + lin(product(_mix, a, v, precision).astype(dt), p["wo"])
    return lin(h, p["head"])
