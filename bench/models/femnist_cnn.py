"""The LEAF FEMNIST CNN (arXiv:1812.01097): its binding to the system
under test, its operation count, and its plain reference.

5x5 conv (c1 channels, SAME) -> ReLU -> 2x2 max-pool -> 5x5 conv (c2)
-> ReLU -> 2x2 max-pool -> dense (hidden) -> ReLU -> dense (classes).
At the published sizes (28x28x1 images, 32/64 channels, 2,048 hidden,
62 classes) it has 6,603,710 parameters.

The reference below is written from that description alone: float32
at ``highest`` matmul precision, one row at a time, no kernels. Its
initialisation follows the same published recipe as the program
(normal weights scaled by 1/sqrt(fan-in) from four splits of the seed's
key, zero biases), so both start from the same weights without the
reference reading any.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: a CPU rehearsal's sizes: 8x8 images, channel and hidden widths as
#: published
TINY = {"image": 8}
#: three bfloat16 passes (hi*hi + hi*lo + lo*hi, accumulated in float32)
#: in every product of the forward and of the backward pass, emulated,
#: so that the check's control computes alike on every backend
BF16_3X = "bf16_3x"


def split_bf16(x):
    """``x`` as the sum of two bfloat16 parts, high and low, each held in
    float32."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _passes(f, a, b):
    """``f(a, b)`` in three bfloat16 passes: exact products of the parts,
    float32 sums."""
    (ah, al), (bh, bl) = split_bf16(a), split_bf16(b)

    def g(x, y):
        return f(x, y, precision=HIGHEST)
    return g(ah, bh) + g(ah, bl) + g(al, bh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def three_pass(f, a, b):
    """A bilinear product ``f(a, b)`` whose forward and backward products
    both take three bfloat16 passes, as a TPU's ``high`` precision does."""
    return _passes(f, a, b)


def _three_pass_fwd(f, a, b):
    return _passes(f, a, b), (a, b)


def _three_pass_bwd(f, res, c):
    a, b = res

    # each transpose is bilinear in (cotangent, other operand): take it in
    # three passes too
    def t_a(cc, y, precision):
        return jax.vjp(lambda x: f(x, y, precision=precision), a)[1](cc)[0]

    def t_b(cc, x, precision):
        return jax.vjp(lambda y: f(x, y, precision=precision), b)[1](cc)[0]
    return _passes(t_a, c, b), _passes(t_b, c, a)


three_pass.defvjp(_three_pass_fwd, _three_pass_bwd)


def product(f, a, b, precision):
    """``f(a, b)`` (a bilinear product) at ``precision``: a JAX
    precision, or :data:`BF16_3X`."""
    if precision != BF16_3X:
        return f(a, b, precision=precision)
    return three_pass(f, a, b)


def system(model: Dict):
    """``(init_fn, apply_fn)`` of the program's own CNN at ``model``'s
    image and class sizes."""
    from repro.models.cnn import apply_femnist_cnn, init_femnist_cnn

    def init(key):
        return init_femnist_cnn(key, num_classes=model["classes"],
                                image_size=model["image"])
    return init, apply_femnist_cnn


def forward_flops(model: Dict) -> int:
    """Forward operations per sample, a multiply-add counted as 2;
    biases, ReLU and pooling count nothing."""
    hw, k = model["image"], model["kernel"]
    cin, c1, c2 = model["channels"], model["conv1"], model["conv2"]
    hidden, classes = model["hidden"], model["classes"]
    conv1 = hw * hw * c1 * k * k * cin * 2
    conv2 = (hw // 2) ** 2 * c2 * k * k * c1 * 2
    feat = (hw // 4) ** 2 * c2
    return conv1 + conv2 + feat * hidden * 2 + hidden * classes * 2


def param_count(model: Dict) -> int:
    hw, k = model["image"], model["kernel"]
    cin, c1, c2 = model["channels"], model["conv1"], model["conv2"]
    hidden, classes = model["hidden"], model["classes"]
    feat = (hw // 4) ** 2 * c2
    return ((k * k * cin + 1) * c1 + (k * k * c1 + 1) * c2
            + (feat + 1) * hidden + (hidden + 1) * classes)


def ref_init(key, model: Dict, dtype=jnp.float32):
    """The published initialisation, in ``dtype``."""
    hw, k = model["image"], model["kernel"]
    cin, c1, c2 = model["channels"], model["conv1"], model["conv2"]
    hidden, classes = model["hidden"], model["classes"]
    feat = (hw // 4) ** 2 * c2
    ks = jax.random.split(key, 4)

    def normal(key, shape, fan_in):
        w = jax.random.normal(key, shape, jnp.float32)
        return (w * (1.0 / jnp.sqrt(fan_in))).astype(dtype)
    return {
        "c1": {"w": normal(ks[0], (k, k, cin, c1), k * k * cin),
               "b": jnp.zeros(c1, dtype)},
        "c2": {"w": normal(ks[1], (k, k, c1, c2), k * k * c1),
               "b": jnp.zeros(c2, dtype)},
        "f1": {"w": normal(ks[2], (feat, hidden), feat),
               "b": jnp.zeros(hidden, dtype)},
        "f2": {"w": normal(ks[3], (hidden, classes), hidden),
               "b": jnp.zeros(classes, dtype)},
    }


def _conv2d(x, w, precision):
    """A stride-1 SAME convolution of ``x`` (B, H, W, C) with ``w``
    (k, k, C, O), as a product of the image's k x k patches with the
    kernel."""
    k, _, c, o = w.shape
    b, h, wd, _ = x.shape
    xp = jnp.pad(x, ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0)))
    patches = jnp.stack([xp[:, i:i + h, j:j + wd, :] for i in range(k)
                         for j in range(k)], axis=3)
    return jnp.dot(patches.reshape(b, h, wd, k * k * c),
                   w.reshape(k * k * c, o), precision=precision)


def _conv(x, w, b, precision):
    return product(_conv2d, x, w, precision).astype(x.dtype) + b


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def ref_apply(p, x, precision=HIGHEST):
    """Logits of one row's parameters ``p`` on images ``x`` (B, H, W, C),
    every product at ``precision`` (a JAX precision or :data:`BF16_3X`)."""
    x = x.astype(p["c1"]["w"].dtype)
    x = _pool(jax.nn.relu(_conv(x, p["c1"]["w"], p["c1"]["b"], precision)))
    x = _pool(jax.nn.relu(_conv(x, p["c2"]["w"], p["c2"]["b"], precision)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(product(jnp.dot, x, p["f1"]["w"], precision)
                    .astype(x.dtype) + p["f1"]["b"])
    return (product(jnp.dot, x, p["f2"]["w"], precision).astype(x.dtype)
            + p["f2"]["b"])
