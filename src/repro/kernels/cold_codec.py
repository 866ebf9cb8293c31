"""Pallas TPU kernels: the streamed cold-row codec on device (ISSUE 10).

The streaming client store (``core/clientstore.py``) keeps paged-out
rows under a cold codec (f32/f16/int8) whose host-numpy reference lives
in ``core.compress.encode_cold_rows``/``decode_cold_rows``. PR 9 ran
that codec on the host *inside* the round loop: every streamed round
pulled the full f32 slab off the device, decoded/encoded in numpy, and
pushed f32 back — so the host↔device link carried 4x the codec width
and the codec itself serialized with compute.

These kernels move the codec into the jitted round: page-in DECODES
encoded rows into the slab on device, page-out ENCODES the slab before
D2H, and the transfer carries codec-width bytes (4x/2x less for
int8/f16). Same per-FlatLayout-segment affine scheme as the host path —
one ``scale = max(|seg|, 1e-12)/127`` per (row, leaf), deterministic
round-half-even — so a row is a re-quantization fixed point on either
side of the link and the f32 codec stays the bitwise identity.

Layout: segments are per-leaf ``(offset, size)`` column ranges of the
FlatLayout — irregular widths, so the kernels run per segment (leaf
counts are small) with a uniform column-block grid inside each:

- ``_absmax_kernel``   per-row |seg| max, accumulated across the column
                       grid in the revisited (rows, 1) output block;
- ``_affine_*_kernel`` elementwise quantize/dequantize against the
                       per-row scale block.

The f16 codec is a plain XLA ``astype`` on every backend: the IEEE cast
is what the host codec computes, and Mosaic does not lower an f32<->f16
cast inside a kernel (it refuses the packing on encode and the f16 load
on decode).

Dispatch follows the ``gossip_mix`` idiom: Pallas on TPU backends,
the pure-jnp oracle (``kernels.ref.cold_encode_ref``/``cold_decode_ref``)
elsewhere; ``interpret=True`` runs the kernel bodies in Python on CPU —
the mode the tier-1 tests validate against the host codec.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref

#: codecs a cold row may be stored under (mirrors compress.COLD_CODECS;
#: kept literal so the kernel module never imports the host path)
CODECS = ("f32", "f16", "int8")

# f32 min tile on TPU is (8, 128); 512 columns keeps each block well
# under VMEM at any cohort-bucket row count while staying tile-aligned
_BLK_ROWS = 8
_BLK_COLS = 512


def _use_pallas(use_pallas) -> bool:
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def _pad2(x, rows: int, cols: int, value=0.0):
    """Pad a 2-D array up to (rows, cols) with ``value``."""
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)), constant_values=value)


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


# -- kernel bodies -----------------------------------------------------------

def _absmax_kernel(x_ref, o_ref):
    """Per-row absmax of one (rows, cols) block, max-accumulated into
    the (rows, 1) output block revisited across the column grid."""
    j = pl.program_id(1)
    part = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), axis=1,
                   keepdims=True)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        o_ref[...] = jnp.maximum(o_ref[...], part)


def _affine_enc_kernel(x_ref, s_ref, q_ref):
    """int8 affine quantize against the per-row scale block (rows, 1):
    deterministic round-half-even, clipped to +/-127 (the host codec's
    ``np.rint`` discipline)."""
    s = s_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / s), -127, 127)
    q_ref[...] = q.astype(jnp.int8)


def _affine_dec_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


# -- per-segment pallas_call wrappers ----------------------------------------

def _segment_absmax(x, interpret: bool):
    """(S, w) -> (S,) per-row absmax via the column-accumulating grid."""
    S, w = x.shape
    Sp, wp = _ceil_to(S, _BLK_ROWS), _ceil_to(w, _BLK_COLS)
    out = pl.pallas_call(
        _absmax_kernel,
        grid=(Sp // _BLK_ROWS, wp // _BLK_COLS),
        in_specs=[pl.BlockSpec((_BLK_ROWS, _BLK_COLS),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((_BLK_ROWS, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, 1), jnp.float32),
        interpret=interpret,
        name="cold_absmax",
    )(_pad2(x, Sp, wp))
    return out[:S, 0]


def _elementwise(kernel, name, x, scale, out_dtype, interpret: bool):
    """Run an elementwise (x, per-row scale) -> out kernel, named
    ``name``, over the column-block grid."""
    S, w = x.shape
    Sp, wp = _ceil_to(S, _BLK_ROWS), _ceil_to(w, _BLK_COLS)
    xspec = pl.BlockSpec((_BLK_ROWS, _BLK_COLS), lambda i, j: (i, j))
    # pad rows with scale 1 so padding lanes never divide by zero
    scale = _pad2(scale[:, None], Sp, 1, value=1.0)
    out = pl.pallas_call(
        kernel,
        grid=(Sp // _BLK_ROWS, wp // _BLK_COLS),
        in_specs=[xspec, pl.BlockSpec((_BLK_ROWS, 1), lambda i, j: (i, 0))],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((Sp, wp), out_dtype),
        interpret=interpret,
        name=name,
    )(_pad2(x, Sp, wp), scale)
    return out[:S, :w]


# -- public codec ------------------------------------------------------------

def encode_rows(rows, codec: str, segments, *, use_pallas=None,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Encode (S, T) f32 rows for the cold store, on device.

    Returns ``(q, scale)``: ``q`` is (S, T) in the codec dtype, ``scale``
    the (S, nseg) f32 per-segment affine scales (width 0 for f32/f16) —
    the same fixed-structure pair as ``compress.encode_cold_rows``, and
    the same bytes: f32 is the identity, f16 the IEEE cast, int8 the
    per-segment ``max(|seg|, 1e-12)/127`` affine with round-half-even.
    """
    assert codec in CODECS, codec
    rows = rows.astype(jnp.float32)
    S = rows.shape[0]
    if codec == "f32":
        return rows, jnp.zeros((S, 0), jnp.float32)
    if codec == "f16" or (not _use_pallas(use_pallas) and not interpret):
        return _ref.cold_encode_ref(rows, codec, segments)
    qs, ss = [], []
    for off, size in segments:
        seg = rows[:, off:off + size]
        s = jnp.maximum(_segment_absmax(seg, interpret), 1e-12) / 127.0
        qs.append(_elementwise(_affine_enc_kernel, "cold_quantize", seg,
                               s, jnp.int8, interpret))
        ss.append(s)
    return jnp.concatenate(qs, axis=1), jnp.stack(ss, axis=1)


def decode_rows(q, scale, codec: str, segments, *, use_pallas=None,
                interpret: bool = False) -> jax.Array:
    """Decode :func:`encode_rows` output back to (S, T) f32 on device
    (exact for f32, the dequantized view for f16/int8). A zero ``q``
    row with zero scales decodes to exact zeros — a never-stored
    client's momentum, which is how the streamed page-in materializes
    first-touch lanes without a host round trip."""
    assert codec in CODECS, codec
    if codec in ("f32", "f16") or (not _use_pallas(use_pallas)
                                   and not interpret):
        return _ref.cold_decode_ref(q, scale, codec, segments)
    outs = []
    for j, (off, size) in enumerate(segments):
        outs.append(_elementwise(_affine_dec_kernel, "cold_dequantize",
                                 q[:, off:off + size], scale[:, j],
                                 jnp.float32, interpret))
    return jnp.concatenate(outs, axis=1)


def row_codec(codec: str, segments, sharding=None, *, use_pallas=None):
    """``(encode, decode)`` of :func:`encode_rows`/:func:`decode_rows`
    for (S, T) slabs. Given a row ``sharding`` (a ``NamedSharding`` whose
    spec splits the rows), both run on each row shard under
    ``jax.shard_map``: rows are coded independently, and XLA cannot
    partition a Pallas kernel across devices."""
    enc = functools.partial(encode_rows, codec=codec, segments=segments,
                            use_pallas=use_pallas)
    dec = functools.partial(decode_rows, codec=codec, segments=segments,
                            use_pallas=use_pallas)
    if sharding is None:
        return enc, dec
    rows = sharding.spec
    return (jax.shard_map(enc, mesh=sharding.mesh, in_specs=rows,
                          out_specs=(rows, rows), check_vma=False),
            jax.shard_map(dec, mesh=sharding.mesh, in_specs=(rows, rows),
                          out_specs=rows, check_vma=False))
