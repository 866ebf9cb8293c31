"""Pallas TPU kernel for fused gossip mixing — the paper-specific hot loop.

CE-FedAvg's aggregation boundaries apply a mixing operator W of eq. (11)
over the device axis of Y, which stacks the n device models row-wise
(eq. 10). Done naively (per-leaf tensordot) each parameter block is
re-read from HBM once per *leaf* per boundary; this kernel streams the
whole flattened parameter bank once: each (n × block) tile is read once,
hit with a skinny (n×n) matmul in VMEM, and written once — the op is
purely memory-bound, so one pass is the roofline.

Two call conventions:

- :func:`gossip_mix_flat` — the raw kernel, ``(W, Y) -> WᵀY``
  (column application; W[j,i] is the weight j→i).
- :func:`gossip_mix_rows` — ``(W, Y) -> W @ Y`` (row application,
  matching :func:`repro.core.cefedavg.mix` for arbitrary — including
  asymmetric row-stochastic masked — operators). This is the ModelBank
  mixing boundary: Pallas on TPU, a single XLA gemm elsewhere (the
  ``kernels/ref.py`` oracle; XLA already emits one streaming pass).

:class:`FlatLayout` is the cached concat/split plan between a pytree of
``(n, ...)`` leaves and the flat ``(n, T)`` bank; ``gossip_mix_tree``
re-uses it so external per-call concatenate/split planning happens once
per tree structure, and ``repro.core.modelbank`` re-uses it to keep the
whole simulation state flat for the run.

Validated on CPU with interpret=True against kernels/ref.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref


def _kernel(w_ref, y_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)        # (n, k), W[j,i] = weight j->i
    y = y_ref[...].astype(jnp.float32)        # (n, block)
    o = jax.lax.dot_general(w, y, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)


def gossip_mix_flat(W: jax.Array, Y: jax.Array, *, block: int = 2048,
                    interpret: bool = False) -> jax.Array:
    """Y: (n, T) flattened stacked models; W: (n, k). Returns WᵀY (k, T).

    Rectangular W supports the edge-model projection P ∈ R^{m×n} (pass
    ``P.T``) as well as the square mixing operators."""
    n, T = Y.shape
    k = W.shape[1]
    nb = -(-T // block)
    pad = nb * block - T
    if pad:
        Y = jnp.pad(Y, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, nb * block), Y.dtype),
        interpret=interpret,
        name="gossip_mix",
    )(W, Y)
    return out[:, :T]


#: column tile of the CPU/GPU in-place streaming pass: n×(1<<18) f32 is a
#: 16 MB working set at n=16 — big enough to amortize loop overhead,
#: small enough that the tile's read-modify-write stays cache-friendly
_BLOCK_COLS_XLA = 1 << 18


def _mix_rows_blocked(W: jax.Array, Y: jax.Array,
                      block_cols: int = _BLOCK_COLS_XLA) -> jax.Array:
    """In-place cache-blocked ``W @ Y`` for square W — the CPU/GPU
    lowering of the fused streaming pass.

    XLA's ``dot`` cannot alias its output, so a plain bank-sized gemm
    allocates (and page-faults) a second (n, T) buffer on every boundary;
    tiling the columns and writing each ``W @ tile`` back over its own
    tile (exact: an output tile depends only on the matching input tile)
    keeps the op at one read + one write of the bank, the same roofline
    the Pallas kernel hits on TPU. ~3x faster than the gemm at the
    FEMNIST-CNN bank size on a 2-core host (BENCH_pr3.json)."""
    n, T = Y.shape
    Wj = jnp.asarray(W, jnp.float32)
    nb = T // block_cols

    def tile(blk):
        return (Wj @ blk.astype(jnp.float32)).astype(Y.dtype)

    def body(i, Y):
        off = i * block_cols
        blk = jax.lax.dynamic_slice(Y, (0, off), (n, block_cols))
        return jax.lax.dynamic_update_slice(Y, tile(blk), (0, off))

    if nb:
        Y = jax.lax.fori_loop(0, nb, body, Y)
    rem = T - nb * block_cols
    if rem:
        blk = jax.lax.dynamic_slice(Y, (0, nb * block_cols), (n, rem))
        Y = jax.lax.dynamic_update_slice(Y, tile(blk),
                                         (0, nb * block_cols))
    return Y


def gossip_mix_rows(W, Y: jax.Array, *, block: int = 2048,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> jax.Array:
    """Row-apply W (k, n) to the flat bank Y (n, T): out = W @ Y.

    One streaming pass over the bank — the ModelBank mixing boundary.
    On TPU this lowers to the fused Pallas kernel; on CPU/GPU to the
    in-place blocked pass (:func:`_mix_rows_blocked`) when W is square,
    else one XLA gemm (the rectangular edge-model projection;
    :func:`repro.kernels.ref.gossip_mix_rows_ref` is the oracle for
    both). Everything it lowers to, the kernel with its pad and slice
    copies, sits under ``jax.named_scope("mix")``."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    with jax.named_scope("mix"):
        if use_pallas or interpret:
            Wj = jnp.asarray(W, jnp.float32)
            return gossip_mix_flat(Wj.T, Y, block=block,
                                   interpret=interpret)
        if W.shape[0] == Y.shape[0]:          # square: stream in place
            return _mix_rows_blocked(W, Y)
        return _ref.gossip_mix_rows_ref(jnp.asarray(W, jnp.float32), Y)


# ---------------------------------------------------------------------------
# FlatLayout: the cached concat/split plan between pytrees and the bank
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Concat/split plan between a pytree and its flat (n, T) bank.

    Stores per-leaf trailing ``shapes`` (the device axis excluded),
    ``dtypes``, byte-order ``offsets``/``sizes`` into the flat axis, and
    the ``treedef`` — everything needed to materialize pytree views from
    the bank and to flatten trees into it. Built once per tree structure
    and memoized (:meth:`for_tree` / :meth:`for_stacked`), so repeated
    ``gossip_mix_tree`` calls and every ModelBank round re-use the same
    plan instead of rebuilding it per invocation."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]   # per-leaf shape, no device axis
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    total: int                            # T = sum(sizes)

    @property
    def segments(self) -> Tuple[Tuple[int, int], ...]:
        """Static (offset, size) per leaf — the per-leaf boundaries that
        flat-domain upload transforms (top-k, int8) preserve."""
        return tuple(zip(self.offsets, self.sizes))

    @property
    def row_nbytes(self) -> int:
        """Bytes of one f32 bank row (= one device model = one per-device
        bank shard of the sharded engine, and the |θ| multiplier in every
        boundary-traffic formula of docs/PERFORMANCE.md)."""
        return 4 * self.total

    # -- constructors (memoized) --------------------------------------------
    @classmethod
    def _build(cls, tree, strip_leading: bool) -> "FlatLayout":
        leaves, treedef = jax.tree.flatten(tree)
        shapes = tuple(tuple(l.shape[1:] if strip_leading else l.shape)
                       for l in leaves)
        dtypes = tuple(jnp.asarray(l).dtype for l in leaves)
        key = (treedef, shapes, dtypes)
        hit = _LAYOUT_CACHE.get(key)
        if hit is not None:
            return hit
        sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
        offsets = tuple(int(o) for o in np.cumsum((0,) + sizes[:-1]))
        layout = cls(treedef, shapes, dtypes, offsets, sizes,
                     int(sum(sizes)))
        _LAYOUT_CACHE[key] = layout
        return layout

    @classmethod
    def for_tree(cls, tree) -> "FlatLayout":
        """Layout of a single model pytree (no leading device axis)."""
        return cls._build(tree, strip_leading=False)

    @classmethod
    def for_stacked(cls, tree) -> "FlatLayout":
        """Layout of a device-stacked pytree: every leaf is (n, ...) and
        the leading axis is excluded from the plan."""
        return cls._build(tree, strip_leading=True)

    # -- single model <-> (T,) ----------------------------------------------
    def flatten_one(self, tree) -> jax.Array:
        """Pytree -> (T,) f32 row (the bank stores f32, as the mixing
        algebra always computed in f32)."""
        leaves = jax.tree.leaves(tree)
        return jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unflatten_one(self, vec: jax.Array):
        """(T,) -> pytree of per-leaf views (original shapes/dtypes)."""
        out = [vec[o:o + s].reshape(shape).astype(dt)
               for o, s, shape, dt in zip(self.offsets, self.sizes,
                                          self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, out)

    # -- stacked models <-> (n, T) ------------------------------------------
    def flatten_stack(self, tree) -> jax.Array:
        """Pytree of (n, ...) leaves -> (n, T) f32 bank."""
        leaves = jax.tree.leaves(tree)
        n = leaves[0].shape[0]
        return jnp.concatenate(
            [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1)

    def unflatten_stack(self, Y: jax.Array):
        """(n, T) bank -> pytree of (n, ...) leaves."""
        n = Y.shape[0]
        out = [Y[:, o:o + s].reshape((n,) + shape).astype(dt)
               for o, s, shape, dt in zip(self.offsets, self.sizes,
                                          self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, out)


_LAYOUT_CACHE: Dict[Any, FlatLayout] = {}


def gossip_mix_tree(W, params, *, block: int = 2048,
                    interpret: bool = False):
    """Row-apply W over the leading device axis of every leaf via one
    fused flattened pass (single HBM read/write of the whole stacked
    model). Matches :func:`repro.core.cefedavg.mix` for arbitrary W,
    including the asymmetric row-stochastic masked operators of
    ``core/scenario.py`` (previously this column-applied, which agreed
    only for symmetric W). The concat/split plan is cached per tree
    structure in a :class:`FlatLayout`."""
    layout = FlatLayout.for_stacked(params)
    flat = layout.flatten_stack(params)
    Wj = jnp.asarray(np.asarray(W), jnp.float32)
    mixed = gossip_mix_flat(Wj.T, flat, block=block, interpret=interpret)
    return layout.unflatten_stack(mixed)
