"""Flat ModelBank: the simulation engine's resident state as (n, T) buffers.

The paper-faithful engine materializes all n device models (eq. 10 stacks
them row-wise). Keeping that stack as a *pytree* of (n, ...) leaves makes
every mixing boundary L per-leaf contractions — each parameter block
re-read from HBM once per leaf — and forces ``gossip_mix_tree`` callers
to rebuild a concat/split plan per invocation. The ModelBank instead
keeps params, momentum and the error-feedback residual as single
contiguous ``(n, T)`` float32 buffers for the whole run; pytree views are
materialized only inside the per-device ``apply_fn`` call and at
checkpoint/eval edges, and every mixing boundary is one streaming pass of
:func:`repro.kernels.gossip_mix.gossip_mix_rows` (Pallas on TPU, a single
XLA gemm on CPU/GPU).

Cohort compaction (client sampling, ``core/scenario.py``): when only k of
n devices participate, the gradient/momentum work runs on a dense
``(k_pad, T)`` gather of the participating rows instead of a full-n vmap
with ``where``-frozen masked devices. ``k_pad`` is the cohort size
rounded up to a static bucket (:func:`cohort_buckets`) so the jitted
round compiles once per bucket, not once per cohort size; padding lanes
are filled with *distinct non-participating* rows and masked inactive, so
the scatter back into the bank writes disjoint rows deterministically.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gossip_mix import FlatLayout, gossip_mix_rows
from repro.kernels.ref import gossip_mix_rows_ref


class ModelBank:
    """Params / momentum / EF-residual of all n devices as (n, T) buffers.

    ``layout`` is the :class:`repro.kernels.gossip_mix.FlatLayout` of one
    device model; ``params``/``mom``/``residual`` are the flat buffers
    (``residual`` is None unless error-feedback compression is on). The
    buffers are plain attributes so the jitted round can donate them and
    the caller reassigns the outputs — peak memory stays ~1× the bank.
    """

    def __init__(self, layout: FlatLayout, n: int, params_row: jax.Array,
                 *, with_residual: bool = False):
        self.layout = layout
        self.n = n
        self.params = jnp.tile(params_row[None, :], (n, 1))
        self.mom = jnp.zeros((n, layout.total), jnp.float32)
        self.residual = (jnp.zeros((n, layout.total), jnp.float32)
                         if with_residual else None)

    @classmethod
    def from_model(cls, one_model, n: int, *,
                   with_residual: bool = False) -> "ModelBank":
        """Broadcast a single init model to all n rows (Algorithm 1's
        shared init, as the pytree engine does)."""
        layout = FlatLayout.for_tree(one_model)
        return cls(layout, n, layout.flatten_one(one_model),
                   with_residual=with_residual)

    @classmethod
    def from_model_sharded(cls, one_model, n: int, sharding, *,
                           with_residual: bool = False) -> "ModelBank":
        """Shared-init bank built per-shard via
        ``jax.make_array_from_callback``: each device fills only its own
        ``(rows_per_device, T)`` slice by broadcasting the host-side init
        row, so the full (n, T) bank is NEVER materialized on one device
        — the multi-host-correct init path (the old build-then-``place``
        route allocates the whole bank on the default device first)."""
        layout = FlatLayout.for_tree(one_model)
        self = cls.__new__(cls)
        self.layout = layout
        self.n = n
        T = layout.total
        row = np.asarray(layout.flatten_one(one_model), np.float32)

        def shard_rows(idx):
            nrows = len(range(*idx[0].indices(n)))
            return np.broadcast_to(row[idx[1]], (nrows,) + row[idx[1]].shape)

        def shard_zeros(idx):
            nrows = len(range(*idx[0].indices(n)))
            ncols = len(range(*idx[1].indices(T)))
            return np.zeros((nrows, ncols), np.float32)

        self.params = jax.make_array_from_callback((n, T), sharding,
                                                   shard_rows)
        self.mom = jax.make_array_from_callback((n, T), sharding,
                                                shard_zeros)
        self.residual = (jax.make_array_from_callback((n, T), sharding,
                                                      shard_zeros)
                         if with_residual else None)
        return self

    @classmethod
    def from_rows(cls, layout: FlatLayout, params_rows: np.ndarray,
                  mom_rows: np.ndarray, *, sharding=None) -> "ModelBank":
        """Wrap host-paged (S, T) rows as a hot slab bank (the streamed
        engine's per-round working set, ``core/clientstore.py``). With a
        ``sharding``, rows are placed per-shard via
        ``jax.make_array_from_callback`` so no single device ever holds
        the whole slab."""
        params_rows = np.asarray(params_rows, np.float32)
        mom_rows = np.asarray(mom_rows, np.float32)
        S, T = params_rows.shape
        assert T == layout.total and mom_rows.shape == (S, T)
        self = cls.__new__(cls)
        self.layout = layout
        self.n = S
        if sharding is None:
            self.params = jnp.asarray(params_rows)
            self.mom = jnp.asarray(mom_rows)
        else:
            self.params = jax.make_array_from_callback(
                (S, T), sharding, lambda idx: params_rows[idx])
            self.mom = jax.make_array_from_callback(
                (S, T), sharding, lambda idx: mom_rows[idx])
        self.residual = None
        return self

    @property
    def resident_nbytes(self) -> int:
        """Accelerator-resident bytes of the bank's buffers."""
        total = self.params.nbytes + self.mom.nbytes
        if self.residual is not None:
            total += self.residual.nbytes
        return int(total)

    def load_rows(self, params: np.ndarray, mom: np.ndarray,
                  residual=None) -> None:
        """Overwrite the resident (n, T) buffers from host arrays via
        per-shard placement: each device fills only its own row slice
        through ``jax.make_array_from_callback`` against the CURRENT
        buffer shardings, so a sharded bank restore
        (``RunCheckpoint``) never materializes the full bank on one
        device — the restore-side mirror of :meth:`from_model_sharded`.
        ``residual`` is required iff the bank carries one."""
        def put(host, like):
            a = np.asarray(host, np.float32)
            assert a.shape == like.shape, (a.shape, like.shape)
            return jax.make_array_from_callback(
                a.shape, like.sharding, lambda idx: a[idx])
        self.params = put(params, self.params)
        self.mom = put(mom, self.mom)
        if self.residual is not None:
            assert residual is not None, "bank carries an EF residual"
            self.residual = put(residual, self.residual)

    # -- placement -----------------------------------------------------------
    def place(self, sharding) -> None:
        """Re-place the resident buffers onto ``sharding`` — e.g. the
        sharded engine's row sharding ``NamedSharding(mesh, P(replica,
        None))``, under which each device holds its own contiguous
        ``(rows_per_device, T)`` bank shard for the whole run."""
        self.params = jax.device_put(self.params, sharding)
        self.mom = jax.device_put(self.mom, sharding)
        if self.residual is not None:
            self.residual = jax.device_put(self.residual, sharding)

    # -- pytree edges --------------------------------------------------------
    def params_tree(self):
        """Materialize the (n, ...)-leaved pytree view (eval/ckpt edge)."""
        return self.layout.unflatten_stack(self.params)

    def mean_model(self):
        """Device-average model as a pytree (the global model x̄)."""
        return self.layout.unflatten_one(jnp.mean(self.params, 0))

    def project(self, P):
        """Row-apply a rectangular (m, n) operator to the bank and
        materialize the resulting m models as a pytree — the edge-model
        projection P of eq. 11 in one streaming pass. A bank whose rows
        live on several devices projects through an XLA matmul, which
        the partitioner splits across them; a Pallas kernel cannot be
        partitioned."""
        P = jnp.asarray(P, jnp.float32)
        if len(self.params.sharding.device_set) > 1:
            out = gossip_mix_rows_ref(P, self.params)
        else:
            out = gossip_mix_rows(P, self.params)
        return self.layout.unflatten_stack(out)


# ---------------------------------------------------------------------------
# cohort compaction: static bucket sizes + padded gather plans
# ---------------------------------------------------------------------------

def cohort_buckets(n: int) -> Tuple[int, ...]:
    """Static cohort capacities: powers of two up to n, plus n itself.

    The compacted round is traced once per bucket (shapes are static
    under jit), so a scenario whose cohort size wanders round-to-round
    compiles at most ``len(cohort_buckets(n))`` variants instead of one
    per distinct cohort size."""
    assert n >= 1
    out = []
    b = 1
    while b < n:
        out.append(b)
        b <<= 1
    out.append(n)
    return tuple(out)


def bucket_for(k: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket capacity >= k."""
    for b in buckets:
        if b >= k:
            return b
    raise ValueError(f"cohort {k} exceeds largest bucket {buckets[-1]}")


@dataclasses.dataclass(frozen=True)
class CompactPlan:
    """Padded gather plan for one round's cohort.

    ``idx`` holds ``k_pad`` *distinct* device rows: the k participants
    first, then non-participants as inert padding; ``lane`` marks the
    real cohort lanes. Distinctness makes the scatter back into the bank
    (``bank.at[idx].set``) write disjoint rows — deterministic, and the
    padding lanes write back their untouched values."""
    idx: np.ndarray     # (k_pad,) int32, distinct
    lane: np.ndarray    # (k_pad,) bool
    k: int              # true cohort size
    k_pad: int          # bucket capacity


def compact_plan(mask: np.ndarray,
                 buckets: Optional[Tuple[int, ...]] = None) -> CompactPlan:
    """Build the padded cohort gather plan for a 0/1 participation mask."""
    mask = np.asarray(mask)
    n = mask.shape[0]
    if buckets is None:
        buckets = cohort_buckets(n)
    cohort = np.nonzero(mask > 0)[0]
    k = int(cohort.shape[0])
    assert k >= 1, "compact_plan needs at least one participant"
    k_pad = bucket_for(k, buckets)
    pad = k_pad - k
    if pad:
        complement = np.nonzero(mask <= 0)[0]
        cohort = np.concatenate([cohort, complement[:pad]])
    lane = np.zeros(k_pad, bool)
    lane[:k] = True
    return CompactPlan(cohort.astype(np.int32), lane, k, k_pad)
