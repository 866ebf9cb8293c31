"""Replica-axis helpers for the sparse aggregation backends.

The backends run inside ``jax.shard_map`` bodies and treat the mesh's
replica axes (``pod`` × ``data``) as ONE flattened logical axis: JAX
collectives accept a tuple of axis names, with the flat index being
``pod_idx * data_size + data_idx`` — exactly the replica numbering of
``ReplicaGeometry``. Working on the flat axis lets a single ``ppermute``
express any replica permutation, including multi-pod edge crossings,
with no per-topology special cases.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import Mesh


def replica_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes carrying federated replicas, major-to-minor."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def flat_axis_size(mesh: Mesh) -> int:
    out = 1
    for a in replica_axis_names(mesh):
        out *= mesh.shape[a]
    return out


def flat_axis_index(mesh: Mesh) -> jax.Array:
    """Flattened replica index inside a shard_map body.

    Equals ``pod_idx * data_size + data_idx`` on a multi-pod mesh, i.e. the
    global replica id of ``ReplicaGeometry``.
    """
    names = replica_axis_names(mesh)
    idx = None
    for a in names:
        i = jax.lax.axis_index(a)
        idx = i if idx is None else idx * mesh.shape[a] + i
    assert idx is not None, "mesh has no replica axes"
    return idx


def ppermute(x: jax.Array, mesh: Mesh,
             perm: Sequence[Tuple[int, int]]) -> jax.Array:
    """Permute over the flat replica axis; unmatched receivers get zeros."""
    return jax.lax.ppermute(x, replica_axis_names(mesh), perm=list(perm))


def rotate_perm(mesh: Mesh, shift: int = 1) -> Tuple[Tuple[int, int], ...]:
    """Cyclic (src, dst) pairs on the flat replica axis: after one
    application of the returned perm, device d holds what device
    ``(d + shift) % R`` held — the building block of the weighted-rotation
    mixes (``core.gossip.dense_mix_rows`` and the ``ringweight`` backend).
    """
    R = flat_axis_size(mesh)
    return tuple(((d + shift) % R, d) for d in range(R))


def psum_groups(x: jax.Array, mesh: Mesh,
                groups: Sequence[Sequence[int]]) -> jax.Array:
    """Grouped psum over the flat replica axis (flat replica ids)."""
    return jax.lax.psum(x, replica_axis_names(mesh),
                        axis_index_groups=[list(g) for g in groups])
