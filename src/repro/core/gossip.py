"""Topology-general sparse gossip schedules for the sharded trainer.

Replaces the ring-only ``sparse_gossip``/``cluster_ring_mix`` pair: for ANY
connected backhaul graph (ring, torus, star, complete, erdos_renyi, …) a
:class:`GossipSchedule` precomputes host-side a sequence of replica-level
``ppermute`` permutations plus per-cluster weight tables that realize either

- ``rounds`` (gossip_impl="sparse"): π applications of the mixing matrix H.
  The directed edge set of H is greedily colored into partial matchings
  (no two edges in a matching share a source or a destination), so each
  matching is a valid ``ppermute`` — unmatched receivers get zeros, which
  the weight table also zeroes. One gossip round is
  ``y_c = H[c,c]·x_c + Σ_k W_k[c]·recv_k(x)`` and moves deg(c)·|θ|
  neighbor bytes per replica.
- ``exact`` (gossip_impl="ringweight"): the exact operator H^π in M−1
  weighted cyclic rotations of the cluster models — each replica rotates
  its buffer one cluster step at a time and accumulates
  ``Σ_s H^π[(c+s)%M, c]·buf`` on the fly: (M−1)·|θ| neighbor bytes,
  bit-identical to the dense operator for any H (H^π is just a table).

Both run on the FLAT replica axis (``pod`` × ``data`` as one tuple axis, see
``core.collectives``), so multi-pod edge crossings need no special casing:
a cluster permutation is a replica permutation, wherever the replicas live.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import collectives as col


# ---------------------------------------------------------------------------
# host-side schedule construction
# ---------------------------------------------------------------------------

def staleness_mask(W: np.ndarray, labels: np.ndarray, phases: np.ndarray,
                   staleness: int, advancing: np.ndarray) -> np.ndarray:
    """Gate a dense (n, n) mixing operator for ONE async event.

    In bounded-staleness execution (``FLSimulator.step_round_async``) a
    mixing boundary fires per *cluster* as soon as that cluster's own
    block clears. ``advancing`` marks the clusters applying this
    boundary: every other device row becomes the identity (their models
    are frozen until their own boundary fires). ``phases`` counts blocks
    completed per cluster; advancing rows additionally drop columns of
    clusters whose phase lags (or leads) the advancing phase by more
    than ``staleness``, folding the removed mass onto the diagonal so
    rows stay stochastic — reading a neighbor within the bound is the
    whole point of async (a bounded-stale read), reading past it is
    forbidden.

    When every cluster advances at one common phase (the s = 0 barrier
    degeneracy) the operator is returned unchanged, bit for bit — the
    correctness anchor ``tests/test_async.py`` leans on."""
    labels = np.asarray(labels)
    phases = np.asarray(phases)
    adv = np.asarray(advancing, bool)
    if adv.all() and (phases == phases[0]).all():
        return np.asarray(W, np.float32)
    n = W.shape[0]
    Wm = np.array(W, np.float32, copy=True)
    p = int(phases[adv][0]) if adv.any() else 0
    keep_col = (np.abs(phases - p) <= staleness)[labels]     # (n,)
    row_adv = adv[labels]                                    # (n,)
    Wm = np.where(keep_col[None, :], Wm, 0.0)
    Wm[~row_adv] = np.eye(n, dtype=np.float32)[~row_adv]
    deficit = np.where(row_adv,
                       np.asarray(W, np.float64).sum(1) - Wm.sum(1), 0.0)
    Wm[np.arange(n), np.arange(n)] += deficit.astype(np.float32)
    return Wm


def fault_gate(W: np.ndarray, labels: np.ndarray,
               cluster_down: np.ndarray) -> np.ndarray:
    """Gate a dense (n, n) mixing operator for edge-server outages.

    ``cluster_down`` marks clusters whose edge server is dark this
    round (``FaultModel.outage windows``): their device rows become the
    identity (the cluster's models are frozen until it recovers) and
    every surviving row drops the dark clusters' columns, folding the
    removed mass onto its diagonal — exactly the
    :func:`staleness_mask` construction with the dark clusters pushed
    out of the staleness bound, so the result is row-stochastic by the
    same argument. With no cluster down the operator is returned
    unchanged, bit for bit (the fault-free parity anchor).

    Recovery needs no special casing: a cluster that comes back simply
    stops being gated and rejoins the next boundary (in async mode,
    through the existing staleness-bounded catch-up path)."""
    down = np.asarray(cluster_down, bool)
    if not down.any():
        return np.asarray(W, np.float32)
    phases = np.where(down, -1, 0)
    return staleness_mask(W, labels, phases, staleness=0,
                          advancing=~down)


def color_edges(adj: np.ndarray) -> List[Dict[int, int]]:
    """Partition the directed edge set into partial matchings.

    Greedy bipartite edge coloring: each color (matching) maps dst -> src
    with all sources distinct and all destinations distinct, so it lowers
    to one ``ppermute``. Uses at most 2·Δ−1 colors (König's bound is Δ;
    greedy is within 2×, which only affects the *number* of ppermutes, not
    the bytes moved — every directed edge appears exactly once overall).
    """
    m = adj.shape[0]
    edges = [(i, j) for i in range(m) for j in range(m)
             if i != j and adj[i, j]]
    colors: List[Dict[int, int]] = []   # dst -> src
    used_src: List[set] = []
    for (i, j) in edges:
        for k in range(len(colors)):
            if i not in used_src[k] and j not in colors[k]:
                colors[k][j] = i
                used_src[k].add(i)
                break
        else:
            colors.append({j: i})
            used_src.append({i})
    return colors


def _replica_perm(matching: Dict[int, int], dpc: int
                  ) -> Tuple[Tuple[int, int], ...]:
    """Cluster-level matching -> flat replica-level (src, dst) pairs."""
    return tuple((src * dpc + t, dst * dpc + t)
                 for dst, src in sorted(matching.items())
                 for t in range(dpc))


@dataclasses.dataclass(frozen=True)
class GossipSchedule:
    """Host-precomputed permutation + weight plan for one (H, π, geometry)."""
    mode: str                         # "rounds" | "exact"
    num_clusters: int                 # M
    devices_per_cluster: int          # dpc
    pi: int
    w_self: np.ndarray                # (M,)  diag of H            [rounds]
    perms: Tuple[Tuple[Tuple[int, int], ...], ...]  # K replica perms [rounds]
    weights: np.ndarray               # (K, M) weight per dst cluster[rounds]
    h_pi: np.ndarray                  # (M, M) H^π                  [exact]
    degrees: np.ndarray               # (M,) backhaul degree per cluster

    @staticmethod
    def build(H: np.ndarray, pi: int, devices_per_cluster: int,
              mode: str = "rounds") -> "GossipSchedule":
        assert mode in ("rounds", "exact"), mode
        H = np.asarray(H, np.float64)
        M = H.shape[0]
        adj = (np.abs(H) > 1e-12) & ~np.eye(M, dtype=bool)
        assert np.allclose(H, H.T), "mixing matrix must be symmetric"
        matchings = color_edges(adj)
        K = len(matchings)
        weights = np.zeros((max(K, 1), M))
        for k, mt in enumerate(matchings):
            for dst, src in mt.items():
                weights[k, dst] = H[src, dst]
        perms = tuple(_replica_perm(mt, devices_per_cluster)
                      for mt in matchings)
        return GossipSchedule(
            mode=mode, num_clusters=M,
            devices_per_cluster=devices_per_cluster, pi=pi,
            w_self=np.diag(H).copy(), perms=perms, weights=weights,
            h_pi=np.linalg.matrix_power(H, pi),
            degrees=adj.sum(1).astype(np.int64))

    # -- traffic accounting (used by benchmarks and the runtime model) ------
    @property
    def num_matchings(self) -> int:
        return len(self.perms)

    def models_received_per_replica(self) -> int:
        """Worst-case neighbor models received by one replica per
        inter-cluster aggregation (the |θ| multiplier)."""
        if self.num_clusters == 1:
            return 0
        if self.mode == "exact":
            return self.num_clusters - 1
        return int(self.pi * self.degrees.max())

    def models_received_total(self, num_replicas: int) -> int:
        """Network-wide models moved per inter-cluster aggregation."""
        if self.num_clusters == 1:
            return 0
        dpc = self.devices_per_cluster
        if self.mode == "exact":
            return (self.num_clusters - 1) * num_replicas
        return int(self.pi * self.degrees.sum() * dpc)

    # -- reference reconstruction (tested host-side) ------------------------
    def dense_equivalent(self) -> np.ndarray:
        """The M×M cluster operator this schedule applies (for parity
        tests): H for one round of ``rounds`` mode, H^π for ``exact``."""
        M = self.num_clusters
        if self.mode == "exact":
            return self.h_pi.copy()
        op = np.diag(self.w_self)
        for k, perm_k in enumerate(self.perms):
            for src_r, dst_r in perm_k:
                src_c = src_r // self.devices_per_cluster
                dst_c = dst_r // self.devices_per_cluster
                if src_r % self.devices_per_cluster == 0:
                    op[src_c, dst_c] += self.weights[k, dst_c]
        return op


# ---------------------------------------------------------------------------
# device-side application (inside an existing shard_map body or standalone)
# ---------------------------------------------------------------------------

def _unrolled() -> bool:
    from repro.flags import analysis_mode
    return analysis_mode()


def gossip_in_body(sched: GossipSchedule, mesh: Mesh, p):
    """Apply the schedule to the LOCAL shard ``p`` (pytree of f32 leaves)
    inside an existing ``shard_map`` body.

    This is the reusable core of :func:`apply_gossip`: the pytree trainer
    wraps it in its own shard_map, and the sharded ModelBank engine
    (``core.sharded.ShardedBankCEFedAvg``) calls it on bank-row shards to
    fuse the π gossip rounds into the same pass as the intra-cluster psum
    — O(π·deg·|row|) neighbor ``ppermute`` traffic, the full bank never
    materialized on one device."""
    M = sched.num_clusters
    if M == 1:
        return p
    dpc = sched.devices_per_cluster
    R = col.flat_axis_size(mesh)
    assert R == M * dpc, (R, M, dpc)

    if sched.mode == "exact":
        h_pi = jnp.asarray(sched.h_pi, jnp.float32)
        rot = col.rotate_perm(mesh, dpc)
        c = col.flat_axis_index(mesh) // dpc
        buf = p
        acc = jax.tree.map(lambda b: h_pi[c, c] * b, buf)
        for s in range(1, M):
            buf = jax.tree.map(
                lambda b: col.ppermute(b, mesh, rot), buf)
            w = h_pi[(c + s) % M, c]
            acc = jax.tree.map(lambda a, b: a + w * b, acc, buf)
        return acc

    w_self = jnp.asarray(sched.w_self, jnp.float32)
    w_tbl = jnp.asarray(sched.weights, jnp.float32)
    perms = sched.perms
    c = col.flat_axis_index(mesh) // dpc
    ws = w_self[c]
    wk = w_tbl[:, c]

    def gossip_step(_, q):
        def leaf(xf):
            acc = ws * xf
            for k, perm_k in enumerate(perms):
                acc = acc + wk[k] * col.ppermute(xf, mesh, perm_k)
            return acc
        return jax.tree.map(leaf, q)

    if _unrolled():   # unroll so cost_analysis counts every step
        q = p
        for i in range(sched.pi):
            q = gossip_step(i, q)
        return q
    return jax.lax.fori_loop(0, sched.pi, gossip_step, p)


def apply_gossip(sched: GossipSchedule, params, specs, mesh: Mesh):
    """Apply the schedule to replica-stacked params (leading axis R)."""
    if sched.num_clusters == 1:
        return params

    def body(p):
        q = gossip_in_body(
            sched, mesh, jax.tree.map(lambda x: x.astype(jnp.float32), p))
        return jax.tree.map(lambda x, o: o.astype(x.dtype), p, q)

    return jax.shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                         check_vma=False)(params)


def group_mean_in_body(mesh: Mesh, p, groups):
    """Mean of the LOCAL f32 shard over arbitrary equal-size replica
    groups inside an existing ``shard_map`` body: one grouped psum per
    leaf over the flat replica axis. ``groups`` is a partition of the
    replica ids (e.g. a :class:`repro.core.groups.TierGroups` member
    list); each group averages over its own members only."""
    size = len(groups[0])
    if size == 1:
        return p
    groups = [list(g) for g in groups]
    inv = 1.0 / size
    return jax.tree.map(
        lambda x: col.psum_groups(x, mesh, groups) * inv, p)


def cluster_mean_in_body(mesh: Mesh, p, num_clusters: int,
                         devices_per_cluster: int):
    """Intra-cluster averaging of the LOCAL f32 shard inside an existing
    ``shard_map`` body: one grouped psum per leaf over the flat replica
    axis (eq. 11's V restricted to this shard). Shared by
    :func:`apply_cluster_mean` and the sharded ModelBank engine's fused
    τ/qτ boundary. Thin wrapper over :func:`group_mean_in_body` with the
    contiguous per-cluster partition."""
    dpc = devices_per_cluster
    if dpc == 1:
        return p
    groups = [tuple(range(c * dpc, (c + 1) * dpc))
              for c in range(num_clusters)]
    return group_mean_in_body(mesh, p, groups)


def apply_cluster_mean(params, specs, mesh: Mesh, num_clusters: int,
                       devices_per_cluster: int):
    """Intra-cluster averaging via grouped psum on the flat replica axis."""
    if devices_per_cluster == 1:
        return params

    def body(p):
        q = cluster_mean_in_body(
            mesh, jax.tree.map(lambda x: x.astype(jnp.float32), p),
            num_clusters, devices_per_cluster)
        return jax.tree.map(lambda x, o: o.astype(x.dtype), p, q)
    return jax.shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                         check_vma=False)(params)


def dense_mix_rows(W: jax.Array, x: jax.Array, mesh: Mesh) -> jax.Array:
    """Row-apply an ARBITRARY dense (R, R) operator to per-device bank
    rows inside an existing ``shard_map`` body, without ever gathering the
    (R, T) bank: R−1 weighted cyclic rotations accumulate
    ``y_d = Σ_j W[d, j]·x_j`` on the fly (the ``ringweight`` lowering
    generalized to asymmetric row-stochastic operators — the
    masked/mobility W_t of ``core.scenario``). ``x`` is this device's f32
    row(s) ``(1, T)``; ``W`` is replicated. Traffic: (R−1)·|row| neighbor
    bytes per device per boundary."""
    R = col.flat_axis_size(mesh)
    my = col.flat_axis_index(mesh)
    if R == 1:
        return W[0, 0] * x
    rot = col.rotate_perm(mesh, 1)
    Wf = W.astype(jnp.float32)

    def step(s, carry):
        acc, buf = carry
        buf = col.ppermute(buf, mesh, rot)
        acc = acc + Wf[my, (my + s) % R] * buf
        return acc, buf

    init = (Wf[my, my] * x, x)
    if _unrolled():
        acc, buf = init
        for s in range(1, R):
            acc, buf = step(s, (acc, buf))
    else:
        acc, buf = jax.lax.fori_loop(1, R, step, init)
    return acc
