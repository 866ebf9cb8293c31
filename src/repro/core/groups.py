"""Communicator-group registry: named per-tier collective groups.

The two-tier device→edge→backhaul layout used to be hard-coded into the
collectives layer — replica-axis names, flat-index math, and
``axis_index_groups`` lists recomputed ad hoc wherever a mean or a gossip
round was needed. The :class:`GroupRegistry` builds that state ONCE per
``(FLConfig, Mesh)`` and exposes it by tier name (vLLM's
``parallel_state`` pattern): ``device`` (intra-cluster), ``edge``
(backhaul gossip), and arbitrary deeper tiers (``region``, ``tier3``,
…), each a :class:`TierGroups` with member lists, mean/gossip wrappers
over the flat replica axis, and a cached per-tier
:class:`~repro.core.gossip.GossipSchedule`. Engines query the registry
instead of recomputing group math inline, which is what makes depth>2
``TierMix`` lowerings and multi-host meshes possible without touching
the callers again.

Tier semantics (see :class:`repro.core.topology.Hierarchy`): a
``TierMix(level)`` averages each tier-``level`` device group, then (for
``level >= 1``) gossips among the ``num_siblings`` aggregation nodes
under each common parent — a block-diagonal mixing matrix
``kron(I, H_block)``, which the existing edge-colored
:class:`~repro.core.gossip.GossipSchedule` machinery lowers unchanged
because the groups are contiguous in the flat replica numbering.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.config import FLConfig
from repro.core import collectives as col
from repro.core import gossip as gsp
from repro.core import topology as topo


@dataclasses.dataclass(frozen=True)
class TierGroups:
    """One tier's communicator groups: ``members[g]`` lists the flat
    replica ids averaged together by ``TierMix(level)`` (contiguous under
    the static assignment), so ``len(members)`` groups of
    ``group_size`` replicas partition the mesh's flat replica axis."""
    name: str
    level: int
    num_groups: int
    group_size: int
    members: Tuple[Tuple[int, ...], ...]


class GroupRegistry:
    """Per-(FLConfig, Mesh) registry of tiered communicator groups.

    Built once (use :func:`get_registry` for the cached instance) and
    queried everywhere: ``tier(level_or_name)`` returns the
    :class:`TierGroups`, ``mean_in_body``/``gossip_in_body`` apply the
    tier's collective to a local shard inside an existing ``shard_map``
    body, ``mixing``/``operator`` expose the dense H_ℓ / (n, n) forms the
    dense engines and the clock consume, and ``gossip_schedule`` caches
    the edge-colored ppermute plan per ``(level, pi, mode)``.
    """

    def __init__(self, fl: FLConfig, mesh: Mesh):
        fl.validate()
        self.fl = fl
        self.mesh = mesh
        self.hier = topo.Hierarchy.from_config(fl)
        R = col.flat_axis_size(mesh)
        assert self.hier.n == R, (
            f"hierarchy has {self.hier.n} leaf devices but the mesh's "
            f"flat replica axis has {R}")
        tiers = []
        for lvl in range(self.hier.depth):
            ng = self.hier.num_groups(lvl)
            gs = self.hier.group_size(lvl)
            members = tuple(tuple(range(g * gs, (g + 1) * gs))
                            for g in range(ng))
            tiers.append(TierGroups(
                name=self.hier.tier_name(lvl), level=lvl,
                num_groups=ng, group_size=gs, members=members))
        self._tiers: Tuple[TierGroups, ...] = tuple(tiers)
        self._by_name: Dict[str, TierGroups] = {t.name: t for t in tiers}
        self._mixing: Dict[int, object] = {}
        self._scheds: Dict[Tuple[int, int, str], gsp.GossipSchedule] = {}

    # -- lookup -------------------------------------------------------------
    @property
    def depth(self) -> int:
        return self.hier.depth

    def tier(self, key: Union[int, str]) -> TierGroups:
        """The tier's groups, by level (int) or name ('device', 'edge',
        'region', 'tier<ℓ>')."""
        if isinstance(key, str):
            return self._by_name[key]
        return self._tiers[key]

    # -- dense forms (host-side numpy) --------------------------------------
    def mixing(self, level: int):
        """H_ℓ: the (num_nodes, num_nodes) block-diagonal Metropolis
        mixing matrix of tier ``level`` >= 1, cached."""
        if level not in self._mixing:
            self._mixing[level] = self.hier.mixing(
                level, self.fl.topology, self.fl.mixing, self.fl)
        return self._mixing[level]

    def operator(self, level: int, pi: int = 1):
        """Dense (n, n) ``TierMix(level, pi)`` operator under the static
        contiguous assignment (the legacy/flat engines' form)."""
        return self.hier.tier_operator(
            level, pi, self.fl.topology, self.fl.mixing, self.fl)

    def stale_operator(self, level: int, pi: int, phases, staleness: int,
                       advancing):
        """Dense ``TierMix(level, pi)`` operator gated for one async
        event: clusters in ``advancing`` apply the boundary reading only
        neighbors whose phase is within ``staleness`` of theirs; all
        other device rows are identity (see
        :func:`repro.core.gossip.staleness_mask`). Degenerates to
        :meth:`operator` when every cluster advances at one phase."""
        labels = np.repeat(np.arange(self.fl.num_clusters),
                           self.fl.devices_per_cluster)
        return gsp.staleness_mask(self.operator(level, pi), labels,
                                  phases, staleness, advancing)

    def faulted_operator(self, level: int, pi: int, cluster_down):
        """Dense ``TierMix(level, pi)`` operator degraded for an
        edge-outage round: dark clusters become identity rows and are
        dropped from surviving rows' reads, the deficit folded onto the
        diagonal (see :func:`repro.core.gossip.fault_gate`) — the tiered
        form of the per-op gating the plan-level ``FaultGate`` applies.
        Bitwise equal to :meth:`operator` when nothing is down."""
        labels = np.repeat(np.arange(self.fl.num_clusters),
                           self.fl.devices_per_cluster)
        return gsp.fault_gate(self.operator(level, pi), labels,
                              cluster_down)

    def gossip_schedule(self, level: int, pi: int,
                        mode: str = "rounds") -> gsp.GossipSchedule:
        """The tier's sparse ppermute plan: H_ℓ edge-colored into
        matchings over ``node_size(level)``-wide nodes; cached per
        ``(level, pi, mode)``. Block-diagonal H_ℓ colors into per-parent
        matchings that never cross parents."""
        key = (level, pi, mode)
        if key not in self._scheds:
            self._scheds[key] = gsp.GossipSchedule.build(
                self.mixing(level), pi, self.hier.node_size(level),
                mode=mode)
        return self._scheds[key]

    # -- collectives (inside an existing shard_map body) --------------------
    def mean_in_body(self, p, level: int):
        """Average the local f32 shard over this tier's groups (one
        grouped psum per leaf)."""
        t = self.tier(level)
        if t.group_size == 1:
            return p
        return gsp.group_mean_in_body(self.mesh, p, t.members)

    def gossip_in_body(self, p, level: int, pi: int,
                       mode: str = "rounds"):
        """π gossip rounds among tier-``level`` sibling nodes, applied to
        the local f32 shard via the tier's cached schedule."""
        return gsp.gossip_in_body(
            self.gossip_schedule(level, pi, mode), self.mesh, p)

    # -- collectives (standalone, on replica-stacked pytrees) ----------------
    def mean(self, params, specs, level: int):
        """Tier mean on replica-stacked params (leading axis R): wraps
        :meth:`mean_in_body` in its own ``shard_map``."""
        if self.tier(level).group_size == 1:
            return params

        def body(p):
            q = self.mean_in_body(
                jax.tree.map(lambda x: x.astype(jnp.float32), p), level)
            return jax.tree.map(lambda x, o: o.astype(x.dtype), p, q)
        return jax.shard_map(body, mesh=self.mesh, in_specs=(specs,),
                             out_specs=specs, check_vma=False)(params)

    def gossip(self, params, specs, level: int, pi: int,
               mode: str = "rounds"):
        """Tier gossip on replica-stacked params via the tier's cached
        schedule (see :func:`repro.core.gossip.apply_gossip`)."""
        return gsp.apply_gossip(
            self.gossip_schedule(level, pi, mode), params, specs,
            self.mesh)

    # -- introspection -------------------------------------------------------
    def describe(self) -> str:
        """Human-readable tier → group table (one line per tier)."""
        lines = []
        for t in self._tiers:
            lines.append(
                f"level {t.level} ({t.name}): {t.num_groups} groups × "
                f"{t.group_size} replicas")
        return "\n".join(lines)


_REGISTRY_CACHE: Dict[Tuple[FLConfig, Mesh], GroupRegistry] = {}


def get_registry(fl: FLConfig, mesh: Mesh) -> GroupRegistry:
    """The process-wide cached registry for ``(fl, mesh)`` — built once,
    shared by every engine touching the same config and mesh."""
    key = (fl, mesh)
    if key not in _REGISTRY_CACHE:
        _REGISTRY_CACHE[key] = GroupRegistry(fl, mesh)
    return _REGISTRY_CACHE[key]
