"""Production CE-FedAvg trainer: stacked federated replicas on a TPU mesh.

Parameters/optimizer state carry a leading replica axis R sharded over the
mesh's replica axes (``pod`` × ``data``); the ``model`` axis is tensor
parallel *within* a replica. One ``global_round`` = q edge rounds of
(τ local SGD steps + intra-cluster averaging) followed by π gossip steps of
inter-cluster mixing — a literal, sharded implementation of eq. (10)/(11).

Three aggregation backends (see ``core.gossip`` for the sparse two):
- ``dense``      (paper-faithful baseline): the full W_t operators applied
  as a (R,R)·(R,…) contraction over the replica axis — XLA lowers this to
  all-gathers over the replica axes.
- ``sparse``     (beyond-paper optimized): shard_map with
  ``psum(axis_index_groups=clusters)`` for V and π gossip rounds of
  weighted neighbor ``ppermute`` matchings realizing H on ANY connected
  backhaul graph — O(π·deg·|θ|) neighbor traffic and O(|θ|) peak memory
  instead of O(R·|θ|).
- ``ringweight`` (beyond-paper optimized): the exact H^π in M−1 weighted
  cyclic rotations — (M−1)·|θ| neighbor traffic, any topology.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import sharding as sh
from repro.config import ExperimentConfig, FLConfig
from repro.core import collectives as col
from repro.core import gossip as gsp
from repro.core import program as prg
from repro.core.cefedavg import FLSimulator, make_w_schedule, mix
from repro.core.groups import get_registry
from repro.core.modelbank import ModelBank
from repro.models import model as mdl
from repro.optim import make_optimizer, make_lr_schedule
from repro.optim.optimizers import apply_updates


# ---------------------------------------------------------------------------
# replica geometry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplicaGeometry:
    num_replicas: int          # R
    num_clusters: int          # M (global)
    devices_per_cluster: int
    clusters_per_pod: int
    num_pods: int

    @staticmethod
    def build(fl: FLConfig, mesh: Mesh) -> "ReplicaGeometry":
        data = mesh.shape["data"]
        pods = mesh.shape.get("pod", 1)
        R = data * pods
        M = fl.num_clusters
        assert R % M == 0, f"{R} replicas not divisible into {M} clusters"
        dpc = R // M
        assert data % dpc == 0, "clusters must not span pods"
        return ReplicaGeometry(R, M, dpc, data // dpc, pods)

    def cluster_of(self, r: int) -> int:
        return r // self.devices_per_cluster


# ---------------------------------------------------------------------------
# abstract init + logical axes (no allocation — works for 123B params)
# ---------------------------------------------------------------------------

def abstract_model(model_cfg):
    """(param ShapeDtypeStructs, logical axes) without allocating."""
    box = []

    def f(k):
        p, logical = mdl.init_model(k, model_cfg)
        box.append(logical)
        return p
    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box[0]


def stacked_abstract(model_cfg, R: int):
    shapes, logical = abstract_model(model_cfg)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((R,) + tuple(s.shape), s.dtype),
        shapes)
    logical = sh.prepend_axis(logical, "replica")
    return stacked, logical


# ---------------------------------------------------------------------------
# sparse aggregation backends — see core.gossip for the schedule machinery
# ---------------------------------------------------------------------------

def sparse_intra_mix(params, specs, mesh: Mesh, geo: ReplicaGeometry):
    """Intra-cluster averaging (V) via grouped psum on the replica axis."""
    return gsp.apply_cluster_mean(params, specs, mesh, geo.num_clusters,
                                  geo.devices_per_cluster)


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

class ShardedCEFedAvg:
    """Builds jittable FL step functions + shardings for one experiment."""

    def __init__(self, exp: ExperimentConfig, mesh: Mesh,
                 loss_fn: Optional[Callable] = None):
        self.exp = exp
        self.mesh = mesh
        self.geo = ReplicaGeometry.build(exp.fl, mesh)
        self.fl = dataclasses.replace(
            exp.fl, devices_per_cluster=self.geo.devices_per_cluster)
        self.sched = make_w_schedule(self.fl)
        self.model_cfg = exp.model
        self.loss_fn = loss_fn or (
            lambda p, b: mdl.lm_loss(self.model_cfg, p, b,
                                     remat=exp.train.remat))
        self.opt_init, self.opt_update = make_optimizer(exp.train)
        self.lr_fn = make_lr_schedule(exp.train)
        impl = exp.fl.gossip_impl
        # communicator groups: built once per (fl, mesh) and queried for
        # every tiered collective (means, gossip schedules)
        self.registry = get_registry(self.fl, mesh)
        self.gossip_schedule: Optional[gsp.GossipSchedule] = None
        if impl in ("sparse", "ringweight") and \
                self.fl.algorithm in ("ce_fedavg", "dec_local_sgd"):
            self.gossip_schedule = self.registry.gossip_schedule(
                1, self.fl.pi,
                mode="exact" if impl == "ringweight" else "rounds")
        self._build_specs()

    # -- specs ---------------------------------------------------------------
    def _build_specs(self):
        R = self.geo.num_replicas
        self.param_shapes, self.param_logical = stacked_abstract(
            self.model_cfg, R)
        self.param_specs = sh.resolve_specs(
            self.param_shapes, self.param_logical, self.mesh)
        opt_shapes = jax.eval_shape(
            lambda p: jax.vmap(self.opt_init)(p), self.param_shapes)
        # opt leaves mirror params (plus scalar counters -> replicate)
        self.opt_shapes = opt_shapes
        self.opt_specs = self._opt_specs(opt_shapes)

    def _opt_specs(self, opt_shapes):
        pleaves = {tuple(s.shape): spec for s, spec in zip(
            jax.tree.leaves(self.param_shapes),
            jax.tree.leaves(self.param_specs,
                            is_leaf=lambda x: isinstance(x, P)))}

        def one(s):
            return pleaves.get(tuple(s.shape), P())
        return jax.tree.map(one, opt_shapes)

    # -- init ----------------------------------------------------------------
    def init_fn(self):
        R = self.geo.num_replicas

        def init(key):
            keys = jax.random.split(key, R)
            params = jax.vmap(
                lambda k: mdl.init_model(k, self.model_cfg)[0])(keys)
            opt = jax.vmap(self.opt_init)(params)
            return params, opt
        return init

    # -- mixing --------------------------------------------------------------
    def _intra(self, params):
        if self.fl.algorithm == "fedavg":
            return params  # cloud FedAvg: no intra-cluster boundary
        if self.exp.fl.gossip_impl in ("sparse", "ringweight"):
            return self.registry.mean(params, self.param_specs, 0)
        return mix(self.sched.W_intra, params)

    def _inter(self, params):
        if self.gossip_schedule is not None:
            params = self.registry.mean(params, self.param_specs, 0)
            impl = self.exp.fl.gossip_impl
            return self.registry.gossip(
                params, self.param_specs, 1, self.fl.pi,
                mode="exact" if impl == "ringweight" else "rounds")
        return mix(self.sched.W_inter, params)

    # -- the steps -----------------------------------------------------------
    def make_global_round(self):
        """fn(params, opt_state, batch, step) -> (params, opt, metrics, step)

        batch: dict of arrays with leading (q, tau, R, ...) dims.
        """
        fl = self.fl
        loss_fn = self.loss_fn

        def replica_loss(params, mb):
            losses = jax.vmap(loss_fn)(params, mb)
            return jnp.sum(losses), losses

        grad_fn = jax.value_and_grad(replica_loss, has_aux=True)

        def local_step(carry, mb):
            params, opt, step = carry
            (_, losses), grads = grad_fn(params, mb)
            lr = self.lr_fn(step)
            upd, opt = jax.vmap(
                self.opt_update, in_axes=(0, 0, 0, None)
            )(grads, opt, params, lr)
            params = apply_updates(params, upd)
            return (params, opt, step + 1), jnp.mean(losses)

        def edge_round(carry, ebatch):
            carry, losses = jax.lax.scan(local_step, carry, ebatch)
            params, opt, step = carry
            params = self._intra(params)
            return (params, opt, step), losses

        def global_round(params, opt, batch, step):
            (params, opt, step), losses = jax.lax.scan(
                edge_round, (params, opt, step), batch)
            params = self._inter(params)
            return params, opt, {"loss": jnp.mean(losses)}, step

        return global_round

    # -- component steps (analysis-mode lowering units) -----------------------
    def make_local_step(self):
        """One local SGD step on one microbatch (R,B,...); no mixing."""
        loss_fn = self.loss_fn

        def replica_loss(params, mb):
            losses = jax.vmap(loss_fn)(params, mb)
            return jnp.sum(losses), losses

        grad_fn = jax.value_and_grad(replica_loss, has_aux=True)

        def local_step(params, opt, mb, step):
            (_, losses), grads = grad_fn(params, mb)
            lr = self.lr_fn(step)
            upd, opt = jax.vmap(
                self.opt_update, in_axes=(0, 0, 0, None)
            )(grads, opt, params, lr)
            params = apply_updates(params, upd)
            return params, opt, jnp.mean(losses), step + 1
        return local_step

    def make_intra_fn(self):
        return lambda params: self._intra(params)

    def make_inter_fn(self):
        return lambda params: self._inter(params)

    def microbatch_specs(self, mb_shapes) -> Any:
        """Specs for (R, B, ...) microbatches."""
        raxes = sh.replica_axes(self.mesh)
        rspec = tuple(raxes) if len(raxes) > 1 else (raxes[0] if raxes
                                                     else None)

        def one(s):
            return P(rspec, *([None] * (len(s.shape) - 1)))
        return jax.tree.map(one, mb_shapes)

    # -- sharding helpers for jit --------------------------------------------
    def batch_specs(self, batch_shapes) -> Any:
        """Specs for (q, tau, R, B, ...) batches: R over replica axes."""
        raxes = sh.replica_axes(self.mesh)
        rspec = tuple(raxes) if len(raxes) > 1 else (raxes[0] if raxes
                                                     else None)

        def one(s):
            return P(None, None, rspec, *([None] * (len(s.shape) - 3)))
        return jax.tree.map(one, batch_shapes)

    def in_shardings(self, batch_shapes):
        ns = lambda t: jax.tree.map(  # noqa: E731
            lambda p: NamedSharding(self.mesh, p), t,
            is_leaf=lambda x: isinstance(x, P))
        return (ns(self.param_specs), ns(self.opt_specs),
                ns(self.batch_specs(batch_shapes)),
                NamedSharding(self.mesh, P()))

    def out_shardings(self):
        ns = lambda t: jax.tree.map(  # noqa: E731
            lambda p: NamedSharding(self.mesh, p), t,
            is_leaf=lambda x: isinstance(x, P))
        return (ns(self.param_specs), ns(self.opt_specs),
                NamedSharding(self.mesh, P()),
                NamedSharding(self.mesh, P()))


# ---------------------------------------------------------------------------
# sharded ModelBank engine: device-parallel flat-bank CE-FedAvg
# ---------------------------------------------------------------------------

class ShardedBankCEFedAvg(FLSimulator):
    """Device-parallel flat-bank CE-FedAvg: the :class:`FLSimulator`
    ModelBank engine with the ``(n, T)`` bank row-sharded over the mesh's
    replica axes (``pod`` × ``data``) — one bank row (one paper device
    model) per mesh device, for the whole run.

    Params, momentum and the EF-residual live as contiguous per-device
    ``(1, T)`` bank shards; the jitted global round is ONE ``shard_map``
    whose q·τ local SGD steps run on the local row (pytree views exist
    only inside the per-row ``apply_fn`` call) and whose mixing
    boundaries never materialize the bank on one device:

    - **static schedule** (no scenario, ``ce_fedavg``): intra-cluster
      averaging is a grouped ``psum`` over the cluster's rows
      (:func:`repro.core.gossip.cluster_mean_in_body`); the coincident
      τ/qτ boundary fuses that psum with π gossip rounds of
      :class:`repro.core.gossip.GossipSchedule`'s edge-colored
      ``ppermute`` matchings in the same pass
      (:func:`repro.core.gossip.gossip_in_body`) — O(π·deg·T) neighbor
      traffic, mirroring the fused single-pass
      ``gossip_mix_rows(W_inter @ W_intra, ·)`` boundary of the
      single-device bank.
    - **scenario rounds** (masked / mobility / non-gossip baselines): the
      exact per-round dense operators are row-applied by R−1 weighted
      cyclic rotations (:func:`repro.core.gossip.dense_mix_rows`), which
      handles arbitrary asymmetric row-stochastic W_t.

    The legacy per-leaf pytree trainer (:class:`ShardedCEFedAvg`, and
    ``FLSimulator(bank=False)`` on one device) stays as the parity
    oracle. Semantics — key schedule, batch draws, SGD+momentum updates,
    mixing algebra — match the single-device ModelBank engine row for
    row, so trajectories agree to float tolerance (asserted in
    ``tests/test_sharded_bank.py``).

    Tiered collectives come from the :class:`repro.core.groups.
    GroupRegistry` built once for ``(fl, mesh)``: any ``TierMix(ℓ)`` —
    ``IntraMix`` (tier 0), ``InterGossip`` (tier 1), or deeper tiers of
    an ``fl.hierarchy`` like (2, 2, 2) — lowers to that tier's grouped
    psum plus its cached block-diagonal gossip matchings, so a depth-3
    round still contains no all-gather.

    Constraints: ``fl.n`` must equal the replica-axis device count (one
    row per device), and any ``model`` mesh axis must have size 1 (bank
    rows are not tensor-parallel). The never-materialize guarantee
    covers init as well as the steady-state round: the bank is built
    per-shard via ``ModelBank.from_model_sharded``
    (``jax.make_array_from_callback``), each device filling only its own
    ``(1, T)`` rows — the multi-host-correct path. Checkpoint *restore*
    keeps the same guarantee: ``RunCheckpoint`` writes the buffers back
    through :meth:`ModelBank.load_rows`, which fills each device's row
    shard against the resident sharding. Fault injection
    (``ScenarioConfig.faults``) needs no sharded special-casing either:
    a scenario engine forces the dense-operator path (``structured``
    False below), so outage-gated / link-degraded mixing matrices flow
    through ``dense_mix_rows`` like any other row-stochastic operator.
    """

    def __init__(self, init_fn: Callable, apply_fn: Callable, fl, data,
                 mesh: Mesh, **kw):
        assert kw.pop("bank", True), \
            "ShardedBankCEFedAvg IS the bank engine; use FLSimulator or " \
            "ShardedCEFedAvg for the pytree engines"
        self.mesh = mesh
        raxes = col.replica_axis_names(mesh)
        assert raxes, f"mesh {mesh.axis_names} has no replica axes"
        R = col.flat_axis_size(mesh)
        assert fl.n == R, \
            f"need one bank row per replica device: n={fl.n}, devices={R}"
        if "model" in mesh.axis_names:
            assert mesh.shape["model"] == 1, \
                "bank rows are not tensor-parallel (model axis must be 1)"
        self._rspec = raxes if len(raxes) > 1 else raxes[0]
        self._row_sharding = NamedSharding(mesh, P(self._rspec, None))
        self.registry = get_registry(fl, mesh)
        placed = {}
        for key, v in data.items():
            spec = P(self._rspec) if key in ("xs", "ys") else P()
            placed[key] = jax.device_put(jnp.asarray(v),
                                         NamedSharding(mesh, spec))
        super().__init__(init_fn, apply_fn, fl, placed, bank=True, **kw)
        # rows are pinned to devices: no cohort compaction; scenario
        # rounds run mask-frozen on the full (sharded) bank instead
        self._compact_enabled = False

    def _make_bank(self, one, n: int, with_residual: bool) -> ModelBank:
        """Per-shard init: each device fills its own bank rows directly
        (``jax.make_array_from_callback``); the full (n, T) bank never
        exists on one device, init included."""
        return ModelBank.from_model_sharded(
            one, n, self._row_sharding, with_residual=with_residual)

    # -- the sharded round ---------------------------------------------------
    def _lower_compact(self, program):
        """Never dispatched: rows are pinned to devices, so compaction
        (a cross-device cohort gather) is disabled in ``__init__``."""
        raise AssertionError(
            "ShardedBankCEFedAvg disables cohort compaction")

    def _lower_flat(self, program, block_keyed: bool = False):
        """Compile a :class:`repro.core.program.RoundProgram` to ONE
        jitted ``shard_map`` global round over the bank shards — the
        sharded lowering of the IR, same operand schedule as the
        single-device flat lowering so ``step_round`` dispatches
        identically:

        - ``LocalSteps`` → q·τ local SGD steps on the local row (the
          single-device key/batch schedule, with per-device ``tau_dev``
          cutoffs for adaptive programs);
        - ``TierMix(ℓ, π)`` (``IntraMix`` = tier 0, ``InterGossip`` =
          tier 1) → the registry tier's grouped ``psum`` plus, for
          ℓ >= 1, π gossip rounds of that tier's edge-colored
          ``ppermute`` matchings (one cached ``GossipSchedule`` per
          distinct (ℓ, π) in the program), or dense masked operators
          via weighted rotations on the scenario/non-gossip path.
          Means dedupe through the ``usize`` uniformity tracker (a row
          already uniform over a tier-ℓ' ⊇ tier-ℓ group needs no new
          psum), which is exactly how the fused τ∘qτ boundary stays a
          single psum + gossip pass at any depth.

        Buffers are donated: peak per-device memory stays ~1× the
        (1, T) bank shard per resident buffer.

        ``block_keyed`` is the single-block async-event variant (see
        ``FLSimulator._lower_flat``): the passed key is consumed
        directly, and the dense-operator path is forced — staleness-
        masked operators are arbitrary row-stochastic matrices the
        structured collectives can't express."""
        fl = self.fl
        n = self.sched.n
        mesh = self.mesh
        comp, dp = self.compression, self.dp
        with_res = self.bank.residual is not None
        xs, ys = self.data["xs"], self.data["ys"]
        N = xs.shape[1]
        layout = self.bank.layout
        batch, momentum, lr0 = self.batch, self.momentum, self.lr
        segments = layout.segments
        plans = prg.lowering_plan(program, fuse=True)
        runs = prg.block_runs(plans)
        nblocks = len(plans)
        assert not block_keyed or nblocks == 1, \
            "block_keyed lowers single-block programs"
        adaptive = program.adaptive
        goffs, nmats = [], 0
        for bp, _cnt in runs:
            goffs.append(nmats)
            nmats += len(bp.groups)
        # static ce_fedavg schedule -> structured collectives (registry
        # tier psums + gossip matchings); anything time-varying or
        # non-gossip — including async staleness-masked operators —
        # -> exact dense operators via weighted rotations
        structured = (self.engine is None and fl.algorithm == "ce_fedavg"
                      and not block_keyed)
        registry = self.registry
        gsize = tuple(registry.tier(lvl).group_size
                      for lvl in range(registry.depth))
        gscheds = {}
        if structured:
            for bp in plans:
                for g in bp.groups:
                    for op in g.ops:
                        key_lp = (op.level, op.pi)
                        if (op.level >= 1 and key_lp not in gscheds
                                and registry.hier.num_siblings(
                                    op.level) > 1):
                            gscheds[key_lp] = registry.gossip_schedule(
                                op.level, op.pi)

        def loss_row(row, x, y):
            return self._loss(layout.unflatten_one(row), x, y)
        grad_row = jax.grad(loss_row)

        def body(*flat):
            Y, M = flat[0], flat[1]
            i = 2
            Rres = None
            if with_res:
                Rres, i = flat[2], 3
            key = flat[i]
            mats = flat[i + 1:i + 1 + nmats]
            i += 1 + nmats
            td = None
            if adaptive:
                td, i = flat[i], i + 1
            mask, xs_l, ys_l = flat[i], flat[i + 1], flat[i + 2]
            my = col.flat_axis_index(mesh)
            act = jax.lax.dynamic_slice_in_dim(
                (mask > 0.5)[:, None], my, 1, 0)          # (1, 1)
            td_my = (jax.lax.dynamic_slice_in_dim(td, my, 1, 0)
                     if adaptive else None)               # (1,)
            x0, y0 = xs_l[0], ys_l[0]

            def make_local_step(op):
                lr = lr0 * op.lr_scale

                def local_step(carry, xs_):
                    if op.adaptive:
                        k, s = xs_
                        a = act & (s < td_my[:, None])
                    else:
                        k, a = xs_, act
                    Y, M = carry
                    idx = jax.random.randint(k, (n, batch), 0, N)
                    ib = jax.lax.dynamic_slice_in_dim(idx, my, 1, 0)[0]
                    G = grad_row(Y[0], x0[ib], y0[ib])[None]
                    M = jnp.where(a, momentum * M + G, M)
                    Y = jnp.where(a, Y - lr * M, Y)
                    return (Y, M), None
                return local_step

            def train_block(Y, M, k1, op):
                keys = jax.random.split(k1, op.tau)
                xs_ = (keys, jnp.arange(op.tau)) if op.adaptive else keys
                (Y, M), _ = jax.lax.scan(make_local_step(op), (Y, M), xs_)
                return Y, M

            def upload_row(d_row, r_row, key, bp):
                """Device-side upload transform of the LOCAL delta row —
                same per-row key schedule as the single-device engine
                (row i of split(key, n)), so uploads are bit-matched."""
                if bp.privatize and dp is not None and dp.enabled:
                    from repro.core.privacy import privatize_update_flat
                    keys = jax.random.split(key, n)
                    d_row = privatize_update_flat(d_row, dp, keys[my])
                if bp.compress and comp is not None and comp.kind != "none":
                    from repro.core.compress import compress_flat
                    keys = jax.random.split(jax.random.fold_in(key, 1), n)
                    d_row, r_row = compress_flat(comp, d_row, r_row,
                                                 keys[my], segments)
                return d_row, r_row

            def apply_group(Y, g, Wg, usize):
                """Lower one MixGroup. ``usize`` tracks the tier group
                size at which rows are already uniform (1 = not), so
                consecutive tier means dedupe into one psum (V
                idempotent, W_inter's leading B^T…B, and — contiguous
                nesting — any coarser tier implying the finer ones):
                the fused τ∘qτ boundary at any depth. Gossip at tier ℓ
                keeps rows node-uniform at ℓ but breaks coarser
                uniformity, so it resets ``usize`` to its tier's
                size."""
                if not structured:
                    return gsp.dense_mix_rows(Wg, Y, mesh), 1
                for op in g.ops:
                    s = gsize[op.level]
                    if usize < s:
                        Y = registry.mean_in_body(Y, op.level)
                        usize = s
                    if op.level >= 1:
                        gs = gscheds.get((op.level, op.pi))
                        if gs is not None:
                            Y = gsp.gossip_in_body(gs, mesh, Y)
                            usize = s
                return Y, usize

            def run_block(bp, goff, Y, M, Rres, k1):
                op = bp.local
                if not bp.upload:
                    Y, M = train_block(Y, M, k1, op)
                    usize = 1
                    for j, g in enumerate(bp.groups):
                        Y, usize = apply_group(Y, g, mats[goff + j],
                                               usize)
                    return Y, M, Rres
                Y0 = Y
                Y, M = train_block(Y, M, k1, op)
                d_row, r_row = upload_row(
                    (Y - Y0)[0], None if Rres is None else Rres[0],
                    jax.random.fold_in(k1, 7), bp)
                Rres = Rres if r_row is None else r_row[None]
                d, _ = apply_group(d_row[None], bp.groups[0], mats[goff],
                                   1)
                Y = Y0 + d
                usize = 1
                for j in range(1, len(bp.groups)):
                    Y, usize = apply_group(Y, bp.groups[j],
                                           mats[goff + j], usize)
                return Y, M, Rres

            keys = (key[None] if block_keyed
                    else jax.random.split(key, nblocks))
            ki = 0
            for (bp, count), goff in zip(runs, goffs):
                bkeys = keys[ki:ki + count]
                ki += count
                if count > 1:
                    def qbody(carry, k1, bp=bp, goff=goff):
                        Y, M, Rr = carry
                        Y, M, Rr = run_block(bp, goff, Y, M, Rr, k1)
                        return (Y, M, Rr), None
                    (Y, M, Rres), _ = jax.lax.scan(qbody, (Y, M, Rres),
                                                   bkeys)
                else:
                    Y, M, Rres = run_block(bp, goff, Y, M, Rres, bkeys[0])
            return (Y, M, Rres) if with_res else (Y, M)

        row = P(self._rspec, None)
        rep = P()
        nbank = 3 if with_res else 2
        nextra = 1 + nmats + (1 if adaptive else 0) + 1  # key+mats+td+mask
        in_specs = (row,) * nbank + (rep,) * nextra + (P(self._rspec),) * 2
        out_specs = (row,) * nbank
        mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)

        @functools.partial(jax.jit,
                           donate_argnums=(0, 1, 2) if with_res else (0, 1))
        def global_round(Y, M, R, key, args, mask):
            extras = tuple(args.mats)
            if adaptive:
                extras = extras + (args.tau_dev,)
            if with_res:
                return mapped(Y, M, R, key, *extras, mask, xs, ys)
            Y, M = mapped(Y, M, key, *extras, mask, xs, ys)
            return Y, M, R

        return global_round


# ---------------------------------------------------------------------------
# sharded streamed engine: row-sharded hot slab over a virtual population
# ---------------------------------------------------------------------------

class ShardedStreamedBank(FLSimulator):
    """Streamed client-store engine (ISSUE 9) with the per-round hot
    slab row-sharded over the mesh's replica axes.

    Where :class:`ShardedBankCEFedAvg` pins one *enumerated* device row
    per mesh device for the whole run, this engine scales past
    enumeration: the population lives in per-shard cold stores
    (``client_id % R`` routing, one :class:`~repro.core.clientstore.
    ClientStore` shard per bank shard) and only each round's working
    set — cohort + one representative lane per cluster — exists on the
    accelerators, as an ``(S, T)`` slab placed per-shard via
    ``ModelBank.from_rows(..., sharding=...)`` so no single device ever
    holds the whole working set. ``min_bucket = R`` keeps every slab
    bucket divisible by the shard count (even row shards).

    The slab round itself is the ordinary ``_lower_streamed`` lowering:
    mixing is a cohort-sized ``(S, S)·(S, T)`` contraction that GSPMD
    partitions over the row shards — at streamed scale the slab, not
    the population, bounds the communication, so no structured
    collective path is needed. Requires a scenario carrying a
    ``PopulationConfig`` (virtual clients are what make per-shard cold
    stores meaningful).

    ``pipeline=True`` (forwarded to :class:`FLSimulator`) composes with
    the sharding: the pipelined driver stages encoded rows with
    ``jax.device_put(..., slab_sharding)``, so prefetched cohorts land
    row-sharded and the on-device codec kernels run per shard."""

    def __init__(self, init_fn: Callable, apply_fn: Callable, fl, data,
                 mesh: Mesh, **kw):
        assert kw.pop("bank", True), \
            "ShardedStreamedBank IS a bank engine"
        scenario = kw.get("scenario")
        assert scenario is not None and scenario.population is not None, \
            "ShardedStreamedBank streams a virtual population " \
            "(ScenarioConfig.population)"
        self.mesh = mesh
        raxes = col.replica_axis_names(mesh)
        assert raxes, f"mesh {mesh.axis_names} has no replica axes"
        R = col.flat_axis_size(mesh)
        if "model" in mesh.axis_names:
            assert mesh.shape["model"] == 1, \
                "slab rows are not tensor-parallel (model axis must be 1)"
        self._rspec = raxes if len(raxes) > 1 else raxes[0]
        super().__init__(
            init_fn, apply_fn, fl, data, bank=True,
            slab_sharding=NamedSharding(mesh, P(self._rspec, None)),
            store_shards=R, min_bucket=R, **kw)
        assert self._streamed
        self._compact_enabled = False


# ---------------------------------------------------------------------------
# serving (non-FL: global/edge model)
# ---------------------------------------------------------------------------

def make_prefill_fn(model_cfg):
    def prefill(params, batch):
        logits, _ = mdl.forward(model_cfg, params, batch)
        return logits
    return prefill


def make_decode_fn(model_cfg):
    def decode(params, cache, tokens, pos):
        return mdl.decode_step(model_cfg, params, cache, tokens, pos)
    return decode


def serve_specs(model_cfg, mesh: Mesh, batch: int, seq: int):
    """(param specs, cache specs) for single-model serving."""
    shapes, logical = abstract_model(model_cfg)
    pspecs = sh.resolve_specs(shapes, logical, mesh)
    cache_shapes = jax.eval_shape(
        lambda: mdl.init_decode_cache(model_cfg, batch, seq)[0])
    _, cache_logical = mdl.init_decode_cache(model_cfg, 1, 1)
    # decode cache sharding: batch over data when divisible, else kv_seq
    rules = dict(sh.DEFAULT_RULES)
    if batch % mesh.shape["data"] != 0:
        rules["batch"] = None
        rules["kv_seq"] = "data"
    cspecs = sh.resolve_specs(cache_shapes, cache_logical, mesh, rules)
    return shapes, pspecs, cache_shapes, cspecs
