"""CE-FedAvg (Algorithm 1) — operator algebra + the simulation engine.

The paper's update rule (eq. 10):  X_{t+1} = (X_t − η G_t) W_t, with
W_t ∈ {I, V, B^T diag(c) H^π B} depending on the iteration (eq. 11).
``make_w_schedule`` builds those operators for CE-FedAvg and for every
baseline (Table 1 / §4.3 special cases); ``FLSimulator`` runs the literal
matrix form with all n device models materialized — the paper-faithful
engine used for the Figure 2–6 reproductions and for unit-testing the
sharded production trainer against.

Two interchangeable engines live behind the same ``FLSimulator`` API:

- **ModelBank (default, ``bank=True``)** — params, momentum and the
  error-feedback residual are single contiguous ``(n, T)`` float32
  buffers (``core/modelbank.py``); pytree views exist only inside the
  per-device ``apply_fn`` and at eval/checkpoint edges. Every mixing
  boundary is ONE streaming pass of the fused gossip kernel
  (``kernels/gossip_mix.gossip_mix_rows``), the coincident τ/qτ boundary
  is folded into a single pass with the precomputed operator
  ``W_inter @ W_intra``, the jitted round donates its buffers (peak
  memory ~1× the bank), and scenario rounds with partial participation
  run their gradient work on a compacted ``(k_pad, T)`` cohort gather
  (static bucketed sizes, ``modelbank.cohort_buckets``).
- **legacy pytree (``bank=False``)** — per-leaf ``tensordot`` mixing and
  full-n ``where``-frozen local steps; kept as the bit-faithful parity
  reference (``tests/test_modelbank.py``).

Both engines (and the sharded bank in ``core/sharded.py``) execute one
shared declarative schedule: a :class:`repro.core.program.RoundProgram`.
The static τ/q/π knobs compile to the canonical program
(``program.canonical_program``); ``_lower_legacy`` / ``_lower_flat`` /
``_lower_compact`` are *compilers* from any validated program to that
engine's jitted round, and a ``schedule=`` hook (a name from
``program.SCHEDULES``, a ``ScheduleFn``, or a fixed ``RoundProgram``)
swaps in non-canonical schedules — adaptive per-cluster τ_k,
time-varying π_t — without touching engine code.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FLConfig
from repro.core import program as prg
from repro.core import topology as topo
from repro.core.modelbank import (ModelBank, bucket_for, cohort_buckets,
                                  compact_plan)
from repro.core.spans import span
from repro.kernels.gossip_mix import FlatLayout, gossip_mix_rows


@dataclass
class WSchedule:
    """Mixing operators applied at iteration boundaries (eq. 11)."""
    W_intra: np.ndarray      # applied when (t+1) % tau == 0 (and not inter)
    W_inter: np.ndarray      # applied when (t+1) % (q*tau) == 0
    H: np.ndarray            # m x m backhaul mixing matrix
    zeta: float
    cluster_sizes: List[int]
    adj: np.ndarray          # m x m backhaul adjacency (bool)

    @property
    def n(self) -> int:
        return self.W_intra.shape[0]

    @property
    def degrees(self) -> np.ndarray:
        """Backhaul degree of each cluster (traffic accounting)."""
        return self.adj.sum(1).astype(np.int64)


def make_w_schedule(fl: FLConfig) -> WSchedule:
    """Static mixing schedule (eq. 11 / Table 1): W_intra applied at
    τ-boundaries, W_inter at qτ-boundaries, specialized per algorithm via
    the §4.3 reductions (Hier-FAvg, FedAvg, Local-Edge, dec. local SGD).
    Assumes equal clusters and full participation; the scenario engine
    (core/scenario.py) builds the time-varying masked generalization."""
    fl.validate()
    m, n = fl.num_clusters, fl.n
    sizes = [fl.devices_per_cluster] * m
    V = topo.intra_cluster_operator(sizes)
    A = np.ones((n, n)) / n
    eye = np.eye(n)
    # tier-1 backhaul graph: one topology graph over all m edges at depth
    # 2 (the paper), block-diagonal per-parent graphs for deeper
    # hierarchies (kron(I, H_block) — see topology.Hierarchy)
    hier = topo.Hierarchy.from_config(fl)
    adj = hier.adjacency(1, fl.topology, fl)
    H = topo.mixing_matrix(adj, fl.mixing)
    if fl.algorithm == "ce_fedavg":
        W_intra, W_inter = V, topo.inter_cluster_operator(sizes, H, fl.pi)
    elif fl.algorithm == "hier_favg":
        W_intra, W_inter = V, A
    elif fl.algorithm == "fedavg":
        W_intra, W_inter = eye, A
    elif fl.algorithm == "local_edge":
        W_intra, W_inter = V, V
    elif fl.algorithm == "dec_local_sgd":
        # n == m: every device is its own cluster, neighbors gossip
        assert fl.devices_per_cluster == 1, "dec_local_sgd requires n == m"
        W_intra = eye
        W_inter = np.linalg.matrix_power(H, fl.pi)
    else:
        raise ValueError(fl.algorithm)
    return WSchedule(W_intra, W_inter, H, topo.zeta(H), sizes, adj)


def mix(W, params):
    """Apply a mixing operator over the leading device axis of every leaf:
    x_k ← Σ_j W[k,j]·x_j (row application).

    The paper's eq. 10 operators are symmetric doubly stochastic, where
    row and column application coincide; the masked/unequal-cluster
    generalizations (core/scenario.py) are only row-stochastic, so the
    row form is the correct one for both."""
    Wj = jnp.asarray(W, jnp.float32)

    def one(leaf):
        out = jnp.tensordot(Wj, leaf.astype(jnp.float32), axes=[[1], [0]])
        return out.astype(leaf.dtype)
    return jax.tree.map(one, params)


#: share of the device's memory limit that one chunk of per-row backward
#: residuals may take. TPU layout padding inflates the residuals' logical
#: bytes about 4-5x (channel-minor activations pad 32 lanes to 128), and
#: the slab's (rows, T) buffers (params, momentum, the gradient) need the
#: rest of the memory.
ROW_CHUNK_SHARE = 1 / 32
#: rows of a TPU float32 tile: a chunk of a multiple of 8 rows compiles
#: to a third of the temporaries of a 9-row one, in a seventh of the
#: time (72-row slab of the FEMNIST CNN, compiled for a v5e)
TILE_ROWS = 8


def device_bytes_limit() -> Optional[int]:
    """The memory limit the first local device reports, or None where
    the backend reports none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def row_residual_bytes(loss_row: Callable, row, x, y) -> int:
    """Logical bytes of one row's backward residuals: what ``jax.vjp``
    of ``loss_row`` in its first argument keeps for the backward pass,
    at the shapes of ``row``, ``x`` and ``y`` (arrays or shape structs)."""
    res = jax.eval_shape(
        lambda r, x, y: jax.vjp(lambda r: loss_row(r, x, y), r)[1],
        row, x, y)
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(res))


def grad_chunk_rows(rows: int, row_bytes: int,
                    bytes_limit: Optional[int]) -> int:
    """Rows whose per-row gradients are computed at once: the largest
    divisor ``c`` of ``rows`` with ``c * row_bytes`` within
    :data:`ROW_CHUNK_SHARE` of ``bytes_limit``, a multiple of
    :data:`TILE_ROWS` where one fits (at least 1); all rows where no
    limit is known."""
    if not bytes_limit:
        return rows
    budget = bytes_limit * ROW_CHUNK_SHARE
    fits = [c for c in range(1, rows + 1)
            if rows % c == 0 and c * row_bytes <= budget]
    return max([c for c in fits if c % TILE_ROWS == 0] or fits or [1])


def paging_fns(codec: str, segments, sharding=None):
    """The pipelined streamed round's pre/post jits. The CORE round stays
    the serial streamed round's own compiled lowering (``_get_round``) — f32
    bit-identity holds by construction because the same executable sees
    the same input bits. pre/post only gather, scatter and run the cold
    codec, all bit-exact at f32:

    - pre (``named_scope("page_in")``): page-in on device — params from
      the resident cluster references, momentum decoded from the staged
      encoded rows after scattering in forwarded rows (clients sampled
      in consecutive rounds, whose newest momentum exists only as the
      previous round's device-side page-out);
    - post (``named_scope("page_out")``): page-out on device — fold each
      updated cluster's synced lane into the references, encode the
      slab's momentum so the D2H transfer carries codec-width bytes."""
    from repro.kernels import cold_codec
    encode, decode = cold_codec.row_codec(codec, segments, sharding)

    # q_in/s_in are staged fresh every round and consumed only
    # here: donating them makes the forwarding scatter in-place
    # (CPU ignores donation and warns, so only donate off-CPU)
    donate = (() if jax.default_backend() == "cpu" else (2, 3))

    @functools.partial(jax.jit, donate_argnums=donate)
    def pre(refs, src_labels, q_in, s_in, q_prev, s_prev, src, dst):
        with jax.named_scope("page_in"):
            q = q_in.at[dst].set(q_prev[src], mode="drop")
            s = s_in.at[dst].set(s_prev[src], mode="drop")
            Y0 = refs[src_labels]
            M0 = decode(q, s)
            if sharding is not None:
                Y0 = jax.lax.with_sharding_constraint(Y0, sharding)
                M0 = jax.lax.with_sharding_constraint(M0, sharding)
        return Y0, M0

    # refs must NOT be donated: the previous round's pending
    # page-out still holds this buffer until the next drain
    @jax.jit
    def post(Y, M, refs, upd, lanes):
        with jax.named_scope("page_out"):
            refs_new = jnp.where(upd[:, None], Y[lanes], refs)
            q_out, s_out = encode(M)
        return refs_new, q_out, s_out

    return pre, post


# ---------------------------------------------------------------------------
# Simulation engine (paper-faithful, laptop scale)
# ---------------------------------------------------------------------------

class FLSimulator:
    """Runs Algorithm 1 with n materialized device models.

    init_fn(key) -> params;  apply_fn(params, x) -> logits.
    data: dict with xs (n, N, ...), ys (n, N) — per-device training shards;
          test_x, test_y — the common test set.
    scenario: optional config.ScenarioConfig — per-round client sampling,
          straggler dropout and device mobility (core/scenario.py); pair
          with core.clock.run_wall_clock for time-to-accuracy curves.
    schedule: optional round schedule override — a name from
          ``program.SCHEDULES`` ("static", "adaptive_tau", "pi_decay"),
          a ``program.ScheduleFn``, or a fixed ``program.RoundProgram``.
          None runs the canonical program compiled from fl's τ/q/π.
    bank: True (default) runs the flat ModelBank engine; False the legacy
          per-leaf pytree engine (parity/debug escape hatch). ``params``,
          ``mom`` and ``residual`` read/write as pytrees in both modes.
    streaming: True pages client state through a
          :class:`repro.core.clientstore.ClientStore` instead of a
          resident (n, T) bank — only each round's working set (cohort
          + one cold representative per cluster) is materialized as the
          hot slab. Implied (and required) when the scenario carries a
          ``PopulationConfig``; at enumerated n it reproduces the
          resident trajectory to float tolerance (the gemm shapes — and
          so the fp summation order — of the restricted operators
          differ; everything keyed is identical).
    codec: cold-row codec for the streamed store ("f32"/"f16"/"int8");
          a population scenario's ``PopulationConfig.codec`` wins.
    pipeline: True overlaps streamed paging with compute (ISSUE 10):
          the cold codec runs on device (``kernels/cold_codec.py``), the
          cluster references stay device-resident, round t's page-out
          drains asynchronously while round t+1 computes, and — every
          engine draw being a pure function of (seed, round) — round
          t+1's cohort is peeked and its cold rows staged/H2D'd during
          round t. Matches the serial streamed driver bit-identically
          at f32 (to codec tolerance at f16/int8); requires streaming.
    """

    def __init__(self, init_fn: Callable, apply_fn: Callable, fl: FLConfig,
                 data: Dict[str, Any], *, lr: float = 0.05,
                 momentum: float = 0.9, batch_size: int = 50, seed: int = 0,
                 compression=None, dp=None, scenario=None, schedule=None,
                 bank: bool = True, streaming: bool = False,
                 codec: str = "f32", store_shards: int = 1,
                 slab_sharding=None, min_bucket: int = 1,
                 pipeline: bool = False):
        self.fl = fl
        self.apply_fn = apply_fn
        self.sched = make_w_schedule(fl)
        n = self.sched.n
        assert data["xs"].shape[0] == n
        self.data = data
        self.lr, self.momentum, self.batch = lr, momentum, batch_size
        self.compression = compression  # core.compress.CompressionConfig
        self.dp = dp                    # core.privacy.DPConfig
        # wall-clock scenario (config.ScenarioConfig): per-round sampling,
        # mobility and heterogeneity — None keeps the static schedule. A
        # scenario with a PopulationConfig swaps in the PopulationEngine
        # (virtual clients, keyed cohort draws) and forces streaming.
        self.pop = None
        if scenario is not None and scenario.population is not None:
            from repro.core.scenario import PopulationEngine
            self.engine = PopulationEngine(scenario, fl)
            self.pop = self.engine
            streaming = True
        elif scenario is not None:
            from repro.core.scenario import ScenarioEngine
            self.engine = ScenarioEngine(scenario, fl)
        else:
            self.engine = None
        # current cluster assignment B_t (mobility re-draws it per round)
        self.labels = np.repeat(np.arange(fl.num_clusters),
                                fl.devices_per_cluster)
        self._full_mask = jnp.ones((n,), jnp.float32)
        with_residual = (compression is not None
                         and compression.error_feedback)
        # Algorithm 1 initializes every device from its edge model y_{0,0};
        # we use one shared init (common FL practice), so params are
        # cluster-uniform from the start.
        one = init_fn(jax.random.PRNGKey(seed))
        self._layout = FlatLayout.for_tree(one)
        self.bank: Optional[ModelBank] = None
        self.store = None  # clientstore.ClientStore (streamed mode only)
        self._streamed = bool(streaming)
        # cohort compaction gathers bank rows into a dense (k_pad, T) slab;
        # the sharded engine (core.sharded.ShardedBankCEFedAvg) pins rows
        # to devices and disables it, running mask-frozen full rows instead
        self._compact_enabled = True
        if self._streamed:
            assert bank, "the streaming client store is a bank engine"
            assert compression is None and dp is None, \
                "streamed rounds run plain programs (no upload transforms)"
            assert fl.algorithm != "dec_local_sgd", \
                "dec_local_sgd ties devices to clusters (n == m) — " \
                "no cold rows to stream"
            from repro.core.clientstore import ClientStore
            if self.pop is not None:
                codec = scenario.population.codec
            self.store = ClientStore(
                self._layout, fl.num_clusters,
                np.asarray(self._layout.flatten_one(one), np.float32),
                codec=codec, num_shards=store_shards)
            self._slab_sharding = slab_sharding
            # slab capacity: the cohort cap plus one representative per
            # cluster, bucketed like compaction (power-of-two retrace
            # bound); min_bucket keeps every bucket divisible by the
            # sharded engine's row-shard count
            cap = (self.engine.cohort_cap if self.pop is not None
                   else n + fl.num_clusters)
            cap = -(-max(cap, min_bucket) // min_bucket) * min_bucket
            self._buckets = tuple(
                b for b in cohort_buckets(cap) if b % min_bucket == 0)
            # params of a cold client = its cluster's reference at its
            # LAST sync — track each enumerated device's label as of the
            # previous round's trailing boundary (page-in value source)
            self._page_labels = self.labels.copy()
            self._peak_slab = 0
            self.last_paging = None
            # overlapped driver state (ISSUE 10): device refs, the
            # in-flight page-out, the prefetched next working set
            self._pipe = None
            self._pipe_fns = None
        elif bank:
            self.bank = self._make_bank(one, n, with_residual)
            self._buckets = cohort_buckets(n)
        else:
            self._params = jax.tree.map(
                lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), one)
            self._mom = jax.tree.map(jnp.zeros_like, self._params)
            self._residual = (jax.tree.map(jnp.zeros_like, self._params)
                              if with_residual else None)
        self._pipeline = bool(pipeline)
        assert not self._pipeline or self._streamed, \
            "pipeline=True overlaps *paging* with compute — it requires " \
            "the streamed engine (streaming=True or a population scenario)"
        # cumulative host seconds per span of ``step_round``
        # (``core/spans.py``: round, assemble, dispatch, page.*)
        self.host_seconds: Dict[str, float] = {}
        # rows per per-row gradient pass, per traced (lowering, slab rows)
        self.grad_chunk_rows: Dict = {}
        self.last_bucket = n   # cohort capacity used by the latest round
        # -- round schedule (RoundProgram IR) -------------------------------
        # every engine round is a lowering of a RoundProgram; the static
        # τ/q/π knobs compile to the canonical program once, and a
        # schedule hook may swap in a different program each round
        faulted = (self.engine is not None
                   and self.engine.faults is not None)
        self._canonical = prg.canonical_program(
            fl, privatize=dp is not None, compress=compression is not None,
            faults=faulted)
        if schedule is None:
            self._schedule_fn: Optional[prg.ScheduleFn] = None
        elif isinstance(schedule, str):
            self._schedule_fn = prg.make_schedule(
                schedule, fl, engine=self.engine,
                privatize=dp is not None, compress=compression is not None,
                faults=faulted, sim=self)
        elif isinstance(schedule, prg.RoundProgram):
            def _fixed(r, plan, _program=schedule):
                return _program
            self._schedule_fn = _fixed
        else:
            self._schedule_fn = schedule
        if self.pop is not None:
            assert schedule is None, \
                "round schedules are not supported with a virtual " \
                "population (tau_dev/speed vectors are per enumerated " \
                "device)"
        self.round_index = 0
        self.last_program: Optional[prg.RoundProgram] = None
        self._lowered: Dict = {}       # (engine kind, signature) -> jitted
        self._static_mats: Dict = {}   # (fuse, signature) -> resolved mats
        self._inter_static: Dict = {fl.pi: self.sched.W_inter}
        # depth>2 tiers: static TierMix operators / H_ℓ, cached per level
        self._hier = topo.Hierarchy.from_config(fl)
        self._tier_static: Dict = {}
        self._static_labels = self.labels.copy()
        self.key = jax.random.PRNGKey(seed + 1)
        self._eval_fn = self._build_eval()

    def _span(self, name: str):
        """A ``repro.<name>`` host span counted in ``host_seconds``."""
        return span(self.host_seconds, name)

    @property
    def _page_seconds(self) -> float:
        """Cumulative host seconds spent paging (the ``page.*`` spans:
        wait, commit, stage); ``clock.run_wall_clock`` splits eval
        windows into page_s/compute_s from deltas of this counter."""
        return sum(v for k, v in self.host_seconds.items()
                   if k.startswith("page."))

    def _make_bank(self, one, n: int, with_residual: bool) -> ModelBank:
        """Bank construction hook: the single-process engine broadcasts
        the shared init on the default device; the sharded engine
        (core/sharded.py) overrides this with per-shard init via
        ``ModelBank.from_model_sharded``."""
        return ModelBank.from_model(one, n, with_residual=with_residual)

    # -- state as pytrees (both engines) ------------------------------------
    @property
    def params(self):
        """Device-stacked model pytree; in bank mode a materialized view
        of the flat (n, T) buffer (fresh arrays, safe across rounds)."""
        if self.bank is not None:
            return self.bank.params_tree()
        if self._streamed:
            raise AttributeError(
                "the streamed engine keeps no resident per-client "
                "params — read sim.store.cluster_params / edge_models()")
        return self._params

    @params.setter
    def params(self, tree):
        if self.bank is not None:
            self.bank.params = self.bank.layout.flatten_stack(tree)
        else:
            self._params = tree

    @property
    def mom(self):
        """Device-stacked momentum pytree (see ``params``)."""
        if self.bank is not None:
            return self.bank.layout.unflatten_stack(self.bank.mom)
        if self._streamed:
            raise AttributeError(
                "the streamed engine keeps no resident momentum — "
                "cold rows live in sim.store")
        return self._mom

    @mom.setter
    def mom(self, tree):
        if self.bank is not None:
            self.bank.mom = self.bank.layout.flatten_stack(tree)
        else:
            self._mom = tree

    @property
    def residual(self):
        """Error-feedback residual pytree, or None when compression with
        error feedback is off."""
        if self.bank is not None:
            if self.bank.residual is None:
                return None
            return self.bank.layout.unflatten_stack(self.bank.residual)
        if self._streamed:
            return None  # streamed rounds reject upload/EF programs
        return self._residual

    @residual.setter
    def residual(self, tree):
        if self.bank is not None:
            self.bank.residual = (
                None if tree is None
                else self.bank.layout.flatten_stack(tree))
        else:
            self._residual = tree

    # -- loss --------------------------------------------------------------
    def _loss(self, p, x, y):
        logits = self.apply_fn(p, x)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)

    # -- program lowering: legacy pytree engine -----------------------------
    def _lower_legacy(self, program: prg.RoundProgram):
        """Compile a RoundProgram to the legacy pytree round (fuse=False:
        one per-leaf ``mix`` contraction per mix op, the paper-literal
        sequential form). Operators/mask are *arguments* so the scenario
        engine can re-draw them between rounds without recompiling:
        masked devices (and, past their ``tau_dev`` cutoff, adaptive
        devices) are frozen via ``where``; the canonical program with a
        full mask reproduces the original fixed-schedule round."""
        n = self.sched.n
        N = self.data["xs"].shape[1]
        grad_fn = jax.grad(self._loss)
        comp, dp = self.compression, self.dp
        plans = prg.lowering_plan(program, fuse=False)
        runs = prg.block_runs(plans)
        nblocks = len(plans)

        def bcast(act, leaf):
            return act.reshape((-1,) + (1,) * (leaf.ndim - 1))

        def make_local_step(op, act, tau_dev):
            lr = self.lr * op.lr_scale

            def local_step(carry, xs_):
                if op.adaptive:
                    key, s = xs_
                    stepact = act & (s < tau_dev)
                else:
                    key, stepact = xs_, act
                params, mom = carry
                idx = jax.random.randint(key, (n, self.batch), 0, N)
                xb = jax.vmap(lambda x, i: x[i])(self.data["xs"], idx)
                yb = jax.vmap(lambda y, i: y[i])(self.data["ys"], idx)
                grads = jax.vmap(grad_fn)(params, xb, yb)
                mom = jax.tree.map(
                    lambda v, g: jnp.where(bcast(stepact, v),
                                           self.momentum * v + g, v),
                    mom, grads)
                params = jax.tree.map(
                    lambda p, v: jnp.where(bcast(stepact, p),
                                           p - lr * v, p),
                    params, mom)
                return (params, mom), None
            return local_step

        def train_block(params, mom, key, op, act, tau_dev):
            local_step = make_local_step(op, act, tau_dev)
            keys = jax.random.split(key, op.tau)
            xs_ = (keys, jnp.arange(op.tau)) if op.adaptive else keys
            (params, mom), _ = jax.lax.scan(local_step, (params, mom), xs_)
            return params, mom

        def upload_transform(delta, residual, key, bp):
            """Device-side: (optional) DP then compression of the delta."""
            if bp.privatize and dp is not None and dp.enabled:
                from repro.core.privacy import privatize_update
                keys = jax.random.split(key, n)
                delta = jax.vmap(
                    lambda d, k: privatize_update(d, dp, k))(
                        delta, keys)
            if bp.compress and comp is not None and comp.kind != "none":
                from repro.core.compress import compress_tree
                keys = jax.random.split(jax.random.fold_in(key, 1), n)
                delta, residual = jax.vmap(
                    lambda d, r, k: compress_tree(comp, d, r, k)
                )(delta, residual, keys)
            return delta, residual

        def run_block(bp, gm, params, mom, residual, k1, act, tau_dev):
            if not bp.upload:
                params, mom = train_block(params, mom, k1, bp.local, act,
                                          tau_dev)
                for W in gm:
                    params = mix(W, params)
                return params, mom, residual
            # devices upload (privatized/compressed) deltas; the edge
            # reconstructs x_start + V·delta (exact when both are off)
            params0 = params
            params, mom = train_block(params, mom, k1, bp.local, act,
                                      tau_dev)
            delta = jax.tree.map(lambda a, b: a - b, params, params0)
            delta, residual = upload_transform(
                delta, residual, jax.random.fold_in(k1, 7), bp)
            params = jax.tree.map(
                lambda p0, d: p0 + d, params0, mix(gm[0], delta))
            for W in gm[1:]:
                params = mix(W, params)
            return params, mom, residual

        @jax.jit
        def global_round(params, mom, residual, key, args, mask):
            act = mask > 0.5
            tau_dev = args.tau_dev
            keys = jax.random.split(key, nblocks)
            mi = ki = 0
            for bp, count in runs:
                gm = args.mats[mi:mi + len(bp.groups)]
                mi += len(bp.groups)
                bkeys = keys[ki:ki + count]
                ki += count
                if count > 1:
                    def body(carry, k1, bp=bp, gm=gm):
                        p, m, r = carry
                        p, m, r = run_block(bp, gm, p, m, r, k1, act,
                                            tau_dev)
                        return (p, m, r), None
                    (params, mom, residual), _ = jax.lax.scan(
                        body, (params, mom, residual), bkeys)
                else:
                    params, mom, residual = run_block(
                        bp, gm, params, mom, residual, bkeys[0], act,
                        tau_dev)
            return params, mom, residual

        return global_round

    # -- program lowering: flat ModelBank engine ----------------------------
    def _flat_helpers(self, lowering: str):
        """Local-step factory shared by the flat rounds; the per-row grad
        closure materializes pytree views only inside the apply call.
        ``lowering`` names the round in :attr:`grad_chunk_rows`."""
        n = self.sched.n
        N = self.data["xs"].shape[1]
        layout = self._layout

        def loss_row(row, x, y):
            return self._loss(layout.unflatten_one(row), x, y)
        grad_row = jax.grad(loss_row)

        def chunk_rows(xs, ys, rows):
            """Rows per gradient pass (:func:`grad_chunk_rows`): few
            enough that one pass's backward residuals fit beside the
            slab, so XLA need not rematerialise the forward pass. A
            row-sharded slab keeps one pass: a contiguous chunk would
            span its shards."""
            if self._streamed and self._slab_sharding is not None:
                return rows
            S = jax.ShapeDtypeStruct
            r = row_residual_bytes(
                loss_row, S((layout.total,), jnp.float32),
                S((self.batch,) + xs.shape[2:], xs.dtype),
                S((self.batch,), ys.dtype))
            c = grad_chunk_rows(rows, r, device_bytes_limit())
            self.grad_chunk_rows[(lowering, rows)] = c
            return c

        def make_local_step(xs, ys, act2d, gather=None, tau_dev=None,
                            lr_scale=1.0, fold_ids=None):
            """One SGD+momentum step on a (rows, T) slab. ``gather``
            (compaction) maps the full-n batch-index draw onto the slab's
            rows so the cohort sees the same batches as the full path;
            ``fold_ids`` (virtual populations, streamed rounds) instead
            draws each row's batch from the step key folded with its
            client id — O(rows) draws independent of the population
            size, and a client redrawn in a later round with the same
            key would see the same batches regardless of cohort
            composition; ``tau_dev`` (adaptive programs) freezes each
            row past its per-device step cutoff."""
            lr = self.lr * lr_scale
            rows = act2d.shape[0]
            c = chunk_rows(xs, ys, rows)

            def local_step(carry, xs_):
                if tau_dev is not None:
                    key, s = xs_
                    act = act2d & (s < tau_dev[:, None])
                else:
                    key, act = xs_, act2d
                Y, M = carry
                if fold_ids is not None:
                    idx = jax.vmap(lambda i: jax.random.randint(
                        jax.random.fold_in(key, i),
                        (self.batch,), 0, N))(fold_ids)
                else:
                    idx = jax.random.randint(key, (n, self.batch), 0, N)
                    if gather is not None:
                        idx = idx[gather]
                xb = jax.vmap(lambda x, i: x[i])(xs, idx)
                yb = jax.vmap(lambda y, i: y[i])(ys, idx)
                if c == rows:
                    G = jax.vmap(grad_row)(Y, xb, yb)
                else:
                    with jax.named_scope("row_grads"):
                        G = jax.lax.map(lambda a: grad_row(*a),
                                        (Y, xb, yb), batch_size=c)
                M = jnp.where(act, self.momentum * M + G, M)
                Y = jnp.where(act, Y - lr * M, Y)
                return (Y, M), None
            return local_step

        return make_local_step

    @staticmethod
    def _train_scan(local_step, Y, M, key, op):
        """τ local steps of one block: scan over the block's step keys
        (plus the step index when the op is adaptive)."""
        with jax.named_scope("local_steps"):
            keys = jax.random.split(key, op.tau)
            xs_ = (keys, jnp.arange(op.tau)) if op.adaptive else keys
            (Y, M), _ = jax.lax.scan(local_step, (Y, M), xs_)
        return Y, M

    def _lower_flat(self, program: prg.RoundProgram,
                    block_keyed: bool = False):
        """Compile a RoundProgram to the flat global round: all state
        stays (n, T); each MixGroup is one streaming pass
        (``gossip_mix_rows``) of its fused operator — for the canonical
        program the final τ-boundary coincides with the qτ-boundary and
        arrives pre-fused as ``W_inter @ W_intra`` (the delta/upload
        path keeps the first mix separate, where the fold is invalid).
        Identical consecutive blocks compile to ONE ``lax.scan``;
        buffers are donated so peak memory stays ~1× the bank.

        ``block_keyed`` lowers a SINGLE-block program that consumes the
        passed key directly instead of splitting it — the async event
        executor (:meth:`step_round_async`) splits the round key into
        per-block keys on the host (``jax.random.split`` is
        deterministic in or out of jit) and replays one block per
        event, so each device sees exactly the barrier key schedule."""
        n = self.sched.n
        comp, dp = self.compression, self.dp
        xs, ys = self.data["xs"], self.data["ys"]
        make_local_step = self._flat_helpers("flat")
        segments = self._layout.segments
        plans = prg.lowering_plan(program, fuse=True)
        runs = prg.block_runs(plans)
        nblocks = len(plans)
        assert not block_keyed or nblocks == 1, \
            "block_keyed lowers single-block programs"

        def upload(delta, R, key, bp):
            """Flat-domain device uploads: DP then compression, row-wise
            (same per-device/per-leaf key schedule as the pytree path)."""
            if bp.privatize and dp is not None and dp.enabled:
                from repro.core.privacy import privatize_update_flat
                keys = jax.random.split(key, n)
                delta = jax.vmap(
                    lambda d, k: privatize_update_flat(d, dp, k))(
                        delta, keys)
            if bp.compress and comp is not None and comp.kind != "none":
                from repro.core.compress import compress_flat
                keys = jax.random.split(jax.random.fold_in(key, 1), n)
                delta, R = jax.vmap(
                    lambda d, r, k: compress_flat(comp, d, r, k, segments)
                )(delta, R, keys)
            return delta, R

        def run_block(bp, gm, Y, M, R, k1, act2d, tau_dev):
            op = bp.local
            local_step = make_local_step(
                xs, ys, act2d, tau_dev=tau_dev if op.adaptive else None,
                lr_scale=op.lr_scale)
            if not bp.upload:
                Y, M = self._train_scan(local_step, Y, M, k1, op)
                for W in gm:
                    Y = gossip_mix_rows(W, Y)
                return Y, M, R
            Y0 = Y
            Y, M = self._train_scan(local_step, Y, M, k1, op)
            delta = Y - Y0
            delta, R = upload(delta, R, jax.random.fold_in(k1, 7), bp)
            Y = Y0 + gossip_mix_rows(gm[0], delta)
            for W in gm[1:]:
                Y = gossip_mix_rows(W, Y)
            return Y, M, R

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def global_round(Y, M, R, key, args, mask):
            act2d = (mask > 0.5)[:, None]
            tau_dev = args.tau_dev
            keys = (key[None] if block_keyed
                    else jax.random.split(key, nblocks))
            mi = ki = 0
            for bp, count in runs:
                gm = args.mats[mi:mi + len(bp.groups)]
                mi += len(bp.groups)
                bkeys = keys[ki:ki + count]
                ki += count
                if count > 1:
                    def body(carry, k1, bp=bp, gm=gm):
                        Y, M, R = carry
                        Y, M, R = run_block(bp, gm, Y, M, R, k1, act2d,
                                            tau_dev)
                        return (Y, M, R), None
                    (Y, M, R), _ = jax.lax.scan(body, (Y, M, R), bkeys)
                else:
                    Y, M, R = run_block(bp, gm, Y, M, R, bkeys[0], act2d,
                                        tau_dev)
            return Y, M, R

        return global_round

    def _lower_compact(self, program: prg.RoundProgram):
        """Compile a RoundProgram to the compacted scenario round:
        gradient/momentum work runs on a dense (k_pad, T) gather of the
        participating rows (``idx`` holds distinct rows — cohort first,
        inert padding after — so the scatter back is deterministic);
        mixing boundaries still stream the full bank, since masked
        operators move every device's row. Traced once per cohort bucket
        (static shapes under jit). Upload programs never dispatch here."""
        xs, ys = self.data["xs"], self.data["ys"]
        make_local_step = self._flat_helpers("compact")
        plans = prg.lowering_plan(program, fuse=True)
        runs = prg.block_runs(plans)
        nblocks = len(plans)
        assert not program.has_upload, \
            "compacted rounds are for plain programs only"

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def compact_round(Y, M, key, idx, lane, args):
            lane2d = lane[:, None]
            xs_c, ys_c = xs[idx], ys[idx]
            tau_c = (None if args.tau_dev is None else args.tau_dev[idx])

            def train_edge(carry, k1, op):
                Y, M = carry
                P, Mc = Y[idx], M[idx]
                local_step = make_local_step(
                    xs_c, ys_c, lane2d, gather=idx,
                    tau_dev=tau_c if op.adaptive else None,
                    lr_scale=op.lr_scale)
                P, Mc = self._train_scan(local_step, P, Mc, k1, op)
                return Y.at[idx].set(P), M.at[idx].set(Mc)

            keys = jax.random.split(key, nblocks)
            mi = ki = 0
            for bp, count in runs:
                gm = args.mats[mi:mi + len(bp.groups)]
                mi += len(bp.groups)
                bkeys = keys[ki:ki + count]
                ki += count

                def one(carry, k1, bp=bp, gm=gm):
                    Y, M = train_edge(carry, k1, bp.local)
                    for W in gm:
                        Y = gossip_mix_rows(W, Y)
                    return Y, M
                if count > 1:
                    def body(carry, k1, one=one):
                        return one(carry, k1), None
                    (Y, M), _ = jax.lax.scan(body, (Y, M), bkeys)
                else:
                    Y, M = one((Y, M), bkeys[0])
            return Y, M

        return compact_round

    def _lower_streamed(self, program: prg.RoundProgram,
                        per_client: bool = False):
        """Compile a RoundProgram to the streamed working-set round
        (ISSUE 9): ALL state is the hot (S, T) slab — the paged-in
        cohort plus one cold representative lane per cluster — and the
        mixing operators arrive already restricted to the working set
        (exact, because every masked operator row reads participant
        columns only and is a function of the row's cluster label).
        ``didx`` maps each lane to its data shard, ``cids`` carries the
        lane's virtual client id, ``lane`` marks the trainers (cold
        representative/padding lanes are ``where``-frozen and only
        mixed). ``per_client`` switches the batch draw from the
        enumerated-n gather (bitwise parity with the compacted resident
        round) to the fold_in(client id) schedule of virtual
        populations. Traced once per slab bucket."""
        xs = jnp.asarray(self.data["xs"])
        ys = jnp.asarray(self.data["ys"])
        make_local_step = self._flat_helpers("streamed")
        plans = prg.lowering_plan(program, fuse=True)
        runs = prg.block_runs(plans)
        nblocks = len(plans)
        assert not program.has_upload, \
            "streamed rounds are for plain programs only"
        assert plans[-1].groups, \
            "streamed rounds need a trailing mixing boundary (page-out " \
            "reads cluster-synced rows back as the references)"

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def streamed_round(Y, M, key, didx, cids, lane, args):
            lane2d = lane[:, None]
            xs_c, ys_c = xs[didx], ys[didx]
            tau_c = (None if args.tau_dev is None else args.tau_dev[didx])

            def train_slab(carry, k1, op):
                Y, M = carry
                local_step = make_local_step(
                    xs_c, ys_c, lane2d,
                    gather=None if per_client else didx,
                    fold_ids=cids if per_client else None,
                    tau_dev=tau_c if op.adaptive else None,
                    lr_scale=op.lr_scale)
                return self._train_scan(local_step, Y, M, k1, op)

            keys = jax.random.split(key, nblocks)
            mi = ki = 0
            for bp, count in runs:
                gm = args.mats[mi:mi + len(bp.groups)]
                mi += len(bp.groups)
                bkeys = keys[ki:ki + count]
                ki += count

                def one(carry, k1, bp=bp, gm=gm):
                    Y, M = train_slab(carry, k1, bp.local)
                    for W in gm:
                        Y = gossip_mix_rows(W, Y)
                    return Y, M
                if count > 1:
                    def body(carry, k1, one=one):
                        return one(carry, k1), None
                    (Y, M), _ = jax.lax.scan(body, (Y, M), bkeys)
                else:
                    Y, M = one((Y, M), bkeys[0])
            return Y, M

        return streamed_round

    # -- per-round program machinery ----------------------------------------
    def _get_round(self, kind: str, program: prg.RoundProgram):
        """The jitted lowering of ``program`` for one engine, compiled
        once per program *structure* (``program.signature``)."""
        key = (kind, program.signature)
        fn = self._lowered.get(key)
        if fn is None:
            lower = {"legacy": self._lower_legacy,
                     "flat": self._lower_flat,
                     "flat_block": functools.partial(self._lower_flat,
                                                     block_keyed=True),
                     "compact": self._lower_compact,
                     "streamed": self._lower_streamed,
                     "streamed_pop": functools.partial(
                         self._lower_streamed, per_client=True)}[kind]
            fn = lower(program)
            self._lowered[key] = fn
        return fn

    @property
    def _round(self):
        """Canonical-program legacy round (kept for tests/debugging)."""
        return self._get_round("legacy", self._canonical)

    @property
    def _round_flat(self):
        """Canonical-program flat round (kept for tests/debugging)."""
        return self._get_round("flat", self._canonical)

    @property
    def _round_compact(self):
        """Canonical-program compacted round (kept for tests)."""
        return self._get_round("compact", self._canonical)

    def _scenario_h(self, plan=None):
        if plan is not None and plan.H_eff is not None:
            return plan.H_eff  # link-loss-degraded backhaul (FaultModel)
        return self.engine.H if self.engine is not None else self.sched.H

    def _inter_operator(self, pi: int, plan, renorm: bool) -> np.ndarray:
        """The (n, n) inter-cluster operator at gossip depth ``pi`` for
        this round — the static schedule's W_inter when possible, else
        the (masked) time-varying eq. 11 form at the requested depth,
        built over the plan's surviving backhaul under link faults."""
        from repro.core.scenario import make_masked_w
        if plan is None:
            W = self._inter_static.get(pi)
            if W is None:
                W = make_masked_w(self.fl, self._static_labels,
                                  np.ones(self.sched.n), self.sched.H,
                                  pi=pi)[1]
                self._inter_static[pi] = W
            return W
        if renorm:
            if pi == self.fl.pi:
                return plan.W_inter
            return make_masked_w(self.fl, plan.labels, plan.mask,
                                 self._scenario_h(plan), pi=pi)[1]
        return make_masked_w(self.fl, plan.labels,
                             np.ones(plan.labels.shape[0]),
                             self._scenario_h(plan), pi=pi)[1]

    def _tier_operator(self, op: prg.TierMix, plan, renorm: bool):
        """The (n, n) dense operator of any ``TierMix`` this round.

        Levels 0/1 delegate to the existing intra/inter resolvers (the
        paper's two tiers, including the masked scenario forms). Deeper
        tiers build B_ℓ^T diag(c) H_ℓ^π B_ℓ from the hierarchy: static
        rounds cache the contiguous-assignment operator per (level, pi);
        scenario rounds recompose it from the plan's device→edge labels
        lifted to tier-ℓ nodes (mobility composes, participation masks
        renormalize)."""
        hier = self._hier
        if not (0 <= op.level < hier.depth):
            raise ValueError(
                f"TierMix level {op.level} outside hierarchy of depth "
                f"{hier.depth} (tiers {hier.levels})")
        if op.level == 0:
            if plan is None:
                return self.sched.W_intra
            if renorm:
                return plan.W_intra
            from repro.core.scenario import make_masked_w
            return make_masked_w(self.fl, plan.labels,
                                 np.ones(plan.labels.shape[0]),
                                 self._scenario_h(plan))[0]
        if op.level == 1:
            return self._inter_operator(op.pi, plan, renorm)
        ck = ("H", op.level)
        H_l = self._tier_static.get(ck)
        if H_l is None:
            H_l = hier.mixing(op.level, self.fl.topology, self.fl.mixing,
                              self.fl)
            self._tier_static[ck] = H_l
        if plan is None:
            key = (op.level, op.pi)
            W = self._tier_static.get(key)
            if W is None:
                W = hier.tier_operator(op.level, op.pi, self.fl.topology,
                                       self.fl.mixing, self.fl)
                self._tier_static[key] = W
            return W
        B = topo.assignment_matrix(
            hier.node_labels(op.level, plan.labels),
            hier.num_nodes(op.level))
        return topo.masked_inter_operator(
            B, H_l, op.pi, plan.mask if renorm else None)

    def _fault_gate(self, program: prg.RoundProgram, plan):
        """Per-op operator gate for the plan's realized faults: under a
        ``FaultGate`` directive with dark clusters, every resolved
        operator gets :func:`repro.core.gossip.fault_gate` applied
        *before* any fusion — gate(A)·gate(B) is what both the fused
        and unfused lowerings execute, keeping engine parity under
        faults. Identity otherwise."""
        if (program.fault_gate and plan is not None
                and plan.fault is not None
                and plan.fault.cluster_down.any()):
            from repro.core import gossip as gsp
            down = plan.fault.cluster_down
            labels = plan.labels
            return lambda W: gsp.fault_gate(W, labels, down)
        return lambda W: W

    def _resolve_args(self, program: prg.RoundProgram, plan,
                      fuse: bool) -> prg.RoundArgs:
        """Concrete runtime operands (mixing matrices + adaptive step
        cutoffs) for one round of ``program`` under ``plan``. Static
        rounds cache their matrices per program structure."""
        plans = prg.lowering_plan(program, fuse=fuse)
        renorm = program.mask_renorm
        if plan is None:
            ck = (fuse, program.signature)
            mats = self._static_mats.get(ck)
            if mats is None:
                mats = tuple(jnp.asarray(m) for m in prg.resolve_matrices(
                    plans, self.sched.W_intra,
                    lambda pi: self._inter_operator(pi, None, renorm),
                    tier_of=lambda op: self._tier_operator(
                        op, None, renorm)))
                self._static_mats[ck] = mats
        else:
            gate = self._fault_gate(program, plan)
            if renorm:
                W_intra = plan.W_intra
            else:
                from repro.core.scenario import make_masked_w
                W_intra = make_masked_w(self.fl, plan.labels,
                                        np.ones(plan.labels.shape[0]),
                                        self._scenario_h(plan))[0]
            mats = tuple(jnp.asarray(m) for m in prg.resolve_matrices(
                plans, gate(W_intra),
                lambda pi: gate(self._inter_operator(pi, plan, renorm)),
                tier_of=lambda op: gate(
                    self._tier_operator(op, plan, renorm))))
        tau_dev = (jnp.asarray(program.tau_dev, jnp.int32)
                   if program.adaptive else None)
        return prg.RoundArgs(mats, tau_dev)

    # -- driver -------------------------------------------------------------
    def step_round(self):
        """Advance ONE global round.

        With a scenario attached, first realizes this round's plan
        (mobility re-draws B_t, sampling draws the cohort); the schedule
        hook (or the canonical program) then decides this round's
        :class:`repro.core.program.RoundProgram`, whose resolved
        operators feed the program's lowered round for the active
        engine. In bank mode a partial cohort of a plain program
        dispatches to the compacted lowering (``last_bucket`` records
        the capacity used). Returns the ``RoundPlan`` (or None without a
        scenario); ``last_program`` records the executed program so
        callers — e.g. the wall-clock harness in core/clock.py — can
        charge the cohort per op. The call is the ``repro.round`` host
        span; its parts are the ``assemble``, ``dispatch`` and
        ``page.*`` spans (``core/spans.py``)."""
        with self._span("round"):
            if not self._streamed:
                return self._step_round_resident()
            if self._pipeline:
                return self._step_round_streamed_pipelined()
            return self._step_round_streamed()

    def _next_program(self, plan) -> prg.RoundProgram:
        """Advance the round counter and pick this round's program."""
        r = self.round_index
        self.round_index += 1
        program = (self._schedule_fn(r, plan)
                   if self._schedule_fn is not None else self._canonical)
        self.last_program = program
        return program

    def _step_round_resident(self):
        """One round of the resident engines (legacy, flat, compact)."""
        b = self.bank
        with self._span("assemble"):
            if self.engine is not None:
                plan = self.engine.step()
                self.labels = plan.labels
                mask_np = plan.mask
            else:
                plan = None
                mask_np = None
            program = self._next_program(plan)
            mask = (jnp.asarray(mask_np, jnp.float32)
                    if mask_np is not None else self._full_mask)
            self.key, k = jax.random.split(self.key)
            args = self._resolve_args(program, plan, fuse=b is not None)
            cp = None
            if b is not None:
                k_active = b.n if mask_np is None else int(mask_np.sum())
                # 0 < k_active: a fully-dark fault round (empty cohort)
                # cannot compact — it runs the flat path, where the zero
                # mask freezes training and the fault-gated operators are
                # the identity
                if (not program.has_upload and 0 < k_active < b.n
                        and self._compact_enabled):
                    cp = compact_plan(mask_np, self._buckets)
                self.last_bucket = b.n if cp is None else cp.k_pad
        with self._span("dispatch"):
            if b is None:
                fn = self._get_round("legacy", program)
                self._params, self._mom, self._residual = fn(
                    self._params, self._mom, self._residual, k, args, mask)
            elif cp is not None:
                fn = self._get_round("compact", program)
                b.params, b.mom = fn(b.params, b.mom, k,
                                     jnp.asarray(cp.idx),
                                     jnp.asarray(cp.lane), args)
            else:
                fn = self._get_round("flat", program)
                b.params, b.mom, b.residual = fn(
                    b.params, b.mom, b.residual, k, args, mask)
        return plan

    def _check_streamed(self, program: prg.RoundProgram) -> None:
        assert not program.has_upload, \
            "streamed rounds reject upload programs (EF residual and " \
            "DP noise are per-device state the store does not page)"
        assert program.mask_renorm, \
            "streamed rounds need mask-renormalized operators — " \
            "unrenormalized rows weight absent cold members"

    def _step_round_streamed(self):
        """One streamed global round: page in the working set, run the
        slab-restricted program, page out (ISSUE 9).

        The working set is the round's cohort plus one cold
        representative lane per cluster; its params page in from the
        store's per-cluster references (each lane reads the reference
        of its cluster *at its last sync* — tracked by ``_page_labels``
        at enumerated n, the current attachment with a virtual
        population, where attaching IS downloading the edge's model),
        its momentum from the cold rows (zeros on first touch). After
        the round the trailing cluster-level boundary has synced every
        lane of a cluster to one value, so page-out reads one lane per
        cluster back as its reference (skipping fault-dark clusters,
        whose gated rows never mixed) and re-encodes the cohort's
        momentum. Known, documented approximations vs the resident
        engine: a cluster left without any working-set lane (possible
        only under visit mobility + full sampling) keeps a stale
        reference for the round."""
        from repro.core.modelbank import ModelBank as MB
        from repro.core.scenario import RoundPlan, make_masked_w
        st = self.store
        m = self.fl.num_clusters
        with self._span("assemble"):
            plan = self.engine.step() if self.engine is not None else None
            r = self.round_index
            program = self._next_program(plan)
            self._check_streamed(program)
            ws = self._working_set(plan)
            if self.pop is None:
                self.labels = ws["labels_now"]
            k, S = ws["k"], ws["S"]
            clients, ws_labels = ws["clients"], ws["ws_labels"]
            H_t = self._scenario_h(plan)
            W_i, W_e = make_masked_w(self.fl, ws_labels, ws["mask_slab"],
                                     H_t)
            splan = RoundPlan(r, m, ws_labels, ws["mask_slab"], W_i, W_e,
                              fault=ws["fault"], H_eff=ws["h_eff"])
            args = self._resolve_args(program, splan, fuse=True)
        # page-in: params from each lane's last-sync cluster reference,
        # momentum decoded for the trainers only (cold lanes never step)
        with self._span("page.stage"):
            params_rows = st.cluster_params[ws["src_labels"]]
            mom_rows = np.zeros((S, self._layout.total), np.float32)
            if k:
                mom_rows[:k] = st.fetch(clients[:k])
            slab = MB.from_rows(self._layout, params_rows, mom_rows,
                                sharding=self._slab_sharding)
        with self._span("dispatch"):
            self.key, k_ = jax.random.split(self.key)
            fn = self._get_round(
                "streamed_pop" if self.pop is not None else "streamed",
                program)
            Y, M = fn(slab.params, slab.mom, k_,
                      jnp.asarray(ws["didx"], jnp.int32),
                      jnp.asarray(clients, jnp.int32),
                      jnp.asarray(ws["lane"]), args)
        # the wait for the round's compute is no paging: page.wait
        # starts once the slab is ready
        jax.block_until_ready((Y, M))
        with self._span("page.wait"):
            Yh = np.asarray(jax.device_get(Y), np.float32)
            Mh = np.asarray(jax.device_get(M), np.float32)
        # page-out: last lane of each cluster (representatives win over
        # participants by position) carries the synced reference
        with self._span("page.commit"):
            fault = ws["fault"]
            ref_lane = np.full(m, -1, np.int64)
            ref_lane[ws_labels] = np.arange(S)
            down = (fault.cluster_down if fault is not None else None)
            refs = st.cluster_params.copy()
            for c in range(m):
                j = int(ref_lane[c])
                if j < 0 or (down is not None and down[c]):
                    continue  # no working-set lane / dark cluster: stale
                refs[c] = Yh[j]
            st.update_clusters(refs)
            if k:
                st.commit(clients[:k], Mh[:k])
        if self.pop is None:
            # next round's page-in reads the reference of the cluster a
            # device sat in NOW: the trailing boundary synced every row
            self._page_labels = self.labels.copy()
        self.last_bucket = S
        self._peak_slab = max(self._peak_slab,
                              2 * 4 * S * self._layout.total)
        # paging = device↔edge traffic: each trainer downloads its row
        # and uploads it back (references live at the edge already)
        self.last_paging = {"rows_in": k, "rows_out": k,
                            "bits_per_row": st.bits_per_row}
        return plan

    def _working_set(self, plan):
        """Assemble one streamed round's working set from its plan —
        shared verbatim by the serial and pipelined drivers (identical
        assembly is half of their bit-identity). Pure w.r.t. engine and
        store state; reads ``self._page_labels`` (enumerated mode), so
        the pipelined prefetch must call it *after* the previous round
        updated the labels."""
        m = self.fl.num_clusters
        if self.pop is not None:
            # virtual population: cohort ids from the keyed engine, one
            # cold representative per (not fully sampled) cluster; a
            # lane's data shard is its id mod the enumerated shard count
            cohort = np.asarray(plan.clients, np.int64)
            reps = self.engine.representatives(cohort)
            clients = np.concatenate([cohort, reps])
            ws_labels = np.concatenate(
                [np.asarray(plan.labels, np.int64),
                 self.engine.home_cluster(reps)])
            src_labels = ws_labels
            didx = clients % self.data["xs"].shape[0]
            labels_now, h_eff = None, None
        else:
            # enumerated n: the scenario plan's cohort (or everyone)
            if plan is not None:
                labels_now = np.asarray(plan.labels, np.int64)
                mask_np = np.asarray(plan.mask)
                h_eff = plan.H_eff
            else:
                labels_now = self.labels
                mask_np = np.ones(self.sched.n)
                h_eff = None
            cold = mask_np <= 0
            cohort = np.nonzero(~cold)[0].astype(np.int64)
            reps = np.asarray(
                [np.nonzero(cold & (labels_now == c))[0][0]
                 for c in range(m)
                 if (cold & (labels_now == c)).any()], np.int64)
            clients = np.concatenate([cohort, reps])
            ws_labels = labels_now[clients]
            src_labels = self._page_labels[clients]
            didx = clients
        k = int(cohort.shape[0])
        S_raw = int(clients.shape[0])
        S = bucket_for(S_raw, self._buckets)
        pad = S - S_raw
        if pad:
            # padding duplicates lane 0 wholesale (client id, labels,
            # data shard) with lane=False: a frozen extra cold member of
            # lane 0's cluster, whose post-round row is that cluster's
            # synced value — safe even as a page-out read
            clients = np.concatenate([clients, np.repeat(clients[:1], pad)])
            ws_labels = np.concatenate(
                [ws_labels, np.repeat(ws_labels[:1], pad)])
            src_labels = np.concatenate(
                [src_labels, np.repeat(src_labels[:1], pad)])
            didx = np.concatenate([didx, np.repeat(didx[:1], pad)])
        lane = np.zeros(S, bool)
        lane[:k] = True
        return {"cohort": cohort, "clients": clients,
                "ws_labels": ws_labels, "src_labels": src_labels,
                "didx": didx, "k": k, "S": S, "lane": lane,
                "mask_slab": lane.astype(float),
                "labels_now": labels_now, "h_eff": h_eff,
                "fault": getattr(plan, "fault", None)}

    # -- overlapped streamed driver (ISSUE 10) -------------------------------
    def _peek_plan(self):
        """Compute the NEXT round's plan without advancing the engine.

        Sound because every engine draw is keyed by (seed, round,
        stream, entity) — ``step()`` only *reassigns* ``round_index`` /
        ``labels`` / ``speed_multipliers`` (and FaultModel is stateless)
        — so saving those references, stepping, and restoring them
        leaves the engine bit-identical while yielding the plan the
        real ``step()`` will reproduce next round (asserted there)."""
        eng = self.engine
        if eng is None:
            return None
        saved = [(a, getattr(eng, a))
                 for a in ("round_index", "labels", "speed_multipliers")
                 if hasattr(eng, a)]
        try:
            plan = eng.step()
        finally:
            for a, v in saved:
                setattr(eng, a, v)
        return plan

    @staticmethod
    def _plans_match(a, b) -> bool:
        """Prefetch-invariant check: the peeked plan equals the real one
        (keyed draws make this structural; a mismatch means engine state
        was perturbed between rounds)."""
        if a is None or b is None:
            return a is b
        for f in ("clients", "labels", "mask"):
            va, vb = getattr(a, f, None), getattr(b, f, None)
            if (va is None) != (vb is None):
                return False
            if va is not None and not np.array_equal(np.asarray(va),
                                                     np.asarray(vb)):
                return False
        return True

    def _stage_pipelined(self, plan, r: int):
        """Stage round ``r``'s page-in: assemble its working set, gather
        the cohort's *encoded* cold rows (commits ≤ r-2 from the store;
        the r-1 delta arrives by device-side forwarding at dispatch) and
        start their H2D transfer — all while round r-1 computes."""
        ws = self._working_set(plan)
        k, S = ws["k"], ws["S"]
        qc, sc = self.store.fetch_encoded(ws["cohort"])
        # rep/pad lanes page in zero momentum, exactly like the serial
        # driver's zero-fill beyond [:k] (zero q + zero scale decode to
        # exact zeros under every codec). The host buffers are cached,
        # two per bucket used in turn: a put may read its host buffer
        # until the round that consumes it has run (the CPU backend
        # aliases it outright), and the buffer staged two rounds back
        # belongs to a round the previous drain has already landed.
        # Only [k:] needs re-zeroing.
        bufs = getattr(self, "_stage_bufs", None)
        if bufs is None:
            bufs = self._stage_bufs = {}
        if S not in bufs:
            bufs[S] = [(np.zeros((S, self._layout.total), qc.dtype),
                        np.zeros((S, sc.shape[1]), np.float32))
                       for _ in range(2)]
        bufs[S].reverse()
        q, s = bufs[S][0]
        q[:k] = qc
        q[k:] = 0
        s[:k] = sc
        s[k:] = 0
        ws["q"], ws["s"] = jax.device_put((q, s), self._slab_sharding)
        ws["plan"], ws["r"] = plan, r
        return ws

    def _land_pending(self):
        """Host copies of the in-flight page-out (None if there is none):
        blocks on the async D2H of the last dispatched round."""
        p = getattr(self, "_pipe", None)
        if not p or p.get("pending") is None:
            return None
        pend, p["pending"] = p["pending"], None
        k = pend["k"]
        landed = {"cohort": pend["cohort"], "k": k,
                  "refs": np.asarray(pend["refs"], np.float32)}
        if k:
            landed["q"] = np.asarray(pend["q"])[:k]
            landed["s"] = np.asarray(pend["s"], np.float32)[:k]
        return landed

    def _commit_landed(self, landed) -> None:
        """Commit a landed page-out's encoded momentum to the store and
        mirror its cluster references."""
        if landed is None:
            return
        st = self.store
        st.update_clusters(landed["refs"])
        if landed["k"]:
            st.commit_encoded(landed["cohort"], landed["q"], landed["s"])

    def _drain_pipeline(self):
        """Land the in-flight page-out (if any) in the host store:
        blocks on the async D2H of the last dispatched round, then
        commits its encoded momentum and mirrors the cluster references.
        Called by the next round (overlapped by that round's compute)
        and by every store reader — eval, checkpoint capture — so
        observable host state is always round-complete."""
        self._commit_landed(self._land_pending())

    def _step_round_streamed_pipelined(self):
        """One overlapped streamed round (ISSUE 10 tentpole).

        Vs the serial driver, per dispatched round t the host only (a)
        drains round t-1's encoded page-out and (b) stages round t+1's
        page-in from the peeked plan — both overlapped by round t's
        device compute, so steady-state round time approaches
        max(compute, page) instead of compute + page. The cluster
        references live on device across rounds (params never ride the
        link per round; only the (m, T) mirror comes back), and the
        momentum link traffic is codec-width both ways.

        Delayed-commit bookkeeping: when round t+1 is staged, the store
        holds commits ≤ t-1 (t is still in flight), so clients sampled
        in both rounds t and t+1 get their newest momentum forwarded
        on device from round t's encoded page-out — covering exactly
        the missing delta. The store itself is only read by staging,
        never by the round, so eval/checkpoint drains stay cheap. The
        first round (and the first after a restore) stages its own
        page-in from the peeked plan, as every later round's is."""
        from repro.core.scenario import RoundPlan, make_masked_w
        st = self.store
        m = self.fl.num_clusters
        if self._pipe is None:
            self._pipe = {"refs": None, "pending": None,
                          "staged": None, "prev": None}
        if self._pipe_fns is None:
            self._pipe_fns = paging_fns(st.codec, self._layout.segments,
                                        self._slab_sharding)
        p = self._pipe
        if p["refs"] is None:
            p["refs"] = jnp.asarray(st.cluster_params, jnp.float32)
        pre_fn, post_fn = self._pipe_fns
        if p["staged"] is None:
            with self._span("page.stage"):
                p["staged"] = self._stage_pipelined(self._peek_plan(),
                                                    self.round_index)
        with self._span("assemble"):
            plan = self.engine.step() if self.engine is not None else None
            r = self.round_index
            program = self._next_program(plan)
            self._check_streamed(program)
            ws, p["staged"] = p["staged"], None
            assert ws["r"] == r and self._plans_match(ws["plan"], plan), \
                "prefetched plan diverged from the engine's real draw " \
                "(engine state was perturbed between rounds)"
            if self.pop is None:
                self.labels = ws["labels_now"]
                self._page_labels = ws["labels_now"].copy()
            k, S = ws["k"], ws["S"]
            H_t = self._scenario_h(plan)
            W_i, W_e = make_masked_w(self.fl, ws["ws_labels"],
                                     ws["mask_slab"], H_t)
            splan = RoundPlan(r, m, ws["ws_labels"], ws["mask_slab"], W_i,
                              W_e, fault=ws["fault"], H_eff=ws["h_eff"])
            args = self._resolve_args(program, splan, fuse=True)
            # device-side forwarding: rows of the previous cohort sampled
            # again now (their commit is still in flight); padded to a
            # static length, OOB dst entries drop
            src = np.zeros(S, np.int64)
            dst = np.full(S, S, np.int64)
            prev = p["prev"]
            if prev is not None:
                _, si, di = np.intersect1d(prev["cohort"], ws["cohort"],
                                           assume_unique=True,
                                           return_indices=True)
                src[:si.shape[0]] = si
                dst[:di.shape[0]] = di
            # page-out on device: the synced lane of each cluster
            fault = ws["fault"]
            ref_lane = np.full(m, -1, np.int64)
            ref_lane[ws["ws_labels"]] = np.arange(S)
            down = (np.asarray(fault.cluster_down, bool)
                    if fault is not None else np.zeros(m, bool))
            upd = (ref_lane >= 0) & ~down
            lanes = np.where(ref_lane >= 0, ref_lane, 0)
        with self._span("dispatch"):
            if prev is not None:
                q_prev, s_prev = prev["q"], prev["s"]
            else:
                q_prev = jnp.zeros((1,) + ws["q"].shape[1:], ws["q"].dtype)
                s_prev = jnp.zeros((1,) + ws["s"].shape[1:], jnp.float32)
            Y0, M0 = pre_fn(p["refs"],
                            jnp.asarray(ws["src_labels"], jnp.int32),
                            ws["q"], ws["s"], q_prev, s_prev,
                            jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32))
            self.key, k_ = jax.random.split(self.key)
            fn = self._get_round(
                "streamed_pop" if self.pop is not None else "streamed",
                program)
            Y, M = fn(Y0, M0, k_,
                      jnp.asarray(ws["didx"], jnp.int32),
                      jnp.asarray(ws["clients"], jnp.int32),
                      jnp.asarray(ws["lane"]), args)
            # D2H starts now, lands at the next drain
            refs_new, q_out, s_out = post_fn(Y, M, p["refs"],
                                             jnp.asarray(upd),
                                             jnp.asarray(lanes, jnp.int32))
            for a in (q_out, s_out, refs_new):
                a.copy_to_host_async()
        p["refs"] = refs_new
        # drain round r-1 (its D2H overlapped round r's dispatch) and
        # only then stage r+1, so staging sees commits ≤ r-1 and the
        # forwarding delta is exactly cohort r
        with self._span("page.wait"):
            landed = self._land_pending()
        with self._span("page.commit"):
            self._commit_landed(landed)
            p["pending"] = {"cohort": ws["cohort"], "k": k,
                            "q": q_out, "s": s_out, "refs": refs_new}
            p["prev"] = {"cohort": ws["cohort"], "q": q_out, "s": s_out}
        with self._span("page.stage"):
            p["staged"] = self._stage_pipelined(self._peek_plan(), r + 1)
        self.last_bucket = S
        self._peak_slab = max(self._peak_slab,
                              2 * 4 * S * self._layout.total)
        self.last_paging = {"rows_in": k, "rows_out": k,
                            "bits_per_row": st.bits_per_row}
        return plan

    @property
    def peak_slab_bytes(self) -> int:
        """Largest hot slab (params + momentum) any streamed round
        materialized — the O(cohort) resident bound the scale bench
        guards; 0 before the first round / for resident engines."""
        return int(getattr(self, "_peak_slab", 0))

    def step_round_async(self, staleness: int, rt, *,
                         uplink_ratio: float = 1.0):
        """Advance ONE global round in async bounded-staleness mode.

        Instead of one barrier round, the round's blocks execute as a
        per-cluster *event sequence*:
        :func:`repro.core.clock.async_program_timeline` schedules when
        each cluster clears each block under the wait rule (own previous
        block done AND every dependency neighbor within ``staleness``
        blocks), and each event replays that block for its advancing
        clusters only — local steps masked to their devices, the block's
        fused mixing operator gated by
        :func:`repro.core.gossip.staleness_mask` so a boundary never
        reads a model more than ``staleness`` blocks away. At
        ``staleness == 0`` every event advances all clusters in lockstep
        with the unmodified operator and the barrier key schedule,
        reproducing ``step_round``'s flat path (the parity anchor
        ``tests/test_async.py`` fuzzes).

        ``rt`` is the :class:`repro.core.runtime.RuntimeModel` whose
        compute/comm pricing orders the events (the model state only
        depends on the event *order*, not the absolute times). Only
        plain programs are supported — upload blocks carry
        error-feedback residual state that is not staleness-safe — and
        only the bank engines. Returns the round's ``RoundPlan`` (or
        None without a scenario) and records ``last_async`` with the
        timeline, the staleness bound, the cumulative per-cluster phase
        vector, and a per-event trace (pre-advance phases + realized
        cross-cluster gossip edges of the masked operator)."""
        assert not self._streamed, \
            "async bounded-staleness execution needs resident rows " \
            "(blocks replay against the full bank, not a paged slab)"
        assert self.bank is not None, \
            "async bounded-staleness execution requires a bank engine"
        from repro.core import clock as clk
        from repro.core import gossip as gsp
        if self.engine is not None:
            plan = self.engine.step()
            self.labels = plan.labels
            mask_np = plan.mask
        else:
            plan = None
            mask_np = None
        r = self.round_index
        self.round_index += 1
        program = (self._schedule_fn(r, plan)
                   if self._schedule_fn is not None else self._canonical)
        assert not program.has_upload, \
            "async mode supports plain programs only (no upload/EF state)"
        self.last_program = program
        m = self.fl.num_clusters
        mult = (None if self.engine is None
                else np.asarray(self.engine.speed_multipliers, float))
        fleet = None if mult is None else mult * rt.hw.device_flops
        # per-cluster timeline carried across rounds — same evolution as
        # EventClock.charge_program_async's, so the executor's event
        # order matches the charged timeline; s=0 is a pure barrier, so
        # it forgets any staggered front a previous async round left
        carry = (None if staleness == 0
                 else getattr(self, "_async_carry", None))
        tl = clk.async_program_timeline(
            rt, self.fl, program, fleet, mask_np, self.labels,
            staleness, uplink_ratio, carry=carry)
        self._async_carry = None if staleness == 0 else tl["carry_out"]
        bprogs = prg.block_programs(program)
        nblocks = len(bprogs)
        base_args = [self._resolve_args(bp, plan, fuse=True)
                     for bp in bprogs]
        cohort = (np.ones(self.sched.n) if mask_np is None
                  else np.asarray(mask_np, float))
        self.key, k = jax.random.split(self.key)
        # host-side split == the barrier round's in-jit split of k
        bkeys = jax.random.split(k, nblocks)
        b = self.bank
        self.last_bucket = b.n
        phases = np.zeros(m, dtype=int)
        trace: List[Dict[str, Any]] = []
        for ev in tl["events"]:
            adv = np.zeros(m, dtype=bool)
            adv[list(ev.clusters)] = True
            assert (phases[adv] == ev.block).all(), "phase skew"
            base = base_args[ev.block]
            assert len(base.mats) == 1  # fused plain block: one MixGroup
            Wm = gsp.staleness_mask(np.asarray(base.mats[0]),
                                    self.labels, phases, staleness, adv)
            ev_mask = jnp.asarray(cohort * adv[self.labels], jnp.float32)
            args = prg.RoundArgs((jnp.asarray(Wm),), base.tau_dev)
            fn = self._get_round("flat_block", bprogs[ev.block])
            b.params, b.mom, b.residual = fn(
                b.params, b.mom, b.residual, bkeys[ev.block], args,
                ev_mask)
            cross = ((np.asarray(Wm) != 0)
                     & (self.labels[:, None] != self.labels[None, :]))
            ii, jj = np.nonzero(cross)
            edges = sorted({(int(a), int(c)) for a, c in
                            zip(self.labels[ii], self.labels[jj])})
            trace.append({"time": ev.time, "block": ev.block,
                          "clusters": ev.clusters,
                          "phases": phases.copy(), "edges": edges})
            phases[adv] += 1
        assert (phases == nblocks).all(), "round left clusters mid-phase"
        self._async_phases = (getattr(self, "_async_phases",
                                      np.zeros(m, dtype=int)) + phases)
        self.last_async = {"timeline": tl, "trace": trace,
                           "staleness": int(staleness),
                           "phases": self._async_phases.copy()}
        return plan

    def run(self, rounds: int, eval_every: int = 1,
            eval_batch: int = 512) -> Dict[str, List[float]]:
        hist: Dict[str, List[float]] = {"round": [], "acc": [], "loss": []}
        for r in range(rounds):
            self.step_round()
            if (r + 1) % eval_every == 0:
                acc, loss = self.evaluate(eval_batch)
                hist["round"].append(r + 1)
                hist["acc"].append(acc)
                hist["loss"].append(loss)
        return hist

    def edge_models(self):
        """Cluster-averaged (edge) models y_t — what the paper evaluates.
        Uses the CURRENT assignment B_t (mobility moves devices between
        clusters, so membership is re-read every call). In bank mode the
        (m, n) projection streams the flat bank once."""
        if self._streamed:
            # the streamed store's per-cluster references ARE y_t
            # (pipelined: land the in-flight round's refs first)
            self._drain_pipeline()
            return self._layout.unflatten_stack(
                jnp.asarray(self.store.cluster_params))
        B = topo.assignment_matrix(self.labels, self.fl.num_clusters)
        P = topo.masked_cluster_average(B)
        if self.bank is not None:
            return self.bank.project(P)
        # mix() row-applies, so a rectangular (m, n) averaging operator
        # maps the n device models straight to the m edge models
        return mix(P, self._params)

    def global_model(self):
        """Device-average model x̄ as a single pytree."""
        if self._streamed:
            self._drain_pipeline()
            # end-of-round rows are cluster-uniform, so the device
            # average is the cluster-size-weighted reference average
            sizes = (self.pop.sizes.astype(np.float64)
                     if self.pop is not None
                     else np.bincount(self.labels,
                                      minlength=self.fl.num_clusters)
                     .astype(np.float64))
            w = sizes / sizes.sum()
            row = (np.asarray(self.store.cluster_params, np.float64)
                   * w[:, None]).sum(0).astype(np.float32)
            return self._layout.unflatten_one(jnp.asarray(row))
        if self.bank is not None:
            return self.bank.mean_model()
        return jax.tree.map(lambda l: jnp.mean(l, 0), self._params)

    def _build_eval(self):
        """One jitted eval closure for the simulator's lifetime; jit's
        shape cache makes each distinct (m, eval_batch) trace once
        instead of re-tracing the vmapped closure per ``evaluate`` call."""
        def eval_impl(em, tx, ty):
            def one(p):
                logits = self.apply_fn(p, tx)
                acc = jnp.mean(
                    (jnp.argmax(logits, -1) == ty).astype(jnp.float32))
                return acc, self._loss(p, tx, ty)
            accs, losses = jax.vmap(one)(em)
            return jnp.mean(accs), jnp.mean(losses)
        return jax.jit(eval_impl)

    def evaluate(self, eval_batch: int = 512):
        """Mean test accuracy of the m edge models on the common test set."""
        em = self.edge_models()
        tx = self.data["test_x"][:eval_batch]
        ty = self.data["test_y"][:eval_batch]
        acc, loss = self._eval_fn(em, tx, ty)
        return float(acc), float(loss)
