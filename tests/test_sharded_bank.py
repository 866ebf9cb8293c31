"""Sharded ModelBank engine (ISSUE 4): device-parallel flat-bank CE-FedAvg.

These tests run IN-PROCESS on a multi-device host: they are marked
``multidevice`` and skip themselves unless jax sees >= 8 devices. The CI
``multidevice`` lane (and the slow subprocess wrapper in
``test_sharded.py``, which keeps tier-1 coverage on single-device hosts)
runs them under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
so bank-shard parity is checked on every PR without subprocess latency.

Covered: trajectory parity vs the single-device ModelBank engine (static
schedule, lognormal+mobility+sampling scenario, compression with error
feedback, every baseline algorithm, multi-pod meshes), the traffic
contract (the gossip boundary lowers to neighbor ``collective-permute``s,
never an all-gather of the bank), and the memory contract (per-device
state is the (1, T) row shard; round buffers are donated).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig, ScenarioConfig
from repro.core.cefedavg import FLSimulator
from repro.core.compress import CompressionConfig
from repro.core.sharded import ShardedBankCEFedAvg
from repro.data.federated import (build_fl_data, dirichlet_partition,
                                  make_synthetic_classification)
from repro.launch.mesh import make_replica_mesh
from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier

NDEV = 8

pytestmark = [
    pytest.mark.multidevice,
    pytest.mark.skipif(
        jax.device_count() < NDEV,
        reason=f"needs {NDEV} devices; run under XLA_FLAGS="
               f"--xla_force_host_platform_device_count={NDEV} "
               f"(the CI multidevice lane does)"),
]

_FL = FLConfig(algorithm="ce_fedavg", num_clusters=4,
               devices_per_cluster=2, tau=2, q=2, pi=4, topology="ring")
ATOL = 2e-4


def _data(fl, seed=3):
    x, y = make_synthetic_classification(800, 16, 4, seed=seed)
    tx, ty = make_synthetic_classification(200, 16, 4, seed=seed + 1)
    parts = dirichlet_partition(y, fl.n, alpha=0.5, seed=5)
    d = build_fl_data(x, y, parts, tx, ty, samples_per_device=64)
    return {k: jnp.asarray(v) for k, v in d.items()}


def _pair(fl, mesh, **kw):
    """(single-device ModelBank sim, sharded-bank sim) — same seeds."""
    kw.setdefault("lr", 0.1)
    kw.setdefault("batch_size", 16)
    kw.setdefault("seed", 0)
    init = lambda k: init_mlp_classifier(k, 16, 32, 4)   # noqa: E731
    ref = FLSimulator(init, apply_mlp_classifier, fl, _data(fl), **kw)
    sb = ShardedBankCEFedAvg(init, apply_mlp_classifier, fl, _data(fl),
                             mesh, **kw)
    return ref, sb


def _maxdiff(a, b):
    return float(jnp.max(jnp.abs(a - b)))


@pytest.fixture(scope="module")
def mesh():
    return make_replica_mesh(NDEV)


# ---------------------------------------------------------------------------
# trajectory parity vs the single-device ModelBank engine
# ---------------------------------------------------------------------------

def test_static_trajectory_parity(mesh):
    """3 rounds of the static ce_fedavg schedule: the psum+ppermute
    boundaries reproduce the fused dense W_inter@W_intra pass."""
    ref, sb = _pair(_FL, mesh)
    for _ in range(3):
        ref.step_round()
        sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL
    assert _maxdiff(ref.bank.mom, sb.bank.mom) < ATOL
    acc_r, loss_r = ref.evaluate(128)
    acc_s, loss_s = sb.evaluate(128)
    assert acc_r == pytest.approx(acc_s, abs=1e-6)
    assert loss_r == pytest.approx(loss_s, abs=1e-4)


def test_scenario_trajectory_parity(mesh):
    """Lognormal speeds + mobility + client sampling: identical plans on
    both engines (same scenario seed), and the dense-rotation boundary
    reproduces the masked time-varying operators row for row."""
    # 0.5 of each 2-device cluster: the stratified keyed sampler draws
    # 1 per cluster, so every round has a partial cohort
    sc = ScenarioConfig(name="t", speed_dist="lognormal", speed_spread=0.6,
                        sample_fraction=0.5, move_prob=0.3, seed=7)
    ref, sb = _pair(_FL, mesh, scenario=sc)
    sampled = False
    for _ in range(4):
        p1 = ref.step_round()
        p2 = sb.step_round()
        assert np.array_equal(p1.mask, p2.mask)
        assert np.array_equal(p1.labels, p2.labels)
        sampled |= bool(p1.mask.sum() < _FL.n)
    assert sampled, "scenario never sampled a partial cohort"
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL
    assert _maxdiff(ref.bank.mom, sb.bank.mom) < ATOL


def test_compression_error_feedback_parity(mesh):
    """Upload path: top-k compression with EF — the residual bank shard
    threads through the sharded round bit-compatibly."""
    comp = CompressionConfig(kind="topk", topk_frac=0.25,
                             error_feedback=True)
    ref, sb = _pair(_FL, mesh, compression=comp)
    for _ in range(2):
        ref.step_round()
        sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL
    assert _maxdiff(ref.bank.residual, sb.bank.residual) < ATOL


@pytest.mark.parametrize("algo,m,dpc", [
    ("fedavg", 1, 8), ("hier_favg", 4, 2),
    ("local_edge", 4, 2), ("dec_local_sgd", 8, 1)])
def test_baseline_algorithms_parity(mesh, algo, m, dpc):
    """Non-gossip baselines take the general dense-rotation path."""
    fl = FLConfig(algorithm=algo, num_clusters=m, devices_per_cluster=dpc,
                  tau=2, q=2, pi=2)
    ref, sb = _pair(fl, mesh)
    ref.step_round()
    sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL


def test_multipod_trajectory_parity():
    """pod x data mesh: flat replica ids cross the pod boundary."""
    mesh2 = make_replica_mesh(NDEV, pods=2)
    ref, sb = _pair(_FL, mesh2)
    for _ in range(2):
        ref.step_round()
        sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL


# ---------------------------------------------------------------------------
# traffic + memory contracts
# ---------------------------------------------------------------------------

def test_gossip_boundary_is_ppermute_not_allgather(mesh):
    """The static round's inter-cluster boundary must lower to neighbor
    collective-permutes (O(pi*deg*T) bytes); an all-gather would
    materialize the full (n, T) bank on every device."""
    _, sb = _pair(_FL, mesh)
    b = sb.bank
    args = sb._resolve_args(sb._canonical, None, fuse=True)
    hlo = sb._round_flat.lower(
        b.params, b.mom, None, sb.key, args,
        sb._full_mask).compile().as_text()
    assert "collective-permute" in hlo, "gossip boundary lost its ppermutes"
    assert "all-gather" not in hlo, \
        "round all-gathers the bank — sharding is broken"
    assert "all-to-all" not in hlo


def test_row_shards_and_donation(mesh):
    """Each device holds exactly its contiguous (1, T) bank row, and the
    jitted round donates the previous round's buffers (peak per-device
    memory ~1x the resident shards)."""
    _, sb = _pair(_FL, mesh)
    T = sb.bank.layout.total
    for buf in (sb.bank.params, sb.bank.mom):
        shards = buf.addressable_shards
        assert len(shards) == NDEV
        assert all(s.data.shape == (1, T) for s in shards)
        assert all(s.data.nbytes == sb.bank.layout.row_nbytes
                   for s in shards)
    y0, m0 = sb.bank.params, sb.bank.mom
    sb.step_round()
    assert y0.is_deleted() and m0.is_deleted(), \
        "round did not donate the bank shards"
    # state stays row-sharded across rounds (no silent re-layout)
    assert sb.bank.params.sharding == sb._row_sharding


def test_edge_projection_of_sharded_bank_is_xla(mesh, monkeypatch):
    """A row-sharded bank projects its edge models through XLA: the
    Pallas mixing kernel cannot be partitioned across the row shards
    (on a TPU mesh it would refuse to compile)."""
    from repro.core import modelbank
    ref, sb = _pair(_FL, mesh)
    ref.step_round()
    sb.step_round()

    def no_kernel(*a, **k):
        raise AssertionError("Pallas path on a sharded bank")
    monkeypatch.setattr(modelbank, "gossip_mix_rows", no_kernel)
    got = sb.edge_models()
    monkeypatch.undo()
    want = ref.edge_models()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _maxdiff(g, w) < ATOL


def test_mesh_guards():
    """Row-per-device and no-tensor-parallel preconditions are enforced."""
    mesh = make_replica_mesh(NDEV)
    fl = FLConfig(num_clusters=2, devices_per_cluster=2)  # n=4 != 8
    init = lambda k: init_mlp_classifier(k, 16, 32, 4)    # noqa: E731
    with pytest.raises(AssertionError, match="one bank row per replica"):
        ShardedBankCEFedAvg(init, apply_mlp_classifier, fl, _data(fl),
                            mesh)
    # model axis > 1: rows are not tensor-parallel
    import numpy as _np
    mesh_mp = jax.sharding.Mesh(
        _np.asarray(jax.devices()[:NDEV]).reshape(4, 2),
        ("data", "model"))
    with pytest.raises(AssertionError, match="not tensor-parallel"):
        ShardedBankCEFedAvg(init, apply_mlp_classifier, fl, _data(fl),
                            mesh_mp)


# ---------------------------------------------------------------------------
# RoundProgram lowering parity (ISSUE 5): arbitrary programs, sharded
# ---------------------------------------------------------------------------

def _random_program(seed, n):
    from test_program import random_program
    return random_program(np.random.default_rng(seed), n)


@pytest.mark.parametrize("seed", [0, 1])
def test_program_fuzz_parity_static(mesh, seed):
    """Randomized-schedule fuzz: the sharded lowering (psum + per-π
    ppermute matchings, cluster-mean dedupe at fused boundaries) must
    reproduce the single-device flat lowering on the same program."""
    prog = _random_program(seed, _FL.n)
    ref, sb = _pair(_FL, mesh, schedule=prog)
    for _ in range(2):
        ref.step_round()
        sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL
    assert _maxdiff(ref.bank.mom, sb.bank.mom) < ATOL


def test_program_fuzz_parity_scenario(mesh):
    """Masked/mobility rounds of a random program take the dense-rotation
    path; trajectories still match the single-device engine."""
    prog = _random_program(7, _FL.n)
    sc = ScenarioConfig(name="t", speed_dist="lognormal", speed_spread=0.6,
                        sample_fraction=0.5, move_prob=0.3, seed=5)
    ref, sb = _pair(_FL, mesh, scenario=sc, schedule=prog)
    for _ in range(3):
        p1 = ref.step_round()
        p2 = sb.step_round()
        assert np.array_equal(p1.mask, p2.mask)
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL


def test_adaptive_tau_schedule_parity(mesh):
    """The adaptive-τ_k schedule (per-device tau_dev cutoffs threaded as
    a replicated operand into the shard_map body) matches the
    single-device engine under a heterogeneous scenario."""
    sc = ScenarioConfig(name="t", speed_dist="lognormal", speed_spread=0.6,
                        seed=9)
    fl = FLConfig(algorithm="ce_fedavg", num_clusters=4,
                  devices_per_cluster=2, tau=4, q=2, pi=4, topology="ring")
    ref, sb = _pair(fl, mesh, scenario=sc, schedule="adaptive_tau")
    for _ in range(2):
        ref.step_round()
        sb.step_round()
    assert ref.last_program.adaptive
    assert np.array_equal(ref.last_program.tau_dev,
                          sb.last_program.tau_dev)
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL


def test_pi_decay_schedule_parity_and_recompile_bound(mesh):
    """π_t decay: the sharded lowering rebuilds its GossipSchedule per
    distinct π (structured path) — exactly two compiled variants."""
    ref, sb = _pair(_FL, mesh, schedule="pi_decay")
    for _ in range(3):
        ref.step_round()
        sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL
    # decay_round=5 default: only the early program compiled so far
    assert len(sb._lowered) == 1


# ---------------------------------------------------------------------------
# GroupRegistry tiers + per-shard init (ISSUE 6)
# ---------------------------------------------------------------------------

_FL3 = FLConfig(algorithm="ce_fedavg", num_clusters=4,
                devices_per_cluster=2, tau=2, q=2, pi=2, topology="ring",
                hierarchy=(2, 2, 2))


def test_depth3_trajectory_parity(mesh):
    """Depth-3 (device→edge→region) TierMix program: the registry-tier
    lowering (per-tier psums + block-diagonal gossip matchings) matches
    the dense single-device engine."""
    ref, sb = _pair(_FL3, mesh)
    for _ in range(3):
        ref.step_round()
        sb.step_round()
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL
    assert _maxdiff(ref.bank.mom, sb.bank.mom) < ATOL


def test_depth3_scenario_trajectory_parity(mesh):
    """Masked/mobility depth-3 rounds take the dense-rotation path with
    per-tier masked operators; parity must hold."""
    sc = ScenarioConfig(name="t", speed_dist="lognormal", speed_spread=0.6,
                        sample_fraction=0.5, move_prob=0.3, seed=7)
    ref, sb = _pair(_FL3, mesh, scenario=sc)
    for _ in range(3):
        p1 = ref.step_round()
        p2 = sb.step_round()
        assert np.array_equal(p1.mask, p2.mask)
    assert _maxdiff(ref.bank.params, sb.bank.params) < ATOL


def test_depth3_round_has_no_allgather(mesh):
    """A depth-3 TierMix round must still lower to grouped psums +
    collective-permutes only — the region tier adds a wider psum and its
    own matchings, never an all-gather of the bank."""
    _, sb = _pair(_FL3, mesh)
    assert sb._canonical.ops[-1].level == 2
    b = sb.bank
    args = sb._resolve_args(sb._canonical, None, fuse=True)
    hlo = sb._round_flat.lower(
        b.params, b.mom, None, sb.key, args,
        sb._full_mask).compile().as_text()
    assert "collective-permute" in hlo
    assert "all-gather" not in hlo
    assert "all-to-all" not in hlo


def test_sharded_init_parity_and_no_full_bank(mesh, monkeypatch):
    """Per-shard init (``ModelBank.from_model_sharded``) is bit-identical
    to the old build-then-place path, and the sharded engine never calls
    the full-bank constructor — init never materializes (n, T) on one
    device (each addressable shard is the device's own (1, T) row)."""
    from repro.core.modelbank import ModelBank
    from repro.models.cnn import init_mlp_classifier
    fl = _FL
    one = init_mlp_classifier(jax.random.PRNGKey(0), 16, 32, 4)
    old = ModelBank.from_model(one, fl.n)
    old.place(jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None)))

    def _forbidden(*a, **kw):
        raise AssertionError(
            "sharded init must not build the full bank on one device")
    monkeypatch.setattr(ModelBank, "from_model", _forbidden)
    init = lambda k: init_mlp_classifier(k, 16, 32, 4)   # noqa: E731
    sb = ShardedBankCEFedAvg(init, apply_mlp_classifier, fl, _data(fl),
                             mesh, lr=0.1, batch_size=16, seed=0)
    assert np.array_equal(np.asarray(old.params), np.asarray(sb.bank.params))
    assert np.array_equal(np.asarray(old.mom), np.asarray(sb.bank.mom))
    T = sb.bank.layout.total
    for buf in (sb.bank.params, sb.bank.mom):
        assert all(s.data.shape == (1, T) for s in buf.addressable_shards)
        assert buf.sharding == sb._row_sharding
