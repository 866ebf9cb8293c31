"""End-to-end CE-FedAvg training driver (real execution, any device count).

Runs the sharded trainer on whatever devices exist (1 CPU device locally,
a real mesh on TPU), streaming synthetic federated token data, logging loss
per global round and checkpointing the gossip-averaged global model.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --rounds 5 --data-parallel 4 --model-parallel 1

``--engine bank`` instead runs the device-parallel flat-bank engine
(``core.sharded.ShardedBankCEFedAvg``): one (1, T) bank-row shard per
device on synthetic federated classification data — the same fused
single-pass mixing hot path the simulator benchmarks, on a real mesh:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --engine bank --data-parallel 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.compile_cache import use_compile_cache
from repro.config import ExperimentConfig, FLConfig, TrainConfig
from repro.configs import ARCHS, get_model_config
from repro.core.sharded import ShardedCEFedAvg
from repro.data.lm import TokenStream
from repro.launch.mesh import make_mesh


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale model (CPU-friendly)")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--clusters", type=int, default=0)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--q", type=int, default=2)
    ap.add_argument("--pi", type=int, default=4)
    from repro.core.topology import TOPOLOGIES
    ap.add_argument("--gossip", choices=FLConfig.GOSSIP_IMPLS,
                    default="dense")
    ap.add_argument("--topology", default="ring",
                    choices=sorted(TOPOLOGIES))
    ap.add_argument("--er-prob", type=float, default=0.4)
    ap.add_argument("--algorithm", default="ce_fedavg")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--engine", choices=("pytree", "bank"),
                    default="pytree",
                    help="pytree: LM trainer with stacked replica pytrees; "
                         "bank: device-parallel flat (n, T) ModelBank "
                         "shards (classification workload)")
    from repro.core.program import SCHEDULES
    ap.add_argument("--schedule", choices=SCHEDULES, default="static",
                    help="round schedule (RoundProgram IR, bank engine): "
                         "static reproduces the paper's fixed tau/q/pi; "
                         "adaptive_tau gives slow clusters fewer local "
                         "steps; pi_decay runs deep gossip early, sparse "
                         "late")
    from repro.core.scenario import FAULTS, SCENARIOS
    ap.add_argument("--scenario", choices=sorted(SCENARIOS), default="",
                    help="named wall-clock scenario (bank engine): device "
                         "heterogeneity / client sampling / mobility — "
                         "adaptive_tau needs a heterogeneous one to bite")
    ap.add_argument("--faults", choices=sorted(FAULTS), default="",
                    help="named fault preset (bank engine, "
                         "docs/FAULT_MODEL.md): edge outages / backhaul "
                         "link loss / straggler timeouts injected into "
                         "the scenario; engines degrade gracefully "
                         "instead of crashing")
    ap.add_argument("--ckpt-dir", default="",
                    help="crash-consistent run checkpoint directory "
                         "(bank engine): full run state written "
                         "atomically every --ckpt-every rounds")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="rounds between run checkpoints (with "
                         "--ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--ckpt-dir (bit-identical to the "
                         "uninterrupted run)")
    ap.add_argument("--async-staleness", type=int, default=-1,
                    metavar="S",
                    help="bounded-staleness async rounds (bank engine): "
                         "each cluster advances to its next block as "
                         "soon as its own boundary clears, gossiping "
                         "only with neighbors within S blocks; 0 is the "
                         "global barrier (identical trajectory), -1 "
                         "(default) disables async execution")
    ap.add_argument("--hierarchy", default="",
                    help="depth>2 tier preset (bank engine): comma-"
                         "separated branching factors root->leaf, e.g. "
                         "'2,2,2' = 2 regions x 2 edges x 2 devices; "
                         "overrides --clusters/--data-parallel geometry")
    ap.add_argument("--population", type=int, default=0, metavar="N",
                    help="stream a virtual population of N clients "
                         "through the cold client store "
                         "(core/clientstore.py) instead of enumerating "
                         "devices: per-round resident memory is bounded "
                         "by the cohort, not N — n~1e5 runs on a laptop "
                         "(docs/PERFORMANCE.md, population scaling)")
    ap.add_argument("--cohort", type=int, default=8, metavar="K",
                    help="sampled clients per cluster per round with "
                         "--population (before sample_fraction/dropout)")
    ap.add_argument("--codec", choices=("f32", "f16", "int8"),
                    default="f32",
                    help="cold-row codec of the streamed client store "
                         "(--population): f32 lossless, f16/int8 trade "
                         "round-trip error for 2x/4x smaller cold rows")
    ap.add_argument("--pipeline", action="store_true",
                    help="overlap paging with compute (--population): "
                         "round t's page-out drains and round t+1's "
                         "cohort prefetches while round t runs; encoded "
                         "rows cross the host-device link and the cold "
                         "codec runs on device (kernels/cold_codec.py). "
                         "Bit-identical to the serial driver at f32 "
                         "(docs/PERFORMANCE.md, paging pipeline)")
    ap.add_argument("--multihost", action="store_true",
                    help="call jax.distributed.initialize before any "
                         "device use (real-cluster entry point; "
                         "auto-detects on Cloud TPU, or pass the "
                         "--coordinator/--num-processes/--process-id "
                         "trio / JAX_* env vars)")
    ap.add_argument("--coordinator", default="",
                    help="coordinator address host:port for --multihost")
    ap.add_argument("--num-processes", type=int, default=0)
    ap.add_argument("--process-id", type=int, default=-1)
    args = ap.parse_args(argv)
    if args.population:
        if (args.schedule != "static" or args.hierarchy or args.faults
                or args.async_staleness >= 0):
            ap.error("--population supports --scenario/--ckpt-dir/"
                     "--resume/--pipeline only (no schedules, "
                     "hierarchies, faults or async rounds over a "
                     "virtual population)")
    elif args.pipeline:
        ap.error("--pipeline overlaps the streamed engine's paging; "
                 "it requires --population")
    elif args.engine != "bank" and (args.schedule != "static"
                                    or args.scenario or args.hierarchy
                                    or args.async_staleness >= 0
                                    or args.faults or args.ckpt_dir
                                    or args.resume):
        ap.error("--schedule/--scenario/--hierarchy/--async-staleness/"
                 "--faults/--ckpt-dir/--resume require --engine bank")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")

    if args.multihost:
        from repro.launch.mesh import initialize_multihost
        initialize_multihost(
            coordinator_address=args.coordinator or None,
            num_processes=args.num_processes or None,
            process_id=args.process_id if args.process_id >= 0 else None)

    if args.population:
        return run_population_engine(args)
    if args.engine == "bank":
        return run_bank_engine(args)

    ndev = len(jax.devices())
    dp, mp = args.data_parallel, args.model_parallel
    assert dp * mp <= ndev, f"need {dp*mp} devices, have {ndev}"
    mesh = make_mesh((dp, mp), ("data", "model"))

    cfg = get_model_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    m = args.clusters or max(1, dp // 2)
    exp = ExperimentConfig(
        model=cfg,
        fl=FLConfig(algorithm=args.algorithm, num_clusters=m,
                    devices_per_cluster=max(dp // m, 1), tau=args.tau,
                    q=args.q, pi=args.pi, topology=args.topology,
                    er_prob=args.er_prob, gossip_impl=args.gossip),
        train=TrainConfig(optimizer="sgd", learning_rate=args.lr,
                          momentum=0.9),
    )
    tr = ShardedCEFedAvg(exp, mesh)
    R = tr.geo.num_replicas
    stream = TokenStream(cfg.vocab_size, R, tr.geo.cluster_of)

    with mesh:
        params, opt = jax.jit(tr.init_fn())(jax.random.PRNGKey(0))
        round_fn = jax.jit(tr.make_global_round(), donate_argnums=(0, 1))
        step = jnp.zeros((), jnp.int32)
        for r in range(args.rounds):
            t0 = time.time()
            # draw q·tau genuinely distinct microbatches from the stream:
            # (R, q*tau, B, S) -> (q, tau, R, B, S), one per local step
            qt = exp.fl.q * exp.fl.tau
            nb = stream.next_batch((qt, args.batch, args.seq))
            batch = {k: jnp.asarray(np.moveaxis(v, 0, 1).reshape(
                exp.fl.q, exp.fl.tau, R, args.batch, args.seq))
                for k, v in nb.items()}
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (exp.fl.q, exp.fl.tau, R, args.batch, cfg.encoder_seq,
                     cfg.d_model), jnp.dtype(cfg.dtype))
            if cfg.family == "vlm":
                batch["patch_embeds"] = jnp.zeros(
                    (exp.fl.q, exp.fl.tau, R, args.batch, cfg.num_patches,
                     cfg.d_model), jnp.dtype(cfg.dtype))
            params, opt, metrics, step = round_fn(params, opt, batch, step)
            print(f"round {r}: loss={float(metrics['loss']):.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)

        if args.ckpt:
            # checkpoint the gossip-consensus global model (replica average)
            gl = jax.tree.map(lambda l: jnp.mean(l.astype(jnp.float32), 0),
                              params)
            save_checkpoint(args.ckpt, jax.device_get(gl),
                            {"arch": args.arch, "rounds": args.rounds})
            print(f"saved global model to {args.ckpt}")


def run_population_engine(args):
    """Drive the streamed client-store engine over a virtual population
    of ``--population`` clients (ISSUE 9): only each round's cohort (+
    one representative lane per cluster) is resident; cold state pages
    through the compressed host store. With ``--data-parallel R > 1``
    the hot slab is row-sharded over a replica mesh
    (``core.sharded.ShardedStreamedBank``)."""
    import dataclasses

    from repro.checkpoint import RunCheckpoint
    from repro.config import PopulationConfig
    from repro.core.cefedavg import FLSimulator
    from repro.core.clientstore import resident_slab_nbytes
    from repro.core.scenario import get_scenario
    from repro.data.federated import (build_fl_data, dirichlet_partition,
                                      make_synthetic_classification)
    from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier

    m = args.clusters or 4
    # enumerated *data shards* (client_id mod n picks one) — a small
    # constant; the population itself is never enumerated
    n = m * 4
    fl = FLConfig(algorithm=args.algorithm, num_clusters=m,
                  devices_per_cluster=n // m, tau=args.tau, q=args.q,
                  pi=args.pi, topology=args.topology,
                  er_prob=args.er_prob)
    x, y = make_synthetic_classification(1600, 16, 8, seed=0, noise=2.5)
    tx, ty = make_synthetic_classification(400, 16, 8, seed=1, noise=2.5)
    parts = dirichlet_partition(y, n, alpha=0.3, seed=0)
    data = build_fl_data(x, y, parts, tx, ty, samples_per_device=64)
    base = get_scenario(args.scenario) if args.scenario else \
        get_scenario("sampled")
    scenario = dataclasses.replace(
        base, population=PopulationConfig(
            clients_per_cluster=max(1, -(-args.population // m)),
            cohort_per_cluster=args.cohort, codec=args.codec))
    init = lambda k: init_mlp_classifier(k, 16, 32, 8)   # noqa: E731
    if args.data_parallel > 1:
        from repro.core.sharded import ShardedStreamedBank
        from repro.launch.mesh import make_replica_mesh
        assert args.model_parallel == 1, \
            "slab rows are not tensor-parallel; use --model-parallel 1"
        mesh = make_replica_mesh(args.data_parallel)
        sim = ShardedStreamedBank(
            init, apply_mlp_classifier, fl, data, mesh, lr=args.lr,
            batch_size=args.batch, seed=0, scenario=scenario,
            pipeline=args.pipeline)
    else:
        sim = FLSimulator(
            init, apply_mlp_classifier, fl, data, lr=args.lr,
            batch_size=args.batch, seed=0, scenario=scenario,
            pipeline=args.pipeline)
    eng = sim.engine
    print(f"population engine: N={eng.population} virtual clients over "
          f"m={m} clusters (codec={args.codec}), slab cap "
          f"{max(sim._buckets)} rows x T={sim._layout.total} = "
          f"{resident_slab_nbytes(max(sim._buckets), sim._layout.total)}"
          f" B resident", flush=True)
    rc = RunCheckpoint(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if args.resume and rc is not None and rc.exists():
        meta = rc.restore(sim)
        start = meta["round"]
        print(f"resumed from {rc.path} at round {start}")
    for r in range(start, args.rounds):
        t0 = time.time()
        plan = sim.step_round()
        acc, loss = sim.evaluate(256)
        print(f"round {r}: acc={acc:.3f} loss={loss:.4f} "
              f"cohort={plan.clients.shape[0]} "
              f"slab={sim.last_bucket} rows "
              f"store={sim.store.nbytes / 1e6:.2f}MB "
              f"({time.time()-t0:.1f}s)", flush=True)
        if rc is not None and (r + 1) % max(args.ckpt_every, 1) == 0:
            rc.save(sim, round_idx=r + 1)
    print(f"peak resident slab: {sim.peak_slab_bytes} B "
          f"(population {eng.population}, cold store "
          f"{sim.store.nbytes / 1e6:.2f}MB host)")
    if args.ckpt:
        save_checkpoint(args.ckpt, jax.device_get(sim.global_model()),
                        {"engine": "streamed", "rounds": args.rounds})
        print(f"saved global model to {args.ckpt}")


def run_bank_engine(args):
    """Drive ``ShardedBankCEFedAvg`` — one bank row per device — on
    synthetic federated classification data, logging loss/accuracy of the
    edge models per global round (the paper's evaluation protocol)."""
    import dataclasses

    from repro.checkpoint import RunCheckpoint
    from repro.core.runtime import compute_bound_runtime_model
    from repro.core.scenario import get_faults, get_scenario
    from repro.core.sharded import ShardedBankCEFedAvg
    from repro.data.federated import (build_fl_data, dirichlet_partition,
                                      make_synthetic_classification)
    from repro.launch.mesh import make_replica_mesh
    from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier

    n = args.data_parallel
    assert args.model_parallel == 1, \
        "bank rows are not tensor-parallel; use --model-parallel 1"
    if args.gossip != "dense":
        print(f"note: --gossip {args.gossip} only selects a backend for "
              "the pytree engine; the bank engine always lowers its "
              "boundaries to psum + ppermute matchings (static schedule) "
              "or weighted rotations (scenario rounds)")
    if args.hierarchy:
        # depth>2 preset: geometry comes from the branching factors
        tiers = tuple(int(s) for s in args.hierarchy.split(","))
        n = int(np.prod(tiers))
        m = int(np.prod(tiers[:-1]))
        fl = FLConfig(algorithm=args.algorithm, num_clusters=m,
                      devices_per_cluster=tiers[-1], tau=args.tau,
                      q=args.q, pi=args.pi, topology=args.topology,
                      er_prob=args.er_prob, hierarchy=tiers)
    else:
        m = args.clusters or max(1, n // 2)
        assert n % m == 0, f"{n} devices not divisible into {m} clusters"
        fl = FLConfig(algorithm=args.algorithm, num_clusters=m,
                      devices_per_cluster=n // m, tau=args.tau, q=args.q,
                      pi=args.pi, topology=args.topology,
                      er_prob=args.er_prob)
    mesh = make_replica_mesh(n)
    x, y = make_synthetic_classification(1600, 16, 8, seed=0, noise=2.5)
    tx, ty = make_synthetic_classification(400, 16, 8, seed=1, noise=2.5)
    parts = dirichlet_partition(y, n, alpha=0.3, seed=0)
    data = build_fl_data(x, y, parts, tx, ty, samples_per_device=64)
    scenario = get_scenario(args.scenario) if args.scenario else None
    if args.faults:
        # fault injection rides on the scenario engine; without a named
        # scenario, attach the faults to the homogeneous baseline
        scenario = dataclasses.replace(
            scenario or get_scenario("homogeneous"),
            faults=get_faults(args.faults))
    schedule = None if args.schedule == "static" else args.schedule
    sim = ShardedBankCEFedAvg(
        lambda k: init_mlp_classifier(k, 16, 32, 8), apply_mlp_classifier,
        fl, data, mesh, lr=args.lr, batch_size=args.batch, seed=0,
        scenario=scenario, schedule=schedule)
    use_async = args.async_staleness >= 0
    print(f"bank engine: n={n} rows x T={sim.bank.layout.total} "
          f"({sim.bank.layout.row_nbytes} B/row), m={m} clusters, "
          f"mesh={dict(mesh.shape)}, schedule={args.schedule}"
          + (f", scenario={args.scenario}" if args.scenario else "")
          + (f", faults={args.faults}" if args.faults else "")
          + (f", async_staleness={args.async_staleness}" if use_async
             else ""))
    rt = compute_bound_runtime_model() if use_async else None
    rc = RunCheckpoint(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if args.resume and rc is not None and rc.exists():
        meta = rc.restore(
            sim, staleness=args.async_staleness if use_async else None)
        start = meta["round"]
        print(f"resumed from {rc.path} at round {start}")
    for r in range(start, args.rounds):
        t0 = time.time()
        if use_async:
            sim.step_round_async(args.async_staleness, rt)
            nev = len(sim.last_async["timeline"]["events"])
            extra = (f" events={nev} "
                     f"makespan={sim.last_async['timeline']['makespan']:.1f}s")
        else:
            sim.step_round()
            extra = ""
        acc, loss = sim.evaluate(256)
        print(f"round {r}: acc={acc:.3f} loss={loss:.4f} "
              f"({time.time()-t0:.1f}s){extra}", flush=True)
        if rc is not None and (r + 1) % max(args.ckpt_every, 1) == 0:
            rc.save(sim, round_idx=r + 1,
                    staleness=args.async_staleness if use_async else None)
    if args.ckpt:
        save_checkpoint(args.ckpt, jax.device_get(sim.global_model()),
                        {"engine": "bank", "rounds": args.rounds})
        print(f"saved global model to {args.ckpt}")


if __name__ == "__main__":
    main()
