"""Per-topology gossip backend comparison: bytes/round and step time.

For every backhaul topology the paper evaluates (ring, torus, star,
complete, ER p∈{0.2,0.4,0.6}) this prints, per ``gossip_impl`` backend:

- neighbor-traffic bits moved per inter-cluster aggregation (per replica
  and network-total, from ``core.runtime.gossip_traffic_per_round`` — the
  formulas the GossipSchedule lowering realizes), plus the schedule shape
  (number of ppermute matchings / rotations), and
- with ``--measure``, measured wall time of the jitted inter-cluster mix
  on a mesh of every device JAX finds (at least 4; on a CPU host, start
  it with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Asserts the headline claim: for every non-complete topology the sparse
backends move strictly less traffic than the dense all-gather.

  PYTHONPATH=src python benchmarks/gossip_topologies.py [--measure]
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.config import FLConfig  # noqa: E402
from repro.core import topology as topo  # noqa: E402
from repro.core.gossip import GossipSchedule  # noqa: E402
from repro.core.runtime import gossip_traffic_per_round  # noqa: E402

M, DPC, PI = 8, 2, 3
MODEL_BITS = 6_603_710 * 32.0      # paper's FEMNIST CNN, fp32

CASES = [("ring", {}), ("torus", {"num_clusters": 9}), ("star", {}),
         ("erdos_renyi", {"er_prob": 0.2}),
         ("erdos_renyi", {"er_prob": 0.4}),
         ("erdos_renyi", {"er_prob": 0.6}),
         ("complete", {})]


def _case_name(name: str, kw) -> str:
    return (f"{name}_p{kw['er_prob']}" if name == "erdos_renyi" else name)


def measure_step_times(fl: FLConfig):
    """Wall time of the jitted inter-cluster mix per backend, one replica
    per device found: 2 devices per cluster from 8 devices up, else one
    (R = every device, at least 4 so the ring has 4 clusters)."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.cefedavg import make_w_schedule, mix
    from repro.core.gossip import apply_gossip
    from repro.launch.mesh import make_replica_mesh

    R = jax.device_count()
    if R < 4:
        raise SystemExit(f"--measure needs at least 4 devices, found {R}")
    dpc = 2 if R >= 8 and R % 2 == 0 else 1
    fl = dataclasses.replace(fl, num_clusters=R // dpc,
                             devices_per_cluster=dpc)
    mesh = make_replica_mesh(R)
    sched = make_w_schedule(fl)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(R, 1 << 18)).astype(np.float32))
    out = {}
    with mesh:
        for impl in ("dense", "sparse", "ringweight"):
            if impl == "dense":
                fn = jax.jit(lambda p: mix(sched.W_inter, p))
            else:
                gs = GossipSchedule.build(
                    sched.H, fl.pi, fl.devices_per_cluster,
                    mode="exact" if impl == "ringweight" else "rounds")
                fn = jax.jit(lambda p, gs=gs: apply_gossip(
                    gs, p, P("data"), mesh))
            jax.block_until_ready(fn(x))       # compile
            t0 = time.perf_counter()
            for _ in range(5):
                jax.block_until_ready(fn(x))
            out[impl] = (time.perf_counter() - t0) / 5
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--measure", action="store_true",
                    help="also time the jitted mix on the devices found")
    args = ap.parse_args()

    print(f"{'topology':16s} {'impl':10s} {'matchings':>9s} "
          f"{'per_replica_MB':>14s} {'total_MB':>9s} {'vs_dense':>8s}"
          + ("  step_ms" if args.measure else ""))
    for name, kw in CASES:
        m = kw.pop("num_clusters", M)
        fl = FLConfig(num_clusters=m, devices_per_cluster=DPC, pi=PI,
                      topology=name, **kw)
        fl.validate()
        adj = topo.build_adjacency(name, m, fl)
        H = topo.mixing_matrix(adj)
        deg = adj.sum(1)
        times = (measure_step_times(fl)
                 if args.measure and name != "torus" else {})
        dense_total = None
        for impl in ("dense", "sparse", "ringweight"):
            tr = gossip_traffic_per_round(
                impl, num_clusters=m, devices_per_cluster=DPC, pi=PI,
                degrees=deg, model_bits=MODEL_BITS)
            if impl == "dense":
                dense_total = tr["total_bits"]
                nmatch = m * DPC - 1
            elif impl == "ringweight":
                nmatch = m - 1
            else:
                sch = GossipSchedule.build(H, PI, DPC, "rounds")
                nmatch = sch.num_matchings
                # the formula IS what the schedule moves — keep them honest
                assert sch.models_received_total(m * DPC) * MODEL_BITS == \
                    tr["total_bits"], (name, impl)
            ratio = tr["total_bits"] / dense_total
            if impl != "dense" and name != "complete":
                assert tr["total_bits"] < dense_total, \
                    f"{impl} must beat dense all-gather on {name}"
            extra = (f"  {times[impl] * 1e3:7.2f}" if impl in times else "")
            print(f"{_case_name(name, kw):16s} {impl:10s} {nmatch:9d} "
                  f"{tr['per_replica_bits'] / 8e6:14.1f} "
                  f"{tr['total_bits'] / 8e6:9.1f} {ratio:8.2f}" + extra)
    if args.measure:
        import jax
        print(f"\nnote: step_ms ran on {jax.device_count()} "
              f"{jax.devices()[0].platform} device(s); on a CPU host the "
              "collectives are memcpys — the bytes columns are what govern "
              "wall time on real interconnects (see core/runtime.py).")
    print("\nOK: sparse and ringweight move less traffic than the dense "
          "all-gather on every non-complete backhaul.")


if __name__ == "__main__":
    main()
