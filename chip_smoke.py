#!/usr/bin/env python3
"""Bring-up smoke of CE-FedAvg on a TPU: the main path, once, at full width.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # the sharded bank on four chips

One chip runs, in order:

1. kernels at the widths the system runs them: ``gossip_mix_rows`` over
   the paper cell's (64, 6,603,710) bank, square and the (8, 64) edge
   projection, against the ``kernels/ref.py`` oracle; the cold codec
   (int8, f16) on a wide-MLP slab against the host codec of
   ``core/compress.py``. Every Pallas kernel must show up as a
   ``tpu_custom_call`` in its compiled program.
2. the paper cell (``configs/femnist_cnn.FL``: n = 64, m = 8 ring,
   tau=2, q=8, pi=10, FEMNIST CNN, batch 50) for 3 rounds through
   ``benchmarks.common.make_sim``; if the chip refuses n = 64 for
   memory, n = 32 (m = 8 x 4), with the refusal printed.
3. the reference: one n = 16 round of the bank engine against the
   per-leaf pytree engine (``FLSimulator(bank=False)``), same seed.
4. the population cell: 10^4 virtual clients streamed through the
   pipelined driver (int8 cold codec), against the serial driver; at
   f32 the two must agree bit for bit.

``--four-chips`` runs only ``ShardedBankCEFedAvg`` with four FEMNIST-CNN
rows on a (4, 1) mesh against ``FLSimulator`` on one chip.

Inputs are made from ``--seed``; nothing is read from disk. Without a
TPU the script exits non-zero before any phase. The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
sys.path.insert(0, HERE)

from repro.compile_cache import use_compile_cache  # noqa: E402

#: largest relative norm difference of two engines' banks after the same
#: rounds at ``highest`` matmul precision (see ``phase_reference``)
REF_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok, what: str) -> None:
    """Fail the phase unless ``ok`` (kept under ``python -O``)."""
    if not ok:
        raise SmokeFailure(what)


def has_pallas(compiled) -> bool:
    """Whether a compiled program holds a Pallas (Mosaic) kernel."""
    return "tpu_custom_call" in compiled.as_text()


def require_pallas(jitted, *args, what: str):
    """Compile ``jitted`` for ``args`` and fail unless a Pallas kernel is
    in the program; returns the compiled program."""
    compiled = jitted.lower(*args).compile()
    check(has_pallas(compiled), f"{what}: no Pallas kernel compiled")
    return compiled


def cache_events():
    """A counter of JAX's persistent-cache ``hits``/``misses`` from now on
    (JAX's own monitoring events)."""
    import collections

    import jax
    counts = collections.Counter()

    def listen(event: str, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            counts[event.rsplit("_", 1)[1]] += 1
    jax.monitoring.register_event_listener(listen)
    return counts


def device_check():
    import jax
    import jaxlib
    devs = jax.devices()
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__}")
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU found (JAX sees {devs[0].platform}); "
                 "this smoke runs on the chip only")
    return devs


def memory_stats(dev) -> dict:
    return dev.memory_stats() or {}


# -- phase 1: kernels ---------------------------------------------------------

def phase_kernels(seed: int, n: int = 64, T: int = 6_603_710,
                  slab: int = 16):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import topology as topo
    from repro.core.compress import decode_cold_rows, encode_cold_rows
    from repro.kernels import cold_codec
    from repro.kernels import ref as kref
    from repro.kernels.gossip_mix import FlatLayout, gossip_mix_rows
    from repro.models.cnn import init_mlp_classifier

    rng = np.random.default_rng(seed)
    A = rng.random((n, n))
    W = A / A.sum(1, keepdims=True)                      # row-stochastic
    labels = np.repeat(np.arange(8), n // 8)
    P = topo.masked_cluster_average(topo.assignment_matrix(labels, 8))
    Y = jax.random.normal(jax.random.PRNGKey(seed), (n, T), jnp.float32)
    mix = jax.jit(gossip_mix_rows)
    with jax.default_matmul_precision("highest"):
        oracle = jax.jit(kref.gossip_mix_rows_ref)
    ymax = float(jnp.max(jnp.abs(Y)))
    for name, Wm in (("square", W), ("edge_projection", P)):
        Wj = jnp.asarray(Wm, jnp.float32)
        require_pallas(mix, Wj, Y, what=f"gossip_mix_rows {name}")
        t0 = time.perf_counter()
        out = jax.block_until_ready(mix(Wj, Y))
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = oracle(Wj, Y)
        err = float(jnp.max(jnp.abs(out - ref)))
        # W is row-stochastic, so one bf16 pass over its inputs (2^-9
        # each) errs by at most 2^-8 max|Y|: anything above is a bug
        bound = 2.0 ** -8 * ymax
        log(f"[kernels] gossip_mix_rows {name} W{Wj.shape} x Y{Y.shape}: "
            f"tpu_custom_call=yes first_call_s={dt:.3f} "
            f"max_abs_err={err:.3e} (bound {bound:.3e})")
        check(out.shape == (Wj.shape[0], T) and err <= bound,
              f"gossip_mix_rows {name}: error {err:.3e} > {bound:.3e}")
        del out, ref
    del Y
    gc.collect()

    one = init_mlp_classifier(jax.random.PRNGKey(seed), 64, 2048, 32)
    layout = FlatLayout.for_tree(one)
    segs = layout.segments
    kx, ks = jax.random.split(jax.random.PRNGKey(seed + 1))
    rows = (jax.random.normal(kx, (slab, layout.total), jnp.float32)
            * jnp.exp(jax.random.normal(ks, (slab, 1))))
    x = np.asarray(rows)
    for codec in ("int8", "f16"):
        enc = jax.jit(functools.partial(cold_codec.encode_rows,
                                        codec=codec, segments=segs))
        dec = jax.jit(functools.partial(cold_codec.decode_rows,
                                        codec=codec, segments=segs))
        host = encode_cold_rows(x, codec, segs)
        hq, hs = jnp.asarray(host["q"]), jnp.asarray(host["scale"])
        if codec == "int8":
            require_pallas(enc, rows, what="cold_codec int8 encode")
            require_pallas(dec, hq, hs, what="cold_codec int8 decode")
        q, s = (np.asarray(a) for a in enc(rows))
        d = np.asarray(dec(hq, hs))
        hd = decode_cold_rows(host, codec, segs)
        if codec == "int8":
            q_diff = np.abs(q.astype(np.int16) - host["q"].astype(np.int16))
            s_rel = float(np.max(np.abs(s - host["scale"]) / host["scale"]))
            log(f"[kernels] cold_codec int8 T={layout.total} rows={slab}: "
                f"tpu_custom_call=yes q_mismatch={int((q_diff > 0).sum())}"
                f"/{q.size} max_q_diff={int(q_diff.max())} "
                f"scale_mismatch={int((s != host['scale']).sum())} "
                f"scale_max_rel={s_rel:.2e} "
                f"decode_mismatch={int((d != hd).sum())}")
            check(q_diff.max() <= 1 and s_rel <= 2.0 ** -22,
                  "cold_codec int8 encode: off the host codec")
            check(np.allclose(d, hd, rtol=2.0 ** -23, atol=0),
                  "cold_codec int8 decode: off the host codec")
        else:
            bad = q.view(np.uint16) != host["q"].view(np.uint16)
            # f16 holds |x| < 2^-14 only as a subnormal; above that the
            # device cast must be the IEEE cast bit for bit
            normal_bad = int((bad & (np.abs(x) >= 2.0 ** -14)).sum())
            log(f"[kernels] cold_codec f16 T={layout.total} rows={slab}: "
                f"xla_cast q_mismatch={int(bad.sum())}/{q.size} "
                f"(in the f16-normal range: {normal_bad}) "
                f"decode_mismatch={int((d != hd).sum())}")
            normal = np.abs(hd) >= 2.0 ** -14
            check(normal_bad == 0 and q.dtype == np.float16,
                  "cold_codec f16 encode: not the IEEE cast")
            check(np.array_equal(d[normal], hd[normal]),
                  "cold_codec f16 decode: not the IEEE cast")


# -- phase 2: the paper cell ----------------------------------------------------

def _paper_fl(n: int):
    from repro.configs import femnist_cnn
    return dataclasses.replace(femnist_cnn.FL, devices_per_cluster=n // 8)


def _round_program(sim):
    """The jitted flat round this simulator dispatches, and its operands
    (lowering them does not consume the donated bank)."""
    import jax
    prog = sim._canonical
    fn = sim._get_round("flat", prog)
    args = sim._resolve_args(prog, None, fuse=True)
    b = sim.bank
    return fn, (b.params, b.mom, b.residual, jax.random.PRNGKey(0), args,
                sim._full_mask)


def run_paper_cell(seed: int, n: int, rounds: int = 3):
    import jax
    import jax.numpy as jnp

    from benchmarks.common import make_data, make_sim

    fl = _paper_fl(n)
    sim = make_sim(fl, make_data(fl, full=True, seed=seed), full=True,
                   batch_size=50, seed=seed)
    T = sim._layout.total
    acc0, loss0 = sim.evaluate()
    log(f"[paper] n={fl.n} m={fl.num_clusters} T={T} tau={fl.tau} "
        f"q={fl.q} pi={fl.pi} batch=50 init loss={loss0:.4f} "
        f"acc={acc0:.4f}")
    fn, args = _round_program(sim)
    events = cache_events()
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t1
    check(has_pallas(compiled), "paper round: no Pallas mixing kernel")
    ma = compiled.memory_analysis()
    log(f"[paper] round lower_s={t1 - t0:.2f} compile_s={compile_s:.2f} "
        f"persistent_cache hits={events['hits']} "
        f"misses={events['misses']} memory_analysis "
        f"argument={ma.argument_size_in_bytes} "
        f"output={ma.output_size_in_bytes} temp={ma.temp_size_in_bytes} "
        f"alias={ma.alias_size_in_bytes}")
    del args
    times, losses = [], []
    for r in range(rounds):
        t0 = time.perf_counter()
        sim.step_round()
        jax.block_until_ready(sim.bank.params)
        times.append(time.perf_counter() - t0)
        acc, loss = sim.evaluate()
        losses.append(loss)
        log(f"[paper] round {r + 1} s={times[-1]:.3f} loss={loss:.4f} "
            f"acc={acc:.4f}")
    finite = bool(jnp.isfinite(sim.bank.params).all()
                  and jnp.isfinite(sim.bank.mom).all())
    peak = memory_stats(jax.devices()[0]).get("peak_bytes_in_use")
    steady = times[1:]
    log(f"[paper] n={fl.n} compile_s={compile_s:.2f} "
        f"first_round_s={times[0]:.3f} steady_round_s="
        f"{','.join(f'{t:.3f}' for t in steady)} "
        f"peak_bytes_in_use={peak} finite={finite}")
    check(finite, "paper cell: non-finite params")
    check(losses[-1] < loss0,
          f"paper cell: loss did not fall ({loss0:.4f} -> {losses[-1]:.4f})")


def phase_paper_cell(seed: int, n: int = 64):
    import jax
    try:
        run_paper_cell(seed, n)
        return
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        refusal = str(e).splitlines()[0][:400]
    gc.collect()
    log(f"[paper] REFUSED n={n} for memory: {refusal}")
    run_paper_cell(seed, n // 2)


# -- phase 3: the reference on the chip ----------------------------------------

def _rel(a, b) -> float:
    import numpy as np
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare_engines(make_a, make_b, rounds: int, precision=None):
    """Run two simulators built by ``make_a``/``make_b`` (same config and
    seed) for ``rounds`` under ``precision`` (None: the default, which
    rounds TPU f32 matmul and convolution inputs to bf16). Returns the
    relative norm of their bank difference, of their update difference,
    and each one's (acc, loss)."""
    import contextlib

    import jax
    import numpy as np
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        a, b = make_a(), make_b()
        Y0 = np.asarray(a.bank.params)
        for _ in range(rounds):
            a.step_round()
            b.step_round()
        Ya = np.asarray(a.bank.params)
        Yb = np.asarray(b.bank.params if b.bank is not None
                        else a.bank.layout.flatten_stack(b.params))
        evals = a.evaluate(), b.evaluate()
    check(np.isfinite(Ya).all() and np.isfinite(Yb).all(),
          "non-finite bank")
    return _rel(Ya, Yb), _rel(Ya - Y0, Yb - Y0), evals


def phase_reference(seed: int, n: int = 16):
    """One round, bank engine (Pallas mixing, fused boundary) vs the
    per-leaf pytree engine (XLA ``mix``), same seed and batches.

    At default precision TPU f32 matmuls and convolutions round their
    inputs to bf16 (2^-9), the pytree engine at each of its 9 mixing
    passes and both in every gradient; 16 SGD steps at lr 0.1 and
    momentum 0.9 amplify those differences, so the default-precision
    numbers are printed, not held to a bound. At ``highest`` precision
    both engines compute in f32 and differ only in summation order
    (the bank fuses W_inter W_intra into one pass): ``REF_TOL`` = 1e-4
    holds them to that, while a wrong operator or schedule moves the
    bank by the size of a round's update (~2e-2 of its norm here)."""
    from benchmarks.common import make_data, make_sim

    fl = dataclasses.replace(_paper_fl(64), num_clusters=4,
                             devices_per_cluster=n // 4)
    data = make_data(fl, full=True, seed=seed)
    make = functools.partial(make_sim, fl, data, full=True, batch_size=50,
                             seed=seed)
    for precision in (None, "highest"):
        rel, upd, ((ab, lb), (at, lt)) = compare_engines(
            make, functools.partial(make, bank=False), 1, precision)
        log(f"[reference] n={fl.n} m={fl.num_clusters} one round "
            f"precision={precision or 'default'}: bank_vs_pytree "
            f"rel_norm={rel:.3e} update_rel_norm={upd:.3e} loss "
            f"bank={lb:.5f} pytree={lt:.5f} acc bank={ab:.4f} "
            f"pytree={at:.4f}")
    check(rel <= REF_TOL,
          f"reference: bank vs pytree {rel:.3e} > {REF_TOL} at highest")


# -- phase 4: the population cell ------------------------------------------------

def phase_population(seed: int, rounds: int = 6, population: int = 10_000):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import FLConfig, PopulationConfig
    from repro.core.cefedavg import FLSimulator
    from repro.core.scenario import get_scenario
    from repro.data.federated import (build_fl_data, dirichlet_partition,
                                      make_synthetic_classification)
    from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier

    m, n = 4, 16
    fl = FLConfig(algorithm="ce_fedavg", num_clusters=m,
                  devices_per_cluster=n // m, tau=1, q=1, pi=1,
                  topology="ring")
    x, y = make_synthetic_classification(1600, 64, 32, seed=seed)
    tx, ty = make_synthetic_classification(128, 64, 32, seed=seed + 1)
    data = build_fl_data(x, y, dirichlet_partition(y, n, 0.5, seed), tx, ty,
                         samples_per_device=96)
    base = dataclasses.replace(get_scenario("sampled"), sample_fraction=1.0,
                               dropout_prob=0.0, seed=seed)
    init = lambda k: init_mlp_classifier(k, 64, 2048, 32)  # noqa: E731
    for codec in ("f32", "int8"):
        sc = dataclasses.replace(base, population=PopulationConfig(
            clients_per_cluster=population // m, cohort_per_cluster=3,
            codec=codec))
        sims = {pipe: FLSimulator(init, apply_mlp_classifier, fl, data,
                                  lr=0.1, batch_size=16, seed=seed,
                                  scenario=sc, codec=codec, pipeline=pipe)
                for pipe in (False, True)}
        times = {False: [], True: []}
        for _ in range(rounds):
            for pipe, sim in sims.items():
                t0 = time.perf_counter()
                sim.step_round()
                times[pipe].append(time.perf_counter() - t0)
        ser, pip = sims[False], sims[True]
        acc, loss = pip.evaluate()             # drains the pipeline
        refs = [np.asarray(s.store.cluster_params) for s in (ser, pip)]
        if codec == "int8":
            pre, post = pip._pipe_fns
            S, T = pip.last_bucket, pip._layout.total
            f32 = jnp.float32
            shp = jax.ShapeDtypeStruct
            require_pallas(post, shp((S, T), f32), shp((S, T), f32),
                           shp((m, T), f32), shp((m,), jnp.bool_),
                           shp((m,), jnp.int32),
                           what="pipelined page-out encode")
            q, s = pip._pipe["prev"]["q"], pip._pipe["prev"]["s"]
            require_pallas(pre, shp((m, T), f32), shp((S,), jnp.int32),
                           q, s, q, s, shp((S,), jnp.int32),
                           shp((S,), jnp.int32),
                           what="pipelined page-in decode")
        diff = float(np.max(np.abs(refs[0] - refs[1])))
        steady = np.median(times[True][2:]), np.median(times[False][2:])
        log(f"[population] codec={codec} clients={pip.engine.population} "
            f"T={pip._layout.total} slab_rows={pip.last_bucket} "
            f"rounds={rounds} pipelined_vs_serial max_abs_diff={diff:.3e} "
            f"median_round_s pipelined={steady[0]:.4f} "
            f"serial={steady[1]:.4f} loss={loss:.4f} acc={acc:.4f} "
            f"tpu_custom_call={'yes' if codec == 'int8' else 'n/a'}")
        check(np.isfinite(refs[1]).all(), "population: non-finite refs")
        if codec == "f32":
            check(np.array_equal(refs[0], refs[1]),
                  "population: pipelined f32 not bit-identical to serial")
        else:
            check(diff <= 5e-3, f"population: int8 drifted {diff:.3e}")


# -- four chips -------------------------------------------------------------------

def phase_four_chips(seed: int, rounds: int = 2):
    """The sharded bank (one FEMNIST-CNN row per chip, m = 2 clusters):
    timed and placed at default precision, then compared with the
    single-chip bank engine at ``highest`` precision (``REF_TOL``, as in
    :func:`phase_reference`)."""
    import jax

    from benchmarks.common import make_data, make_sim
    from repro.core.sharded import ShardedBankCEFedAvg
    from repro.launch.mesh import make_replica_mesh
    from repro.models.cnn import apply_femnist_cnn, init_femnist_cnn

    R = 4
    fl = dataclasses.replace(_paper_fl(64), num_clusters=2,
                             devices_per_cluster=2)
    data = make_data(fl, full=True, seed=seed)
    mesh = make_replica_mesh(R)
    sharded = functools.partial(
        ShardedBankCEFedAvg, init_femnist_cnn, apply_femnist_cnn, fl, data,
        mesh, lr=0.1, batch_size=50, seed=seed)
    sb = sharded()
    row = sb._layout.row_nbytes
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        sb.step_round()
        jax.block_until_ready(sb.bank.params)
        times.append(time.perf_counter() - t0)
    shards = {s.device: s.data.shape for s in sb.bank.params.addressable_shards}
    devs = list(mesh.devices.flat)
    stats = [memory_stats(d) for d in devs]
    in_use = [st.get("bytes_in_use", 0) for st in stats]
    log(f"[four_chips] n={fl.n} m={fl.num_clusters} T={sb._layout.total} "
        f"mesh={dict(mesh.shape)} round_s="
        f"{','.join(f'{t:.3f}' for t in times)} shard_shapes="
        f"{[shards[d] for d in devs]} bytes_in_use={in_use} "
        f"peak_bytes_in_use={[st.get('peak_bytes_in_use') for st in stats]}")
    check(all(shards[d] == (1, sb._layout.total) for d in devs),
          "four chips: bank rows are not one (1, T) shard per chip")
    # params + momentum of its own row on every chip; chip 0 holds no
    # other chip's rows (that would be 2 x row bytes per extra row)
    check(min(in_use) >= 2 * row, "four chips: a chip holds no row")
    check(in_use[0] - min(in_use[1:]) < 2 * row,
          "four chips: chip 0 holds other chips' rows")
    del sb
    gc.collect()
    rel, upd, ((_, ls), (acc, loss)) = compare_engines(
        sharded, functools.partial(make_sim, fl, data, full=True,
                                   batch_size=50, seed=seed),
        rounds, "highest")
    log(f"[four_chips] {rounds} rounds precision=highest: sharded_vs_one_chip "
        f"rel_norm={rel:.3e} (tol {REF_TOL:.0e}) update_rel_norm={upd:.3e} "
        f"loss sharded={ls:.5f} one_chip={loss:.5f} one_chip_acc={acc:.4f}")
    check(rel <= REF_TOL,
          f"four chips: sharded vs one chip {rel:.3e} > {REF_TOL}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded bank on a (4, 1) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = use_compile_cache()
    devs = device_check()
    log(f"compile cache: {cache}")
    if args.four_chips:
        check(len(devs) >= 4, f"--four-chips needs 4 chips, found "
                               f"{len(devs)}")
        phases = [phase_four_chips]
    else:
        phases = [phase_kernels, phase_paper_cell, phase_reference,
                  phase_population]
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        log(f"[{phase.__name__}] passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
