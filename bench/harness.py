"""One benchmark run of one cell: build it from its files and the seed,
warm it up, time a window of global rounds, check what the window
computed against the plain reference, and print the result line.

Everything particular to a cell is data, found by name:

- ``BENCHMARK.json``: the cell's configuration, traffic and chips, and
  the metrics it reports;
- ``bench/workloads/<cell>.json``: the rounds the check follows, the
  limits of the numbers it compares;
- ``bench/configs/<config>.json``: the model, the federation, the
  optimiser, the engine the deployment runs;
- ``bench/traffic/<mix>.json``: the data and the participation mix;
- ``bench/models/<model>.py``: the model's binding to the program, its
  operation count and its plain reference;
- ``bench/metrics/<metric>.py``: one reducer per metric.

:func:`run_cell` does the work; the command line (``bench/run.py``)
adds only the look for the chip.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: what the runs leave behind (traces), inside the checkout
OUT = os.path.join(ROOT, ".bench_out")
#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: fewest rounds a window times
MIN_ROUNDS = 3
#: seconds of rounds a traced window holds (at least two rounds)
TRACE_SECONDS = 4.0
#: rounds of a traced run whose host time is read with the device idle
PROBE_ROUNDS = 5
#: the numbers :func:`compare` gives; a cell's workload file names each
#: either under ``limits`` or, with the reason, under ``not_compared``
NUMBERS = ("mom_gap", "mom_row_gap", "mom_rows_wrong", "dparam_gap")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_seed(seed: int) -> int:
    """The 31-bit seed handed to the program and the reference: a fixed
    mix of the run's seed, which may be wider than JAX's keys hold."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


@dataclasses.dataclass
class Cell:
    """A cell's files, resolved."""
    name: str
    entry: Dict          # the cell's entry in BENCHMARK.json
    workload: Dict       # bench/workloads/<name>.json
    config: Dict         # bench/configs/<config>.json
    traffic: Dict        # bench/traffic/<traffic>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _reported(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def resolve(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(name, entry, load_json(BENCH, "workloads", name + ".json"),
                load_json(ROOT, cfg["file"]),
                load_json(BENCH, "traffic", entry["traffic"] + ".json"),
                _reported(bench["end_to_end"], name),
                _reported(bench["per_layer"], name))


# -- JAX set-up ----------------------------------------------------------------

def use_cache() -> str:
    """Turn on JAX's persistent compilation cache for every program,
    however quick to compile: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it), else :data:`CACHE_DIR`."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def use_precision(cell: Cell) -> str:
    """Run the program's float32 products at the precision the cell's
    configuration states (``default``: one bf16 pass on a TPU;
    ``highest``: full float32). A process-wide JAX setting: the command
    sets it once, before the program traces anything."""
    import jax
    prec = cell.config["precision"]["matmul"]
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)
    return prec


class CompileEvents:
    """Counts of JAX's compile events, from JAX's own monitoring: traces
    of a jitted function, backend compiles, persistent-cache hits and
    misses."""

    NAMES = {"/jax/core/compile/jaxpr_trace_duration": "traces",
             "/jax/core/compile/backend_compile_duration": "compiles",
             "/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax
        self.counts = collections.Counter()
        self.compiles: List = []     # (seconds, function) per backend compile
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1

    def _duration(self, event: str, duration: float, **kw):
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1
        if self.NAMES.get(event) == "compiles":
            self.compiles.append((round(duration, 3), kw.get("fun_name")))

    def snapshot(self) -> Dict[str, int]:
        return {k: self.counts[k] for k in self.NAMES.values()}

    def longest(self, k: int = 3) -> List:
        """The ``k`` longest backend compiles so far: a persistent-cache
        miss is one of them."""
        return sorted(self.compiles, key=lambda c: c[0], reverse=True)[:k]


# -- the cell's engines ----------------------------------------------------------

class Engine:
    """A simulator of the program built for one cell, with what the
    harness needs around its ``step_round``."""

    def __init__(self, cell: Cell, data: Dict, seed: int, model_mod):
        import jax.numpy as jnp

        from repro.config import FLConfig, PopulationConfig, ScenarioConfig
        from repro.core.cefedavg import FLSimulator
        cfg, tr = cell.config, cell.traffic
        fed, opt = cfg["federation"], cfg["optimizer"]
        self.fl = FLConfig(
            algorithm="ce_fedavg", num_clusters=fed["num_clusters"],
            devices_per_cluster=fed["devices_per_cluster"], tau=fed["tau"],
            q=fed["q"], pi=fed["pi"], topology=fed["topology"],
            mixing=fed["mixing"])
        sc = tr.get("scenario")
        scenario = None
        pop = cfg.get("population")
        if sc is not None:
            scenario = ScenarioConfig(
                name=tr["name"], sample_fraction=sc["sample_fraction"],
                dropout_prob=sc["dropout_prob"], seed=seed,
                population=(None if pop is None else PopulationConfig(
                    clients_per_cluster=pop["clients_per_cluster"],
                    cohort_per_cluster=pop["cohort_per_cluster"],
                    codec=pop["codec"])))
        self.streamed = cfg["engine"] == "streamed"
        init, apply = model_mod.system(cfg["model_sizes"])
        self.sim = FLSimulator(
            init, apply, self.fl,
            {k: jnp.asarray(v) for k, v in data.items()},
            lr=opt["lr"], momentum=opt["momentum"], batch_size=opt["batch"],
            seed=seed, scenario=scenario, pipeline=self.streamed,
            codec=pop["codec"] if pop else "f32")
        self.layout = self.sim._layout
        self.n = self.fl.n
        self.last_rows = 0

    def paged_rows(self) -> int:
        """Rows the latest round paged in and out (its cohort)."""
        return self.last_rows

    # rows that train in a round, from the plan step_round returns
    def trained_rows(self, plan) -> int:
        if plan is None:
            return self.n
        return int(np.asarray(plan.mask).sum())

    def step(self):
        plan = self.sim.step_round()
        self.last_rows = self.trained_rows(plan)
        return plan

    def finish(self) -> None:
        """Wait until every dispatched round has finished on the device
        (and, streamed, landed in the host store)."""
        import jax
        if self.streamed:
            self.sim._drain_pipeline()
            jax.block_until_ready(self.sim._pipe["refs"])
        else:
            jax.block_until_ready(self.sim.bank.params)

    def page_seconds(self) -> float:
        return float(self.sim._page_seconds)

    def mix_rows(self) -> int:
        """Rows each mixing pass streams: the slab, or the whole bank."""
        return int(self.sim.last_bucket if self.streamed else self.n)

    # -- what the check reads ------------------------------------------------
    def initial_row(self):
        if self.streamed:
            return np.array(self.sim.store.cluster_params[0])
        return self.sim.bank.params[0]

    def params_rows(self):
        return (self.sim.store.cluster_params if self.streamed
                else self.sim.bank.params)

    def momentum_rows(self, plan):
        """Momentum of the rows that trained in round 1, as the
        optimiser holds it (streamed: decoded from the store)."""
        if self.streamed:
            return self.sim.store.fetch(np.asarray(plan.clients))
        return self.sim.bank.mom

    def fill_store(self) -> int:
        """Store a momentum row for every client of the population, as a
        long run holds one for each, so that the host arena is at its
        steady size from the first round. Each row is what the program's
        own codec makes of a zero row (a fresh client's momentum), so the
        rounds compute what they would with every client unseen."""
        from repro.core.compress import encode_cold_rows
        store = self.sim.store
        pop = int(self.sim.engine.population)
        zero = encode_cold_rows(np.zeros((1, self.layout.total), np.float32),
                                store.codec, self.layout.segments)
        step = 64
        q = np.broadcast_to(zero["q"], (step,) + zero["q"].shape[1:])
        sc = np.broadcast_to(zero["scale"], (step,) + zero["scale"].shape[1:])
        for lo in range(0, pop, step):
            ids = np.arange(lo, min(pop, lo + step))
            store.commit_encoded(ids, q[:ids.size], sc[:ids.size])
        return int(store.num_stored)


def seg_norms_fn(segments):
    """A jitted per-leaf norm of ``rows - row0`` (or of ``rows``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(rows, row0):
        out = []
        for off, size in segments:
            d = rows[:, off:off + size]
            if row0 is not None:
                d = d - row0[off:off + size]
            out.append(jnp.sqrt(jnp.sum(jnp.square(d))))
        return jnp.stack(out)
    return norms


# -- the check -------------------------------------------------------------------

def gap(prog: Dict[str, float], ref: Dict[str, float],
        leaves: List[str]) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in leaves)


def compared_leaves(ref_mom: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: a
    momentum norm after round 1 of at least a thousandth of the median
    leaf's."""
    med = float(np.median(list(ref_mom.values())))
    return [k for k, v in ref_mom.items() if v >= 1e-3 * med]


def row_gap(prog_rows, ref_rows) -> float:
    """Median trained row's gap between the program's norm and the
    reference's, over the reference's (rows the reference leaves at
    nought have not trained)."""
    p, r = np.asarray(prog_rows), np.asarray(ref_rows)
    t = r > 0
    return float(np.median(np.abs(p[t] - r[t]) / r[t]))


def rows_wrong(prog_rows, ref_rows) -> int:
    """Rows whose momentum after round 1 is nought on one side only: a
    row the program stepped that the reference left alone, or the
    reverse (an update scattered to the wrong row, or lost)."""
    return int(np.sum((np.asarray(prog_rows) > 0)
                      != (np.asarray(ref_rows) > 0)))


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers a check can compare (:data:`NUMBERS`): ``mom_gap``
    (momentum after round 1, the worst leaf), ``mom_row_gap`` (momentum
    after round 1, the median trained row), ``mom_rows_wrong`` (rows
    stepped in round 1 by one side only) and ``dparam_gap`` (change of
    the parameters after each checked round, the worst round's worst
    leaf). A workload's ``limits`` name the ones its runs compare."""
    leaves = compared_leaves(ref["mom1"])
    return {"mom_gap": gap(prog["mom1"], ref["mom1"], leaves),
            "mom_row_gap": row_gap(prog["mom1_rows"], ref["mom1_rows"]),
            "mom_rows_wrong": rows_wrong(prog["mom1_rows"],
                                         ref["mom1_rows"]),
            "dparam_gap": max(gap(p, r, leaves) for p, r in
                              zip(prog["dparam"], ref["dparam"]))}


def compared(workload: Dict) -> List[str]:
    """The numbers a cell's runs compare: its ``limits``. Every other
    number of :data:`NUMBERS` has to be under ``not_compared`` with the
    reason, so that none drops out of a check unseen."""
    limits, skip = set(workload["limits"]), set(workload.get(
        "not_compared", {}))
    if limits & skip or limits | skip != set(NUMBERS):
        raise ValueError(
            f"workload {workload['name']!r}: limits {sorted(limits)} and "
            f"not_compared {sorted(skip)} must split {list(NUMBERS)}")
    return sorted(limits)


# -- one run -------------------------------------------------------------------------

def inputs(cell: Cell, seed: int):
    """The cell's data, its model's module and the program's seed, all
    from the run's seed."""
    pseed = program_seed(seed)
    cfg = cell.config
    model_mod = load_module("models", cfg["model"])
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    fed = cfg["federation"]
    data = traffic_mod.make(cell.traffic["data"], cfg["model_sizes"],
                            fed["num_clusters"] * fed["devices_per_cluster"],
                            pseed)
    return data, model_mod, pseed


def prepare(cell: Cell, seed: int):
    """The cell's simulator and its inputs, from the run's seed. A
    streamed population starts with every client stored."""
    data, model_mod, pseed = inputs(cell, seed)
    eng = Engine(cell, data, pseed, model_mod)
    if eng.streamed:
        eng.fill_store()
    return eng, data, model_mod, pseed


def checked_rounds(eng: Engine, cell: Cell, model_mod):
    """The cell's first rounds, through the window's own ``step_round``:
    returns the norms the check compares (momentum after round 1, per
    leaf and per row; change of the parameters after each round, per
    leaf) and the rounds' seconds."""
    import jax
    import jax.numpy as jnp

    from bench.reference import leaf_names, row_norms
    names = leaf_names(jax.eval_shape(
        model_mod.system(cell.config["model_sizes"])[0],
        jax.random.PRNGKey(0)))
    norms = seg_norms_fn(eng.layout.segments)
    row0 = jnp.asarray(eng.initial_row())
    prog = {"dparam": []}
    times = []
    for r in range(int(cell.workload["check_rounds"])):
        t0 = time.perf_counter()
        plan = eng.step()
        eng.finish()
        times.append(time.perf_counter() - t0)
        if r == 0:
            mom = jnp.asarray(eng.momentum_rows(plan))
            prog["mom1"] = dict(zip(names, map(float, norms(mom, None))))
            prog["mom1_rows"] = row_norms([mom])
            del mom
        prog["dparam"].append(dict(zip(names, map(float, norms(
            jnp.asarray(eng.params_rows()), row0)))))
    return prog, times


def reference_numbers(cell: Cell, data: Dict, pseed: int, model_mod,
                      prog: Dict) -> Dict:
    """Run the plain reference over the checked rounds and compare the
    program's norms with it."""
    from bench.reference import Reference
    ref = Reference(cell.config, cell.traffic, data, pseed, model_mod)
    return compare(prog, ref.run(int(cell.workload["check_rounds"])))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[Dict] = None,
             cell: Optional[Cell] = None) -> Dict:
    """Build, warm, time and check one cell; returns the result line
    (the ``checks`` key last). ``t_start`` is the process's start on
    the ``perf_counter`` clock: set-up is timed from it."""
    import jax

    from bench import counts
    from bench import trace as tr
    cell = cell or resolve(name, bench)
    cfg, wl = cell.config, cell.workload
    compared(wl)
    events = CompileEvents()
    log(f"[bench] cell={cell.name} seed={seed}")
    eng, data, model_mod, pseed = prepare(cell, seed)
    sim = eng.sim
    k_check = int(wl["check_rounds"])
    warm = {"check_rounds": k_check}
    if eng.streamed:
        warm["stored_clients"] = int(sim.store.num_stored)
    prog, warm_times = checked_rounds(eng, cell, model_mod)
    est = float(np.median(warm_times[1:] or warm_times))
    n_window = max(MIN_ROUNDS, int(round(seconds / est)))
    c_setup = events.snapshot()
    log(f"[bench] warm-up {warm} round_estimate_s={est:.4f} "
        f"checked_rounds_s={[round(t, 3) for t in warm_times]} "
        f"window_rounds={n_window} compile_events={c_setup} "
        f"longest_compiles={events.longest()}")

    # the window
    page0 = eng.page_seconds()
    host0 = dict(sim.host_seconds)
    stamps, rows = [], 0
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    # the streamed driver paces itself (each call drains the round before),
    # so it runs until the seconds are up; the resident rounds are
    # dispatched ahead of the device, so their count is fixed up front
    t1 = t_win
    while (len(stamps) < n_window if not eng.streamed
           else t1 - t_win < seconds or len(stamps) < MIN_ROUNDS):
        plan = eng.step()
        t1 = time.perf_counter()
        stamps.append(t1)
        rows += eng.trained_rows(plan)
    eng.finish()
    t_end = time.perf_counter()
    n_window = len(stamps)
    c_window = {k: v - c_setup[k] for k, v in events.snapshot().items()}
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    run = {
        "cell": cell.name, "setup_s": setup_s, "window_s": t_end - t_win,
        "rounds": n_window,
        "page_s": eng.page_seconds() - page0,
        "model_flops": counts.model_flops(cfg, model_mod, rows),
        "trace": None,
    }
    log(f"[bench] window rounds={n_window} window_s={run['window_s']:.4f} "
        f"compile_events_in_window={c_window} peak_bytes_in_use={peak}")
    # where a slow window lost its time: each round's interval, and the
    # program's host spans over the window
    spans = {k: round(v - host0.get(k, 0.0), 4)
             for k, v in sim.host_seconds.items()}
    log(f"[bench] window round_intervals_s="
        f"{np.round(np.diff([t_win] + stamps + [t_end]), 4).tolist()} "
        f"host_span_s={spans}")
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()),
                     "memory_peak_bytes": peak}
    if trace:
        # the host's own work per round: each call with the queue empty
        probe = []
        for _ in range(PROBE_ROUNDS):
            eng.finish()
            t0 = time.perf_counter()
            eng.step()
            probe.append(time.perf_counter() - t0)
        eng.finish()
        run["host_probe_s"] = probe
        n_trace = max(2, math.ceil(TRACE_SECONDS / est))
        run["trace"] = tr.traced_window(
            eng, n_trace, os.path.join(OUT, "trace", cell.name))
        run["trace"]["counts"] = counts.round_counts(cfg, eng)
        # the CPU of a rehearsal has no peaks; an unknown chip is an error
        run["peaks"] = (counts.peaks(dev.device_kind)
                        if dev.platform == "tpu" else None)
        result_device["busy_s"] = run["trace"]["busy_s"]
        result_device["window_s"] = run["trace"]["window_s"]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # free the program's state, then the reference
    del eng, sim, plan
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    numbers = reference_numbers(cell, data, pseed, model_mod, prog)
    ref_s = time.perf_counter() - t0
    limits = wl["limits"]
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in limits.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    out = {"correct": correct, "attempted": n_window, "failed": 0,
           "metrics": metrics, "device": result_device}
    if trace:
        out["breakdown"] = run["trace"]["breakdown"]
    out["checks"] = checks
    log(f"[bench] reference rounds={k_check} seconds={ref_s:.2f}")
    for k, v in checks.items():
        log(f"check {k} = {v['value']:.6e} limit {v['limit']:.6e}")
    return out


def main(args, t_start: float) -> int:
    """The command: refuse to run without the chips the cell asks for."""
    import jax
    cell = resolve(args.workload)
    devs = jax.devices()
    chips = int(cell.entry["chips"])
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"[bench] needs {chips} TPU chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"[bench] compile_cache={use_cache()} "
        f"matmul_precision={use_precision(cell)}")
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start, cell=cell)
    print(json.dumps(out), flush=True)
    return 0
