"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh)
combination against ShapeDtypeStruct inputs (no allocation) and extract the
roofline terms from the compiled artifacts.

Methodology (documented in EXPERIMENTS.md §Dry-run):
- The PRODUCTION artifact keeps ``lax.scan`` over layers/steps — it is the
  lowering/compile proof and the source of ``memory_analysis()``.
- XLA's ``cost_analysis()`` counts a ``while`` body once (verified), so the
  roofline FLOPs/bytes/collective-bytes come from ANALYSIS artifacts with
  loops unrolled. Model depth is handled with an exact 2-point linear fit:
  lower at L=1 and L=2 layer-units, extrapolate cost(L) — exact because
  layers are homogeneous. Mixing operators (intra/inter) have no loops and
  are lowered at full parameter shapes.
- One train global round = qτ·local_step + q·intra_mix + inter_mix.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--gossip sparse]
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import flags
from repro import sharding as sh
from repro.config import INPUT_SHAPES, ModelConfig
from repro.configs import applicable_shapes, ARCHS, get_experiment
from repro.core.sharded import (ShardedCEFedAvg, abstract_model,
                                make_decode_fn, make_prefill_fn, serve_specs)
from repro.launch import roofline as rf
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh


def _ns(mesh, tree):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _stats(compiled) -> Dict[str, float]:
    ca = rf.cost_dict(compiled)
    coll = rf.collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total_bytes"]),
        "coll": coll,
    }


import contextlib

_ACTIVE_MESH = None


def _compile(fn, args, in_shardings, out_shardings=None, donate=()):
    kw = {"in_shardings": in_shardings}
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    if donate:
        kw["donate_argnums"] = donate
    ctx = _ACTIVE_MESH if _ACTIVE_MESH is not None else \
        contextlib.nullcontext()
    t0 = time.time()
    with ctx:
        lowered = jax.jit(fn, **kw).lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return compiled, round(t1 - t0, 2), round(t2 - t1, 2)


def _memory(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        mem["peak_bytes_per_device"] = (
            mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
            - mem["alias_bytes"])
        return mem
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


# --- layer-unit scaling (exact: homogeneous stacks) -------------------------

def layer_units(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_every
    if cfg.family == "moe" and cfg.moe_shared_expert:
        return cfg.num_layers // 2
    return cfg.num_layers


def with_units(cfg: ModelConfig, u: int) -> ModelConfig:
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, num_layers=u * cfg.attn_every)
    if cfg.family == "moe" and cfg.moe_shared_expert:
        return dataclasses.replace(cfg, num_layers=2 * u)
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, num_layers=u, encoder_layers=u)
    return dataclasses.replace(cfg, num_layers=u)


def _fit(costs: Dict[int, Dict[str, float]], L: int) -> Dict[str, float]:
    (u1, c1), (u2, c2) = sorted(costs.items())
    out = {}
    for k in ("flops", "bytes", "coll_bytes"):
        slope = (c2[k] - c1[k]) / (u2 - u1)
        out[k] = max(c2[k] + slope * (L - u2), 0.0)
    return out




def _finish_skipped(record, cfg, shape, mesh):
    pshapes_count, _ = abstract_model(cfg)
    mf, total_n, active_n = rf.model_flops(
        cfg, pshapes_count, "train" if shape.kind == "train" else "infer",
        record["tokens_per_call"])
    record.update({"model_flops": mf, "params_total": int(total_n),
                   "params_active": int(active_n)})
    return record

# ---------------------------------------------------------------------------
# per-combination lowering
# ---------------------------------------------------------------------------

def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                gossip: str = "dense", algorithm: str = "ce_fedavg",
                remat: bool = False, fl_overrides: Dict[str, Any] = None,
                skip_production: bool = False,
                skip_analysis: bool = False,
                model_overrides: Dict[str, Any] = None) -> Dict[str, Any]:
    global _ACTIVE_MESH
    mesh = make_production_mesh(multi_pod=multi_pod)
    _ACTIVE_MESH = mesh
    exp = get_experiment(arch, multi_pod=multi_pod)
    exp = exp.replace(fl=dataclasses.replace(
        exp.fl, gossip_impl=gossip, algorithm=algorithm,
        **(fl_overrides or {})))
    if remat:
        exp = exp.replace(train=dataclasses.replace(exp.train, remat=True))
    if model_overrides:
        exp = exp.replace(model=dataclasses.replace(exp.model,
                                                    **model_overrides))
    shape = INPUT_SHAPES[shape_name]
    cfg = exp.model
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "algorithm": algorithm, "gossip": gossip,
        "remat": remat, "num_devices": mesh.size,
    }

    if shape.kind == "train":
        tr = ShardedCEFedAvg(exp, mesh)
        R = tr.geo.num_replicas
        batch_shapes = sp.train_batch_shapes(exp, shape, R)
        # ---- production artifact (scan form): proof + memory ----
        if not skip_production:
            compiled, tl, tc = _compile(
                tr.make_global_round(),
                (tr.param_shapes, tr.opt_shapes, batch_shapes,
                 jax.ShapeDtypeStruct((), jnp.int32)),
                tr.in_shardings(batch_shapes), tr.out_shardings(),
                donate=(0, 1))
            record["memory"] = _memory(compiled)
            record["production"] = {"lower_s": tl, "compile_s": tc,
                                    **{k: v for k, v in _stats(compiled).items()
                                       if k != "coll"}}
        # ---- analysis artifacts ----
        if skip_analysis:
            record["analysis"] = "skipped"
            record["tokens_per_call"] = (exp.fl.q * exp.fl.tau
                                         * shape.global_batch * shape.seq_len)
            return _finish_skipped(record, cfg, shape, mesh)
        with flags.analysis():
            costs = {}
            for u in (1, 2):
                e_u = exp.replace(model=with_units(cfg, u))
                tr_u = ShardedCEFedAvg(e_u, mesh)
                mb = {k: jax.ShapeDtypeStruct(v.shape[2:], v.dtype)
                      for k, v in batch_shapes.items()}
                c_u, _, _ = _compile(
                    tr_u.make_local_step(),
                    (tr_u.param_shapes, tr_u.opt_shapes, mb,
                     jax.ShapeDtypeStruct((), jnp.int32)),
                    (tr_u.in_shardings(mb)[0], tr_u.in_shardings(mb)[1],
                     _ns(mesh, tr_u.microbatch_specs(mb)),
                     NamedSharding(mesh, P())))
                costs[u] = _stats(c_u)
            step_cost = _fit(costs, layer_units(cfg))
            # mixing at full parameter shapes (loop-free under analysis)
            c_intra, _, _ = _compile(
                tr.make_intra_fn(), (tr.param_shapes,),
                (tr.in_shardings(batch_shapes)[0],))
            c_inter, _, _ = _compile(
                tr.make_inter_fn(), (tr.param_shapes,),
                (tr.in_shardings(batch_shapes)[0],))
            intra_cost, inter_cost = _stats(c_intra), _stats(c_inter)
        q, tau = exp.fl.q, exp.fl.tau
        flops = q * tau * step_cost["flops"] + q * intra_cost["flops"] \
            + inter_cost["flops"]
        bytes_ = q * tau * step_cost["bytes"] + q * intra_cost["bytes"] \
            + inter_cost["bytes"]
        coll = q * tau * step_cost["coll_bytes"] \
            + q * intra_cost["coll_bytes"] + inter_cost["coll_bytes"]
        record["components"] = {
            "local_step": step_cost,
            "intra_mix": {k: intra_cost[k] for k in
                          ("flops", "bytes", "coll_bytes")},
            "inter_mix": {k: inter_cost[k] for k in
                          ("flops", "bytes", "coll_bytes")},
            "inter_coll_by_kind": inter_cost["coll"]["bytes_by_kind"],
            "step_coll_by_kind": costs[2]["coll"]["bytes_by_kind"],
        }
        tokens = q * tau * shape.global_batch * shape.seq_len
        pshapes_count, _ = abstract_model(cfg)
    else:
        if shape.kind == "prefill":
            shapes, logical = abstract_model(cfg)
            pspecs = sh.resolve_specs(shapes, logical, mesh)
            batch_shapes = sp.prefill_batch_shapes(cfg, shape)
            bspecs = jax.tree.map(
                lambda s: P("data", *([None] * (len(s.shape) - 1))),
                batch_shapes)
            args = (shapes, batch_shapes)
            inshard = (_ns(mesh, pspecs), _ns(mesh, bspecs))
            fn_of = lambda c: make_prefill_fn(c)  # noqa: E731
            donate = ()
            outshard = None
            tokens = shape.global_batch * shape.seq_len
        else:
            pshapes, pspecs, cache_shapes, cspecs = serve_specs(
                cfg, mesh, shape.global_batch, shape.seq_len)
            _, tok_s, pos_s = sp.decode_input_shapes(cfg, shape)
            args = (pshapes, cache_shapes, tok_s, pos_s)
            inshard = (_ns(mesh, pspecs), _ns(mesh, cspecs),
                       NamedSharding(mesh, P()), NamedSharding(mesh, P()))
            outshard = (NamedSharding(mesh, P(None, None, "model")),
                        _ns(mesh, cspecs))
            fn_of = lambda c: make_decode_fn(c)  # noqa: E731
            donate = (1,)
            tokens = shape.global_batch
        # ---- production ----
        if not skip_production:
            compiled, tl, tc = _compile(fn_of(cfg), args, inshard, outshard,
                                        donate)
            record["memory"] = _memory(compiled)
            record["production"] = {"lower_s": tl, "compile_s": tc,
                                    **{k: v for k, v in _stats(compiled).items()
                                       if k != "coll"}}
        # ---- analysis (2-point layer fit, unrolled) ----
        if skip_analysis:
            record["analysis"] = "skipped"
            record["tokens_per_call"] = tokens
            return _finish_skipped(record, cfg, shape, mesh)
        with flags.analysis():
            costs = {}
            for u in (1, 2):
                cfg_u = with_units(cfg, u)
                if shape.kind == "prefill":
                    shapes_u, logical_u = abstract_model(cfg_u)
                    pspecs_u = sh.resolve_specs(shapes_u, logical_u, mesh)
                    args_u = (shapes_u, batch_shapes)
                    inshard_u = (_ns(mesh, pspecs_u), inshard[1])
                    out_u = None
                else:
                    ps_u, pp_u, cs_u, cp_u = serve_specs(
                        cfg_u, mesh, shape.global_batch, shape.seq_len)
                    args_u = (ps_u, cs_u, args[2], args[3])
                    inshard_u = (_ns(mesh, pp_u), _ns(mesh, cp_u),
                                 inshard[2], inshard[3])
                    out_u = (NamedSharding(mesh, P(None, None, "model")),
                             _ns(mesh, cp_u))
                c_u, _, _ = _compile(fn_of(cfg_u), args_u, inshard_u, out_u)
                costs[u] = _stats(c_u)
        fit = _fit(costs, layer_units(cfg))
        flops, bytes_, coll = fit["flops"], fit["bytes"], fit["coll_bytes"]
        record["components"] = {
            "per_unit_fit": fit,
            "coll_by_kind_u2": costs[2]["coll"]["bytes_by_kind"],
        }
        pshapes_count, _ = abstract_model(cfg)

    terms = rf.roofline_terms(flops, bytes_, coll)
    mf, total_n, active_n = rf.model_flops(
        cfg, pshapes_count, "train" if shape.kind == "train" else "infer",
        tokens)
    record.update({
        "tokens_per_call": tokens,
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "collective_bytes_per_device": coll,
        "terms": terms,
        "model_flops": mf,
        "params_total": int(total_n),
        "params_active": int(active_n),
        "useful_ratio": mf / max(flops * mesh.size, 1.0),
    })
    return record


def main():
    # the production meshes need 512 host devices; the CPU backend reads
    # this flag once, when it first starts, which is after this line
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--gossip", choices=("dense", "sparse", "ringweight"), default="dense")
    ap.add_argument("--algorithm", default="ce_fedavg")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--skip-production", action="store_true")
    ap.add_argument("--skip-analysis", action="store_true")
    ap.add_argument("--attn-seq-shard", action="store_true")
    ap.add_argument("--head-pad", type=int, default=0)
    ap.add_argument("--moe-local", action="store_true")
    ap.add_argument("--swa", type=int, default=0,
                    help="serve with a sliding window (dense-arch long-"
                         "context variant)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    combos = []
    if args.all:
        for arch in ARCHS:
            for shape in applicable_shapes(arch):
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in combos:
        name = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
        if args.gossip != "dense":
            name += f"_{args.gossip}"
        if args.algorithm != "ce_fedavg":
            name += f"_{args.algorithm}"
        if args.remat:
            name += "_remat"
        if args.tag:
            name += f"_{args.tag}"
        t0 = time.time()
        try:
            rec = lower_combo(arch, shape, multi_pod=args.multi_pod,
                              gossip=args.gossip, algorithm=args.algorithm,
                              remat=args.remat,
                              skip_production=args.skip_production,
                              skip_analysis=args.skip_analysis,
                              model_overrides=(
                                  ({"attn_seq_shard": True}
                                   if args.attn_seq_shard else {}) |
                                  ({"head_pad_to": args.head_pad}
                                   if args.head_pad else {}) |
                                  ({"moe_local_dispatch": True}
                                   if args.moe_local else {}) |
                                  ({"sliding_window": args.swa}
                                   if args.swa else {}) or None))
            rec["wall_s"] = round(time.time() - t0, 1)
            with open(os.path.join(args.out, name + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
            if "terms" in rec:
                print(rf.summarize(rec), f"[{rec['wall_s']}s]", flush=True)
            else:
                print(f"{name} compiled OK (analysis skipped) "
                      f"mem={rec.get('memory',{}).get('peak_bytes_per_device','?')} "
                      f"[{rec['wall_s']}s]", flush=True)
        except Exception as e:
            failures.append((name, repr(e)))
            print(f"{name} FAILED: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for n, e in failures:
            print(" ", n, e)
        raise SystemExit(1)
    print(f"\nall {len(combos)} combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
