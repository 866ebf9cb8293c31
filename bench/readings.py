"""The readings that the check's limits are set from. The benchmark's own
runs do not run this.

- the program's numbers over many seeds (the lower reading): the
  cell's first rounds through ``step_round``, against the reference;
- the control's (the upper reading): the reference computed in
  bfloat16, put in the program's place;
- faults planted in the reference put in the program's place: half of
  each batch left out, and the mixing left out. A state left unchanged
  reads 1 by the measure (its change of the parameters is nought) and
  needs no run.

  python bench/readings.py --workload <cell> --seeds 1,2,3 --controls 4,5,6

prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402
from bench.reference import Reference  # noqa: E402


class HalfBatch(Reference):
    """The reference with half of each batch left out: the loss is the
    mean over the first half."""

    def _row_grads(self, Y, xb, yb):
        h = xb.shape[1] // 2
        return super()._row_grads(Y, xb[:, :h], yb[:, :h])


class NoMixing(Reference):
    """The reference with the exchange between clients left out: every
    row keeps its own model (a cluster's model is its first member's)."""

    def _mix(self, W, Y):
        import jax
        W = np.asarray(W)
        if W.shape[0] == W.shape[1]:
            return Y
        first = np.argmax(W > 0, axis=1)
        return jax.tree.map(lambda y: y[first], Y)


FAULTS = {"half_batch": HalfBatch, "no_mixing": NoMixing}


def program_reading(cell, seed: int) -> dict:
    """The program's numbers for ``seed``: its first rounds against the
    reference."""
    import jax
    eng, data, model_mod, pseed = harness.prepare(cell, seed)
    prog, times = harness.checked_rounds(eng, cell, model_mod)
    del eng
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    numbers = harness.reference_numbers(cell, data, pseed, model_mod, prog)
    return {"seed": seed, "kind": "program", "numbers": numbers,
            "round_s": times, "reference_s": time.perf_counter() - t0}


#: the control of each stated matmul precision: the reference computed
#: one step below it (``highest``: three bf16 passes; ``default``, one
#: bf16 pass: everything in bfloat16)
CONTROL = {"highest": "bf16_3x", "default": "bf16"}


def control_readings(cell, seed: int, kinds=None):
    """The control's and the faults' numbers for ``seed``: each stands in
    the program's place against the float32 reference."""
    import jax.numpy as jnp
    if kinds is None:
        kinds = (CONTROL[cell.config["precision"]["matmul"]],) + tuple(FAULTS)
    data, model_mod, pseed = harness.inputs(cell, seed)
    k = int(cell.workload["check_rounds"])
    args = (cell.config, cell.traffic, data, pseed, model_mod)
    t0 = time.perf_counter()
    ref = Reference(*args).run(k)
    ref_s = time.perf_counter() - t0
    out = []
    for kind in kinds:
        if kind == "bf16":
            stand_in = Reference(*args, dtype=jnp.bfloat16)
        elif kind == "bf16_3x":
            stand_in = Reference(*args, precision=model_mod.BF16_3X)
        else:
            stand_in = FAULTS[kind](*args)
        numbers = harness.compare(stand_in.run(k), ref)
        out.append({"seed": seed, "kind": kind, "numbers": numbers,
                    "reference_s": ref_s})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--kinds", default="",
                    help="control and fault kinds (default: the control "
                         "of the stated precision and every fault)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.log(f"[readings] compile_cache={harness.use_cache()}")
    cell = harness.resolve(args.workload)
    harness.log(f"[readings] matmul_precision="
                f"{harness.use_precision(cell)}")
    for s in filter(None, args.seeds.split(",")):
        print(json.dumps(program_reading(cell, int(s))), flush=True)
    for s in filter(None, args.controls.split(",")):
        kinds = tuple(filter(None, args.kinds.split(","))) or None
        for line in control_readings(cell, int(s), kinds):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
