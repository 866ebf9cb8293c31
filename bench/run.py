"""The benchmark's command: one run of one cell on the chip.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the cell's metrics as the last line of standard output (the end
to end ones with ``--trace 0``, the per-layer ones with ``--trace 1``)
and the numbers the check compared, each beside its limit, as the last
lines of standard error. Exits non-zero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".bench_out",
                                                  "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    args = parse()
    from bench import harness
    sys.exit(harness.main(args, T_START))
