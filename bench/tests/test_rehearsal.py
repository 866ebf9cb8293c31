"""A CPU rehearsal of whole benchmark runs at a tiny size: the harness's
own cell building, checked first rounds, window, reference, metrics and
result line, without the look for a chip."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.tiny import tiny_cell

ROOT = harness.ROOT
CELLS = ["femnist_paper_full", "femnist_xdevice_paging",
         "femnist_paper_sampled25"]


@pytest.mark.parametrize("name", CELLS)
def test_run_is_correct_and_reports_its_end_to_end_metrics(name):
    cell = tiny_cell(name)
    out = harness.run_cell(name, 2**33 + 11, 0.5, False,
                           t_start=time.perf_counter(), cell=cell)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.workload["limits"])
    assert out["attempted"] >= harness.MIN_ROUNDS and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(out["device"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics_it_can_read():
    name = "femnist_paper_full"
    out = harness.run_cell(name, 5, 0.5, True, t_start=time.perf_counter(),
                           cell=tiny_cell(name))
    assert out["correct"] is True
    # the CPU has no TPU ops and no peaks: only the host's span is read
    assert set(out["metrics"]) == {"host_round_ms"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def test_same_seed_same_inputs_other_seed_other_inputs():
    cell = tiny_cell("femnist_paper_full")
    a, _, pa = harness.inputs(cell, 2**40 + 3)
    b, _, pb = harness.inputs(cell, 2**40 + 3)
    c, _, pc = harness.inputs(cell, 2**40 + 4)
    assert pa == pb != pc and 0 <= pa < 2**31
    assert (a["xs"] == b["xs"]).all() and (a["xs"] != c["xs"]).any()


def test_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "femnist_paper_full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
