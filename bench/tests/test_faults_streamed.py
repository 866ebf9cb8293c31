"""A run of the streamed population cell with its timed path broken
underneath (no look for a chip): ``correct`` has to come out false."""
import time

import pytest

from bench import harness
from bench.tests.faults import planted
from bench.tests.tiny import tiny_cell

NAME = "femnist_xdevice_paging"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_mixing", "momentum_altered"])
def test_fault_makes_the_run_incorrect(monkeypatch, fault):
    with planted(monkeypatch, fault):
        out = harness.run_cell(NAME, 78, 0.2, False,
                               t_start=time.perf_counter(),
                               cell=tiny_cell(NAME))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
