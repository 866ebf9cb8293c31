"""A run of a resident-bank cell with its timed path broken underneath
(no look for a chip): ``correct`` has to come out false."""
import time

import pytest

from bench import harness
from bench.tests.faults import planted
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("name,fault", [
    ("femnist_paper_full", "state_unchanged"),
    ("femnist_paper_full", "half_batch"),
    ("femnist_paper_full", "no_mixing"),
])
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    with planted(monkeypatch, fault):
        out = harness.run_cell(name, 77, 0.2, False,
                               t_start=time.perf_counter(),
                               cell=tiny_cell(name))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
