"""The host spans of ``step_round`` (``core/spans.py``): each
``repro.*`` span is entered once per round and lands in a profiler
trace, the paging counter is the sum of the ``page.*`` spans, and the
pipelined streamed round never overwrites a staged page-in before its
round has run."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig, PopulationConfig, ScenarioConfig
from repro.core.cefedavg import FLSimulator
from repro.core.spans import span
from repro.data.federated import (build_fl_data, dirichlet_partition,
                                  make_synthetic_classification)
from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier

FL = FLConfig(algorithm="ce_fedavg", num_clusters=4,
              devices_per_cluster=4, tau=2, q=2, pi=2, topology="ring")
POP = ScenarioConfig(
    name="pop", sample_fraction=0.5, dropout_prob=0.1, move_prob=0.25,
    seed=7, population=PopulationConfig(
        clients_per_cluster=100, cohort_per_cluster=3, codec="int8"))

PAGE = ("page.wait", "page.commit", "page.stage")
ROUNDS = {
    "pipelined": (dict(scenario=POP, pipeline=True),
                  ("round", "assemble", "dispatch") + PAGE),
    "serial": (dict(scenario=POP), ("round", "assemble", "dispatch") + PAGE),
    "resident": (dict(scenario=dataclasses.replace(POP, population=None)),
                 ("round", "assemble", "dispatch")),
}


def _sim(**kw):
    x, y = make_synthetic_classification(800, 16, 4, seed=3)
    tx, ty = make_synthetic_classification(400, 16, 4, seed=4)
    parts = dirichlet_partition(y, FL.n, alpha=0.5, seed=5)
    d = build_fl_data(x, y, parts, tx, ty, samples_per_device=64)
    return FLSimulator(
        lambda k: init_mlp_classifier(k, 16, 32, 4), apply_mlp_classifier,
        FL, {k: jnp.asarray(v) for k, v in d.items()}, lr=0.1,
        batch_size=16, seed=1, **kw)


def test_span_counts_seconds_and_reraises():
    seconds = {}
    with span(seconds, "a"):
        pass
    with pytest.raises(ValueError):
        with span(seconds, "a"):
            raise ValueError
    assert set(seconds) == {"a"} and seconds["a"] > 0


@pytest.mark.parametrize("kind", list(ROUNDS))
def test_each_span_is_entered_once_per_round(kind, tmp_path):
    kw, names = ROUNDS[kind]
    sim = _sim(**kw)
    sim.step_round()                   # compiles; the pipeline fills
    rounds = 2
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(rounds):
            sim.step_round()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    seen = [e.name for plane in pd.planes for line in plane.lines
            for e in line.events if e.name.startswith("repro.")]
    assert sorted(seen) == sorted("repro." + n for n in names
                                  for _ in range(rounds))
    assert set(sim.host_seconds) == set(names)
    assert sim._page_seconds == sum(
        v for k, v in sim.host_seconds.items() if k.startswith("page."))
    assert (sim._page_seconds > 0) == (kind != "resident")
    parts = sum(v for k, v in sim.host_seconds.items() if k != "round")
    assert 0 < parts <= sim.host_seconds["round"]


def test_first_pipelined_round_stages_its_own_page_in():
    sim = _sim(scenario=POP, pipeline=True)
    sim.step_round()
    assert sim._pipe["staged"]["r"] == 1
    assert sim.host_seconds.keys() == set(ROUNDS["pipelined"][1])


def test_staged_page_in_survives_the_next_stage(monkeypatch):
    """A put may read its host buffer until the round that consumes it
    has run (the CPU backend aliases it outright): model that with a put
    that returns the host buffer itself, and check that staging the next
    round leaves the staged rows of this one as they were."""
    sim = _sim(scenario=POP, pipeline=True)
    for _ in range(2):
        sim.step_round()
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    plan = sim._peek_plan()
    staged = []
    for r in range(3):
        ws = sim._stage_pipelined(plan, sim.round_index + r)
        staged.append((ws, ws["q"].copy(), ws["s"].copy()))
        sim.store.commit_encoded(      # the store moves on between rounds
            ws["cohort"], np.full_like(ws["q"][:ws["k"]], r + 1),
            np.full_like(ws["s"][:ws["k"]], r + 1.0))
        plan = sim._peek_plan()
    (ws0, q0, s0), (ws1, q1, s1), (ws2, q2, s2) = staged
    np.testing.assert_array_equal(ws1["q"], q1)
    np.testing.assert_array_equal(ws1["s"], s1)
    np.testing.assert_array_equal(ws2["q"], q2)
    assert not np.shares_memory(ws1["q"], ws2["q"])
    # two rounds on, the buffer is reused: its round has been drained
    assert np.shares_memory(ws0["q"], ws2["q"])
