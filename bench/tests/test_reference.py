"""The plain reference over inputs and labels of any shape: the loss
over every label element, integer inputs kept integers, a next-token
model going through the reference, both controls, the faults and the
CPU shrink with its own files alone, and the keyed sampled mask."""
import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, readings
from bench.reference import Reference, cross_entropy, sampled_mask
from bench.tests import token_lm
from bench.tests.tiny import tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST


def token_cell() -> harness.Cell:
    """The tiny language model's cell, from ``token_cell.json``."""
    with open(os.path.join(HERE, "token_cell.json")) as f:
        d = json.load(f)
    return harness.Cell(d["entry"]["name"], d["entry"], d["workload"],
                        d["config"], d["traffic"], [], [])


def single_label_loss(logits, y):
    """The reference's loss before it took labels of any shape."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0])


def test_loss_of_a_classifier_is_the_single_label_loss_bit_for_bit():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    logits = jax.random.normal(k1, (50, 62), jnp.float32) * 3
    y = jax.random.randint(k2, (50,), 0, 62)
    for f in (lambda g: g, jax.jit):
        assert f(cross_entropy)(logits, y) == f(single_label_loss)(logits, y)
        np.testing.assert_array_equal(
            f(jax.grad(cross_entropy))(logits, y),
            f(jax.grad(single_label_loss))(logits, y))


def test_cnn_row_grads_are_the_single_label_ones_bit_for_bit():
    cell = tiny_cell("femnist_paper_full")
    data, mod, pseed = harness.inputs(cell, 17)
    ref = Reference(cell.config, cell.traffic, data, pseed, mod)
    Y = jax.tree.map(lambda a: jnp.stack([a, a * 1.5]), ref.p0)
    xb, yb = ref.xs[:2, :4], ref.ys[:2, :4]

    def old(p, x, y):
        return single_label_loss(mod.ref_apply(p, x, HIGHEST), y)
    want = jax.lax.map(lambda a: jax.grad(old)(*a), (Y, xb, yb))
    for g, w in zip(jax.tree.leaves(ref._row_grads(Y, xb, yb)),
                    jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w)


def test_tiny_cell_shrinks_a_token_cell_from_its_own_files():
    cell = tiny_cell(token_cell())
    assert cell.config["model_sizes"] == {"vocab": 300, "dim": 64, "seq": 8}
    assert cell.config["federation"]["num_clusters"] == 3
    assert cell.config["optimizer"]["batch"] == 4
    assert cell.traffic["data"]["samples_per_device"] == 8
    data, _, _ = harness.inputs(cell, 2**33 + 5)
    assert data["xs"].shape == data["ys"].shape == (6, 8, 8)
    assert data["xs"].dtype == data["ys"].dtype == np.int32
    assert 256 < int(data["xs"].max()) < 300


def test_row_grads_are_the_mean_cross_entropy_over_every_position():
    cell = tiny_cell(token_cell())
    data, mod, pseed = harness.inputs(cell, 3)
    ref = Reference(cell.config, cell.traffic, data, pseed, mod)
    Y = jax.tree.map(lambda a: jnp.stack([a, a * 0.5]), ref.p0)
    xb, yb = ref.xs[:2, :4], ref.ys[:2, :4]
    got = ref._row_grads(Y, xb, yb)
    for i in range(2):
        p = jax.tree.map(lambda a: a[i], Y)
        logits, back = jax.vjp(lambda q: mod.ref_apply(q, xb[i], HIGHEST), p)
        # d mean_{b,s} -log softmax(logits)[y] / d logits
        ct = (jax.nn.softmax(logits, -1)
              - jax.nn.one_hot(yb[i], logits.shape[-1])) / yb[i].size
        for g, w in zip(jax.tree.leaves(jax.tree.map(lambda a: a[i], got)),
                        jax.tree.leaves(back(ct)[0])):
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-6)


@pytest.mark.parametrize("kw", [{}, {"dtype": jnp.bfloat16},
                                {"precision": token_lm.BF16_3X}],
                         ids=["f32", "bf16", "bf16_3x"])
def test_token_model_gets_int32_ids_in_the_reference_and_controls(kw):
    cell = tiny_cell(token_cell())
    data, mod, pseed = harness.inputs(cell, 4)
    seen = set()

    def ref_apply(p, x, precision=HIGHEST):
        seen.add(x.dtype)
        return mod.ref_apply(p, x, precision)
    spy = types.SimpleNamespace(**{k: getattr(mod, k) for k in (
        "ref_init", "product", "BF16_3X")}, ref_apply=ref_apply)
    out = Reference(cell.config, cell.traffic, data, pseed, spy,
                    **kw).run(1)
    assert seen == {jnp.dtype(jnp.int32)}
    assert all(np.isfinite(v) and v > 0 for v in out["mom1"].values())


def test_controls_and_faults_read_on_a_token_cell():
    cell = tiny_cell(token_cell())
    kinds = tuple(readings.CONTROL.values()) + tuple(readings.FAULTS)
    lines = readings.control_readings(cell, 6, kinds)
    assert [ln["kind"] for ln in lines] == list(kinds)
    for ln in lines:
        assert set(ln["numbers"]) == set(harness.NUMBERS)
        assert all(np.isfinite(v) for v in ln["numbers"].values()), ln
        assert max(ln["numbers"].values()) > 0, ln


@pytest.mark.parametrize("seed", [2718281828 & 0x7FFFFFFF, 77])
@pytest.mark.parametrize("dropout", [0.0, 0.1, 0.95])
def test_sampled_mask_is_the_scenario_engines_draw(seed, dropout):
    from repro.config import FLConfig, ScenarioConfig
    from repro.core.scenario import ScenarioEngine
    fl = FLConfig(algorithm="ce_fedavg", num_clusters=8,
                  devices_per_cluster=8, tau=2, q=8, pi=10)
    eng = ScenarioEngine(ScenarioConfig(
        name="sampled", sample_fraction=0.25, dropout_prob=dropout,
        seed=seed), fl)
    for r in range(5):
        want = eng.step().mask
        got = sampled_mask(seed, r, 8, 8, 0.25, dropout)
        np.testing.assert_array_equal(got, want)
        assert got.sum() >= 1
        assert dropout or got.sum() == 16


@pytest.mark.parametrize("name", ["femnist_paper_full",
                                  "femnist_xdevice_paging",
                                  "femnist_paper_sampled25"])
def test_each_workload_compares_or_gives_a_reason_for_every_number(name):
    wl = harness.resolve(name).workload
    assert harness.compared(wl) == sorted(wl["limits"])
    assert all(isinstance(v, str) and v
               for v in wl.get("not_compared", {}).values())


def test_a_workload_that_drops_a_number_unseen_is_refused():
    wl = {"name": "w", "limits": {"mom_gap": 1.0, "dparam_gap": 1.0}}
    with pytest.raises(ValueError):
        harness.compared(wl)
    with pytest.raises(ValueError):
        harness.compared(dict(wl, not_compared={
            k: "a reason" for k in harness.NUMBERS}))
    cell = dataclasses.replace(tiny_cell("femnist_paper_full"), workload=wl)
    with pytest.raises(ValueError):
        harness.run_cell(cell.name, 1, 0.1, False, t_start=0.0, cell=cell)


def test_rows_wrong_counts_the_rows_one_side_alone_stepped():
    ref = np.array([0.0, 1.0, 2.0, 0.0])
    assert harness.rows_wrong(ref, ref) == 0
    assert harness.rows_wrong([0.5, 1.0, 2.0, 0.0], ref) == 1
    assert harness.rows_wrong([0.0, 1.0, 0.0, 2.0], ref) == 2


def test_wrong_rows_fault_steps_an_idle_device_of_the_same_server():
    mask = np.array([1.0, 0.0, 0.0, 1.0, 1.0, 1.0])
    assert readings.moved_row(mask, 2) == (0, 1)
    assert readings.moved_row(np.array([1.0, 1.0, 0.0, 1.0]), 2) == (3, 2)
    assert readings.moved_row(np.ones(4), 2) is None
    cell = tiny_cell("femnist_paper_sampled25")
    (line,) = readings.control_readings(cell, 5, ("wrong_rows",))
    assert line["numbers"]["mom_rows_wrong"] == 2
