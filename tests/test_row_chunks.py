"""Per-row gradients in row chunks (``core/cefedavg.py``
``grad_chunk_rows``): a local step whose gradients run a few rows at a
time computes what the one all-rows ``vmap`` computes, and the rule
that sizes the chunks reads only shapes and the device's memory limit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import cefedavg
from repro.core.cefedavg import FLSimulator, grad_chunk_rows
from repro.models.cnn import apply_femnist_cnn, init_femnist_cnn

ROWS = 6
IMAGE = 8
BATCH = 4
#: the memory limit a TPU v5e chip reports usable (15.75 GiB)
V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)


def _cnn_sim(image=IMAGE, rows=ROWS, batch=BATCH, samples=16):
    fl = FLConfig(num_clusters=2, devices_per_cluster=rows // 2, tau=2,
                  q=1, pi=1, topology="ring")
    rng = np.random.default_rng(0)
    data = {"xs": jnp.asarray(rng.normal(
                size=(rows, samples, image, image, 1)), jnp.float32),
            "ys": jnp.asarray(rng.integers(0, 62, (rows, samples)),
                              jnp.int32)}
    return FLSimulator(lambda k: init_femnist_cnn(k, image_size=image),
                       apply_femnist_cnn, fl, data, batch_size=batch)


def _first_grads(sim, draw):
    """The momentum after one local step from zero momentum, that is the
    step's per-row gradients, at distinct parameters on every row."""
    Y = sim.bank.params + 0.01 * jax.random.normal(
        jax.random.PRNGKey(1), sim.bank.params.shape)
    make = sim._flat_helpers("flat")
    step = make(sim.data["xs"], sim.data["ys"],
                jnp.ones((ROWS, 1), bool),
                fold_ids=jnp.arange(ROWS) if draw == "fold_ids" else None)
    (_, G), _ = jax.jit(step)((Y, jnp.zeros_like(Y)),
                              jax.random.PRNGKey(2))
    return np.asarray(G)


@pytest.mark.parametrize("draw", ["flat", "fold_ids"])
@pytest.mark.parametrize("c", [1, 2, 3, 4, 6])
def test_chunked_grads_match_one_pass(monkeypatch, c, draw):
    """Chunks of c rows (4 leaves a remainder of 2) give the all-rows
    gradients to 1e-6, under both batch draws."""
    sim = _cnn_sim()
    ref = _first_grads(sim, draw)
    assert sim.grad_chunk_rows == {("flat", ROWS): ROWS}
    monkeypatch.setattr(cefedavg, "grad_chunk_rows",
                        lambda rows, row_bytes, limit: c)
    got = _first_grads(sim, draw)
    assert sim.grad_chunk_rows == {("flat", ROWS): c}
    assert np.abs(ref).max() > 0
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_rule_takes_all_rows_without_a_limit():
    """No limit reported (the CPU backend): one pass over every row, so
    the program is the all-rows ``vmap``."""
    assert cefedavg.device_bytes_limit() is None
    assert grad_chunk_rows(64, 46_310_066, None) == 64
    assert grad_chunk_rows(64, 10, V5E_BYTES_LIMIT) == 64
    assert grad_chunk_rows(6, 10 ** 12, V5E_BYTES_LIMIT) == 1


def test_rule_gives_eight_rows_for_the_paper_cnn_on_a_v5e():
    """The paper CNN at batch 50 keeps about 46 MB of residuals a row;
    a 15.75 GiB limit takes 8 rows a pass, of 64 or of 72."""
    S = jax.ShapeDtypeStruct
    layout = cefedavg.FlatLayout.for_tree(
        init_femnist_cnn(jax.random.PRNGKey(0)))

    def loss_row(row, x, y):
        logits = apply_femnist_cnn(layout.unflatten_one(row), x)
        return jnp.mean(jax.nn.logsumexp(logits, -1)
                        - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])
    r = cefedavg.row_residual_bytes(
        loss_row, S((layout.total,), jnp.float32),
        S((50, 28, 28, 1), jnp.float32), S((50,), jnp.int32))
    assert 40e6 < r < 50e6
    assert grad_chunk_rows(64, r, V5E_BYTES_LIMIT) == 8
    # the population cell's slab (64 clients, 8 representatives): 9
    # rows would fit too, but 8 fill whole tiles
    assert grad_chunk_rows(72, r, V5E_BYTES_LIMIT) == 8
