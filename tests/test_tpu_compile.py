"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip. Each test lowers a kernel at the width the
system runs it and compiles it with the TPU compiler for one chip of a
described ``v5e:2x2`` topology: the compiler refuses what Mosaic cannot
lower (an unaligned slice, an f16 cast inside a kernel, too much VMEM)
and what does not fit the chip's memory, which interpret mode never
shows. The compiled text must also keep the names a profiler trace is
read by: the kernels' ``name=`` and the ``op_name`` metadata of the
round's named scopes (``local_steps``, ``mix``, ``page_in``,
``page_out``). Shapes:

- ``gossip_mix_rows`` at the paper cell's bank, n = 64 rows of the
  FEMNIST CNN (T = 6,603,710): the square mixing operator after the
  local steps' scan (``FLSimulator._train_scan``, a stand-in step) and
  the (64 -> 8) edge-model projection;
- the flat round's local steps at the paper cell's bank, with the
  per-row gradients in the row chunks the chip's memory limit gives
  (``core/cefedavg.py`` ``grad_chunk_rows``), at ``highest``: no
  ``.remat`` instruction, the working set well inside the chip;
- the pipelined streamed round's page-in/page-out jits (``paging_fns``: the
  ``cold_codec`` decode/encode) at the population cell's wide MLP
  (64 -> 2048 -> 32, T = 198,688) on a 16-row slab, int8 and f16, and
  the codec row-sharded over the four chips (the sharded streamed
  engine's slab).

The topology is described inside a module fixture, never at import:
the TPU library may be loaded by one process at a time, and only the
worker that runs this file loads it.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs import femnist_cnn
from repro.core import cefedavg
from repro.core.cefedavg import FLSimulator, paging_fns
from repro.core.program import LocalSteps
from repro.kernels import cold_codec
from repro.kernels.gossip_mix import FlatLayout, gossip_mix_rows
from repro.models.cnn import (apply_femnist_cnn, init_femnist_cnn,
                              init_mlp_classifier)

#: HBM of one TPU v5e chip
V5E_HBM_BYTES = 16 * 2 ** 30
#: the memory limit a TPU v5e chip reports usable (15.75 GiB)
V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)
N_PAPER = 64
SLAB_ROWS = 16


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mlp_segments():
    one = init_mlp_classifier(jax.random.PRNGKey(0), 64, 2048, 32)
    layout = FlatLayout.for_tree(one)
    assert layout.total == 198_688
    return layout.segments


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _peak_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _op_names(hlo: str):
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _has_scope(hlo: str, scope: str) -> bool:
    return any(scope in name.split("/") for name in _op_names(hlo))


def _local_step(carry, key):
    """A stand-in for the SGD-momentum step over the bank rows."""
    Y, M = carry
    M = 0.9 * M + Y
    return (Y - 0.1 * M, M), None


@pytest.mark.parametrize("k", [N_PAPER, 8], ids=["square", "edge_projection"])
def test_gossip_mix_flat_compiles_at_paper_width(one_chip, k):
    """The mixing kernel at the paper cell's (64, 6,603,710) bank; the
    square operator mixes the bank the local steps' scan left."""
    T = femnist_cnn.PARAMS
    S = jax.ShapeDtypeStruct
    W = S((k, N_PAPER), jnp.float32, sharding=one_chip)
    Y = S((N_PAPER, T), jnp.float32, sharding=one_chip)
    key = S((2,), jnp.uint32, sharding=one_chip)
    if k == N_PAPER:
        def fn(W, Y, key):
            Y, _ = FLSimulator._train_scan(_local_step, Y, Y, key,
                                           LocalSteps(tau=2))
            return gossip_mix_rows(W, Y, use_pallas=True)
    else:
        def fn(W, Y, key):
            return gossip_mix_rows(W, Y, use_pallas=True)
    compiled = _compile(fn, W, Y, key)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert re.search(r"%gossip_mix\.\d+ = ", hlo)
    assert _has_scope(hlo, "mix")
    assert _has_scope(hlo, "local_steps") == (k == N_PAPER)
    assert _peak_bytes(compiled) < V5E_HBM_BYTES


def test_local_steps_compile_without_remat_at_paper_width(one_chip,
                                                         monkeypatch):
    """Two local steps of the paper cell (64 rows, batch 50, the FEMNIST
    CNN at ``highest``) with the row chunks the rule picks for a v5e:
    XLA rematerialises nothing, and the temporaries stay under 6 GB
    (the all-rows ``vmap`` took 14.1 GB and rematerialised six
    instructions)."""
    n, N, image = N_PAPER, 64, femnist_cnn.IMAGE
    # a streamed simulator holds no (64, T) bank on the host; its
    # local-step factory is the flat round's
    data = {"xs": np.zeros((n, N) + image, np.float32),
            "ys": np.zeros((n, N), np.int32)}
    sim = FLSimulator(init_femnist_cnn, apply_femnist_cnn, femnist_cnn.FL,
                      data, batch_size=50, streaming=True)
    # this process's backend is the CPU: state the chip's limit
    monkeypatch.setattr(cefedavg, "device_bytes_limit",
                        lambda: V5E_BYTES_LIMIT)
    make_local_step = sim._flat_helpers("flat")

    def fn(Y, M, key, xs, ys):
        step = make_local_step(xs, ys, jnp.ones((n, 1), bool))
        return FLSimulator._train_scan(step, Y, M, key, LocalSteps(tau=2))
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bank = S((n, femnist_cnn.PARAMS), jnp.float32)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(
            bank, bank, S((2,), jnp.uint32),
            S((n, N) + image, jnp.float32), S((n, N), jnp.int32)).compile()
    assert sim.grad_chunk_rows == {("flat", n): 8}
    hlo = compiled.as_text()
    assert ".remat" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9
    assert _has_scope(hlo, "local_steps") and _has_scope(hlo, "row_grads")


@pytest.mark.parametrize("codec", ["int8", "f16"])
def test_cold_codec_compiles_at_population_width(one_chip, mlp_segments,
                                                 codec, monkeypatch):
    """Device decode + encode of a wide-MLP slab, inside the pipelined
    streamed round's page-in and page-out jits. int8 runs the Pallas absmax/
    affine kernels; f16 is an XLA cast that Mosaic never sees."""
    T = 198_688
    m = 8
    nseg = len(mlp_segments) if codec == "int8" else 0
    qdt = jnp.int8 if codec == "int8" else jnp.float16
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows = S((SLAB_ROWS, T), jnp.float32)
    q = S((SLAB_ROWS, T), qdt)
    scale = S((SLAB_ROWS, nseg), jnp.float32)
    lanes = S((SLAB_ROWS,), jnp.int32)
    refs = S((m, T), jnp.float32)
    # this process's backend is the CPU: take the chip's kernels
    monkeypatch.setattr(cold_codec, "_use_pallas", lambda _: True)
    pre, post = paging_fns(codec, mlp_segments)
    dec = pre.lower(refs, lanes, q, scale, q, scale, lanes, lanes).compile()
    enc = post.lower(rows, rows, refs, S((m,), jnp.bool_),
                     S((m,), jnp.int32)).compile()
    kernels = {"int8": ({"cold_dequantize"}, {"cold_absmax",
                                               "cold_quantize"}),
               "f16": (set(), set())}[codec]
    for compiled, scope, names in ((dec, "page_in", kernels[0]),
                                   (enc, "page_out", kernels[1])):
        hlo = compiled.as_text()
        assert ("tpu_custom_call" in hlo) == (codec == "int8")
        assert _has_scope(hlo, scope)
        for name in names:
            assert re.search(rf"%{name}(\.\d+)? = ", hlo), name
        assert _peak_bytes(compiled) < V5E_HBM_BYTES
    out_q, out_s = jax.eval_shape(lambda r: cold_codec.encode_rows(
        r, codec, mlp_segments, use_pallas=True), rows)
    assert out_q.dtype == np.dtype(qdt) and out_s.shape == scale.shape


def test_cold_codec_compiles_row_sharded_on_four_chips(topo, mlp_segments):
    """``row_codec`` over a slab row-sharded on the 2x2 mesh: each chip
    runs the int8 kernels on its own rows (an unwrapped Pallas kernel
    would be refused: Mosaic kernels cannot be auto-partitioned)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    enc, dec = cold_codec.row_codec("int8", mlp_segments, rows,
                                    use_pallas=True)
    S = jax.ShapeDtypeStruct
    slab = (SLAB_ROWS, 198_688)
    for compiled in (
            _compile(enc, S(slab, jnp.float32, sharding=rows)),
            _compile(dec, S(slab, jnp.int8, sharding=rows),
                     S((SLAB_ROWS, len(mlp_segments)), jnp.float32,
                       sharding=rows))):
        assert "tpu_custom_call" in compiled.as_text()
        assert _peak_bytes(compiled) < V5E_HBM_BYTES
