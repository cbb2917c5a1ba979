"""Compile the main path for a described TPU v5e (no chip needed).

The TPU compiler is installed beside the CPU backend, so XLA and Mosaic can
compile for a v5e that is described, not attached.  That catches what
interpret mode cannot: a kernel block layout Mosaic refuses, or a program
that does not fit the chip's 15.75 GB of HBM.  Sizes are real:

* enron-like (``configs/truss_paper.py``): ``powerlaw_graph(36692, 5)``
  gives 208,475 edges; the service's capacities are then E_cap = 416,950,
  D_max = 2,286 and W = 1,147 bitmap words;
* the node-partitioned engine's gather chunk ``[8192, W/S]`` at the
  million-edge tier's W = 1,024 and S in {1, 4};
* the million-edge tier itself (``powerlaw_graph(32768, 32,
  max_degree=1024)``: 1,049,255 edges), replicated on one chip and
  node-partitioned over the 2x2 mesh.

The topology is described inside a fixture (never at import), the
persistent compilation cache is off around the compiles, and the backend
probe of ``kernels.ops`` is patched so the peel engine dispatches the
compiled kernel even though JAX's default backend here is the CPU.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.graph import GraphSpec, GraphState
from repro.core.peel import peel
from repro.kernels import ops
from repro.kernels.bitmap_support import bitmap_support_kernel
from repro.kernels.peel_wave import peel_wave_kernel

HBM_BYTES = 15.75 * 2**30      # what XLA may place on one v5e chip
ENRON = GraphSpec(n_nodes=36692, d_max=2286, e_cap=416950)
MILLION = GraphSpec(n_nodes=32768, d_max=1024, e_cap=1049255)
KERNEL_SHAPES = [
    (ENRON.e_cap, ENRON.n_words),   # replicated bitmap rows, enron-like
    (8192, 1024),                   # partitioned gather chunk, S = 1
    (8192, 256),                    # partitioned gather chunk, S = 4
    (100, 3),                       # tiny: whole-array blocks
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, compiled.memory_analysis()


def _hbm_bytes(mem) -> int:
    return (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)


@pytest.mark.parametrize("e,w", KERNEL_SHAPES)
@pytest.mark.parametrize("kernel", ["peel_wave", "bitmap_support"])
def test_kernel_compiles_for_v5e(one_chip, kernel, e, w):
    rows = jax.ShapeDtypeStruct((e, w), jnp.uint32, sharding=one_chip)
    if kernel == "peel_wave":
        alive = jax.ShapeDtypeStruct((e,), jnp.bool_, sharding=one_chip)
        k = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled, mem = _compile(peel_wave_kernel, rows, rows, alive, k)
    else:
        compiled, mem = _compile(bitmap_support_kernel, rows, rows)
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel reads the rows in place: no padded copy of [E, W]
    assert mem.temp_size_in_bytes < e * 4 * 4 + 2**20


def _state(spec, edge_rows, edge_table=None, nodes=None):
    """GraphState shapes: edge-indexed arrays on ``edge_rows`` (the edge
    table on ``edge_table``), node tables on ``nodes`` (default: the
    same placement)."""
    n, d, e = spec.n_nodes, spec.d_max, spec.e_cap

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    table, node = edge_table or edge_rows, nodes or edge_rows
    return GraphState(s((e, 2), jnp.int32, table),
                      s((e,), jnp.bool_, edge_rows),
                      s((e,), jnp.int32, edge_rows),
                      s((n, d), jnp.int32, node), s((n, d), jnp.int32, node),
                      s((n,), jnp.int32, node))


def test_sorted_peel_fits_v5e_at_enron_scale(one_chip):
    """The default served engine (sorted support, recompute waves): its
    whole-edge-axis support pass runs in degree-class blocks, so no
    [E_cap, D_max] transient materialises."""
    st = _state(ENRON, one_chip)
    compiled, mem = _compile(
        lambda st: peel(ENRON, st, st.active, method="sorted"), st)
    assert _hbm_bytes(mem) < HBM_BYTES, mem


def test_bitmap_delta_peel_with_kernel_fits_v5e_at_enron_scale(
        one_chip, monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    st = _state(ENRON, one_chip)
    bitmap = jax.ShapeDtypeStruct((ENRON.n_nodes, ENRON.n_words),
                                  jnp.uint32, sharding=one_chip)
    compiled, mem = _compile(
        lambda st, bm: peel(ENRON, st, st.active, bitmap=bm,
                            method="bitmap", engine="delta"), st, bitmap)
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(mem) < HBM_BYTES, mem


def test_replicated_bitmap_peel_fits_v5e_at_million_edges(one_chip,
                                                          monkeypatch):
    """The one-chip reference of ``chip_smoke.py --chips 4``: the
    replicated delta peel gathers two [E, W] row blocks per wave."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    st = _state(MILLION, one_chip)
    bitmap = jax.ShapeDtypeStruct((MILLION.n_nodes, MILLION.n_words),
                                  jnp.uint32, sharding=one_chip)
    compiled, mem = _compile(
        lambda st, bm: peel(MILLION, st, st.active, bitmap=bm,
                            method="bitmap", engine="delta"), st, bitmap)
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(mem) < HBM_BYTES, mem


def test_partitioned_bitmap_peel_compiles_for_v5e_2x2(topo, one_chip,
                                                      monkeypatch):
    """``chip_smoke.py --chips 4``: the node-partitioned delta peel over the
    2x2 mesh, one bitmap word slab per chip and one psum per wave."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.graph import with_mesh

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("shard",))
    spec = with_mesh(MILLION, mesh, partition="nodes")
    st = _state(spec, NamedSharding(mesh, P("shard")),
                NamedSharding(mesh, P("shard", None)),
                NamedSharding(mesh, P()))
    bitmap = jax.ShapeDtypeStruct((spec.n_nodes, spec.n_words), jnp.uint32,
                                  sharding=NamedSharding(mesh,
                                                         P(None, "shard")))
    compiled, mem = _compile(
        lambda st, bm: peel(spec, st, st.active, bitmap=bm, method="bitmap",
                            engine="delta", mesh=mesh), st, bitmap)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert _hbm_bytes(mem) < HBM_BYTES, mem   # bytes per device
