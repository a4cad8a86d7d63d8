"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: scalar stores to VMEM, 1-D in-kernel gathers,
unsupported shape casts, VMEM overuse. These tests lower and compile
each kernel for a described (not attached) v5e chip at the widths of
the products training configuration (batch 1024, fanouts 10,10,10):
the frontier primitives as the ``"pallas"`` backend dispatches them,
at the largest sampling layer, and the SpMM / gather / edge-softmax
kernels on that layer's block.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.interface import suggest_caps
from repro.graph.generators import PAPER_DATASETS
from repro.kernels.edge_softmax.ops import edge_softmax_block
from repro.kernels.spmm.ops import gather_dst_block, spmm_block
from repro.ops import pallas as pallas_backend

BATCH, FANOUTS, GAT_HEADS = 1024, (10, 10, 10), 8
# the largest block feeds the first model layer: raw features in
FEATURES = PAPER_DATASETS["products"].num_features


def _products_caps():
    """The cap schedule ``from_graph_stats`` gives products, with the
    generator's in-degree bound standing in for the sampled graph's
    max in-degree."""
    spec = PAPER_DATASETS["products"]
    n, avg = spec.num_vertices, spec.avg_degree
    max_degree = int(min(n - 1, max(4 * avg, avg * n ** 0.33)))
    return suggest_caps(BATCH, FANOUTS, avg, max_degree, safety=2.0,
                        num_vertices=n, num_edges=int(n * avg))


CAPS = _products_caps()
SEEDS = CAPS[-2].vertex_cap          # seeds of the largest layer
LAST = CAPS[-1]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(one_chip, monkeypatch):
    """Compile ``fn`` over shapes on the described chip; returns the
    HLO text. The backend's kernels are compiled, not interpreted."""
    monkeypatch.setattr(pallas_backend, "interpret_mode", lambda: False)
    # a compile for a described chip cannot be read back from the cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


I32, F32, BOOL = jnp.int32, jnp.float32, jnp.bool_
E_EXP, E_EDGE = LAST.expand_cap, LAST.edge_cap

FRONTIER = {
    "hash_dedup": (
        lambda v, m, s: pallas_backend.hash_dedup(
            v, m, s, LAST.vertex_cap - SEEDS),
        ((E_EDGE,), I32), ((E_EDGE,), BOOL), ((SEEDS,), I32)),
    "compact": (
        lambda f: pallas_backend.compact(f, LAST.edge_cap),
        ((E_EXP,), BOOL)),
    "compact_perm": (
        lambda k, v: pallas_backend.compact_perm(k, v, LAST.vertex_cap),
        ((E_EDGE,), I32), ((E_EDGE,), BOOL)),
    "segment_select": (
        lambda k, sl, m, st, t: pallas_backend.segment_select(
            k, sl, m, st, t, SEEDS, FANOUTS[-1]),
        ((E_EXP,), F32), ((E_EXP,), I32), ((E_EXP,), BOOL),
        ((SEEDS,), I32), ((SEEDS,), I32)),
    "masked_cdf_draw": (
        pallas_backend.masked_cdf_draw,
        ((E_EXP,), F32), ((E_EXP,), BOOL), ((BATCH * FANOUTS[-1],), F32)),
}


@pytest.mark.parametrize("primitive", sorted(FRONTIER))
def test_frontier_primitive_compiles_for_v5e(primitive, compiled):
    fn, *shapes = FRONTIER[primitive]
    assert "tpu_custom_call" in compiled(fn, *shapes)


def test_spmm_and_gather_dst_compile_for_v5e(compiled):
    edge = (((E_EDGE,), I32), ((E_EDGE,), I32), ((E_EDGE,), F32),
            ((E_EDGE,), BOOL))
    hlo = compiled(lambda s, d, w, m, h: spmm_block(s, d, w, m, h, SEEDS),
                   *edge, ((LAST.vertex_cap, FEATURES), F32))
    assert "tpu_custom_call" in hlo
    hlo = compiled(gather_dst_block, ((E_EDGE,), I32), ((E_EDGE,), BOOL),
                   ((SEEDS, FEATURES), F32))
    assert "tpu_custom_call" in hlo


def test_edge_softmax_compiles_for_v5e(compiled):
    hlo = compiled(lambda d, m, lg: edge_softmax_block(d, m, lg, SEEDS),
                   ((E_EDGE,), I32), ((E_EDGE,), BOOL),
                   ((E_EDGE, GAT_HEADS), F32))
    assert "tpu_custom_call" in hlo


# kernel -> the op-path ending its Pallas call must carry on the chip:
# the frontier sorts and the gather by their kernel names; the SpMM and
# edge-softmax kernels directly under their jitted wrappers, the paths
# the benchmark's roofline readers match
_E, _S, _F = 4096, 256, 128
KERNEL_PATHS = {
    "frontier_sort": (
        lambda v, m, s: pallas_backend.hash_dedup(v, m, s, 2 * _S),
        "/frontier_sort_blocks/pallas_call",
        ((_E,), I32), ((_E,), BOOL), ((_S,), I32)),
    "gather_rows_sorted": (
        gather_dst_block, "/gather_rows_sorted/pallas_call",
        ((_E,), I32), ((_E,), BOOL), ((_S, _F), F32)),
    "spmm_sorted": (
        lambda s, d, w, m, h: spmm_block(s, d, w, m, h, _S),
        "jit(spmm_sorted)/pallas_call",
        ((_E,), I32), ((_E,), I32), ((_E,), F32), ((_E,), BOOL),
        ((2 * _S, _F), F32)),
    "edge_softmax_stats": (
        lambda d, m, lg: edge_softmax_block(d, m, lg, _S),
        "jit(edge_softmax_stats)/pallas_call",
        ((_E,), I32), ((_E,), BOOL), ((_E, GAT_HEADS), F32)),
}


@pytest.mark.parametrize("kernel", sorted(KERNEL_PATHS))
def test_kernel_op_path_on_v5e(kernel, compiled):
    from bench.trace import op_paths
    fn, ending, *shapes = KERNEL_PATHS[kernel]
    paths = op_paths(compiled(fn, *shapes)).values()
    assert any(p.endswith(ending) for p in paths), sorted(
        p for p in paths if p.endswith("pallas_call"))
