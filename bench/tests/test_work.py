"""Useful-work functions against hand counts at a tiny size."""
import pytest

from bench import work
from bench.work import kernels

# two blocks, outermost first
COUNTS = [{"seeds": 2, "next": 3, "edges": 4},
          {"seeds": 3, "next": 5, "edges": 6}]


def test_spmm_and_edge_softmax_counts():
    assert kernels.spmm(rows=2, edges=3, feats=4) == (12, 4 * (12 + 3 + 8))
    assert kernels.edge_softmax(rows=2, edges=3, heads=4) == (
        48, 4 * (12 + 3 + 16))


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 10.0, "hbm_bytes_per_s": 100.0}
    assert kernels.roofline_seconds((50, 100), peak) == 5.0
    assert kernels.roofline_seconds((5, 1000), peak) == 10.0


def test_gcn_step_flops_by_hand():
    cfg = {"hidden": 4, "graph": {"num_features": 3, "num_classes": 2}}
    gcn = work.load_model("gcn")
    # outer block: F 4 -> 2, S 2, E 4, input needs a gradient
    outer = (2 * 4 * 4 + 4 * 2 * 4 * 2) + 4 * 2 * 4 * 2 \
        + (4 * 2 * 4 * 2 + 2 * 4 * 4)
    # deepest block: F 3 -> 4, S 3, E 6, input is the features
    deep = (2 * 6 * 3 + 4 * 3 * 3 * 4) + 4 * 3 * 3 * 4
    assert gcn.step_flops(cfg, COUNTS) == outer + deep
    assert gcn.spmm_calls(cfg, COUNTS) == [(2, 4, 4), (3, 4, 4), (3, 6, 3)]


def test_gatv2_step_flops_by_hand():
    cfg = {"hidden": 4, "heads": 2, "last_layer_heads": 1,
           "graph": {"num_features": 3, "num_classes": 2}}
    gat = work.load_model("gatv2")
    # outer block: layer 1, 1 head of 2 from width 4; S 2, T 3, E 4
    proj, edge = 2 * (2 + 3) * 4 * 2, 5 * 4 * 2 + 4 * 4 * 1
    outer = 3 * proj + 3 * edge
    # deepest block: layer 0, 2 heads of 2 from width 3; S 3, T 5, E 6
    proj, edge = 2 * (3 + 5) * 3 * 4, 5 * 6 * 4 + 4 * 6 * 2
    deep = 2 * proj + 3 * edge
    assert gat.step_flops(cfg, COUNTS) == outer + deep
    assert gat.edge_softmax_calls(cfg, COUNTS) == [(2, 4, 1), (3, 6, 2)]


def test_peaks_refuse_an_unlisted_device():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
