"""The benchmark's DC-SBM graphs: published sizes, and the cache."""
import json
import os

import numpy as np

from bench import graph
from conftest import ROOT


def _spec(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)["graph"]


def test_flickr_has_the_published_size_and_width():
    spec = _spec("gatv2-flickr")
    g = graph.build(spec)
    assert g.num_vertices == 89_250
    # duplicate edges are dropped after the draw: within 1% of 10.09
    assert abs(g.num_edges / g.num_vertices - 10.09) < 0.01 * 10.09
    assert g.indptr[0] == 0 and g.indptr[-1] == g.num_edges
    assert np.all(np.diff(g.indptr) >= 0)
    assert g.indices.min() >= 0 and g.indices.max() < g.num_vertices
    assert g.train_idx.shape[0] == int(0.5 * 89_250)
    assert set(np.unique(g.labels)) <= set(range(7))
    feats = graph.device_features(spec, g.labels)
    assert feats.shape == (89_250, 500) and str(feats.dtype) == "float32"


def test_products_degrees_have_the_published_size_and_mean():
    spec = _spec("gcn-products")
    deg = graph.in_degrees(spec, np.random.default_rng(spec["graph_seed"]))
    assert deg.shape == (2_449_029,)
    assert abs(deg.mean() - 25.26) < 0.01 * 25.26
    assert spec["num_features"] == 100


def test_community_draws_keep_most_edges_inside_a_community():
    spec = dict(_spec("gcn-products"), num_vertices=20_000)
    g = graph.build(spec)
    dst = np.repeat(np.arange(g.num_vertices), np.diff(g.indptr))
    same = np.mean(g.labels[dst] == g.labels[g.indices])
    # 75% drawn inside the community, plus global draws that land there
    assert 0.75 <= same <= 0.9


def test_cache_round_trip_gives_the_same_graph(tmp_path):
    spec = dict(_spec("gatv2-flickr"), num_vertices=2_000)
    built = graph.load_or_build("flickr-small", spec, str(tmp_path))
    loaded = graph.load_or_build("flickr-small", spec, str(tmp_path))
    for a, b in zip(built, loaded):
        np.testing.assert_array_equal(a, b)
    assert len(os.listdir(tmp_path)) == 1
