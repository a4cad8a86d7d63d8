import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._jaxpr import eqns

from repro.graph import PAPER_DATASETS, from_coo, generate, paper_dataset, reverse
from repro.graph.csr import Graph, expand_seed_edges
from repro.graph.partition import partition_graph, partition_features
from repro.core.interface import pad_seeds


def test_from_coo_and_degrees():
    src = np.array([1, 2, 3, 1, 0])
    dst = np.array([0, 0, 0, 2, 2])
    g = from_coo(src, dst, 4)
    assert g.num_vertices == 4 and g.num_edges == 5
    np.testing.assert_array_equal(np.asarray(g.degrees()), [3, 0, 2, 0])
    g.validate()
    # in-neighbors of 0 are {1,2,3}
    nbrs = np.asarray(g.indices[g.indptr[0]:g.indptr[1]])
    assert set(nbrs.tolist()) == {1, 2, 3}


def test_from_coo_dedup():
    g = from_coo(np.array([1, 1, 1]), np.array([0, 0, 0]), 2)
    assert g.num_edges == 1


def test_reverse_roundtrip():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 50, 200)
    dst = rng.integers(0, 50, 200)
    g = from_coo(src, dst, 50)
    g2 = reverse(reverse(g))
    np.testing.assert_array_equal(np.asarray(g.indptr), np.asarray(g2.indptr))
    np.testing.assert_array_equal(np.asarray(g.indices), np.asarray(g2.indices))


def test_reverse_preserves_weights():
    """Regression: reverse() used to drop graph.weights on the COO
    round-trip, silently turning a weighted graph uniform."""
    rng = np.random.default_rng(3)
    src = rng.integers(0, 30, 120)
    dst = rng.integers(0, 30, 120)
    w = rng.uniform(0.1, 2.0, 120).astype(np.float32)
    g = from_coo(src, dst, 30, weights=w)
    gr = reverse(g)
    assert gr.weights is not None
    assert gr.num_edges == g.num_edges
    # weight of reversed edge (s -> t) equals weight of original (t -> s)
    def edge_weights(graph):
        indptr = np.asarray(graph.indptr)
        indices = np.asarray(graph.indices)
        ws = np.asarray(graph.weights)
        out = {}
        for v in range(graph.num_vertices):
            for e in range(indptr[v], indptr[v + 1]):
                out[(int(indices[e]), v)] = float(ws[e])
        return out
    fwd = edge_weights(g)
    rev = edge_weights(gr)
    assert rev == {(d, s): w for (s, d), w in fwd.items()}
    # double reverse is the identity, weights included
    g2 = reverse(gr)
    np.testing.assert_array_equal(np.asarray(g.indptr), np.asarray(g2.indptr))
    np.testing.assert_array_equal(np.asarray(g.indices), np.asarray(g2.indices))
    np.testing.assert_allclose(np.asarray(g.weights), np.asarray(g2.weights))


def test_expand_seed_edges_matches_numpy():
    rng = np.random.default_rng(1)
    src = rng.integers(0, 40, 300)
    dst = rng.integers(0, 40, 300)
    g = from_coo(src, dst, 40)
    seeds = pad_seeds(jnp.asarray([3, 7, 0, 39]), 8)
    exp = expand_seed_edges(g, seeds, 256)
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    got = {}
    m = np.asarray(exp["mask"])
    for sl, sr in zip(np.asarray(exp["seed_slot"])[m], np.asarray(exp["src"])[m]):
        got.setdefault(int(sl), []).append(int(sr))
    for slot, s in enumerate([3, 7, 0, 39]):
        expect = indices[indptr[s]:indptr[s + 1]].tolist()
        assert sorted(got.get(slot, [])) == sorted(expect)
    assert int(exp["total"]) == sum(
        indptr[s + 1] - indptr[s] for s in [3, 7, 0, 39])


def test_expand_overflow_detected():
    g = from_coo(np.arange(30), np.zeros(30, np.int64), 31)
    seeds = pad_seeds(jnp.asarray([0]), 1)
    exp = expand_seed_edges(g, seeds, 16)
    assert int(exp["total"]) == 30  # caller compares against cap


def _np_expand(indptr, indices, weights, seeds, rows, edge_cap):
    """Segment-contiguous expansion, seed by seed, truncated at the cap."""
    deg = np.array([indptr[r + 1] - indptr[r] if s >= 0 else 0
                    for s, r in zip(seeds, rows)], np.int64)
    slot = np.concatenate([np.full(d, i) for i, d in enumerate(deg)] + [[]])
    edge = np.concatenate([np.arange(indptr[r], indptr[r] + d)
                           for r, d in zip(rows, deg)] + [[]]).astype(int)
    m = min(len(edge), edge_cap)
    out = dict(seed_slot=np.full(edge_cap, -1), src=np.full(edge_cap, -1),
               mask=np.arange(edge_cap) < m, seg_start=np.cumsum(deg) - deg,
               deg=deg, total=deg.sum(), edge_weight=None)
    out["seed_slot"][:m] = slot[:m]
    out["src"][:m] = indices[edge[:m]]
    if weights is not None:
        out["edge_weight"] = np.zeros(edge_cap, np.float32)
        out["edge_weight"][:m] = weights[edge[:m]]
    return out


# in-degree 0 for every vertex divisible by 3
_V = 48
_rng = np.random.default_rng(5)
_DST = _rng.choice([v for v in range(_V) if v % 3], 400)
_SRC = _rng.integers(0, _V, 400)


@pytest.mark.parametrize("case", [
    "interleaved_zero_degree", "leading_zero_degree", "all_padding",
    "seed_rows", "weighted", "overflow"])
def test_expand_seed_edges_cases(case):
    """Every output equals a numpy expansion; on overflow the buffers
    hold the exact first ``edge_cap`` edges of the layout."""
    weights = (np.random.default_rng(6).uniform(0.1, 2.0, 400)
               .astype(np.float32) if case == "weighted" else None)
    g = from_coo(_SRC, _DST, _V, weights=weights)
    seeds = {"interleaved_zero_degree": [1, 3, -1, 2, 6, 9, 4, -1, 5],
             "leading_zero_degree": [0, 3, 6, 7, 2, 9, 1],
             "all_padding": [-1] * 6,
             "seed_rows": [5, 13, 3, 31, -1, 1, 47],
             "weighted": [4, 0, 8, 10, 1],
             "overflow": [2, 0, 7, 11, 3, 4, 5]}[case]
    seeds = np.asarray(seeds, np.int32)
    edge_cap = 36 if case == "overflow" else 256
    rows = None
    if case == "seed_rows":
        # partition 1 of 2: odd global seeds, local rows v // 2
        g = partition_graph(g, 2).part_graph(1)
        rows = np.where(seeds >= 0, seeds // 2, 0).astype(np.int32)
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    row_ids = np.where(seeds >= 0, seeds if rows is None else rows, 0)
    exp = expand_seed_edges(g, jnp.asarray(seeds), edge_cap,
                            seed_rows=None if rows is None
                            else jnp.asarray(rows))
    want = _np_expand(indptr, indices, None if weights is None
                      else np.asarray(g.weights), seeds, row_ids, edge_cap)
    if case == "overflow":
        # the cap cuts a segment, and whole segments lie beyond it
        assert edge_cap not in want["seg_start"]
        assert want["seg_start"][-2] > edge_cap
    for k, v in want.items():
        if v is None:
            assert exp[k] is None, k
        else:
            np.testing.assert_array_equal(np.asarray(exp[k]), v, err_msg=k)


@pytest.mark.parametrize("weighted", [False, True])
def test_expand_trace_has_one_buffer_sized_gather(weighted):
    """Per-seed values reach their segments by a forward fill: at a
    layer-2-like shape the only gathers with an ``edge_cap``-sized
    output read the edge data (indices, and weights when present)."""
    S, edge_cap = 2**15, 2**18
    V, E = 100_003, 3_000_017
    g = Graph(indptr=jax.ShapeDtypeStruct((V + 1,), jnp.int32),
              indices=jax.ShapeDtypeStruct((E,), jnp.int32),
              weights=jax.ShapeDtypeStruct((E,), jnp.float32)
              if weighted else None)
    closed = jax.make_jaxpr(functools.partial(
        expand_seed_edges, edge_cap=edge_cap))(
        g, jax.ShapeDtypeStruct((S,), jnp.int32))
    big = [e for e in eqns(closed.jaxpr) if e.primitive.name == "gather"
           and any(edge_cap in getattr(v.aval, "shape", ())
                   for v in e.outvars)]
    assert len(big) == (2 if weighted else 1), [
        v.aval for e in big for v in e.outvars]


def test_generator_stats_match_spec():
    ds = paper_dataset("products", scale=0.01, seed=0)
    g = ds.graph
    avg = g.num_edges / g.num_vertices
    assert abs(avg - PAPER_DATASETS["products"].avg_degree) / 25.26 < 0.25
    assert ds.features.shape == (g.num_vertices, 100)
    assert ds.labels.max() < PAPER_DATASETS["products"].num_classes
    # splits are disjoint and cover V
    tot = np.concatenate([ds.train_idx, ds.val_idx, ds.test_idx])
    assert np.unique(tot).size == g.num_vertices


def test_generator_skew():
    """Controlled: same size/avg-degree, different skew knob -> heavier
    degree tail (the quantity LABOR's gains depend on)."""
    from repro.graph.generators import DatasetSpec

    def tail_ratio(skew):
        spec = DatasetSpec("t", 4000, 20.0, 8, 5, 0.5, 0.2, skew, 100)
        ds = generate(spec, seed=0)
        deg = np.diff(np.asarray(ds.graph.indptr))
        return np.sort(deg)[-max(len(deg) // 100, 1):].sum() / deg.sum()

    assert tail_ratio(0.9) > tail_ratio(0.1)


def test_partition_graph_reassembles():
    rng = np.random.default_rng(2)
    src = rng.integers(0, 64, 500)
    dst = rng.integers(0, 64, 500)
    g = from_coo(src, dst, 64)
    pg = partition_graph(g, 4)
    edges = set()
    for p in range(4):
        gp = pg.part_graph(p)
        indptr = np.asarray(gp.indptr)
        for loc in range(pg.local_counts[p]):
            glob_dst = pg.global_id(p, loc)
            for t in np.asarray(gp.indices)[indptr[loc]:indptr[loc + 1]]:
                edges.add((int(t), int(glob_dst)))
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    expect = set()
    for v in range(64):
        for t in indices[indptr[v]:indptr[v + 1]]:
            expect.add((int(t), v))
    assert edges == expect


def test_partition_features_layout():
    f = np.arange(20, dtype=np.float32).reshape(10, 2)
    pf = partition_features(f, 4)
    assert pf.shape == (4, 3, 2)
    np.testing.assert_array_equal(pf[1, 0], f[1])  # owner(v)=v%P, row v//P
    np.testing.assert_array_equal(pf[3, 1], f[7])
