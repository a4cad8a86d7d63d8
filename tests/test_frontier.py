"""Frontier primitives (repro/ops/frontier + repro/kernels/frontier).

Four layers of checks:

  * primitive-level parity — each primitive against its dense/numpy
    oracle and the Pallas interpret-mode kernel against the XLA
    reference, across shapes, cap ratios, and duplicate densities
    (plain randomized sweeps plus hypothesis property tests);
  * the table-full → overflow-flag path (a forced tiny hash table must
    flag, never hang or corrupt the non-contractual outputs);
  * sampler-level bit-exactness — the new O(cap) ``build_block`` /
    importance fixed point / sequential Poisson / ladies draw against
    the retained dense baselines (``build_block_dense``,
    ``_exact_k_include_dense``, ``dense=True`` modes): same inclusion
    sets, same ``next_seeds`` order, same stable ``src_perm``;
  * the acceptance criterion itself — an abstract-lowering walk over
    every registry sampler's ``sample`` jaxpr asserting NO intermediate
    buffer is sized by the vertex count (caps only).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st
from tests._jaxpr import eqns as _eqns

from repro import ops as O
from repro.core import LayerCaps, pad_seeds, samplers
from repro.core import rng as rng_lib
from repro.core.interface import build_block, build_block_dense
from repro.core.labor import (_exact_k_include, _exact_k_include_dense,
                              run_importance_iterations)
from repro.core.ladies import sample_layer_ladies
from repro.graph.csr import expand_seed_edges
from repro.graph.generators import DatasetSpec, generate
from repro.kernels.frontier import ops as frontier_kernel_ops

BACKENDS = ("xla", "pallas")


@pytest.fixture(autouse=True)
def _release_compiled_programs_per_test():
    """Nearly every case here compiles new shapes (hypothesis sweeps,
    tile-boundary grids): release them per test, not per file, or the
    file alone reaches the process's memory-map limit (see conftest)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def ds():
    return generate(DatasetSpec("mini", 3000, 14.0, 16, 5, 0.5, 0.2, 0.6,
                                1500), seed=1)


# ---------------------------------------------------------------------------
# hash_dedup
# ---------------------------------------------------------------------------

def _dedup_oracle(vals, mask, seeds, new_cap):
    """Dense-membership semantics the primitive replaces."""
    vals, mask = np.asarray(vals), np.asarray(mask)
    new = np.unique(vals[mask & (vals >= 0)])
    if seeds is not None:
        new = new[~np.isin(new, np.asarray(seeds)[np.asarray(seeds) >= 0])]
    out = np.full(new_cap, -1, np.int32)
    n = min(len(new), new_cap)
    out[:n] = new[:n]
    return out, len(new)


def _random_dedup_case(rng):
    E = int(rng.integers(4, 300))
    S = int(rng.integers(1, 50))
    new_cap = int(rng.integers(1, 80))
    id_range = int(rng.integers(4, 200))  # controls duplicate density
    vals = rng.integers(0, id_range, size=E).astype(np.int32)
    mask = rng.random(E) < 0.8
    seeds = np.unique(rng.integers(0, id_range, size=S)).astype(np.int32)
    seeds = np.concatenate([seeds, -np.ones(3, np.int32)])
    return vals, mask, seeds, new_cap


@pytest.mark.parametrize("trial", range(12))
def test_hash_dedup_vs_oracle_and_backends(trial):
    rng = np.random.default_rng(trial)
    vals, mask, seeds, new_cap = _random_dedup_case(rng)
    exp_new, exp_n = _dedup_oracle(vals, mask, seeds, new_cap)
    res = {b: O.hash_dedup(jnp.asarray(vals), jnp.asarray(mask),
                           jnp.asarray(seeds), new_cap, backend=b)
           for b in BACKENDS}
    r = res["xla"]
    np.testing.assert_array_equal(np.asarray(r.new), exp_new)
    assert int(r.num_new) == exp_n
    assert bool(r.overflow) == (exp_n > new_cap)
    # slot lookup inverts [seeds ; new]
    nxt = np.concatenate([seeds, np.asarray(r.new)])
    slots = np.asarray(r.slots)
    for e in range(len(vals)):
        if mask[e] and vals[e] >= 0 and vals[e] in nxt:
            assert nxt[slots[e]] == vals[e], e
        elif not mask[e]:
            assert slots[e] == -1, e
    # backend parity (bit-exact on the full contract when not overflowed)
    p = res["pallas"]
    assert bool(p.overflow) == bool(r.overflow)
    if not bool(r.overflow):
        np.testing.assert_array_equal(np.asarray(p.new), np.asarray(r.new))
        np.testing.assert_array_equal(np.asarray(p.slots),
                                      np.asarray(r.slots))
        assert int(p.num_new) == int(r.num_new)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_hash_dedup_property(data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    vals, mask, seeds, new_cap = _random_dedup_case(rng)
    exp_new, exp_n = _dedup_oracle(vals, mask, seeds, new_cap)
    r = O.hash_dedup(jnp.asarray(vals), jnp.asarray(mask),
                     jnp.asarray(seeds), new_cap, backend="xla")
    np.testing.assert_array_equal(np.asarray(r.new), exp_new)
    assert int(r.num_new) == exp_n


def test_hash_dedup_table_full_overflow_flag():
    """A forced tiny hash table must surface give-up through the
    overflow flag — the signal the doubled-caps replay protocol heals —
    and must never spin or crash."""
    vals = jnp.asarray(np.arange(64, dtype=np.int32))
    mask = jnp.ones((64,), bool)
    r = frontier_kernel_ops.hash_dedup_block(vals, mask, None, 64,
                                             table_cap=16, interpret=True)
    assert bool(r.overflow)
    # plenty of room: same inputs, default table — exact and flag-free
    r2 = frontier_kernel_ops.hash_dedup_block(vals, mask, None, 64,
                                              interpret=True)
    assert not bool(r2.overflow)
    np.testing.assert_array_equal(np.asarray(r2.new), np.asarray(vals))


# ---------------------------------------------------------------------------
# compact / compact_perm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trial", range(8))
def test_compact_vs_nonzero_and_backends(trial):
    rng = np.random.default_rng(100 + trial)
    E = int(rng.integers(4, 400))
    cap = int(rng.integers(1, 120))
    flags = jnp.asarray(rng.random(E) < rng.random())
    ref_sel = jnp.nonzero(flags, size=cap, fill_value=0)[0]
    outs = {b: O.compact(flags, cap, backend=b) for b in BACKENDS}
    for b in BACKENDS:
        sel, emask, num = outs[b]
        np.testing.assert_array_equal(np.asarray(sel), np.asarray(ref_sel))
        assert int(num) == int(jnp.sum(flags))
        np.testing.assert_array_equal(
            np.asarray(emask),
            np.arange(cap) < min(int(num), cap))


@pytest.mark.parametrize("trial", range(8))
def test_compact_perm_vs_argsort_and_backends(trial):
    rng = np.random.default_rng(200 + trial)
    E = int(rng.integers(4, 400))
    K = int(rng.integers(2, 60))
    keys = jnp.asarray(rng.integers(-1, K, size=E).astype(np.int32))
    valid = jnp.asarray(rng.random(E) < 0.7)
    ref = jnp.argsort(jnp.where(valid, keys, K))  # stable
    for b in BACKENDS:
        perm = O.compact_perm(keys, valid, K, backend=b)
        np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_compact_perm_property(data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    E = int(rng.integers(1, 200))
    K = int(rng.integers(1, 40))
    keys = jnp.asarray(rng.integers(-1, K, size=E).astype(np.int32))
    valid = jnp.asarray(rng.random(E) < 0.7)
    ref = jnp.argsort(jnp.where(valid, keys, K))
    perm = O.compact_perm(keys, valid, K, backend="pallas")
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref))


# ---------------------------------------------------------------------------
# segment_select
# ---------------------------------------------------------------------------

def _random_segments(rng, with_ties=True):
    S = int(rng.integers(1, 25))
    k = int(rng.integers(1, 9))
    deg = rng.integers(0, 14, size=S)
    E = int(max(deg.sum() + rng.integers(0, 10), 1))
    seg_start = (np.cumsum(deg) - deg).astype(np.int32)
    slot = np.full(E, -1, np.int32)
    keys = np.full(E, 3.4e38, np.float32)
    mask = np.zeros(E, bool)
    pos = 0
    for s in range(S):
        for _ in range(deg[s]):
            slot[pos] = s
            keys[pos] = np.float32(
                0.5 if (with_ties and rng.random() < 0.3)
                else rng.random() * 10)
            mask[pos] = True
            pos += 1
    take = np.minimum(k, deg).astype(np.int32)
    return keys, slot, mask, seg_start, deg, take, S, k


def _lexsort_oracle(keys, slot, mask, take, S):
    big = np.float32(3.4e38)
    E = len(keys)
    key_sorted = np.where(mask, np.minimum(keys, 1e30), big)
    slot_for = np.where(mask, slot, S)
    order = np.lexsort((np.arange(E), key_sorted, slot_for))
    inc = np.zeros(E, bool)
    counts = np.zeros(S + 1, np.int64)
    for e in order:
        s = slot_for[e]
        if s < S and counts[s] < take[s]:
            inc[e] = True
        counts[min(s, S)] += 1
    return inc


@pytest.mark.parametrize("trial", range(12))
def test_segment_select_vs_lexsort_and_backends(trial):
    rng = np.random.default_rng(300 + trial)
    keys, slot, mask, seg_start, deg, take, S, k = _random_segments(rng)
    exp = _lexsort_oracle(keys, slot, mask, take, S)
    for b in BACKENDS:
        inc = O.segment_select(jnp.asarray(keys), jnp.asarray(slot),
                               jnp.asarray(mask), jnp.asarray(seg_start),
                               jnp.asarray(take), S, k, backend=b)
        np.testing.assert_array_equal(np.asarray(inc), exp, err_msg=b)


def test_segment_select_take_zero_selects_none_on_both_backends():
    """take[s] == 0 on a non-empty segment must select nothing —
    including keys that are exactly 0.0 (regression: the pallas
    finalize used to clamp take to >= 1)."""
    keys = jnp.asarray([0.0, 1.0, 2.0, 0.5], jnp.float32)
    slot = jnp.asarray([0, 0, 1, 1], jnp.int32)
    mask = jnp.ones((4,), bool)
    seg_start = jnp.asarray([0, 2], jnp.int32)
    take = jnp.asarray([0, 1], jnp.int32)
    for b in BACKENDS:
        inc = O.segment_select(keys, slot, mask, seg_start, take, 2, 4,
                               backend=b)
        np.testing.assert_array_equal(np.asarray(inc),
                                      [False, False, False, True],
                                      err_msg=b)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_segment_select_property(data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    keys, slot, mask, seg_start, deg, take, S, k = _random_segments(rng)
    exp = _lexsort_oracle(keys, slot, mask, take, S)
    inc = O.segment_select(jnp.asarray(keys), jnp.asarray(slot),
                           jnp.asarray(mask), jnp.asarray(seg_start),
                           jnp.asarray(take), S, k, backend="xla")
    np.testing.assert_array_equal(np.asarray(inc), exp)


# ---------------------------------------------------------------------------
# masked_cdf_draw
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trial", range(8))
def test_masked_cdf_draw_backends_and_bounds(trial):
    rng = np.random.default_rng(400 + trial)
    C = int(rng.integers(2, 300))
    n = int(rng.integers(1, 60))
    p = np.abs(rng.normal(size=C)).astype(np.float32) * (
        10.0 ** rng.integers(-6, 6, size=C))
    valid = rng.random(C) < 0.8
    if not valid.any():
        valid[0] = True
    u = rng.random(n).astype(np.float32)
    draws = {b: np.asarray(O.masked_cdf_draw(
        jnp.asarray(p), jnp.asarray(valid), jnp.asarray(u), backend=b))
        for b in BACKENDS}
    np.testing.assert_array_equal(draws["pallas"], draws["xla"])
    d = draws["xla"]
    assert d.min() >= 0 and d.max() < C
    # every draw with u > 0 lands on a valid, positive-mass entry
    assert valid[d[u > 1e-7]].all()


def test_masked_cdf_draw_adversarial_weights_regression():
    """The ladies CDF robustness fix: with adversarial weight spreads
    float32 cumsum used to end below/above 1.0 and ``searchsorted``
    returned an out-of-range index for u near 1; normalizing by the
    CDF's own final value + clipping keeps every draw in range and on
    positive mass."""
    # many tiny + a few huge masses: cumsum error on the last entries
    p = np.concatenate([np.full(4096, 1e-7, np.float32),
                        np.full(8, 3e8, np.float32),
                        np.full(4096, 1e-7, np.float32)])
    valid = np.ones_like(p, bool)
    u = np.asarray([0.0, 0.5, 1.0 - 1e-7, np.float32(1.0 - 6e-8)],
                   np.float32)
    for b in BACKENDS:
        d = np.asarray(O.masked_cdf_draw(jnp.asarray(p), jnp.asarray(valid),
                                         jnp.asarray(u), backend=b))
        assert d.min() >= 0 and d.max() < len(p), (b, d)
        assert (p[d] > 0).all(), b
    # and through the ladies sampler on a weighted-free graph the fix
    # keeps the layer well-formed at extreme layer sizes
    ds2 = generate(DatasetSpec("mini", 800, 8.0, 8, 3, 0.5, 0.2, 0.6, 400),
                   seed=3)
    caps = [LayerCaps(4096, 2048, 1024)]
    seeds = pad_seeds(jnp.asarray(ds2.train_idx[:64]), 64)
    blk = sample_layer_ladies(ds2.graph, seeds, jnp.uint32(5), 512, caps[0])
    assert not bool(blk.overflow)
    nxt = np.asarray(blk.next_seeds)
    assert (nxt[nxt >= 0] < ds2.graph.num_vertices).all()


# ---------------------------------------------------------------------------
# sampler-level bit-exactness vs the retained dense baselines
# ---------------------------------------------------------------------------

def _block_fields_equal(a, b, what):
    for f in ("seeds", "next_seeds", "src", "dst_slot", "src_slot", "weight",
              "edge_mask", "src_perm", "num_seeds", "num_next", "num_edges",
              "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{what}: {f}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_build_block_matches_dense_baseline(ds, backend):
    """The tentpole contract: the O(cap) epilogue reproduces the O(V)
    dense baseline field for field — inclusion set, ascending
    next_seeds, stable src_perm, counts, overflow."""
    caps = LayerCaps(8192, 4096, 2048)
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:128]), 128)
    exp = expand_seed_edges(ds.graph, seeds, caps.expand_cap)
    rng = np.random.default_rng(7)
    for density in (0.05, 0.4, 0.95):
        include = jnp.asarray(rng.random(caps.expand_cap) < density) \
            & exp["mask"]
        inv_p = jnp.asarray(
            (np.abs(rng.normal(size=caps.expand_cap)) + 0.1).astype(
                np.float32))
        new = build_block(seeds, exp, include, inv_p, caps, backend=backend)
        old = build_block_dense(ds.graph.num_vertices, seeds, exp, include,
                                inv_p, caps)
        _block_fields_equal(new, old, f"density={density}")


def test_build_block_vertex_overflow_matches_dense(ds):
    """Tiny vertex cap: both paths must flag, and the surviving new
    vertices are the same ascending prefix."""
    caps = LayerCaps(8192, 4096, 160)  # 128 seeds + 32 new slots
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:128]), 128)
    exp = expand_seed_edges(ds.graph, seeds, caps.expand_cap)
    include = exp["mask"]
    inv_p = jnp.ones((caps.expand_cap,), jnp.float32)
    new = build_block(seeds, exp, include, inv_p, caps)
    old = build_block_dense(ds.graph.num_vertices, seeds, exp, include,
                            inv_p, caps)
    assert bool(new.overflow) and bool(old.overflow)
    _block_fields_equal(new, old, "vertex-overflow")


def test_importance_fixed_point_matches_dense(ds):
    """Candidate-frontier pi (sparse) vs the retained dense-V layout:
    bit-identical per-edge pi and per-seed c for labor-1/2/*."""
    caps = LayerCaps(8192, 4096, 2048)
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:128]), 128)
    exp = expand_seed_edges(ds.graph, seeds, caps.expand_cap)
    m = np.asarray(exp["mask"])
    for iters in (1, 2, -1):
        pe_s, c_s = run_importance_iterations(ds.graph, exp, 10, 128, iters)
        pe_d, c_d = run_importance_iterations(ds.graph, exp, 10, 128, iters,
                                              dense=True)
        np.testing.assert_array_equal(np.asarray(pe_s)[m],
                                      np.asarray(pe_d)[m], err_msg=str(iters))
        np.testing.assert_array_equal(np.asarray(c_s), np.asarray(c_d),
                                      err_msg=str(iters))


def test_exact_k_matches_dense_lexsort(ds):
    """segment_select against the retained global-lexsort sequential
    Poisson on real expanded neighborhoods + real hash draws."""
    caps = LayerCaps(8192, 4096, 2048)
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:128]), 128)
    exp = expand_seed_edges(ds.graph, seeds, caps.expand_cap)
    slot, mask, deg = exp["seed_slot"], exp["mask"], exp["deg"]
    for salt in (1, 99, 12345):
        r = rng_lib.hash_uniform_edge(
            jnp.uint32(salt), exp["src"],
            jnp.where(mask, seeds[jnp.clip(slot, 0, 127)], 0))
        ratio = jnp.where(mask, r, 3.4e38)
        new = _exact_k_include(ratio, slot, mask, deg, exp["seg_start"],
                               7, 128, caps.expand_cap)
        old = _exact_k_include_dense(ratio, slot, mask, deg,
                                     exp["seg_start"], 7, 128,
                                     caps.expand_cap)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old),
                                      err_msg=str(salt))


@pytest.mark.parametrize("poisson", [False, True])
def test_ladies_candidate_path_matches_dense(ds, poisson):
    """Candidate-frontier LADIES/PLADIES vs the retained dense layout:
    same sampled vertex set, same weights to fp tolerance (the CDF/psum
    reassociation makes weights exact-in-practice, sets exact)."""
    caps = LayerCaps(8192, 4096, 2048)
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:128]), 128)
    for salt in (7, 42):
        b_s = sample_layer_ladies(ds.graph, seeds, jnp.uint32(salt), 300,
                                  caps, poisson=poisson)
        b_d = sample_layer_ladies(ds.graph, seeds, jnp.uint32(salt), 300,
                                  caps, poisson=poisson, dense=True)
        s1 = set(np.asarray(b_s.next_seeds).tolist()) - {-1}
        s2 = set(np.asarray(b_d.next_seeds).tolist()) - {-1}
        assert s1 == s2, (poisson, salt, len(s1 ^ s2))
        np.testing.assert_allclose(np.asarray(b_s.weight),
                                   np.asarray(b_d.weight), rtol=1e-5)


# ---------------------------------------------------------------------------
# the acceptance criterion: no V-sized intermediates in any sample trace
# ---------------------------------------------------------------------------

def _collect_avals(jaxpr, out):
    for eqn in _eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(aval)


@pytest.mark.parametrize("name", ["ns", "labor-0", "labor-1", "labor-*",
                                  "labor-d", "ladies", "pladies", "full"])
def test_sample_trace_has_no_vertex_sized_intermediates(name):
    """Walk the whole (nested) jaxpr of every registry sampler's
    ``sample`` and assert no intermediate buffer dimension equals the
    vertex count: peak sampling memory scales with the caps, not V.
    V is a prime well above every cap so a match cannot be a cap."""
    V = 50021
    rng = np.random.default_rng(0)
    E = 12 * V
    src = rng.integers(0, V, size=E)
    dst = rng.integers(0, V, size=E)
    from repro.graph.csr import from_coo
    g = from_coo(src, dst, V)

    B, fanouts = 64, (4, 3)
    ls = (192, 128) if name in ("ladies", "pladies") else None
    sampler = samplers.from_graph_stats(
        name, batch_size=B, fanouts=fanouts, avg_degree=12.0,
        max_degree=64, layer_sizes=ls, safety=2.0)
    seeds = pad_seeds(jnp.asarray(rng.choice(V, B, replace=False)
                                  .astype(np.int32)), B)
    salts = sampler.spec.salts(jax.random.key(0))

    closed = jax.make_jaxpr(
        lambda graph, s, sl: sampler.sample(graph, s, sl))(g, seeds, salts)
    avals = []
    _collect_avals(closed.jaxpr, avals)
    assert avals, "jaxpr walk found no intermediates"
    bad = [a for a in avals
           if any(d in (V, V + 1, V - 1) for d in a.shape)]
    assert not bad, (name, [a.shape for a in bad[:5]])


def test_dense_baseline_does_have_vertex_sized_intermediates(ds):
    """Sanity check of the detector itself: the retained dense baseline
    MUST trip it (otherwise the test above proves nothing)."""
    V = ds.graph.num_vertices
    caps = LayerCaps(2048, 1024, 512)
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:64]), 64)
    exp = expand_seed_edges(ds.graph, seeds, caps.expand_cap)
    inv_p = jnp.ones((caps.expand_cap,), jnp.float32)
    closed = jax.make_jaxpr(
        lambda e, s, p: build_block_dense(V, s, e, e["mask"], p, caps))(
        exp, seeds, inv_p)
    avals = []
    _collect_avals(closed.jaxpr, avals)
    assert any(any(d == V for d in a.shape) for a in avals)


def test_dedup_trace_has_no_buffer_sized_gathers():
    """The sorted buffer's run heads reach the rest of their runs by a
    forward fill, not by gathers: at a layer-2-like shape no gather in
    the (nested) jaxpr of ``_dedup`` yields a buffer-sized output."""
    S, E = 2**15, 2**18
    n = frontier_par._pow2_at_least(S + E)
    closed = jax.make_jaxpr(functools.partial(
        frontier_par._dedup, new_cap=S, tile=1024, interpret=True))(
        jax.ShapeDtypeStruct((E,), jnp.int32),
        jax.ShapeDtypeStruct((E,), jnp.bool_),
        jax.ShapeDtypeStruct((S,), jnp.int32))
    eqns = list(_eqns(closed.jaxpr))
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    bad = [e for e in eqns if e.primitive.name == "gather"
           and any(n in getattr(v.aval, "shape", ()) for v in e.outvars)]
    assert not bad, [v.aval for e in bad for v in e.outvars]


# ---------------------------------------------------------------------------
# grid-parallel kernels: bit-exact parity vs the serial kernels + refs
# ---------------------------------------------------------------------------

from repro.kernels.frontier import parallel as frontier_par
from repro.kernels.frontier import ref as frontier_ref

# sizes straddling tile boundaries under a forced tiny tile (8): below,
# exactly at, and one past one/two/four tile widths, plus non-multiples
TILE_EDGE_SIZES = (5, 8, 9, 16, 17, 31, 33, 64, 65)
TINY_TILES = (8, 16)


def _dedup_equal(a, b, msg=""):
    for f, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{msg}: {f}")


@pytest.mark.parametrize("E", TILE_EDGE_SIZES)
@pytest.mark.parametrize("tile", TINY_TILES)
def test_parallel_dedup_parity_across_tile_boundaries(E, tile):
    """Forced tiny sort blocks: the multi-block sort passes must
    reproduce the serial kernel and the XLA ref bit for bit at sizes
    below/at/past every tile boundary (new_cap = E: never gives up, so
    the FULL contract is in force)."""
    rng = np.random.default_rng(E * 31 + tile)
    vals = jnp.asarray(rng.integers(0, max(2, E), size=E).astype(np.int32))
    mask = jnp.asarray(rng.random(E) < 0.8)
    seeds = jnp.asarray(np.unique(
        rng.integers(0, max(2, E), size=max(1, E // 3)).astype(np.int32)))
    r_ref = frontier_ref.hash_dedup(vals, mask, seeds, E)
    r_ser = frontier_kernel_ops.hash_dedup_block(vals, mask, seeds, E,
                                                 interpret=True)
    r_par = frontier_par.hash_dedup_block_parallel(vals, mask, seeds, E,
                                                   tile=tile, interpret=True)
    _dedup_equal(r_ser, r_ref, f"serial E={E}")
    _dedup_equal(r_par, r_ref, f"parallel E={E} tile={tile}")


def test_parallel_dedup_stripe_overflow_propagates_across_tiles():
    """More distinct new values than ``new_cap`` must surface as the
    overflow flag, with ``new`` truncated to the smallest ``new_cap``
    exactly like the reference — whichever sort block the excess comes
    from, including only the last one."""
    vals = jnp.asarray(np.arange(64, dtype=np.int32))
    mask = jnp.ones((64,), bool)
    r = frontier_par.hash_dedup_block_parallel(vals, mask, None, 16,
                                               tile=8, interpret=True)
    assert bool(r.overflow)
    _dedup_equal(r, frontier_ref.hash_dedup(vals, mask, None, 16), "all")
    # excess arising ONLY in the last block still propagates
    v2 = np.zeros(64, np.int32)
    v2[56:] = np.arange(100, 108)          # 8 uniques, final block only
    r2 = frontier_par.hash_dedup_block_parallel(
        jnp.asarray(v2), mask, None, 4, tile=8, interpret=True)
    assert bool(r2.overflow)
    _dedup_equal(r2, frontier_ref.hash_dedup(jnp.asarray(v2), mask, None, 4),
                 "last block")
    # room for every unique: exact and flag-free
    r3 = frontier_par.hash_dedup_block_parallel(vals, mask, None, 64,
                                                tile=8, interpret=True)
    assert not bool(r3.overflow)
    np.testing.assert_array_equal(np.asarray(r3.new), np.asarray(vals))


@pytest.mark.parametrize("n,tile,n_keys,n_words", [
    (8, 8, 1, 1), (64, 8, 1, 2), (64, 16, 2, 3), (256, 32, 2, 2),
    (1024, 64, 1, 1), (2048, 1024, 2, 3)])
def test_sort_words_matches_lexsort(n, tile, n_keys, n_words):
    """The one kernel under every parallel primitive: in-block stages,
    cross-block grid passes and in-block merges must sort word tuples
    lexicographically by the key words, duplicates included, and carry
    the payload words without dropping or repeating a tuple."""
    rng = np.random.default_rng(n + tile + n_keys)
    keys = [rng.integers(-5, 7, n).astype(np.int32) for _ in range(n_keys)]
    pays = [rng.integers(0, 2**31 - 1, n).astype(np.int32)
            for _ in range(n_words - n_keys)]
    out = frontier_par.sort_words([jnp.asarray(w) for w in keys + pays],
                                  n_keys, tile=tile, interpret=True)
    out = [np.asarray(o) for o in out]
    order = np.lexsort(keys[::-1])
    for k, o in zip(keys, out):
        np.testing.assert_array_equal(o, k[order])
    assert sorted(zip(*[w.tolist() for w in keys + pays])) == \
        sorted(zip(*[o.tolist() for o in out]))


@pytest.mark.parametrize("n,density", [
    (8, 1.0), (8, 0.0), (8, 0.5), (100, 0.01), (1000, 0.3), (4096, 0.9),
    (2**16, 0.0), (2**16, 0.01), (2**16, 0.2), (2**16, 1.0)])
def test_fill_forward_matches_run_head_gather(n, density):
    """``fill_forward`` equals the gather it replaces, ``x[run]`` with
    ``run`` the cummax of head positions, whatever the heads' density,
    for negative values and values near the int32 top."""
    rng = np.random.default_rng(n * 7 + int(density * 100))
    head = rng.random(n) < density
    head[0] = True
    x = np.where(rng.random(n) < 0.5,
                 rng.integers(-2**31, 0, n),
                 rng.integers(2**31 - 1000, 2**31, n)).astype(np.int32)
    run = np.maximum.accumulate(np.where(head, np.arange(n), 0))
    got = jax.jit(frontier_par.fill_forward)(jnp.asarray(head),
                                             jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), x[run])


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_parallel_dedup_property(data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    tile = data.draw(st.sampled_from((8, 16, 32, 512)))
    rng = np.random.default_rng(seed)
    vals, mask, seeds, _ = _random_dedup_case(rng)
    E = len(vals)
    r_ref = frontier_ref.hash_dedup(jnp.asarray(vals), jnp.asarray(mask),
                                    jnp.asarray(seeds), E)
    r_par = frontier_par.hash_dedup_block_parallel(
        jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(seeds), E,
        tile=tile, interpret=True)
    _dedup_equal(r_par, r_ref, f"seed={seed} tile={tile}")


@pytest.mark.parametrize("E", TILE_EDGE_SIZES)
@pytest.mark.parametrize("tile", TINY_TILES)
def test_parallel_compact_parity_across_tile_boundaries(E, tile):
    rng = np.random.default_rng(E * 17 + tile)
    flags = jnp.asarray(rng.random(E) < rng.random())
    for cap in (1, max(1, E // 2), E):
        sel_r, em_r, n_r = frontier_ref.compact(flags, cap)
        sel_p, em_p, n_p = frontier_par.compact_block_parallel(
            flags, cap, tile=tile, interpret=True)
        msg = f"E={E} tile={tile} cap={cap}"
        np.testing.assert_array_equal(np.asarray(sel_p), np.asarray(sel_r),
                                      err_msg=msg)
        np.testing.assert_array_equal(np.asarray(em_p), np.asarray(em_r),
                                      err_msg=msg)
        assert int(n_p) == int(n_r), msg


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_parallel_perm_and_draw_property(data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    E = int(rng.integers(1, 200))
    K = int(rng.integers(1, 40))
    keys = jnp.asarray(rng.integers(-1, K, size=E).astype(np.int32))
    valid = jnp.asarray(rng.random(E) < 0.7)
    np.testing.assert_array_equal(
        np.asarray(frontier_par.compact_perm_block_parallel(
            keys, valid, K, interpret=True)),
        np.asarray(frontier_ref.compact_perm(keys, valid, K)))
    p = jnp.asarray(np.abs(rng.normal(size=E)).astype(np.float32))
    v = jnp.asarray(rng.random(E) < 0.8)
    if not bool(v.any()):
        v = v.at[0].set(True)
    u = jnp.asarray(rng.random(max(1, E // 3)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(frontier_par.masked_cdf_draw_block_parallel(
            p, v, u, interpret=True)),
        np.asarray(frontier_ref.masked_cdf_draw(p, v, u)))


@pytest.mark.parametrize("trial", range(8))
def test_parallel_segment_select_parity(trial):
    """The tiled sort/select against the ref bisection AND the serial
    kernel, on random segment layouts with ties."""
    rng = np.random.default_rng(700 + trial)
    keys, slot, mask, seg_start, deg, take, S, k = _random_segments(rng)
    args = (jnp.asarray(keys), jnp.asarray(slot), jnp.asarray(mask))
    r_ref = frontier_ref.segment_select(*args, jnp.asarray(seg_start),
                                        jnp.asarray(take), S)
    r_ser = frontier_kernel_ops.segment_select_block(
        *args, jnp.asarray(take), S, k, interpret=True)
    r_par = frontier_par.segment_select_block_parallel(
        *args, jnp.asarray(seg_start), jnp.asarray(take), S, interpret=True)
    np.testing.assert_array_equal(np.asarray(r_ser), np.asarray(r_ref))
    np.testing.assert_array_equal(np.asarray(r_par), np.asarray(r_ref))


def test_registry_dispatch_parallel_serial_switch(monkeypatch):
    """The pallas backend must route by REPRO_FRONTIER_IMPL and return
    identical results either way (the CI forced-impl matrix)."""
    from repro.ops import autotune
    rng = np.random.default_rng(9)
    vals = jnp.asarray(rng.integers(0, 500, 300).astype(np.int32))
    mask = jnp.asarray(rng.random(300) < 0.9)
    seeds = jnp.asarray(np.unique(rng.integers(0, 500, 40).astype(np.int32)))
    ref = frontier_ref.hash_dedup(vals, mask, seeds, 300)
    for impl in ("parallel", "serial"):
        monkeypatch.setenv(autotune.IMPL_ENV, impl)
        got = O.hash_dedup(vals, mask, seeds, 300, backend="pallas")
        _dedup_equal(got, ref, impl)


def test_registry_refuses_serial_kernels_when_compiled(monkeypatch):
    """The serial kernels do not compile for TPU: asking for them where
    kernels are compiled must fail loudly at dispatch, never run."""
    from repro.ops import autotune
    from repro.ops import pallas as pallas_backend
    monkeypatch.setenv(autotune.IMPL_ENV, "serial")
    monkeypatch.setattr(pallas_backend, "interpret_mode", lambda: False)
    with pytest.raises(ValueError, match="does not compile for TPU"):
        pallas_backend.compact(jnp.ones((16,), bool), 8)


# ---------------------------------------------------------------------------
# the autotune cache: roundtrip / corrupt file / missing-entry fallback
# ---------------------------------------------------------------------------

from repro.ops import autotune


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    monkeypatch.delenv(autotune.IMPL_ENV, raising=False)
    monkeypatch.delenv(autotune.TILE_ENV, raising=False)
    autotune.reload()
    yield path
    autotune.reload()


def test_autotune_missing_cache_falls_back_to_defaults(tune_cache):
    assert not tune_cache.exists()
    for prim, want in autotune.DEFAULT_PARAMS.items():
        assert autotune.get_params(prim, E=40960, S=512) == want
    assert autotune.cache_fingerprint() is None


def test_autotune_roundtrip(tune_cache):
    key = autotune.bucket_key("compact", jax.default_backend(),
                              {"E": 40960})
    c = autotune.TuneCache.load(str(tune_cache))
    c.put(key, {"impl": "serial", "tile": 128, "us": 42.0})
    c.save()
    autotune.reload()
    got = autotune.get_params("compact", E=40000)  # same pow2 bucket
    assert got["impl"] == "serial" and got["tile"] == 128
    assert "us" not in got                         # timing not a knob
    # different bucket: untouched -> defaults
    assert autotune.get_params("compact", E=1000) == \
        autotune.DEFAULT_PARAMS["compact"]
    assert autotune.cache_fingerprint() is not None


def test_autotune_corrupt_file_degrades_to_defaults(tune_cache, capsys):
    tune_cache.write_text("{not json at all")
    autotune.reload()
    assert autotune.get_params("hash_dedup", E=512, S=64) == \
        autotune.DEFAULT_PARAMS["hash_dedup"]
    assert "ignoring unusable tuning cache" in capsys.readouterr().err
    # wrong schema is equally survivable
    tune_cache.write_text('{"version": 999, "entries": []}')
    autotune.reload()
    assert autotune.get_params("compact", E=512) == \
        autotune.DEFAULT_PARAMS["compact"]


def test_autotune_env_overrides_beat_cache(tune_cache, monkeypatch):
    key = autotune.bucket_key("hash_dedup", jax.default_backend(),
                              {"E": 512, "S": 64})
    c = autotune.TuneCache.load(str(tune_cache))
    c.put(key, {"impl": "serial", "tile": 256})
    c.save()
    autotune.reload()
    monkeypatch.setenv(autotune.IMPL_ENV, "parallel")
    monkeypatch.setenv(autotune.TILE_ENV, "16")
    got = autotune.get_params("hash_dedup", E=512, S=64)
    assert got["impl"] == "parallel" and got["tile"] == 16


def test_autotune_smoke_writes_and_reads_back(tune_cache):
    """The CI round-trip: a smoke tune must persist winners for every
    primitive and read them back through dispatch."""
    winners = autotune.autotune(sizes=[(256, 32)], smoke=True,
                                verbose=False)
    assert set(k.split("|")[0] for k in winners) == set(autotune.PRIMITIVES)
    autotune.reload()
    assert autotune.cache_fingerprint() is not None
    for prim in autotune.PRIMITIVES:
        got = autotune.get_params(prim, E=256, S=32)
        assert got["impl"] in ("serial", "parallel")
