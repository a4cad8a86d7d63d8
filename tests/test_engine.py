"""The one training engine (repro/runtime/engine.py).

Distributed tests run in a subprocess with a FORCED 4-DEVICE host mesh
(tests/_subproc.py) and check the acceptance contract of the
partition-aware step: for every registry sampler, the distributed
program — seeds routed to owners, sampling partition-local against the
partitioned CSR, features via the all-to-all — produces the SAME
sampled vertex sets (bit-exact, via the shared global-id hash) and
matching loss/gradient effects (fp tolerance) as the single-device
fused step built from the same engine. Host-side tests cover the
partition_graph round-trip invariants and the drop_last seed padding.
"""
import numpy as np
import pytest

from tests._subproc import run_with_devices

# Shared prelude: a small dataset + single-vs-distributed engine pair.
# ladies/pladies get explicit layer sizes: their budgets are
# batch-GLOBAL (one sampled layer shared by the whole batch), so the
# device-local default (local_batch * fanout) would change the math.
_PARITY_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import samplers
from repro.core.interface import pad_seeds
from repro.graph.generators import DatasetSpec, generate
from repro.launch.mesh import make_mesh
from repro.models import gnn as gnn_models
from repro.optim import adam
from repro.runtime.engine import TrainEngine

ds = generate(DatasetSpec("mini", 2000, 12.0, 16, 5, 0.5, 0.2, 0.6, 1000),
              seed=0)
P, B, fanouts = 4, 128, (4, 3)
mesh = make_mesh((P,), ("data",))
opt_cfg = adam.AdamConfig(lr=1e-2)
base_params = gnn_models.gcn_init(jax.random.key(0), 16, 32, 5, len(fanouts))


def engines_for(name):
    ls = (256, 192) if name in ("ladies", "pladies") else None
    s1 = samplers.from_dataset(name, ds, batch_size=B, fanouts=fanouts,
                               safety=3.0, layer_sizes=ls)
    sP = samplers.from_dataset(name, ds, batch_size=B // P, fanouts=fanouts,
                               safety=3.0, layer_sizes=ls, num_parts=P)
    e1 = TrainEngine(s1, gnn_models.gcn_apply, opt_cfg, mesh=None)
    eP = TrainEngine(sP, gnn_models.gcn_apply, opt_cfg, mesh=mesh)
    return e1, eP


def check_parity(name):
    e1, eP = engines_for(name)
    d1 = e1.make_data_from_dataset(ds)
    dP = eP.make_data_from_dataset(ds)
    seeds = pad_seeds(jnp.asarray(np.asarray(ds.train_idx[:B], np.int32)), B)
    key = jax.random.key(7)
    p1 = jax.tree.map(jnp.array, base_params)
    pP = jax.tree.map(jnp.array, base_params)
    st1, stP = e1.init_state(p1), eP.init_state(pP)
    p1, st1, m1 = e1.step(p1, st1, d1, seeds, key)
    pP, stP, mP = eP.step(pP, stP, dP, seeds, key)
    assert not bool(jnp.any(m1["overflow"])), (name, "single overflow")
    assert not bool(jnp.any(mP["overflow"])), (name, "dist overflow")

    # bit-exact sampled vertex sets, layer by layer: frontiers[l] is the
    # union of owner shards of the layer-l seed set; frontiers[-1] the
    # deepest |V^L| set
    blocks = e1.sampler.sample_with_key(ds.graph, seeds, key)
    single_sets = [set(np.asarray(seeds).tolist())] + [
        set(np.asarray(b.next_seeds).tolist()) for b in blocks]
    for l, expect in enumerate(single_sets):
        expect -= {-1}
        got = set(np.asarray(mP["frontiers"][l]).tolist()) - {-1}
        assert got == expect, (name, "layer", l, len(got ^ expect))

    # count metrics identical; loss/acc within fp tolerance; the updated
    # params (i.e. the applied gradients) match to fp tolerance
    assert int(m1["sampled_v"]) == int(mP["sampled_v"]), name
    assert int(m1["sampled_e"]) == int(mP["sampled_e"]), name
    assert abs(float(m1["loss"]) - float(mP["loss"])) < 1e-4, name
    assert abs(float(m1["acc"]) - float(mP["acc"])) < 1e-6, name
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pP)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    print(name, "parity OK: |V^L| =", int(mP["sampled_v"]))
"""


def test_engine_parity_labor_family():
    """ns / labor-0 / labor-* (the acceptance trio) + labor-d: identical
    sampled sets via the stateless global-id hash; the importance fixed
    point crosses partitions through an exact pmax."""
    run_with_devices(_PARITY_PRELUDE + """
for name in ("ns", "labor-0", "labor-*", "labor-d"):
    check_parity(name)
""", n=4, timeout=1200)


def test_engine_parity_remaining_samplers():
    """Every other registry entry: labor-i, the ladies family (batch-
    global column norms completed with a psum — exact for these pinned
    seeds, though the psum's float reassociation makes ladies parity
    exact-in-practice rather than exact-by-construction), and exact
    full-neighborhood inference."""
    run_with_devices(_PARITY_PRELUDE + """
for name in ("labor-1", "ladies", "pladies", "full"):
    check_parity(name)
""", n=4, timeout=1200)


def test_engine_feature_exchange_overflow_replays():
    """All-to-all overflow heals through the SAME doubled-caps replay as
    sampling overflow: shrink only the per-peer caps, train a few steps,
    and require replays that grew peer_caps while keeping params
    finite and moving."""
    run_with_devices(_PARITY_PRELUDE + """
import dataclasses
sP = samplers.from_dataset("labor-0", ds, batch_size=B // P,
                           fanouts=fanouts, safety=3.0, num_parts=P)
# sampling caps untouched; per-peer all-to-all caps far too small
tiny = tuple(max(c // 16, 8) for c in sP.spec.peer_caps)
sP = dataclasses.replace(sP, spec=dataclasses.replace(sP.spec,
                                                      peer_caps=tiny))
eng = TrainEngine(sP, gnn_models.gcn_apply, opt_cfg, mesh=mesh)
data = eng.make_data_from_dataset(ds)
params = jax.tree.map(jnp.array, base_params)
state = eng.init_state(params)
rng = np.random.default_rng(0)
key = jax.random.key(3)
for t in range(4):
    seeds = pad_seeds(jnp.asarray(rng.choice(
        ds.train_idx, size=B, replace=False).astype(np.int32)), B)
    key, sk = jax.random.split(key)
    params, state, m = eng.step(params, state, data, seeds, sk, tag=t)
params, state, _ = eng.flush(params, state, data)
assert eng.stats.overflow_replays >= 1, "ledger never replayed"
assert eng.stats.overflow_retries >= 1, "caps never doubled"
assert all(c > t for c, t in zip(eng.sampler.spec.peer_caps, tiny)), (
    "peer caps did not grow")
assert all(np.isfinite(np.asarray(l)).all()
           for l in jax.tree.leaves(params))
moved = any(not np.allclose(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(base_params),
                            jax.tree.leaves(params)))
assert moved, "replayed batches were dropped, not applied"
print("exchange overflow replay OK:", eng.stats.overflow_replays,
      "replays,", eng.stats.overflow_retries, "doublings")
""", n=4, timeout=1200)


def test_engine_distributed_backend_parity():
    """Graph-ops backend parity under the partitioned engine: for gcn,
    sage, AND gatv2, five fused distributed steps through the Pallas
    kernels (interpret mode on CPU) match the XLA-backend run's
    loss/params to fp tolerance. The same sampler + seeds + salts make
    the sampled blocks identical, so any divergence is the kernels'."""
    run_with_devices(_PARITY_PRELUDE + """
import numpy as np

for model in ("gcn", "sage", "gatv2"):
    init_fn, apply_fn = gnn_models.MODELS[model]
    base = init_fn(jax.random.key(0), 16, 32, 5, len(fanouts))
    sP = samplers.from_dataset("labor-0", ds, batch_size=B // P,
                               fanouts=fanouts, safety=3.0, num_parts=P)
    results = {}
    for backend in ("xla", "pallas"):
        eng = TrainEngine(sP, apply_fn, opt_cfg, mesh=mesh, backend=backend)
        data = eng.make_data_from_dataset(ds)
        params = jax.tree.map(jnp.array, base)
        state = eng.init_state(params)
        rng = np.random.default_rng(5)
        key = jax.random.key(13)
        losses = []
        for _ in range(5):
            seeds = pad_seeds(jnp.asarray(rng.choice(
                ds.train_idx, size=B, replace=False).astype(np.int32)), B)
            key, sk = jax.random.split(key)
            params, state, m = eng.step(params, state, data, seeds, sk)
            losses.append(float(m["loss"]))
        assert not bool(jnp.any(m["overflow"])), (model, backend)
        results[backend] = (losses, params)
    np.testing.assert_allclose(results["pallas"][0], results["xla"][0],
                               atol=1e-4)
    for a, b in zip(jax.tree.leaves(results["pallas"][1]),
                    jax.tree.leaves(results["xla"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    print(model, "distributed backend parity OK")
""", n=4, timeout=2400)


def test_engine_distributed_infer_matches_single():
    """Serving path through the same engine: per-owner logits of the
    distributed fused infer equal the single-device fused infer."""
    run_with_devices(_PARITY_PRELUDE + """
e1, eP = engines_for("full")
d1 = e1.make_data_from_dataset(ds)
dP = eP.make_data_from_dataset(ds)
seeds = pad_seeds(jnp.asarray(np.asarray(ds.val_idx[:B], np.int32)), B)
k = jax.random.key(9)
logits1, ovf1, _ = e1.infer(base_params, d1, seeds, k)
owned, logitsP, ovfP = eP.infer(base_params, dP, seeds, k)
assert not bool(jnp.any(ovf1)) and not bool(jnp.any(ovfP))
pos = {int(v): i for i, v in enumerate(np.asarray(seeds)) if v >= 0}
owned, logitsP, logits1 = map(np.asarray, (owned, logitsP, logits1))
n = 0
for i, v in enumerate(owned):
    if v >= 0:
        np.testing.assert_allclose(logitsP[i], logits1[pos[int(v)]],
                                   atol=1e-4)
        n += 1
assert n == (np.asarray(seeds) >= 0).sum()
print("distributed infer OK,", n, "seeds matched")
""", n=4, timeout=1200)


# ---------------------------------------------------------------------------
# host-side: partition_graph round-trip invariants
# ---------------------------------------------------------------------------

def test_partition_graph_roundtrip_invariants():
    from repro.graph.generators import DatasetSpec, generate
    from repro.graph.partition import partition_graph

    ds = generate(DatasetSpec("mini", 1500, 9.0, 8, 4, 0.5, 0.2, 0.6, 500),
                  seed=1)
    g = ds.graph
    for P in (3, 4):
        pg = partition_graph(g, P)
        V = g.num_vertices
        v = np.arange(V)
        # owner/local_id/global_id round-trip
        assert np.array_equal(pg.owner(v), v % P)
        assert np.array_equal(pg.local_id(v), v // P)
        for p in range(P):
            owned = np.arange(p, V, P)
            assert np.array_equal(pg.global_id(p, pg.local_id(owned)), owned)
        # padded layout: indptr flat beyond the owned range, indices
        # zero-padded beyond edge_counts, common shapes across partitions
        assert pg.indptr.shape == (P, int(pg.local_counts.max()) + 1)
        assert pg.indices.shape[0] == P
        for p in range(P):
            nloc, ne = int(pg.local_counts[p]), int(pg.edge_counts[p])
            assert pg.indptr[p, nloc] == ne
            assert np.all(pg.indptr[p, nloc:] == ne)
            assert np.all(pg.indices[p, ne:] == 0)
        # edge conservation: every partition holds exactly the in-edges
        # of its owned destinations, with global source ids
        assert int(pg.edge_counts.sum()) == g.num_edges
        indptr = np.asarray(g.indptr)
        indices = np.asarray(g.indices)
        for p in range(P):
            local = pg.part_graph(p)
            for lv in range(int(pg.local_counts[p])):
                gv = lv * P + p
                mine = np.sort(np.asarray(
                    local.indices[local.indptr[lv]:local.indptr[lv + 1]]))
                ref = np.sort(indices[indptr[gv]:indptr[gv + 1]])
                assert np.array_equal(mine, ref), (P, p, gv)


def test_partitioned_features_match_mod_ownership():
    from repro.graph.partition import partition_features

    feats = np.arange(22 * 3, dtype=np.float32).reshape(22, 3)
    P = 4
    pf = partition_features(feats, P)
    per = -(-22 // P)
    assert pf.shape == (P, per, 3)
    for v in range(22):
        assert np.array_equal(pf[v % P, v // P], feats[v])
