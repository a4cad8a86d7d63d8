"""Cap growth after an overflow: the rule that grows only the buffers a
batch measured past their caps (core/interface.py), the cap schedules
``from_graph_stats`` gives the benchmark's cells, and the engine proving
a new schedule on its first batch (runtime/engine.py)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import samplers
from repro.core.interface import (LayerCaps, SamplerSpec, cap_overflows,
                                  double_caps, grow_caps, suggest_caps)
from repro.data.gnn_loader import LoaderStats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAPS = (LayerCaps(expand_cap=1024, edge_cap=512, vertex_cap=768),
        LayerCaps(expand_cap=4096, edge_cap=2048, vertex_cap=3072),
        LayerCaps(expand_cap=8192, edge_cap=4096, vertex_cap=6144))
# a seed buffer of 256 slots holding 200 seeds
SEEDS = np.concatenate([np.arange(200), np.full(56, -1)]).astype(np.int32)
# (expanded, sampled, next) per layer, every buffer within its cap: the
# vertex rooms are 768 - 256, 3072 - 768 and 6144 - 3072
FIT = [[800, 400, 500], [3000, 1500, 2000], [8000, 3000, 2500]]


def _counts(**at):
    """FIT with entries replaced: ``at={"l2_expand": 9000, ...}``."""
    col = {"expand": 0, "edge": 1, "next": 2}
    out = [row[:] for row in FIT]
    for key, v in at.items():
        layer, name = key.split("_")
        out[int(layer[1:])][col[name]] = v
    return np.asarray(out, np.int32)


def _with(caps, layer, **kw):
    caps = list(caps)
    caps[layer] = dataclasses.replace(caps[layer], **kw)
    return caps


@pytest.mark.parametrize("counts,over,want", [
    # the layer-2 expansion past its cap, under twice the cap: doubled
    (_counts(l2_expand=9000), {(2, "expand"): (8192, 9000)},
     _with(CAPS, 2, expand_cap=16384)),
    # far past it: 1.25 x the need, rounded up to 128
    (_counts(l2_expand=30000), {(2, "expand"): (8192, 30000)},
     _with(CAPS, 2, expand_cap=37504)),
    # the layer-0 edge buffer alone
    (_counts(l0_edge=600), {(0, "edge"): (512, 600)},
     _with(CAPS, 0, edge_cap=1024)),
    # two buffers of two layers at once
    (_counts(l0_edge=1000, l2_expand=9000),
     {(0, "edge"): (512, 1000), (2, "expand"): (8192, 9000)},
     _with(_with(CAPS, 0, edge_cap=1280), 2, expand_cap=16384)),
    # layer 0's room for new vertices (512) is too small for 700: it
    # grows to 1024, and the vertex buffers below keep their rooms on
    # the larger seed buffers (layer 1 then saw 200 + 512 seeds)
    (_counts(l0_next=900), {(0, "vertex"): (512, 700)},
     [dataclasses.replace(CAPS[0], vertex_cap=256 + 1024),
      dataclasses.replace(CAPS[1], vertex_cap=1280 + 2304),
      dataclasses.replace(CAPS[2], vertex_cap=3584 + 3072)]),
    # every count within its cap: a flag the counts do not explain
    # doubles every buffer
    (_counts(), None, double_caps(CAPS)),
    # nothing measured (a mesh's step reports no counts)
    (None, None, double_caps(CAPS)),
], ids=["expand-2x", "expand-1.25x", "edge", "two-layers", "vertex-room",
        "unexplained", "unmeasured"])
def test_only_the_overflowed_buffers_grow(counts, over, want):
    got = cap_overflows(CAPS, counts, SEEDS)
    assert got == over
    assert grow_caps(CAPS, got, SEEDS.shape[0]) == list(want)
    stats = LoaderStats()
    stats.record_growth(got, len(CAPS))
    assert set(stats.growths) == (
        set(over) if over is not None
        else {(l, b) for l in range(3) for b in ("expand", "edge",
                                                 "vertex")})


def test_peer_caps_double_beside_measured_growth():
    spec = SamplerSpec("labor-0", (10, 10, 10), CAPS,
                       peer_caps=(64, 128, 256, 512))
    over = cap_overflows(CAPS, _counts(l2_expand=9000), SEEDS)
    grown = spec.grown(over, SEEDS.shape[0])
    assert grown.peer_caps == (128, 256, 512, 1024)
    assert grown.caps == tuple(_with(CAPS, 2, expand_cap=16384))


def _traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


# the graphs of the benchmark's configurations as bench/graph.py builds
# them: vertices, edges after duplicate removal, largest in-degree
PRODUCTS = (2_449_029, 61_776_997, 3_217)
FLICKR = (89_250, 899_953, 432)
PRODUCTS_CAPS = [(64640, 21248, 22272), (1136640, 448384, 470656),
                 (9426304, 9426304, 2919808)]


@pytest.mark.parametrize("traffic,graph,want", [
    ("labor0", PRODUCTS, PRODUCTS_CAPS),
    ("ns", PRODUCTS, PRODUCTS_CAPS),
    ("labor0", FLICKR, [(22528, 21248, 22272), (451072, 448384, 111616),
                        (899968, 899968, 200960)]),
], ids=["gcn-products.labor0", "gcn-products.ns", "gatv2-flickr.labor0"])
def test_cap_schedules_of_the_benchmark_cells(traffic, graph, want):
    """The schedules the benchmark's cells start from, pinned: a change
    to ``suggest_caps`` moves every cell and is a change of its own."""
    t = _traffic(traffic)
    v, e, d_max = graph
    s = samplers.from_graph_stats(
        t["sampler"], batch_size=t["batch_size"], fanouts=t["fanouts"],
        avg_degree=e / v, max_degree=d_max, num_vertices=v, num_edges=e,
        safety=t["cap_safety"])
    assert [dataclasses.astuple(c) for c in s.caps] == want


# -- a dense graph whose default caps overflow at the last layer ----------

BATCH, FANOUTS = 64, (10, 10, 10)


@pytest.fixture(scope="module")
def dense():
    """A graph of Reddit's generator settings at 3,000 vertices (average
    in-degree 259 after duplicate removal), the cell's first batches at
    batch 64, and the cap schedule of ``suggest_caps`` with its
    expansion bound scaled down with the graph: as 2^22 does at
    Reddit's size, the bound leaves the last layer's expand buffer at
    its edge-cap floor (100,096 slots), under the 101k-118k in-edges
    that the pool's 396-441 layer-2 seeds expand to; every other
    buffer fits."""
    from bench import graph as graph_lib
    from bench.harness import Feed
    with open(os.path.join(ROOT, "bench", "configs", "gcn-reddit.json")) as f:
        config = json.load(f)
    config["graph"].update(num_vertices=3000, avg_degree=600.0)
    g = graph_lib.build(config["graph"])
    traffic = dict(_traffic("labor0"), batch_size=BATCH)
    caps = suggest_caps(BATCH, FANOUTS, g.num_edges / g.num_vertices,
                        g.max_in_degree, safety=traffic["cap_safety"],
                        num_vertices=g.num_vertices, num_edges=g.num_edges,
                        max_expand=100_000)
    return config, traffic, g, tuple(caps), Feed(g.train_idx, traffic, 5)


def test_first_step_returns_the_replayed_batch(dense):
    """The first batch overflows at layer 2: ``step`` replays it before
    returning, only that expand buffer grows, the three checked steps
    agree with the plain reference, and later steps do not replay."""
    from bench import check, graph as graph_lib
    from bench.reference import train as reference
    from repro.graph.csr import Graph
    from repro.models import gnn as gnn_models
    from repro.optim import adam
    from repro.runtime.engine import TrainEngine

    config, traffic, g, caps, feed = dense
    gspec, opt = config["graph"], config["optimizer"]
    labels = jnp.asarray(g.labels)
    features = graph_lib.device_features(gspec, labels)
    model = reference.load_model(config["model"])
    params = model.init(jax.random.key(feed.param_seed), config,
                        gspec["num_features"], gspec["num_classes"])
    params0 = jax.tree.map(np.asarray, params)
    engine = TrainEngine(
        samplers.get("labor-0", FANOUTS, caps),
        gnn_models.MODELS["gcn"][1],
        adam.AdamConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], grad_clip=opt["grad_clip"]))
    data = engine.make_data(Graph(indptr=jnp.asarray(g.indptr),
                                  indices=jnp.asarray(g.indices)),
                            features, labels)
    state = engine.init_state(params)

    prog = {"losses": [], "counts": []}
    for i in range(traffic["check_steps"]):
        params, state, m = engine.step(params, state, data,
                                       jnp.asarray(feed.batch(i)),
                                       feed.key(i), tag=i)
        if i == 0:
            # polled at once, replayed at once: the metrics returned are
            # the replay's, and its update was applied
            assert engine.stats.overflow_replays == 1
            assert engine.replayed[-1] == (0, m)
            assert not np.asarray(m["overflow"]).any()
            prog["first_grad"] = jax.tree.map(
                lambda mu: np.asarray(mu) / (1 - opt["b1"]),
                state.opt["mu"])
        prog["losses"].append(float(m["loss"]))
        prog["counts"].append((int(m["sampled_v"]), int(m["sampled_e"])))
    prog["delta"] = jax.tree.map(lambda p, p0: np.asarray(p) - p0, params,
                                 params0)

    # the expanded count is exact whatever the cap: the replay's is the
    # overflowed batch's
    need = int(engine.replayed[0][1]["layer_counts"][2, 0])
    assert need > caps[2].expand_cap
    grown = -(-5 * need // 4)              # ceil(1.25 x need)
    grown = -(-grown // 128) * 128
    want = _with(caps, 2, expand_cap=max(2 * caps[2].expand_cap, grown))
    assert list(engine.sampler.caps) == want
    assert engine.stats.growths == {(2, "expand"): 1}

    ref = reference.run(config, traffic, g, features, params0,
                        [feed.batch(k) for k in range(3)],
                        [feed.key(k) for k in range(3)])
    nums = check.numbers(prog, ref)
    # the same sampled sets; the rest differs by float32 rounding on the
    # CPU, 7e-6 or less (the reference sums in another order): a
    # truncated sample moves the loss by 1e-2, a no-op first step reads
    # 1 on grad
    assert nums["sampled_v"] == 0 and nums["sampled_e"] == 0
    assert max(nums["loss"], nums["grad"], nums["update"]) < 1e-4

    for i in range(3, 3 + 2 * feed.size):
        params, state, m = engine.step(params, state, data,
                                       jnp.asarray(feed.batch(i)),
                                       feed.key(i), tag=i)
    engine.flush(params, state, data)
    assert engine.stats.overflow_replays == 1
    assert engine.stats.overflow_retries == 1
    assert engine.dispatches == 3 + 2 * feed.size + 1


def test_infer_retry_grows_only_the_overflowed_buffer(dense):
    """Serving follows the same rule: the infer program returns its
    ``layer_counts`` beside the flags, and the retry grows the layer-2
    expand buffer that the batch measured past its cap, and no other."""
    from bench import graph as graph_lib
    from repro.graph.csr import Graph
    from repro.models import gnn as gnn_models
    from repro.optim import adam
    from repro.runtime.engine import TrainEngine

    config, _, g, caps, feed = dense
    gspec = config["graph"]
    labels = jnp.asarray(g.labels)
    engine = TrainEngine(samplers.get("labor-0", FANOUTS, caps),
                         gnn_models.MODELS["gcn"][1], adam.AdamConfig())
    data = engine.make_data(Graph(indptr=jnp.asarray(g.indptr),
                                  indices=jnp.asarray(g.indices)),
                            graph_lib.device_features(gspec, labels), labels)
    params = gnn_models.MODELS["gcn"][0](
        jax.random.key(0), gspec["num_features"], 16, gspec["num_classes"],
        len(FANOUTS))
    logits, grows = engine.infer_with_retry(
        params, data, jnp.asarray(feed.batch(0)), feed.key(0))
    assert grows == 1 and engine.stats.growths == {(2, "expand"): 1}
    assert list(engine.sampler.caps[:2]) == list(caps[:2])
    assert engine.sampler.caps[2].edge_cap == caps[2].edge_cap
    assert engine.sampler.caps[2].expand_cap >= 2 * caps[2].expand_cap
    assert logits.shape == (BATCH, gspec["num_classes"])
