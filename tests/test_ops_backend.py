"""The graph-ops backend layer (repro/ops): xla-vs-pallas parity.

Unit parity for every primitive (forward AND gradient — the Pallas
backend's custom VJPs against JAX autodiff of the XLA reference),
then end-to-end: ``TrainEngine.step`` with ``backend="pallas"``
(interpret mode on CPU) must match ``backend="xla"`` loss/params to fp
tolerance over 5 fused train steps for gcn, sage, and gatv2. The
4-device partitioned-engine counterpart lives in tests/test_engine.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops as O
from repro.core import LayerCaps, labor_sampler, pad_seeds
from repro.graph.generators import DatasetSpec, generate

BACKENDS = ("xla", "pallas")


@pytest.fixture(scope="module")
def ds():
    return generate(DatasetSpec("mini", 1500, 10.0, 24, 5, 0.5, 0.2, 0.6,
                                700), seed=0)


@pytest.fixture(scope="module")
def block(ds):
    """One real LABOR-sampled block (covers -1 padding, masked edges,
    non-multiple-of-block caps)."""
    caps = [LayerCaps(4096, 2048, 1024)]
    seeds = pad_seeds(jnp.asarray(ds.train_idx[:64]), 64)
    return labor_sampler((6,), caps, 0).sample_with_key(
        ds.graph, seeds, jax.random.key(0))[0]


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_resolution():
    assert set(O.available_backends()) >= {"xla", "pallas"}
    # off-TPU (this CI) auto resolves to the XLA reference
    assert O.resolve_backend(None) == O.resolve_backend("auto")
    assert O.resolve_backend("auto") == (
        "pallas" if jax.default_backend() == "tpu" else "xla")
    assert O.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown graph-ops backend"):
        O.resolve_backend("cuda")


def test_register_backend_validates_primitives():
    class Partial:
        aggregate = staticmethod(lambda blk, h: h)

    with pytest.raises(ValueError, match="missing primitives"):
        O.register_backend("partial", Partial)
    assert "partial" not in O.available_backends()


# ---------------------------------------------------------------------------
# unit parity: forward + gradients per primitive
# ---------------------------------------------------------------------------

def test_aggregate_fwd_parity(block):
    h = jnp.asarray(_rng(1).normal(size=(block.next_cap, 24)), jnp.float32)
    ref = O.aggregate(block, h, backend="xla")
    out = O.aggregate(block, h, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("f_in,f_out", [(24, 8), (24, 24), (8, 24)])
def test_gcn_layer_aggregates_at_the_narrower_width(block, monkeypatch,
                                                    f_in, f_out):
    """(A h) W = A (h W): a layer that narrows projects first, so the
    block's edge-sized message tensors take the narrower width."""
    from repro.models import gnn as gnn_models
    r = _rng(2)
    h = jnp.asarray(r.normal(size=(block.next_cap, f_in)), jnp.float32)
    p = {"w": jnp.asarray(r.normal(size=(f_in, f_out)), jnp.float32),
         "b": jnp.zeros((f_out,), jnp.float32),
         "wr": jnp.asarray(r.normal(size=(f_in, f_out)), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        want = (O.aggregate(block, h, backend="xla") @ p["w"] + p["b"]
                + h[:block.seed_cap] @ p["wr"])
        widths = []
        real = O.aggregate
        monkeypatch.setattr(O, "aggregate", lambda blk, x, backend=None: (
            widths.append(x.shape[-1]), real(blk, x, backend=backend))[1])
        got = gnn_models.gcn_layer(p, block, h, is_last=True, backend="xla")
    assert widths == [min(f_in, f_out)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_aggregate_vjp_vs_grad_of_ref(block):
    """The satellite contract: the Pallas custom VJP (transposed SpMM
    for dh, SDDMM for dweight) against jax.grad of aggregate_ref."""
    rng = _rng(2)
    h = jnp.asarray(rng.normal(size=(block.next_cap, 24)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(block.seed_cap, 24)), jnp.float32)

    def loss(h_, w_, backend):
        b = dataclasses.replace(block, weight=w_)
        if backend == "ref":
            return jnp.sum(O.aggregate_ref(b, h_) * c)
        return jnp.sum(O.aggregate(b, h_, backend=backend) * c)

    g_ref = jax.grad(loss, argnums=(0, 1))(h, block.weight, "ref")
    g_pal = jax.grad(loss, argnums=(0, 1))(h, block.weight, "pallas")
    np.testing.assert_allclose(np.asarray(g_pal[0]), np.asarray(g_ref[0]),
                               atol=2e-4)  # dh: the transposed SpMM
    np.testing.assert_allclose(np.asarray(g_pal[1]), np.asarray(g_ref[1]),
                               atol=2e-4)  # dweight: the SDDMM


def test_scatter_gather_transpose_pair(block):
    """gather_dst and scatter_edges are transposes: <scatter(v), u> ==
    <v, gather(u)> — and each backend's pair agrees with the other's."""
    rng = _rng(3)
    v = jnp.asarray(rng.normal(size=(block.edge_cap, 8)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(block.seed_cap, 8)), jnp.float32)
    for backend in BACKENDS:
        s = O.scatter_edges(block, v, backend=backend)
        g = O.gather_dst(block, u, backend=backend)
        np.testing.assert_allclose(float(jnp.vdot(s, u)),
                                   float(jnp.vdot(v, g)), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(O.scatter_edges(block, v, backend="pallas")),
        np.asarray(O.scatter_edges(block, v, backend="xla")), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(O.gather_dst(block, u, backend="pallas")),
        np.asarray(O.gather_dst(block, u, backend="xla")), atol=1e-5)


@pytest.mark.parametrize("op", ["add", "dot"])
def test_sddmm_fwd_and_grad_parity(block, op):
    rng = _rng(4)
    u = jnp.asarray(rng.normal(size=(block.seed_cap, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(block.next_cap, 8)), jnp.float32)
    shape = (block.edge_cap, 8) if op == "add" else (block.edge_cap,)
    g = jnp.asarray(rng.normal(size=shape), jnp.float32)
    outs, grads = {}, {}
    for backend in BACKENDS:
        outs[backend] = O.sddmm(block, u, v, op=op, backend=backend)
        grads[backend] = jax.grad(
            lambda u_, v_, b=backend: jnp.sum(
                O.sddmm(block, u_, v_, op=op, backend=b) * g),
            argnums=(0, 1))(u, v)
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["xla"]), atol=1e-4)
    for a, b in zip(grads["pallas"], grads["xla"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_edge_softmax_fwd_and_grad_parity(block):
    rng = _rng(5)
    logit = jnp.asarray(rng.normal(size=(block.edge_cap, 4)), jnp.float32) * 3
    g = jnp.asarray(rng.normal(size=logit.shape), jnp.float32)
    a_x = O.edge_softmax(block, logit, backend="xla")
    a_p = O.edge_softmax(block, logit, backend="pallas")
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_x), atol=1e-5)
    # masked edges contribute nothing; valid destinations normalize to 1
    mask = np.asarray(block.edge_mask)
    assert np.all(np.asarray(a_p)[~mask] == 0)
    sums = np.zeros((block.seed_cap, 4))
    np.add.at(sums, np.asarray(block.dst_slot)[mask], np.asarray(a_p)[mask])
    touched = np.unique(np.asarray(block.dst_slot)[mask])
    np.testing.assert_allclose(sums[touched], 1.0, atol=1e-5)

    dl = [jax.grad(lambda l: jnp.sum(
        O.edge_softmax(block, l, backend=b) * g))(logit) for b in BACKENDS]
    np.testing.assert_allclose(np.asarray(dl[1]), np.asarray(dl[0]),
                               atol=1e-5)


def test_edge_softmax_extreme_logit_spread(block):
    """One huge logit in a chunk must not underflow the OTHER rows'
    softmax (regression: a chunk-shared shift collapsed every row
    >~88 below the chunk max to alpha == 0 in f32; the kernel's
    per-row segment max must be exact)."""
    rng = _rng(8)
    logit = jnp.asarray(rng.normal(size=(block.edge_cap, 2)), jnp.float32)
    # spike a single valid edge far above everything else
    first_valid = int(np.flatnonzero(np.asarray(block.edge_mask))[0])
    logit = logit.at[first_valid, 0].set(500.0)
    a_x = O.edge_softmax(block, logit, backend="xla")
    a_p = O.edge_softmax(block, logit, backend="pallas")
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_x), atol=1e-5)
    # every destination with edges still normalizes to 1 in both heads
    mask = np.asarray(block.edge_mask)
    sums = np.zeros((block.seed_cap, 2))
    np.add.at(sums, np.asarray(block.dst_slot)[mask], np.asarray(a_p)[mask])
    touched = np.unique(np.asarray(block.dst_slot)[mask])
    np.testing.assert_allclose(sums[touched], 1.0, atol=1e-5)


def test_pallas_ops_trace_inside_jit_with_grad(block):
    """The primitives must trace inside an enclosing jitted program with
    autodiff — the position they occupy in the fused train step."""
    h = jnp.asarray(_rng(6).normal(size=(block.next_cap, 16)), jnp.float32)

    @jax.jit
    def f(h_):
        return jax.grad(
            lambda x: jnp.sum(O.aggregate(block, x, backend="pallas") ** 2)
        )(h_)

    @jax.jit
    def f_ref(h_):
        return jax.grad(
            lambda x: jnp.sum(O.aggregate(block, x, backend="xla") ** 2)
        )(h_)

    np.testing.assert_allclose(np.asarray(f(h)), np.asarray(f_ref(h)),
                               atol=2e-4)


# ---------------------------------------------------------------------------
# end-to-end: TrainEngine.step parity over 5 fused steps, all models
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "sage", "gatv2"])
def test_engine_step_backend_parity(ds, model):
    from repro.core import samplers
    from repro.models import gnn as gnn_models
    from repro.optim import adam
    from repro.runtime.engine import TrainEngine

    B, fanouts = 96, (4, 3)
    init_fn, apply_fn = gnn_models.MODELS[model]
    base = init_fn(jax.random.key(0), 24, 32, 5, len(fanouts))
    opt_cfg = adam.AdamConfig(lr=1e-2)
    sampler = samplers.from_dataset("labor-0", ds, batch_size=B,
                                    fanouts=fanouts, safety=3.0)
    results = {}
    for backend in BACKENDS:
        eng = TrainEngine(sampler, apply_fn, opt_cfg, mesh=None,
                          backend=backend)
        assert eng.backend == backend
        data = eng.make_data_from_dataset(ds)
        params = jax.tree.map(jnp.array, base)
        state = eng.init_state(params)
        rng = np.random.default_rng(7)
        key = jax.random.key(11)
        losses = []
        for _ in range(5):
            seeds = pad_seeds(jnp.asarray(rng.choice(
                ds.train_idx, size=B, replace=False).astype(np.int32)), B)
            key, sk = jax.random.split(key)
            params, state, m = eng.step(params, state, data, seeds, sk)
            losses.append(float(m["loss"]))
        assert not bool(jnp.any(m["overflow"])), (model, backend)
        results[backend] = (losses, params)

    l_x, p_x = results["xla"]
    l_p, p_p = results["pallas"]
    np.testing.assert_allclose(l_p, l_x, atol=1e-4)
    for a, b in zip(jax.tree.leaves(p_p), jax.tree.leaves(p_x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_restore_meta_records_and_checks_backend(ds, tmp_path):
    from repro.core import samplers
    from repro.runtime import checkpoint as ckpt_lib

    sampler = samplers.from_dataset("labor-0", ds, batch_size=32,
                                    fanouts=(3,))
    meta = ckpt_lib.engine_restore_meta(sampler, backend="pallas")
    assert meta["backend"] == "pallas"
    # same backend: passes, caps re-adopted
    ckpt_lib.validate_restore_meta(meta, sampler, backend="pallas")
    # mismatch: loud error naming both backends
    with pytest.raises(ValueError, match="backend 'pallas' != current"):
        ckpt_lib.validate_restore_meta(meta, sampler, backend="xla")
    # checkpoints predating the key pass through
    legacy = {k: v for k, v in meta.items() if k != "backend"}
    ckpt_lib.validate_restore_meta(legacy, sampler, backend="xla")
