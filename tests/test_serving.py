"""The serving subsystem (repro/serving/, docs/serving.md).

The acceptance contract, bottom-up:

* **Caches** (unit, no engine): the feature cache's gathered rows are
  verbatim feature rows — bit-equal to a direct take — cold, warm,
  across eviction (both policies), and invalid (-1 pad) ids gather
  zeros like ``gather_feats``. The hidden cache never serves an entry
  older than ``max_age`` steps, and at ``max_age=0`` never serves a
  cached entry at all.
* **Engine hook**: for EVERY registry sampler, the cache-aware infer
  program (``engine.cached_infer_fn``) produces logits bit-exact with
  the plain ``engine.infer`` under the same key — cold cache, warm
  cache (repeat traffic), under forced eviction, and after a
  ``grow()`` rebuild. The hidden cache is bit-exact at ``max_age=0``,
  and bit-exact at ANY age on the deterministic ``full`` sampler with
  frozen params.
* **Driver**: coalescing packs whole requests FIFO into the fixed
  batch shape; scatter-back slices each ticket its own rows;
  admission rejects oversized requests and applies backpressure;
  expired tickets time out instead of being served; overflow follows
  the trainer's retry contract (grow, then
  ``SamplingOverflowError``) and never strands a ticket.
"""
import time
from collections import deque

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import samplers  # noqa: E402
from repro.core.interface import pad_seeds  # noqa: E402
from repro.data.gnn_loader import SamplingOverflowError  # noqa: E402
from repro.graph.generators import DatasetSpec, generate  # noqa: E402
from repro.models import gnn as gnn_models  # noqa: E402
from repro.optim import adam  # noqa: E402
from repro.runtime.engine import TrainEngine  # noqa: E402
from repro.serving import (AdmissionError, HiddenCache, ServingDriver,  # noqa: E402
                           Ticket, VertexCache, coalesce, scatter_back)

ALL_SAMPLERS = samplers.list_samplers()
B, FANOUTS, HIDDEN = 64, (4, 3), 16


@pytest.fixture(scope="module")
def ds():
    return generate(DatasetSpec("mini", 2000, 12.0, 16, 5, 0.5, 0.2, 0.6,
                                1000), seed=0)


def _engine(ds, name, *, safety=3.0):
    ls = (192, 128) if name in ("ladies", "pladies") else None
    s = samplers.from_dataset(name, ds, batch_size=B, fanouts=FANOUTS,
                              safety=safety, layer_sizes=ls)
    eng = TrainEngine(s, gnn_models.gcn_apply, adam.AdamConfig())
    return eng, eng.make_data_from_dataset(ds)


def _params(ds, key=0):
    return gnn_models.gcn_init(jax.random.key(key), ds.features.shape[1],
                               HIDDEN, 5, len(FANOUTS))


def _seed_batches(ds, n, rng_seed=3):
    rng = np.random.default_rng(rng_seed)
    idx = np.asarray(ds.val_idx)
    return [pad_seeds(jnp.asarray(rng.choice(idx, B // 2, replace=False)
                                  .astype(np.int32)), B) for _ in range(n)]


def _run_pair(eng, data, params, fc, hc, seeds_list, key0=11):
    """Baseline vs cached logits for a shared key schedule; asserts
    no overflow on either path and returns list of (base, cached)."""
    fn = eng.cached_infer_fn(fc, hc)
    fc_state = (fc.init_state(data.features.shape[1], data.features.dtype)
                if fc else None)
    hc_state = hc.init_state(HIDDEN) if hc else None
    out = []
    for i, seeds in enumerate(seeds_list):
        key = jax.random.fold_in(jax.random.key(key0), i)
        base, ovf, _ = eng.infer(params, data, seeds, key)
        assert not bool(jnp.any(ovf))
        got, ovf2, _, fc_state, hc_state, _ = fn(
            params, data.graph, data.features, fc_state, hc_state, seeds,
            key)
        assert not bool(jnp.any(ovf2))
        valid = np.asarray(seeds) >= 0
        out.append((np.asarray(base)[valid], np.asarray(got)[valid]))
    return out


# ----------------------------------------------------------------------
# feature cache: unit
# ----------------------------------------------------------------------

class TestVertexCache:
    def _feats(self, n=300, f=8, seed=0):
        return jnp.asarray(np.random.default_rng(seed)
                           .normal(size=(n, f)).astype(np.float32))

    def _fetch(self, feats):
        return lambda missed: jnp.take(feats, missed, axis=0, mode="fill",
                                       fill_value=0)

    def _gather_ids(self, cache, state, feats, ids):
        rows, state, m = cache.gather(
            state, jnp.asarray(np.asarray(ids, np.int32)),
            self._fetch(feats))
        return np.asarray(rows), state, m

    @pytest.mark.parametrize("policy", ["fifo", "freq"])
    def test_cold_warm_bitexact(self, policy):
        feats = self._feats()
        cache = VertexCache(64, policy)
        state = cache.init_state(8)
        ids = np.arange(10, 40)
        rows, state, m = self._gather_ids(cache, state, feats, ids)
        assert int(m["hits"]) == 0
        np.testing.assert_array_equal(rows, np.asarray(feats)[ids])
        # warm: same ids all hit, rows still verbatim
        rows, state, m = self._gather_ids(cache, state, feats, ids)
        assert int(m["hits"]) == len(ids)
        assert int(m["misses"]) == 0
        np.testing.assert_array_equal(rows, np.asarray(feats)[ids])

    @pytest.mark.parametrize("policy", ["fifo", "freq"])
    def test_post_eviction_bitexact(self, policy):
        feats = self._feats()
        cache = VertexCache(16, policy)  # far smaller than the id stream
        state = cache.init_state(8)
        rng = np.random.default_rng(1)
        for _ in range(6):
            ids = rng.integers(0, 300, size=24)
            rows, state, m = self._gather_ids(cache, state, feats, ids)
            np.testing.assert_array_equal(rows, np.asarray(feats)[ids])

    def test_fifo_evicts_oldest(self):
        feats = self._feats()
        cache = VertexCache(8, "fifo")
        state = cache.init_state(8)
        _, state, _ = self._gather_ids(cache, state, feats, np.arange(8))
        _, state, _ = self._gather_ids(cache, state, feats,
                                       np.arange(100, 104))
        # ids 0..3 were the oldest ring slots — overwritten
        _, state, m = self._gather_ids(cache, state, feats, np.arange(8))
        assert int(m["hits"]) == 4

    def test_freq_keeps_hot(self):
        feats = self._feats()
        cache = VertexCache(8, "freq")
        state = cache.init_state(8)
        hot = np.arange(4)
        _, state, _ = self._gather_ids(cache, state, feats, np.arange(8))
        for _ in range(3):  # heat up 0..3
            _, state, _ = self._gather_ids(cache, state, feats, hot)
        _, state, _ = self._gather_ids(cache, state, feats,
                                       np.arange(100, 104))
        _, state, m = self._gather_ids(cache, state, feats, hot)
        assert int(m["hits"]) == 4  # the hot set survived eviction

    def test_pad_ids_gather_zero_and_are_not_cached(self):
        feats = self._feats()
        cache = VertexCache(16, "fifo")
        state = cache.init_state(8)
        ids = np.array([5, -1, 7, -1], np.int32)
        rows, state, m = self._gather_ids(cache, state, feats, ids)
        np.testing.assert_array_equal(rows[1], np.zeros(8, np.float32))
        np.testing.assert_array_equal(rows[3], np.zeros(8, np.float32))
        assert int(m["unique_misses"]) == 2
        keys = np.asarray(state.keys)
        assert set(keys[keys >= 0].tolist()) == {5, 7}  # pads not cached

    def test_gather_is_jittable(self):
        feats = self._feats()
        cache = VertexCache(16, "fifo")
        state = cache.init_state(8)
        ids = jnp.arange(10, dtype=jnp.int32)

        @jax.jit
        def step(state, ids):
            return cache.gather(state, ids, self._fetch(feats))

        rows, state, _ = step(state, ids)
        np.testing.assert_array_equal(np.asarray(rows),
                                      np.asarray(feats)[:10])


# ----------------------------------------------------------------------
# hidden cache: unit
# ----------------------------------------------------------------------

class TestHiddenCache:
    def _sub(self, cache, state, ids, fresh):
        h, state, m = cache.substitute(
            state, jnp.asarray(np.asarray(ids, np.int32)),
            jnp.asarray(fresh))
        return np.asarray(h), state, m

    def test_max_age_zero_never_serves_cached(self):
        cache = HiddenCache(32, max_age=0)
        state = cache.init_state(4)
        ids = np.arange(8)
        f0 = np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32)
        f1 = f0 + 1.0
        h, state, _ = self._sub(cache, state, ids, f0)
        np.testing.assert_array_equal(h, f0)
        # repeat traffic: entries are age 1 > max_age 0 — fresh wins
        h, state, m = self._sub(cache, state, ids, f1)
        np.testing.assert_array_equal(h, f1)
        assert int(m["hidden_hits"]) == 0

    def test_serves_stale_within_bound_then_refreshes(self):
        cache = HiddenCache(32, max_age=2)
        state = cache.init_state(4)
        ids = np.arange(8)
        f0 = np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32)
        h, state, _ = self._sub(cache, state, ids, f0)
        for step in range(1, 3):  # ages 1, 2: cached f0 served
            h, state, m = self._sub(cache, state, ids, f0 + step)
            np.testing.assert_array_equal(h, f0)
            assert int(m["hidden_hits"]) == 8
            assert int(m["max_served_age"]) <= 2
        # age 3 > bound: expired, fresh served and re-cached
        h, state, m = self._sub(cache, state, ids, f0 + 3)
        np.testing.assert_array_equal(h, f0 + 3)
        assert int(m["hidden_hits"]) == 0
        h, state, m = self._sub(cache, state, ids, f0 + 4)
        np.testing.assert_array_equal(h, f0 + 3)  # the refreshed entry


# ----------------------------------------------------------------------
# engine hook: cache-on vs cache-off bit-exactness, every sampler
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_SAMPLERS)
def test_feature_cache_bitexact_per_sampler(ds, name):
    """Cold + warm (the second batch repeats the first's seeds):
    feature-cache-on logits bit-equal engine.infer for every
    registered sampler."""
    eng, data = _engine(ds, name)
    params = _params(ds)
    batches = _seed_batches(ds, 2)
    batches.append(batches[0])  # warm repeat
    fc = VertexCache(512, "fifo")
    for base, got in _run_pair(eng, data, params, fc, None, batches):
        np.testing.assert_array_equal(base, got)


@pytest.mark.parametrize("policy", ["fifo", "freq"])
def test_feature_cache_bitexact_under_eviction(ds, policy):
    """A cache far smaller than the working set stays bit-exact while
    evicting every batch."""
    eng, data = _engine(ds, "labor-0")
    params = _params(ds)
    fc = VertexCache(32, policy)
    for base, got in _run_pair(eng, data, params, fc, None,
                               _seed_batches(ds, 4)):
        np.testing.assert_array_equal(base, got)


def test_feature_cache_bitexact_post_grow(ds):
    """grow() bumps the generation and invalidates cached programs; a
    fresh cached program + cold state is bit-exact against the rebuilt
    engine.infer."""
    eng, data = _engine(ds, "labor-0")
    params = _params(ds)
    fc = VertexCache(256, "fifo")
    batches = _seed_batches(ds, 2)
    for base, got in _run_pair(eng, data, params, fc, None, batches):
        np.testing.assert_array_equal(base, got)
    gen = eng.generation
    eng.grow()
    assert eng.generation == gen + 1
    assert eng._infer_cached == {}  # cached programs invalidated
    for base, got in _run_pair(eng, data, params, fc, None, batches,
                               key0=13):
        np.testing.assert_array_equal(base, got)


def test_hidden_cache_age0_bitexact(ds):
    """max_age=0: the hidden cache may insert but never serve, so the
    layered path equals plain inference bit-exactly even on repeat
    traffic."""
    eng, data = _engine(ds, "labor-0")
    params = _params(ds)
    batches = _seed_batches(ds, 2)
    batches.append(batches[0])
    hc = HiddenCache(512, max_age=0)
    for base, got in _run_pair(eng, data, params, None, hc, batches):
        np.testing.assert_array_equal(base, got)


def test_hidden_cache_full_sampler_exact_any_age(ds):
    """The ``full`` sampler is deterministic and params are frozen, so
    a cached deepest-layer state is IDENTICAL to recomputing it — the
    stale cache is bit-exact at any age, while actually serving hits."""
    eng, data = _engine(ds, "full")
    params = _params(ds)
    batches = _seed_batches(ds, 1) * 4
    hc = HiddenCache(2048, max_age=10)
    fn = eng.cached_infer_fn(None, hc)
    hc_state = hc.init_state(HIDDEN)
    served = 0
    for i, seeds in enumerate(batches):
        key = jax.random.fold_in(jax.random.key(5), i)
        base, *_ = eng.infer(params, data, seeds, key)
        got, _, _, _, hc_state, m = fn(params, data.graph, data.features,
                                       None, hc_state, seeds, key)
        valid = np.asarray(seeds) >= 0
        np.testing.assert_array_equal(np.asarray(base)[valid],
                                      np.asarray(got)[valid])
        served += int(m["hidden_hits"])
    assert served > 0  # the exactness was not vacuous


def test_hidden_cache_error_bounded_by_staleness(ds):
    """On a sampled path the served-stale states come from an earlier
    batch's sample of the same seeds — the deviation from the exact
    recompute exists but is the bounded sampling noise of ONE layer,
    and the cache respects its staleness bound."""
    eng, data = _engine(ds, "labor-0")
    params = _params(ds)
    batches = _seed_batches(ds, 1) * 3
    hc = HiddenCache(2048, max_age=4)
    fn = eng.cached_infer_fn(None, hc)
    hc_state = hc.init_state(HIDDEN)
    max_dev, base_scale, served = 0.0, 0.0, 0
    for i, seeds in enumerate(batches):
        key = jax.random.fold_in(jax.random.key(5), i)
        base, *_ = eng.infer(params, data, seeds, key)
        got, _, _, _, hc_state, m = fn(params, data.graph, data.features,
                                       None, hc_state, seeds, key)
        valid = np.asarray(seeds) >= 0
        b, g = np.asarray(base)[valid], np.asarray(got)[valid]
        max_dev = max(max_dev, float(np.abs(b - g).max()))
        base_scale = max(base_scale, float(np.abs(b).max()))
        served += int(m["hidden_hits"])
        assert int(m["max_served_age"]) <= 4
    assert served > 0
    # bounded-error contract: same order of magnitude as the exact
    # logits, not a blow-up (bit-exactness is only promised at age 0)
    assert max_dev <= max(base_scale, 1.0)


# ----------------------------------------------------------------------
# batcher: unit
# ----------------------------------------------------------------------

def _ticket(rid, seeds, deadline_s=None, now=0.0):
    return Ticket(rid=rid, seeds=np.asarray(seeds, np.int32),
                  deadline_s=deadline_s, submitted_s=now)


class TestCoalesce:
    def test_packs_whole_requests_fifo(self):
        q = deque([_ticket(1, [1, 2, 3]), _ticket(2, [4, 5]),
                   _ticket(3, [6, 7, 8, 9])])
        batch, timed_out = coalesce(q, 8, now=1.0)
        assert timed_out == []
        assert [t.rid for t, _, _ in batch.parts] == [1, 2]
        assert batch.n_seeds == 5
        np.testing.assert_array_equal(
            batch.seeds, np.array([1, 2, 3, 4, 5, -1, -1, -1], np.int32))
        assert [t.rid for t in q] == [3]  # big request waits, FIFO kept

    def test_drops_expired(self):
        q = deque([_ticket(1, [1], deadline_s=0.5), _ticket(2, [2])])
        batch, timed_out = coalesce(q, 4, now=1.0)
        assert [t.rid for t in timed_out] == [1]
        assert [t.rid for t, _, _ in batch.parts] == [2]

    def test_empty_queue(self):
        batch, timed_out = coalesce(deque(), 4, now=1.0)
        assert batch is None and timed_out == []

    def test_scatter_back_slices(self):
        q = deque([_ticket(1, [1, 2]), _ticket(2, [3])])
        batch, _ = coalesce(q, 4, now=1.0)
        logits = np.arange(4 * 2, dtype=np.float32).reshape(4, 2)
        scatter_back(batch, logits, now=2.0)
        t1, t2 = (t for t, _, _ in batch.parts)
        assert t1.status == "ok" and t2.status == "ok"
        np.testing.assert_array_equal(t1.logits, logits[0:2])
        np.testing.assert_array_equal(t2.logits, logits[2:3])
        assert t1.done and t1.latency_ms == pytest.approx(2000.0)


# ----------------------------------------------------------------------
# driver: integration
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(ds):
    eng, data = _engine(ds, "full")
    return eng, data, _params(ds)


def test_driver_coalesces_and_answers_exactly(served, ds):
    """Small requests coalesce into shared dispatches, and — on the
    deterministic ``full`` sampler — every ticket's logits bit-equal a
    direct engine.infer of its seeds."""
    eng, data, params = served
    drv = ServingDriver(eng, params, data, batch_size=B)
    rng = np.random.default_rng(9)
    idx = np.asarray(ds.val_idx)
    reqs = [rng.choice(idx, 8, replace=False).astype(np.int32)
            for _ in range(8)]
    tickets = [drv.submit(r) for r in reqs]
    drv.drain()
    assert all(t.status == "ok" for t in tickets)
    assert drv.stats.batches == 1  # 8 x 8 seeds packed into one B=64
    assert drv.stats.served == 8
    ref, *_ = eng.infer(params, data,
                       pad_seeds(jnp.asarray(reqs[3]), B),
                       jax.random.key(0))
    np.testing.assert_array_equal(tickets[3].logits,
                                  np.asarray(ref)[:8])


def test_driver_cache_on_off_tickets_bitexact(served, ds):
    """The acceptance criterion end to end: the same trace served with
    the feature cache on and off yields bit-identical per-ticket
    logits (per-batch keys are salted by batch index, not wall
    clock)."""
    eng, data, params = served
    rng = np.random.default_rng(10)
    idx = np.asarray(ds.val_idx)
    reqs = [rng.choice(idx, 16, replace=False).astype(np.int32)
            for _ in range(6)]

    def run(fc):
        drv = ServingDriver(eng, params, data, batch_size=B,
                            feature_cache=fc, seed=4)
        tickets = [drv.submit(r) for r in reqs]
        drv.drain()
        assert all(t.status == "ok" for t in tickets)
        return drv, tickets

    _, base = run(None)
    drv, got = run(VertexCache(256, "fifo"))
    assert drv.stats.feat_hits > 0  # warm traffic actually hit
    for tb, tg in zip(base, got):
        np.testing.assert_array_equal(tb.logits, tg.logits)


def test_driver_admission_and_backpressure(served):
    eng, data, params = served
    drv = ServingDriver(eng, params, data, batch_size=B, max_queue=2)
    with pytest.raises(AdmissionError):
        drv.submit(np.arange(B + 1))  # oversized
    drv.submit([1]), drv.submit([2])
    with pytest.raises(AdmissionError):
        drv.submit([3])  # queue full
    assert drv.stats.rejected == 2
    drv.drain()


def test_driver_timeout_policy(served):
    eng, data, params = served
    drv = ServingDriver(eng, params, data, batch_size=B, deadline_ms=1.0)
    t = drv.submit([1, 2])
    time.sleep(0.01)  # let the deadline lapse before the pump
    drv.drain()
    assert t.status == "timeout"
    assert drv.stats.timeouts == 1 and drv.stats.served == 0


def test_driver_overflow_contract(ds):
    """Starved caps: the driver grows through the retry schedule and
    then raises the trainer's SamplingOverflowError, resolving every
    packed ticket as errored rather than stranding its waiter."""
    eng, data = _engine(ds, "ns", safety=0.02)
    params = _params(ds)
    drv = ServingDriver(eng, params, data, batch_size=B, max_grows=1)
    t = drv.submit(np.asarray(ds.val_idx)[:B].astype(np.int32))
    with pytest.raises(SamplingOverflowError):
        drv.drain()
    assert t.status == "error" and t.done
    assert drv.stats.grow_events >= 1


def test_driver_grow_invalidates_caches(ds):
    """A mid-trace grow() cold-restarts the cache tables (counted),
    and the post-grow answers remain correct."""
    eng, data = _engine(ds, "ns", safety=0.4)
    params = _params(ds)
    drv = ServingDriver(eng, params, data, batch_size=B,
                        feature_cache=VertexCache(256, "fifo"))
    idx = np.asarray(ds.val_idx)
    tickets = [drv.submit(idx[i * 16:(i + 1) * 16].astype(np.int32))
               for i in range(8)]
    drv.drain()
    assert all(t.status == "ok" for t in tickets)
    if drv.stats.grow_events:  # starved safety should force >= 1 grow
        assert drv.stats.cache_invalidations >= 1
    ref, *_ = eng.infer(params, data,
                       pad_seeds(jnp.asarray(tickets[-1].seeds), B),
                       jax.random.fold_in(jax.random.key(0),
                                          drv._batch_index))
    np.testing.assert_array_equal(tickets[-1].logits, np.asarray(ref)[:16])


def test_driver_background_thread(served, ds):
    eng, data, params = served
    drv = ServingDriver(eng, params, data, batch_size=B)
    drv.start()
    try:
        t = drv.submit(np.asarray(ds.val_idx)[:8].astype(np.int32))
        assert t.wait(timeout=60.0)
        assert t.status == "ok" and t.logits.shape[0] == 8
    finally:
        drv.stop()


# ----------------------------------------------------------------------
# the shared overflow error contract (satellite: one error type)
# ----------------------------------------------------------------------

def test_overflow_error_is_the_shared_type(ds):
    from repro.data.gnn_loader import sample_with_retry
    eng, data = _engine(ds, "ns", safety=0.02)
    params = _params(ds)
    seeds = pad_seeds(jnp.asarray(np.asarray(ds.val_idx[:B], np.int32)), B)
    with pytest.raises(SamplingOverflowError):
        eng.infer_with_retry(params, data, seeds, jax.random.key(0),
                             max_retries=1)
    assert issubclass(SamplingOverflowError, RuntimeError)
    # and the trainer-side loader raises the very same class
    sampler = samplers.from_dataset("ns", ds, batch_size=B,
                                    fanouts=FANOUTS, safety=0.02)
    with pytest.raises(SamplingOverflowError):
        sample_with_retry(sampler, ds.graph, seeds, jax.random.key(0),
                          max_retries=1)
