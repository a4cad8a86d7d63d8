"""Async request driver: continuous batching over the engine's fused
infer program.

``launch/serve.py``'s synchronous baseline pays one fixed-shape program
dispatch per request — a 4-seed request burns the same program as a
full batch. This driver instead keeps a request queue and, every time
the device is free, coalesces whatever is pending (whole requests,
FIFO) into ONE fixed-shape dispatch (:mod:`repro.serving.batcher`),
then slices the per-seed logits back to each request's ticket. That is
continuous batching in the LLM-serving sense, adapted to the GNN
workload: no waiting for a full batch (latency-optimal under light
load), full occupancy under heavy load, one jit specialization
throughout.

Per-request semantics:

* **Admission.** ``submit`` rejects oversized requests (> the engine's
  seed buffer) and, once ``max_queue`` tickets are pending, applies
  backpressure by rejecting instead of buffering unboundedly
  (:class:`~repro.serving.batcher.AdmissionError`).
* **Deadlines.** Each request carries a deadline (default
  ``deadline_ms``). Requests already past it at coalescing time are
  dropped as timeouts — never dispatched; requests served but slower
  than it count as SLO misses. p50/p99 are computed over warm batches
  only: compile events (first dispatch, every ``engine.grow``) are
  tagged and reported separately (:mod:`repro.serving.metrics`).
* **Overflow.** A cap overflow follows the training contract:
  ``engine.grow`` from the batch's measured counts + same-key retry,
  raising :class:`~repro.data.gnn_loader.SamplingOverflowError` when
  growth stops helping. A grow invalidates the device caches (their state
  survives shape changes, but the rebuilt program must start from a
  consistent clock) — counted in ``stats.cache_invalidations``.

The driver owns the cache state pytrees (:mod:`repro.serving.cache`)
and threads them through ``engine.cached_infer_fn``; with both caches
off it dispatches the plain ``engine.infer_fn``. Batches are keyed by
``jax.random.fold_in(key, batch_index)``, so a trace served twice —
with or without caches — sees identical salts per batch, which is what
makes the cache-on/cache-off bit-exactness testable end to end.

Use it inline (``pump`` until drained — deterministic, what the tests
and benchmark do) or start the background thread (``start``/``stop``)
for a live endpoint.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.engine import EngineData, TrainEngine
from repro.runtime.guard import RetryPolicy
from repro.serving.batcher import (AdmissionError, Batch, Ticket, coalesce,
                                   scatter_back)
from repro.serving.cache import HiddenCache, VertexCache
from repro.serving.metrics import ServingStats

from repro.data.gnn_loader import SamplingOverflowError


class ServingDriver:
    """Continuous-batching serving loop over one
    :class:`~repro.runtime.engine.TrainEngine` (single-host).

    Args:
      engine: the engine whose fused infer program answers requests
        (its sampler's cap schedule fixes the seed-buffer shape).
      params: served model parameters (frozen for the driver's life).
      data: :meth:`TrainEngine.make_data` output for the served graph.
      batch_size: the seed-buffer shape of the infer program — the
        coalescing target (must match the batch size the sampler's
        caps were derived for).
      feature_cache / hidden_cache: optional cache configs
        (:mod:`repro.serving.cache`); state is driver-owned.
      deadline_ms: default per-request deadline (None = no deadline).
      max_queue: pending-ticket bound before admission rejects
        (backpressure).
      max_grows: cap-doubling retries per dispatch before
        :class:`SamplingOverflowError` propagates to every ticket in
        the batch.
      seed: base of the per-batch salt schedule.
      inject: optional :class:`~repro.runtime.inject.FaultPlan` arming
        the serving trust boundaries (cache_corrupt / pump_death /
        stall_stage — docs/robustness.md).
      cache_fault_limit: nonfinite-logit faults under an enabled cache
        before the driver falls back to cache-off mode for good.
      watchdog_interval_s: how often the watchdog thread checks that
        the background pump is still alive.
    """

    def __init__(self, engine: TrainEngine, params, data: EngineData, *,
                 batch_size: int,
                 feature_cache: Optional[VertexCache] = None,
                 hidden_cache: Optional[HiddenCache] = None,
                 deadline_ms: Optional[float] = None,
                 max_queue: int = 1024, max_grows: int = 4, seed: int = 0,
                 inject=None, cache_fault_limit: int = 2,
                 watchdog_interval_s: float = 0.05):
        if engine.mesh is not None:
            raise NotImplementedError(
                "the serving driver is single-host; shard the graph "
                "behind one engine per replica instead")
        self.engine = engine
        self.params = params
        self.data = data
        self.batch_size = int(batch_size)
        self.feature_cache = feature_cache
        self.hidden_cache = hidden_cache
        self.deadline_ms = deadline_ms
        self.max_queue = int(max_queue)
        self.max_grows = int(max_grows)
        self.inject = inject
        self.cache_fault_limit = int(cache_fault_limit)
        self.watchdog_interval_s = float(watchdog_interval_s)
        self.stats = ServingStats()
        self._key = jax.random.key(seed)
        self._batch_index = 0
        self._pending: deque = deque()
        self._lock = threading.Lock()
        self._rid = 0
        self._fc_state = None
        self._hc_state = None
        self._cache_gen = engine.generation
        self._cache_faults = 0
        self._compiled_gens: set = set()
        self._thread: Optional[threading.Thread] = None
        self._watchdog: Optional[threading.Thread] = None
        self._pump_iter = 0
        self._stop = threading.Event()
        self._work = threading.Event()
        self._init_cache_state()

    # ------------------------------------------------------------------
    # cache state
    # ------------------------------------------------------------------

    def _init_cache_state(self):
        feat_dim = self.data.features.shape[1]
        if self.feature_cache is not None:
            self._fc_state = self.feature_cache.init_state(
                feat_dim, self.data.features.dtype)
        if self.hidden_cache is not None:
            self._hc_state = self.hidden_cache.init_state(
                self._hidden_dim())

    def _hidden_dim(self) -> int:
        # the deepest layer's output width = its weight's out dim
        layer0 = self.params["layers"][0]
        return int(layer0["w"].shape[-1])

    def _invalidate_caches(self):
        """Cold-restart the cache tables after ``engine.grow()``: the
        feature rows would still be bit-correct, but the rebuilt
        program gets a consistent clean clock — grows are rare and
        amortized, a cold cache refills in a few batches."""
        if self.feature_cache is None and self.hidden_cache is None:
            return
        self.stats.cache_invalidations += 1
        self._init_cache_state()

    # ------------------------------------------------------------------
    # request side
    # ------------------------------------------------------------------

    def submit(self, seeds, deadline_ms: Optional[float] = None) -> Ticket:
        """Enqueue one request (thread-safe). ``seeds`` is a 1-D array
        of vertex ids; raises :class:`AdmissionError` on an oversized
        request or a full queue (backpressure — the caller sheds load
        instead of the queue growing unboundedly)."""
        seeds = np.asarray(seeds, np.int32).reshape(-1)
        now = time.monotonic()
        dl = self.deadline_ms if deadline_ms is None else deadline_ms
        with self._lock:
            self.stats.submitted += 1
            if seeds.size == 0 or seeds.size > self.batch_size:
                self.stats.rejected += 1
                raise AdmissionError(
                    f"request of {seeds.size} seeds does not fit the "
                    f"engine's {self.batch_size}-seed infer program")
            if len(self._pending) >= self.max_queue:
                self.stats.rejected += 1
                raise AdmissionError(
                    f"queue full ({self.max_queue} pending) — backpressure")
            # graceful degradation: under real queue pressure (a full
            # batch already ahead), shed a deadlined request the warm
            # latency profile says cannot be served in time — rejecting
            # now beats dispatching a batch that times out anyway
            if dl is not None and len(self._pending) >= self.batch_size:
                est = self._estimated_wait_ms(len(self._pending))
                if est is not None and est > dl:
                    self.stats.shed += 1
                    raise AdmissionError(
                        f"load shed: estimated wait {est:.1f}ms exceeds "
                        f"the {dl:g}ms deadline")
            self._rid += 1
            t = Ticket(rid=self._rid, seeds=seeds,
                       deadline_s=None if dl is None else now + dl / 1e3,
                       submitted_s=now)
            self._pending.append(t)
        self._work.set()
        return t

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def _estimated_wait_ms(self, pending_n: int) -> Optional[float]:
        """Queue-drain estimate from the warm latency profile: batches
        ahead of a new request x the warm p50. None until the profile
        has at least one warm sample (never shed blind)."""
        p50 = self.stats.percentile_ms(50)
        if p50 is None:
            return None
        batches_ahead = -(-(pending_n + 1) // self.batch_size)
        return batches_ahead * p50

    # ------------------------------------------------------------------
    # serving side
    # ------------------------------------------------------------------

    def _apply_injectors(self):
        """Serving trust boundaries of the fault-injection registry:
        ``stall_stage`` sleeps in the dispatch path (deadline pressure),
        ``cache_corrupt`` NaN-poisons the cache value tables (the
        nonfinite-logit fallback path must recover)."""
        inj = self.inject
        if inj is None:
            return
        if inj.armed("stall_stage"):
            spec = inj.fires("stall_stage", self._batch_index)
            if spec is not None:
                time.sleep(spec.effect)
        if inj.armed("cache_corrupt") and (self._fc_state is not None
                                           or self._hc_state is not None):
            spec = inj.fires("cache_corrupt", self._batch_index)
            if spec is not None:
                def nan_poison(tree):
                    return jax.tree.map(
                        lambda x: (x * jnp.asarray(float("nan"), x.dtype)
                                   if jnp.issubdtype(x.dtype, jnp.floating)
                                   else x), tree)
                if self._fc_state is not None:
                    self._fc_state = nan_poison(self._fc_state)
                if self._hc_state is not None:
                    self._hc_state = nan_poison(self._hc_state)

    def _infer_batch(self, seeds_np: np.ndarray):
        """One dispatch of the (cache-aware) infer program, with the
        grow-retry overflow protocol on the shared
        :class:`~repro.runtime.guard.RetryPolicy`. Returns (logits np,
        compile_event, cache_metrics)."""
        eng = self.engine
        seeds = jnp.asarray(seeds_np)
        self._batch_index += 1
        key = jax.random.fold_in(self._key, self._batch_index)
        self._apply_injectors()
        box = {}

        def attempt(_i):
            if eng.generation != self._cache_gen:
                self._invalidate_caches()
                self._cache_gen = eng.generation
            compile_event = eng.generation not in self._compiled_gens
            cm = {}
            if self.feature_cache is None and self.hidden_cache is None:
                logits, ovf, counts = eng.infer(self.params, self.data,
                                                seeds, key)
                fc2 = hc2 = None
            else:
                fn = eng.cached_infer_fn(self.feature_cache,
                                         self.hidden_cache)
                logits, ovf, counts, fc2, hc2, cm = fn(
                    self.params, self.data.graph, self.data.features,
                    self._fc_state, self._hc_state, seeds, key)
            if bool(jnp.any(ovf)):
                box["counts"] = counts
                return None
            # commit cache state only for a clean (served) dispatch
            if self.feature_cache is not None:
                self._fc_state = fc2
            if self.hidden_cache is not None:
                self._hc_state = hc2
            self._compiled_gens.add(eng.generation)
            return np.asarray(logits), compile_event, cm

        def grow(_i):
            eng.grow(box["counts"], seeds)
            eng.stats.overflow_retries += 1
            self.stats.grow_events += 1

        return RetryPolicy(self.max_grows).run(
            attempt, grow=grow, error=SamplingOverflowError,
            describe="sampling overflow persisted after cap growth "
                     "while serving")

    def _recover_cache_fault(self, seeds_np: np.ndarray) -> np.ndarray:
        """Nonfinite logits under an enabled cache: the device-resident
        cache state is the prime suspect (bit-rot, a poisoned table).
        Cold-restart the caches, re-serve THIS batch cache-off under the
        same salt, and after ``cache_fault_limit`` faults disable the
        caches for good — correct-but-slower beats fast-but-NaN."""
        self.stats.nonfinite_batches += 1
        self._invalidate_caches()
        self._cache_faults += 1
        if self._cache_faults >= self.cache_fault_limit:
            self.feature_cache = None
            self.hidden_cache = None
            self._fc_state = self._hc_state = None
            self.stats.cache_fallbacks += 1
        key = jax.random.fold_in(self._key, self._batch_index)
        logits, *_ = self.engine.infer(self.params, self.data,
                                       jnp.asarray(seeds_np), key)
        return np.asarray(logits)

    def pump(self) -> int:
        """Serve at most one coalesced batch from the queue. Returns
        the number of requests resolved (served + timed out) — 0 means
        the queue was empty. This is the whole serving loop; the
        background thread just calls it repeatedly."""
        with self._lock:
            batch, timed_out = coalesce(self._pending, self.batch_size)
        now = time.monotonic()
        for t in timed_out:
            t.resolve("timeout", now=now)
            self.stats.timeouts += 1
        if batch is None:
            return len(timed_out)
        t0 = time.perf_counter()
        try:
            logits, compile_event, cm = self._infer_batch(batch.seeds)
            if (not np.isfinite(logits).all()
                    and (self.feature_cache is not None
                         or self.hidden_cache is not None)):
                logits = self._recover_cache_fault(batch.seeds)
                compile_event = True  # the retry's timing is tainted
        except Exception as e:
            # no ticket is ever stranded: whatever the dispatch raised,
            # every caller in the batch gets an "error" resolution and
            # the cause lands in the stats before the loop continues
            now = time.monotonic()
            for t, _, _ in batch.parts:
                t.resolve("error", now=now)
            self.stats.pump_errors += 1
            self.stats.last_error = f"{type(e).__name__}: {e}"
            if isinstance(e, SamplingOverflowError):
                # cap exhaustion keeps its historical contract: the
                # caller (or the watchdog, on the background loop)
                # decides whether to continue
                raise
            return len(timed_out) + len(batch.parts)
        dt = time.perf_counter() - t0
        self.stats.record_batch(dt, batch.n_seeds, len(batch.parts),
                                compile_event=compile_event)
        self.stats.record_cache({k: np.asarray(v) for k, v in cm.items()})
        now = time.monotonic()
        scatter_back(batch, logits, compile_tainted=compile_event, now=now)
        for t, _, _ in batch.parts:
            self.stats.served += 1
            if t.deadline_s is not None and now > t.deadline_s:
                self.stats.slo_miss += 1
        return len(timed_out) + len(batch.parts)

    def drain(self) -> int:
        """Pump until the queue is empty; returns requests resolved."""
        n = 0
        while True:
            served = self.pump()
            if served == 0 and self.pending == 0:
                return n
            n += served

    # ------------------------------------------------------------------
    # background loop
    # ------------------------------------------------------------------

    def start(self) -> None:
        """Run the serving loop on a background thread until
        :meth:`stop` (a live endpoint; tests and the benchmark's
        deterministic mode use :meth:`pump`/:meth:`drain` inline).
        A watchdog thread restarts the pump if it dies — including
        deaths the pump loop's own handler cannot catch (the
        ``pump_death`` injector raises a BaseException to model a
        native-code crash)."""
        if self._thread is not None:
            raise RuntimeError("driver already started")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                inj = self.inject
                if inj is not None and inj.armed("pump_death"):
                    spec = inj.fires("pump_death", self._pump_iter)
                    if spec is not None:
                        from repro.runtime.inject import InjectedThreadDeath
                        raise InjectedThreadDeath(
                            f"pump killed at iteration {self._pump_iter}")
                self._pump_iter += 1
                try:
                    served = self.pump()
                except SamplingOverflowError:
                    # tickets were already resolved as errors by pump();
                    # the background loop keeps serving what it can
                    continue
                if served == 0:
                    self._work.clear()
                    self._work.wait(timeout=0.05)

        def watchdog():
            while not self._stop.wait(timeout=self.watchdog_interval_s):
                if self._thread is not None and not self._thread.is_alive():
                    self.stats.pump_restarts += 1
                    self._thread = threading.Thread(target=loop, daemon=True)
                    self._thread.start()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        self._watchdog = threading.Thread(target=watchdog, daemon=True)
        self._watchdog.start()

    def stop(self, drain: bool = True) -> None:
        if self._thread is None:
            return
        if drain:
            while self.pending:
                time.sleep(0.001)
        self._stop.set()
        self._work.set()
        self._thread.join()
        self._watchdog.join()
        self._thread = None
        self._watchdog = None
