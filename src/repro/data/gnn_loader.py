"""GNN minibatch pipeline: seed shuffling, background prefetch, cap
management with overflow retry, and straggler mitigation.

The sampler itself is device-side (repro.core); this pipeline feeds it
padded seed batches and watches the ``overflow`` flags it returns. On
overflow the batch is retried with the overflowed buffers grown
(``repro.core.interface.grow_caps``; a new jit specialization — rare,
amortized). A watchdog timestamps batch production; batches slower
than ``straggler_timeout`` (e.g. a slow storage shard on a real cluster)
are *skipped* and counted, which keeps the synchronous optimizer step
from stalling the whole pod — the standard bounded-staleness mitigation.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.interface import (BUFFERS, cap_overflows, pad_seeds,
                                  sampled_counts)
from repro.runtime.guard import RetryPolicy


@dataclasses.dataclass
class LoaderStats:
    overflow_retries: int = 0
    overflow_replays: int = 0   # fused path: batches replayed one step late
    # entry l: polled batches whose overflow flag l was set — sampling
    # layer l for l < num_layers; on a mesh the later entries are the
    # all-to-all buffers, in the step's flag order
    overflow_by_layer: list = dataclasses.field(default_factory=list)
    stragglers_skipped: int = 0
    # pipelined path: in-flight batches re-sampled after a replay grew
    # the cap schedule (runtime/pipeline.py)
    pipeline_invalidations: int = 0
    # {(layer, buffer): growths} of the LayerCaps buffers, buffer one of
    # repro.core.interface.BUFFERS; a growth that measured nothing
    # (every buffer doubled) counts for every buffer
    growths: dict = dataclasses.field(default_factory=dict)

    def record_growth(self, over, num_layers: int) -> None:
        """Count one growth of the buffers ``over`` names
        (:func:`repro.core.interface.cap_overflows`; None: all)."""
        keys = (over.keys() if over is not None else
                [(l, b) for l in range(num_layers) for b in BUFFERS])
        for k in keys:
            self.growths[k] = self.growths.get(k, 0) + 1


class SamplingOverflowError(RuntimeError):
    """Sampling (or all-to-all) overflow persisted after the cap-
    growth retry schedule was exhausted.

    The ONE error type every overflow-retry surface raises — the eager
    :func:`sample_with_retry`, the engine's async replay protocol
    (``TrainEngine._replay``), and the serving retry
    (``TrainEngine.infer_with_retry`` / the serving driver) — so
    drivers catch cap exhaustion uniformly regardless of which path
    sampled the batch. Subclasses ``RuntimeError`` for compatibility
    with callers of the historical bare-RuntimeError contract."""


class SeedBatches:
    """Shuffled, padded seed batches over training vertices.

    Every yielded batch — including the ``drop_last=False`` remainder —
    has the full static ``batch_size`` shape (-1 padding), so one jit
    specialization serves an entire run; a ``rem``-shaped tail batch
    would force a fresh compile on the last batch of every epoch
    (tests/test_data.py::test_seed_batches_remainder_keeps_static_shape).
    """

    def __init__(self, train_idx: np.ndarray, batch_size: int, seed: int = 0,
                 drop_last: bool = True):
        self.train_idx = np.asarray(train_idx)
        self.batch_size = batch_size
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.drop_last = drop_last
        self._at_cache: Optional[tuple] = None  # (epoch, permutation)

    def epoch(self) -> Iterator[jnp.ndarray]:
        perm = self.rng.permutation(self.train_idx)
        n_full = len(perm) // self.batch_size
        for i in range(n_full):
            yield pad_seeds(
                jnp.asarray(perm[i * self.batch_size:(i + 1) * self.batch_size]),
                self.batch_size,
            )
        rem = len(perm) - n_full * self.batch_size
        if rem and not self.drop_last:
            yield pad_seeds(jnp.asarray(perm[-rem:]), self.batch_size)

    @property
    def per_epoch(self) -> int:
        """Full batches per epoch (the :meth:`at` schedule is full
        batches only — a constant epoch length is what makes the step
        index -> batch map a pure function)."""
        return max(len(self.train_idx) // self.batch_size, 1)

    def at(self, step: int) -> jnp.ndarray:
        """The batch for global ``step``, as a pure function of
        ``(seed, step)`` — the random-access counterpart of the
        :meth:`epoch` stream, required by the guardrail's rollback
        resume (docs/robustness.md): after restoring step ``s`` the
        trainer replays ``at(s), at(s+1), ...`` and lands, bit-exactly,
        on the trajectory an unfaulted run would have taken. Epoch
        ``step // per_epoch`` gets its own independently-seeded
        permutation (cached, so sequential access stays O(1) shuffles
        per epoch)."""
        epoch, i = divmod(step, self.per_epoch)
        if self._at_cache is None or self._at_cache[0] != epoch:
            rng = np.random.default_rng((self.seed, epoch))
            self._at_cache = (epoch, rng.permutation(self.train_idx))
        perm = self._at_cache[1]
        return pad_seeds(
            jnp.asarray(perm[i * self.batch_size:(i + 1) * self.batch_size]),
            self.batch_size,
        )


class PrefetchIterator:
    """Runs ``produce`` in a background thread with a bounded queue and a
    straggler watchdog."""

    def __init__(self, produce: Iterator, depth: int = 2,
                 straggler_timeout: Optional[float] = None,
                 stats: Optional[LoaderStats] = None):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.timeout = straggler_timeout
        self.stats = stats or LoaderStats()
        self._done = object()
        self._thread = threading.Thread(target=self._run, args=(produce,),
                                        daemon=True)
        self._thread.start()

    def _run(self, produce):
        try:
            for item in produce:
                self.q.put(item)
        finally:
            self.q.put(self._done)

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            try:
                t0 = time.monotonic()
                item = self.q.get(timeout=self.timeout) if self.timeout else self.q.get()
            except queue.Empty:
                # straggler: producer missed the deadline — skip this slot
                self.stats.stragglers_skipped += 1
                continue
            if item is self._done:
                raise StopIteration
            return item


def sample_with_retry(sampler, graph, seeds, key,
                      stats: Optional[LoaderStats] = None, max_retries: int = 3):
    """Run a :class:`~repro.core.interface.Sampler`; on overflow grow
    the buffers the blocks' counts show past their caps
    (``sampler.grown``) and retry (one jit specialization per cap
    schedule). Returns ``(blocks, sampler)`` where the returned sampler
    carries the possibly-grown caps — callers thread it forward so
    later batches start from the grown schedule.

    This is the *eager* protocol: it forces a device->host sync on every
    batch to read the overflow flags before the optimizer step may run.
    The fused pipeline uses :class:`OverflowLedger` instead, which defers
    the check by one step so dispatch never stalls."""
    box = {"sampler": sampler}

    def attempt(_i):
        blocks = box["sampler"].sample_with_key(graph, seeds, key)
        if any(bool(b.overflow) for b in blocks):
            box["counts"] = sampled_counts(blocks)["layer_counts"]
            return None
        return blocks

    def grow(_i):
        s = box["sampler"]
        over = cap_overflows(s.caps, box["counts"], seeds)
        if stats is not None:
            stats.overflow_retries += 1
            stats.record_growth(over, s.num_layers)
        box["sampler"] = s.grown(over, seeds.shape[0])

    blocks = RetryPolicy(max_retries).run(
        attempt, grow=grow, error=SamplingOverflowError,
        describe="sampling overflow persisted after cap growth")
    return blocks, box["sampler"]


class OverflowLedger:
    """Async overflow protocol for the fused one-program train step.

    The fused step cannot eagerly check ``bool(b.overflow)`` — that would
    block the Python thread on the in-flight XLA program and re-introduce
    the host round-trip the fusion removed. Instead the step *gates* its
    parameter update on the stacked overflow flags (an overflowed batch
    is a device-side no-op) and returns the flags as a device array.

    The ledger is owned by :class:`repro.runtime.engine.TrainEngine`,
    which records each batch here, polls the flags one step late — by
    then the program has retired, so reading the scalar costs nothing —
    and replays the skipped batch with the overflowed buffers grown
    from the batch's own ``layer_counts``, which the recorded tag
    carries. The first batch under a cap schedule that has never run
    is the exception: the engine polls it at once (:meth:`flush`), so
    a schedule too small for the graph is found before its first
    update is returned, and every later poll is one step late. On a mesh the
    polled flag vector also carries the distributed step's all-to-all
    overflow (seed routing, feature/hidden exchange), so one protocol
    heals every static cap in the program.

    ``depth`` is the poll lag in recorded batches: a record only
    surfaces a replay once ``depth`` newer batches sit on top of it, so
    a pipeline with ``depth`` programs in flight never blocks the host
    on an unretired program. The serial engine uses the historical
    ``depth=1`` (poll the previous batch); the pipelined driver
    (:mod:`repro.runtime.pipeline`) dispatches compute programs in
    batch order through the same ``record``/``flush`` protocol, which
    is what keeps the order of *applied* updates — and therefore the
    replayed-batch off-by-one — identical to the serial trace at any
    pipeline depth.
    """

    def __init__(self, stats: Optional[LoaderStats] = None, depth: int = 1):
        if depth < 1:
            raise ValueError(f"ledger depth must be >= 1, got {depth}")
        self.stats = stats or LoaderStats()
        self.depth = depth
        self._pending: deque = deque()  # (tag, flags), oldest first

    def record(self, tag, flags):
        """Register batch ``tag`` with its device-side overflow flags.
        Returns the tag of the oldest batch that fell out of the
        ``depth``-deep window if it overflowed and must be replayed,
        else None."""
        self._pending.append((tag, flags))
        if len(self._pending) > self.depth:
            return self._overflowed(self._pending.popleft())
        return None

    def flush(self):
        """Drain the window after the last step: poll every still-pending
        batch, oldest first. Returns the first overflowed tag (callers
        re-invoke until None — a replayed batch is re-recorded by the
        replay dispatch itself, never left pending here)."""
        while self._pending:
            due = self._overflowed(self._pending.popleft())
            if due is not None:
                return due
        return None

    def _overflowed(self, entry):
        if entry is None:
            return None
        tag, flags = entry
        flags = np.asarray(flags).reshape(-1)
        if not flags.any():
            return None
        self.stats.overflow_replays += 1
        by = self.stats.overflow_by_layer
        by.extend([0] * (flags.size - len(by)))
        for i in np.flatnonzero(flags):
            by[i] += 1
        return tag
