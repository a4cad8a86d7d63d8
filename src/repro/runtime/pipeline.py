"""Pipelined training driver: sample-ahead execution of the staged
step decomposition.

The fused one-program step (runtime/engine.py) leaves nothing for the
host to overlap: one dispatch per batch, one program on device. But the
sampling half is salt-only — stateless in the parameters — so batch
t+1's frontier can be built while batch t is still training. This
driver runs the engine's staged programs (:attr:`TrainEngine.staged`)
ahead of each other:

``prefetch``
    Two programs per batch. ``sample(t+1)`` is dispatched before
    ``compute_gather(t)``'s result is consumed, so the sampler's
    hash/select work for the next batch queues behind the current
    update instead of serializing after it.

``full``
    Three programs per batch with double-buffered gathers:
    ``sample(t+2)`` and ``gather(t+1)`` are in flight while
    ``compute(t)`` trains. On a mesh this puts the input-feature
    all-to-all (the |V^L|-sized exchange LABOR shrinks) on its own
    program, off the update's critical path; per-layer hidden
    exchanges stay inside ``compute`` (hard data dependency) where
    XLA overlaps them with the previous layer's apply, and the
    gradient all-reduce with the Adam epilogue.

Correctness bar (tests/test_pipeline.py): sampled sets are bit-exact
vs the serial engine — the staged sample program inlines the identical
sampling trace — and parameters match to fp tolerance (splitting the
program changes XLA fusion boundaries, hence rounding, nothing else).

Overflow protocol
-----------------
The driver owns an :class:`~repro.data.gnn_loader.OverflowLedger` with
poll lag 1 over *compute dispatches* (not driver steps). Because
computes retire FIFO in batch order through the same record/poll
protocol as the serial engine (``TrainEngine._settle``), the order of
applied updates — each overflowed batch is a gated device-side no-op,
replayed after the NEXT batch's update, or at once where it was the
first batch under its cap schedule — is identical to the serial trace
at any pipeline depth::

    serial   : u(t+1), replay(t), u(t+2), ...
    pipelined: u(t+1), replay(t), u(t+2), ...   (same, by construction)

A replay grows the cap schedule (``engine.grow``), which
invalidates every still-queued in-flight batch: their block buffers
were sampled at the old caps and the rebuilt compute program cannot
consume them. :meth:`_invalidate` re-samples them with the grown
sampler — exactly what the serial engine would have done, since it
samples every post-replay batch with the grown caps. Sampled sets are
unchanged by regrowth (salt-determined, cap-independent), so parity
survives invalidation; ``stats.pipeline_invalidations`` counts the
re-dispatches.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, List, Optional, Tuple

from repro.data.gnn_loader import OverflowLedger
from repro.runtime.engine import EngineData, EngineState, TrainEngine

MODES = ("prefetch", "full")


@dataclasses.dataclass
class _InFlight:
    """One sampled-ahead batch: the host-side record the driver needs
    to retire (compute), replay (seeds/key/sampler-at-sampling-time),
    or invalidate (re-sample after a cap regrowth) it."""
    seeds: Any
    key: Any
    tag: Any
    sampler: Any          # engine.sampler at sample-dispatch time
    blocks: Any           # single-host: tuple[SampledLayer]; mesh: bnd dict
    gathered: Any = None  # full mode: gather-stage outputs
    extras: Any = None    # mesh: frontier tuple (m["frontiers"])


class PipelinedEngine:
    """Drives a :class:`TrainEngine`'s staged programs with up to
    ``depth`` batches sampled ahead of the compute at the head of the
    queue. Construct one per engine; route all training steps through
    it (mixing with ``engine.step`` would interleave two ledgers).

    ``depth`` defaults to 1 for ``prefetch`` (one batch sampled ahead)
    and 2 for ``full`` (sample t+2 / gather t+1 / compute t).
    """

    def __init__(self, engine: TrainEngine, mode: str = "prefetch",
                 depth: Optional[int] = None):
        if mode not in MODES:
            raise ValueError(f"pipeline mode must be one of {MODES}, "
                             f"got {mode!r}")
        self.engine = engine
        self.mode = mode
        self.depth = depth if depth is not None else (1 if mode == "prefetch"
                                                     else 2)
        if self.depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {self.depth}")
        self.stats = engine.stats
        # poll lag 1 over compute dispatches == the serial protocol; a
        # deeper lag would reorder replays past newer updates and break
        # parity with the serial trace (see module docstring)
        self._ledger = OverflowLedger(engine.stats, depth=1)
        self._queue: deque = deque()
        self._sample_dispatches = 0

    @property
    def in_flight(self) -> int:
        """Batches sampled but not yet retired by a compute dispatch."""
        return len(self._queue)

    # -- stage dispatch -------------------------------------------------

    def _sample(self, data: EngineData, seeds, key) -> Tuple[Any, Any]:
        inj = self.engine.inject
        if inj is not None and inj.armed("stall_stage"):
            spec = inj.fires("stall_stage", self._sample_dispatches)
            if spec is not None:
                # a stalled sample stage: the pipeline must absorb the
                # bubble without corrupting the FIFO retire order
                import time
                time.sleep(spec.effect)
        self._sample_dispatches += 1
        st = self.engine.staged
        if self.engine.mesh is None:
            return st.sample(data.graph, seeds, key), None
        bnd, fronts = st.sample(data.indptr, data.indices, data.labels,
                                seeds, key)
        return bnd, fronts

    def _gather(self, data: EngineData, ent: _InFlight):
        st = self.engine.staged
        if self.engine.mesh is None:
            return st.gather(data.features, data.labels, ent.blocks)
        return st.gather(data.features, ent.blocks)

    def _compute(self, params, state: EngineState, data: EngineData,
                 ent: _InFlight):
        st = self.engine.staged
        self.engine.dispatches += 1
        guarded = self.engine.guard is not None
        if self.engine.mesh is None:
            if self.mode == "full":
                feats, labels = ent.gathered
                if guarded:
                    params, opt, g, m = st.compute(params, state.opt,
                                                   state.guard, ent.blocks,
                                                   feats, labels)
                else:
                    params, opt, m = st.compute(params, state.opt,
                                                ent.blocks, feats, labels)
                    g = state.guard
            else:
                if guarded:
                    params, opt, g, m = st.compute_gather(
                        params, state.opt, state.guard, data.features,
                        data.labels, ent.blocks)
                else:
                    params, opt, m = st.compute_gather(
                        params, state.opt, data.features, data.labels,
                        ent.blocks)
                    g = state.guard
            return params, EngineState(opt=opt, err=state.err, guard=g), m
        if self.mode == "full":
            feats_in, f_ovf = ent.gathered
            if guarded:
                params, opt, err, g, m = st.compute(
                    params, state.opt, state.err, state.guard, data.labels,
                    ent.blocks, feats_in, f_ovf)
            else:
                params, opt, err, m = st.compute(params, state.opt,
                                                 state.err, data.labels,
                                                 ent.blocks, feats_in, f_ovf)
                g = state.guard
        else:
            if guarded:
                params, opt, err, g, m = st.compute_gather(
                    params, state.opt, state.err, state.guard,
                    data.features, data.labels, ent.blocks)
            else:
                params, opt, err, m = st.compute_gather(
                    params, state.opt, state.err, data.features,
                    data.labels, ent.blocks)
                g = state.guard
        m["frontiers"] = ent.extras
        return params, EngineState(opt=opt, err=err, guard=g), m

    # -- driver protocol ------------------------------------------------

    def _enqueue(self, data: EngineData, seeds, key, tag):
        blocks, extras = self._sample(data, seeds, key)
        ent = _InFlight(seeds=seeds, key=key, tag=tag,
                        sampler=self.engine.sampler, blocks=blocks,
                        extras=extras)
        if self.mode == "full":
            ent.gathered = self._gather(data, ent)
        self._queue.append(ent)

    def _retire(self, params, state, data, done: List[Tuple[Any, Any]]):
        """Pop the oldest in-flight batch, dispatch its compute, and run
        the record/poll/replay protocol — the serial engine's step body
        with the sampling already in flight."""
        ent = self._queue.popleft()
        params, state, m = self._compute(params, state, data, ent)
        params, state, m = self.engine._settle(
            self._ledger, params, state, data,
            (ent.seeds, ent.key, ent.tag, ent.sampler), m)
        done.append((ent.tag, m))
        self._invalidate(data)
        return params, state

    def _invalidate(self, data: EngineData):
        """Re-sample every queued batch whose blocks were built at a
        now-stale cap schedule (a replay called ``engine.grow()``).
        Matches the serial engine, which samples all post-replay batches
        with the grown caps; sampled sets are salt-determined so the
        parity contract is unaffected."""
        for i, ent in enumerate(self._queue):
            if ent.sampler is self.engine.sampler:
                continue
            self.stats.pipeline_invalidations += 1
            blocks, extras = self._sample(data, ent.seeds, ent.key)
            fresh = _InFlight(seeds=ent.seeds, key=ent.key, tag=ent.tag,
                              sampler=self.engine.sampler, blocks=blocks,
                              extras=extras)
            if self.mode == "full":
                fresh.gathered = self._gather(data, fresh)
            self._queue[i] = fresh

    def step(self, params, state: EngineState, data: EngineData, seeds, key,
             tag: Any = None):
        """Feed one batch into the pipeline. Returns ``(params, state,
        done)`` where ``done`` is a list of ``(tag, metrics)`` for every
        batch whose compute was dispatched this call — empty while the
        pipeline fills (the first ``depth`` calls), one entry per call
        in steady state. Replay metrics land in ``engine.replayed``,
        exactly as on the serial path.

        Retire BEFORE enqueue. The retire path ends in a host sync (the
        ledger polls the retired compute's overflow flag), so on a
        single execution stream enqueue-first orders the device queue
        ``sample(t), compute(t-1)`` and the poll of compute(t-1) then
        waits behind the whole of sample(t) — the pipeline runs *slower*
        than the serial fused step. Retiring first keeps the poll
        adjacent to its compute while preserving the identical FIFO
        compute order, fill/steady done schedule, and replay protocol;
        it also detects a replay before this call's sample, saving one
        stale-caps invalidation."""
        done: List[Tuple[Any, Any]] = []
        while len(self._queue) >= self.depth:
            params, state = self._retire(params, state, data, done)
        self._enqueue(data, seeds, key, tag)
        return params, state, done

    def flush(self, params, state: EngineState, data: EngineData):
        """Drain the pipeline: retire every in-flight batch, then drain
        the ledger window (end of training, or before persisting a
        checkpoint — a gated no-op batch must be replayed before its
        params are saved). Returns ``(params, state, done)``."""
        done: List[Tuple[Any, Any]] = []
        while self._queue:
            params, state = self._retire(params, state, data, done)
        while True:
            due = self._ledger.flush()
            if due is None:
                break
            params, state, _ = self.engine._replay(params, state, data, *due)
        return params, state, done

    def reset(self):
        """Drop every in-flight batch and the ledger window without
        retiring them (the guardrail's rollback path: the queued samples
        belong to a discarded trajectory; the trainer re-feeds from the
        restored step)."""
        self._queue.clear()
        self._ledger = OverflowLedger(self.engine.stats, depth=1)
        self.engine.reset_protocol()
