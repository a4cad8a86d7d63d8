"""One training engine: the fused sample→gather→fwd/bwd→optimizer step,
assembled once and shared by single-host training, the partitioned
multi-device path, and serving.

:class:`TrainEngine` is the only place a GNN train/infer step is built.
Constructed from ``(sampler, model_apply, optimizer, mesh | None)``:

* ``mesh=None`` lowers to exactly the single-device one-program step of
  docs/pipeline.md — multi-layer sampling, feature gather, fwd/bwd and
  the Adam update in one jitted XLA program with donated buffers and the
  async (gated-update) overflow protocol.

* on a mesh the same iteration runs under ONE ``shard_map`` over the
  destination-owned modulo partitioning of ``repro.graph.partition``:

    1. **Seed routing.** Each layer's frontier is routed to the owner of
       each vertex (``v % P``) with a fixed-capacity all-to-all and
       deduplicated there — so every vertex is sampled exactly once,
       partition-locally, against the partitioned CSR. No device holds
       the global topology.
    2. **Partition-local LABOR.** ``Sampler.sample_layer_partitioned``
       runs the registry sampler on the owner's local CSR with GLOBAL
       vertex ids: the stateless hash r_t is a function of the global
       id, so LABOR's cross-seed correlation — the paper's
       vertex-efficiency — holds across partitions with zero extra
       communication, and the union of the per-partition sampled sets is
       bit-identical to the single-device trace. Batch-global state
       (importance pi, LADIES column norms) is completed with one
       pmax/psum per iteration.
    3. **Feature / hidden exchange.** Input features come from the
       modulo-partitioned feature array via
       ``distributed.feature_exchange.exchange_features``; between GNN
       layers the hidden states cross partitions through the same
       fixed-capacity all-to-all (owners scatter their outputs into an
       owned-row buffer, consumers fetch by global id).
    4. **Gradient all-reduce.** Per-partition gradients are mean-reduced
       (optionally bf16/int8-compressed with error feedback) and the
       replicated Adam update is applied identically everywhere.

  Every static cap in the distributed step — LayerCaps AND the per-peer
  all-to-all caps (``SamplerSpec.peer_caps``) — comes from the sampler
  registry, and every overflow (sampling, seed routing, feature or
  hidden exchange) feeds the same stacked flag vector, so one protocol
  covers them all: the update is gated on device, the engine-owned
  ledger polls the flags one step late, and the batch is replayed with
  ``Sampler.grown`` caps (on a mesh, whose step reports no per-layer
  counts, every cap doubles).

The paper connection: LABOR's ~7x reduction in sampled vertices
(Table 2) multiplies directly into the bytes of every one of these
all-to-alls — the collective that dominates distributed GNN training.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P_

from repro import ops as graph_ops
from repro.core.interface import (Sampler, cap_overflows, overflow_flags,
                                  sampled_counts)
from repro.data.gnn_loader import (LoaderStats, OverflowLedger,
                                   SamplingOverflowError)
from repro.runtime import spans
from repro.runtime.guard import (GuardConfig, RetryPolicy, guard_update,
                                 init_guard_state)
from repro.distributed import compression as comp
from repro.distributed.feature_exchange import (exchange_features,
                                                request_layout)
from repro.graph.csr import Graph
from repro.graph.partition import partition_features, partition_graph
from repro.models import gnn as gnn_models
from repro.optim import adam


def gather_feats(features: jax.Array, block) -> jax.Array:
    """Single-host feature gather: rows of the replicated feature matrix
    for a block's ``next_seeds``. Padding slots (-1) are served by the
    gather's fill value — they never read a feature row from HBM, where
    the old ``features[idx] * mask`` fetched row 0 for every padding
    slot and then multiplied it away."""
    return jnp.take(features, block.next_seeds, axis=0, mode="fill",
                    fill_value=0)


def gnn_loss_fn(apply_fn, params, blocks, feats, labels, backend=None):
    """Masked mean NLL + accuracy over a sampled block list."""
    logits = apply_fn(params, blocks, feats, backend=backend)
    valid = blocks[0].seeds >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    nll = jnp.where(valid, lse - gold, 0.0)
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)
    acc = jnp.sum((jnp.argmax(logits, -1) == safe) & valid) / jnp.maximum(
        jnp.sum(valid), 1)
    return loss, acc


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineData:
    """Step-invariant device inputs, prepared once by
    :meth:`TrainEngine.make_data`.

    Single-host: ``graph`` is the replicated CSR, ``features``/``labels``
    the full [V, F]/[V] arrays. Distributed: ``indptr``/``indices`` are
    the stacked per-partition CSR ([P, max_local_v + 1]/[P, max_local_e],
    sharded one row per device), ``features``/``labels`` the modulo-
    partitioned rows ([P * per, F]/[P * per], owner ``v % P`` holding row
    ``v // P``); ``graph`` is None — no replicated topology exists.
    """
    graph: Optional[Graph]
    indptr: Optional[jax.Array]
    indices: Optional[jax.Array]
    features: jax.Array
    labels: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineState:
    """Optimizer state plus the gradient-compression error feedback
    (``err`` is None when compression is off) and the guardrail's loss
    EMA (``guard`` is None unless the engine was built with a
    :class:`~repro.runtime.guard.GuardConfig` — see docs/robustness.md).
    All three ride in checkpoints."""
    opt: Any
    err: Any
    guard: Any = None


def _guard_gate(guard_cfg, loss, grads, gstate, any_ovf):
    """The traced guard hook every train epilogue shares: returns
    ``(bad, gstate', extra_metrics)`` where ``bad`` extends the overflow
    gate with the guard's [nonfinite, spike] flags. With the guard off
    this is the identity on the overflow protocol — the lowered program
    is byte-identical to the unguarded build."""
    if guard_cfg is None:
        return any_ovf, None, {}
    gflags, gstate_out = guard_update(guard_cfg, loss, grads, gstate,
                                      any_ovf)
    return any_ovf | jnp.any(gflags), gstate_out, {"guard_flags": gflags}


def _flat_axis_index(mesh, axes):
    """This device's position along the flattened mesh axes (= its
    partition id), inside shard_map."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _route_to_owners(ids: jax.Array, num_parts: int, per_peer_cap: int,
                     axis_name, owned_cap: int, v_local: int,
                     my_part: jax.Array):
    """Send each padded global id (-1 pad) to its owner (``v % P``) via a
    fixed-capacity all-to-all and deduplicate there.

    Returns (owned ids int32[owned_cap] — global ids, sorted by local
    row, -1 pad; owned local rows int32[owned_cap]; owned count int32[];
    overflow bool[] — send-side per-peer cap or receive-side dedup
    buffer exceeded, local to this device).
    """
    # send side: the same owner-grouping layout as the feature fetch
    # (request_layout already speaks the modulo convention, and its
    # local-row payload IS the id in the owner's space)
    req_rows, _, send_ovf = request_layout(ids, num_parts, per_peer_cap,
                                           v_local, owner_mode="mod")
    incoming = jax.lax.all_to_all(
        req_rows[None], axis_name, split_axis=1, concat_axis=0,
        tiled=False)[:, 0].reshape(-1)
    # owner-side dedup through the same frontier primitive the sampler
    # epilogue uses: unique incoming local rows, ASCENDING — an order
    # that, unlike arrival order, is deterministic across replays — in
    # O(received) work instead of a dense membership scan over every
    # owned row of the partition
    dd = graph_ops.hash_dedup(incoming, incoming >= 0, None, owned_cap)
    local_rows = dd.new
    owned = jnp.where(local_rows >= 0,
                      local_rows * num_parts + my_part, -1).astype(jnp.int32)
    ovf = send_ovf | dd.overflow
    return owned, jnp.where(local_rows >= 0, local_rows, 0), dd.num_new, ovf


def _scatter_owned_rows(rows: jax.Array, valid: jax.Array, values: jax.Array,
                        v_local: int) -> jax.Array:
    """Scatter per-seed values into a dense (v_local, F) owned-row buffer
    (the response table of a subsequent modulo all-to-all fetch)."""
    rows_eff = jnp.where(valid, rows, v_local)  # invalid -> dropped (OOB)
    out = jnp.zeros((v_local, values.shape[-1]), values.dtype)
    return out.at[rows_eff].set(values, mode="drop")


def _owned_cap_schedule(spec, P: int):
    """Owner-side seed buffer caps per layer + the deep-frontier cap.

    Bounded by what the all-to-all can deliver, kept under the layer's
    vertex buffer so next_seeds retains headroom for newly sampled
    vertices (both double together on overflow replay)."""
    caps, peer, L = spec.caps, spec.peer_caps, spec.num_layers
    owned_caps = [min(P * peer[l], max(caps[l].vertex_cap // 2, 8))
                  for l in range(L)]
    deep_cap = min(P * peer[L], caps[-1].vertex_cap)
    return owned_caps, deep_cap


def _route_and_sample(sampler, mesh, axes, P: int, graph_l: Graph,
                      v_local: int, my_part, seeds, salts, *,
                      with_deep: bool):
    """The partitioned sampling half: per layer, route the frontier to
    its owners (``v % P``) and run the registry sampler partition-
    locally with GLOBAL ids; optionally dedup the deepest frontier at
    its owners (``with_deep`` — train only: |V^L| is the paper's
    headline metric and the engine-parity comparison set).

    Shared verbatim by the serial one-program step and the staged
    sample program (runtime/pipeline.py) so their sampled sets are
    bit-identical by construction. Returns (blocks, owned_rows,
    route_ovf, frontiers, deep_n — None unless ``with_deep``)."""
    spec = sampler.spec
    L = spec.num_layers
    peer = spec.peer_caps
    owned_caps, deep_cap = _owned_cap_schedule(spec, P)
    blocks, owned_rows, route_ovf, frontiers = [], [], [], []
    frontier = seeds
    for l in range(L):
        owned, rows, _, r_ovf = _route_to_owners(
            frontier, P, peer[l], axes, owned_caps[l], v_local, my_part)
        blk = sampler.sample_layer_partitioned(
            graph_l, owned, salts[l], l, seed_rows=rows,
            num_vertices=P * v_local, axis_name=axes)
        blocks.append(blk)
        owned_rows.append(rows)
        route_ovf.append(r_ovf)
        frontiers.append(owned)
        frontier = blk.next_seeds
    deep_n = None
    if with_deep:
        deep_owned, _, deep_n, deep_ovf = _route_to_owners(
            frontier, P, peer[L], axes, deep_cap, v_local, my_part)
        frontiers.append(deep_owned)
        route_ovf.append(deep_ovf)
    return blocks, owned_rows, route_ovf, frontiers, deep_n


def _forward_partitioned(layer_fn, params, blocks, owned_rows, h, peer,
                         axes, v_local: int, backend):
    """Partitioned multi-layer forward: between GNN layers the hidden
    states cross partitions through the fixed-capacity all-to-all
    (owners scatter their outputs into an owned-row buffer, consumers
    fetch by global id). Returns (logits, hidden-exchange overflow
    flags). Shared by the serial program and the staged compute
    program."""
    L = len(blocks)
    h_ovfs = []
    for b in range(L - 1, -1, -1):
        h = layer_fn(params["layers"][L - 1 - b], blocks[b], h,
                     is_last=b == 0, backend=backend)
        if b > 0:
            dense = _scatter_owned_rows(
                owned_rows[b], blocks[b].seeds >= 0, h, v_local)
            h, ovf_h = exchange_features(
                dense, blocks[b - 1].next_seeds, axes, peer[b],
                owner_mode="mod")
            h_ovfs.append(ovf_h)
    return h, h_ovfs


@dataclasses.dataclass(frozen=True)
class StagedFns:
    """The fused step split at its stage boundaries — the jitted
    programs the pipeline driver (:mod:`repro.runtime.pipeline`)
    dispatches ahead of each other. Built per cap schedule by
    :attr:`TrainEngine.staged`; ``pipeline=off`` never builds these
    (the serial path lowers to the single fused program unchanged).

    Single-host signatures::

        sample(graph, seeds, key)                     -> blocks
        gather(features, labels_all, blocks)          -> (feats, labels)
        compute(params, opt, blocks, feats, labels)   -> (params, opt, m)
        compute_gather(params, opt, features,
                       labels_all, blocks)            -> (params, opt, m)

    Distributed (per-device boundary leaves carry a leading axis of 1
    so one ``P_(ax)`` prefix spec moves the whole pytree between
    shard_map programs)::

        sample(indptr, indices, labels, seeds, key)   -> (bnd, frontiers)
        gather(features, bnd)                         -> (feats_in, f_ovf)
        compute(params, opt, err, labels, bnd,
                feats_in, f_ovf)                      -> (p, o, e, m)
        compute_gather(params, opt, err, features,
                       labels, bnd)                   -> (p, o, e, m)

    ``compute_gather`` (the ``prefetch`` mode) folds the feature
    gather/exchange into the update program; ``gather`` + ``compute``
    (the ``full`` mode) double-buffer it as its own program."""
    sample: Callable
    gather: Callable
    compute: Callable
    compute_gather: Callable


class TrainEngine:
    """The one train/infer step builder (see module docstring).

    Usage::

        eng = TrainEngine(sampler, apply_fn, opt_cfg, mesh=mesh_or_None)
        data = eng.make_data(graph, features, labels)
        state = eng.init_state(params)
        for seeds in batches:
            params, state, m = eng.step(params, state, data, seeds, key)
        params, state, _ = eng.flush(params, state, data)  # drain ledger

    ``step`` owns the async overflow protocol end to end: it dispatches
    the fused program, records the device-resident overflow flags in the
    engine's ledger, polls the PREVIOUS batch's flags (already retired —
    free), and replays an overflowed batch with ``Sampler.grown`` caps
    — sampling-cap and all-to-all-cap overflow alike. The first batch
    under a cap schedule that has never run is polled before ``step``
    returns. Replay metrics are appended to :attr:`replayed` as
    ``(tag, metrics)`` for callers that keep step-indexed histories.

    On a mesh the sampler must carry ``spec.peer_caps`` (build it with
    ``samplers.from_graph_stats(..., num_parts=P)`` and the DEVICE-LOCAL
    batch size); ``model_apply`` must be a registered per-layer model
    (``repro.models.gnn.LAYER_FNS``).
    """

    def __init__(self, sampler: Sampler, model_apply: Callable,
                 opt_cfg: adam.AdamConfig, mesh=None, *,
                 backend: Optional[str] = None, grad_compression: str = "none",
                 max_replay_retries: int = 3,
                 stats: Optional[LoaderStats] = None,
                 guard: Optional[GuardConfig] = None,
                 inject: Any = None):
        self.sampler = sampler
        self.model_apply = model_apply
        self.opt_cfg = opt_cfg
        self.mesh = mesh
        # guardrail: when set, every train program additionally computes
        # the [nonfinite, spike] flag pair, gates the update on it (a
        # flagged batch is a device-side no-op, like an overflowed one)
        # and returns it in m["guard_flags"]; the step signatures gain a
        # guard-state arg. None leaves every program byte-identical to
        # the historical build.
        self.guard = guard
        # fault-injection plan (repro.runtime.inject.FaultPlan); the
        # engine owns the overflow_storm site — see _read_overflow
        self.inject = inject
        # dispatched train programs (tests assert a clean guarded run
        # adds zero dispatches over an unguarded one)
        self.dispatches = 0
        self._ovf_reads = 0
        # the graph-ops backend ("auto"/None resolves by platform HERE,
        # once — every step this engine builds, single-host or
        # partitioned, runs the same resolved MODEL primitive set, and
        # the resolved name lands in checkpoint engine_restore_meta).
        # The sampling half's frontier primitives are NOT governed by
        # this flag: they dispatch auto-by-platform inside the sample
        # trace, which is safe to leave unpinned because their backends
        # are bit-identical (docs/kernels.md, "Backend selection
        # boundary")
        self.backend = graph_ops.resolve_backend(backend)
        self.comp_cfg = comp.CompressionConfig(grad_compression)
        self.max_replay_retries = max_replay_retries
        self.stats = stats or LoaderStats()
        self.replayed: List[Tuple[Any, Dict[str, Any]]] = []
        self._ledger = OverflowLedger(self.stats)
        # the sampler (cap schedule) a batch has run under without
        # overflow; a batch under any other is polled at once (_settle)
        self._proven: Optional[Sampler] = None
        self._step = None
        self._infer = None
        self._staged = None
        self._infer_cached: Dict[Any, Callable] = {}
        # program generation: bumped by grow(), so serving drivers can
        # tag the next dispatch of each program as a fresh compile and
        # know when to invalidate device caches keyed to the old shapes
        self.generation = 0
        if mesh is not None:
            self.axes = tuple(mesh.axis_names)
            self.num_parts = 1
            for a in self.axes:
                self.num_parts *= mesh.shape[a]
            self._layer_fn = gnn_models.LAYER_FNS.get(model_apply)
            if self._layer_fn is None:
                raise ValueError(
                    "distributed engine needs a per-layer model "
                    "(repro.models.gnn.LAYER_FNS); got "
                    f"{getattr(model_apply, '__name__', model_apply)!r}")
            if sampler.spec.peer_caps is None:
                raise ValueError(
                    f"sampler {sampler.name!r} has no per-peer all-to-all "
                    "caps; build it with samplers.from_graph_stats(..., "
                    f"num_parts={self.num_parts}) for the distributed "
                    "engine")
        else:
            self.axes = None
            self.num_parts = 1

    # ------------------------------------------------------------------
    # state / data preparation
    # ------------------------------------------------------------------

    def init_state(self, params) -> EngineState:
        return EngineState(opt=adam.init_state(params, self.opt_cfg),
                           err=comp.init_error_state(params, self.comp_cfg),
                           guard=(None if self.guard is None
                                  else init_guard_state()))

    def make_data(self, graph: Graph, features, labels) -> EngineData:
        """Stage the step-invariant inputs on device: replicated arrays
        on a single host, owner-partitioned (graph CSR, feature rows,
        label rows — all modulo ``v % P``) on a mesh."""
        if self.mesh is None:
            return EngineData(graph=graph, indptr=None, indices=None,
                              features=jnp.asarray(features),
                              labels=jnp.asarray(labels))
        if graph.weights is not None:
            raise NotImplementedError(
                "the partitioned engine does not thread edge weights yet")
        P = self.num_parts
        pg = partition_graph(graph, P)
        per = -(-graph.num_vertices // P)
        feats = np.asarray(features)
        pf = partition_features(feats, P).reshape(P * per, feats.shape[1])
        lab = np.asarray(labels)
        pl = np.zeros((P, per), lab.dtype)
        for p in range(P):
            rows = np.arange(p, graph.num_vertices, P)
            pl[p, : rows.size] = lab[rows]
        ax = self._ax_spec()
        row_sh = NamedSharding(self.mesh, P_(ax, None))
        vec_sh = NamedSharding(self.mesh, P_(ax))
        return EngineData(
            graph=None,
            indptr=jax.device_put(jnp.asarray(pg.indptr), row_sh),
            indices=jax.device_put(jnp.asarray(pg.indices), row_sh),
            features=jax.device_put(jnp.asarray(pf), row_sh),
            labels=jax.device_put(jnp.asarray(pl.reshape(-1)), vec_sh),
        )

    def make_data_from_dataset(self, ds) -> EngineData:
        return self.make_data(ds.graph, ds.features, ds.labels)

    def _ax_spec(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    # ------------------------------------------------------------------
    # step construction
    # ------------------------------------------------------------------

    @property
    def step_fn(self):
        """The raw fused train step (one jit specialization per cap
        schedule). Single-host signature — unchanged from the original
        fused trainer:

            step(params, opt_state, graph, features, labels_all, seeds,
                 key) -> (params, opt_state, metrics)

        distributed signature (donated params/opt/err; all-to-all caps
        live on the sampler spec):

            step(params, opt_state, err, indptr, indices, features,
                 labels, seeds, key) -> (params, opt_state, err, metrics)
        """
        if self._step is None:
            self._step = (self._build_single_train() if self.mesh is None
                          else self._build_distributed(train=True))
        return self._step

    @property
    def guarded(self) -> bool:
        return self.guard is not None

    @property
    def infer_fn(self):
        """Fused sample + gather + forward, from the same sampler object.

        Single-host: ``infer(params, graph, features, seeds, key) ->
        (logits, overflow_flags, layer_counts)`` — exact with the
        ``full`` registry entry, sampled otherwise; ``layer_counts``
        (int32[L, 3], :func:`sampled_counts`) size a :meth:`grow` after
        an overflow. Distributed: ``infer(params, indptr,
        indices, features, seeds, key) -> (owned_seeds, logits, flags)``
        where row i of ``logits`` answers global vertex
        ``owned_seeds[i]`` (each device returns its owned share of the
        batch).
        """
        if self._infer is None:
            self._infer = (self._build_single_infer() if self.mesh is None
                           else self._build_distributed(train=False))
        return self._infer

    def _single_stages(self):
        """The single-host step's stages as traceable functions, each
        under its stage scope (``repro.runtime.spans``): the one
        definition the fused program and the staged programs share.

        ``sample(graph, seeds, key) -> blocks``,
        ``gather(features, labels_all, blocks) -> (feats, labels)``,
        ``epilogue(params, opt, gstate, blocks, feats, labels) ->
        (params, opt, gstate, metrics)``: the loss and its gradient
        under ``model``, then the Adam update, the overflow/guard gate
        and the step's metrics under ``optimizer``."""
        sampler, apply_fn = self.sampler, self.model_apply
        opt_cfg, backend, guard_cfg = self.opt_cfg, self.backend, self.guard

        def sample(graph, seeds, key):
            with jax.named_scope(spans.SAMPLE):
                return tuple(sampler.sample(graph, seeds,
                                            sampler.spec.salts(key)))

        def gather(features, labels_all, blocks):
            with jax.named_scope(spans.FEATURE_GATHER):
                feats = gather_feats(features, blocks[-1])
                seeds = blocks[0].seeds
                labels = labels_all[jnp.where(seeds >= 0, seeds, 0)]
            return feats, labels

        def epilogue(params, opt_state, gstate, blocks, feats, labels):
            def loss_fn(p):
                with jax.named_scope(spans.MODEL):
                    return gnn_loss_fn(apply_fn, p, blocks, feats, labels,
                                       backend)

            (loss, acc), grads = jax.value_and_grad(loss_fn,
                                                    has_aux=True)(params)
            with jax.named_scope(spans.OPTIMIZER):
                new_params, new_opt, m = adam.apply_updates(
                    params, grads, opt_state, opt_cfg)
                ovf = overflow_flags(blocks)
                any_ovf = jnp.any(ovf)
                bad, gstate_out, gm = _guard_gate(guard_cfg, loss, grads,
                                                  gstate, any_ovf)
                gate = lambda new, old: jnp.where(bad, old, new)
                params_out = jax.tree.map(gate, new_params, params)
                opt_out = jax.tree.map(gate, new_opt, opt_state)
                m.update(loss=loss, acc=acc, overflow=ovf, **gm,
                         **sampled_counts(blocks))
            return params_out, opt_out, gstate_out, m

        return sample, gather, epilogue

    def _build_single_train(self):
        sample, gather, epilogue = self._single_stages()

        def body(params, opt_state, gstate, graph, features, labels_all,
                 seeds, key):
            blocks = sample(graph, seeds, key)
            feats, labels = gather(features, labels_all, blocks)
            return epilogue(params, opt_state, gstate, blocks, feats, labels)

        if self.guard is None:
            @partial(jax.jit, donate_argnums=(0, 1))
            def step(params, opt_state, graph, features, labels_all, seeds,
                     key):
                p, o, _, m = body(params, opt_state, None, graph, features,
                                  labels_all, seeds, key)
                return p, o, m

            return step

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def gstep(params, opt_state, gstate, graph, features, labels_all,
                  seeds, key):
            return body(params, opt_state, gstate, graph, features,
                        labels_all, seeds, key)

        return gstep

    def _build_single_infer(self):
        sampler, apply_fn = self.sampler, self.model_apply
        backend = self.backend

        @jax.jit
        def infer(params, graph, features, seeds, key):
            blocks = sampler.sample(graph, seeds, sampler.spec.salts(key))
            feats = gather_feats(features, blocks[-1])
            logits = apply_fn(params, blocks, feats, backend=backend)
            return (logits, overflow_flags(blocks),
                    sampled_counts(blocks)["layer_counts"])

        return infer

    def cached_infer_fn(self, feature_cache=None, hidden_cache=None):
        """The cache-aware gather hook on the infer path: the same
        fused sample + gather + forward program as :attr:`infer_fn`,
        with the feature gather routed through a device-resident
        :class:`~repro.serving.cache.VertexCache` (fetching only the
        unique cache misses from the feature store) and, optionally,
        the deepest layer's output substituted from a
        :class:`~repro.serving.cache.HiddenCache` under its staleness
        bound. Single-host only (the partitioned infer path already
        owner-shards its feature reads).

        Signature::

            infer_c(params, graph, features, fc_state, hc_state,
                    seeds, key) -> (logits, overflow_flags, layer_counts,
                                    fc_state', hc_state', cache_metrics)

        Pass ``None`` for a disabled cache's state. Feature-cache
        values are verbatim feature rows, so ``logits`` are bit-exact
        vs :attr:`infer_fn`; the hidden cache is bit-exact at
        ``max_age=0`` by construction. One program is compiled per
        (cache config, cap schedule) pair; :meth:`grow` invalidates
        them alongside the other programs.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "cached inference is single-host; the partitioned infer "
                "path reads owner-sharded features already")
        cache_key = (feature_cache, hidden_cache)
        fn = self._infer_cached.get(cache_key)
        if fn is not None:
            return fn
        sampler, apply_fn = self.sampler, self.model_apply
        backend = self.backend
        layer_fn = None
        if hidden_cache is not None:
            layer_fn = gnn_models.LAYER_FNS.get(apply_fn)
            if layer_fn is None:
                raise ValueError(
                    "the hidden-state cache needs a per-layer model "
                    "(repro.models.gnn.LAYER_FNS); got "
                    f"{getattr(apply_fn, '__name__', apply_fn)!r}")

        @jax.jit
        def infer_c(params, graph, features, fc_state, hc_state, seeds,
                    key):
            blocks = sampler.sample(graph, seeds, sampler.spec.salts(key))
            metrics = {}
            if feature_cache is not None:
                feats, fc_state_out, fm = feature_cache.gather(
                    fc_state, blocks[-1].next_seeds,
                    lambda missed: jnp.take(features, missed, axis=0,
                                            mode="fill", fill_value=0))
                metrics.update(fm)
            else:
                feats, fc_state_out = gather_feats(features, blocks[-1]), None
            if hidden_cache is None:
                logits = apply_fn(params, blocks, feats, backend=backend)
                hc_state_out = None
            else:
                L = len(blocks)
                h = feats
                for l, blk in enumerate(reversed(blocks)):
                    h = layer_fn(params["layers"][l], blk, h,
                                 is_last=l == L - 1, backend=backend)
                    if l == 0 and L > 1:
                        # deepest layer's output, keyed by its seed ids
                        h, hc_state, hm = hidden_cache.substitute(
                            hc_state, blk.seeds, h)
                        metrics.update(hm)
                logits, hc_state_out = h, hc_state
            return (logits, overflow_flags(blocks),
                    sampled_counts(blocks)["layer_counts"], fc_state_out,
                    hc_state_out, metrics)

        self._infer_cached[cache_key] = infer_c
        return infer_c

    # ------------------------------------------------------------------
    # the staged decomposition (pipeline driver programs)
    # ------------------------------------------------------------------

    @property
    def staged(self) -> StagedFns:
        """The fused step split into composable jitted stages (one
        bundle per cap schedule; invalidated by :meth:`grow` exactly
        like the fused program). Only the pipeline driver builds these
        — ``pipeline=off`` keeps dispatching :attr:`step_fn`."""
        if self._staged is None:
            self._staged = (self._build_single_stages() if self.mesh is None
                            else self._build_distributed_stages())
        return self._staged

    def _build_single_stages(self) -> StagedFns:
        # sample is salt-only: stateless in params, so batch t+1's
        # frontier can be in flight while batch t trains. Same trace as
        # the sampling half of the fused program -> bit-identical sets.
        _sample, _gather, _epilogue = self._single_stages()
        sample = jax.jit(_sample)
        gather = jax.jit(_gather)

        if self.guard is None:
            @partial(jax.jit, donate_argnums=(0, 1))
            def compute(params, opt_state, blocks, feats, labels):
                p, o, _, m = _epilogue(params, opt_state, None, blocks,
                                       feats, labels)
                return p, o, m

            @partial(jax.jit, donate_argnums=(0, 1))
            def compute_gather(params, opt_state, features, labels_all,
                               blocks):
                feats, labels = _gather(features, labels_all, blocks)
                p, o, _, m = _epilogue(params, opt_state, None, blocks,
                                       feats, labels)
                return p, o, m
        else:
            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def compute(params, opt_state, gstate, blocks, feats, labels):
                return _epilogue(params, opt_state, gstate, blocks, feats,
                                 labels)

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def compute_gather(params, opt_state, gstate, features,
                               labels_all, blocks):
                feats, labels = _gather(features, labels_all, blocks)
                return _epilogue(params, opt_state, gstate, blocks, feats,
                                 labels)

        return StagedFns(sample=sample, gather=gather, compute=compute,
                         compute_gather=compute_gather)

    def _build_distributed_stages(self) -> StagedFns:
        mesh, axes, P = self.mesh, self.axes, self.num_parts
        sampler, layer_fn = self.sampler, self._layer_fn
        opt_cfg, comp_cfg, backend = (self.opt_cfg, self.comp_cfg,
                                      self.backend)
        spec = sampler.spec
        L = spec.num_layers
        peer = spec.peer_caps
        # boundary convention: every per-device leaf crosses the stage
        # boundary with a leading axis of 1, so a single P_(ax) prefix
        # spec shards the whole pytree (scalars become (P,) globally)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        unwrap = lambda t: jax.tree.map(lambda x: x[0], t)

        def sample_body(indptr, indices, labels, seeds, salts):
            graph_l = Graph(indptr=indptr[0], indices=indices[0])
            v_local = labels.shape[0]
            my_part = _flat_axis_index(mesh, axes)
            blocks, owned_rows, route_ovf, frontiers, deep_n = (
                _route_and_sample(sampler, mesh, axes, P, graph_l, v_local,
                                  my_part, seeds, salts, with_deep=True))
            bnd = dict(
                blocks=tuple(expand(b) for b in blocks),
                owned_rows=tuple(r[None] for r in owned_rows),
                route_flags=jnp.stack(route_ovf)[None],
                # psum here: replicated by construction, read back as a
                # plain scalar metric by the compute stage
                deep_n=jax.lax.psum(deep_n, axes)[None],
            )
            return bnd, tuple(frontiers)

        def gather_body(features, bnd):
            # the input-feature all-to-all — the |V^L|-sized collective
            # LABOR shrinks — moved OFF the update's critical path
            feats_in, f_ovf = exchange_features(
                features, bnd["blocks"][-1].next_seeds[0], axes, peer[L],
                owner_mode="mod")
            return feats_in[None], f_ovf[None]

        def compute_core(params, opt_state, err, gstate, labels, bnd,
                         feats_in, f_ovf):
            blocks = [unwrap(b) for b in bnd["blocks"]]
            owned_rows = [r[0] for r in bnd["owned_rows"]]
            route_flags = bnd["route_flags"][0]
            v_local = labels.shape[0]

            valid0 = blocks[0].seeds >= 0
            labels_own = labels[jnp.where(valid0, owned_rows[0], 0)]
            total_valid = jax.lax.psum(jnp.sum(valid0.astype(jnp.int32)),
                                       axes)

            def loss_fn(p):
                logits, h_ovfs = _forward_partitioned(
                    layer_fn, p, blocks, owned_rows, feats_in, peer, axes,
                    v_local, backend)
                safe = jnp.where(valid0, labels_own, 0)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, safe[:, None],
                                           axis=-1)[:, 0]
                nll = jnp.where(valid0, lse - gold, 0.0)
                # x P so the pmean of per-device grads below equals the
                # gradient of the batch-global mean NLL
                local = jnp.sum(nll) * P / jnp.maximum(total_valid, 1)
                correct = jnp.sum((jnp.argmax(logits, -1) == safe) & valid0)
                return local, (correct, h_ovfs)

            (local_loss, (correct, h_ovfs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, new_err = comp.compressed_mean(grads, err, comp_cfg, axes)
            new_params, new_opt, m = adam.apply_updates(params, grads,
                                                        opt_state, opt_cfg)

            flags = jnp.concatenate([
                overflow_flags(blocks),
                route_flags,
                jnp.stack([f_ovf] + h_ovfs) if h_ovfs else f_ovf[None],
            ])
            ovf = jax.lax.pmax(flags.astype(jnp.int32), axes) > 0
            any_ovf = jnp.any(ovf)
            # guard math on replicated values (pmean'd loss, all-reduced
            # grads) so the flags — and the gate — agree on every device
            gloss = jax.lax.pmean(local_loss, axes)
            bad, gstate_out, gm = _guard_gate(guard_cfg, gloss, grads,
                                              gstate, any_ovf)
            gate = lambda new, old: jnp.where(bad, old, new)
            params_out = jax.tree.map(gate, new_params, params)
            opt_out = jax.tree.map(gate, new_opt, opt_state)
            err_out = jax.tree.map(gate, new_err, err)
            m.update(
                loss=gloss,
                acc=jax.lax.psum(correct, axes)
                / jnp.maximum(total_valid, 1),
                overflow=ovf,
                **gm,
                sampled_v=bnd["deep_n"][0],
                sampled_e=jax.lax.psum(sum(b.num_edges for b in blocks),
                                       axes),
            )
            return params_out, opt_out, err_out, gstate_out, m

        def compute_body(params, opt_state, err, gstate, labels, bnd,
                         feats_in_b, f_ovf_b):
            return compute_core(params, opt_state, err, gstate, labels, bnd,
                                feats_in_b[0], f_ovf_b[0])

        def compute_gather_body(params, opt_state, err, gstate, features,
                                labels, bnd):
            feats_in, f_ovf = exchange_features(
                features, bnd["blocks"][-1].next_seeds[0], axes, peer[L],
                owner_mode="mod")
            return compute_core(params, opt_state, err, gstate, labels, bnd,
                                feats_in, f_ovf)

        rep = P_()
        ax = self._ax_spec()
        row, vec, bnd_spec = P_(ax, None), P_(ax), P_(ax)
        front_specs = tuple(P_(ax) for _ in range(L + 1))

        @jax.jit
        def sample_fn(indptr, indices, labels, seeds, key):
            salts = spec.salts(key)
            return shard_map(
                sample_body, mesh=mesh,
                in_specs=(row, row, vec, vec, rep),
                out_specs=(bnd_spec, front_specs),
                check_rep=False)(indptr, indices, labels, seeds, salts)

        @jax.jit
        def gather_fn(features, bnd):
            return shard_map(
                gather_body, mesh=mesh, in_specs=(row, bnd_spec),
                out_specs=(bnd_spec, vec),
                check_rep=False)(features, bnd)

        guard_cfg = self.guard
        if guard_cfg is None:
            # unguarded bodies drop the (None) guard state inside the
            # shard_map so no None pytree crosses the spec boundary and
            # the historical 4-output signature is preserved
            def compute_body_u(params, opt_state, err, labels, bnd,
                               feats_in_b, f_ovf_b):
                p, o, e, _, m = compute_body(params, opt_state, err, None,
                                             labels, bnd, feats_in_b,
                                             f_ovf_b)
                return p, o, e, m

            def compute_gather_body_u(params, opt_state, err, features,
                                      labels, bnd):
                p, o, e, _, m = compute_gather_body(params, opt_state, err,
                                                    None, features, labels,
                                                    bnd)
                return p, o, e, m

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def compute_fn(params, opt_state, err, labels, bnd, feats_in,
                           f_ovf):
                return shard_map(
                    compute_body_u, mesh=mesh,
                    in_specs=(rep, rep, rep, vec, bnd_spec, bnd_spec, vec),
                    out_specs=(rep, rep, rep, rep),
                    check_rep=False)(params, opt_state, err, labels, bnd,
                                     feats_in, f_ovf)

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def compute_gather_fn(params, opt_state, err, features, labels,
                                  bnd):
                return shard_map(
                    compute_gather_body_u, mesh=mesh,
                    in_specs=(rep, rep, rep, row, vec, bnd_spec),
                    out_specs=(rep, rep, rep, rep),
                    check_rep=False)(params, opt_state, err, features,
                                     labels, bnd)
        else:
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def compute_fn(params, opt_state, err, gstate, labels, bnd,
                           feats_in, f_ovf):
                return shard_map(
                    compute_body, mesh=mesh,
                    in_specs=(rep, rep, rep, rep, vec, bnd_spec, bnd_spec,
                              vec),
                    out_specs=(rep, rep, rep, rep, rep),
                    check_rep=False)(params, opt_state, err, gstate, labels,
                                     bnd, feats_in, f_ovf)

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def compute_gather_fn(params, opt_state, err, gstate, features,
                                  labels, bnd):
                return shard_map(
                    compute_gather_body, mesh=mesh,
                    in_specs=(rep, rep, rep, rep, row, vec, bnd_spec),
                    out_specs=(rep, rep, rep, rep, rep),
                    check_rep=False)(params, opt_state, err, gstate,
                                     features, labels, bnd)

        return StagedFns(sample=sample_fn, gather=gather_fn,
                         compute=compute_fn, compute_gather=compute_gather_fn)

    # ------------------------------------------------------------------
    # the partition-aware distributed program
    # ------------------------------------------------------------------

    def _build_distributed(self, train: bool):
        mesh, axes, P = self.mesh, self.axes, self.num_parts
        sampler, layer_fn = self.sampler, self._layer_fn
        opt_cfg, comp_cfg, backend = (self.opt_cfg, self.comp_cfg,
                                      self.backend)
        spec = sampler.spec
        L = spec.num_layers
        peer = spec.peer_caps

        guard_cfg = self.guard

        def body(params, opt_state, err, gstate, indptr, indices, features,
                 labels, seeds, salts):
            graph_l = Graph(indptr=indptr[0], indices=indices[0])
            v_local = features.shape[0]
            my_part = _flat_axis_index(mesh, axes)

            # ---- per-layer: route frontier to owners, sample locally;
            # train additionally dedups the deepest frontier at its
            # owners (|V^L|, the paper's headline metric and the set the
            # engine-parity tests compare bit-exactly — serving has no
            # use for the extra all-to-all)
            blocks, owned_rows, route_ovf, frontiers, deep_n = (
                _route_and_sample(sampler, mesh, axes, P, graph_l, v_local,
                                  my_part, seeds, salts, with_deep=train))

            # ---- input features: the all-to-all LABOR shrinks
            feats_in, f_ovf = exchange_features(
                features, blocks[-1].next_seeds, axes, peer[L],
                owner_mode="mod")

            valid0 = blocks[0].seeds >= 0
            labels_own = labels[jnp.where(valid0, owned_rows[0], 0)]
            total_valid = jax.lax.psum(jnp.sum(valid0.astype(jnp.int32)),
                                       axes)

            def forward(p, h):
                return _forward_partitioned(layer_fn, p, blocks, owned_rows,
                                            h, peer, axes, v_local, backend)

            def collect_flags(h_ovfs):
                flags = jnp.concatenate([
                    overflow_flags(blocks),
                    jnp.stack(route_ovf),
                    jnp.stack([f_ovf] + h_ovfs) if h_ovfs
                    else f_ovf[None],
                ])
                return jax.lax.pmax(flags.astype(jnp.int32), axes) > 0

            if not train:
                logits, h_ovfs = forward(params, feats_in)
                return blocks[0].seeds, logits, collect_flags(h_ovfs)

            def loss_fn(p):
                logits, h_ovfs = forward(p, feats_in)
                safe = jnp.where(valid0, labels_own, 0)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, safe[:, None],
                                           axis=-1)[:, 0]
                nll = jnp.where(valid0, lse - gold, 0.0)
                # x P so the pmean of per-device grads below equals the
                # gradient of the batch-global mean NLL
                local = jnp.sum(nll) * P / jnp.maximum(total_valid, 1)
                correct = jnp.sum((jnp.argmax(logits, -1) == safe) & valid0)
                return local, (correct, h_ovfs)

            (local_loss, (correct, h_ovfs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, new_err = comp.compressed_mean(grads, err, comp_cfg, axes)
            new_params, new_opt, m = adam.apply_updates(params, grads,
                                                        opt_state, opt_cfg)

            ovf = collect_flags(h_ovfs)
            any_ovf = jnp.any(ovf)
            # guard math on replicated values (pmean'd loss, all-reduced
            # grads) so the flags — and the gate — agree on every device
            gloss = jax.lax.pmean(local_loss, axes)
            bad, gstate_out, gm = _guard_gate(guard_cfg, gloss, grads,
                                              gstate, any_ovf)
            gate = lambda new, old: jnp.where(bad, old, new)
            params_out = jax.tree.map(gate, new_params, params)
            opt_out = jax.tree.map(gate, new_opt, opt_state)
            err_out = jax.tree.map(gate, new_err, err)
            m.update(
                loss=gloss,
                acc=jax.lax.psum(correct, axes)
                / jnp.maximum(total_valid, 1),
                overflow=ovf,
                **gm,
                sampled_v=jax.lax.psum(deep_n, axes),
                sampled_e=jax.lax.psum(sum(b.num_edges for b in blocks),
                                       axes),
            )
            return params_out, opt_out, err_out, gstate_out, m, \
                tuple(frontiers)

        rep = P_()
        ax = self._ax_spec()
        front_specs = tuple(P_(ax) for _ in range(L + 1))
        if train and guard_cfg is not None:
            in_specs = (rep, rep, rep, rep, P_(ax, None), P_(ax, None),
                        P_(ax, None), P_(ax), P_(ax), rep)
            out_specs = (rep, rep, rep, rep, rep, front_specs)
        elif train:
            in_specs = (rep, rep, rep, P_(ax, None), P_(ax, None),
                        P_(ax, None), P_(ax), P_(ax), rep)
            out_specs = (rep, rep, rep, rep, front_specs)
        else:
            in_specs = (rep, P_(ax, None), P_(ax, None), P_(ax, None),
                        P_(ax), rep)
            out_specs = (P_(ax), P_(ax, None), rep)

        if train:
            if guard_cfg is None:
                def train_body(params, opt_state, err, indptr, indices,
                               features, labels, seeds, salts):
                    p, o, e, _, m, fronts = body(
                        params, opt_state, err, None, indptr, indices,
                        features, labels, seeds, salts)
                    return p, o, e, m, fronts

                @partial(jax.jit, donate_argnums=(0, 1, 2))
                def step(params, opt_state, err, indptr, indices, features,
                         labels, seeds, key):
                    salts = spec.salts(key)
                    sharded = shard_map(
                        train_body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_rep=False)
                    p, o, e, m, fronts = sharded(params, opt_state, err,
                                                 indptr, indices, features,
                                                 labels, seeds, salts)
                    m["frontiers"] = fronts
                    return p, o, e, m

                return step

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def gstep(params, opt_state, err, gstate, indptr, indices,
                      features, labels, seeds, key):
                salts = spec.salts(key)
                sharded = shard_map(
                    body, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_rep=False)
                p, o, e, g, m, fronts = sharded(params, opt_state, err,
                                                gstate, indptr, indices,
                                                features, labels, seeds,
                                                salts)
                m["frontiers"] = fronts
                return p, o, e, g, m

            return gstep

        def infer_body(params, indptr, indices, features, seeds, salts):
            out = body(params, None, None, None, indptr, indices, features,
                       jnp.zeros((features.shape[0],), jnp.int32), seeds,
                       salts)
            return out

        @jax.jit
        def infer(params, indptr, indices, features, seeds, key):
            salts = spec.salts(key)
            return shard_map(
                infer_body, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_rep=False)(
                params, indptr, indices, features, seeds, salts)

        return infer

    # ------------------------------------------------------------------
    # dispatch + the engine-owned async overflow/replay protocol
    # ------------------------------------------------------------------

    def _dispatch(self, params, state: EngineState, data: EngineData, seeds,
                  key):
        with jax.profiler.TraceAnnotation(spans.ENGINE_DISPATCH):
            self.dispatches += 1
            if self.mesh is None:
                if self.guard is None:
                    params, opt, m = self.step_fn(
                        params, state.opt, data.graph, data.features,
                        data.labels, seeds, key)
                    return params, EngineState(opt=opt, err=state.err), m
                params, opt, g, m = self.step_fn(
                    params, state.opt, state.guard, data.graph,
                    data.features, data.labels, seeds, key)
                return params, EngineState(opt=opt, err=state.err,
                                           guard=g), m
            if seeds.shape[0] % self.num_parts:
                raise ValueError(
                    f"global seed batch {seeds.shape[0]} must divide over "
                    f"{self.num_parts} devices (pad with pad_seeds)")
            if self.guard is None:
                params, opt, err, m = self.step_fn(
                    params, state.opt, state.err, data.indptr, data.indices,
                    data.features, data.labels, seeds, key)
                return params, EngineState(opt=opt, err=err), m
            params, opt, err, g, m = self.step_fn(
                params, state.opt, state.err, state.guard, data.indptr,
                data.indices, data.features, data.labels, seeds, key)
            return params, EngineState(opt=opt, err=err, guard=g), m

    def _read_overflow(self, m):
        """The ONE place step metrics' overflow flags are read for the
        ledger/replay protocol — and therefore the ``overflow_storm``
        injection site: a firing storm replaces the device flags with
        all-TRUE, driving the grow/replay surface exactly as a real
        persistent overflow would."""
        flags = m["overflow"]
        if self.inject is not None and self.inject.armed("overflow_storm"):
            if self.inject.fires("overflow_storm", self._ovf_reads) is not None:
                flags = jnp.ones_like(flags)
        self._ovf_reads += 1
        return flags

    def reset_protocol(self):
        """Drop the in-flight overflow window (the guardrail's rollback
        path: pending entries describe a discarded trajectory)."""
        self._ledger = OverflowLedger(self.stats, depth=self._ledger.depth)

    def grow(self, layer_counts=None, seeds=None):
        """Grow the cap schedule after an overflow and invalidate the
        compiled programs. ``layer_counts`` (int32[L, 3]) are the counts
        the overflowed batch measured and ``seeds`` its padded seed
        batch: each buffer they show past its cap grows to
        max(2 x cap, 1.25 x need), and no other
        (:func:`~repro.core.interface.grow_caps`). Without counts (a
        mesh's step reports none), or where they show no buffer past
        its cap, every cap doubles; per-peer all-to-all caps always
        double."""
        with jax.profiler.TraceAnnotation(spans.ENGINE_GROW):
            over = cap_overflows(self.sampler.caps, layer_counts, seeds)
            self.stats.record_growth(over, self.sampler.num_layers)
            self.sampler = self.sampler.grown(
                over, None if over is None else seeds.shape[0])
            self._step = None
            self._infer = None
            self._staged = None
            self._infer_cached = {}
            self.generation += 1

    def step(self, params, state: EngineState, data: EngineData, seeds, key,
             tag: Any = None):
        """One fused train step with the async overflow protocol: the
        update is gated on device; the PREVIOUS batch's flags are polled
        (free — its program has retired) and an overflowed batch is
        replayed with grown caps. Returns (params, state, metrics) of
        THIS batch; replay metrics land in :attr:`replayed`.

        The first batch under a cap schedule that has never run (after
        construction, or after a :meth:`grow` from outside a replay) is
        polled before ``step`` returns, and if it overflowed it is
        replayed at once: ``step`` then returns the replayed batch's
        params, state and metrics. That costs one host sync per new
        schedule, beside a compile; every later batch is polled one
        step late."""
        with jax.profiler.TraceAnnotation(spans.ENGINE_STEP):
            params, state, m = self._dispatch(params, state, data, seeds,
                                              key)
            return self._settle(self._ledger, params, state, data,
                                (seeds, key, tag, self.sampler), m)

    def _settle(self, ledger: OverflowLedger, params, state, data, entry, m):
        """Record a dispatched batch (``entry`` = seeds, key, tag,
        sampler) in ``ledger`` with its flags and counts; replay what
        the poll finds due, and poll the batch itself at once if its
        schedule has never run. Shared by :meth:`step` and the
        pipelined driver (``runtime/pipeline.py``), so both apply
        replays in the same order."""
        flags = self._read_overflow(m)
        entry = (*entry, m.get("layer_counts"))
        with jax.profiler.TraceAnnotation(spans.ENGINE_POLL):
            # blocks until the previous batch's program has retired
            due = ledger.record(entry, flags)
        if due is not None:
            params, state, _ = self._replay(params, state, data, *due)
        if entry[3] is not self._proven:
            with jax.profiler.TraceAnnotation(spans.ENGINE_POLL):
                due = ledger.flush()
            if due is None:
                self._proven = entry[3]
            else:
                params, state, m = self._replay(params, state, data, *due)
        return params, state, m

    def flush(self, params, state: EngineState, data: EngineData):
        """Resolve the last in-flight batch (end of training, or before
        persisting a checkpoint: a gated no-op batch must be replayed
        before its params are saved). Returns (params, state, metrics of
        the replayed batch or None)."""
        with jax.profiler.TraceAnnotation(spans.ENGINE_FLUSH):
            with jax.profiler.TraceAnnotation(spans.ENGINE_POLL):
                due = self._ledger.flush()
            if due is None:
                return params, state, None
            return self._replay(params, state, data, *due)

    def _replay(self, params, state, data, seeds, key, tag, sampler_then,
                counts):
        box = {"params": params, "state": state, "then": sampler_then,
               "counts": counts}

        def attempt(_i):
            if self.sampler is box["then"]:
                self.stats.overflow_retries += 1
                self.grow(box["counts"], seeds)
            p, s, m = self._dispatch(box["params"], box["state"], data,
                                     seeds, key)
            box["params"], box["state"] = p, s
            self.replayed.append((tag, m))
            if bool(jnp.any(self._read_overflow(m))):
                box["then"], box["counts"] = self.sampler, m.get(
                    "layer_counts")
                return None
            self._proven = self.sampler
            return (p, s, m)

        with jax.profiler.TraceAnnotation(spans.ENGINE_REPLAY):
            return RetryPolicy(self.max_replay_retries).run(
                attempt, error=SamplingOverflowError,
                describe="sampling overflow persisted after cap growth")

    def infer(self, params, data: EngineData, seeds, key):
        """Fused inference through the engine (see :attr:`infer_fn`)."""
        if self.mesh is None:
            return self.infer_fn(params, data.graph, data.features, seeds,
                                 key)
        return self.infer_fn(params, data.indptr, data.indices,
                             data.features, seeds, key)

    def infer_with_retry(self, params, data: EngineData, seeds, key, *,
                         max_retries: int = 4):
        """:meth:`infer` under the trainer's overflow-retry contract:
        on overflow, :meth:`grow` from the batch's ``layer_counts`` (a
        fresh specialization) and re-run with the SAME key — the
        sampled set is salt-determined, so the retry answers the same
        request, just un-truncated. Raises
        :class:`~repro.data.gnn_loader.SamplingOverflowError` (the
        same type ``sample_with_retry`` and the async replay raise)
        when ``max_retries`` growths don't clear it, so serving
        drivers catch cap exhaustion uniformly with training drivers.

        Returns ``(logits, grows)`` — ``grows`` > 0 tells the caller
        the dispatch paid one or more fresh compiles (latency
        accounting must tag, not fold, that time)."""
        box = {"grows": 0, "counts": None}

        def attempt(_i):
            out = self.infer(params, data, seeds, key)
            # single-host (logits, flags, counts); a mesh returns
            # (owned, logits, flags) and no counts
            flags = out[1] if self.mesh is None else out[2]
            if bool(jnp.any(flags)):
                box["counts"] = out[2] if self.mesh is None else None
                return None
            return out

        def escalate(_i):
            self.grow(box["counts"], seeds)
            self.stats.overflow_retries += 1
            box["grows"] += 1

        out = RetryPolicy(max_retries).run(
            attempt, grow=escalate, error=SamplingOverflowError,
            describe="sampling overflow persisted after cap growth "
                     "while serving")
        return (out[0] if self.mesh is None else out), box["grows"]

    # ------------------------------------------------------------------
    # AOT lowering support (launch/perf.py roofline accounting)
    # ------------------------------------------------------------------

    def abstract_inputs(self, *, global_batch: int, num_vertices: int,
                        num_edges: int, feature_dim: int,
                        edge_balance: float = 1.5) -> Dict[str, Any]:
        """ShapeDtypeStructs (with NamedShardings) for lowering the
        distributed step without materializing a graph: partition shapes
        are derived analytically (owned rows = ceil(V/P); owned edges =
        E/P with an imbalance allowance)."""
        if self.mesh is None:
            raise ValueError("abstract_inputs is for the distributed engine")
        P = self.num_parts
        per = -(-num_vertices // P)
        max_e = int(num_edges / P * edge_balance) + 64
        ax = self._ax_spec()
        row = lambda shape: jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(self.mesh, P_(ax, None)))
        return dict(
            indptr=row((P, per + 1)),
            indices=row((P, max_e)),
            features=jax.ShapeDtypeStruct(
                (P * per, feature_dim), jnp.float32,
                sharding=NamedSharding(self.mesh, P_(ax, None))),
            labels=jax.ShapeDtypeStruct(
                (P * per,), jnp.int32,
                sharding=NamedSharding(self.mesh, P_(ax))),
            seeds=jax.ShapeDtypeStruct(
                (global_batch,), jnp.int32,
                sharding=NamedSharding(self.mesh, P_(ax))),
            key=jax.ShapeDtypeStruct((), jax.random.key(0).dtype),
        )
