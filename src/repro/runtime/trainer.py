"""Training loops with fault tolerance: GNN (the paper's workload) and a
small LM loop for the examples. Both support checkpoint/auto-resume,
async saving, and straggler-aware input pipelines.

Every fused train/infer step is assembled by
:class:`repro.runtime.engine.TrainEngine` — the single step builder
shared with the distributed launch path and serving. This module keeps
the driver loop (batching, checkpointing, history) plus the eager
unfused baseline used for parity measurement.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as graph_ops
from repro.core import samplers as sampler_registry
from repro.core.interface import Sampler, pad_seeds
from repro.data.gnn_loader import LoaderStats, SeedBatches, sample_with_retry
from repro.graph.generators import GraphDataset
from repro.models import gnn as gnn_models
from repro.optim import adam
from repro.runtime import checkpoint as ckpt_lib
from repro.runtime import inject as inject_lib
from repro.runtime.engine import TrainEngine, gather_feats, gnn_loss_fn
from repro.runtime.guard import (GuardConfig, GuardFault, GuardRail,
                                 init_guard_state, quarantine_key)
from repro.runtime.pipeline import PipelinedEngine

# the loss/gather helpers moved to the engine; re-exported here for the
# unfused baseline's callers (benchmarks, fault-tolerance harness)
_gnn_loss_fn = gnn_loss_fn


@dataclasses.dataclass
class GNNTrainConfig:
    model: str = "gcn"                  # gcn | sage | gatv2
    hidden: int = 256
    num_layers: int = 0                 # 0 -> len(fanouts)
    fanouts: tuple = (10, 10, 10)
    sampler: str = "labor-0"             # any repro.core.samplers entry
    layer_sizes: Optional[tuple] = None  # (p)ladies budgets; None -> default
    batch_size: int = 1000
    lr: float = 1e-3
    steps: int = 200
    eval_every: int = 50
    eval_batches: int = 4
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    seed: int = 0
    cap_safety: float = 2.0
    # graph-ops backend for every model primitive (repro.ops): "xla",
    # "pallas", or "auto" (resolved once by platform in the engine)
    backend: str = "auto"
    # fuse sampling + gather + fwd/bwd + Adam into one XLA program with
    # donated buffers — every registered sampler traces inside it
    fused: bool = True
    # "off": the single fused program above. "prefetch"/"full": the
    # staged pipeline driver (repro.runtime.pipeline) — sample-ahead
    # dispatch of the salt-only sampling program, and in "full" mode
    # double-buffered feature gathers on their own program. Requires
    # fused; parity vs "off" is bit-exact on sampled sets, fp-tolerance
    # on params (tests/test_pipeline.py).
    pipeline: str = "off"
    max_replay_retries: int = 3
    # > 0: run the partition-aware distributed engine over this many
    # devices (one shard_map; partitioned CSR + features; seed routing;
    # feature all-to-all; gradient all-reduce — docs/distributed.md).
    # Requires the process to expose that many jax devices.
    mesh_devices: int = 0
    grad_compression: str = "none"       # none | bf16 | int8 (mesh only)
    # guardrail (docs/robustness.md): "off", or a recovery mode —
    # "quarantine" re-draws a NaN/spiking batch under a fresh fold_in
    # salt (escalating to rollback when re-draws keep faulting),
    # "rollback" restores the last CRC-verified checkpoint and resumes
    # deterministically. Requires fused (the flags ride in the fused
    # program's metrics).
    guard: str = "off"
    guard_spike_factor: float = 4.0
    guard_warmup: int = 5
    guard_max_quarantine: int = 2
    guard_max_rollbacks: int = 3
    # fault injection: a repro.runtime.inject spec string (or a
    # pre-parsed FaultPlan) arming injectors at the run's trust
    # boundaries; None also consults $REPRO_INJECT via the launchers
    inject: Any = None


def build_sampler(ds: GraphDataset, cfg: GNNTrainConfig,
                  num_parts: Optional[int] = None) -> Sampler:
    """The one sampler construction path: registry entry + caps derived
    from the dataset's graph stats (train and eval share it). On a mesh
    the caps are sized for the DEVICE-LOCAL batch and the per-peer
    all-to-all schedule rides along (``num_parts``)."""
    batch = cfg.batch_size if not num_parts else cfg.batch_size // num_parts
    return sampler_registry.from_dataset(
        cfg.sampler, ds, batch_size=batch, fanouts=cfg.fanouts,
        layer_sizes=cfg.layer_sizes, safety=cfg.cap_safety,
        num_parts=num_parts)


def make_gnn_train_step(apply_fn, opt_cfg: adam.AdamConfig, backend=None):
    """The eager unfused baseline step (sampling happens outside): kept
    for measurement against the engine's fused program."""
    backend = graph_ops.resolve_backend(backend)

    @jax.jit
    def step(params, opt_state, blocks, feats, labels):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gnn_loss_fn(apply_fn, p, blocks, feats, labels,
                                  backend),
            has_aux=True,
        )(params)
        params, opt_state, m = adam.apply_updates(params, grads, opt_state, opt_cfg)
        m.update(loss=loss, acc=acc)
        return params, opt_state, m
    return step


def make_fused_train_step(apply_fn, opt_cfg: adam.AdamConfig,
                          sampler: Sampler, backend=None):
    """One-dispatch train step — built by the engine (single-host mode).

    Signature: step(params, opt_state, graph, features, labels_all,
    seeds, key) -> (params, opt_state, metrics). See
    :class:`repro.runtime.engine.TrainEngine` and docs/pipeline.md for
    the program layout and the async overflow protocol.
    """
    return TrainEngine(sampler, apply_fn, opt_cfg, mesh=None,
                       backend=backend).step_fn


def make_fused_infer_step(apply_fn, sampler: Sampler, backend=None):
    """One-dispatch serving step — the engine's fused infer program.

    Signature: infer(params, graph, features, seeds, key) ->
    (logits, overflow_flags, layer_counts). With the ``full`` registry
    entry the logits are exact (full-neighborhood aggregation); with any
    other entry this is sampled inference. Overflow handling is the
    caller's usual protocol: grow the caps via ``sampler.grown`` from
    ``layer_counts`` and rebuild.
    """
    return TrainEngine(sampler, apply_fn, adam.AdamConfig(), mesh=None,
                       backend=backend).infer_fn


def _mesh_for(cfg: GNNTrainConfig):
    if not cfg.mesh_devices:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((cfg.mesh_devices,), ("data",))


def train_gnn(ds: GraphDataset, cfg: GNNTrainConfig,
              log_every: int = 50, history_metrics: bool = True) -> Dict[str, Any]:
    """Full GNN training with auto-resume. Returns metrics history.

    One loop serves both scales: with ``cfg.mesh_devices == 0`` the
    engine lowers to the single-device fused program; with a mesh it
    runs the partition-aware distributed step — same batching,
    checkpointing, and overflow-replay protocol either way.
    """
    if cfg.num_layers and cfg.num_layers != len(cfg.fanouts):
        raise ValueError("num_layers must match len(fanouts)")
    cfg = dataclasses.replace(cfg, num_layers=len(cfg.fanouts))
    mesh = _mesh_for(cfg)
    if mesh is not None and not cfg.fused:
        raise ValueError("the distributed engine is always fused")
    g = ds.graph
    in_dim, n_cls = ds.features.shape[1], int(ds.labels.max()) + 1

    init_fn, apply_fn = gnn_models.MODELS[cfg.model]
    params = init_fn(jax.random.key(cfg.seed), in_dim, cfg.hidden, n_cls,
                     cfg.num_layers)
    opt_cfg = adam.AdamConfig(lr=cfg.lr)

    stats = LoaderStats()
    plan = cfg.inject
    if isinstance(plan, str):
        plan = inject_lib.parse(plan)
    guard_cfg = None
    if cfg.guard != "off":
        if not cfg.fused:
            raise ValueError("the guardrail requires the fused engine "
                             "(fused=True): the [nonfinite, spike] flags "
                             "ride in the fused program's metrics")
        guard_cfg = GuardConfig(mode=cfg.guard,
                                spike_factor=cfg.guard_spike_factor,
                                warmup=cfg.guard_warmup,
                                max_quarantine=cfg.guard_max_quarantine,
                                max_rollbacks=cfg.guard_max_rollbacks)
    sampler = build_sampler(ds, cfg, num_parts=cfg.mesh_devices or None)
    engine = TrainEngine(sampler, apply_fn, opt_cfg, mesh=mesh,
                         backend=cfg.backend,
                         grad_compression=cfg.grad_compression,
                         max_replay_retries=cfg.max_replay_retries,
                         stats=stats, guard=guard_cfg, inject=plan)
    data = engine.make_data_from_dataset(ds)
    state = engine.init_state(params)
    driver = None
    if cfg.pipeline != "off":
        if not cfg.fused:
            raise ValueError("pipeline modes require the fused engine "
                             "(fused=True)")
        driver = PipelinedEngine(engine, mode=cfg.pipeline)
    if not cfg.fused:
        feats = data.features
        labels_all = data.labels
        step_fn = make_gnn_train_step(apply_fn, opt_cfg, engine.backend)

    def state_tree(params, state):
        t = {"params": params, "opt": state.opt}
        if state.err is not None:  # compression error-feedback rides along
            t["err"] = state.err
        if state.guard is not None:  # guard EMA/step counter rides along
            t["guard"] = state.guard
        return t

    start_step = 0
    saver = None
    if cfg.ckpt_dir:
        saver = ckpt_lib.AsyncSaver(cfg.ckpt_dir, inject=plan)
        last = ckpt_lib.latest_step(cfg.ckpt_dir)
        if last is not None:
            meta = ckpt_lib.read_meta(cfg.ckpt_dir, last)
            # rebuild the exact jit specialization the checkpoint was
            # trained under; loud error on sampler/mesh/compression
            # mismatch (must precede restore: a compression mismatch
            # also changes the checkpoint tree)
            engine.sampler = ckpt_lib.validate_restore_meta(
                meta, engine.sampler, mesh_devices=cfg.mesh_devices,
                grad_compression=cfg.grad_compression,
                backend=engine.backend)
            like = state_tree(params, state)
            try:
                restored = ckpt_lib.restore(cfg.ckpt_dir, last, like)
            except KeyError:
                if "guard" not in like:
                    raise
                # pre-guard checkpoint: restore everything else and keep
                # the fresh guard state (its warmup re-runs, harmlessly)
                like = {k: v for k, v in like.items() if k != "guard"}
                restored = ckpt_lib.restore(cfg.ckpt_dir, last, like)
            params = restored["params"]
            state = dataclasses.replace(
                state, opt=restored["opt"],
                err=restored.get("err", state.err),
                guard=restored.get("guard", state.guard))
            start_step = last

    if len(ds.train_idx) < cfg.batch_size:
        raise ValueError(
            f"batch_size {cfg.batch_size} exceeds the {len(ds.train_idx)}"
            "-vertex train split (SeedBatches drops partial batches)")
    batches = SeedBatches(ds.train_idx, cfg.batch_size, seed=cfg.seed)
    # metrics stay on device during the loop (no per-step host sync);
    # floatified once after the last step.
    device_history: List[Dict[str, Any]] = []
    # batch schedule as a pure function of the step index: seeds from
    # batches.at(step), per-batch key from fold_in(base_key, step). A
    # rollback that resumes at step s therefore replays the exact
    # batches/keys the unfaulted run would have used (docs/robustness.md)
    base_key = jax.random.key(cfg.seed + 1)
    rail = GuardRail(guard_cfg) if guard_cfg is not None else None
    # host-side snapshot of the starting state: the rollback target when
    # no verified checkpoint exists yet
    snap0 = (jax.tree.map(np.asarray, state_tree(params, state))
             if rail is not None else None)
    # pipelined dispatch order == FIFO retire order, so a deque of
    # (step, seeds, key) maps each retired batch back to its identity
    pending_meta: deque = deque()

    def scalars(m):
        """History keeps scalar metrics only — the distributed step's
        per-layer frontier arrays would pin device memory for the whole
        run if retained per step."""
        return {k: v for k, v in m.items() if k != "frontiers"}

    def drain_replays():
        """Patch step-indexed history with metrics of replayed batches
        (the engine appends (tag, metrics) per replay attempt)."""
        for hist_idx, rm in engine.replayed:
            if history_metrics and hist_idx is not None:
                device_history[hist_idx] = {**device_history[hist_idx],
                                            **scalars(rm)}
        engine.replayed.clear()

    def absorb(done):
        """Fold the pipeline driver's retired batches into history —
        retirement is FIFO in tag order, so appends land at the history
        index the tag was assigned at dispatch. Guarded runs also feed
        each retired batch's flags into the rail (poll lag 1, same
        protocol as the serial path)."""
        nonlocal m
        for dtag, dm in done:
            if history_metrics and dtag is not None:
                device_history.append({"step": start_step + dtag + 1,
                                       **scalars(dm)})
            m = dm
            if rail is not None:
                ps, pseeds, pkey = pending_meta.popleft()
                due = rail.record(ps, pseeds, pkey, dm["guard_flags"])
                if due is not None:
                    recover(due)  # may raise _Rollback
        drain_replays()

    class _Rollback(Exception):
        """Control-flow only: unwinds the driver loop to the restored
        step after the guardrail rolled state back."""

        def __init__(self, resume: int):
            self.resume = resume

    def recover(w):
        """React to a flagged batch (guard.py _Watched): quarantine
        re-draws under fresh fold_in salts, escalating to (or starting
        at, mode="rollback") a checkpoint rollback."""
        nonlocal params, state, m
        if guard_cfg.mode == "quarantine":
            def attempt(i):
                nonlocal params, state, m
                rail.stats.quarantines += 1
                qk = quarantine_key(w.key, i)
                p2, s2, m2 = engine.step(params, state, data, w.seeds, qk,
                                         tag=None)
                # resolve the re-draw eagerly: its overflow replay (if
                # any) and its flags, before deciding success
                p2, s2, rm = engine.flush(p2, s2, data)
                drain_replays()  # tag=None redraw entries are skipped
                params, state = p2, s2
                if rm is not None:
                    m2 = rm
                if bool(np.any(np.asarray(m2["guard_flags"]))):
                    return None
                m = m2
                idx = w.step - start_step
                if history_metrics and 0 <= idx < len(device_history):
                    device_history[idx] = {"step": w.step + 1,
                                           **scalars(m2)}
                return m2
            try:
                guard_cfg.quarantine_policy().run(
                    attempt, error=GuardFault,
                    describe=f"quarantined batch at step {w.step} kept "
                             "faulting under fresh salts")
                return
            except GuardFault:
                pass  # every re-draw faulted: escalate to rollback
        do_rollback()

    def do_rollback():
        """Restore the last CRC-verified checkpoint (or the run's
        starting state) and unwind the loop to resume from it. The
        grown cap schedule is deliberately kept — sampled sets are
        cap-independent, so replayed batches stay bit-exact while
        avoiding a re-growth storm."""
        nonlocal params, state
        rail.stats.rollbacks += 1
        if rail.stats.rollbacks > guard_cfg.max_rollbacks:
            raise GuardFault(
                f"rollback budget exhausted ({guard_cfg.max_rollbacks}): "
                "faults persisted across restores")
        if saver is not None:
            saver.wait()  # in-flight save must land (or raise) first
        good = (ckpt_lib.latest_good_step(cfg.ckpt_dir)
                if cfg.ckpt_dir else None)
        if good is None or good < start_step:
            t = jax.tree.map(jnp.asarray, snap0)
            resume = start_step
        else:
            like = state_tree(params, state)
            try:
                t = ckpt_lib.restore(cfg.ckpt_dir, good, like)
            except KeyError:  # pre-guard checkpoint (resumed-from)
                like = {k: v for k, v in like.items() if k != "guard"}
                t = ckpt_lib.restore(cfg.ckpt_dir, good, like)
            resume = good
        params = t["params"]
        state = dataclasses.replace(
            state, opt=t["opt"], err=t.get("err", None),
            guard=(t.get("guard", init_guard_state())
                   if rail is not None else None))
        rail.reset()
        engine.replayed.clear()
        pending_meta.clear()
        if driver is not None:
            driver.reset()
        else:
            engine.reset_protocol()
        if history_metrics:
            del device_history[max(resume - start_step, 0):]
        raise _Rollback(resume)

    def heal():
        """Drain the rail window (before a save / at end of run) so a
        flagged batch is never persisted or left unresolved."""
        if rail is None:
            return
        while True:
            due = rail.flush()
            if due is None:
                return
            recover(due)

    def ckpt_meta():
        return {"loss": float(m["loss"]),
                **ckpt_lib.engine_restore_meta(
                    engine.sampler, mesh_devices=cfg.mesh_devices,
                    grad_compression=cfg.grad_compression,
                    backend=engine.backend)}

    t0 = time.time()
    m = {"loss": jnp.float32(0)}
    step = start_step
    while True:
        try:
            while step < cfg.steps:
                seeds = batches.at(step)
                sk = jax.random.fold_in(base_key, step)
                data_t = (inject_lib.poison_batch(plan, step, data)
                          if plan is not None else data)
                if driver is not None:
                    # tag = the history index this batch will retire into
                    # (appended batches + batches in flight ahead of it)
                    tag = (len(device_history) + driver.in_flight
                           if history_metrics else None)
                    if rail is not None:
                        pending_meta.append((step, seeds, sk))
                    params, state, done = driver.step(params, state, data_t,
                                                      seeds, sk, tag=tag)
                    absorb(done)
                elif cfg.fused:
                    hist_idx = (len(device_history) if history_metrics
                                else None)
                    params, state, m = engine.step(params, state, data_t,
                                                   seeds, sk, tag=hist_idx)
                    if history_metrics:
                        device_history.append({"step": step + 1,
                                               **scalars(m)})
                    drain_replays()
                    if rail is not None:
                        due = rail.record(step, seeds, sk,
                                          m["guard_flags"])
                        if due is not None:
                            recover(due)
                else:
                    blocks, smp = sample_with_retry(engine.sampler, g,
                                                    seeds, sk, stats)
                    engine.sampler = smp
                    bf = gather_feats(feats, blocks[-1])
                    lab = labels_all[jnp.where(seeds >= 0, seeds, 0)]
                    params, opt, m = step_fn(params, state.opt, blocks, bf,
                                             lab)
                    state = dataclasses.replace(state, opt=opt)
                    if history_metrics:
                        device_history.append({
                            "step": step + 1, "loss": m["loss"],
                            "acc": m["acc"],
                            "sampled_v": blocks[-1].num_next,
                            "sampled_e": sum(b.num_edges for b in blocks)})
                if saver and (step + 1) % cfg.ckpt_every == 0:
                    if driver is not None:
                        # drain the whole pipeline before persisting:
                        # in-flight batches have no update yet, and a
                        # gated no-op batch must be replayed before its
                        # params are saved
                        params, state, done = driver.flush(params, state,
                                                           data)
                        absorb(done)
                    elif cfg.fused:
                        # resolve the just-dispatched batch before
                        # persisting: if it overflowed its update was
                        # gated off on device and would otherwise be
                        # replayed only after the save
                        params, state, rm = engine.flush(params, state,
                                                         data)
                        drain_replays()
                        if rm is not None:
                            m = rm
                    # a flagged batch must be recovered (not persisted);
                    # on rollback the save re-runs after the resumed
                    # trajectory passes this step again
                    heal()
                    saver.save(step + 1, state_tree(params, state),
                               meta=ckpt_meta())
                step += 1
            if driver is not None:
                params, state, done = driver.flush(params, state, data)
                absorb(done)
            elif cfg.fused:
                params, state, _ = engine.flush(params, state, data)
                drain_replays()
            heal()
            break
        except _Rollback as r:
            step = r.resume
    wall = time.time() - t0
    history: List[Dict[str, float]] = [
        {"step": int(r["step"]), "loss": float(r["loss"]),
         "acc": float(r["acc"]), "sampled_v": int(r["sampled_v"]),
         "sampled_e": int(r["sampled_e"])}
        for r in device_history]
    if saver:
        saver.save(cfg.steps, state_tree(params, state), meta=ckpt_meta())
        saver.wait()
    out = {
        "params": params,
        "history": history,
        "stats": stats,
        "wall_time": wall,
    }
    if rail is not None:
        out["guard_stats"] = rail.stats
    if plan is not None:
        out["inject_log"] = list(plan.log)
    return out


def evaluate_gnn(ds: GraphDataset, params, cfg: GNNTrainConfig,
                 idx: np.ndarray, batches: int = 8, key=None) -> float:
    """Sampled evaluation accuracy on ``idx`` vertices."""
    g = ds.graph
    feats = jnp.asarray(ds.features)
    labels_all = jnp.asarray(ds.labels)
    cfg = dataclasses.replace(cfg, num_layers=len(cfg.fanouts))
    _, apply_fn = gnn_models.MODELS[cfg.model]
    backend = graph_ops.resolve_backend(cfg.backend)
    # same construction path as training: registry entry + derived caps
    sampler = build_sampler(ds, cfg)
    key = key if key is not None else jax.random.key(1234)
    correct = total = 0
    for i in range(batches):
        lo = i * cfg.batch_size
        if lo >= len(idx):
            break
        chunk = idx[lo:lo + cfg.batch_size]
        seeds = pad_seeds(jnp.asarray(chunk), cfg.batch_size)
        key, sk = jax.random.split(key)
        blocks, sampler = sample_with_retry(sampler, g, seeds, sk)
        bf = gather_feats(feats, blocks[-1])
        logits = apply_fn(params, blocks, bf, backend=backend)
        valid = np.asarray(seeds >= 0)
        pred = np.asarray(jnp.argmax(logits, -1))
        lab = np.asarray(labels_all[jnp.where(seeds >= 0, seeds, 0)])
        correct += int(((pred == lab) & valid).sum())
        total += int(valid.sum())
    return correct / max(total, 1)
