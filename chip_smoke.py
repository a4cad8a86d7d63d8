#!/usr/bin/env python3
"""Smoke run of the LABOR training and serving path on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the partition-aware path only

One chip, in one process, through the entry points a user calls:

  1. generate the products graph at its published size (scale 1.0:
     2.45M vertices, ~62M edges, 100 float32 features per vertex);
  2. train GCN (hidden 256, 3 layers, fanouts 10,10,10, sampler
     labor-0, batch 1024) with ``train_gnn`` — the ``TrainEngine`` path
     of ``launch/train.py`` — for a few steps after the compile;
  3. sample one batch with the Pallas frontier kernels and again with
     the XLA references: the sampled blocks must be identical, which is
     the kernels' bit-exact contract;
  4. answer a few requests through ``ServingDriver`` (the path of
     ``launch/serve.py``) with the trained params.

``--chips 4`` trains the same configuration on the partition-aware
engine over four chips (``mesh_devices=4``) and on one chip, and
compares them step by step: sampled vertex counts must be equal, losses
within float tolerance.

Every phase prints one JSON line. Any failed check, or a process that
finds no TPU, exits non-zero without the result line; on success the
last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DATASET, SCALE, SEED = "products", 1.0, 0
TRAIN_STEPS = 5
REQUESTS, REQUEST_SIZE = 4, 256
# distributed vs single-chip loss: same sampled sets, different
# reduction order (partitioned aggregation, gradient all-reduce)
LOSS_ATOL = 1e-3

# lowering to MLIR and the XLA/Mosaic compile; tracing is left out, as
# its events nest (every inner jit reports inside its caller's trace)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Seconds JAX spent lowering and compiling, from its own duration
    events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs


def train_config(**overrides):
    from repro.runtime.trainer import GNNTrainConfig
    cfg = GNNTrainConfig(model="gcn", hidden=256, fanouts=(10, 10, 10),
                         sampler="labor-0", batch_size=1024,
                         steps=TRAIN_STEPS, seed=SEED)
    return dataclasses.replace(cfg, **overrides)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    from repro.ops.backend import interpret_mode
    info = device_info()
    check(info["platform"] == "tpu", f"JAX found no TPU: {info}")
    check(not interpret_mode(), "Pallas kernels would run interpreted")
    check(info["count"] >= chips,
          f"{chips} chips asked for, JAX sees {info['count']}")
    return info


def generate():
    from repro.graph import paper_dataset
    t0 = time.perf_counter()
    ds = paper_dataset(DATASET, scale=SCALE, seed=SEED)
    log("generate", seconds=time.perf_counter() - t0,
        vertices=ds.graph.num_vertices, edges=ds.graph.num_edges,
        feature_gb=ds.features.nbytes / 1e9,
        max_in_degree=ds.max_in_degree)
    return ds


def backends() -> None:
    from repro.ops import autotune
    from repro.ops.backend import get_backend, resolve_backend
    model_ops = ("aggregate", "scatter_edges", "gather_dst", "edge_softmax")
    log("backends",
        resolved={p: resolve_backend(None)
                  for p in model_ops + autotune.PRIMITIVES},
        namespace=get_backend(None).__name__,
        frontier_params={p: autotune.get_params(p)
                         for p in autotune.PRIMITIVES},
        autotune_cache=autotune.default_cache_path(),
        autotune_fingerprint=autotune.cache_fingerprint())


def train(ds, clock: CompileClock, cfg, phase: str = "train"):
    from repro.runtime.trainer import train_gnn
    before = clock.seconds
    out = train_gnn(ds, cfg)
    hist = out["history"]
    for r in hist:
        log(phase + "_step", step=r["step"], loss=r["loss"],
            sampled_vertices=r["sampled_v"], sampled_edges=r["sampled_e"])
    compile_s = clock.seconds - before
    log(phase, compile_seconds=compile_s, wall_seconds=out["wall_time"],
        steps=len(hist), mesh_devices=cfg.mesh_devices,
        overflow_replays=out["stats"].overflow_replays)
    check(len(hist) == cfg.steps, f"{phase}: {len(hist)} of {cfg.steps} "
          "steps in the history")
    check(all(math.isfinite(r["loss"]) for r in hist),
          f"{phase}: non-finite loss")
    return out


def _blocks_equal(a, b) -> list:
    import numpy as np
    bad = []
    for l, (x, y) in enumerate(zip(a, b)):
        for f in dataclasses.fields(x):
            u, v = getattr(x, f.name), getattr(y, f.name)
            if not np.array_equal(np.asarray(u), np.asarray(v)):
                bad.append(f"layer {l} {f.name}")
    return bad


def frontier_parity(ds, cfg) -> None:
    """One batch sampled twice in fresh traces: with the Pallas frontier
    kernels, then with the XLA references registered under the backend
    name the frontier dispatch resolves to."""
    import jax
    import numpy as np

    from repro.data.gnn_loader import SeedBatches
    from repro.ops import pallas as pallas_ns
    from repro.ops import ref as xla_ns
    from repro.ops.backend import register_backend, resolve_backend
    from repro.runtime.trainer import build_sampler

    sampler = build_sampler(ds, cfg)
    seeds = SeedBatches(ds.train_idx, cfg.batch_size, seed=cfg.seed).at(0)
    salts = sampler.spec.salts(jax.random.key(cfg.seed + 1))

    def sample():
        return jax.block_until_ready(jax.jit(
            lambda g, s, t: sampler.sample(g, s, t))(ds.graph, seeds, salts))

    name = resolve_backend(None)
    check(name == "pallas", f"frontier dispatch resolves to {name!r}")
    t0 = time.perf_counter()
    kernels = sample()
    t_kernels = time.perf_counter() - t0
    register_backend(name, xla_ns)
    try:
        t0 = time.perf_counter()
        refs = sample()
        t_refs = time.perf_counter() - t0
    finally:
        register_backend(name, pallas_ns)
    bad = _blocks_equal(kernels, refs)
    overflow = [bool(np.asarray(b.overflow)) for b in kernels]
    log("frontier_parity", identical=not bad, differing=bad,
        sampled_vertices=[int(b.num_next) for b in kernels],
        sampled_edges=[int(b.num_edges) for b in kernels],
        overflow=overflow, pallas_seconds=t_kernels, xla_seconds=t_refs)
    check(not any(overflow), "parity batch overflowed its caps")
    check(not bad, f"Pallas and XLA sampled sets differ: {bad}")


def serve(ds, params, cfg) -> None:
    import numpy as np

    from repro.models import gnn as gnn_models
    from repro.optim import adam
    from repro.runtime.engine import TrainEngine
    from repro.runtime.trainer import build_sampler
    from repro.serving import ServingDriver

    _, apply_fn = gnn_models.MODELS[cfg.model]
    engine = TrainEngine(build_sampler(ds, cfg), apply_fn,
                         adam.AdamConfig(), backend=cfg.backend)
    data = engine.make_data_from_dataset(ds)
    driver = ServingDriver(engine, params, data, batch_size=cfg.batch_size,
                           seed=cfg.seed + 1)
    val = np.asarray(ds.val_idx)
    requests = [val[i * REQUEST_SIZE:(i + 1) * REQUEST_SIZE]
                for i in range(REQUESTS)]
    t0 = time.perf_counter()
    tickets = [driver.submit(r) for r in requests]
    driver.drain()
    wall = time.perf_counter() - t0
    n_cls = int(ds.labels.max()) + 1
    statuses = [t.status for t in tickets]
    correct = 0
    for r, t in zip(requests, tickets):
        if t.status != "ok":
            continue
        logits = np.asarray(t.logits)
        check(logits.shape == (len(r), n_cls) and np.isfinite(logits).all(),
              f"serving logits: shape {logits.shape}, finite "
              f"{bool(np.isfinite(logits).all())}")
        correct += int((logits.argmax(-1) == ds.labels[r]).sum())
    log("serve", statuses=statuses, wall_seconds=wall,
        accuracy=correct / (REQUESTS * REQUEST_SIZE),
        backend=engine.backend)
    check(all(s == "ok" for s in statuses), f"serving tickets: {statuses}")


def four_chips(ds, clock: CompileClock) -> None:
    """The partition-aware engine on four chips against one chip."""
    single = train(ds, clock, train_config(), "single_chip")["history"]
    gc.collect()
    dist = train(ds, clock, train_config(mesh_devices=4),
                 "four_chips")["history"]
    counts = [(a["sampled_v"], b["sampled_v"]) for a, b in zip(single, dist)]
    dloss = [abs(a["loss"] - b["loss"]) for a, b in zip(single, dist)]
    log("four_chip_parity", sampled_vertices=counts, loss_abs_diff=dloss,
        loss_atol=LOSS_ATOL)
    check(all(a == b for a, b in counts), "sampled vertex counts differ")
    check(max(dloss) <= LOSS_ATOL, f"losses differ by up to {max(dloss)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the partition-aware path and the "
                         "single-chip run it is compared with")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    info = require_tpu(args.chips)
    log("device", **info, compile_cache=enable_compile_cache())
    clock = CompileClock()
    ds = generate()
    backends()
    if args.chips == 4:
        four_chips(ds, clock)
    else:
        cfg = train_config()
        params = train(ds, clock, cfg)["params"]
        # the trainer's device copies of graph and features sit in
        # closure cycles: free them before the next phase stages its own
        gc.collect()
        frontier_parity(ds, cfg)
        gc.collect()
        serve(ds, params, cfg)
    log("setup", compile_seconds=clock.seconds)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
