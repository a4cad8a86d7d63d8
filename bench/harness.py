"""The benchmark harness: one training cell, one process.

A cell of ``BENCHMARK.json`` names a configuration
(``configs/<config>.json``: model, graph, optimizer), a traffic mix
(``traffic/<traffic>.json``: sampler, batch, fanouts) and its limits
(``limits/<cell>.json``); each per-layer metric is read by
``metrics/<name>.py``. A run:

1. refuses to run without the chips the cell asks for;
2. builds or loads the graph, makes its features and the weights on the
   device from the seeds, and builds the program under test: the
   trainer's ``TrainEngine`` fused step (``pipeline=off``) on the cell's
   sampler, with the trainer's overflow protocol;
3. drives the first ``check_steps`` batches through that step (they
   compile it and are the steps the reference checks), keeping the
   optimizer state after the first and the weights after the last;
4. with ``--trace 0`` trains for ``--seconds``, rounded up to whole
   passes over the traffic's pool of batches (``Feed``), with one
   device sync at the end; with ``--trace 1`` profiles one pass, then
   the engine's staged sampling program on the same batches;
5. frees the program's state, runs the reference over the checked
   steps and compares (``check.py``);
6. prints the result line last on standard output, and the numbers
   compared with their limits last on standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")

# lowering to MLIR and the XLA/Mosaic compile; tracing is left out, as
# its events nest (every inner jit reports inside its caller's trace)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Compilations and their seconds, from JAX's own duration events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
            self.count += event.endswith("backend_compile_duration")


def device_info() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> Dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise NoDevice(f"JAX found no TPU: {info}")
    if info["count"] < chips:
        raise NoDevice(f"{chips} chips asked for, JAX sees {info['count']}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` where
    set, else at the fixed ``bench/.cache/jax`` of this checkout; every
    program is kept, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(bench: Dict, name: str) -> Dict:
    """The cell's entry with its configuration, traffic and limits."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise ValueError(f"no workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    return {"cell": cell,
            "config": _load_json(BENCH, "configs", cell["config"] + ".json"),
            "traffic": _load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            "limits": _load_json(BENCH, "limits", name + ".json")}


def per_layer_metrics(bench: Dict, cell: str) -> List[Dict]:
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Feed:
    """Batches and keys of every step: a pool of ``batch_pool`` disjoint
    batches of the train split, each with its own PRNG key, fixed by the
    traffic's ``pool_seed``, taken in an order drawn from ``--seed``.
    Every seed trains on the same batches, so every run does the same
    work; the step's time depends on what a batch samples."""

    def __init__(self, train_idx: np.ndarray, traffic: Dict, seed: int):
        import jax
        words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
        self.param_seed = int(words[0])
        batch, n = traffic["batch_size"], traffic["batch_pool"]
        if n * batch > len(train_idx):
            raise ValueError(f"{n} batches of {batch} exceed the "
                             f"{len(train_idx)}-vertex train split")
        rows = np.random.default_rng(traffic["pool_seed"]).permutation(
            train_idx)[:n * batch].astype(np.int32)
        self.pool = rows.reshape(n, batch)
        pool_key = jax.random.key(traffic["pool_seed"])
        self.keys = [jax.random.fold_in(pool_key, i) for i in range(n)]
        self.order = np.random.default_rng(int(words[1])).permutation(n)

    @property
    def size(self) -> int:
        return self.pool.shape[0]

    def batch(self, step: int) -> np.ndarray:
        return self.pool[self.order[step % self.size]]

    def key(self, step: int):
        return self.keys[self.order[step % self.size]]


def run_cell(bench: Dict, name: str, seed: int, seconds: float,
             trace: bool, t_start: float,
             adjust: Optional[Callable[[Dict], Dict]] = None,
             check_chips: bool = True) -> Dict:
    """One run of a cell; returns the result line as a dict. ``adjust``
    rewrites the loaded cell (tests shrink it); ``check_chips=False``
    skips the look for a TPU (tests)."""
    import jax
    import jax.numpy as jnp

    spec = load_cell(bench, name)
    if adjust is not None:
        spec = adjust(spec)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    info = require_tpu(cell["chips"]) if check_chips else device_info()
    enable_compile_cache()
    clock = CompileClock()

    from bench import check, graph as graph_lib
    from bench.reference import train as reference
    from repro.core import samplers
    from repro.graph.csr import Graph
    from repro.models import gnn as gnn_models
    from repro.optim import adam
    from repro.runtime.engine import TrainEngine

    gspec = config["graph"]
    t = time.perf_counter()
    g = graph_lib.load_or_build(config["name"], gspec)
    graph_build_s = time.perf_counter() - t

    # every set-up stage ends before the next allocates, and garbage is
    # collected before the first timed step: the order in which device
    # buffers are placed is then the same in every run
    labels = jnp.asarray(g.labels)
    features = jax.block_until_ready(
        graph_lib.device_features(gspec, labels))
    n_cls = gspec["num_classes"]
    batch, fanouts = traffic["batch_size"], tuple(traffic["fanouts"])
    feed = Feed(g.train_idx, traffic, seed)
    model_ref = reference.load_model(config["model"])
    params = jax.jit(lambda k: model_ref.init(k, config, gspec["num_features"],
                                              n_cls))(
        jax.random.key(feed.param_seed))
    params0 = jax.tree.map(np.asarray, params)

    opt = config["optimizer"]
    sampler = samplers.from_graph_stats(
        traffic["sampler"], batch_size=batch, fanouts=fanouts,
        avg_degree=g.num_edges / g.num_vertices, max_degree=g.max_in_degree,
        num_vertices=g.num_vertices, num_edges=g.num_edges,
        safety=traffic["cap_safety"])
    engine = TrainEngine(
        sampler, gnn_models.MODELS[config["model"]][1],
        adam.AdamConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], grad_clip=opt["grad_clip"]))
    data = jax.block_until_ready(engine.make_data(
        Graph(indptr=jnp.asarray(g.indptr), indices=jnp.asarray(g.indices)),
        features, labels))
    state = jax.block_until_ready(engine.init_state(params))

    def step(i):
        seeds = jax.device_put(feed.batch(i))
        return engine.step(params, state, data, seeds, feed.key(i), tag=i)

    prog = {"losses": [], "counts": []}
    n_check = traffic["check_steps"]
    for i in range(n_check):
        params, state, m = step(i)
        prog["losses"].append(float(m["loss"]))
        prog["counts"].append((int(m["sampled_v"]), int(m["sampled_e"])))
        if i == 0:
            # the optimizer's first moment after one step is
            # (1 - b1) x the gradient it was given
            prog["first_grad"] = jax.tree.map(
                lambda mu: np.asarray(mu) / (1 - opt["b1"]),
                state.opt["mu"])
    prog["delta"] = jax.tree.map(lambda p, p0: np.asarray(p) - p0, params,
                                 params0)
    compiles_before = clock.count
    gc.collect()

    sampled_v: List = []
    i = n_check
    ctx = None
    out: Dict = {}
    if not trace:
        t_win = time.perf_counter()
        setup_s = t_win - t_start
        # whole passes over the pool: every window trains on each pooled
        # batch equally often
        while (time.perf_counter() - t_win < seconds
               or (i - n_check) % feed.size):
            params, state, m = step(i)
            sampled_v.append(m["sampled_v"])
            i += 1
        params, state, _ = engine.flush(params, state, data)
        jax.block_until_ready((params, state.opt))
        window = time.perf_counter() - t_win
        steps = i - n_check
        out["metrics"] = {
            "train_seeds_per_s": {"value": steps * batch / window,
                                  "unit": "seeds/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        from bench.trace import Trace, op_paths
        tdir = os.path.join(CACHE, "traces", name)
        shutil.rmtree(tdir, ignore_errors=True)
        n_trace = feed.size
        traced = [(jax.device_put(feed.batch(i + k)), feed.key(i + k))
                  for k in range(n_trace)]
        jax.profiler.start_trace(os.path.join(tdir, "steps"))
        t_win = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for seeds, key in traced:
                with jax.profiler.TraceAnnotation("bench.step"):
                    params, state, m = engine.step(params, state, data,
                                                   seeds, key, tag=i)
                sampled_v.append(m["sampled_v"])
                i += 1
            with jax.profiler.TraceAnnotation("bench.flush"):
                params, state, _ = engine.flush(params, state, data)
                jax.block_until_ready((params, state.opt))
        window = time.perf_counter() - t_win
        jax.profiler.stop_trace()
        steps = n_trace
    peak = jax.devices()[0].memory_stats() or {}
    window_compiles = clock.count - compiles_before

    if trace:
        # the compiled step's HLO gives the traced ops their op paths
        # (found in the persistent cache: the window ran this program)
        compiled = engine.step_fn.lower(
            params, state.opt, data.graph, data.features, data.labels,
            *traced[0]).compile()
        paths = op_paths(compiled.as_text())
        memory = compiled.memory_analysis()
        # per-layer counts of the traced steps: the engine's staged
        # sampling program gives the fused step's sampled sets bit for bit
        sample = engine.staged.sample
        jax.block_until_ready(sample(data.graph, *traced[0]))
        jax.profiler.start_trace(os.path.join(tdir, "sample"))
        with jax.profiler.TraceAnnotation("bench.window"):
            blocks = [sample(data.graph, s, k) for s, k in traced]
            jax.block_until_ready(blocks)
        jax.profiler.stop_trace()
        counts = [[{"seeds": int(b.num_seeds), "next": int(b.num_next),
                    "edges": int(b.num_edges)} for b in bl] for bl in blocks]
        del blocks
        steps_trace = Trace.from_dir(os.path.join(tdir, "steps"), paths)
        from bench import work
        # what a per-layer metric's reader may read
        ctx = SimpleNamespace(config=config, traffic=traffic,
                      graph_build_s=graph_build_s, trace=steps_trace,
                      sample_trace=Trace.from_dir(os.path.join(tdir,
                                                               "sample")),
                      steps=steps, step_s=window / steps, counts=counts,
                      sampled_v=[int(v) for v in sampled_v],
                      peak=(work.peaks(info["kind"])
                            if info["platform"] == "tpu" else None),
                      work=work.load_model(config["model"]))
    stats = engine.stats
    del engine, data, state, params, m
    gc.collect()

    ref = reference.run(config, traffic, g, features, params0,
                        [feed.batch(k) for k in range(n_check)],
                        [feed.key(k) for k in range(n_check)])
    ok, table = check.verdict(check.numbers(prog, ref), spec["limits"])

    if trace:
        out["metrics"] = {}
        for mdef in per_layer_metrics(bench, name):
            value = load_reader(mdef["name"]).read(ctx)
            if value is not None:
                out["metrics"][mdef["name"]] = {"value": value,
                                                "unit": mdef["unit"]}
    # the TPU runtime reserves a program's temporary buffers apart from
    # the arrays it holds: the chip's peak is the sum of both peaks
    device = dict(info, memory_peak_bytes=int(
        peak.get("peak_bytes_in_use", 0)
        + peak.get("peak_bytes_reserved", 0)))
    if trace and ctx.trace.busy_s is not None:
        device.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
    result = {"correct": ok, "attempted": steps, "failed": 0,
              "metrics": out["metrics"], "device": device}
    if trace:
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                               "idle_gaps": ctx.trace.idle_gaps(10)}
    result["checks"] = table
    log = {}
    if trace:
        log["step_program_gb"] = {
            "temp": memory.temp_size_in_bytes / 1e9,
            "arguments": memory.argument_size_in_bytes / 1e9}
    result["_log"] = {**log,
        "graph_build_s": graph_build_s, "vertices": g.num_vertices,
        "edges": g.num_edges, "max_in_degree": g.max_in_degree,
        "caps": [dataclasses.astuple(c) for c in sampler.spec.caps],
        "check_losses": prog["losses"], "ref_losses": ref["losses"],
        "check_counts": prog["counts"], "ref_counts": ref["counts"],
        "window_s": window, "steps": steps,
        "window_compiles": window_compiles,
        "compile_s": clock.seconds, "overflow_replays": stats.overflow_replays,
        "overflow_retries": stats.overflow_retries,
        "memory_stats": peak,
        "sampled_v_mean": (float(np.mean([int(v) for v in sampled_v]))
                           if sampled_v else None)}
    return result


def emit(result: Dict) -> None:
    """The run's log line and checks on standard error, the result line
    last on standard output."""
    log = result.pop("_log")
    print(json.dumps({"log": log}), file=sys.stderr)
    print(json.dumps(result), flush=True)
    for n, v in result["checks"].items():
        print(f"check {n}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = _load_json(ROOT, "BENCHMARK.json")
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0
