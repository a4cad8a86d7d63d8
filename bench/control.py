#!/usr/bin/env python3
"""Readings that set a cell's upper limits: the control and the planted
faults, with the reference put in the program's place.

    python3 bench/control.py --workload gcn-products.labor0 \
        --seeds 11,12,13

For each seed, on the cell's own graph, weights, batches and keys (as a
benchmark run makes them), the float32 reference is compared by
``check.py`` with:

* ``control``: the same reference with the operands of every matrix
  product rounded to float8, one step below the bfloat16 products of
  the program's float32 matmuls at XLA's default precision;
* ``half_batch``: the reference trained on the first half of each batch
  alone, its loss the mean over that half.

A step that returns its state unchanged needs no run: it reads 1 on
``update`` by construction. The benchmark's own runs never run this.
Prints one JSON line per seed and reading.
"""
import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(_ROOT, "bench", ".cache", "tpu_logs"))


def readings(bench, name, seed, adjust=None, check_chips=True):
    """{reading: numbers} for one seed of a cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check, graph as graph_lib, harness
    from bench.reference import train as reference

    spec = harness.load_cell(bench, name)
    if adjust is not None:
        spec = adjust(spec)
    config, traffic = spec["config"], spec["traffic"]
    if check_chips:
        harness.require_tpu(spec["cell"]["chips"])
    harness.enable_compile_cache()
    gspec = config["graph"]
    g = graph_lib.load_or_build(config["name"], gspec)
    features = graph_lib.device_features(gspec, jnp.asarray(g.labels))
    feed = harness.Feed(g.train_idx, traffic, seed)
    model = reference.load_model(config["model"])
    params0 = jax.tree.map(np.asarray, jax.jit(
        lambda k: model.init(k, config, gspec["num_features"],
                             gspec["num_classes"]))(
        jax.random.key(feed.param_seed)))
    n = traffic["check_steps"]
    args = (config, traffic, g, features, params0,
            [feed.batch(k) for k in range(n)],
            [feed.key(k) for k in range(n)])
    ref = reference.run(*args)
    return {
        "control": check.numbers(reference.run(*args, control=True), ref),
        "half_batch": check.numbers(reference.run(*args, keep=0.5), ref)}


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = readings(bench, args.workload, seed)
        except harness.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        for what, nums in out.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": what, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
