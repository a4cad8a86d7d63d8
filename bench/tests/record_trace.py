#!/usr/bin/env python3
"""Record the small device trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py     # on a TPU

Traces, inside a ``bench.window`` span, two calls of a program ``step``
that runs the three kernels the benchmark's metrics look for (the
frontier sort under ``jit(compact_block_parallel)``, the SpMM under
``jit(spmm_sorted)`` and the edge-softmax statistics under
``jit(edge_softmax_stats)``), with a host-side sleep between them, and
then one call of a program ``sample``. Writes
``fixtures/small.xplane.pb`` next to this file, and beside it
``small.paths.json``: the op paths of ``step``'s compiled HLO
(``bench.trace.op_paths``).
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def program():
    """The traced program ``step`` and a function making its arguments."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.edge_softmax.ops import edge_softmax_block
    from repro.kernels.frontier.parallel import compact_block_parallel
    from repro.kernels.spmm.ops import scatter_sorted_block

    E, S, F, H = 1 << 15, 1024, 128, 8
    dst = jnp.sort(jax.random.randint(jax.random.key(0), (E,), 0, S))
    mask = jnp.arange(E) < E - 100

    @jax.jit
    def step(vals, logits, flags):
        sel, emask, n = compact_block_parallel(flags, E // 2)
        out = scatter_sorted_block(dst, mask, vals, S)
        alpha = edge_softmax_block(dst, mask, logits, S)
        return out.sum() + alpha.sum() + n + sel.sum()

    def args():
        return (jax.random.normal(jax.random.key(1), (E, F)),
                jax.random.normal(jax.random.key(2), (E, H)),
                jax.random.bernoulli(jax.random.key(3), 0.3, (E,)))

    return step, args


def main():
    import jax
    import jax.numpy as jnp

    from bench.trace import op_paths

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    step, args = program()

    @jax.jit
    def sample(vals):
        return jnp.cumsum(vals, axis=0)

    vals, logits, flags = args()
    jax.block_until_ready((step(vals, logits, flags), sample(vals)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(step(vals, logits, flags))
            with jax.profiler.TraceAnnotation("bench.host"):
                time.sleep(0.01)
        jax.block_until_ready(sample(vals))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst_path = os.path.join(HERE, "fixtures", "small.xplane.pb")
    os.makedirs(os.path.dirname(dst_path), exist_ok=True)
    shutil.copy(src, dst_path)
    shutil.rmtree(tmp)
    hlo = step.lower(vals, logits, flags).compile().as_text()
    with open(os.path.join(HERE, "fixtures", "small.paths.json"), "w") as f:
        json.dump(op_paths(hlo), f, indent=0, sort_keys=True)
    print(dst_path, os.path.getsize(dst_path))


if __name__ == "__main__":
    main()
