"""Serving drivers.

GNN node-classification serving (the paper's workload): a stream of
small seed requests answered from the same registry ``Sampler`` the
trainer uses — ``full`` gives exact (full-neighborhood) inference, any
other entry gives sampled inference. By default requests flow through
the async serving driver (``repro.serving``): continuous batch
coalescing into the engine's fixed-shape fused infer program, optional
device-resident feature / stale hidden-state caches, deadline + SLO
accounting (docs/serving.md):

  PYTHONPATH=src python -m repro.launch.serve --workload gnn \
      --dataset products --scale 0.01 --sampler labor-0 \
      --requests 64 --request-size 8 --feature-cache 4096

``--driver off`` keeps the synchronous baseline — one fixed-shape
dispatch per request — with honest latency accounting: compile time
(first dispatch, and every ``engine.grow()`` cap retry, each a fresh
jit specialization) is tagged and excluded from the warm p50/p99
instead of silently folding into the tail.

LM batched decode (CPU-scale demo of the serve_step the dry-run lowers
at production scale):

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --reduce --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import json
import time


def _build_gnn_serving(args):
    """Shared setup of both GNN serve paths: dataset, params, engine."""
    import jax
    import numpy as np

    from repro.core import samplers
    from repro.graph import paper_dataset
    from repro.models import gnn as gnn_models
    from repro.optim import adam
    from repro.runtime import checkpoint as ckpt_lib
    from repro.runtime.engine import TrainEngine

    ds = paper_dataset(args.dataset, scale=args.scale, seed=args.seed)
    fanouts = tuple(int(x) for x in args.fanouts.split(","))
    n_cls = int(ds.labels.max()) + 1

    init_fn, apply_fn = gnn_models.MODELS[args.model]
    params = init_fn(jax.random.key(args.seed), ds.features.shape[1],
                     args.hidden, n_cls, len(fanouts))
    if args.ckpt_dir:
        last = ckpt_lib.latest_step(args.ckpt_dir)
        if last is not None:
            params = ckpt_lib.restore(args.ckpt_dir, last,
                                      {"params": params})["params"]

    # the engine's fused infer program from the same registry object +
    # overflow protocol as training: engine.grow() grows the caps that
    # overflowed and rebuilds (rare, amortized)
    sampler = samplers.from_dataset(args.sampler, ds, batch_size=args.batch,
                                    fanouts=fanouts, safety=2.0)
    engine = TrainEngine(sampler, apply_fn, adam.AdamConfig(),
                         backend=args.backend)
    data = engine.make_data_from_dataset(ds)
    return ds, engine, data, params, np.asarray(ds.labels)


def _gnn_trace(args, ds):
    """The request stream: ``--requests`` requests of ``--request-size``
    seeds each over the validation ids — sequential scan, or a Zipfian
    draw (``--trace zipf``) modelling skewed, repeat-heavy production
    traffic."""
    import numpy as np

    idx = np.asarray(ds.val_idx)
    size = args.request_size or args.batch
    rng = np.random.default_rng(args.seed + 7)
    out = []
    for r in range(args.requests):
        if args.trace == "zipf":
            ranks = np.arange(1, len(idx) + 1, dtype=np.float64)
            p = ranks ** -args.zipf_a
            out.append(rng.choice(idx, size=size, p=p / p.sum()))
        else:
            lo = (r * size) % max(len(idx) - size, 1)
            out.append(idx[lo:lo + size])
    return out


def _accuracy(requests, tickets_logits, labels):
    import numpy as np
    correct = total = 0
    for seeds, logits in zip(requests, tickets_logits):
        if logits is None:
            continue
        pred = np.argmax(logits, -1)
        correct += int((pred == labels[seeds]).sum())
        total += len(seeds)
    return correct / max(total, 1)


def serve_gnn_sync(args):
    """The ``--driver off`` baseline: one fixed-shape fused infer
    dispatch per request, synchronous. Retries follow the trainer's
    ``sample_with_retry`` contract (``TrainEngine.infer_with_retry`` —
    grow + same-key re-dispatch, ``SamplingOverflowError`` on
    exhaustion), and every fresh jit specialization is recorded as a
    tagged compile event, never folded into p50/p99."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.interface import pad_seeds
    from repro.serving.metrics import ServingStats

    ds, engine, data, params, labels = _build_gnn_serving(args)
    requests = _gnn_trace(args, ds)
    stats = ServingStats()
    key = jax.random.key(args.seed + 1)
    answers = []
    for seeds_np in requests:
        stats.submitted += 1
        seeds = pad_seeds(jnp.asarray(seeds_np), args.batch)
        key, sk = jax.random.split(key)
        gen_before = engine.generation
        first = stats.batches == 0
        t0 = time.perf_counter()
        logits, grows = engine.infer_with_retry(params, data, seeds, sk)
        logits = np.asarray(logits)[:len(seeds_np)]
        dt = time.perf_counter() - t0
        stats.grow_events += grows
        stats.record_batch(
            dt, len(seeds_np), 1,
            compile_event=first or engine.generation != gen_before,
            grows=grows)
        stats.served += 1
        answers.append(logits)
    report = stats.report()
    report.update(sampler=engine.sampler.name, backend=engine.backend,
                  exact=engine.sampler.name == "full", driver="off",
                  requests=args.requests,
                  request_size=args.request_size or args.batch,
                  batch=args.batch,
                  accuracy=round(_accuracy(requests, answers, labels), 4))
    print(json.dumps(report, indent=1))
    return report


def serve_gnn_driver(args):
    """The async serving path: requests stream into the
    :class:`~repro.serving.driver.ServingDriver`, which coalesces them
    into the engine's fixed-shape program and scatters per-seed logits
    back, with the device-resident caches exploiting request skew."""
    import os

    from repro.runtime import inject as inject_lib
    from repro.serving import HiddenCache, ServingDriver, VertexCache

    ds, engine, data, params, labels = _build_gnn_serving(args)
    requests = _gnn_trace(args, ds)
    fc = (VertexCache(args.feature_cache, args.cache_policy)
          if args.feature_cache else None)
    hc = (HiddenCache(args.hidden_cache, max_age=args.max_age,
                      policy=args.cache_policy)
          if args.hidden_cache else None)
    inject_spec = ",".join(
        s for s in (os.environ.get(inject_lib.ENV_VAR),
                    getattr(args, "inject", None)) if s)
    driver = ServingDriver(engine, params, data, batch_size=args.batch,
                           feature_cache=fc, hidden_cache=hc,
                           deadline_ms=args.deadline_ms,
                           max_queue=args.max_queue, seed=args.seed + 1,
                           inject=inject_lib.parse(inject_spec),
                           cache_fault_limit=args.cache_fault_limit)
    tickets = [driver.submit(r) for r in requests]
    driver.drain()
    report = driver.stats.report()
    report.update(sampler=engine.sampler.name, backend=engine.backend,
                  exact=engine.sampler.name == "full", driver="async",
                  requests=args.requests,
                  request_size=args.request_size or args.batch,
                  batch=args.batch,
                  accuracy=round(_accuracy(
                      requests,
                      [t.logits if t.status == "ok" else None
                       for t in tickets], labels), 4))
    print(json.dumps(report, indent=1))
    return report


def serve_lm(args):
    import jax
    import jax.numpy as jnp
    from repro import configs as cfgreg
    from repro.models.transformer import stack

    cfg = cfgreg.get_config(args.arch, dtype="float32")
    if args.reduce:
        from repro.configs.reduce import reduce_cfg
        cfg = reduce_cfg(cfg)

    key = jax.random.key(args.seed)
    params = stack.init_params(key, cfg)
    B, P, G = args.batch, args.prompt_len, args.gen
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab)
    xsource = None
    if cfg.xattn_source_len:
        dim = (cfg.encoder.d_model if cfg.encoder is not None
               else cfg.xattn_source_dim)
        xsource = jax.random.normal(key, (B, cfg.xattn_source_len, dim))

    t0 = time.time()
    last_logits, cache = stack.prefill(params, prompts, cfg, xsource=xsource)
    # widen kv caches for the generated region
    cache = jax.tree.map(
        lambda a: (jnp.pad(a, ((0, 0), (0, 0), (0, G), (0, 0), (0, 0)))
                   if a.ndim == 5 and a.shape[2] == P else a), cache)
    t_prefill = time.time() - t0

    decode = jax.jit(lambda p, t, c, pos: stack.decode_step(p, t, c, pos, cfg))
    tok = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]
    out = [tok]
    t0 = time.time()
    for i in range(G - 1):
        logits, cache = decode(params, tok, cache, jnp.int32(P + i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(tok)
    toks = jnp.concatenate(out, 1)
    dt = time.time() - t0
    print(f"prefill {B}x{P} in {t_prefill:.2f}s; "
          f"decoded {B}x{G} in {dt:.2f}s "
          f"({B * (G - 1) / max(dt, 1e-9):.1f} tok/s)")
    print("sample:", toks[0, :12].tolist())


def main():
    from repro.core.samplers import (make_list_samplers_action,
                                     sampler_arg_type)
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=["lm", "gnn"], default="lm")
    # lm
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="lm: decode batch; gnn: the fused infer "
                         "program's seed-buffer shape (the coalescing "
                         "target)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    # gnn
    ap.add_argument("--dataset", default="products")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--sampler", default="full", type=sampler_arg_type,
                    help="any registered sampler; 'full' = exact "
                         "inference (see --list-samplers)")
    ap.add_argument("--list-samplers", action=make_list_samplers_action(),
                    help="print the sampler registry and exit")
    ap.add_argument("--model", default="gcn")
    ap.add_argument("--fanouts", default="10,10,10")
    ap.add_argument("--hidden", type=int, default=256)
    from repro.ops.backend import BACKEND_CHOICES
    ap.add_argument("--backend", default="auto",
                    choices=list(BACKEND_CHOICES),
                    help="graph-ops backend for the fused infer program "
                         "(repro.ops; auto = Pallas kernels on TPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--request-size", type=int, default=0,
                    help="seeds per request (0 = one full batch per "
                         "request, the historical baseline shape)")
    ap.add_argument("--driver", default="async", choices=["async", "off"],
                    help="async = continuous-batching request driver "
                         "(repro.serving); off = one synchronous "
                         "dispatch per request (baseline)")
    ap.add_argument("--trace", default="scan", choices=["scan", "zipf"],
                    help="request stream: sequential scan of val ids, "
                         "or a Zipfian (skewed, repeat-heavy) draw")
    ap.add_argument("--zipf-a", type=float, default=1.1,
                    help="Zipf exponent of --trace zipf")
    ap.add_argument("--feature-cache", type=int, default=0,
                    help="device-resident feature-cache slots "
                         "(0 = off; bit-exact either way)")
    ap.add_argument("--hidden-cache", type=int, default=0,
                    help="stale hidden-state cache slots (0 = off)")
    ap.add_argument("--max-age", type=int, default=0,
                    help="hidden-cache staleness bound in serve steps "
                         "(0 = bit-exact, entries never served stale)")
    ap.add_argument("--cache-policy", default="fifo",
                    choices=["fifo", "freq"],
                    help="cache slot eviction policy")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for timeout/SLO "
                         "accounting (async driver)")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="pending-request bound before admission "
                         "rejects (backpressure)")
    ap.add_argument("--inject", default=None,
                    help="fault-injection plan (repro.runtime.inject "
                         "spec, e.g. 'cache_corrupt@2,pump_death@1'); "
                         "concatenated with $REPRO_INJECT; async "
                         "driver only")
    ap.add_argument("--cache-fault-limit", type=int, default=2,
                    help="nonfinite-logit faults under an enabled "
                         "cache before the driver falls back to "
                         "cache-off for good (graceful degradation)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.workload == "gnn":
        if args.driver == "async":
            serve_gnn_driver(args)
        else:
            serve_gnn_sync(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
