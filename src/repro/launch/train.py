"""Training launcher.

GNN (the paper's workload):
  PYTHONPATH=src python -m repro.launch.train --workload gnn \
      --dataset products --scale 0.01 --sampler labor-0 --steps 200
  PYTHONPATH=src python -m repro.launch.train --list-samplers
GNN on the partition-aware distributed engine (docs/distributed.md):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --workload gnn \
      --mesh-devices 4 --batch-size 512 --steps 50
LM (any assigned arch, reduced or full):
  PYTHONPATH=src python -m repro.launch.train --workload lm \
      --arch gemma2-2b --reduce --steps 50 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import json


def main():
    from repro.core.samplers import (make_list_samplers_action,
                                     sampler_arg_type)
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=["gnn", "lm"], default="gnn")
    # gnn
    ap.add_argument("--dataset", default="products")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--sampler", default="labor-0", type=sampler_arg_type,
                    help="any registered sampler (see --list-samplers)")
    ap.add_argument("--list-samplers", action=make_list_samplers_action(),
                    help="print the sampler registry and exit")
    ap.add_argument("--model", default="gcn")
    ap.add_argument("--fanouts", default="10,10,10")
    ap.add_argument("--layer-sizes", default=None,
                    help="comma-separated per-layer budgets for (p)ladies")
    ap.add_argument("--batch-size", type=int, default=1000)
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="one-program sample+train step with donated "
                         "buffers (--no-fused for the eager baseline)")
    ap.add_argument("--pipeline", default="off",
                    choices=["off", "prefetch", "full"],
                    help="staged pipeline driver (runtime/pipeline.py): "
                         "off lowers to the single fused program; "
                         "prefetch samples one batch ahead; full adds "
                         "double-buffered feature gathers")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="> 0: run the partition-aware distributed engine "
                         "over this many devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count on CPU)")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8"],
                    help="gradient all-reduce compression (mesh only)")
    from repro.ops.backend import BACKEND_CHOICES
    ap.add_argument("--backend", default="auto",
                    choices=list(BACKEND_CHOICES),
                    help="graph-ops backend (repro.ops): auto resolves "
                         "to the Pallas MXU kernels on TPU, the XLA "
                         "reference elsewhere; pallas off-TPU runs in "
                         "interpret mode (parity debugging, slow)")
    # lm
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduce", action="store_true",
                    help="shrink the arch for CPU-scale runs")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--guard", default="off",
                    choices=["off", "quarantine", "rollback"],
                    help="guardrail (docs/robustness.md): detect "
                         "NaN/Inf loss/grads and loss spikes on device "
                         "(polled one step late, no per-step host sync) "
                         "and recover by batch quarantine or checkpoint "
                         "rollback")
    ap.add_argument("--guard-warmup", type=int, default=5,
                    help="clean batches before spike detection arms")
    ap.add_argument("--guard-spike-factor", type=float, default=4.0,
                    help="loss > factor x EMA flags a spike")
    ap.add_argument("--inject", default=None,
                    help="fault-injection plan (repro.runtime.inject "
                         "spec, e.g. 'nan_grad@5,torn_ckpt@1'); "
                         "concatenated with $REPRO_INJECT")
    # common
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.workload == "gnn":
        import os

        from repro.graph import paper_dataset
        from repro.runtime import inject as inject_lib
        from repro.runtime.trainer import GNNTrainConfig, evaluate_gnn, train_gnn

        ds = paper_dataset(args.dataset, scale=args.scale, seed=args.seed)
        fanouts = tuple(int(x) for x in args.fanouts.split(","))
        layer_sizes = (tuple(int(x) for x in args.layer_sizes.split(","))
                       if args.layer_sizes else None)
        # --inject and $REPRO_INJECT are concatenated: the env var arms
        # a whole CI job, the flag arms one launch
        inject_spec = ",".join(
            s for s in (os.environ.get(inject_lib.ENV_VAR), args.inject) if s)
        cfg = GNNTrainConfig(
            model=args.model, fanouts=fanouts, num_layers=len(fanouts),
            sampler=args.sampler, layer_sizes=layer_sizes,
            batch_size=args.batch_size,
            steps=args.steps, lr=args.lr, ckpt_dir=args.ckpt_dir,
            seed=args.seed, fused=args.fused,
            mesh_devices=args.mesh_devices,
            grad_compression=args.grad_compression,
            backend=args.backend, pipeline=args.pipeline,
            guard=args.guard, guard_warmup=args.guard_warmup,
            guard_spike_factor=args.guard_spike_factor,
            inject=inject_lib.parse(inject_spec))
        out = train_gnn(ds, cfg)
        val = evaluate_gnn(ds, out["params"], cfg, ds.val_idx)
        h = out["history"]
        report = {
            "final_loss": h[-1]["loss"], "val_acc": val,
            "wall_time_s": round(out["wall_time"], 1),
            "avg_sampled_vertices": sum(x["sampled_v"] for x in h) / len(h),
            "stragglers_skipped": out["stats"].stragglers_skipped,
            "overflow_retries": out["stats"].overflow_retries,
            "overflow_replays": out["stats"].overflow_replays,
            "overflow_by_layer": out["stats"].overflow_by_layer,
        }
        if "guard_stats" in out:
            gs = out["guard_stats"]
            report.update(guard=args.guard,
                          guard_quarantines=gs.quarantines,
                          guard_rollbacks=gs.rollbacks,
                          guard_nonfinite_batches=gs.nonfinite_batches,
                          guard_spike_batches=gs.spike_batches)
        if "inject_log" in out:
            report["inject_fired"] = [list(x) for x in out["inject_log"]]
        print(json.dumps(report, indent=1))
    else:
        import jax
        import jax.numpy as jnp
        from repro import configs as cfgreg
        from repro.data.tokens import BigramStream
        from repro.models.transformer import lm as lm_lib, stack
        from repro.optim import adam

        cfg = cfgreg.get_config(args.arch, dtype="float32")
        if args.reduce:
            from repro.configs.reduce import reduce_cfg
            cfg = reduce_cfg(cfg)
        params = stack.init_params(jax.random.key(args.seed), cfg)
        opt_cfg = adam.AdamConfig(lr=args.lr)
        opt = adam.init_state(params, opt_cfg)
        step = jax.jit(lm_lib.make_train_step(cfg, opt_cfg))
        stream = BigramStream(cfg.vocab, seed=args.seed)
        xsrc = None
        if cfg.xattn_source_len:
            dim = (cfg.encoder.d_model if cfg.encoder is not None
                   else cfg.xattn_source_dim)
            xsrc = jnp.zeros((args.batch, cfg.xattn_source_len, dim),
                             jnp.dtype(cfg.dtype))
        losses = []
        for i in range(args.steps):
            toks, labels = stream.batch(args.batch, args.seq)
            batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
            if xsrc is not None:
                batch["xsource"] = xsrc
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
            if (i + 1) % 10 == 0:
                print(f"step {i+1} loss {losses[-1]:.4f}")
        print(json.dumps({"first_loss": losses[0], "final_loss": losses[-1]}))


if __name__ == "__main__":
    main()
