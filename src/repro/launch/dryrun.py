import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=512", ""))
# NOTE: the two lines above MUST run before any jax import — jax locks the
# device count on first initialization. Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on
the production meshes and extract memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun
Each cell writes a JSON record; failures are bugs (sharding mismatch,
compile OOM) and are reported with the exception text.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs as cfgreg
from repro.configs.labor_gcn import GNNWorkloadConfig
from repro.distributed import sharding as sh
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import lm, stack
from repro.models.transformer.config import shape_by_name
from repro.optim import adam

BIG_ARCHS = {"qwen3-moe-235b-a22b"}  # bf16 opt state, 16 GB/chip


def _param_count(cfg) -> float:
    import math
    shapes = jax.eval_shape(lambda: stack.init_params(jax.random.key(0), cfg))
    return float(sum(math.prod(s.shape) for s in jax.tree.leaves(shapes)))


def _active_frac(arch: str, cfg) -> float:
    """active/total parameter fraction for MoE archs (MODEL_FLOPS)."""
    if isinstance(cfg, GNNWorkloadConfig) or getattr(cfg, "moe", None) is None:
        return 1.0
    shapes = jax.eval_shape(lambda: stack.init_params(jax.random.key(0), cfg))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = active = 0.0
    m = cfg.moe
    for path, leaf in flat:
        n = 1
        for d in leaf.shape:
            n *= d
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        total += n
        if len(leaf.shape) >= 3 and leaf.shape[-3] == m.num_experts and any(
                nm in ("ewi", "ewg", "ewo") for nm in names):
            active += n * m.top_k / m.num_experts
        else:
            active += n
    return active / total


HBM_BUDGET = 14 * 2**30  # leave headroom under 16 GiB/chip


def microbatches_for(cfg, shape, dp, chips=256, n_params=0.0,
                     opt_bytes=4) -> int:
    """Pick the SMALLEST microbatch count whose activation footprint fits
    the HBM budget (§Perf iteration 1: every extra microbatch re-pays the
    FSDP weight all-gathers, so blanket token targets over-communicate —
    small models need no microbatching at all).

    Activation model per device per microbatch (bf16, full-remat scan):
      carries   = repeats x tokens_mb x d_model x 2
      logits    = tokens_mb x vocab/TP x 4 x 2   (fwd value + bwd cotangent)
      dispatch  = tokens_mb x top_k x cf x d x 2 x 3   (MoE xd/ye/yf)
    """
    tp = 16
    tokens_dev = shape.global_batch * shape.seq_len // max(dp, 1)
    # params + grads + 2 optimizer moments, fully sharded
    state_dev = n_params * (2 + 2 + 2 * opt_bytes) / max(chips, 1)
    budget = max((HBM_BUDGET - state_dev) * 0.6, 2 * 2**30)

    def act_bytes(n_mb):
        t = tokens_dev / n_mb
        b = cfg.repeats * t * cfg.d_model * 2
        b += t * cfg.vocab / tp * 4 * 2
        if cfg.moe is not None:
            b += t * cfg.moe.top_k * cfg.moe.capacity_factor * cfg.d_model * 2 * 3
        return b

    for n_mb in sorted({d for d in range(1, shape.global_batch + 1)
                        if shape.global_batch % d == 0}):
        if act_bytes(n_mb) < budget:
            return n_mb
    return shape.global_batch


def lower_lm_cell(arch: str, shape_name: str, mesh, *, seq_shard_cache=True,
                  cfg=None, n_mb_override=None):
    if cfg is None:
        cfg = cfgreg.get_config(arch, dtype="bfloat16")
    shape = shape_by_name(shape_name)
    chips = 1
    for a in mesh.axis_names:
        chips *= mesh.shape[a]
    dp = chips // mesh.shape["model"]
    dp_ok = shape.global_batch % dp == 0
    dp_axes = ("pod", "data") if dp_ok else ()

    param_specs = sh.shard_params_specs(
        lambda: stack.init_params(jax.random.key(0), cfg), mesh)

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = adam.AdamConfig(
                lr=1e-3,
                state_dtype="bfloat16" if arch in BIG_ARCHS else "float32")
            opt_shapes = jax.eval_shape(
                lambda p: adam.init_state(p, opt_cfg), param_specs)

            def attach(tree):
                shards = sh.params_shardings(tree, mesh)
                return jax.tree.map(
                    lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                        sharding=shd),
                    tree, shards)

            opt_specs = {"mu": attach(opt_shapes["mu"]),
                         "nu": attach(opt_shapes["nu"]),
                         "step": opt_shapes["step"]}
            ispecs = lm.input_specs(cfg, shape, mesh, dp_axes)
            if n_mb_override is not None:
                n_mb = n_mb_override
            elif cfg.scan_layers:
                n_mb = microbatches_for(
                    cfg, shape, dp, chips=chips, n_params=_param_count(cfg),
                    opt_bytes=2 if arch in BIG_ARCHS else 4)
            else:
                n_mb = 1
            step = lm.make_train_step(
                cfg, opt_cfg, num_microbatches=n_mb,
                accum_dtype="bfloat16" if arch in BIG_ARCHS else "float32",
                unroll_microbatches=not cfg.scan_layers)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                param_specs, opt_specs, ispecs["batch"])
            tokens = shape.global_batch * shape.seq_len
            is_train = True
        elif shape.kind == "prefill":
            ispecs = lm.input_specs(cfg, shape, mesh, dp_axes)
            step = lm.make_prefill_step(cfg)
            lowered = jax.jit(step).lower(param_specs, ispecs["batch"])
            tokens = shape.global_batch * shape.seq_len
            is_train = False
        else:  # decode
            ispecs = lm.input_specs(cfg, shape, mesh, dp_axes)
            cache = lm.cache_specs(cfg, shape, mesh,
                                   seq_shard=seq_shard_cache,
                                   dp_axes=dp_axes)
            step = lm.make_serve_step(cfg)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                param_specs, cache, ispecs["tokens"], ispecs["pos"])
            tokens = shape.global_batch  # one token per sequence
            is_train = False

        compiled = lowered.compile()

    n_params = _param_count(cfg)
    mf = rl.model_flops(n_params, tokens, _active_frac(arch, cfg), is_train)
    return lowered, compiled, dict(model_flops=mf, params=n_params,
                                   chips=chips)


def lower_gnn_cell(arch: str, mesh):
    from repro.launch.gnn_step import abstract_param_state, build_gnn_engine
    cfg = cfgreg.get_config(arch)
    chips = 1
    for a in mesh.axis_names:
        chips *= mesh.shape[a]
    engine, meta = build_gnn_engine(mesh, cfg)
    pspec, ospec, espec = abstract_param_state(engine, cfg)
    ins = engine.abstract_inputs(
        global_batch=meta["global_batch"], num_vertices=cfg.num_vertices,
        num_edges=int(cfg.num_vertices * cfg.avg_degree),
        feature_dim=cfg.feature_dim)
    with jax.sharding.set_mesh(mesh):
        args = (pspec, ospec, espec, ins["indptr"], ins["indices"],
                ins["features"], ins["labels"], ins["seeds"], ins["key"])
        lowered = engine.step_fn.lower(*args)
        compiled = lowered.compile()
    # GCN "model flops": 3 layers x (agg + dense) over sampled graph; use
    # dense-update flops of the expected sampled sizes (fanout geometry)
    lb = meta["local_batch"] * meta["num_devices"]
    sizes = [lb]
    for k in cfg.fanouts:
        sizes.append(sizes[-1] * (1 + min(k, cfg.avg_degree)))
    dims = [cfg.feature_dim] + [cfg.hidden] * (cfg.num_layers - 1) + [cfg.num_classes]
    mf = 0.0
    for l in range(cfg.num_layers):
        mf += 2 * sizes[cfg.num_layers - 1 - l] * dims[l] * dims[l + 1] * 2  # w + wr
    mf *= 3  # fwd + bwd
    return lowered, compiled, dict(model_flops=mf, params=0, chips=chips,
                                   meta={k: str(v) for k, v in meta.items()})


def _cost_of(compiled):
    cost = compiled.cost_analysis() or {}
    coll = rl.collective_wire_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _depth_variant(arch: str, repeats: int):
    """Unrolled small-depth config for cost extrapolation (scan bodies are
    counted once by cost_analysis, so we difference 1- and 2-repeat
    unrolled compiles — see roofline.extrapolate_depth)."""
    base = cfgreg.get_config(arch, dtype="bfloat16")
    enc = base.encoder
    if enc is not None:
        enc = dataclasses.replace(enc, scan_layers=False)
    cfg = dataclasses.replace(
        base, num_layers=len(base.layer_pattern) * repeats,
        scan_layers=False, encoder=enc)
    return cfg, base.repeats


def lm_cell_costs(arch: str, shape_name: str, mesh, n_mb=None):
    """(flops, bytes, wire_bytes, by_kind) per device, depth-extrapolated.

    ``n_mb``: microbatch count of the REAL step; the unrolled cost
    variants replay it (unrolled) so per-microbatch FSDP weight
    re-gathers are counted in the collective term."""
    cfg1, repeats = _depth_variant(arch, 1)
    cfg2, _ = _depth_variant(arch, 2)
    _, c1, _ = lower_lm_cell(arch, shape_name, mesh, cfg=cfg1,
                             n_mb_override=n_mb)
    _, c2, _ = lower_lm_cell(arch, shape_name, mesh, cfg=cfg2,
                             n_mb_override=n_mb)
    f1, b1, w1 = _cost_of(c1)
    f2, b2, w2 = _cost_of(c2)
    ex = rl.extrapolate_depth
    by_kind = {}
    for kind in set(w1.by_kind) | set(w2.by_kind):
        by_kind[kind] = ex(w1.by_kind.get(kind, 0.0),
                           w2.by_kind.get(kind, 0.0), repeats)
    return (ex(f1, f2, repeats), ex(b1, b2, repeats),
            ex(w1.wire_bytes, w2.wire_bytes, repeats), by_kind)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir=None,
             verbose=True):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "status": "ok"}
    try:
        if arch.startswith("labor-gcn"):
            lowered, compiled, info = lower_gnn_cell(arch, mesh)
            flops, bytes_, coll = _cost_of(compiled)
            wire, by_kind = coll.wire_bytes, coll.by_kind
        else:
            lowered, compiled, info = lower_lm_cell(arch, shape_name, mesh)
            flops, bytes_, wire, by_kind = lm_cell_costs(arch, shape_name,
                                                         mesh)
        ma = compiled.memory_analysis()
        terms = rl.roofline_terms(flops, bytes_, wire, by_kind,
                                  model_flops_total=info["model_flops"],
                                  chips=info["chips"])
        rec.update(
            compile_s=round(time.time() - t0, 1),
            params=info.get("params"),
            memory=dict(
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes,
                peak_per_device=ma.argument_size_in_bytes
                + ma.temp_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes,
            ),
            roofline=terms,
        )
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_kind}] OK "
                  f"compile={rec['compile_s']}s "
                  f"peak/dev={rec['memory']['peak_per_device']/2**30:.2f}GiB "
                  f"flops/dev={terms['flops_per_device']:.3e} "
                  f"dominant={terms['dominant']} "
                  f"roofline={terms['roofline_fraction']:.3f}")
            print("  memory_analysis:", ma)
            print(f"  extrapolated: flops/dev={flops:.3e} "
                  f"bytes/dev={bytes_:.3e} wire/dev={wire:.3e}")
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_kind}] FAIL: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}__{shape_name}__{mesh_kind}.json".replace("/", "_")
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gnn", action="store_true", help="include labor-gcn cells")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch, cell in cfgreg.all_lm_cells():
            if cell["run"]:
                cells.append((arch, cell["shape"]))
            else:
                print(f"[{arch} x {cell['shape']}] SKIP: {cell['reason']}")
        if args.gnn:
            cells.append(("labor-gcn", "train_batch"))
    else:
        cells.append((args.arch, args.shape))

    results = []
    for arch, shape in cells:
        for mk in meshes:
            results.append(run_cell(arch, shape, mk, out_dir=args.out))
    n_ok = sum(r["status"] == "ok" for r in results)
    print(f"\n{n_ok}/{len(results)} cells compiled")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
