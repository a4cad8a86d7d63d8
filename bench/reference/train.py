"""Plain reference of the first training steps of a cell.

From the same graph, weights, batches and PRNG keys that the program is
given, and from nothing the program made: the reference samples each
batch with :mod:`bench.reference.sampling`, runs the configuration's
model (``models/<model>.py``) over the sampled blocks in plain
``jax.numpy``, takes the masked mean negative log-likelihood of the
batch's labels, and applies Adam with global-norm clipping as the
configuration states it.

Every matrix product runs at the highest precision: the reference.
With ``control=True`` the operands of every matrix product are first
rounded to float8 (e4m3, scaled per tensor so that its largest entry
maps to the format's largest), the gradients flowing back unrounded:
the control, one step below the bfloat16 products that XLA's default
precision gives float32 matmuls on the TPU, and the step a later change
might be tempted to take.
"""
from __future__ import annotations

import importlib.util
import os
from types import SimpleNamespace
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import sampling

_MODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "models")


def load_model(name: str):
    path = os.path.join(_MODELS, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no reference model {name!r} under {_MODELS}")
    spec = importlib.util.spec_from_file_location(f"ref_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bucket(n: int) -> int:
    """Power-of-two padding, so that batches of one cell share programs."""
    return max(8, 1 << (max(n, 1) - 1).bit_length())


def pad_blocks(blocks: Sequence[sampling.Block]):
    """Device arrays of the blocks at power-of-two sizes. Padding edges
    point at an extra destination row that is dropped; padding rows of
    a vertex list are never the source of a real edge."""
    out = []
    for b in blocks:
        S, E = _bucket(b.seeds.shape[0]), _bucket(b.dst.shape[0])
        e = b.dst.shape[0]
        dst = np.full(E, S, np.int32)
        dst[:e] = b.dst
        src = np.zeros(E, np.int32)
        src[:e] = b.src
        w = np.zeros(E, np.float32)
        w[:e] = b.weight
        mask = np.zeros(E, bool)
        mask[:e] = True
        seed_mask = np.zeros(S, bool)
        seed_mask[:b.seeds.shape[0]] = True
        out.append({"dst": jnp.asarray(dst), "src": jnp.asarray(src),
                    "w": jnp.asarray(w), "mask": jnp.asarray(mask),
                    "seed_mask": jnp.asarray(seed_mask)})
    return tuple(out)


def input_rows(features, ids: np.ndarray):
    """Features of the deepest vertex list, zero rows as padding."""
    n = _bucket(ids.shape[0])
    idx = np.zeros(n, np.int32)
    idx[:ids.shape[0]] = ids
    rows = jnp.take(features, jnp.asarray(idx), axis=0)
    return jnp.where(jnp.arange(n)[:, None] < ids.shape[0], rows, 0)


_FP8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def fp8_round(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale; the
    gradient passes through as if unrounded."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    r = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(r - x)


def products(control: bool = False) -> SimpleNamespace:
    """The matrix products a model layer uses: ``mm(a, b)`` and
    ``einsum(spec, a, b)``, at the highest precision, on float8-rounded
    operands for the control."""
    hi = jax.lax.Precision.HIGHEST
    q = fp8_round if control else (lambda x: x)

    return SimpleNamespace(
        mm=lambda a, b: jnp.matmul(q(a), q(b), precision=hi),
        einsum=lambda spec, a, b: jnp.einsum(spec, q(a), q(b), precision=hi))


def make_loss(model, control: bool = False):
    ops = products(control)

    def loss_fn(params, blocks, feats, labels, valid):
        h = feats
        L = len(blocks)
        for l, blk in enumerate(reversed(blocks)):
            h = model.layer(params["layers"][l], blk, h, l == L - 1, ops)
        logits = h[:labels.shape[0]]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        nll = jnp.where(valid, lse - gold, 0)
        return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)

    return jax.jit(jax.value_and_grad(loss_fn))


def adam_step(params, grads, mu, nu, step: int, opt: Dict):
    """One Adam step with global-norm clipping, in float32."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    if opt.get("grad_clip") is not None:
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * (m / bc1) / (jnp.sqrt(v / bc2)
                                                     + opt["eps"]),
        params, mu, nu)
    return params, grads, mu, nu


def run(config: Dict, traffic: Dict, host_graph, features, params0,
        batches: List[np.ndarray], keys: List, control: bool = False,
        keep: float = 1.0) -> Dict:
    """The reference's readings over ``len(batches)`` steps: each step's
    loss, the clipped first gradient, the change of the weights over all
    steps, and the sampled counts. ``keep`` < 1 trains on that leading
    share of each batch alone (a planted fault)."""
    model = load_model(config["model"])
    sampler = sampling.load_sampler(traffic["sampler"])
    loss_grad = make_loss(model, control)
    params = jax.tree.map(jnp.asarray, params0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, counts, first_grad = [], [], None
    for i, (batch, key) in enumerate(zip(batches, keys)):
        blocks = sampling.sample(sampler, host_graph.indptr,
                                 host_graph.indices, batch, key,
                                 traffic["fanouts"])
        counts.append((int(blocks[-1].next.shape[0]),
                       int(sum(b.dst.shape[0] for b in blocks))))
        pb = pad_blocks(blocks)
        feats = input_rows(features, blocks[-1].next)
        B = pb[0]["seed_mask"].shape[0]
        labels = np.zeros(B, np.int32)
        labels[:batch.shape[0]] = host_graph.labels[batch]
        valid = np.arange(B) < int(round(keep * batch.shape[0]))
        loss, grads = loss_grad(params, pb, feats, jnp.asarray(labels),
                                jnp.asarray(valid))
        params, clipped, mu, nu = adam_step(params, grads, mu, nu, i + 1,
                                            config["optimizer"])
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.tree.map(np.asarray, clipped)
    delta = jax.tree.map(lambda p, p0: np.asarray(p) - np.asarray(p0),
                         params, params0)
    return {"losses": losses, "first_grad": first_grad, "delta": delta,
            "counts": counts}
