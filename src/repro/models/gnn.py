"""GNN models over static-shape sampled blocks (paper §4 experimental
setup: 3-layer GCN, hidden 256, residual skip connections; plus GraphSAGE
and the GATv2 of §A.6).

A model consumes ``blocks`` as produced by the samplers (outermost layer
first) and the input features of the deepest layer's ``next_seeds``; each
layer aggregates messages src->dst with the sampler's Hajek weights A'
(so the aggregation IS the paper's estimator H''_s, eq. 6) and applies a
dense update. ALL graph compute — the weighted SpMM and, for GATv2, the
per-edge scores and attention softmax — goes through the ``repro.ops``
primitives, so one ``backend`` argument ("xla" | "pallas", resolved from
"auto" by the engine) switches every model between the XLA reference
ops and the Pallas MXU kernels, forward and backward alike.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import ops as O
from repro.core.interface import SampledLayer


def _dense_init(key, d_in, d_out):
    lim = math.sqrt(6.0 / (d_in + d_out))
    return jax.random.uniform(key, (d_in, d_out), minval=-lim, maxval=lim)


# ---------------------------------------------------------------------------
# GCN (paper eq. 2) with residual skip connections
# ---------------------------------------------------------------------------

def gcn_init(key, in_dim: int, hidden: int, out_dim: int, num_layers: int = 3):
    dims = [in_dim] + [hidden] * (num_layers - 1) + [out_dim]
    keys = jax.random.split(key, num_layers * 2)
    layers = []
    for l in range(num_layers):
        layers.append({
            "w": _dense_init(keys[2 * l], dims[l], dims[l + 1]),
            "b": jnp.zeros((dims[l + 1],)),
            # residual projection (identity-shaped layers could skip it, but
            # the paper's dims change at first/last layer so project always)
            "wr": _dense_init(keys[2 * l + 1], dims[l], dims[l + 1]),
        })
    return {"layers": layers}


def gcn_layer(p, blk: SampledLayer, h: jax.Array, *, is_last: bool,
              backend: Optional[str] = None) -> jax.Array:
    """One GCN layer over one sampled block: h over ``blk.next_seeds``
    in, h over ``blk.seeds`` out. The per-layer granularity is what the
    distributed engine interleaves with cross-partition hidden-state
    exchanges; the whole-batch ``gcn_apply`` chains the same function.

    The aggregation runs at the narrower of the two widths, as DGL's
    GraphConv does: (A h) W = A (h W), so a layer that narrows projects
    first. At 602 input features, aggregating first would write the
    block's edges x 602 message tensors (26.9 GB for a Reddit-sized
    graph's deepest block at batch 1024)."""
    if h.shape[-1] > p["w"].shape[-1]:
        z = O.aggregate(blk, h @ p["w"], backend=backend) + p["b"]
    else:
        z = O.aggregate(blk, h, backend=backend) @ p["w"] + p["b"]
    res = h[: blk.seed_cap] @ p["wr"]                          # seeds prefix
    h = z + res
    return h if is_last else jax.nn.relu(h)


def gcn_apply(params, blks: Sequence[SampledLayer], feats: jax.Array,
              backend: Optional[str] = None) -> jax.Array:
    """feats: features of blocks[-1].next_seeds. Returns logits for
    blocks[0].seeds."""
    h = feats
    n_layers = len(params["layers"])
    assert n_layers == len(blks)
    for l, blk in enumerate(reversed(blks)):
        h = gcn_layer(params["layers"][l], blk, h,
                      is_last=l == n_layers - 1, backend=backend)
    return h


# ---------------------------------------------------------------------------
# GraphSAGE (mean aggregator + self concat)
# ---------------------------------------------------------------------------

def sage_init(key, in_dim: int, hidden: int, out_dim: int, num_layers: int = 3):
    dims = [in_dim] + [hidden] * (num_layers - 1) + [out_dim]
    keys = jax.random.split(key, num_layers)
    layers = []
    for l in range(num_layers):
        layers.append({
            "w": _dense_init(keys[l], 2 * dims[l], dims[l + 1]),
            "b": jnp.zeros((dims[l + 1],)),
        })
    return {"layers": layers}


def sage_layer(p, blk: SampledLayer, h: jax.Array, *, is_last: bool,
               backend: Optional[str] = None) -> jax.Array:
    agg = O.aggregate(blk, h, backend=backend)
    self_h = h[: blk.seed_cap]
    z = jnp.concatenate([self_h, agg], axis=-1) @ p["w"] + p["b"]
    return z if is_last else jax.nn.relu(z)


def sage_apply(params, blks: Sequence[SampledLayer], feats: jax.Array,
               backend: Optional[str] = None) -> jax.Array:
    h = feats
    n_layers = len(params["layers"])
    for l, blk in enumerate(reversed(blks)):
        h = sage_layer(params["layers"][l], blk, h,
                       is_last=l == n_layers - 1, backend=backend)
    return h


# ---------------------------------------------------------------------------
# GATv2 (Brody et al. 2022), multi-head, over sampled blocks  (paper §A.6)
# ---------------------------------------------------------------------------

def gatv2_init(key, in_dim: int, hidden: int, out_dim: int,
               num_layers: int = 3, heads: int = 8):
    layers = []
    d_in = in_dim
    for l in range(num_layers):
        last = l == num_layers - 1
        heads_l = 1 if last else heads           # exact out_dim on last layer
        per_head = out_dim if last else max(hidden // heads, 1)
        ks = jax.random.split(jax.random.fold_in(key, l), 4)
        layers.append({
            "ws": _dense_init(ks[0], d_in, heads_l * per_head),   # dst transform
            "wt": _dense_init(ks[1], d_in, heads_l * per_head),   # src transform
            "attn": jax.random.normal(ks[2], (heads_l, per_head)) * 0.1,
            "b": jnp.zeros((heads_l * per_head,)),
        })
        d_in = heads_l * per_head
    return {"layers": layers}


def gatv2_layer(p, blk: SampledLayer, h: jax.Array, *, is_last: bool,
                backend: Optional[str] = None) -> jax.Array:
    """GATv2 attention expressed entirely in the graph-ops primitives:
    per-edge scores via ``sddmm(add)``, normalization via
    ``edge_softmax``, message aggregation via ``scatter_edges`` — so the
    attention path runs (and differentiates) through the same backend
    kernels as gcn/sage instead of special-casing."""
    H, Ph = p["attn"].shape                # head structure from the params
    S = blk.seed_cap
    hs = h[:S] @ p["ws"]                                         # (S, H*Ph)
    ht = h @ p["wt"]                                             # (T, H*Ph)
    e = O.sddmm(blk, hs, ht, op="add", backend=backend)          # (E, H*Ph)
    e = jax.nn.leaky_relu(e.reshape(-1, H, Ph), 0.2)
    logit = jnp.einsum("ehp,hp->eh", e, p["attn"])               # (E, H)
    alpha = O.edge_softmax(blk, logit, backend=backend)          # (E, H)
    msg = O.gather_src(blk, ht).reshape(-1, H, Ph) * alpha[..., None]
    out = O.scatter_edges(blk, msg.reshape(-1, H * Ph), backend=backend)
    out = out + p["b"]
    return out if is_last else jax.nn.elu(out)


def gatv2_apply(params, blks: Sequence[SampledLayer], feats: jax.Array,
                backend: Optional[str] = None) -> jax.Array:
    h = feats
    n_layers = len(params["layers"])
    for l, blk in enumerate(reversed(blks)):
        h = gatv2_layer(params["layers"][l], blk, h,
                        is_last=l == n_layers - 1, backend=backend)
    return h


MODELS = {
    "gcn": (gcn_init, gcn_apply),
    "sage": (sage_init, sage_apply),
    "gatv2": (gatv2_init, gatv2_apply),
}

# per-layer view of each model's apply, keyed by the apply fn itself —
# the distributed engine interleaves these with hidden-state exchanges
# (h crosses partitions between layers, so the whole-batch apply cannot
# run as one local call there)
LAYER_FNS = {
    gcn_apply: gcn_layer,
    sage_apply: sage_layer,
    gatv2_apply: gatv2_layer,
}
