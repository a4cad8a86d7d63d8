"""GATv2 (Brody et al. 2022; paper appendix A.6): per edge t -> s the
score a_h . LeakyReLU_0.2(W_s h_s + W_t h_t) per head, softmax over the
in-edges of s, messages W_t h_t weighted by it and summed, plus a bias;
ELU between layers. The sampler's edge weights are not used."""
import math

import jax
import jax.numpy as jnp


def _glorot(key, d_in, d_out):
    lim = math.sqrt(6.0 / (d_in + d_out))
    return jax.random.uniform(key, (d_in, d_out), jnp.float32, -lim, lim)


def init(key, config, in_dim, n_cls):
    """Weights in the layout the trainer's GATv2 reads."""
    L, hid, heads = config["num_layers"], config["hidden"], config["heads"]
    layers, d_in = [], in_dim
    for l in range(L):
        last = l == L - 1
        h = config["last_layer_heads"] if last else heads
        per = n_cls if last else hid // heads
        ks = jax.random.split(jax.random.fold_in(key, l), 3)
        layers.append({
            "ws": _glorot(ks[0], d_in, h * per),
            "wt": _glorot(ks[1], d_in, h * per),
            "attn": 0.1 * jax.random.normal(ks[2], (h, per), jnp.float32),
            "b": jnp.zeros((h * per,), jnp.float32)})
        d_in = h * per
    return {"layers": layers}


def layer(p, blk, h, is_last, ops):
    """h over the block's next list in, h over its seeds out; ``ops``
    holds the matrix products (``bench.reference.train.products``)."""
    S = blk["seed_mask"].shape[0]
    H, P = p["attn"].shape
    dst, src = blk["dst"], blk["src"]
    hs = ops.mm(h[:S], p["ws"])
    ht = ops.mm(h, p["wt"])
    e = jax.nn.leaky_relu((hs[jnp.minimum(dst, S - 1)] + ht[src])
                          .reshape(-1, H, P), 0.2)
    logit = ops.einsum("ehp,hp->eh", e, p["attn"])
    logit = jnp.where(blk["mask"][:, None], logit, 0)
    peak = jax.ops.segment_max(logit, dst, num_segments=S + 1)
    ex = jnp.exp(logit - peak[dst]) * blk["mask"][:, None].astype(logit.dtype)
    den = jax.ops.segment_sum(ex, dst, num_segments=S + 1)
    alpha = ex / jnp.where(den > 0, den, 1)[dst]
    msg = (ht[src].reshape(-1, H, P) * alpha[..., None]).reshape(-1, H * P)
    out = jax.ops.segment_sum(msg, dst, num_segments=S + 1)[:S]
    out = out + p["b"]
    return out if is_last else jax.nn.elu(out)
