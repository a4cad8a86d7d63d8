"""GCN (paper eq. 2) with a residual projection on every layer (paper
section 4): h'_s = ReLU((sum_t A'_ts h_t) @ W + b + h_s @ W_r), with
A'_ts the sampler's Hajek weights and no ReLU on the output layer."""
import math

import jax
import jax.numpy as jnp


def _glorot(key, d_in, d_out):
    lim = math.sqrt(6.0 / (d_in + d_out))
    return jax.random.uniform(key, (d_in, d_out), jnp.float32, -lim, lim)


def init(key, config, in_dim, n_cls):
    """Weights in the layout the trainer's GCN reads."""
    L, hid = config["num_layers"], config["hidden"]
    dims = [in_dim] + [hid] * (L - 1) + [n_cls]
    keys = jax.random.split(key, 2 * L)
    return {"layers": [
        {"w": _glorot(keys[2 * l], dims[l], dims[l + 1]),
         "b": jnp.zeros((dims[l + 1],), jnp.float32),
         "wr": _glorot(keys[2 * l + 1], dims[l], dims[l + 1])}
        for l in range(L)]}


def layer(p, blk, h, is_last, ops):
    """h over the block's next list in, h over its seeds out; ``ops``
    holds the matrix products (``bench.reference.train.products``)."""
    S = blk["seed_mask"].shape[0]
    msg = h[blk["src"]] * blk["w"][:, None].astype(h.dtype)
    agg = jax.ops.segment_sum(msg, blk["dst"], num_segments=S + 1)[:S]
    out = ops.mm(agg, p["w"]) + p["b"] + ops.mm(h[:S], p["wr"])
    return out if is_last else jax.nn.relu(out)
