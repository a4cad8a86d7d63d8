"""Neighbour sampling as sequential Poisson sampling (paper appendix
A.3): per seed, the min(k, d_s) in-edges with the smallest
r_ts / c_s, ties broken by the edge's position in the seed's row."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.sampling import (edge_uniform, elementwise_on_device,
                                      fanout_rate)


@jax.jit
def _ratio(r, c):
    return r / jnp.maximum(c, 1e-20)


def include(salt, k, seeds, exp):
    c = fanout_rate(k, exp.deg)[exp.seg]
    r = edge_uniform(salt, exp.src, seeds[exp.seg])
    ratio = elementwise_on_device(_ratio, r, c)
    ratio = np.minimum(ratio, np.float32(1e30))
    order = np.lexsort((exp.pos, ratio, exp.seg))
    first = np.cumsum(exp.deg) - exp.deg
    rank = np.empty_like(order)
    rank[order] = np.arange(order.shape[0]) - first[exp.seg[order]]
    take = np.minimum(k, exp.deg)[exp.seg]
    inv_p = np.float32(1.0) / np.maximum(np.minimum(c, np.float32(1.0)),
                                         np.float32(1e-20))
    return rank < take, inv_p
