"""LABOR-0 (paper section 3.2 with uniform pi): seed ``s`` takes source
``t`` iff the shared variate r_t is below c_s = min(1, k / d_s), so
seeds that share a neighbour tend to take it together."""
import numpy as np

from bench.reference.sampling import fanout_rate, vertex_uniform


def include(salt, k, seeds, exp):
    c = fanout_rate(k, exp.deg)[exp.seg]
    r = vertex_uniform(salt, exp.src)
    inv_p = np.float32(1.0) / np.maximum(np.minimum(c, np.float32(1.0)),
                                         np.float32(1e-20))
    return r < c, inv_p
