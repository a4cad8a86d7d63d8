"""Pallas TPU kernel: per-destination segment softmax statistics.

The GATv2 attention softmax normalizes each destination's incoming-edge
logits — a segment max + segment sum, i.e. two more TPU-hostile
scatter reductions on the same dst-sorted edge layout as the SpMM. This
kernel computes both in ONE pass over the chunked edge layout
(repro/kernels/spmm/ops.prepare_chunks) with the flash-attention online
rescaling idiom:

  * the running per-row shift ``m`` is the EXACT per-row max: each
    chunk's segment max comes from a masked (BE, H, BS) reduce — laid
    out heads-in-sublanes / rows-in-lanes so the minor dim stays a
     128-lane block — over the real (unpadded) head count, which keeps
    the buffer at BE*H*BS floats (2 MB at 256/8/256). An exact shift
    matters: a merely-valid upper bound (e.g. the chunk-scalar max)
    underflows every row sitting >~88 below it to an all-zero
    denominator in f32 — silent wrong attention, not reduced precision.
  * the denominator accumulates as ``s = s * exp(m_old - m_new)
    + P^T @ exp(logit - P @ m_new)`` — the same one-hot matmul pair as
    the SpMM kernel (P: edges->rows one-hot).

Consecutive chunks of one row block accumulate in VMEM (chunks is the
only grid dim; heads are padded to a single lane block in the layout,
but only real heads pay the 3D reduce). The wrapper in ops.py turns
(m, s) into normalized per-edge coefficients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BE = 256   # edges per chunk
DEFAULT_BS = 256   # destination rows per block
NEG = -1e30        # "minus infinity" that survives subtraction


def _stats_kernel(heads, row_block_ref, first_ref, dst_ref, logit_ref,
                  m_ref, s_ref):
    c = pl.program_id(0)

    @pl.when(first_ref[c] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    dst_local = dst_ref[...]  # (BE, 1) int32, -1 for padding lanes
    be = dst_local.shape[0]
    bs = m_ref.shape[0]
    hp = m_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (be, bs), 1)
    P = (dst_local == cols).astype(jnp.float32)        # (BE, BS) one-hot

    logit = logit_ref[...].astype(jnp.float32)         # (BE, Hp), NEG pad
    # exact per-row segment max of this chunk, real heads only:
    # (BE, H, BS) masked reduce over the edge axis. Padding edges have
    # an all-zero P row and padded heads never enter (sliced off).
    lg3 = jnp.where(jnp.expand_dims(P, 1) > 0,
                    jnp.expand_dims(logit[:, :heads], 2), NEG)
    cmax = jnp.transpose(jnp.max(lg3, axis=0))         # (BS, H)
    if hp > heads:
        cmax = jnp.concatenate(
            [cmax, jnp.full((bs, hp - heads), NEG, jnp.float32)], axis=1)

    m_old = m_ref[...]
    # rows without edges in this chunk have cmax = NEG -> m unchanged
    m_new = jnp.maximum(m_old, cmax)
    # per-edge shift = its row's m_new, fetched with the one-hot matmul;
    # padding edges (all-zero P row) get shift 0 and logit NEG -> exp 0
    shift = jax.lax.dot_general(
        P, m_new, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (BE, Hp)
    ex = jnp.exp(logit - shift)
    contrib = jax.lax.dot_general(
        P, ex, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (BS, Hp)
    # first touch: m_old = NEG -> rescale factor exp(NEG - m_new) = 0,
    # matching the zero-initialized s
    s_ref[...] = s_ref[...] * jnp.exp(m_old - m_new) + contrib
    m_ref[...] = m_new


@functools.partial(
    jax.jit, static_argnames=("num_rows", "heads", "be", "bs", "interpret"))
def edge_softmax_stats(logits: jax.Array, dst: jax.Array, num_rows: int,
                       heads: int, be: int = DEFAULT_BE,
                       bs: int = DEFAULT_BS, interpret: bool = False):
    """Per-row softmax statistics over dst-sorted chunked edges.

    logits (E, Hp) float32 with NEG at padding positions (edges and
    heads — ``heads`` is the real count, the rest is lane padding), dst
    int32[E] (chunk layout, -1 pad). Returns (m, s), each
    (num_rows, Hp): the exact per-row max and the sum of
    exp(logit - m). Requirements as for ``spmm_sorted``: one row block
    per chunk, E % be == 0, num_rows % bs == 0; Hp one lane block; the
    caller sizes (be, bs) so be * heads * bs floats fit VMEM.
    """
    E, Hp = logits.shape
    assert E % be == 0 and num_rows % bs == 0 and 1 <= heads <= Hp
    nchunks = E // be

    first_dst = dst[:: be]
    row_block = jnp.where(first_dst >= 0, first_dst // bs,
                          num_rows // bs - 1).astype(jnp.int32)
    first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (row_block[1:] != row_block[:-1]).astype(jnp.int32),
    ])
    dst_local = jnp.where(dst >= 0, dst % bs, -1).astype(jnp.int32)[:, None]

    m, s = pl.pallas_call(
        functools.partial(_stats_kernel, heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nchunks,),
            in_specs=[
                pl.BlockSpec((be, 1), lambda c, rb, fs: (c, 0)),
                pl.BlockSpec((be, Hp), lambda c, rb, fs: (c, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bs, Hp), lambda c, rb, fs: (rb[c], 0)),
                pl.BlockSpec((bs, Hp), lambda c, rb, fs: (rb[c], 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((num_rows, Hp), jnp.float32),
            jax.ShapeDtypeStruct((num_rows, Hp), jnp.float32),
        ],
        # no name=: a kernel name becomes an op-path segment between
        # jit(edge_softmax_stats) and pallas_call, the path the
        # benchmark's edge_softmax_roofline reads
        interpret=interpret,
    )(row_block, first, dst_local, logits)
    return m, s
