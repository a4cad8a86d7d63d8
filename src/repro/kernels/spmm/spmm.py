"""Pallas TPU kernel: weighted segment-sum SpMM (GNN block aggregation).

TPU adaptation of the paper's CUDA scatter-aggregate hot spot: TPUs have
no fast scatter, so per edge-chunk we build a (BE x BS) one-hot selection
matrix from local destination ids and turn scatter-accumulate into an
MXU matmul:  out[rows] += P^T @ M  (P: edges->rows one-hot, M: gathered
weighted messages). Edges arrive sorted by destination (the samplers
emit segment-contiguous blocks), so ops.py re-buckets them into chunks
that each touch exactly ONE destination row-block; chunk->row-block ids
and first-visit flags come in via scalar prefetch, and consecutive
chunks hitting the same output block accumulate in VMEM.

Grid: (feature_blocks, chunks) — chunks fastest-varying so output-block
revisits are consecutive (Pallas TPU accumulation idiom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BE = 256   # edges per chunk
DEFAULT_BS = 256   # destination rows per block
DEFAULT_BF = 128   # feature columns per block


def _spmm_kernel(row_block_ref, first_ref, dst_ref, msg_ref, out_ref):
    c = pl.program_id(1)

    @pl.when(first_ref[c] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dst_local = dst_ref[...]  # (BE, 1) int32, -1 for padding lanes
    be = dst_local.shape[0]
    bs = out_ref.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (be, bs), 1)
    P = (dst_local == cols).astype(msg_ref.dtype)      # (BE, BS) one-hot
    acc = jax.lax.dot_general(
        P, msg_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),    # P^T @ M -> (BS, BF)
        preferred_element_type=jnp.float32,
    )
    out_ref[...] += acc.astype(out_ref.dtype)


def _gather_kernel(row_block_ref, dst_ref, rows_ref, out_ref):
    """Per-edge dst-row gather as a one-hot MXU matmul: out[e] =
    rows[dst_local_e]. The inverse data motion of ``_spmm_kernel`` —
    the chunk's (BS, BF) row block sits in VMEM and is reused by every
    edge of the chunk, so the random-access gather becomes P @ R."""
    del row_block_ref
    dst_local = dst_ref[...]  # (BE, 1) int32, -1 for padding lanes
    be = dst_local.shape[0]
    bs = rows_ref.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (be, bs), 1)
    P = (dst_local == cols).astype(rows_ref.dtype)     # (BE, BS) one-hot
    out_ref[...] = jax.lax.dot_general(
        P, rows_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),    # P @ R -> (BE, BF)
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("be", "bs", "bf", "interpret"))
def gather_rows_sorted(rows: jax.Array, dst: jax.Array,
                       be: int = DEFAULT_BE, bs: int = DEFAULT_BS,
                       bf: int = DEFAULT_BF, interpret: bool = False) -> jax.Array:
    """out[e] = rows[dst[e]] (0 where dst[e] == -1), for the chunked
    edge layout of :func:`spmm_sorted` (dst sorted ascending, -1 pad,
    one row-block per chunk, E % be == 0, F % bf == 0)."""
    E = dst.shape[0]
    S, F = rows.shape
    assert E % be == 0 and F % bf == 0 and S % bs == 0
    nchunks = E // be

    first_dst = dst[:: be]
    row_block = jnp.where(first_dst >= 0, first_dst // bs, 0).astype(jnp.int32)
    dst_local = jnp.where(dst >= 0, dst % bs, -1).astype(jnp.int32)[:, None]

    grid = (F // bf, nchunks)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((be, 1), lambda f, c, rb: (c, 0)),
                pl.BlockSpec((bs, bf), lambda f, c, rb: (rb[c], f)),
            ],
            out_specs=pl.BlockSpec((be, bf), lambda f, c, rb: (c, f)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, F), rows.dtype),
        interpret=interpret, name="gather_rows_sorted",
    )(row_block, dst_local, rows)
    return out


@functools.partial(
    jax.jit, static_argnames=("num_rows", "be", "bs", "bf", "interpret"))
def spmm_sorted(messages: jax.Array, dst: jax.Array, num_rows: int,
                be: int = DEFAULT_BE, bs: int = DEFAULT_BS,
                bf: int = DEFAULT_BF, interpret: bool = False) -> jax.Array:
    """out[r] = sum_{e: dst[e]==r} messages[e].

    Requirements (enforced by ops.prepare_chunks): dst sorted ascending,
    padding = -1, edges of one row-block never straddle a chunk, E % be
    == 0, F % bf == 0, num_rows % bs == 0.
    """
    E, F = messages.shape
    assert E % be == 0 and F % bf == 0 and num_rows % bs == 0
    nchunks = E // be

    # per-chunk row block + first-visit flag (host-of-device: cheap jnp)
    first_dst = dst[:: be]                              # (nchunks,)
    row_block = jnp.where(first_dst >= 0, first_dst // bs, num_rows // bs - 1)
    row_block = row_block.astype(jnp.int32)
    first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (row_block[1:] != row_block[:-1]).astype(jnp.int32),
    ])
    dst_local = jnp.where(dst >= 0, dst % bs, -1).astype(jnp.int32)[:, None]

    grid = (F // bf, nchunks)
    out = pl.pallas_call(
        _spmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((be, 1), lambda f, c, rb, fs: (c, 0)),
                pl.BlockSpec((be, bf), lambda f, c, rb, fs: (c, f)),
            ],
            out_specs=pl.BlockSpec((bs, bf), lambda f, c, rb, fs: (rb[c], f)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_rows, F), messages.dtype),
        # no name=: a kernel name becomes an op-path segment between
        # jit(spmm_sorted) and pallas_call, the path the benchmark's
        # spmm_roofline reads
        interpret=interpret,
    )(row_block, first, dst_local, messages)
    return out
