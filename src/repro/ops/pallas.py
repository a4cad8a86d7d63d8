"""The ``"pallas"`` graph-ops backend: one-hot MXU kernels with
``jax.custom_vjp`` backwards built from the SAME kernels.

Forward data motion (repro/kernels/spmm, repro/kernels/edge_softmax):
scatter-accumulate and segment softmax become matmuls against a one-hot
edges->rows selection matrix over dst-sorted, row-block-aligned edge
chunks; gathers stay in XLA (fast on TPU).

Backward structure (the DGL gSpMM/gSDDMM factorization):

  * ``aggregate`` (weighted SpMM)
      - grad wrt ``h`` is the TRANSPOSED SpMM — the same kernel with
        src/dst roles swapped, fed through ``SampledLayer.src_perm``
        (the precomputed permutation putting edges in src-sorted order,
        so the transposed edges satisfy the kernel's dst-sorted
        contract with zero per-step sorting).
      - grad wrt ``weight`` is an SDDMM: per-edge <g[dst], h[src]>,
        dst side via the one-hot gather kernel, src side an XLA gather.
  * ``scatter_edges`` / ``gather_dst`` are exact transposes of each
    other through the shared chunk layout, so each one's backward IS
    the other's forward.
  * ``edge_softmax`` backward is the segment softmax Jacobian
    ``alpha * (g - (sum_seg alpha*g)[dst])`` — one scatter kernel, one
    gather kernel.

Integer/bool block metadata (slots, masks, the permutation) rides
through every ``custom_vjp`` as regular arguments with ``float0``
cotangents. Off-TPU the kernels run in Pallas interpret mode
(``repro.ops.backend.interpret_mode``) — bit-faithful to the kernel
body, which is what the parity suite exercises on CPU CI.
"""
from __future__ import annotations

from functools import partial
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.edge_softmax.ops import edge_softmax_block
from repro.kernels.frontier import ops as frontier_ops
from repro.kernels.frontier import parallel as frontier_par
from repro.kernels.spmm.ops import (gather_dst_block, scatter_sorted_block,
                                    spmm_block)
from repro.ops import autotune
from repro.ops.backend import interpret_mode

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.interface import SampledLayer


def _f0(x):
    """Zero cotangent for an integer/bool primal (what JAX expects)."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# aggregate — weighted SpMM with the transposed-SpMM/SDDMM backward
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _aggregate(h, weight, src_slot, dst_slot, mask, src_perm, num_rows):
    return spmm_block(src_slot, dst_slot, weight, mask, h, num_rows,
                      interpret=interpret_mode())


def _aggregate_fwd(h, weight, src_slot, dst_slot, mask, src_perm, num_rows):
    out = _aggregate(h, weight, src_slot, dst_slot, mask, src_perm, num_rows)
    return out, (h, weight, src_slot, dst_slot, mask, src_perm)


def _aggregate_bwd(num_rows, res, g):
    h, weight, src_slot, dst_slot, mask, perm = res
    interp = interpret_mode()
    # dL/dh: transposed SpMM — permute edges into src-sorted order and
    # swap roles; the permuted "dst" (= src_slot) satisfies the kernel's
    # sorted contract by construction of src_perm
    dh = spmm_block(dst_slot[perm], src_slot[perm], weight[perm],
                    mask[perm], g, h.shape[0], interpret=interp)
    # dL/dweight: SDDMM — per-edge <g[dst], h[src]>; dst side through
    # the one-hot gather kernel, src side an XLA gather
    g_dst = gather_dst_block(dst_slot, mask, g, interpret=interp)
    h_src = h[jnp.where(mask, src_slot, 0)]
    dw = jnp.sum(g_dst * h_src, axis=-1).astype(weight.dtype)
    return (dh.astype(h.dtype), dw, _f0(src_slot), _f0(dst_slot), _f0(mask),
            _f0(perm))


_aggregate.defvjp(_aggregate_fwd, _aggregate_bwd)


def aggregate(blk: SampledLayer, h: jax.Array) -> jax.Array:
    """Weighted SpMM over a sampled block (see repro.ops.ref for the
    semantics): Pallas forward, differentiable end to end."""
    return _aggregate(h, blk.weight, blk.src_slot, blk.dst_slot,
                      blk.edge_mask, blk.src_perm, blk.seed_cap)


# ---------------------------------------------------------------------------
# scatter_edges / gather_dst — mutual transposes through one chunk layout
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _scatter_edges(values, dst_slot, mask, num_rows):
    return scatter_sorted_block(dst_slot, mask, values, num_rows,
                                interpret=interpret_mode())


def _scatter_edges_fwd(values, dst_slot, mask, num_rows):
    return (_scatter_edges(values, dst_slot, mask, num_rows),
            (dst_slot, mask))


def _scatter_edges_bwd(num_rows, res, g):
    dst_slot, mask = res
    dv = gather_dst_block(dst_slot, mask, g, interpret=interpret_mode())
    return dv, _f0(dst_slot), _f0(mask)


_scatter_edges.defvjp(_scatter_edges_fwd, _scatter_edges_bwd)


def scatter_edges(blk: SampledLayer, values: jax.Array) -> jax.Array:
    return _scatter_edges(values, blk.dst_slot, blk.edge_mask, blk.seed_cap)


@jax.custom_vjp
def _gather_dst(rows, dst_slot, mask):
    return gather_dst_block(dst_slot, mask, rows,
                            interpret=interpret_mode())


def _gather_dst_fwd(rows, dst_slot, mask):
    return (_gather_dst(rows, dst_slot, mask),
            (dst_slot, mask, rows.shape[0]))


def _gather_dst_bwd(res, g):
    dst_slot, mask, num_rows = res
    dr = scatter_sorted_block(dst_slot, mask, g, num_rows,
                              interpret=interpret_mode())
    return dr, _f0(dst_slot), _f0(mask)


_gather_dst.defvjp(_gather_dst_fwd, _gather_dst_bwd)


def gather_dst(blk: SampledLayer, rows: jax.Array) -> jax.Array:
    return _gather_dst(rows, blk.dst_slot, blk.edge_mask)


# ---------------------------------------------------------------------------
# edge_softmax — one-pass stats kernel; Jacobian from the two above
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _edge_softmax(logits, dst_slot, mask, num_rows):
    return edge_softmax_block(dst_slot, mask, logits, num_rows,
                              interpret=interpret_mode())


def _edge_softmax_fwd(logits, dst_slot, mask, num_rows):
    alpha = _edge_softmax(logits, dst_slot, mask, num_rows)
    return alpha, (alpha, dst_slot, mask)


def _edge_softmax_bwd(num_rows, res, g):
    alpha, dst_slot, mask = res
    interp = interpret_mode()
    # segment softmax Jacobian: dl_e = alpha_e * (g_e - sum_{seg(e)}
    # alpha g) — the inner segment sum is the scatter kernel, the
    # broadcast back to edges the gather kernel
    inner = scatter_sorted_block(dst_slot, mask, alpha * g, num_rows,
                                 interpret=interp)
    dl = alpha * (g - gather_dst_block(dst_slot, mask, inner,
                                       interpret=interp))
    return dl.astype(alpha.dtype), _f0(dst_slot), _f0(mask)


_edge_softmax.defvjp(_edge_softmax_fwd, _edge_softmax_bwd)


def edge_softmax(blk: SampledLayer, logits: jax.Array) -> jax.Array:
    return _edge_softmax(logits, blk.dst_slot, blk.edge_mask, blk.seed_cap)


# ---------------------------------------------------------------------------
# frontier primitives — integer data motion, so no custom VJPs are needed
# ---------------------------------------------------------------------------

# Each frontier primitive resolves its tuning params (serial vs
# grid-parallel, tile width) through repro.ops.autotune at trace time —
# shapes are static under jit, so the cache lookup never enters the
# traced program and a re-tune only changes which kernel gets traced.
# Both implementations are bit-exact by contract (tests/test_frontier.py)
# so the choice is pure perf. The serial kernels (kernels/frontier/
# frontier.py) hold (N, 1) columns and scalar VMEM stores the TPU
# compiler refuses: they run in interpret mode only.

def _params(primitive: str, **shapes: int) -> dict:
    p = autotune.get_params(primitive, **shapes)
    if p["impl"] == "serial" and not interpret_mode():
        raise ValueError(
            f"frontier primitive {primitive!r}: impl='serial' (from "
            f"{autotune.IMPL_ENV} or the tuning cache) does not compile for "
            f"TPU; use impl='parallel'")
    return p


def _tile(p: dict) -> int:
    return int(p.get("tile", frontier_par.DEFAULT_TILE))


def hash_dedup(values: jax.Array, mask: jax.Array,
               seeds: Optional[jax.Array], new_cap: int):
    p = _params("hash_dedup", E=values.shape[0],
                S=0 if seeds is None else seeds.shape[0])
    if p["impl"] == "serial":
        s = 0 if seeds is None else seeds.shape[0]
        load = float(p.get("table_load", 2.0))
        cap = max(8, 1 << (int(load * (s + values.shape[0])) - 1)
                  .bit_length())
        return frontier_ops.hash_dedup_block(values, mask, seeds, new_cap,
                                             table_cap=cap,
                                             interpret=interpret_mode())
    return frontier_par.hash_dedup_block_parallel(
        values, mask, seeds, new_cap, tile=_tile(p),
        interpret=interpret_mode())


def compact(flags: jax.Array, cap: int):
    p = _params("compact", E=flags.shape[0])
    if p["impl"] == "serial":
        return frontier_ops.compact_block(flags, cap,
                                          interpret=interpret_mode())
    return frontier_par.compact_block_parallel(
        flags, cap, tile=_tile(p), interpret=interpret_mode())


def compact_perm(keys: jax.Array, valid: jax.Array,
                 num_keys: int) -> jax.Array:
    p = _params("compact_perm", E=keys.shape[0], S=num_keys)
    if p["impl"] == "serial":
        return frontier_ops.compact_perm_block(keys, valid, num_keys,
                                               interpret=interpret_mode())
    return frontier_par.compact_perm_block_parallel(
        keys, valid, num_keys, tile=_tile(p), interpret=interpret_mode())


def segment_select(keys: jax.Array, slot: jax.Array, mask: jax.Array,
                   seg_start: jax.Array, take: jax.Array, num_seeds: int,
                   max_take: int) -> jax.Array:
    p = _params("segment_select", E=keys.shape[0], S=num_seeds)
    if p["impl"] == "serial":
        # the serial kernel re-derives segment bounds from its scan and
        # never reads seg_start; the parallel sort/select needs it
        return frontier_ops.segment_select_block(keys, slot, mask, take,
                                                 num_seeds, max_take,
                                                 interpret=interpret_mode())
    return frontier_par.segment_select_block_parallel(
        keys, slot, mask, seg_start, take, num_seeds, tile=_tile(p),
        interpret=interpret_mode())


def masked_cdf_draw(p: jax.Array, valid: jax.Array,
                    u: jax.Array) -> jax.Array:
    params = _params("masked_cdf_draw", E=p.shape[0], S=u.shape[0])
    if params["impl"] == "serial":
        return frontier_ops.masked_cdf_draw_block(p, valid, u,
                                                  interpret=interpret_mode())
    return frontier_par.masked_cdf_draw_block_parallel(
        p, valid, u, tile=_tile(params), interpret=interpret_mode())
