"""jit'd wrappers for the frontier Pallas kernels.

Each wrapper stages cap-bounded buffers into the kernels' (N, 1) VMEM
layout, runs the serial kernel (one grid step — the working set is the
block itself, not the graph), and post-processes with cheap cap-sized
XLA ops (the ascending sort of the deduped output, mask/overflow
assembly). Semantics are bit-compatible with kernels/frontier/ref.py —
see that module's contract notes (on a hash-table give-up only the
overflow flag is contractual). The ``"pallas"`` graph-ops backend
dispatches here only with ``impl="serial"`` and only in interpret mode:
the TPU compiler refuses the scalar VMEM stores of these kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.frontier import frontier as K
from repro.kernels.frontier.ref import DedupResult, normalized_cdf

_INT_MAX = jnp.int32(2**31 - 1)


def _pow2_at_least(x: int) -> int:
    p = 8
    while p < x:
        p *= 2
    return p


def _col(x):
    return jnp.reshape(x, (-1, 1))


def _i32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("new_cap", "table_cap", "interpret"))
def _dedup_collect(values, mask, seeds, new_cap: int, table_cap: int,
                   interpret: bool):
    return pl.pallas_call(
        K.dedup_kernel,
        out_shape=(_i32((new_cap, 1)), _i32((1, 1)), _i32((1, 1))),
        scratch_shapes=[pltpu.VMEM((table_cap, 1), jnp.int32)],
        interpret=interpret, name="frontier_dedup_collect",
    )(_col(values.astype(jnp.int32)), _col(mask.astype(jnp.int32)),
      _col(seeds.astype(jnp.int32)))


@functools.partial(jax.jit, static_argnames=("table_cap", "interpret"))
def _dedup_lookup(next_vals, values, mask, table_cap: int, interpret: bool):
    E = values.shape[0]
    return pl.pallas_call(
        K.lookup_kernel,
        out_shape=_i32((E, 1)),
        scratch_shapes=[pltpu.VMEM((table_cap, 1), jnp.int32),
                        pltpu.VMEM((table_cap, 1), jnp.int32)],
        interpret=interpret, name="frontier_dedup_lookup",
    )(_col(next_vals.astype(jnp.int32)), _col(values.astype(jnp.int32)),
      _col(mask.astype(jnp.int32)))


def hash_dedup_block(values: jax.Array, mask: jax.Array,
                     seeds: Optional[jax.Array], new_cap: int,
                     table_cap: Optional[int] = None,
                     interpret: bool = False) -> DedupResult:
    """Linear-probe hash dedup + value→slot lookup: one collection
    kernel, an ascending sort of the cap-sized new set (the order
    contract of ``build_block``), then one lookup kernel over the
    finished ``[seeds ; new]`` buffer.

    ``table_cap`` defaults to a pow2 >= 2x the worst-case occupancy
    (seeds + all-distinct values), so probing provably terminates at an
    empty slot; passing a smaller cap exercises the table-full give-up
    → overflow-flag path (healed by the doubled-caps replay, exactly
    like a too-small vertex buffer).
    """
    E = values.shape[0]
    S = seeds.shape[0] if seeds is not None else 0
    if table_cap is None:
        table_cap = _pow2_at_least(2 * (S + E))
    seeds_in = (jnp.full((1,), -1, jnp.int32) if seeds is None
                else seeds.astype(jnp.int32))
    new_raw, cnt, flag = _dedup_collect(values, mask, seeds_in, new_cap,
                                        table_cap, interpret)
    # insertion order -> the ascending contract (-1 padding last)
    new = jnp.sort(jnp.where(new_raw[:, 0] >= 0, new_raw[:, 0], _INT_MAX))
    new = jnp.where(new == _INT_MAX, -1, new).astype(jnp.int32)
    if seeds is not None:
        next_vals = jnp.concatenate([seeds.astype(jnp.int32), new])
    else:
        next_vals = new
    slots = _dedup_lookup(next_vals, values, mask,
                          _pow2_at_least(2 * next_vals.shape[0]),
                          interpret)[:, 0]
    num_new = cnt[0, 0]
    overflow = (num_new > new_cap) | (flag[0, 0] != 0)
    return DedupResult(new=new, slots=slots, num_new=num_new,
                       overflow=overflow)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def compact_block(flags: jax.Array, cap: int, interpret: bool = False):
    """Serial stream compaction (see ref.compact for the contract)."""
    sel, num = pl.pallas_call(
        K.compact_kernel,
        out_shape=(_i32((cap, 1)), _i32((1, 1))),
        interpret=interpret, name="frontier_compact",
    )(_col(flags.astype(jnp.int32)))
    num = num[0, 0]
    emask = jnp.arange(cap) < jnp.minimum(num, cap)
    return sel[:, 0], emask, num


@functools.partial(jax.jit, static_argnames=("num_keys", "interpret"))
def compact_perm_block(keys: jax.Array, valid: jax.Array, num_keys: int,
                       interpret: bool = False) -> jax.Array:
    """Stable counting-sort permutation (see ref.compact_perm): keys in
    [-1, num_keys) ascend with -1 first, invalid entries last."""
    E = keys.shape[0]
    # shift to a dense non-negative range: -1 -> 0, k -> k + 1,
    # invalid -> num_keys + 1
    eff = jnp.where(valid, jnp.clip(keys, -1, num_keys - 1),
                    num_keys) + 1
    perm = pl.pallas_call(
        K.perm_kernel,
        out_shape=_i32((E, 1)),
        scratch_shapes=[pltpu.VMEM((num_keys + 2, 1), jnp.int32)],
        interpret=interpret, name="frontier_compact_perm",
    )(_col(eff.astype(jnp.int32)))
    return perm[:, 0]


@functools.partial(jax.jit, static_argnames=("num_seeds", "k", "interpret"))
def segment_select_block(keys: jax.Array, slot: jax.Array, mask: jax.Array,
                         take: jax.Array, num_seeds: int, k: int,
                         interpret: bool = False) -> jax.Array:
    """Per-segment smallest-``take`` selection with a static fanout
    bound ``k >= max(take)`` (the insertion-buffer size). Requires the
    segment-contiguous non-decreasing slot layout of
    ``expand_seed_edges`` (see ref.segment_select)."""
    E = keys.shape[0]
    slot_in = jnp.where(mask, slot, -1)
    inc = pl.pallas_call(
        K.select_kernel,
        out_shape=_i32((E, 1)),
        scratch_shapes=[pltpu.VMEM((max(k, 1), 1), jnp.float32),
                        pltpu.VMEM((num_seeds, 1), jnp.float32),
                        pltpu.VMEM((num_seeds, 1), jnp.int32)],
        interpret=interpret, name="frontier_segment_select",
    )(_col(keys.astype(jnp.float32)), _col(slot_in.astype(jnp.int32)),
      _col(take.astype(jnp.int32)))
    return inc[:, 0] != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_cdf_draw_block(p: jax.Array, valid: jax.Array, u: jax.Array,
                          interpret: bool = False) -> jax.Array:
    """Inverse-CDF draws: the normalized CDF is shared with the XLA
    reference (identical floats on a platform); the kernel runs one
    binary search per draw over the VMEM-resident CDF."""
    cdf = normalized_cdf(p, valid)
    out = pl.pallas_call(
        K.search_kernel,
        out_shape=_i32((u.shape[0], 1)),
        interpret=interpret, name="frontier_cdf_search",
    )(_col(cdf.astype(jnp.float32)), _col(u.astype(jnp.float32)))
    return out[:, 0]
