"""CSR/CSC graph containers used across the framework.

Everything is stored as device (jnp) arrays so that samplers and models
can run fully jitted / shard_mapped. The convention follows the paper:
we sample *incoming* edges of seed (destination) vertices, so the primary
structure is a CSC-like "in-neighborhood CSR": for a destination vertex
``s``, ``indices[indptr[s]:indptr[s+1]]`` lists source vertices ``t`` with
an edge ``t -> s``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.frontier.parallel import fill_forward
from repro.runtime import spans


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """In-neighborhood CSR graph (paper notation: N(s) = {t | t->s}).

    Attributes:
      indptr:  int32[num_vertices + 1]
      indices: int32[num_edges]  (source vertex of each in-edge)
      weights: optional float32[num_edges] edge weights A_ts (paper §A.7);
               ``None`` means uniform weights (A_ts = 1).
    """

    indptr: jax.Array
    indices: jax.Array
    weights: Optional[jax.Array] = None

    @property
    def num_vertices(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[0]

    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def in_degree(self, v: jax.Array) -> jax.Array:
        v = jnp.asarray(v)
        return self.indptr[v + 1] - self.indptr[v]

    def validate(self) -> None:
        """Host-side structural validation (not jittable)."""
        indptr = np.asarray(self.indptr)
        indices = np.asarray(self.indices)
        if indptr[0] != 0 or indptr[-1] != indices.shape[0]:
            raise ValueError("indptr does not cover indices")
        if np.any(np.diff(indptr) < 0):
            raise ValueError("indptr must be non-decreasing")
        if indices.size and (indices.min() < 0 or indices.max() >= self.num_vertices):
            raise ValueError("indices out of range")
        if self.weights is not None and self.weights.shape != self.indices.shape:
            raise ValueError("weights shape mismatch")


def from_coo(
    src: np.ndarray,
    dst: np.ndarray,
    num_vertices: int,
    weights: Optional[np.ndarray] = None,
    dedup: bool = True,
) -> Graph:
    """Build an in-neighborhood CSR ``Graph`` from a COO edge list (host)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if dedup:
        key = dst * num_vertices + src
        if weights is None:
            key = np.unique(key)
            dst, src = key // num_vertices, key % num_vertices
        else:
            key, idx = np.unique(key, return_index=True)
            dst, src = key // num_vertices, key % num_vertices
            weights = np.asarray(weights)[idx]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    if weights is not None:
        weights = np.asarray(weights)[order]
    counts = np.bincount(dst, minlength=num_vertices)
    indptr = np.zeros(num_vertices + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    g = Graph(
        indptr=jnp.asarray(indptr, dtype=jnp.int32),
        indices=jnp.asarray(src, dtype=jnp.int32),
        weights=None if weights is None else jnp.asarray(weights, dtype=jnp.float32),
    )
    return g


def reverse(graph: Graph) -> Graph:
    """Reverse edge directions (host-side), preserving edge weights."""
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    n = graph.num_vertices
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    weights = None if graph.weights is None else np.asarray(graph.weights)
    return from_coo(dst, indices.astype(np.int64), n, weights=weights,
                    dedup=False)


@partial(jax.jit, static_argnames=("edge_cap",))
def expand_seed_edges(graph: Graph, seeds: jax.Array, edge_cap: int,
                      seed_rows: Optional[jax.Array] = None):
    """Edge-centric CSR expansion with a static edge budget.

    Given padded ``seeds`` (int32[S], padding = -1), produce flat edge
    buffers of length ``edge_cap`` describing every in-edge of every valid
    seed, laid out segment-contiguously (all edges of seed 0, then seed 1,
    ...).

    ``seed_rows`` optionally maps each seed to its CSR row (default: the
    seed id itself). The distributed engine passes local row ids
    (``v // num_parts``) here so sampling runs against a partition-local
    CSR while seeds — and the ``src`` ids the partitioned CSR stores —
    stay in global-id space.

    Returns a dict with (all int32[edge_cap] unless noted):
      seed_slot: index into ``seeds`` for each edge (edge's destination)
      src:       source vertex id ``t`` of each edge
      mask:      bool[edge_cap], True for real edges
      seg_start: int32[S] start offset of each seed's segment
      deg:       int32[S] degree of each seed (0 for padding)
      total:     int32[] total real edges (may exceed edge_cap => overflow)

    Edges beyond ``edge_cap`` are dropped: on overflow the buffers hold
    the exact first ``edge_cap`` edges of the layout. Callers must check
    ``total <= edge_cap`` (the data pipeline sizes caps so overflow is
    rare and re-tries with a bigger bucket when it happens).
    """
    with jax.named_scope(spans.EXPAND_SEED_EDGES):
        return _expand(graph, seeds, edge_cap, seed_rows)


def _expand(graph: Graph, seeds: jax.Array, edge_cap: int,
            seed_rows: Optional[jax.Array]):
    S = seeds.shape[0]
    valid = seeds >= 0
    safe_seeds = jnp.where(valid, seeds if seed_rows is None else seed_rows, 0)
    row_start = graph.indptr[safe_seeds]
    deg = jnp.where(valid, graph.indptr[safe_seeds + 1] - row_start, 0)
    seg_start = jnp.cumsum(deg) - deg  # exclusive prefix sum
    total = jnp.sum(deg)

    # the fill carries each segment head's slot and row offset over its segment
    at = jnp.where(deg > 0, seg_start, edge_cap)
    slot_at = jnp.full((edge_cap,), -1, jnp.int32).at[at].set(
        jnp.arange(S, dtype=jnp.int32), mode="drop")
    delta_at = jnp.zeros((edge_cap,), jnp.int32).at[at].set(
        row_start - seg_start, mode="drop")
    seed_slot, delta = fill_forward(slot_at >= 0, (slot_at, delta_at))

    pos = jnp.arange(edge_cap, dtype=jnp.int32)
    mask = pos < jnp.minimum(total, edge_cap)
    edge = jnp.where(mask, pos + delta, 0)
    src = jnp.where(mask, graph.indices[edge], -1)
    seed_slot = jnp.where(mask, seed_slot, -1)
    ew = None
    if graph.weights is not None:
        ew = jnp.where(mask, graph.weights[edge], 0.0)
    return dict(
        seed_slot=seed_slot,
        src=src,
        mask=mask,
        seg_start=seg_start,
        deg=deg,
        total=total,
        edge_weight=ew,
    )
