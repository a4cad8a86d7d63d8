"""Frontier-primitive dispatch — the sampling half of the graph-ops
backend registry.

PR 4 put the model's hot path (SpMM, edge-softmax) behind the backend
registry; this module does the same for the sampling hot path. Each
function dispatches to the registered backend namespace (``"xla"``
reference scans/sorts over cap-sized buffers, ``"pallas"`` serial VMEM
kernels; ``"auto"``/None picks by platform exactly like the model
primitives). The shared contract — and the point of the family — is
O(cap) cost and memory: nothing here allocates or touches a buffer
sized by the graph's vertex count. Each primitive runs under a
``jax.named_scope`` of its own name (``repro.runtime.spans``), so its
device ops carry the same op-path segment whatever backend or private
wrapper serves it.

Import-graph note: the samplers (``repro.core``) import this module at
module scope, which runs the ops package __init__ and registers the
built-in backends. That is cycle-free because no ops module imports
``repro.core`` at module scope anymore (SampledLayer appears only
under TYPE_CHECKING) — this module itself depends only on
``repro.ops.backend`` and the name constants of ``repro.runtime.spans``.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.ops.backend import get_backend
from repro.runtime import spans


def hash_dedup(values: jax.Array, mask: jax.Array,
               seeds: Optional[jax.Array], new_cap: int, *,
               backend: Optional[str] = None):
    """Unique new values (ascending, -1 pad) among masked ``values``
    not present in ``seeds`` (None: plain dedup), plus the value→slot
    lookup into ``[seeds ; new]``. Returns a
    :class:`repro.kernels.frontier.ref.DedupResult`; ``overflow`` feeds
    the doubled-caps replay protocol. Replaces the three dense V-sized
    membership/position buffers of the old ``build_block``."""
    with jax.named_scope(spans.HASH_DEDUP):
        return get_backend(backend).hash_dedup(values, mask, seeds, new_cap)


def compact(flags: jax.Array, cap: int, *,
            backend: Optional[str] = None):
    """Order-preserving stream compaction: (sel int32[cap], emask
    bool[cap], num int32[]) — the indices of the first ``cap`` set
    flags, matching ``jnp.nonzero(flags, size=cap, fill_value=0)``."""
    with jax.named_scope(spans.COMPACT):
        return get_backend(backend).compact(flags, cap)


def compact_perm(keys: jax.Array, valid: jax.Array, num_keys: int, *,
                 backend: Optional[str] = None) -> jax.Array:
    """The compaction family's ordering face: a STABLE permutation
    sorting entries by ascending key (keys in [-1, num_keys); invalid
    last) — ``SampledLayer.src_perm`` without a per-step argsort."""
    with jax.named_scope(spans.COMPACT_PERM):
        return get_backend(backend).compact_perm(keys, valid, num_keys)


def segment_select(keys: jax.Array, slot: jax.Array, mask: jax.Array,
                   seg_start: jax.Array, take: jax.Array, num_seeds: int,
                   max_take: int, *, backend: Optional[str] = None
                   ) -> jax.Array:
    """Per-segment smallest-``take`` selection (ties by arrival order)
    over the segment-contiguous ``expand_seed_edges`` layout — the
    sequential-Poisson (§A.3) inclusion set without a global lexsort.
    ``max_take`` is the static fanout bound (>= every take[s])."""
    with jax.named_scope(spans.SEGMENT_SELECT):
        return get_backend(backend).segment_select(
            keys, slot, mask, seg_start, take, num_seeds, max_take)


def masked_cdf_draw(p: jax.Array, valid: jax.Array, u: jax.Array, *,
                    backend: Optional[str] = None) -> jax.Array:
    """Inverse-CDF draws over the valid entries of ``p`` in one
    cap-bounded pass, normalized by the CDF's own final value so
    float32 accumulation error can never index out of range."""
    with jax.named_scope(spans.MASKED_CDF_DRAW):
        return get_backend(backend).masked_cdf_draw(p, valid, u)
