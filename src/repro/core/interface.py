"""The ``Sampler`` protocol and the static-shape sampled-block pytree.

A ``SampledLayer`` is the TPU-friendly analogue of a DGL message-flow
block: every buffer has a static cap so the whole multi-layer sampling +
training step lowers to a single XLA program. Real sizes are carried as
scalars; overflow (real size > cap) is detected and surfaced — never
silently truncated inside a step.

Layout conventions:
  * ``seeds`` are this layer's destination vertices (padding = -1).
  * ``next_seeds`` are the input vertices of this layer = seeds of the
    next (deeper) sampling layer. Seeds come FIRST in ``next_seeds``, so
    a model can take residuals/self-features as ``H_prev[:num_seeds]``.
  * edges are compacted post-sampling: src/dst_slot/src_slot/weight are
    aligned, padded with -1 / 0.

Every sampler — NS, the LABOR family, LADIES/PLADIES, full-neighbor —
implements the :class:`Sampler` protocol: a frozen, hashable
:class:`SamplerSpec` (name, per-layer budgets, static caps, salt
schedule) plus a pure ``sample(graph, seeds, salts) -> [SampledLayer]``
that traces inside any enclosing program. The registry in
``repro.core.samplers`` is the one construction path from trainer to
serving.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rng as rng_lib
from repro.core.cs_solve import _segment_sum
from repro.ops import frontier as frontier_ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SampledLayer:
    seeds: jax.Array        # int32[S] destination vertex ids, -1 pad
    next_seeds: jax.Array   # int32[T] input vertex ids (seeds prefix), -1 pad
    src: jax.Array          # int32[E] source vertex id per sampled edge
    dst_slot: jax.Array     # int32[E] index into seeds
    src_slot: jax.Array     # int32[E] index into next_seeds
    weight: jax.Array       # float32[E] Hajek-normalized A'_ts (Algorithm 1)
    edge_mask: jax.Array    # bool[E]
    # permutation putting edges in src_slot-sorted order (padding last):
    # the TRANSPOSED view of the block, so the Pallas SpMM's grad-wrt-h
    # can reuse the dst-sorted one-hot MXU kernel with src/dst roles
    # swapped (repro.ops backward pass) without re-sorting per step
    src_perm: jax.Array     # int32[E]
    num_seeds: jax.Array    # int32[] real seed count
    num_next: jax.Array     # int32[] real next_seeds count
    num_edges: jax.Array    # int32[] real sampled edge count
    num_expanded: jax.Array  # int32[] real in-edge count of the seeds
    overflow: jax.Array     # bool[] any cap exceeded while building this layer

    @property
    def seed_cap(self) -> int:
        return self.seeds.shape[0]

    @property
    def next_cap(self) -> int:
        return self.next_seeds.shape[0]

    @property
    def edge_cap(self) -> int:
        return self.src.shape[0]


def overflow_flags(blocks: Sequence["SampledLayer"]) -> jax.Array:
    """Per-layer overflow flags stacked as bool[num_layers].

    The fused train step returns these as a device array instead of
    syncing per layer: the loader polls the stacked flags one step late
    (see docs/pipeline.md) so overflow detection never stalls dispatch.
    """
    return jnp.stack([b.overflow for b in blocks])


def sampled_counts(blocks: Sequence["SampledLayer"]) -> dict:
    """Device-side sampling size metrics for a multi-layer block list:
    ``sampled_v`` = |V^3|-style vertex count of the deepest layer,
    ``sampled_e`` = total sampled edges across layers, ``layer_counts``
    = int32[num_layers, 3] of (expanded in-edges, sampled edges, next
    vertices) per layer — the real sizes beside each layer's static
    ``expand_cap``, ``edge_cap`` and ``vertex_cap``."""
    return {
        "sampled_v": blocks[-1].num_next,
        "sampled_e": sum(b.num_edges for b in blocks),
        "layer_counts": jnp.stack([
            jnp.stack([b.num_expanded, b.num_edges, b.num_next])
            for b in blocks]).astype(jnp.int32),
    }


@dataclasses.dataclass(frozen=True)
class LayerCaps:
    """Static buffer sizes for one sampling layer."""
    expand_cap: int   # buffer for ALL in-edges of the layer's seeds
    edge_cap: int     # buffer for sampled edges
    vertex_cap: int   # buffer for next_seeds


def double_caps(caps: Sequence[LayerCaps]) -> list[LayerCaps]:
    """Every buffer of every layer doubled: what :func:`grow_caps` does
    where no buffer was measured past its cap. That is a mesh's step,
    which reports no per-layer counts, or an overflow flag that the
    counts do not explain (an injected storm)."""
    return [dataclasses.replace(c, expand_cap=c.expand_cap * 2,
                                edge_cap=c.edge_cap * 2,
                                vertex_cap=c.vertex_cap * 2) for c in caps]


# the buffers of one layer that an overflow can name
BUFFERS = ("expand", "edge", "vertex")


def _vertex_layout(caps: Sequence[LayerCaps], seed_cap: int) -> list:
    """The one statement of the vertex buffers' layout: each layer's
    vertex buffer is ``[seed buffer ; room for new vertices]``, its seed
    buffer the batch's (``seed_cap`` long) at layer 0 and the previous
    layer's vertex buffer below. Returns ``(seed buffer, room)`` per
    layer."""
    bufs = [seed_cap] + [c.vertex_cap for c in caps[:-1]]
    return [(b, c.vertex_cap - b) for b, c in zip(bufs, caps)]


def cap_overflows(caps: Sequence[LayerCaps], layer_counts,
                  seeds) -> Optional[dict]:
    """The buffers that a batch's measured counts show past their caps:
    ``{(layer, buffer): (size, need)}``, or None where nothing was
    measured past its cap (``layer_counts`` None, or a flag the counts
    do not explain).

    ``layer_counts`` is the step's int32[L, 3] (:func:`sampled_counts`)
    and ``seeds`` the padded seed batch it was sampled from. The
    expanded count is exact. The sampled count is exact unless the
    expand buffer overflowed, and the next count unless the edge buffer
    did: then each counts what fitted, a lower bound, and a replay that
    overflows again is grown again from its own counts. A vertex
    buffer's size here is its room for new vertices
    (:func:`_vertex_layout`), and its need the new vertices, ``next``
    less the real seeds."""
    if layer_counts is None:
        return None
    counts = np.asarray(layer_counts)
    seeds = np.asarray(seeds)
    n_seeds = int(np.sum(seeds >= 0))
    over = {}
    for l, (c, (_, room), (expanded, sampled, nxt)) in enumerate(
            zip(caps, _vertex_layout(caps, seeds.shape[0]), counts)):
        for buf, size, need in (("expand", c.expand_cap, expanded),
                                ("edge", c.edge_cap, sampled),
                                ("vertex", room, nxt - n_seeds)):
            if need > size:
                over[(l, buf)] = (int(size), int(need))
        # the next layer's seeds: this layer's, and the new vertices
        # that fitted
        n_seeds += min(int(nxt) - n_seeds, room)
    return over or None


def grow_caps(caps: Sequence[LayerCaps], over: Optional[dict],
              seed_cap: Optional[int]) -> list[LayerCaps]:
    """The overflow-retry growth rule: each buffer in ``over``
    (:func:`cap_overflows`) grows to max(2 x size, 1.25 x need), rounded
    up to 128; every other buffer keeps its size, and a vertex buffer
    keeps its room for new vertices when the seed buffer beneath it
    grows. One jit specialization exists per cap schedule, and the
    doubling floor keeps the number of recompiles logarithmic. With
    ``over`` None every buffer doubles (:func:`double_caps`).
    ``seed_cap`` is the length of the batch's seed buffer, read only
    where ``over`` names a buffer."""
    if over is None:
        return double_caps(caps)

    def size(l, buf, old):
        if (l, buf) not in over:
            return old
        cap, need = over[(l, buf)]
        return _round_up(max(2 * cap, math.ceil(1.25 * need)), 128)

    out = []
    for l, (c, (_, room)) in enumerate(zip(caps,
                                           _vertex_layout(caps, seed_cap))):
        seed_buf = out[-1].vertex_cap if out else seed_cap
        out.append(LayerCaps(expand_cap=size(l, "expand", c.expand_cap),
                             edge_cap=size(l, "edge", c.edge_cap),
                             vertex_cap=seed_buf + size(l, "vertex", room)))
    return out


def suggest_peer_caps(batch_size: int, caps: Sequence[LayerCaps],
                      num_parts: int, safety: float = 2.0) -> tuple:
    """Per-peer all-to-all slot counts for the partition-aware engine.

    ``peer_caps[i]`` bounds how many ids one device may address to one
    peer in an all-to-all keyed on frontier buffer ``i``: buffer 0 is
    the device-local seed batch, buffer ``l + 1`` is layer ``l``'s
    ``next_seeds`` buffer (``caps[l].vertex_cap``). The same schedule
    covers seed routing, hidden-state exchange, and the feature fetch —
    every collective the distributed step issues. Ids spread over
    owners ~uniformly (modulo partition of hash-scale vertex ids), so
    mean/num_parts plus slack concentrates like the LayerCaps geometry.
    """
    sizes = [batch_size] + [c.vertex_cap for c in caps]
    return tuple(
        _round_up(int(t / num_parts * safety) + 6 * int(t ** 0.5) + 16, 8)
        for t in sizes)


def suggest_caps(
    batch_size: int,
    fanouts: Sequence[int],
    avg_degree: float,
    max_degree: int,
    safety: float = 1.5,
    max_expand: int = 1 << 22,
    num_vertices: int | None = None,
    num_edges: int | None = None,
) -> list[LayerCaps]:
    """Heuristic cap schedule: E[sizes] from fanout geometry + slack.

    Poisson sampling concentrates tightly around its mean (sum of
    independent Bernoullis), so mean * safety + a few sigma is enough;
    an overflow is replayed with the buffers it names grown
    (:func:`grow_caps`). Caps are
    clamped to the whole graph when ``num_vertices``/``num_edges`` given.
    """
    caps = []
    n_seeds = batch_size
    for k in fanouts:
        exp_edges = n_seeds * min(k, avg_degree)
        sampled = int(exp_edges * safety + 6 * exp_edges ** 0.5) + 64
        expand = int(min(n_seeds * avg_degree * safety + 4 * max_degree, max_expand)) + 64
        if num_edges is not None:
            sampled = min(sampled, num_edges)
            expand = min(expand, num_edges)
        n_next = n_seeds + sampled
        if num_vertices is not None:
            # next_seeds = [seed buffer ; new unique vertices]: the new
            # part is bounded by |V|, the buffer keeps its padded slots
            n_next = min(n_next, n_seeds + num_vertices)
        caps.append(LayerCaps(
            expand_cap=_round_up(max(expand, sampled), 128),
            edge_cap=_round_up(sampled, 128),
            vertex_cap=_round_up(max(n_next, n_seeds + 128), 128),
        ))
        # next layer's seed buffer is exactly this layer's vertex buffer
        n_seeds = caps[-1].vertex_cap
    return caps


def _round_up(x: int, m: int) -> int:
    return ((int(x) + m - 1) // m) * m


def pad_seeds(seeds: jax.Array, cap: int) -> jax.Array:
    n = seeds.shape[0]
    if n > cap:
        raise ValueError(f"seed count {n} exceeds cap {cap}")
    return jnp.concatenate([
        seeds.astype(jnp.int32),
        jnp.full((cap - n,), -1, jnp.int32),
    ])


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Frozen, hashable description of a configured sampler.

    Attributes:
      name:     registry name (``ns``, ``labor-0``, ``ladies``, ...).
      budgets:  per-layer budget, outermost first — the fanout ``k`` for
                neighbor-style samplers, the layer size ``n`` for the
                ladies family, a cap-sizing hint for ``full``.
      caps:     static buffer schedule, one :class:`LayerCaps` per layer.
                Caps live HERE (not on sampler configs): overflow retry
                is :meth:`grown`.
      shared_salts: one salt reused across layers (§A.8 layer
                dependency) instead of an independent salt per layer.
      peer_caps: optional per-peer all-to-all slot schedule for the
                partition-aware distributed engine (length num_layers+1,
                see :func:`suggest_peer_caps`); ``None`` on samplers
                built without a partition count. Overflow replay doubles
                them alongside the LayerCaps (:meth:`grown`), so a
                feature-exchange overflow heals through the same
                protocol as a sampling overflow.
    """
    name: str
    budgets: tuple
    caps: tuple
    shared_salts: bool = False
    peer_caps: Optional[tuple] = None

    def __post_init__(self):
        object.__setattr__(self, "budgets",
                           tuple(int(b) for b in self.budgets))
        object.__setattr__(self, "caps", tuple(self.caps))
        if len(self.caps) != len(self.budgets):
            raise ValueError(
                f"spec {self.name!r}: {len(self.budgets)} budgets but "
                f"{len(self.caps)} LayerCaps — need one cap per layer")
        if self.peer_caps is not None:
            object.__setattr__(self, "peer_caps",
                               tuple(int(c) for c in self.peer_caps))
            if len(self.peer_caps) != len(self.caps) + 1:
                raise ValueError(
                    f"spec {self.name!r}: peer_caps must have "
                    f"num_layers + 1 = {len(self.caps) + 1} entries "
                    f"(got {len(self.peer_caps)})")

    @property
    def num_layers(self) -> int:
        return len(self.caps)

    def salts(self, key: jax.Array) -> jax.Array:
        """Per-layer uint32 salt schedule from a PRNG key (traceable)."""
        return rng_lib.layer_salts_from_key(key, self.num_layers,
                                            shared=self.shared_salts)

    def salts_from_uint32(self, salt: jax.Array) -> jax.Array:
        """Salt schedule from a raw uint32 (shard_map-friendly)."""
        return rng_lib.layer_salts_from_uint32(salt, self.num_layers,
                                               shared=self.shared_salts)

    def with_caps(self, caps: Sequence[LayerCaps]) -> "SamplerSpec":
        """New LayerCaps schedule; ``peer_caps`` are left untouched (use
        :meth:`grown` for the overflow-retry growth of both)."""
        return dataclasses.replace(self, caps=tuple(caps))

    def grown(self, over: Optional[dict],
              seed_cap: Optional[int]) -> "SamplerSpec":
        """The overflow-retry step: the LayerCaps buffers ``over`` names
        grown by :func:`grow_caps`, every per-peer all-to-all cap
        doubled."""
        peer = (None if self.peer_caps is None
                else tuple(c * 2 for c in self.peer_caps))
        return dataclasses.replace(
            self, caps=tuple(grow_caps(self.caps, over, seed_cap)),
            peer_caps=peer)


@dataclasses.dataclass(frozen=True)
class Sampler:
    """Protocol base for every sampler: a frozen spec + a pure trace.

    Subclasses implement :meth:`sample`; everything else (cap
    management, salt derivation, the jitted standalone entry point) is
    shared. Instances are hashable and compare by value, so they can be
    closed over by — or passed as static arguments to — jitted
    programs, with one compilation per (sampler, caps) pair.
    """
    spec: SamplerSpec

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def caps(self) -> tuple:
        return self.spec.caps

    @property
    def num_layers(self) -> int:
        return self.spec.num_layers

    def sample(self, graph, seeds: jax.Array,
               salts: jax.Array) -> list:
        """Multi-layer sampling from an explicit per-layer salt schedule
        (uint32[num_layers]). Pure and fully traceable — this is the
        entry point fused train/infer steps inline, with ``salts`` as a
        dynamic argument so recompilation never happens across steps.
        Returns blocks, batch (outermost) layer first."""
        raise NotImplementedError

    def with_caps(self, caps: Sequence[LayerCaps]) -> "Sampler":
        """Clone with a new static cap schedule (same sampling math)."""
        return dataclasses.replace(self, spec=self.spec.with_caps(caps))

    def grown(self, over: Optional[dict],
              seed_cap: Optional[int]) -> "Sampler":
        """The one overflow-retry idiom (:meth:`SamplerSpec.grown`):
        the overflowed LayerCaps buffers grown, per-peer all-to-all caps
        doubled, sampling math unchanged."""
        return dataclasses.replace(self, spec=self.spec.grown(over,
                                                              seed_cap))

    def sample_layer_partitioned(self, graph, seeds: jax.Array,
                                 salt: jax.Array, layer: int, *,
                                 seed_rows: jax.Array, num_vertices: int,
                                 axis_name=None):
        """One sampling layer against a partition-local CSR, inside the
        distributed engine's shard_map body.

        ``seeds`` are GLOBAL vertex ids owned by this partition (so the
        stateless hash r_t — and therefore the sampled set — matches the
        single-device trace bit-exactly); ``seed_rows`` maps each seed to
        its row in the partition-local ``graph`` (local id = v // P);
        ``num_vertices`` is the GLOBAL vertex count for the dense
        membership epilogue; ``axis_name`` names the mesh axis for the
        cross-partition reductions batch-global samplers need (LABOR
        importance pmax, LADIES column-norm psum). Returns one
        :class:`SampledLayer` in global-id space."""
        raise NotImplementedError(
            f"sampler {self.name!r} does not implement the "
            "partition-local sampling path of the distributed engine")

    def sample_with_key(self, graph, seeds: jax.Array,
                        key: jax.Array) -> list:
        """Standalone jitted sampling from a PRNG key. Runs the same
        trace as :meth:`sample` (cached per sampler value), so
        standalone blocks are bit-identical to blocks sampled inside a
        fused program with the same key."""
        return _sample_jit(self, graph, seeds, self.spec.salts(key))

    def sample_with_salt(self, graph, seeds: jax.Array,
                         salt: jax.Array) -> list:
        """Unjitted trace from a raw uint32 salt — for use inside an
        enclosing shard_map/jit where key objects are awkward."""
        return self.sample(graph, seeds, self.spec.salts_from_uint32(salt))


@partial(jax.jit, static_argnames=("sampler",))
def _sample_jit(sampler: Sampler, graph, seeds, salts):
    return sampler.sample(graph, seeds, salts)


def build_block(seeds: jax.Array, exp: dict, include: jax.Array,
                inv_p: jax.Array, caps: LayerCaps,
                backend: Optional[str] = None) -> SampledLayer:
    """Shared epilogue of every sampler: from per-edge inclusion
    decisions over an expanded seed neighborhood to a finished
    :class:`SampledLayer`.

    Hajek-normalizes ``inv_p`` (1/p_ts per expanded edge; values outside
    ``include`` are ignored) into edge weights (Algorithm 1), compacts
    included edges into the static edge buffer, builds ``next_seeds =
    [seeds ; sorted unique new srcs]``, maps sources to slots, and
    raises the overflow flag if any static cap was exceeded.

    Every step runs on the frontier primitives (repro.ops.frontier), so
    cost and peak memory are O(cap) — independent of the graph's vertex
    count. The emitted block is bit-identical to the retained dense
    baseline :func:`build_block_dense` (same inclusion set, same
    ascending ``next_seeds`` order, same stable ``src_perm``), which is
    what keeps the fused and partitioned parity suites exact.
    """
    S = seeds.shape[0]
    src, slot, mask = exp["src"], exp["seed_slot"], exp["mask"]
    safe_slot = jnp.clip(slot, 0, S - 1)

    # Hajek weights (Algorithm 1): A'_ts = (1/p_ts) / sum_{t'} 1/p_t's
    inv_p = jnp.where(include, inv_p, 0.0)
    w = _segment_sum(inv_p, jnp.where(include, slot, -1), S)
    weight_full = jnp.where(include, inv_p / jnp.maximum(w[safe_slot], 1e-20),
                            0.0)

    # Compact sampled edges into the static edge_cap buffer
    # (order-preserving, so edges stay dst-segment-contiguous).
    sel, emask, num_sampled = frontier_ops.compact(include, caps.edge_cap,
                                                   backend=backend)
    e_src = jnp.where(emask, src[sel], -1)
    e_dst_slot = jnp.where(emask, slot[sel], -1)
    e_weight = jnp.where(emask, weight_full[sel], 0.0)

    # next_seeds = [seeds ; sorted unique sampled srcs not already
    # seeds] and the src -> next_seeds slot map, in one cap-bounded
    # dedup instead of three dense V-sized membership/position buffers
    new_cap = caps.vertex_cap - S
    if new_cap <= 0:
        raise ValueError("vertex_cap must exceed seed buffer size")
    dd = frontier_ops.hash_dedup(e_src, emask, seeds, new_cap,
                                 backend=backend)
    next_seeds = jnp.concatenate([seeds.astype(jnp.int32), dd.new])
    e_src_slot = jnp.where(emask, dd.slots, -1)

    num_seeds = jnp.sum((seeds >= 0).astype(jnp.int32))
    # transposed edge order (sorted by src_slot, padding last; stable,
    # so ties keep the dst-sorted order) — precomputed once here rather
    # than per backward pass (see SampledLayer.src_perm)
    src_perm = frontier_ops.compact_perm(e_src_slot, emask,
                                         caps.vertex_cap, backend=backend)
    overflow = (
        (exp["total"] > caps.expand_cap)
        | (num_sampled > caps.edge_cap)
        | dd.overflow
    )
    return SampledLayer(
        seeds=seeds.astype(jnp.int32),
        next_seeds=next_seeds,
        src=e_src,
        dst_slot=e_dst_slot,
        src_slot=e_src_slot,
        weight=e_weight,
        edge_mask=emask,
        src_perm=src_perm,
        num_seeds=num_seeds,
        num_next=num_seeds + dd.num_new,
        num_edges=num_sampled,
        num_expanded=exp["total"],
        overflow=overflow,
    )


def build_block_dense(num_vertices: int, seeds: jax.Array, exp: dict,
                      include: jax.Array, inv_p: jax.Array,
                      caps: LayerCaps) -> SampledLayer:
    """The ORIGINAL dense epilogue, retained verbatim as the O(V)
    baseline: three dense V-sized scatters (seed membership, sampled
    membership, id→slot position map) plus a full argsort per layer.

    Kept for two jobs: the benchmark baseline the BENCH_sampling.json
    sample-phase comparison is measured against, and the bit-exactness
    oracle of tests/test_frontier.py (``build_block`` must reproduce
    this block field for field). Not used on any hot path.
    """
    S = seeds.shape[0]
    src, slot, mask = exp["src"], exp["seed_slot"], exp["mask"]
    safe_slot = jnp.clip(slot, 0, S - 1)

    inv_p = jnp.where(include, inv_p, 0.0)
    w = _segment_sum(inv_p, jnp.where(include, slot, -1), S)
    weight_full = jnp.where(include, inv_p / jnp.maximum(w[safe_slot], 1e-20),
                            0.0)

    num_sampled = jnp.sum(include.astype(jnp.int32))
    sel = jnp.nonzero(include, size=caps.edge_cap, fill_value=0)[0]
    emask = jnp.arange(caps.edge_cap) < jnp.minimum(num_sampled, caps.edge_cap)
    e_src = jnp.where(emask, src[sel], -1)
    e_dst_slot = jnp.where(emask, slot[sel], -1)
    e_weight = jnp.where(emask, weight_full[sel], 0.0)

    V = num_vertices
    seed_member = jnp.zeros((V,), jnp.bool_).at[jnp.where(seeds >= 0, seeds, 0)].set(
        seeds >= 0, mode="drop"
    )
    samp_member = jnp.zeros((V,), jnp.bool_).at[jnp.where(emask, e_src, 0)].set(
        emask, mode="drop"
    )
    new_member = samp_member & ~seed_member
    num_new = jnp.sum(new_member.astype(jnp.int32))
    new_cap = caps.vertex_cap - S
    if new_cap <= 0:
        raise ValueError("vertex_cap must exceed seed buffer size")
    new_vs = jnp.nonzero(new_member, size=new_cap, fill_value=-1)[0].astype(jnp.int32)
    next_seeds = jnp.concatenate([seeds.astype(jnp.int32), new_vs])

    pos = jnp.full((V,), -1, jnp.int32).at[jnp.where(next_seeds >= 0, next_seeds, 0)].set(
        jnp.arange(caps.vertex_cap, dtype=jnp.int32), mode="drop"
    )
    e_src_slot = jnp.where(emask, pos[jnp.where(emask, e_src, 0)], -1)

    num_seeds = jnp.sum((seeds >= 0).astype(jnp.int32))
    src_perm = jnp.argsort(
        jnp.where(emask, e_src_slot, caps.vertex_cap)).astype(jnp.int32)
    overflow = (
        (exp["total"] > caps.expand_cap)
        | (num_sampled > caps.edge_cap)
        | (num_new > new_cap)
    )
    return SampledLayer(
        seeds=seeds.astype(jnp.int32),
        next_seeds=next_seeds,
        src=e_src,
        dst_slot=e_dst_slot,
        src_slot=e_src_slot,
        weight=e_weight,
        edge_mask=emask,
        src_perm=src_perm,
        num_seeds=num_seeds,
        num_next=num_seeds + num_new,
        num_edges=num_sampled,
        num_expanded=exp["total"],
        overflow=overflow,
    )
