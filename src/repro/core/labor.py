"""LABOR sampling (paper §3.2) — pure-JAX, jittable, static-shape.

One call to :func:`sample_layer` performs a single layer of LABOR-i
sampling for a padded seed set; :class:`LaborSampler` recurses it over
layers. Setting ``per_edge_rng=True`` with ``importance_iters=0``
degenerates to (Poisson) Neighbor Sampling — the equivalence the paper
notes at the end of §3.2 — and ``exact_k=True`` switches Poisson
inclusion to sequential Poisson sampling (paper §A.3), which reproduces
vanilla NS exactly in the uniform case.

Per-vertex state is CAP-BOUNDED on the single-host path: the importance
fixed point runs over the deduplicated candidate frontier (unique
sources of the expanded neighborhood, via ``repro.ops.frontier``), and
sequential Poisson selects per segment without a global sort — nothing
in a ``sample`` trace allocates a V-sized buffer. Only the distributed
partition-local mode (``axis_name``) keeps dense-V per-vertex state,
because its cross-partition pmax needs one aligned layout on every
device. Per-edge state is segment-contiguous with static caps (see
repro/graph/csr.py::expand_seed_edges).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import rng as rng_lib
from repro.core.cs_solve import solve_cs, solve_cs_weighted
from repro.core.interface import (LayerCaps, SampledLayer, Sampler,
                                  SamplerSpec, build_block)
from repro.graph.csr import Graph, expand_seed_edges
from repro.ops import frontier as frontier_ops
from repro.runtime import spans

CONVERGE = -1  # importance_iters value for LABOR-*


@dataclasses.dataclass(frozen=True)
class LaborConfig:
    fanouts: Sequence[int]
    importance_iters: int = 0          # 0 -> LABOR-0, i -> LABOR-i, CONVERGE -> LABOR-*
    layer_dependency: bool = False     # reuse r_t across layers (§A.8)
    per_edge_rng: bool = False         # r_ts instead of r_t  => Neighbor Sampling
    exact_k: bool = False              # sequential Poisson (§A.3): exactly min(k, d_s)
    converge_tol: float = 1e-4         # paper: rel change of E[|T|] < 1e-4
    converge_max_iters: int = 30
    # closed-form uniform-pi c + warm-started importance solves; False
    # reproduces the original cold-start solver (benchmark baseline)
    fast_solve: bool = True


def _expected_num_sampled(pi: jax.Array, max_c: jax.Array) -> jax.Array:
    """E[|T|] = sum_t min(1, pi_t * max_{t->s} c_s)   (eq. 11)."""
    return jnp.sum(jnp.minimum(1.0, pi * max_c))


def _scatter_max_c(c_edges, src, mask, num_vertices):
    """max_{t->s} c_s per source vertex t, dense over V (0 elsewhere)."""
    safe_src = jnp.where(mask, src, 0)
    vals = jnp.where(mask, c_edges, 0.0)
    return jnp.zeros((num_vertices,), jnp.float32).at[safe_src].max(
        vals, mode="drop"
    )


def run_importance_iterations(
    graph: Graph,
    exp: dict,
    k: jax.Array,
    num_seeds: int,
    importance_iters: int,
    converge_tol: float = 1e-4,
    converge_max_iters: int = 30,
    fast_solve: bool = True,
    num_vertices: Optional[int] = None,
    axis_name=None,
    dense: Optional[bool] = None,
):
    """Fixed-point iterations on pi (eq. 18): pi_t <- pi_t * max_{t->s} c_s.

    Returns (pi_e float32[expand_cap] — pi gathered per expanded edge,
    c float32[S]). For importance_iters == 0 this is a single c solve
    with uniform pi (no per-vertex state at all).

    ``fast_solve`` enables the post-fusion fast path: the closed-form
    uniform-pi solution for LABOR-0/NS and warm-started c solves across
    importance iterations. ``fast_solve=False`` reproduces the original
    cold-start iterative solver on every call — kept as the benchmark
    baseline and for solver cross-validation.

    Per-vertex pi state lives on the deduplicated CANDIDATE frontier
    (unique expanded sources — cap-bounded), not on a dense V vector:
    the eq. 18 update multiplies each vertex's pi by exactly the same
    factor sequence either way (the scatter-max is order-free), so the
    candidate-frontier fixed point is bit-identical per vertex to the
    retained dense layout.

    ``dense=True`` (forced, or implied by ``axis_name``) keeps the
    original dense-V layout: inside the distributed engine's shard_map
    body each partition holds only its owned seeds, and the eq. 18 max
    over destinations is completed with a cross-partition ``pmax``
    that needs one aligned per-vertex layout on every device. Because
    max commutes exactly in floating point, the resulting pi — and
    hence every inclusion decision — matches the single-device trace;
    c_s solves stay partition-local (per-seed). ``num_vertices``
    overrides the dense-state size with the GLOBAL vertex count when
    ``graph`` is a partition-local CSR.
    """
    if dense is None:
        dense = axis_name is not None
    src, slot, mask, deg = exp["src"], exp["seed_slot"], exp["mask"], exp["deg"]
    E = src.shape[0]

    if importance_iters == 0:
        pi_e = jnp.ones((E,), jnp.float32)
        if not fast_solve:
            return pi_e, solve_cs(pi_e, slot, deg, k, num_seeds, mask)
        # Uniform pi: eq. 14 reduces to d / min(1, c) = d^2 / k, i.e. the
        # closed form c = k/d for k < d and c = 1 (max 1/pi) otherwise —
        # the exact fixed point solve_cs iterates toward (see
        # tests/test_cs_solve.py::test_uniform_pi_closed_form). Skipping
        # the iterative solve removes the O(E) x iters segment reductions
        # from the LABOR-0 / NS hot path entirely.
        degf = deg.astype(jnp.float32)
        kf = jnp.broadcast_to(jnp.asarray(k, jnp.float32), (num_seeds,))
        valid = deg > 0
        c = jnp.where(valid,
                      jnp.where(kf >= degf, 1.0,
                                kf / jnp.maximum(degf, 1.0)),
                      0.0)
        return pi_e, c

    if dense:
        V = num_vertices if num_vertices is not None else graph.num_vertices
        gather = jnp.where(mask, src, 0)

        def fac_of(c):
            fac = _scatter_max_c(c[jnp.clip(slot, 0, num_seeds - 1)], src,
                                 mask, V)
            if axis_name is not None:
                fac = jax.lax.pmax(fac, axis_name)
            return fac

        pi0 = jnp.ones((V,), jnp.float32)
    else:
        # candidate frontier: one slot per unique expanded source; the
        # gather/scatter target is cap-bounded and V never appears
        dd = frontier_ops.hash_dedup(src, mask, None, E)
        cidx = jnp.where(mask, dd.slots, E)

        def fac_of(c):
            c_e = jnp.where(mask, c[jnp.clip(slot, 0, num_seeds - 1)], 0.0)
            return jnp.zeros((E + 1,), jnp.float32).at[cidx].max(
                c_e, mode="drop")[:E]

        gather = jnp.clip(cidx, 0, E - 1)
        pi0 = jnp.ones((E,), jnp.float32)

    def c_of(pi, c_prev=None):
        return solve_cs(pi[gather], slot, deg, k, num_seeds, mask,
                        c_init=c_prev if fast_solve else None)

    def one_step(pi, c_prev=None):
        c = c_of(pi, c_prev)
        fac = fac_of(c)
        pi_new = jnp.where(fac > 0, pi * fac, pi)
        return pi_new, c

    if importance_iters > 0:
        pi, c = pi0, None
        for _ in range(importance_iters):
            pi, c = one_step(pi, c)
        return pi[gather], c_of(pi, c)

    # LABOR-*: iterate until relative change in E[|T|] < tol (paper §4.3).
    def cost(pi, c):
        return _expected_num_sampled(pi, fac_of(c))

    def body(state):
        pi, c_prev, prev_cost, _, i = state
        pi_new, c = one_step(pi, c_prev)
        c_new = c_of(pi_new, c)
        new_cost = cost(pi_new, c_new)
        # relative change across successive iterations — computed here,
        # where both costs exist, so cond never re-evaluates the cost of
        # the state it is comparing against (which made rel identically
        # zero and silently capped the loop at 2 iterations)
        rel = jnp.abs(prev_cost - new_cost) / jnp.maximum(new_cost, 1.0)
        return pi_new, c_new, new_cost, rel, i + 1

    def cond(state):
        *_, rel, i = state
        return (i < converge_max_iters) & ((i < 2) | (rel > converge_tol))

    c0 = c_of(pi0)
    pi, c, _, _, _ = jax.lax.while_loop(
        cond, body,
        (pi0, c0, cost(pi0, c0), jnp.float32(jnp.inf), jnp.int32(0))
    )
    return pi[gather], c_of(pi, c)


def _exact_k_include(r, slot, mask, deg, seg_start, k, num_seeds, expand_cap):
    """Sequential Poisson (§A.3): per segment take the min(k, d) smallest r.

    r is already divided by (c_s * pi_t) by the caller. Runs on the
    ``segment_select`` frontier primitive — one cap-bounded threshold
    pass instead of the global O(E log E) lexsort (retained below as
    the benchmark baseline / bit-exactness oracle).
    """
    del expand_cap  # the selection is cap-bounded by construction
    keys = jnp.minimum(r, 1e30)
    kk = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (num_seeds,))
    take = jnp.minimum(kk, deg)
    return frontier_ops.segment_select(keys, slot, mask, seg_start, take,
                                       num_seeds, int(k))


def _exact_k_include_dense(r, slot, mask, deg, seg_start, k, num_seeds,
                           expand_cap):
    """The ORIGINAL global-lexsort sequential Poisson, retained verbatim
    as the O(E log E) benchmark baseline and the oracle
    tests/test_frontier.py checks ``segment_select`` against bit for
    bit. Not used on any hot path."""
    big = jnp.float32(3.4e38)
    key_sorted = jnp.where(mask, jnp.minimum(r, 1e30), big)
    slot_for_sort = jnp.where(mask, slot, num_seeds)
    order = jnp.lexsort((key_sorted, slot_for_sort))
    slot_s = slot_for_sort[order]
    pos = jnp.arange(expand_cap, dtype=jnp.int32)
    # segments are contiguous after the sort and retain their original
    # lengths, so each segment s starts at seg_start[s].
    seg_start_s = jnp.where(slot_s < num_seeds, seg_start[jnp.clip(slot_s, 0, num_seeds - 1)], 0)
    pos_in_seg = pos - seg_start_s
    kk = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (num_seeds,))
    take = jnp.minimum(kk[jnp.clip(slot_s, 0, num_seeds - 1)], deg[jnp.clip(slot_s, 0, num_seeds - 1)])
    inc_sorted = (slot_s < num_seeds) & (pos_in_seg < take)
    return jnp.zeros((expand_cap,), jnp.bool_).at[order].set(inc_sorted)


def sample_layer(
    graph: Graph,
    seeds: jax.Array,
    salt: jax.Array,
    k: int,
    caps: LayerCaps,
    importance_iters: int = 0,
    per_edge_rng: bool = False,
    exact_k: bool = False,
    converge_tol: float = 1e-4,
    converge_max_iters: int = 30,
    fast_solve: bool = True,
    seed_rows: Optional[jax.Array] = None,
    num_vertices: Optional[int] = None,
    axis_name=None,
) -> SampledLayer:
    """One layer of LABOR-i sampling for padded ``seeds`` (int32[S], -1 pad).

    ``seed_rows``/``num_vertices``/``axis_name`` are the partition-local
    mode of the distributed engine: seeds stay GLOBAL ids (so the
    stateless r_t hash matches the single-device trace bit-exactly)
    while CSR rows are looked up at ``seed_rows`` in a partition-local
    ``graph``; dense per-vertex state spans the global ``num_vertices``;
    the eq. 18 importance max is completed across partitions over
    ``axis_name``."""
    S = seeds.shape[0]
    exp = expand_seed_edges(graph, seeds, caps.expand_cap,
                            seed_rows=seed_rows)
    src, slot, mask, deg = exp["src"], exp["seed_slot"], exp["mask"], exp["deg"]
    safe_slot = jnp.clip(slot, 0, S - 1)

    # from the expanded edges to the inclusion mask and 1/p: the
    # importance iterations, the variates, c_s per edge, the comparison
    # or the exact-k selection
    with jax.named_scope(spans.INCLUDE):
        if graph.weights is None:
            pi_e, c = run_importance_iterations(
                graph, exp, k, S, importance_iters, converge_tol,
                converge_max_iters, fast_solve=fast_solve,
                num_vertices=num_vertices, axis_name=axis_name,
            )
        else:
            # weighted case (§A.7): per-edge pi initialised to A_ts
            a_e = exp["edge_weight"]
            pi_e = jnp.where(mask, a_e, 1.0)
            c = solve_cs_weighted(pi_e, a_e, slot, deg, k, S, mask)

        # Inclusion: r < c_s * pi_t with shared-per-vertex r (LABOR) or
        # per-edge r (NS equivalence).
        if per_edge_rng:
            r = rng_lib.hash_uniform_edge(salt, src, jnp.where(mask, seeds[safe_slot], 0))
        else:
            r = rng_lib.hash_uniform(salt, src)
        c_e = c[safe_slot]
        prob = jnp.minimum(1.0, c_e * jnp.maximum(pi_e, 0.0))

        if exact_k:
            ratio = jnp.where(mask, r / jnp.maximum(c_e * pi_e, 1e-20), 3.4e38)
            include = _exact_k_include(ratio, slot, mask, deg, exp["seg_start"], k, S, caps.expand_cap)
        else:
            include = mask & (r < c_e * pi_e)
        inv_p = 1.0 / jnp.maximum(prob, 1e-20)

    # Hajek normalization + edge compaction + next_seeds construction is
    # the epilogue every sampler shares (core.interface.build_block).
    return build_block(seeds, exp, include, inv_p, caps)


def layer_salts(cfg: LaborConfig, key: jax.Array) -> jax.Array:
    """Per-layer uint32 salts for ``cfg`` derived from a PRNG key.

    Stacked as uint32[num_layers] so the whole schedule can be passed as
    one device array into a fused (sampling traced inside jit) train
    step. ``layer_dependency`` broadcasts the base salt (§A.8)."""
    return rng_lib.layer_salts_from_key(key, len(cfg.fanouts),
                                        shared=cfg.layer_dependency)


def sample_with_salts(cfg: LaborConfig, caps: Sequence[LayerCaps],
                      graph: Graph, seeds: jax.Array,
                      salts: jax.Array) -> list[SampledLayer]:
    """Multi-layer sampling from an explicit per-layer salt schedule
    (uint32[num_layers], see :func:`layer_salts`). Fully traceable — this
    is the entry point the fused one-program train step uses, with
    ``salts`` as a dynamic argument so recompilation never happens across
    steps."""
    blocks = []
    cur = seeds
    for layer, (k, lcaps) in enumerate(zip(cfg.fanouts, caps)):
        with jax.named_scope(spans.layer(layer)):
            blk = sample_layer(
                graph, cur, salts[layer], k, lcaps,
                importance_iters=cfg.importance_iters,
                per_edge_rng=cfg.per_edge_rng,
                exact_k=cfg.exact_k,
                converge_tol=cfg.converge_tol,
                converge_max_iters=cfg.converge_max_iters,
                fast_solve=cfg.fast_solve,
            )
        blocks.append(blk)
        cur = blk.next_seeds
    return blocks


def _labor_name(cfg: LaborConfig) -> str:
    """Canonical registry name for a LABOR-family config."""
    if cfg.per_edge_rng:
        return "ns"
    if cfg.layer_dependency and cfg.importance_iters == 0:
        return "labor-d"
    if cfg.importance_iters == CONVERGE:
        return "labor-*"
    return f"labor-{cfg.importance_iters}"


@dataclasses.dataclass(frozen=True)
class LaborSampler(Sampler):
    """Multi-layer LABOR-i sampler (paper Algorithm 1 over l layers) on
    the :class:`~repro.core.interface.Sampler` protocol. Construct via
    :meth:`build`, :func:`labor_sampler`/:func:`neighbor_sampler`, or
    the registry (``repro.core.samplers.get``)."""
    config: LaborConfig = None

    @classmethod
    def build(cls, config: LaborConfig, caps: Sequence[LayerCaps],
              name: Optional[str] = None) -> "LaborSampler":
        if len(caps) != len(config.fanouts):
            raise ValueError("need one LayerCaps per fanout")
        config = dataclasses.replace(config, fanouts=tuple(config.fanouts))
        spec = SamplerSpec(name=name or _labor_name(config),
                           budgets=config.fanouts, caps=tuple(caps),
                           shared_salts=config.layer_dependency)
        return cls(spec=spec, config=config)

    def with_caps(self, caps: Sequence[LayerCaps]) -> "LaborSampler":
        if len(caps) != len(self.config.fanouts):
            raise ValueError("need one LayerCaps per fanout")
        return super().with_caps(caps)

    def sample(self, graph: Graph, seeds: jax.Array,
               salts: jax.Array) -> list[SampledLayer]:
        return sample_with_salts(self.config, self.spec.caps, graph, seeds,
                                 salts)

    def sample_layer_partitioned(self, graph: Graph, seeds: jax.Array,
                                 salt: jax.Array, layer: int, *,
                                 seed_rows: jax.Array, num_vertices: int,
                                 axis_name=None) -> SampledLayer:
        cfg = self.config
        return sample_layer(
            graph, seeds, salt, cfg.fanouts[layer], self.spec.caps[layer],
            importance_iters=cfg.importance_iters,
            per_edge_rng=cfg.per_edge_rng,
            exact_k=cfg.exact_k,
            converge_tol=cfg.converge_tol,
            converge_max_iters=cfg.converge_max_iters,
            fast_solve=cfg.fast_solve,
            seed_rows=seed_rows, num_vertices=num_vertices,
            axis_name=axis_name,
        )


def sample_with_salt(cfg: LaborConfig, caps: Sequence[LayerCaps],
                     graph: Graph, seeds: jax.Array,
                     salt: jax.Array) -> list[SampledLayer]:
    """Multi-layer sampling from a raw uint32 salt (no PRNG key object) —
    used inside shard_map where keys are awkward to thread. Layer salts
    are derived by remixing unless layer_dependency is set."""
    salts = rng_lib.layer_salts_from_uint32(salt, len(cfg.fanouts),
                                            shared=cfg.layer_dependency)
    return sample_with_salts(cfg, caps, graph, seeds, salts)


def neighbor_sampler(fanouts: Sequence[int], caps: Sequence[LayerCaps],
                     exact: bool = True) -> LaborSampler:
    """Vanilla Neighbor Sampling (Hamilton et al. 2017) as the degenerate
    LABOR configuration the paper identifies: per-edge randomness, uniform
    pi; ``exact=True`` takes exactly min(k, d_s) neighbors."""
    return LaborSampler.build(
        LaborConfig(fanouts=tuple(fanouts), importance_iters=0,
                    per_edge_rng=True, exact_k=exact),
        caps,
    )


def labor_sampler(fanouts: Sequence[int], caps: Sequence[LayerCaps],
                  variant: int | str = 0, layer_dependency: bool = False) -> LaborSampler:
    """LABOR-i factory. variant: 0, 1, 2, ... or '*' for convergence."""
    iters = CONVERGE if variant in ("*", CONVERGE) else int(variant)
    return LaborSampler.build(
        LaborConfig(fanouts=tuple(fanouts), importance_iters=iters,
                    layer_dependency=layer_dependency),
        caps,
    )
