"""LADIES (Zou et al. 2019) baseline and PLADIES (paper §3.1).

Both sample a fixed number ``n`` of vertices per layer with probabilities
proportional to the squared column norms of the row-normalized adjacency
restricted to the seeds:  p_t  ∝  sum_{s in S, t->s} 1/d_s^2.

* LADIES: n draws WITH replacement (inverse-CDF), deduplicated, Hajek
  row-normalized — mirroring the reference implementation the paper
  critiques (biased without-replacement use of with-replacement math).
* PLADIES: Poisson sampling with inclusion probs pi_t = min(1, lam*p_t)
  water-filled so that sum pi = n (unbiased by construction, linear
  time — the paper's first contribution).

Blocks carry ALL edges from sampled vertices into the seeds, which is
what makes LADIES-style methods edge-inefficient (paper Table 2).

Randomness is salt-based (stateless hashes of a per-layer uint32 salt,
see repro.core.rng), the same scheme as the LABOR family — so both
samplers trace inside the fused one-program train step and the
standalone path stays bit-identical to the fused path.

The single-host path keeps every per-vertex quantity on the CANDIDATE
frontier — the deduplicated sources of the expanded neighborhood
(``repro.ops.frontier.hash_dedup``) — so column norms, the water-fill,
and the inverse-CDF draws (``masked_cdf_draw``) are all cap-bounded:
no dense-V probability vector, no dense-V CDF. Only the distributed
partition-local mode (``axis_name``) keeps the dense layout, because
its cross-partition ``psum`` needs one aligned per-vertex vector on
every device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import rng as rng_lib
from repro.core.interface import (LayerCaps, SampledLayer, Sampler,
                                  SamplerSpec, build_block)
from repro.graph.csr import Graph, expand_seed_edges
from repro.ops import frontier as frontier_ops
from repro.runtime import spans


def _edge_contrib(exp: dict) -> jax.Array:
    """Per expanded edge: A_ts^2 / d_s^2 (the column-norm term each
    edge contributes to its source's p_t)."""
    slot, mask, deg = exp["seed_slot"], exp["mask"], exp["deg"]
    degf = jnp.maximum(deg.astype(jnp.float32), 1.0)
    contrib = jnp.where(mask, 1.0 / degf[jnp.clip(slot, 0, deg.shape[0] - 1)] ** 2, 0.0)
    if exp.get("edge_weight") is not None:
        contrib = contrib * jnp.where(mask, exp["edge_weight"] ** 2, 0.0)
    return contrib


def _layer_probs(graph: Graph, exp: dict, num_vertices: int) -> jax.Array:
    """p_t ∝ sum_{s} A_ts^2 / d_s^2 over dense V (0 outside N(S)) —
    the distributed layout (one aligned vector per device for the
    cross-partition psum) and the oracle the candidate-frontier path
    is tested against."""
    src, mask = exp["src"], exp["mask"]
    contrib = _edge_contrib(exp)
    p = jnp.zeros((num_vertices,), jnp.float32).at[jnp.where(mask, src, 0)].add(
        jnp.where(mask, contrib, 0.0), mode="drop"
    )
    return p


def _waterfill_lambda(p: jax.Array, n: int, iters: int = 50) -> jax.Array:
    """Find lam with sum min(1, lam p) = n (monotone -> bisection)."""
    total = jnp.maximum(jnp.sum(p), 1e-20)
    lo = jnp.float32(0.0)
    hi = jnp.float32(1.0)

    # grow hi until feasible or all clipped
    def grow(state):
        lo, hi = state
        return lo, hi * 4.0

    def grow_cond(state):
        _, hi = state
        return (jnp.sum(jnp.minimum(1.0, hi * p / total * n)) < n * 0.999) & (hi < 1e12)

    lo, hi = jax.lax.while_loop(grow_cond, grow, (lo, jnp.float32(1.0)))

    def body(_, state):
        lo, hi = state
        mid = 0.5 * (lo + hi)
        val = jnp.sum(jnp.minimum(1.0, mid * p / total * n))
        return jnp.where(val < n, mid, lo), jnp.where(val < n, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi) / total * n


def sample_layer_ladies(
    graph: Graph,
    seeds: jax.Array,
    salt: jax.Array,
    n: int,
    caps: LayerCaps,
    poisson: bool = False,
    seed_rows: Optional[jax.Array] = None,
    num_vertices: Optional[int] = None,
    axis_name=None,
    dense: Optional[bool] = None,
) -> SampledLayer:
    """One LADIES/PLADIES layer from a uint32 ``salt`` (fully traceable).

    Per-vertex state (column norms p_t, water-filled pi, the CDF) lives
    on the candidate frontier — the deduplicated expanded sources, a
    cap-bounded buffer — and the random draws hash GLOBAL vertex ids,
    so the sampled set is the same one the retained dense layout
    (``dense=True``) produces.

    In the distributed engine's partition-local mode (``seed_rows``/
    ``num_vertices``/``axis_name``, see ``Sampler.sample_layer_partitioned``)
    each partition contributes its owned seeds' column-norm terms and a
    cross-partition ``psum`` completes the batch-global p_t; that psum
    needs one aligned per-vertex vector on every device, so the
    distributed mode keeps the dense layout."""
    if dense is None:
        dense = axis_name is not None
    exp = expand_seed_edges(graph, seeds, caps.expand_cap,
                            seed_rows=seed_rows)
    src, slot, mask = exp["src"], exp["seed_slot"], exp["mask"]
    safe_src = jnp.where(mask, src, 0)

    if dense:
        V = num_vertices if num_vertices is not None else graph.num_vertices
        p = _layer_probs(graph, exp, V)
        if axis_name is not None:
            p = jax.lax.psum(p, axis_name)
        ids = jnp.arange(V)
        valid = p > 0
        eidx = safe_src          # per-edge index into the dense layout
    else:
        # candidate frontier: every distinct expanded source, ascending
        # (cap-bounded by the expand buffer — never dense over V)
        E = src.shape[0]
        dd = frontier_ops.hash_dedup(src, mask, None, E)
        cands, cidx = dd.new, jnp.where(mask, dd.slots, 0)
        contrib = _edge_contrib(exp)
        p = jnp.zeros((E + 1,), jnp.float32).at[
            jnp.where(mask, cidx, E)].add(
            jnp.where(mask, contrib, 0.0), mode="drop")[:E]
        ids = jnp.where(cands >= 0, cands, -1)
        valid = (cands >= 0) & (p > 0)
        eidx = cidx

    if poisson:
        lam = _waterfill_lambda(p, n)
        pi = jnp.minimum(1.0, lam * p)                      # sum pi = n
        r = rng_lib.hash_uniform(salt, ids)
        member = (r < pi) & valid
        inv_pi = jnp.where(member, 1.0 / jnp.maximum(pi, 1e-20), 0.0)
    else:
        # n draws with replacement via inverse CDF, deduplicated. The
        # CDF is normalized by its own final value and the draws are
        # clipped, so float32 accumulation error can never index out of
        # range (masked_cdf_draw), whatever the weight spread.
        total = jnp.maximum(jnp.sum(jnp.where(valid, p, 0.0)), 1e-20)
        u = rng_lib.hash_uniform(salt, jnp.arange(n))
        draws = frontier_ops.masked_cdf_draw(p, valid, u)
        member = jnp.zeros(p.shape, jnp.bool_).at[draws].set(True)
        member = member & valid
        # reference-impl weights: 1/(n * p_t) as if HT, then row-normalize
        inv_pi = jnp.where(member, total / jnp.maximum(p * n, 1e-20), 0.0)

    # block edges: every edge t->s with t sampled
    include = mask & member[eidx]
    return build_block(seeds, exp, include, inv_pi[eidx], caps)


@dataclasses.dataclass(frozen=True)
class LadiesConfig:
    layer_sizes: Sequence[int]   # n per layer, outermost first
    poisson: bool = False        # True => PLADIES


@dataclasses.dataclass(frozen=True)
class LadiesSampler(Sampler):
    """LADIES/PLADIES on the :class:`~repro.core.interface.Sampler`
    protocol — salt-based, so it traces inside fused programs exactly
    like the LABOR family."""
    config: LadiesConfig = None

    @classmethod
    def build(cls, config: LadiesConfig, caps: Sequence[LayerCaps],
              name: Optional[str] = None) -> "LadiesSampler":
        if len(caps) != len(config.layer_sizes):
            raise ValueError("need one LayerCaps per layer size")
        config = dataclasses.replace(config,
                                     layer_sizes=tuple(config.layer_sizes))
        spec = SamplerSpec(name=name or ("pladies" if config.poisson
                                         else "ladies"),
                           budgets=config.layer_sizes, caps=tuple(caps))
        return cls(spec=spec, config=config)

    def with_caps(self, caps: Sequence[LayerCaps]) -> "LadiesSampler":
        if len(caps) != len(self.config.layer_sizes):
            raise ValueError("need one LayerCaps per layer size")
        return super().with_caps(caps)

    def sample(self, graph: Graph, seeds: jax.Array,
               salts: jax.Array) -> list[SampledLayer]:
        blocks = []
        cur = seeds
        for layer, (n, caps) in enumerate(zip(self.config.layer_sizes,
                                              self.spec.caps)):
            with jax.named_scope(spans.layer(layer)):
                blk = sample_layer_ladies(graph, cur, salts[layer], n, caps,
                                          poisson=self.config.poisson)
            blocks.append(blk)
            cur = blk.next_seeds
        return blocks

    def sample_layer_partitioned(self, graph: Graph, seeds: jax.Array,
                                 salt: jax.Array, layer: int, *,
                                 seed_rows: jax.Array, num_vertices: int,
                                 axis_name=None) -> SampledLayer:
        return sample_layer_ladies(
            graph, seeds, salt, self.config.layer_sizes[layer],
            self.spec.caps[layer], poisson=self.config.poisson,
            seed_rows=seed_rows, num_vertices=num_vertices,
            axis_name=axis_name)


def ladies_sampler(layer_sizes, caps):
    return LadiesSampler.build(LadiesConfig(tuple(layer_sizes), poisson=False),
                               caps)


def pladies_sampler(layer_sizes, caps):
    return LadiesSampler.build(LadiesConfig(tuple(layer_sizes), poisson=True),
                               caps)
