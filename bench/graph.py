"""The benchmark's graphs: a degree-corrected stochastic block model at a
published dataset's statistics, built on the host from the
configuration's own ``graph_seed`` and cached inside the checkout.

The public graphs (ogbn-products, Flickr) cannot be downloaded where the
benchmark runs, so each configuration names the published vertex count,
average in-degree, feature width, class count and train share, and this
module generates a graph with those numbers:

* in-degrees from a Pareto tail mixed into a uniform body, rescaled to
  the published mean and clipped at ``V ** 0.33 * avg`` (``skew`` sets
  the tail);
* one community per vertex (the label), drawn from Dirichlet(0.6)
  shares over the classes;
* each in-edge's source drawn, by popularity (degree + 1), from the
  destination's own community with probability ``in_community`` and
  from the whole graph otherwise: one sort of the vertices by community
  and one search against per-community CDFs, with no loop over classes;
* duplicate edges removed, giving an in-neighbourhood CSR (sources of
  the edges into ``s`` are ``indices[indptr[s]:indptr[s+1]]``).

Features (community centroid plus Gaussian noise) are made on the
device from the same seed by :func:`device_features`.

The CSR, labels and train split are cached under ``bench/.cache`` keyed
by a hash of the graph section, so every run of a cell after the first
loads the same graph, whatever its ``--seed``.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, NamedTuple

import numpy as np

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".cache", "graphs")


class HostGraph(NamedTuple):
    indptr: np.ndarray     # int32[V + 1]
    indices: np.ndarray    # int32[E]
    labels: np.ndarray     # int32[V]
    train_idx: np.ndarray  # int32[n_train]

    @property
    def num_vertices(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[0]

    @property
    def max_in_degree(self) -> int:
        return int(np.max(np.diff(self.indptr)))


def in_degrees(spec: Dict, rng: np.random.Generator) -> np.ndarray:
    """Integer in-degrees with the published mean and a Pareto tail."""
    n, avg, skew = spec["num_vertices"], spec["avg_degree"], spec["skew"]
    alpha = 3.5 - 2.3 * skew
    raw = rng.pareto(alpha, size=n) + 1.0
    deg = raw / raw.mean() * avg
    d_max = int(min(n - 1, max(4 * avg, avg * n ** 0.33)))
    deg = np.clip(deg, 1, d_max)
    deg *= avg / deg.mean()
    deg = np.clip(deg, 1, d_max)
    ideg = np.floor(deg).astype(np.int64)
    ideg += rng.random(n) < deg - ideg
    return ideg


def _draw(cdf: np.ndarray, targets: np.ndarray) -> np.ndarray:
    idx = np.searchsorted(cdf, targets, side="right")
    return np.minimum(idx, cdf.shape[0] - 1)


def build(spec: Dict) -> HostGraph:
    """Generate the graph of a configuration's ``graph`` section."""
    rng = np.random.default_rng(spec["graph_seed"])
    n, ncls = spec["num_vertices"], spec["num_classes"]
    deg = in_degrees(spec, rng)
    comm = rng.choice(ncls, size=n,
                      p=rng.dirichlet(np.full(ncls, spec["community_alpha"])))
    pop = deg.astype(np.float64) + 1.0
    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    local = rng.random(dst.shape[0]) < spec["in_community"]
    u = rng.random(dst.shape[0])

    # global draws: popularity-weighted over all vertices
    glob = np.cumsum(pop)
    src = _draw(glob / glob[-1], u)
    # community draws: vertices sorted by community, each community's
    # popularity CDF normalised to (c, c + 1], one search for all edges
    order = np.argsort(comm, kind="stable")
    cpop = pop[order]
    csum = np.cumsum(cpop)
    starts = np.searchsorted(comm[order], np.arange(ncls))
    ends = np.append(starts[1:], n)
    before = np.where(starts > 0, csum[np.maximum(starts - 1, 0)], 0.0)
    total = np.where(ends > starts, csum[np.maximum(ends - 1, 0)] - before, 1.0)
    c_sorted = comm[order]
    cdf = c_sorted + (csum - before[c_sorted]) / total[c_sorted]
    cdf[ends[ends > starts] - 1] = np.arange(ncls)[ends > starts] + 1.0
    targets = comm[dst[local]] + u[local]
    src[local] = order[_draw(cdf, targets)]

    key = np.unique(dst * n + src)
    indices = (key % n).astype(np.int32)
    counts = np.bincount(key // n, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])

    split = np.random.default_rng([spec["graph_seed"], 1]).permutation(n)
    n_train = int(spec["train_frac"] * n)
    return HostGraph(indptr=indptr.astype(np.int32), indices=indices,
                     labels=comm.astype(np.int32),
                     train_idx=np.sort(split[:n_train]).astype(np.int32))


def spec_hash(spec: Dict) -> str:
    blob = json.dumps(spec, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def load_or_build(name: str, spec: Dict, cache_dir: str = CACHE_DIR
                  ) -> HostGraph:
    """The cached graph of ``spec``, built and written on first use."""
    path = os.path.join(cache_dir, f"{name}-{spec_hash(spec)}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return HostGraph(**{f: z[f] for f in HostGraph._fields})
    g = build(spec)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, **g._asdict())
    os.replace(tmp, path)
    return g


def device_features(spec: Dict, labels):
    """float32[V, F] features on the default device: the label's
    centroid plus N(0, noise^2) per entry, from ``graph_seed``."""
    import jax
    import jax.numpy as jnp

    n, nfeat = spec["num_vertices"], spec["num_features"]
    ncls, noise = spec["num_classes"], spec["feature_noise"]

    @jax.jit
    def make(key, labels):
        kc, kn = jax.random.split(key)
        centroids = jax.random.normal(kc, (ncls, nfeat), jnp.float32)
        return centroids[labels] + noise * jax.random.normal(
            kn, (n, nfeat), jnp.float32)

    return make(jax.random.key(spec["graph_seed"]), labels)
