"""Plain reference of layer-wise neighbour sampling on the host.

What every sampler of the paper shares, written from its description
(Balin & Catalyurek 2023, section 3.2 and appendix A.3) in NumPy:

* the random variates are a stateless 32-bit hash of (salt, vertex) for
  LABOR's shared r_t, and of (salt, source, destination) for the
  per-edge r_ts of neighbour sampling; the top 24 bits give a uniform
  in [0, 1);
* the per-layer salts are folded from the step's PRNG key: layer ``l``
  takes ``fold_in(key, l)`` and mixes its two key words;
* a layer expands every in-edge of its seeds, decides inclusion per
  edge, and hands on ``[seeds ; new sampled sources in ascending id]``
  as the next layer's seeds;
* each included edge carries its Hajek weight
  ``(1 / p_ts) / sum_t' (1 / p_t's)``.

A sampler module (``samplers/<name>.py``) supplies the inclusion rule.
Where a rule compares a variate against a float32 quotient, the
quotient is computed with ``jax.numpy`` on the default device, so that
the division rounds as it does on the chip under test.
"""
from __future__ import annotations

import importlib.util
import os
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_M1, _M2, _M3 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35), \
    np.uint32(0x27D4EB2F)


def _mix(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    return h ^ (h >> np.uint32(16))


def _to_unit(h: np.ndarray) -> np.ndarray:
    return (h >> np.uint32(8)).astype(np.float32) * np.float32(1.0 / (1 << 24))


def vertex_uniform(salt: np.uint32, ids: np.ndarray) -> np.ndarray:
    """r_t: one uniform per vertex, shared by every seed that sees it."""
    with np.errstate(over="ignore"):
        h = ids.astype(np.int64).astype(np.uint32)
        h = _mix(h ^ (salt * _M3))
        return _to_unit(_mix(h + salt))


def edge_uniform(salt: np.uint32, src: np.ndarray, dst: np.ndarray
                 ) -> np.ndarray:
    """r_ts: one uniform per (source, destination) edge."""
    with np.errstate(over="ignore"):
        s = src.astype(np.int64).astype(np.uint32)
        d = dst.astype(np.int64).astype(np.uint32)
        h = _mix(s ^ (salt * _M3))
        return _to_unit(_mix(h ^ (d * _M1) ^ salt))


def layer_salts(key, num_layers: int) -> List[np.uint32]:
    """Per-layer uint32 salts of a step's PRNG key."""
    salts = []
    for layer in range(num_layers):
        data = np.asarray(jax.random.key_data(
            jax.random.fold_in(key, layer))).reshape(-1).astype(np.uint32)
        with np.errstate(over="ignore"):
            salts.append(_mix(np.uint32(data[0]) ^ _mix(np.uint32(data[-1]))))
    return salts


class Expanded(NamedTuple):
    seg: np.ndarray   # int64[E] index of each edge's seed
    src: np.ndarray   # int32[E] source vertex
    deg: np.ndarray   # int64[S] in-degree of each seed
    pos: np.ndarray   # int64[E] position of the edge in its seed's row


def expand(indptr: np.ndarray, indices: np.ndarray, seeds: np.ndarray
           ) -> Expanded:
    """Every in-edge of every seed, seed by seed, in CSR order."""
    lo = indptr[seeds].astype(np.int64)
    deg = indptr[seeds + 1].astype(np.int64) - lo
    seg = np.repeat(np.arange(seeds.shape[0]), deg)
    first = np.cumsum(deg) - deg
    pos = np.arange(seg.shape[0]) - first[seg]
    return Expanded(seg=seg, src=indices[lo[seg] + pos], deg=deg, pos=pos)


def elementwise_on_device(fn, *arrays: np.ndarray) -> np.ndarray:
    """The jitted ``fn`` applied on the default device to equal-length
    float32 arrays, padded to a power of two so that one program serves
    every batch of a cell."""
    n = arrays[0].shape[0]
    size = max(8, 1 << (max(n, 1) - 1).bit_length())
    padded = [np.pad(a.astype(np.float32), (0, size - n), constant_values=1)
              for a in arrays]
    return np.asarray(fn(*padded))[:n]


@jax.jit
def _rate(k, d):
    return jnp.where(d > 0, jnp.where(k >= d, 1.0, k / jnp.maximum(d, 1.0)),
                     0.0)


def fanout_rate(k: int, deg: np.ndarray) -> np.ndarray:
    """c_s = min(1, k / d_s) in float32, divided on the default device."""
    return elementwise_on_device(_rate, np.full(deg.shape, k, np.float32),
                                 deg.astype(np.float32))


class Block(NamedTuple):
    """One sampled layer in plain arrays (real entries only)."""
    seeds: np.ndarray     # int32[S] destination vertices
    next: np.ndarray      # int32[T] seeds first, then new sources
    dst: np.ndarray       # int64[E] index into seeds
    src: np.ndarray       # int64[E] index into next
    weight: np.ndarray    # float32[E] Hajek weights


def finish_layer(seeds: np.ndarray, exp: Expanded, include: np.ndarray,
                 inv_p: np.ndarray) -> Block:
    """Hajek weights, the next seed list and the edges' slots."""
    seg, src = exp.seg[include], exp.src[include]
    inv_p = inv_p[include].astype(np.float64)
    total = np.bincount(seg, weights=inv_p, minlength=seeds.shape[0])
    weight = (inv_p / total[seg]).astype(np.float32)
    new = np.setdiff1d(np.unique(src), seeds, assume_unique=False)
    nxt = np.concatenate([seeds, new.astype(np.int32)])
    order = np.argsort(nxt, kind="stable")
    slot = order[np.searchsorted(nxt[order], src)]
    return Block(seeds=seeds, next=nxt, dst=seg, src=slot, weight=weight)


_SAMPLERS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "samplers")


def load_sampler(name: str):
    """The module ``samplers/<name>.py``: ``include(salt, k, seeds, exp)
    -> (include bool[E], inv_p float32[E])``."""
    path = os.path.join(_SAMPLERS, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no reference sampler {name!r} under {_SAMPLERS}")
    spec = importlib.util.spec_from_file_location(f"ref_sampler_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(sampler, indptr: np.ndarray, indices: np.ndarray,
           batch: np.ndarray, key, fanouts) -> List[Block]:
    """Blocks of one batch, outermost (the batch's own layer) first."""
    blocks = []
    seeds = batch.astype(np.int32)
    for salt, k in zip(layer_salts(key, len(fanouts)), fanouts):
        exp = expand(indptr, indices, seeds)
        include, inv_p = sampler.include(salt, int(k), seeds, exp)
        blk = finish_layer(seeds, exp, include, inv_p)
        blocks.append(blk)
        seeds = blk.next
    return blocks
