"""Grid-parallel Pallas kernels for the frontier primitives.

Every primitive of the family is built from two blocks: a lane-dense
bitonic sort of int32 word tuples (:func:`sort_words`, in Pallas) and a
segmented forward fill (:func:`fill_forward`), which carries each
sorted run's head value over its run; cap-sized XLA elementwise ops and
cumulative sums sit around them. Data-dependent gathers over cap-sized
buffers cost about 20 ns an element on a TPU v5e (three over 2^24
elements were a third of a products training step), so a value that
has to reach the rest of its run travels by the fill, in streaming
passes, not by a gather:

  * ``hash_dedup``     — sort ``[seeds ; values]`` by (value, position):
                         runs of equal values are adjacent, a seed sorts
                         first in its run, so run heads give the unique
                         new values and every run's slot in
                         ``[seeds ; new]``, which the fill carries over
                         the run; a second sort keyed by position puts
                         the slots back in edge order.
  * ``compact``        — sort flag-tagged positions (set flags first, in
                         arrival order) and keep the head.
  * ``compact_perm``   — sort (key, index) — packed into one word when
                         the key range allows — and keep the indices.
  * ``segment_select`` — sort (segment, key bits): each segment's
                         take-th smallest key sits at a known position;
                         inclusion then replays the reference's
                         threshold / tie-rank formula in arrival order.
  * ``masked_cdf_draw`` — sort draws together with the CDF: a draw's
                         rank among the CDF entries is its inverse-CDF
                         index; a sort keyed by position restores draw
                         order.

The sort works on a ``(N // lanes, lanes)`` view of each word (lanes =
128 on TPU), in blocks of ``tile`` elements that live in VMEM:

  1. one kernel sorts every block (all bitonic stages whose
     compare-exchange distance is below ``tile``), alternating
     direction by block as the bitonic network requires;
  2. each later stage runs its compare-exchange steps at distances
     >= ``tile`` as grid passes over HBM — grid step ``b`` reads its
     own block and partner block ``b ^ (d // tile)`` and keeps the min
     or the max — then finishes the distances below ``tile`` inside
     each block.

Inside a block, a compare-exchange at distance ``d`` fetches partners
with two ``pltpu.roll`` rotations (lanes when ``d < lanes``, sublanes
otherwise) and picks the right one by rotating the index vector the
same way, so the kernel does not depend on the rotation's direction
convention. The pass kernels take the stage and distance as scalar
prefetch operands and run under ``lax.fori_loop``, so each sort
compiles three kernels whatever its length.

Bit-compatibility: identical to kernels/frontier/ref.py on every
contractual output (see ref.py's notes). The sort never drops or
duplicates a word tuple, including equal keys: at equal keys both
sides of a compare-exchange keep their own element.

``tile`` is the knob the autotune cache (repro/ops/autotune.py) tunes.
Compiled kernels need ``tile >= 1024`` (one (8, 128) int32 vreg tile
per block); interpret mode accepts any power of two, which is how the
tests drive multi-block passes on small inputs.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.frontier.ref import DedupResult, normalized_cdf

_INT_MAX = 2**31 - 1

DEFAULT_TILE = 8192
LANES = 128
_MIN_COMPILED_TILE = 8 * LANES
_MIN_TILE = 8  # keeps padded dims off the jaxpr gate's prime V window


def _pow2_at_least(x: int) -> int:
    p = _MIN_TILE
    while p < x:
        p *= 2
    return p


def _iota(n: int):
    return jnp.arange(n, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# the sort: in-block bitonic stages + HBM grid passes
# ---------------------------------------------------------------------------

def _exchange(words, partner, take_min, n_keys: int):
    """Keep, per element, the lexicographic min (``take_min``) or max
    of itself and its partner over the first ``n_keys`` words; the
    remaining words ride along. Equal keys keep their own element."""
    p_lt = partner[0] < words[0]
    eq = partner[0] == words[0]
    for i in range(1, n_keys):
        p_lt = p_lt | (eq & (partner[i] < words[i]))
        eq = eq & (partner[i] == words[i])
    use_p = (take_min & p_lt) | (~take_min & ~(p_lt | eq))
    return [jnp.where(use_p, p, w) for w, p in zip(words, partner)]


def _block_step(words, local, d: int, lanes: int, rows: int, desc,
                n_keys: int):
    """Compare-exchange at in-block distance ``d`` (a static power of
    two below the block size); ``desc`` marks descending elements."""
    if d < lanes:
        axis, s, size = 1, d, lanes
    else:
        axis, s, size = 0, d // lanes, rows
    from_a = pltpu.roll(local, s, axis) == (local ^ d)
    partner = [jnp.where(from_a, pltpu.roll(w, s, axis),
                         pltpu.roll(w, size - s, axis)) for w in words]
    take_min = ((local & d) == 0) != desc
    return _exchange(words, partner, take_min, n_keys)


def _local_index(rows: int, lanes: int):
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    return r * lanes + c


def _sort_blocks_kernel(*refs, n_words: int, n_keys: int):
    """Bitonic stages 0..log2(tile)-1 of the global network, in one
    block: afterwards each block is sorted, ascending or descending by
    the direction bit of its global index."""
    ins, outs = refs[:n_words], refs[n_words:]
    rows, lanes = ins[0].shape
    tile = rows * lanes
    local = _local_index(rows, lanes)
    gidx = pl.program_id(0) * tile + local
    words = [r[...] for r in ins]
    for st in range(tile.bit_length() - 1):
        desc = ((gidx >> (st + 1)) & 1) != 0
        for sub in range(st, -1, -1):
            words = _block_step(words, local, 1 << sub, lanes, rows, desc,
                                n_keys)
    for r, w in zip(outs, words):
        r[...] = w


def _merge_blocks_kernel(st_ref, *refs, n_words: int, n_keys: int):
    """The in-block tail of stage ``st`` (distances below the block)."""
    ins, outs = refs[:n_words], refs[n_words:]
    rows, lanes = ins[0].shape
    tile = rows * lanes
    local = _local_index(rows, lanes)
    desc = (((pl.program_id(0) * tile + local) >> (st_ref[0] + 1)) & 1) != 0
    words = [r[...] for r in ins]
    for sub in range(tile.bit_length() - 2, -1, -1):
        words = _block_step(words, local, 1 << sub, lanes, rows, desc,
                            n_keys)
    for r, w in zip(outs, words):
        r[...] = w


def _cross_blocks_kernel(st_ref, db_ref, *refs, n_words: int, n_keys: int):
    """One compare-exchange step of stage ``st`` at distance
    ``db_ref[0]`` blocks: this block against its partner block."""
    own, partner = refs[:2 * n_words:2], refs[1:2 * n_words:2]
    outs = refs[2 * n_words:]
    rows, lanes = own[0].shape
    b = pl.program_id(0)
    # take the min iff this block is the low one of an ascending pair
    # or the high one of a descending pair (scalar, as an int vector:
    # Mosaic cannot broadcast a scalar bool)
    low = jnp.where((b & db_ref[0]) == 0, 1, 0)
    desc = ((b * (rows * lanes)) >> (st_ref[0] + 1)) & 1
    take_min = jnp.full((rows, lanes), low ^ desc, jnp.int32) != 0
    words = _exchange([r[...] for r in own], [r[...] for r in partner],
                      take_min, n_keys)
    for r, w in zip(outs, words):
        r[...] = w


def _block_shape(n: int, tile: int, interpret: bool) -> Tuple[int, int]:
    if not interpret:
        tile = max(tile, _MIN_COMPILED_TILE)
    tile = min(_pow2_at_least(tile), n)
    lanes = min(LANES, tile)
    return tile // lanes, lanes


def sort_words(words: Sequence[jax.Array], n_keys: int,
               tile: int = DEFAULT_TILE,
               interpret: bool = False) -> list:
    """Sort int32 word tuples ascending, lexicographically over the
    first ``n_keys`` words; the remaining words are carried along.
    Every word has the same power-of-two length (>= 8)."""
    n = words[0].shape[0]
    if n & (n - 1) or n < _MIN_TILE:
        raise ValueError(f"sort_words needs a power-of-two length >= "
                         f"{_MIN_TILE}, got {n}")
    rows, lanes = _block_shape(n, tile, interpret)
    tile = rows * lanes
    nw = len(words)
    nb = n // tile
    x = [w.astype(jnp.int32).reshape(n // lanes, lanes) for w in words]
    shape = [jax.ShapeDtypeStruct(x[0].shape, jnp.int32)] * nw
    own = pl.BlockSpec((rows, lanes), lambda b, *_: (b, 0))
    x = pl.pallas_call(
        functools.partial(_sort_blocks_kernel, n_words=nw, n_keys=n_keys),
        grid=(nb,), in_specs=[own] * nw, out_specs=[own] * nw,
        out_shape=shape, interpret=interpret, name="frontier_sort_blocks",
    )(*x)
    log_t, log_n = tile.bit_length() - 1, n.bit_length() - 1
    if log_n == log_t:
        return [w.reshape(n) for w in x]

    partner = pl.BlockSpec((rows, lanes),
                           lambda b, st, db: (jnp.bitwise_xor(b, db[0]), 0))
    cross = pl.pallas_call(
        functools.partial(_cross_blocks_kernel, n_words=nw, n_keys=n_keys),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb,),
            in_specs=[own, partner] * nw, out_specs=[own] * nw),
        out_shape=shape, interpret=interpret, name="frontier_sort_cross")
    merge = pl.pallas_call(
        functools.partial(_merge_blocks_kernel, n_words=nw, n_keys=n_keys),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb,),
            in_specs=[own] * nw, out_specs=[own] * nw),
        out_shape=shape, interpret=interpret, name="frontier_sort_merge")

    def stage(st, x):
        st_arr = jnp.reshape(st, (1,)).astype(jnp.int32)

        def step(j, x):
            db = jnp.left_shift(jnp.int32(1), st - j - log_t)
            pairs = [a for w in x for a in (w, w)]
            return list(cross(st_arr, jnp.reshape(db, (1,)), *pairs))

        x = jax.lax.fori_loop(0, st - log_t + 1, step, x)
        return list(merge(st_arr, *x))

    x = jax.lax.fori_loop(log_t, log_n, stage, list(x))
    return [w.reshape(n) for w in x]


def _pad(x, n: int, fill):
    return jnp.pad(x.astype(jnp.int32), (0, n - x.shape[0]),
                   constant_values=fill)


def _head(x, m: int, fill):
    """``x[:m]``, padded with ``fill`` when ``x`` is shorter."""
    return x[:m] if x.shape[0] >= m else _pad(x, m, fill)


def _exclusive_cumsum(b):
    b = b.astype(jnp.int32)
    return jnp.cumsum(b) - b


def fill_forward(head, x):
    """Each position takes the value of the nearest head at or before
    it; position 0 is a head. ``x`` is an array or a tuple of arrays of
    ``head``'s length, filled in the same passes."""
    # a doubling scan, log2(n) streaming shift-and-select passes:
    # jax.lax.associative_scan's strided recursion takes the TPU
    # compiler minutes from n = 2**18 on
    n = head.shape[0]
    d = 1
    while d < n:
        # combine each position with the one d before it:
        # (fa | fb, where(fb, vb, va)), a = position - d, b = position
        x = jax.tree.map(
            lambda v: jnp.where(head, v, jnp.pad(v[:-d], (d, 0))), x)
        head = head | jnp.pad(head[:-d], (d, 0))
        d *= 2
    return x


# ---------------------------------------------------------------------------
# hash_dedup
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("new_cap", "tile", "interpret"))
def _dedup(values, mask, seeds, new_cap: int, tile: int, interpret: bool):
    e, s = values.shape[0], seeds.shape[0]
    n = _pow2_at_least(s + e)
    valid = mask & (values >= 0)
    key = _pad(jnp.concatenate([jnp.where(seeds >= 0, seeds, _INT_MAX),
                                jnp.where(valid, values, _INT_MAX)]),
               n, _INT_MAX)
    v, pos = sort_words([key, _iota(n)], 2, tile, interpret)
    live = v != _INT_MAX
    head = jnp.concatenate([jnp.ones((1,), bool), v[1:] != v[:-1]])
    # a seed has the smallest position of its run, so it is the head
    seed_at = pos < s
    new_head = head & live & ~seed_at
    num_new = jnp.sum(new_head.astype(jnp.int32))
    # rank only steps at a new run's head, so it is constant over runs;
    # the slot is right at every head and filled forward over its run
    rank = jnp.cumsum(new_head.astype(jnp.int32)) - 1
    at_head = jnp.where(seed_at, pos,
                        jnp.where(rank < new_cap, s + rank, -1))
    slot = jnp.where(live, fill_forward(head, at_head), -1)
    (newv,) = sort_words([jnp.where(new_head, v, _INT_MAX)], 1, tile,
                         interpret)
    new = jnp.where(_iota(new_cap) < num_new, _head(newv, new_cap, -1), -1)
    _, by_pos = sort_words([pos, slot], 1, tile, interpret)
    return new, by_pos[s:s + e], num_new


def hash_dedup_block_parallel(values: jax.Array, mask: jax.Array,
                              seeds: Optional[jax.Array], new_cap: int,
                              tile: int = DEFAULT_TILE,
                              interpret: bool = False) -> DedupResult:
    """Sort-based hash_dedup (contract of ref.hash_dedup, bit-exact)."""
    seeds_in = (jnp.zeros((0,), jnp.int32) if seeds is None
                else seeds.astype(jnp.int32))
    new, slots, num_new = _dedup(values.astype(jnp.int32), mask, seeds_in,
                                 new_cap, tile, interpret)
    return DedupResult(new=new, slots=slots, num_new=num_new,
                       overflow=num_new > new_cap)


# ---------------------------------------------------------------------------
# compact / compact_perm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cap", "tile", "interpret"))
def compact_block_parallel(flags: jax.Array, cap: int,
                           tile: int = DEFAULT_TILE,
                           interpret: bool = False):
    """Order-preserving stream compaction (contract of ref.compact)."""
    e = flags.shape[0]
    n = _pow2_at_least(e)
    f = jnp.pad(flags.astype(bool), (0, n - e))
    (k,) = sort_words([jnp.where(f, _iota(n), n + _iota(n))], 1, tile,
                      interpret)
    num = jnp.sum(flags.astype(jnp.int32))
    sel = jnp.where(_iota(cap) < num, _head(k, cap, 0), 0)
    emask = jnp.arange(cap) < jnp.minimum(num, cap)
    return sel, emask, num


@functools.partial(jax.jit, static_argnames=("num_keys", "tile",
                                             "interpret"))
def compact_perm_block_parallel(keys: jax.Array, valid: jax.Array,
                                num_keys: int, tile: int = DEFAULT_TILE,
                                interpret: bool = False) -> jax.Array:
    """Stable ascending-key permutation (contract of ref.compact_perm).
    (key, index) pairs are unique, so the sort's order is the stable
    one; they pack into one word when the key range allows."""
    e = keys.shape[0]
    n = _pow2_at_least(e)
    eff = _pad(jnp.where(valid, jnp.clip(keys, -1, num_keys - 1),
                         num_keys) + 1, n, num_keys + 1)
    if (num_keys + 2) * n < 2**31:
        (s,) = sort_words([eff * n + _iota(n)], 1, tile, interpret)
        return (s & (n - 1))[:e]
    # padded entries carry index >= E and sort after every real entry
    _, idx = sort_words([eff, _iota(n)], 2, tile, interpret)
    return idx[:e]


# ---------------------------------------------------------------------------
# segment_select
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_seeds", "tile",
                                             "interpret"))
def segment_select_block_parallel(keys: jax.Array, slot: jax.Array,
                                  mask: jax.Array, seg_start: jax.Array,
                                  take: jax.Array, num_seeds: int,
                                  tile: int = DEFAULT_TILE,
                                  interpret: bool = False) -> jax.Array:
    """Per-segment smallest-``take`` selection (ref.segment_select
    contract): one (segment, key) sort yields every segment's
    take-th-smallest key by position (segments stay contiguous and
    masked entries sit on the tail), then the reference's threshold /
    tie-budget formula runs in arrival order — bit-identical ties."""
    e = keys.shape[0]
    n = _pow2_at_least(e)
    s = num_seeds
    u = jax.lax.bitcast_convert_type(keys.astype(jnp.float32), jnp.int32)
    maskv = mask & (slot >= 0)
    sl = jnp.where(maskv, slot, s)
    _, us = sort_words([_pad(sl, n, s), _pad(u, n, 0)], 2, tile, interpret)

    nv = jnp.sum(maskv.astype(jnp.int32))
    starts = jnp.clip(seg_start, 0, e).astype(jnp.int32)
    ends = jnp.concatenate([starts[1:], jnp.full((1,), e, jnp.int32)])
    present = jnp.clip(jnp.minimum(ends, nv) - starts, 0, None)
    # the take-th smallest key of segment s sits at its sorted start +
    # take - 1; a segment whose buffer holds fewer than take edges
    # (expand truncation, already overflow-flagged) saturates the
    # threshold and includes everything present — same as the bisection
    at = jnp.clip(jnp.minimum(starts, nv) + take - 1, 0, n - 1)
    thresh = jnp.where(take == 0, 0,
                       jnp.where(take <= present, us[at], _INT_MAX))
    cslot = jnp.clip(slot, 0, s - 1)
    te = thresh[cslot]
    lt = maskv & (u < te)
    ex = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(lt.astype(jnp.int32))])
    cnt_lt = ex[ends] - ex[starts]
    eq = maskv & (u == te)
    excl = _exclusive_cumsum(eq)
    base = excl[jnp.clip(seg_start, 0, e - 1)]
    eq_rank = excl - base[cslot]
    budget = (take - cnt_lt)[cslot]
    return lt | (eq & (eq_rank < budget))


# ---------------------------------------------------------------------------
# masked_cdf_draw
# ---------------------------------------------------------------------------

def _float_key(x):
    """int32 view ordering like float32 (-0.0 folded onto +0.0)."""
    b = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, 0.0, x).astype(jnp.float32), jnp.int32)
    return b ^ ((b >> 31) & _INT_MAX)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def masked_cdf_draw_block_parallel(p: jax.Array, valid: jax.Array,
                                   u: jax.Array, tile: int = DEFAULT_TILE,
                                   interpret: bool = False) -> jax.Array:
    """Inverse-CDF draws (ref.masked_cdf_draw contract); the CDF comes
    from the shared ``normalized_cdf`` so draws cannot drift across
    backends. A draw sorts before CDF entries equal to it, so the CDF
    entries ahead of it are those below it: its left search index."""
    cdf = normalized_cdf(p, valid)
    c, nd = cdf.shape[0], u.shape[0]
    n = _pow2_at_least(c + nd)
    key = _pad(jnp.concatenate([_float_key(u), _float_key(cdf)]), n,
               _INT_MAX)
    _, pos = sort_words([key, _iota(n)], 2, tile, interpret)
    is_draw = pos < nd
    below = _iota(n) - _exclusive_cumsum(is_draw)
    _, draws = sort_words([pos, jnp.clip(below, 0, c - 1)], 1, tile,
                          interpret)
    return draws[:nd]
