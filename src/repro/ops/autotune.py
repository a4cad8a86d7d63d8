"""Persistent autotuning for the frontier kernel family.

The grid-parallel frontier kernels (`repro.kernels.frontier.parallel`)
have one real tuning knob — the block width of their bitonic sort (the
elements one grid step holds in VMEM) — plus the coarser
serial-vs-parallel choice (the serial kernels run in interpret mode
only; the serial dedup additionally has a hash-table load factor). The
right settings depend on problem size and platform, so instead of
hard-coding them this module:

  * buckets shapes to powers of two (``E=7000`` and ``E=8191`` share a
    tuning entry; re-tuning per exact shape would thrash),
  * keys entries as ``"<primitive>|<platform>|<bucket>"`` where
    platform is ``jax.default_backend()``,
  * times a candidate grid per key (``autotune()`` / the CLI below)
    and persists winners in a small JSON cache, consulted by
    :func:`get_params` at dispatch time (trace time — shapes are
    static there, so the lookup never enters the jitted program).

Cache file format (see docs/kernels.md):

    {"version": 1,
     "entries": {"hash_dedup|tpu|E=16384,S=512":
                     {"impl": "parallel", "tile": 8192, "us": 1234.5},
                 ...}}

The cache lives at ``$REPRO_AUTOTUNE_CACHE`` or, by default, at
``frontier_autotune.json`` in the root of the checkout — so dispatch
depends only on files of the checkout; a missing or corrupt file
degrades to the deterministic defaults in :data:`DEFAULT_PARAMS` —
tuning is a perf knob, never a correctness one (every candidate is
bit-exact by the parity contract, CI-gated in tests/test_frontier.py).
Two env overrides exist for CI/debugging and win over the cache:
``REPRO_FRONTIER_IMPL=serial|parallel`` forces the implementation and
``REPRO_FRONTIER_FORCE_TILE=<n>`` forces the tile width (the forced
small tiles in the frontier-parity CI job exercise multi-tile code
paths on small inputs).

:func:`cache_fingerprint` summarizes the active cache; the engine
records it in checkpoint ``engine_restore_meta`` next to the backend
choice. Unlike a backend mismatch it is informational only — tile
sizes never change results, so restore warns instead of refusing.

Re-tune with ``python -m repro.ops.autotune`` (``--smoke`` for the
seconds-scale CI round-trip, ``--cache PATH`` to redirect the file).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from typing import Any, Dict, Optional

from repro import CHECKOUT_DIR

PRIMITIVES = ("hash_dedup", "compact", "compact_perm", "segment_select",
              "masked_cdf_draw")

#: deterministic fallbacks when no cache entry exists: the parallel
#: kernels (the only ones that compile for TPU) with (64, 128) int32
#: sort blocks — a 16M-element sort compiles in ~3 s on v5e at this
#: width, against ~20 s at 32768 (untimed on the chip so far).
DEFAULT_PARAMS: Dict[str, Dict[str, Any]] = {
    p: {"impl": "parallel", "tile": 8192} for p in PRIMITIVES}

#: keys a cache entry may override (anything else — e.g. the recorded
#: timing — is carried but ignored by dispatch)
_TUNABLE = ("impl", "tile", "table_load")

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
IMPL_ENV = "REPRO_FRONTIER_IMPL"
TILE_ENV = "REPRO_FRONTIER_FORCE_TILE"
_VERSION = 1


def default_cache_path() -> str:
    return (os.environ.get(CACHE_ENV)
            or os.path.join(CHECKOUT_DIR, "frontier_autotune.json"))


def _bucket(n: int) -> int:
    """Round up to a power of two — the shape-bucket granularity."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def bucket_key(primitive: str, platform: str, shapes: Dict[str, int]) -> str:
    dims = ",".join(f"{k}={_bucket(v)}" for k, v in sorted(shapes.items()))
    return f"{primitive}|{platform}|{dims}"


class TuneCache:
    """The JSON tuning cache: load-tolerant, atomically saved."""

    def __init__(self, path: str, entries: Optional[dict] = None):
        self.path = path
        self.entries: Dict[str, Dict[str, Any]] = dict(entries or {})

    @classmethod
    def load(cls, path: str) -> "TuneCache":
        """Read ``path``; a missing, unreadable, corrupt, or
        wrong-version file yields an EMPTY cache (defaults apply) —
        never an exception on the dispatch path."""
        try:
            with open(path) as f:
                doc = json.load(f)
            if (not isinstance(doc, dict) or doc.get("version") != _VERSION
                    or not isinstance(doc.get("entries"), dict)):
                raise ValueError("bad schema")
            entries = {k: v for k, v in doc["entries"].items()
                       if isinstance(k, str) and isinstance(v, dict)}
            return cls(path, entries)
        except FileNotFoundError:
            return cls(path)
        except (OSError, ValueError) as e:  # ValueError covers JSONDecodeError
            print(f"repro.ops.autotune: ignoring unusable tuning cache "
                  f"{path!r} ({e}); using defaults", file=sys.stderr)
            return cls(path)

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        return self.entries.get(key)

    def put(self, key: str, params: Dict[str, Any]) -> None:
        self.entries[key] = dict(params)

    def save(self) -> str:
        """Atomic publish (tmp + rename), creating parent dirs."""
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _VERSION, "entries": self.entries}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, self.path)
        return self.path

    def fingerprint(self) -> Optional[str]:
        """Short content digest of the entries, None when empty (pure
        defaults). Recorded in engine_restore_meta — informational."""
        if not self.entries:
            return None
        blob = json.dumps(self.entries, sort_keys=True).encode()
        return hashlib.sha1(blob).hexdigest()[:12]


# process-wide cache, lazily loaded from the CURRENT env-resolved path
# (re-resolved on every access so tests can monkeypatch the env var;
# the file is only re-read when the path changes or reload() is called)
_STATE: Dict[str, Any] = {"path": None, "cache": None}


def _cache() -> TuneCache:
    path = default_cache_path()
    if _STATE["cache"] is None or _STATE["path"] != path:
        _STATE["path"] = path
        _STATE["cache"] = TuneCache.load(path)
    return _STATE["cache"]


def reload() -> None:
    """Drop the in-process cache so the next lookup re-reads the file."""
    _STATE["path"] = None
    _STATE["cache"] = None


def cache_fingerprint() -> Optional[str]:
    return _cache().fingerprint()


def get_params(primitive: str, **shapes: int) -> Dict[str, Any]:
    """Resolved tuning params for one dispatch: defaults <- cache entry
    <- env overrides. Called at trace time by ``repro.ops.pallas``."""
    import jax

    params = dict(DEFAULT_PARAMS[primitive])
    hit = _cache().get(bucket_key(primitive, jax.default_backend(), shapes))
    if hit:
        params.update({k: hit[k] for k in _TUNABLE if k in hit})
    impl = os.environ.get(IMPL_ENV)
    if impl in ("serial", "parallel"):
        params["impl"] = impl
    tile = os.environ.get(TILE_ENV)
    if tile and "tile" in params:
        try:
            params["tile"] = max(1, int(tile))
        except ValueError:
            pass
    if params.get("impl") not in ("serial", "parallel"):
        params["impl"] = DEFAULT_PARAMS[primitive]["impl"]
    return params


# ---------------------------------------------------------------------------
# the tuner: synthetic workloads + candidate grids, timed best-of-N


def _candidates(primitive: str, smoke: bool):
    import jax

    tiles = (1024, 2048) if smoke else (2048, 4096, 8192, 16384)
    out = [{"impl": "parallel", "tile": t} for t in tiles]
    if jax.default_backend() == "tpu":
        return out  # the serial kernels do not compile for TPU
    if primitive == "hash_dedup":
        loads = (2.0,) if smoke else (2.0, 4.0)
        out += [{"impl": "serial", "table_load": l} for l in loads]
    else:
        out += [{"impl": "serial"}]
    return out


def _inputs(primitive: str, e: int, s: int):
    """Synthetic workload shaped like a sampler epilogue: ``e`` edge
    endpoints over a vertex id space 8x larger, ``s`` seeds/segments."""
    import jax
    import numpy as np

    rng = np.random.default_rng(0)
    vspace = max(8 * e, 1024)
    if primitive == "hash_dedup":
        values = rng.integers(0, vspace, size=e).astype(np.int32)
        mask = rng.random(e) < 0.9
        seeds = np.unique(rng.integers(0, vspace, size=s).astype(np.int32))
        return (jax.numpy.asarray(values), jax.numpy.asarray(mask),
                jax.numpy.asarray(seeds), e)
    if primitive == "compact":
        flags = rng.random(e) < 0.5
        return (jax.numpy.asarray(flags), max(1, e // 2))
    if primitive == "compact_perm":
        keys = rng.integers(-1, s, size=e).astype(np.int32)
        valid = rng.random(e) < 0.9
        return (jax.numpy.asarray(keys), jax.numpy.asarray(valid), s)
    if primitive == "segment_select":
        fan = max(1, e // max(s, 1))
        seg_start = (np.arange(s) * fan).astype(np.int32)
        keys = rng.random(e).astype(np.float32)
        slot = np.repeat(np.arange(s), fan)[:e].astype(np.int32)
        mask = np.ones(e, bool)
        take = np.minimum(fan, rng.integers(1, fan + 1, size=s)).astype(
            np.int32)
        return (jax.numpy.asarray(keys), jax.numpy.asarray(slot),
                jax.numpy.asarray(mask), jax.numpy.asarray(seg_start),
                jax.numpy.asarray(take), s, fan)
    if primitive == "masked_cdf_draw":
        p = rng.random(e).astype(np.float32)
        valid = rng.random(e) < 0.9
        u = rng.random(max(1, e // 4)).astype(np.float32)
        return (jax.numpy.asarray(p), jax.numpy.asarray(valid),
                jax.numpy.asarray(u))
    raise ValueError(primitive)


def _build(primitive: str, params: Dict[str, Any], inputs):
    """A zero-arg thunk running one candidate on the prepared inputs."""
    from repro.kernels.frontier import ops as serial
    from repro.kernels.frontier import parallel as par
    from repro.ops.backend import interpret_mode

    interp = interpret_mode()
    impl = params["impl"]
    if primitive == "hash_dedup":
        values, mask, seeds, new_cap = inputs
        if impl == "parallel":
            return lambda: par.hash_dedup_block_parallel(
                values, mask, seeds, new_cap, tile=params["tile"],
                interpret=interp)
        load = float(params.get("table_load", 2.0))
        cap = _bucket(int(load * (seeds.shape[0] + values.shape[0])))
        return lambda: serial.hash_dedup_block(values, mask, seeds, new_cap,
                                               table_cap=cap,
                                               interpret=interp)
    if primitive == "compact":
        flags, cap = inputs
        if impl == "parallel":
            return lambda: par.compact_block_parallel(
                flags, cap, tile=params["tile"], interpret=interp)
        return lambda: serial.compact_block(flags, cap, interpret=interp)
    if primitive == "compact_perm":
        keys, valid, nk = inputs
        if impl == "parallel":
            return lambda: par.compact_perm_block_parallel(
                keys, valid, nk, tile=params["tile"], interpret=interp)
        return lambda: serial.compact_perm_block(keys, valid, nk,
                                                 interpret=interp)
    if primitive == "segment_select":
        keys, slot, mask, seg_start, take, ns, mt = inputs
        if impl == "parallel":
            return lambda: par.segment_select_block_parallel(
                keys, slot, mask, seg_start, take, ns, tile=params["tile"],
                interpret=interp)
        return lambda: serial.segment_select_block(keys, slot, mask, take,
                                                   ns, mt, interpret=interp)
    if primitive == "masked_cdf_draw":
        p, valid, u = inputs
        if impl == "parallel":
            return lambda: par.masked_cdf_draw_block_parallel(
                p, valid, u, tile=params["tile"], interpret=interp)
        return lambda: serial.masked_cdf_draw_block(p, valid, u,
                                                    interpret=interp)
    raise ValueError(primitive)


def _block(x):
    import jax
    jax.tree.map(lambda a: a.block_until_ready(), x)


def _time_us(thunk, reps: int) -> float:
    _block(thunk())  # warmup: trace + compile outside the timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _block(thunk())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def autotune(sizes=None, smoke: bool = False,
             cache: Optional[TuneCache] = None,
             verbose: bool = True) -> Dict[str, Dict[str, Any]]:
    """Time every candidate per (primitive, size), persist winners.

    Returns the {key: winning-params} dict that was merged into the
    cache (each entry also records the winning time in ``us``)."""
    import jax

    if sizes is None:
        sizes = [(2048, 128)] if smoke else [(8192, 512), (40960, 2048)]
    reps = 1 if smoke else 3
    cache = cache if cache is not None else _cache()
    platform = jax.default_backend()
    winners: Dict[str, Dict[str, Any]] = {}
    for e, s in sizes:
        for prim in PRIMITIVES:
            inputs = _inputs(prim, e, s)
            best_us, best_params = float("inf"), None
            for cand in _candidates(prim, smoke):
                us = _time_us(_build(prim, cand, inputs), reps)
                if verbose:
                    print(f"  {prim:16s} E={e:<7d} {cand}  {us:9.1f}us")
                if us < best_us:
                    best_us, best_params = us, cand
            key = bucket_key(prim, platform, {"E": e, "S": s})
            winners[key] = {**best_params, "us": round(best_us, 1)}
            cache.put(key, winners[key])
            if verbose:
                print(f"* {key} -> {winners[key]}")
    cache.save()
    if verbose:
        print(f"wrote {len(winners)} entries to {cache.path}")
    return winners


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.ops.autotune",
        description="Tune frontier-kernel tile sizes and persist winners "
                    "in the JSON tuning cache.")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, 1 rep, reduced candidate grid "
                         "(seconds — the CI round-trip)")
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default ${CACHE_ENV} or "
                         f"{default_cache_path()})")
    args = ap.parse_args(argv)
    if args.cache:
        os.environ[CACHE_ENV] = args.cache
        reload()
    c = _cache()
    autotune(smoke=args.smoke, cache=c)
    # read-back proves the round-trip (CI asserts on this line)
    reload()
    rb = _cache()
    print(f"round-trip: {len(rb.entries)} entries, "
          f"fingerprint={rb.fingerprint()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
