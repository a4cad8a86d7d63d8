"""What the per-layer metrics read from the program's own measurement
points (``src/repro/runtime/spans.py``): the device scopes.

A device scope is a ``jax.named_scope`` of the train step: it is one
segment of the JAX op path of every op traced under it, bare or, inside
``jax.value_and_grad``, wrapped by the transform that traced it
(``jvp(model)``, ``transpose(jvp(model))``). An op lies under a scope
when one of its path's segments, with those wrappers taken off, is the
scope's name: ``jit(step)/gather`` does not lie under ``feature_gather``,
nor ``jit(sample)/...`` under ``sample``.

A program without the scopes (an older checkout) gives these readers
nothing to read: they return None.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench.trace import _merge

_AUTODIFF = ("jvp(", "transpose(")


def unwrap(segment: str) -> str:
    """``transpose(jvp(model))`` -> ``model``; other segments as they
    are."""
    while segment.endswith(")") and segment.startswith(_AUTODIFF):
        segment = segment[segment.index("(") + 1:-1]
    return segment


def under(path: str, scope: str) -> bool:
    return any(unwrap(s) == scope for s in path.split("/"))


def scope_seconds(trace, scope: str) -> Optional[float]:
    """Device seconds in which some op under ``scope`` ran, mean over
    chips; None where no op matches. The union of the ops' intervals,
    not their sum: a ``while`` op's event spans the events of the ops of
    its body, which lie under the same scope."""
    per, hits = [], 0
    for d in trace.devices:
        iv = np.array([[e.start_ns, e.end_ns] for e in d.ops
                       if under(e.path, scope)], float).reshape(-1, 2)
        hits += iv.shape[0]
        iv = _merge(iv)
        per.append(float(np.sum(iv[:, 1] - iv[:, 0])))
    return float(np.mean(per)) * 1e-9 if hits else None


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per traced step under ``scope``."""
    seconds = scope_seconds(ctx.trace, scope)
    return None if seconds is None else 1e3 * seconds / ctx.steps

