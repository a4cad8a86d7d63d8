"""The mesh active for the current trace, as sharding hints need it."""
from __future__ import annotations

import jax


def current_mesh():
    """The abstract mesh of the current trace, or ``None`` off-mesh
    (callers then skip their sharding hints)."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m
