"""Production mesh factory. Never touches jax device state at import."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """jax.make_mesh over a prefix of jax.devices() (so a 256-device mesh
    can be built while 512 placeholder devices exist)."""
    import numpy as np

    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — the "
            "dry-run launcher must set XLA_FLAGS="
            "--xla_force_host_platform_device_count before importing jax")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(num_devices=None, axes=("data", "model")):
    """Small host mesh for unit tests (uses however many devices exist)."""
    devs = jax.devices()
    n = num_devices or len(devs)
    if len(axes) == 2:
        d = max(1, n // 2) if n > 1 else 1
        shape = (d, n // d)
    else:
        shape = (n,)
    return make_mesh(shape, axes)
