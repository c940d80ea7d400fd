"""Production meshes.  Functions, not module-level constants — importing this
module never touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(tp: int = 1):
    """Whatever this host actually has (smoke tests: 1 CPU device), split
    ``(n // tp, tp)`` over ``("data", "model")``.  ``tp > 1`` is how tests
    and the serving CLI build a real host TP mesh (typically under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    n = len(jax.devices())
    if tp < 1 or n % tp:
        raise ValueError(
            f"tp={tp} must be >= 1 and divide the host device count ({n})")
    return _make_mesh((n // tp, tp), ("data", "model"))
