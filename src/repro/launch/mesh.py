"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _auto_mesh(shape, axes):
    # Auto axes: shardings propagate from the in/out specs, as the
    # partitioning rules in models/shardings.py expect
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
    the slowest (DCN-connected) axis and carries only data parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (requires host device count
    >= prod(shape), set via XLA_FLAGS in the test's subprocess)."""
    return _auto_mesh(shape, axes)
