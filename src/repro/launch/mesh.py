"""Production meshes (DESIGN.md §7).

Single pod: 256 chips as ('data'=16, 'model'=16).
Multi-pod:  2 pods = 512 chips as ('pod'=2, 'data'=16, 'model'=16); the
'pod' axis extends data parallelism (one cross-pod gradient all-reduce
per step — the DCN-class axis stays outermost).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the sharding hooks place activations with
    # ``with_sharding_constraint``, which Explicit axes (the default of
    # ``jax.make_mesh``) reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh(n_devices: int = 0):
    """Tiny mesh over whatever devices exist (tests: 1 CPU device ->
    (1, 1); an 8-device forced-host run -> (4, 2))."""
    n = n_devices or len(jax.devices())
    data = max(1, n // 2)
    model = n // data
    return _mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (§Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
HBM_CAPACITY = 16 * 2**30       # bytes per chip
