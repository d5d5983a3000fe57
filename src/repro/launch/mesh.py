"""Production mesh definitions.

A pod is a 16x16 (256-chip) slice with axes ("data", "model"); the
multi-pod configuration adds a leading "pod" axis (2 x 16 x 16 = 512
chips).  Exposed as a FUNCTION so importing this module never touches jax
device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: sharding follows the
    arrays' placements and ``with_sharding_constraint`` hints, as the
    model and serve code expect (jax's own default is ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_serve_mesh(tp: int = 1):
    """1-axis ``("model",)`` mesh for tensor-parallel paged serving: the
    KV pools and QKV weights shard ``tp`` ways along the KV-head axis
    (``sharding.serve_pool_specs`` / ``serve_param_specs``).

    On CPU, force host devices first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    n = jax.device_count()
    if tp > n:
        raise ValueError(
            f"serve mesh wants tp={tp} but only {n} device(s) exist; on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{tp} before jax initialises")
    return _auto_mesh((tp,), ("model",))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — used by tests
    and CPU examples."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    return _auto_mesh(shape, axes)


# hardware constants (TPU v5e-class, per the assignment)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (one direction)
VMEM_BYTES = 128 * 1024 * 1024
HBM_BYTES = 16 * 1024 ** 3
