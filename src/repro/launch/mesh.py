"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state. The dry-run entrypoint (dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing jax.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: shardings propagate through jit
    from the operands' placements (``make_mesh`` now defaults to Explicit
    axes, under which sharding becomes part of every array's type)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ('data','model') = 256 chips.
    Multi-pod:  (2, 16, 16) ('pod','data','model') = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over however many devices this host actually has
    (tests / examples on CPU)."""
    n = jax.device_count()
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def make_request_mesh(data: int | None = None):
    """1-axis ('data',) mesh for request-parallel serving/sampling.

    The serving stack shards stacked solves over the REQUEST axis only (the
    eps network is replicated), so its mesh needs just a data axis. ``data``
    defaults to every device this process sees; tests force a multi-device
    host with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set
    BEFORE importing jax).
    """
    n = jax.device_count() if data is None else data
    return _auto_mesh((n,), ("data",))


def mesh_fingerprint(mesh) -> tuple:
    """Hashable identity of a mesh for compile-cache keys.

    Two meshes with the same axis names/sizes over the same devices (in the
    same order) produce identical executables; anything else must not share
    a cache slot -- in particular, a resharding recompile hides behind a
    mesh swap, which is exactly what cache keys exist to surface.
    """
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in np.ravel(mesh.devices)))


# TPU v5e-ish hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link (~per-chip usable bisection)
