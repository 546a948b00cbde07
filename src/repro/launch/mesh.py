"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax
device state; dryrun.py sets XLA_FLAGS before any jax import.  Axes are
``Auto`` (``dist.sharding.auto_mesh``).
"""
from __future__ import annotations

from repro.dist.sharding import auto_mesh

__all__ = ["make_production_mesh", "required_devices"]


def required_devices(*, multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (data, model) or 2x16x16 (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (data=1, model=1)."""
    return auto_mesh((1, 1), ("data", "model"))
