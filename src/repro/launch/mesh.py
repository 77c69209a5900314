"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: (data=16, model=16) = 256 chips — one TPU
v5e pod.  Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod"
axis is hierarchical data parallelism (params replicated across pods,
gradients all-reduced over pod once per step — the only traffic that
crosses the slower inter-pod links).

``make_elastic_mesh`` derives a (data, model) factorization from whatever
device count survives a failure — paired with checkpoint resharding-restore
this is the elastic-scaling path.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_elastic_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_elastic_mesh(n_devices: Optional[int] = None, *,
                      model_parallel: int = 16):
    """Best (data, model) mesh for an arbitrary surviving device count."""
    n = n_devices or len(jax.devices())
    model = model_parallel
    while model > 1 and n % model != 0:
        model //= 2
    return _auto_mesh((n // model, model), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host/test devices (e.g. forced host-device tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """Mesh whose axes are sharded by the compiler (``Auto``).

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every
    gather and reduction must name its output sharding; the parameter and
    batch specs in ``repro.distributed.sharding`` are placement hints for
    the compiler, so the axes stay ``Auto``.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
