"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets ``--xla_force_host_platform_device_count=512``
*before* calling these.
"""
from __future__ import annotations

import jax

from repro.runtime.sharding import ParallelCtx


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    The sharding code here places arrays with ``with_sharding_constraint``
    and leaves the rest to GSPMD, which only ``Auto`` axes allow; a bare
    ``jax.make_mesh`` defaults to ``Explicit`` axes and is refused by
    both.  Every mesh in the program, its tests and its benchmarks is
    built here."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ctx(mesh, *, ep: bool = True) -> ParallelCtx:
    axes = mesh.axis_names
    dp = tuple(a for a in axes if a in ("pod", "data"))
    tp = "model" if "model" in axes else None
    return ParallelCtx(mesh=mesh, dp=dp, tp=tp, ep=ep)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for host-device-count tests (not the production shape)."""
    return make_mesh((n_data, n_model), ("data", "model"))
