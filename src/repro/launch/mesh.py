"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the ``pod``
axis doubles as the HI cascade's tier axis (DESIGN.md §2).

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices) -> Mesh:
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings from the
    jit boundaries and ``with_sharding_constraint`` hints, which is what the
    sharding rules here are written for (the installed JAX defaults to
    Explicit axes, under which those hints raise)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"need {need} devices for mesh {shape}, have {len(devices)}; "
            "run under launch/dryrun.py which forces 512 host devices")
    return _auto_mesh(shape, axes, devices[:need])


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over however many real devices exist (tests)."""
    return make_serving_mesh(data, model)


def make_serving_mesh(data: int = 1, model: int = 1) -> Mesh:
    """(data, model) mesh for the mesh-sharded serving scheduler.

    ``data`` is the S-tier replica count (each replica owns a disjoint slot
    slice + its own paged-pool shard); ``model`` is the L tier's tensor-
    parallel axis.  A (1, 1) mesh is the DEBUG configuration: the sharded
    tick runs on one device and must be token-identical to the unsharded
    path.  Validates the device count up front — ``jax.make_mesh`` with too
    few devices fails with an opaque reshape error.
    """
    need = data * model
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"serving mesh ({data}, {model}) needs {need} devices, have "
            f"{len(devices)}; on CPU force host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before the "
            "first jax import (tests/conftest.py does this under "
            "REPRO_MULTI_DEVICE=1)")
    return _auto_mesh((data, model), ("data", "model"), devices[:need])
