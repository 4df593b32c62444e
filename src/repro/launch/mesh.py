"""Production mesh: TPU v5e, 256 chips/pod.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False,
                         shape_override: tuple | None = None) -> Mesh:
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) for two.

    ``shape_override`` reshapes the SAME 256-chip pod into a different
    logical (data, model) factorization (e.g. (32, 8) for archs whose head
    geometry does not divide 16 — granite's 24q/8kv). Perf-iteration knob;
    the assignment's canonical meshes remain the defaults.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if shape_override is not None:
        shape = tuple(shape_override)
        axes = ("pod", "data", "model")[-len(shape):]
    n = int(np.prod(shape))
    try:
        # the model code leaves propagation to GSPMD (`sharding/partition`
        # pins inputs and parameters only), so the axes are Auto, not
        # make_mesh's Explicit default
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    except (ValueError, AssertionError):
        devices = jax.devices()
        if len(devices) < n:
            raise RuntimeError(
                f"need {n} devices for mesh {shape}; have {len(devices)} "
                "(the dry-run sets XLA_FLAGS=--xla_force_host_platform_"
                "device_count=512 before importing jax)")
        return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_debug_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for CPU tests (requires >= data*model host devices)."""
    devices = jax.devices()
    n = data * model
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(data, model),
                ("data", "model"))
