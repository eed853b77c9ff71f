"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is an
additional **data-parallel** dimension (gradient sync crosses the DCN/ICI
pod boundary — exactly the communication COVAP compresses).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def dp_axes(*, multi_pod: bool = False) -> tuple[str, ...]:
    """The data-parallel (gradient-sync) axes of the production mesh."""
    return ("pod", "data") if multi_pod else ("data",)


def model_axis_size() -> int:
    return 16


def make_slice_mesh(n_slices: int, data: int = 8, model: int = 8):
    """Compile-only N-slice mesh for the multislice dry-run sweep
    (modeled on MaxText's multislice launch flow: every slice is one pod
    behind a DCN crossing).  Row-major (pod, data, model), so device rank
    ``r`` lives in pod ``r // (data * model)`` — the layout
    ``launch.hlo_analysis.group_link`` assumes.  ``n_slices <= 1``
    degenerates to the flat (data, model) mesh."""
    if n_slices <= 1:
        return _mesh((data, model), ("data", "model"))
    return _mesh((n_slices, data, model), ("pod", "data", "model"))


def make_test_mesh(data: int = 4, model: int = 2):
    """Small mesh for multi-device CPU tests (spawned with fake devices)."""
    return _mesh((data, model), ("data", "model"))
