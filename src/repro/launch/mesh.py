"""Production meshes for the dry-run target: TPU v5e pods.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod: 2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with "data" for batch/FSDP sharding; gradient
all-reduces cross the pod boundary (DCN in a real deployment).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes the global batch (and FSDP dim) shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_axis(mesh) -> str:
    return "model"


def axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n

