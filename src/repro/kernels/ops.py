"""jit'd public wrappers around the Pallas kernels.

Handles what the raw kernels don't: dtype policy, BatchNorm folding, the
per-call choice between the Pallas interpreter (CPU hosts) and compiled
Mosaic (TPU) — ``resolve_interpret`` — and a kernel-backed MeshNet
forward pass (`meshnet_apply`) that fuses conv+BN+ReLU per layer.

``meshnet_apply`` is the "pallas_fused" backend of the executor registry
(core/executors.py); ``meshnet_apply_megakernel`` is the depth-first
"pallas_megakernel" backend (kernels/megakernel.py) — the pipeline's
production path on TPU when the tile plan fits VMEM ("auto" prefers it).
Parity with ``meshnet.apply`` (eval mode) is
enforced by tests/test_executors.py and tests/test_megakernel.py across
the PAPER_MODELS sweep.
"""

from __future__ import annotations

import jax

from repro.kernels import dice as dice_kernel
from repro.kernels import dilated_conv3d as conv_kernel
from repro.kernels import megakernel as mega_kernel


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether this call runs the Pallas interpreter, decided when it is
    made: ``None`` means interpret exactly when no TPU backs the call (the
    CPU correctness path). On a TPU the interpreter is never chosen —
    asking for it there is an error, not a silent slow path."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is a CPU path; refused on a TPU")
    return bool(interpret)


def dilated_conv3d(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    dilation: int = 1,
    scale=None,
    offset=None,
    fuse_affine: bool = False,
    block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """'Same' 3-D dilated conv for any (B, D, H, W[, Cin])."""
    if x.ndim == 4:
        x = x[..., None]
    return conv_kernel.dilated_conv3d(
        x, w, b,
        dilation=dilation, scale=scale, offset=offset,
        block=block, interpret=resolve_interpret(interpret),
        fuse_affine=fuse_affine,
    )


def fold_batchnorm(layer: dict, eps: float = 1e-5) -> tuple[jax.Array, jax.Array]:
    """Fold inference BN into (scale, offset) for the fused epilogue."""
    inv = jax.lax.rsqrt(layer["bn_var"] + eps)
    scale = layer["bn_scale"] * inv
    offset = layer["bn_bias"] - layer["bn_mean"] * scale
    return scale, offset


def meshnet_apply(
    params,
    x: jax.Array,
    cfg,
    *,
    interpret: bool | None = None,
    precision: str = "fp32",
) -> jax.Array:
    """Kernel-backed MeshNet inference forward (== meshnet.apply, eval mode),
    the "pallas_fused" backend: the megakernel's schedule with one segment
    per layer, so each hidden layer is ONE fused Pallas call (conv+BN+ReLU
    epilogue) and activations make a single HBM round-trip per layer
    instead of three; the 1x1x1 head folds into the last layer's call.

    ``precision`` (kernels/quantize.py): "fp32" is the f32 path; "bf16"
    ships bf16 activations/weights with fp32 accumulate in the kernel;
    "int8w" streams per-output-channel int8 weights whose dequant scale
    rides the (always-fused) affine epilogue. Inter-layer activations stay
    bf16 on this per-layer path (only the megakernel stages int8).
    """
    return mega_kernel.meshnet_apply(
        params,
        x,
        cfg,
        interpret=resolve_interpret(interpret),
        fold_affine=fold_batchnorm if cfg.use_batchnorm else None,
        precision=precision,
        per_layer=True,
    )


def meshnet_apply_megakernel(
    params,
    x: jax.Array,
    cfg,
    *,
    vmem_budget: int | None = None,
    interpret: bool | None = None,
    z_bounds: jax.Array | None = None,
    precision: str = "fp32",
    staging_scales=None,
) -> jax.Array:
    """Depth-first tiled MeshNet forward (== meshnet.apply, eval mode).

    The whole hidden stack (and the 1x1x1 head) runs per VMEM-resident
    tile inside a handful of ``pallas_call``s — hidden activations never
    round-trip HBM within a segment (kernels/megakernel.py, EXPERIMENTS.md
    §Perf H9). The "pallas_megakernel" backend of the executor registry.

    ``z_bounds`` (dynamic (2,)-int32) narrows the per-layer zero-masked
    Z-valid interval — the sharded executor's slab+halo windows pass the
    true volume extent here (core/spatial_shard.py).

    ``precision``/``staging_scales`` select the storage policy and (for
    int8w) the calibrated per-channel staging scales — see
    kernels/megakernel.py and kernels/quantize.py.
    """
    return mega_kernel.meshnet_apply(
        params,
        x,
        cfg,
        vmem_budget=vmem_budget or mega_kernel.VMEM_BUDGET,
        interpret=resolve_interpret(interpret),
        fold_affine=fold_batchnorm if cfg.use_batchnorm else None,
        z_bounds=z_bounds,
        precision=precision,
        staging_scales=staging_scales,
    )


def dice(pred: jax.Array, truth: jax.Array, num_classes: int, *, interpret: bool | None = None) -> jax.Array:
    """Macro Dice score via the fused count-accumulator kernel."""
    counts = dice_kernel.dice_counts(
        pred, truth, num_classes, interpret=resolve_interpret(interpret)
    )
    return dice_kernel.dice_from_counts(counts)
