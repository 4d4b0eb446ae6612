"""Layer-by-layer progressive inference — Brainchop's memory strategy.

The paper: "progressive utilization of the MeshNet model on a layer-by-layer
basis, coupled with the strategic disposal of the MRI tensor from the
preceding layer" — i.e. at any instant only one layer's weights + one
activation live in memory.

TPU/JAX adaptation: MeshNet's hidden layers 2..L are shape-uniform
(C -> C, 3^3 kernels), so we *stack* their weights and run a
``jax.lax.scan`` whose carry is the single live activation. XLA then
allocates exactly one activation buffer (double-buffered) regardless of
depth, and the per-layer dilation rides along as a scanned operand.
Input/output buffers are donated by the jit wrapper in ops-level callers.

This module is also the template for the transformer zoo: every assigned
architecture scans over stacked layer params for the same reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import meshnet
from repro.core.meshnet import MeshNetConfig
from repro.kernels import quantize


def stack_layer_params(params) -> tuple[dict, dict, dict]:
    """Split MeshNet params into (first_layer, stacked_middle, head).

    Layer 1 has in_channels != channels, so it stays unstacked; layers
    2..L-1 are stacked leaf-wise into arrays with a leading layer axis.
    """
    layers = params["layers"]
    first = layers[0]
    middle = jax.tree.map(lambda *xs: jnp.stack(xs), *layers[1:])
    return first, middle, params["head"]


def streaming_apply(
    params, x: jax.Array, cfg: MeshNetConfig, precision: str = "fp32"
) -> jax.Array:
    """Memory-streamed forward pass: logits (B, D, H, W, classes).

    Mathematically identical to ``meshnet.apply`` (inference mode); the
    difference is the execution schedule: scan keeps one live activation.

    ``precision`` (kernels/quantize.py): "fp32" is the legacy path below;
    the reduced policies keep the identical scan schedule but carry the
    live activation in bf16 with fp32 tap accumulation, and for "int8w"
    scan over *stacked int8 weights* with the per-output-channel dequant
    (and folded BN) applied as the fp32 epilogue — the streamed weight
    footprint, this schedule's defining cost, shrinks 4x.
    """
    if precision != "fp32":
        return _streaming_apply_precision(params, x, cfg, precision)
    if x.ndim == 4:
        x = x[..., None]
    first, middle, head = stack_layer_params(params)
    dilations = jnp.asarray(cfg.dilations[1:], jnp.int32)

    x, _ = meshnet.apply_layer(first, x, cfg.dilations[0], cfg, training=False)

    dmax = int(max(cfg.dilations))

    def step(carry, inp):
        layer, dilation = inp
        # Dilation is a *traced* scanned operand, so we cannot pass it to
        # conv_general_dilated (static). Instead the 3^3 dilated conv is 27
        # shifted taps: out[p] = sum_t w[t] * x[p + dilation*t]. Shifts are
        # realised as dynamic_slice into a once-padded buffer (zero 'same'
        # padding semantics; dynamic_slice accepts traced starts).
        xp = jnp.pad(carry, [(0, 0)] + [(dmax, dmax)] * 3 + [(0, 0)])
        w3 = layer["w"]  # (3, 3, 3, Cin, Cout)
        acc = jnp.zeros(carry.shape[:-1] + (w3.shape[-1],), carry.dtype)
        for tz in (-1, 0, 1):
            for ty in (-1, 0, 1):
                for tx in (-1, 0, 1):
                    start = (
                        0,
                        dmax + dilation * tz,
                        dmax + dilation * ty,
                        dmax + dilation * tx,
                        0,
                    )
                    tap = jax.lax.dynamic_slice(xp, start, carry.shape)
                    acc = acc + jnp.einsum(
                        "bdhwi,io->bdhwo", tap, w3[tz + 1, ty + 1, tx + 1]
                    )
        out = acc + layer["b"]
        if cfg.use_batchnorm:
            out = (out - layer["bn_mean"]) * jax.lax.rsqrt(layer["bn_var"] + 1e-5)
            out = out * layer["bn_scale"] + layer["bn_bias"]
        return jax.nn.relu(out), None

    x, _ = jax.lax.scan(step, x, (middle, dilations))
    return meshnet.dilated_conv3d(x, head["w"], head["b"], dilation=1)


def _streaming_apply_precision(
    params, x: jax.Array, cfg: MeshNetConfig, precision: str
) -> jax.Array:
    """The scan schedule at bf16/int8w storage (see streaming_apply)."""
    quantize.validate(precision)
    params = quantize.prepare_params(params, cfg, precision)
    adt = quantize.act_dtype(precision)
    if x.ndim == 4:
        x = x[..., None]
    if precision == "int8w":
        if x.dtype != jnp.int8:
            x = quantize.quantize_input(x)
        x = x.astype(adt) * jnp.asarray(quantize.INPUT_SCALE, adt)
    else:
        x = x.astype(adt)
    first, middle, head = stack_layer_params(params)
    dilations = jnp.asarray(cfg.dilations[1:], jnp.int32)
    # layer 1 runs unstacked through the one shared reduced-precision
    # block (static dilation); the scanned middle layers below must keep
    # the same rounding points by hand — their dilation is traced, so the
    # conv is 27 dynamic-slice taps instead of lax.conv.
    x = quantize.conv_block_reduced(
        x, first, cfg.dilations[0], cfg.use_batchnorm, adt
    )
    # fold_epilogue is elementwise over the channel axis, so it maps over
    # the stacked (L, C) leaves unchanged.
    mid_epilogue = quantize.fold_epilogue(middle, cfg.use_batchnorm)

    dmax = int(max(cfg.dilations))

    def step(carry, inp):
        layer, (bias, scale, offset), dilation = inp
        xp = jnp.pad(carry, [(0, 0)] + [(dmax, dmax)] * 3 + [(0, 0)])
        w3 = layer["w"]
        if w3.dtype == jnp.int8:
            w3 = w3.astype(adt)
        acc = jnp.zeros(
            carry.shape[:-1] + (w3.shape[-1],), jnp.float32
        )
        for tz in (-1, 0, 1):
            for ty in (-1, 0, 1):
                for tx in (-1, 0, 1):
                    start = (
                        0,
                        dmax + dilation * tz,
                        dmax + dilation * ty,
                        dmax + dilation * tx,
                        0,
                    )
                    tap = jax.lax.dynamic_slice(xp, start, carry.shape)
                    acc = acc + jnp.einsum(
                        "bdhwi,io->bdhwo",
                        tap,
                        w3[tz + 1, ty + 1, tx + 1],
                        preferred_element_type=jnp.float32,
                    )
        out = jnp.maximum((acc + bias) * scale + offset, 0.0)
        return out.astype(adt), None

    x, _ = jax.lax.scan(step, x, (middle, mid_epilogue, dilations))
    logits = (
        jnp.einsum(
            "bdhwi,io->bdhwo",
            x,
            head["w"][0, 0, 0].astype(adt),
            preferred_element_type=jnp.float32,
        )
        + head["b"].astype(jnp.float32)
    )
    return logits.astype(adt)
