"""Precision-policy engine: fp32 | bf16 | int8w across every executor.

MeshNet inference on TPU is memory-bound at every paper channel width
(kernels/dilated_conv3d.py): the wall is HBM bytes, not FLOPs, so halving
or quartering the bytes each schedule moves is a direct speedup on the
exact metric the bench gate enforces (``hbm_bytes_modeled``). This module
defines the three storage policies and owns every dtype decision the
kernels, planner, traffic models, pipeline, and serving engine make:

  ``fp32``  — the legacy bit-exact path. Nothing is cast; every existing
              fp32 test, benchmark baseline, and plan is unchanged.
  ``bf16``  — weights and activations cross HBM as bfloat16; every kernel
              accumulates in fp32 and rounds once per HBM crossing
              (per-layer for the fused path, per-segment for the
              megakernel). ~2x byte cut; logits stay within 1e-2 of fp32
              (tests/test_precision.py).
  ``int8w`` — per-output-channel *symmetric* int8 weights with the
              inference BatchNorm folded into the dequant scale, bf16
              activation compute, fp32 accumulate. The megakernel backend
              additionally streams the conformed input volume and its
              inter-segment staging activations as int8 (calibrated
              per-channel scales, below), so int8 is what crosses HBM on
              the production path: >=3x modeled byte cut at 256^3.

Why the accumulate stays fp32: MeshNet's 3^3 x C taps sum up to 135
(C=5) .. 567 (C=21) products per output; bf16's 8-bit mantissa loses ~3
bits to a sum that long, and int8 products need 18+ bits. Accumulating in
fp32 keeps the only rounding at the HBM boundary, which is what makes the
bf16-vs-fp32 parity bound (1e-2) hold across nine stacked layers.

Weight quantization (``quantize_symmetric``) is per-OUTPUT-channel so the
dequant scale rides the conv epilogue: ``conv(x, q) * (wscale * bn_scale)
+ (b * bn_scale + bn_offset)`` — one fused multiply the kernels already
perform for folded BatchNorm (``fold_epilogue``). The round-trip error is
bounded by ``scale / 2`` per element (``roundtrip_bound``), so int8w
logits converge to fp32 as weight magnitude shrinks
(tests/test_quantize.py property test).

Activation staging scales (int8w, megakernel only): inter-segment staging
is quantized with *static per-channel* scales so the reader can dequant
without a global reduction. ``staging_scales_from_bn`` derives a bound
from the folded BatchNorm statistics (post-BN activations are ~N(bias,
scale^2), ReLU-clipped: bound = relu(bias) + K*|scale|) — accurate
exactly when the running stats describe the activations, i.e. for trained
or BN-calibrated models, the production regime. ``calibrate`` tightens
the scales to observed per-channel maxima from a probe forward; the dice
gate in tests/test_precision.py uses it. Models without BatchNorm have no
bound to derive, so the megakernel stages bf16 for them.

The conformed input is [0, 1] by construction (core/conform.py's uint8
rescale), so its int8 scale is the fixed ``INPUT_SCALE = 1/127`` —
faithful to Brainchop, whose conformed volumes literally are uint8.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

#: the three storage policies, plus the sentinel the pipeline resolves.
PRECISIONS = ("fp32", "bf16", "int8w")
AUTO = "auto"

#: fixed dequant scale of the int8-quantized conformed input volume
#: (conform guarantees [0, 1]; symmetric int8 over that range).
INPUT_SCALE = 1.0 / 127.0

#: sigma multiplier of the BN-derived staging bound: P(|z| > 6) over a
#: 256^3 volume is ~1e-2 voxels, so saturation is practically impossible.
BN_BOUND_SIGMA = 6.0

_ACT_DTYPE = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8w": jnp.bfloat16}
#: bytes per element crossing HBM, by tensor role. ``act`` is the compute/
#: VMEM width (and the logits write); ``weight`` the streamed conv taps;
#: ``input`` the conformed volume; ``staging`` the megakernel's
#: inter-segment activation arrays. fp32 keeps every legacy width.
_ACT_BYTES = {"fp32": 4, "bf16": 2, "int8w": 2}
_WEIGHT_BYTES = {"fp32": 4, "bf16": 2, "int8w": 1}
_INPUT_BYTES = {"fp32": 4, "bf16": 2, "int8w": 1}
_STAGING_BYTES = {"fp32": 4, "bf16": 2, "int8w": 1}


def validate(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS} "
            f"(or {AUTO!r} where a resolver is available)"
        )
    return precision


def act_dtype(precision: str):
    """Activation compute/storage dtype (bf16 for both reduced policies)."""
    return _ACT_DTYPE[validate(precision)]


def act_bytes(precision: str) -> int:
    return _ACT_BYTES[validate(precision)]


def weight_bytes(precision: str) -> int:
    return _WEIGHT_BYTES[validate(precision)]


def input_bytes(precision: str) -> int:
    return _INPUT_BYTES[validate(precision)]


def staging_bytes(precision: str) -> int:
    return _STAGING_BYTES[validate(precision)]


def resolve_precision(name: Optional[str], *, backend: Optional[str] = None) -> str:
    """Map None/"auto" to the device default; validate explicit names.

    Policy: CPU hosts serve fp32 — the Pallas paths there are interpret-
    mode correctness tools and the XLA fp32 graph is the oracle every
    parity test compares against. TPU serves bf16 for every model, at
    every width (the 2x byte cut is numerically free at our parity
    bound). int8w is served only when named: against the fp32 reference
    it does not yet agree (ROADMAP C.9). An explicit name always wins.
    """
    if name is not None and name != AUTO:
        return validate(name)
    backend = backend or jax.default_backend()
    return "bf16" if backend == "tpu" else "fp32"


# ------------------------------------------------------------- weights ---


def quantize_symmetric(w: jax.Array, axis: int = -1):
    """Per-slice symmetric int8 quantization along ``axis``.

    Returns ``(q, scale)`` with ``q = round(w / scale)`` in [-127, 127]
    and ``scale = max|w| / 127`` per slice of ``axis`` (conv weights:
    axis=-1 is the output channel). Zero slices get scale 1 so the
    round-trip stays exact (all-zero q).
    """
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale).astype(jnp.float32).reshape(w.shape[axis])


def dequantize(q: jax.Array, scale: jax.Array, axis: int = -1) -> jax.Array:
    """Inverse of ``quantize_symmetric``: float weights, error <= scale/2."""
    shape = [1] * q.ndim
    shape[axis % q.ndim] = q.shape[axis]
    return q.astype(jnp.float32) * scale.reshape(shape)


def roundtrip_bound(scale: jax.Array) -> jax.Array:
    """Element-wise bound on |w - dequantize(quantize(w))|: half a step."""
    return scale / 2.0


def quantize_input(x: jax.Array) -> jax.Array:
    """Quantize a conformed ([0, 1]) volume to int8 with the fixed
    ``INPUT_SCALE`` (symmetric over [-1, 1]; conform never goes negative,
    so the spare sign half of the range is the zero 'same' padding's)."""
    return (
        jnp.clip(jnp.round(x.astype(jnp.float32) / INPUT_SCALE), -127, 127)
        .astype(jnp.int8)
    )


# ------------------------------------------------------- params pytrees ---


def is_prepared(params: Any, precision: str) -> bool:
    """Whether ``params`` already carry ``precision``'s storage dtypes —
    ``prepare_params`` is idempotent through this check, so serving
    engines can cache prepared pytrees and executors accept either form."""
    if validate(precision) == "fp32":
        return True
    w = params["layers"][0]["w"]
    if precision == "bf16":
        return w.dtype == jnp.bfloat16
    return w.dtype == jnp.int8


def prepare_params(params: Any, cfg: Any, precision: str) -> Any:
    """Cast/quantize a MeshNet params pytree into ``precision`` storage.

    bf16: conv and head weights become bfloat16 (biases and BN statistics
    stay fp32 — they are folded into the fp32 epilogue and are KB-scale).
    int8w: each hidden layer's ``w`` becomes int8 with a per-output-
    channel ``wscale``; the 1x1x1 head stays bf16 (no BN to fold, its
    bytes are negligible, and its error lands directly on the logits).
    Idempotent: already-prepared params pass through unchanged.
    """
    if validate(precision) == "fp32" or is_prepared(params, precision):
        return params
    layers = []
    for layer in params["layers"]:
        new = dict(layer)
        if precision == "bf16":
            new["w"] = layer["w"].astype(jnp.bfloat16)
        else:
            q, scale = quantize_symmetric(layer["w"], axis=-1)
            new["w"] = q
            new["wscale"] = scale
        layers.append(new)
    head = dict(params["head"])
    head["w"] = head["w"].astype(jnp.bfloat16)
    return {"layers": layers, "head": head}


def fold_epilogue(layer: dict, use_batchnorm: bool, eps: float = 1e-5):
    """The per-layer fused epilogue ``relu(acc * scale + offset)`` for a
    (possibly quantized) layer, with the conv bias — and for int8w the
    weight dequant scale — folded in.

    Returns ``(bias, scale, offset)`` where ``bias`` is what the kernel
    adds to the raw accumulator *before* the affine. For fp32/bf16 layers
    this reproduces ops.fold_batchnorm exactly (bias = layer b); for
    int8w layers the accumulator is in quantized-weight units, so the
    bias moves inside the affine: ``bias = 0``, ``scale = wscale *
    bn_scale``, ``offset = b * bn_scale + bn_offset``.
    """
    if use_batchnorm:
        inv = jax.lax.rsqrt(layer["bn_var"].astype(jnp.float32) + eps)
        bn_scale = layer["bn_scale"].astype(jnp.float32) * inv
        bn_offset = (
            layer["bn_bias"].astype(jnp.float32)
            - layer["bn_mean"].astype(jnp.float32) * bn_scale
        )
    else:
        bn_scale = jnp.ones(layer["b"].shape, jnp.float32)
        bn_offset = jnp.zeros(layer["b"].shape, jnp.float32)
    b = layer["b"].astype(jnp.float32)
    if "wscale" in layer:  # int8w: dequant rides the affine
        zero = jnp.zeros_like(b)
        return zero, layer["wscale"] * bn_scale, b * bn_scale + bn_offset
    return b, bn_scale, bn_offset


def params_bytes(params: Any) -> int:
    """Actual bytes of a (possibly prepared) params pytree — the streamed
    weight footprint stamped on TelemetryRecord.params_bytes."""
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )


def model_params_bytes(cfg: Any, precision: str = "fp32") -> int:
    """Analytic ``params_bytes`` from a MeshNetConfig: conv weights at the
    policy's weight width, the bf16 head for reduced precisions, fp32
    biases/BN vectors/dequant scales."""
    validate(precision)
    wb = weight_bytes(precision)
    hb = 4 if precision == "fp32" else 2
    k = cfg.kernel_size ** 3
    total = 0
    cin = cfg.in_channels
    for _ in cfg.dilations:
        total += k * cin * cfg.channels * wb  # conv taps
        total += cfg.channels * 4  # bias
        if cfg.use_batchnorm:
            total += 4 * cfg.channels * 4  # scale/bias/mean/var
        if precision == "int8w":
            total += cfg.channels * 4  # wscale
        cin = cfg.channels
    total += cfg.channels * cfg.num_classes * hb + cfg.num_classes * 4
    return total


# --------------------------------------------------- staging activation ---


def staging_scales_from_bn(params: Any, cfg: Any) -> Optional[list]:
    """Per-layer per-channel int8 staging scales from folded BN statistics.

    Post-BN activations are ~N(bn_bias, bn_scale^2) when the running
    stats describe the data (trained / BN-calibrated models); after ReLU
    the observable range is [0, relu(bias) + K*|scale|]. Returns one
    (C,) fp32 scale per hidden layer, or None when the config has no
    BatchNorm to bound with (the megakernel stages bf16 instead).
    """
    if not cfg.use_batchnorm:
        return None
    scales = []
    for layer in params["layers"]:
        bound = jax.nn.relu(layer["bn_bias"].astype(jnp.float32))
        bound = bound + BN_BOUND_SIGMA * jnp.abs(
            layer["bn_scale"].astype(jnp.float32)
        )
        scales.append(jnp.maximum(bound, 1e-6) / 127.0)
    return scales


def calibrate(params: Any, cfg: Any, x: jax.Array, margin: float = 1.25) -> list:
    """Observed per-layer per-channel staging scales from a probe forward.

    Runs the fp32 reference forward on ``x`` and returns ``max_c *
    margin / 127`` per hidden layer — tighter than the BN bound by the
    ratio of the observed max to the K-sigma bound, at the cost of one
    forward. The margin absorbs probe-vs-serve distribution drift.
    """
    from repro.core import meshnet

    if x.ndim == 4:
        x = x[..., None]
    x = x.astype(jnp.float32)
    scales = []
    for i, d in enumerate(cfg.dilations):
        x, _ = meshnet.apply_layer(
            params["layers"][i], x, d, cfg, training=False
        )
        amax = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)))
        scales.append(jnp.maximum(amax * margin, 1e-6) / 127.0)
    return scales


def quantize_staging(x: jax.Array, scale: jax.Array) -> jax.Array:
    """ReLU activations -> int8 with a per-channel static scale (values
    beyond the calibrated bound saturate at 127)."""
    return (
        jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
        .astype(jnp.int8)
    )


# ------------------------------------------------------------ reference ---


def conv_block_reduced(
    x: jax.Array,
    layer: dict,
    dilation: int,
    use_batchnorm: bool,
    adt,
    *,
    z_same: bool = True,
) -> jax.Array:
    """One reduced-precision MeshNet conv block — THE shared rounding
    points of every non-Pallas backend: fp32-accumulated lax conv over
    the (bf16-cast, possibly int8) taps, the fused fp32 epilogue
    (``fold_epilogue`` — dequant/bias/BN), one round to ``adt`` at the
    layer boundary. The xla reference, the streaming first layer, and the
    sharded layer-wise slabs all call this one function, so cross-backend
    bit-closeness within a policy is structural, not copy-paste
    (tests/test_precision.py). ``z_same=False`` drops the Z padding — the
    sharded slab schedule supplies Z context via the halo exchange.
    """
    bias, scale, offset = fold_epilogue(layer, use_batchnorm)
    pad = [(dilation, dilation)] * 3
    if not z_same:
        pad[0] = (0, 0)
    acc = jax.lax.conv_general_dilated(
        x,
        layer["w"].astype(adt),
        (1, 1, 1),
        pad,
        rhs_dilation=(dilation,) * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        preferred_element_type=jnp.float32,
    )
    return jnp.maximum((acc + bias) * scale + offset, 0.0).astype(adt)


def reference_apply(params: Any, x: jax.Array, cfg: Any, precision: str) -> jax.Array:
    """Precision-aware XLA reference forward — the parity oracle the
    "xla" executor serves for non-fp32 policies.

    Mirrors the kernels' rounding points exactly: weights dequantized /
    cast once, activations rounded to bf16 at each layer boundary (the
    HBM crossing), every conv and the head accumulating in fp32. No
    staging quantization — int8 staging is a megakernel schedule detail,
    gated by dice agreement rather than elementwise parity.
    """
    from repro.core import meshnet

    if validate(precision) == "fp32":
        return meshnet.apply(params, x, cfg)
    if x.ndim == 4:
        x = x[..., None]
    adt = act_dtype(precision)
    if x.dtype == jnp.int8:  # pre-quantized conformed input
        x = x.astype(adt) * jnp.asarray(INPUT_SCALE, adt)
    elif precision == "int8w":
        x = quantize_input(x).astype(adt) * jnp.asarray(INPUT_SCALE, adt)
    else:
        x = x.astype(adt)
    for i, d in enumerate(cfg.dilations):
        # int8 taps are exact in bf16 (integers <= 127); their dequant
        # scale rides the fold_epilogue affine inside conv_block_reduced.
        x = conv_block_reduced(
            x, params["layers"][i], d, cfg.use_batchnorm, adt
        )
    head = params["head"]
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
