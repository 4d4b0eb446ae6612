"""The plain reference: MeshNet as published, with its conform step, in
float32 ``jax.numpy``. It imports nothing of the program under test.

* ``init_params`` makes the weights both sides use, from the seed, in one
  jitted call: He-normal 3^3 convolutions, zero biases, BatchNorm at its
  inference identity (scale 1, bias 0, mean 0, variance 1). The pytree
  has the layout the program's engine takes
  (``{"layers": [{w, b, bn_*}...], "head": {w, b}}``).
* ``conform`` resamples a raw scan onto the target grid (trilinear,
  grids centred, edges clamped) and rescales intensities to [0, 1] by the
  1% / 99% quantiles, non-finite voxels zeroed.
* ``logits`` runs the network on a conformed volume: 3^3 dilated
  convolutions with 'same' zero padding, BatchNorm in inference mode,
  ReLU, and the 1x1x1 head. Each convolution is written as its 27 taps,
  each tap a shifted view of the zero-padded input mixed over channels by
  elementwise float32 products, so no matrix unit and no matmul precision
  setting is involved, and the activations are stored channels-first
  ``(C, Z, Y, X)`` so a whole 256^3 volume fits one chip (a 5-channel
  activation is 335 MB).

``logit_gap`` compares a served segmentation with these logits: for each
voxel, how far the served class's reference logit lies below the
reference's best. A served class that is the reference's argmax has gap 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BN_EPS = 1e-5


def model_key(model: dict) -> tuple:
    """The hashable form of a configuration file's ``model`` block."""
    return (
        int(model["in_channels"]),
        int(model["channels"]),
        int(model["num_classes"]),
        tuple(int(d) for d in model["dilations"]),
        int(model["kernel_size"]),
        bool(model["use_batchnorm"]),
    )


@functools.partial(jax.jit, static_argnums=1)
def _init(key, mk: tuple):
    cin, c, n, dilations, k, use_bn = mk
    keys = jax.random.split(key, len(dilations) + 1)

    def he(key, shape):
        fan_in = shape[0] * shape[1] * shape[2] * shape[3]
        return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)

    layers = []
    for i in range(len(dilations)):
        layer = {"w": he(keys[i], (k, k, k, cin if i == 0 else c, c)),
                 "b": jnp.zeros((c,), jnp.float32)}
        if use_bn:
            layer["bn_scale"] = jnp.ones((c,), jnp.float32)
            layer["bn_bias"] = jnp.zeros((c,), jnp.float32)
            layer["bn_mean"] = jnp.zeros((c,), jnp.float32)
            layer["bn_var"] = jnp.ones((c,), jnp.float32)
        layers.append(layer)
    head = {"w": he(keys[-1], (1, 1, 1, c, n)), "b": jnp.zeros((n,), jnp.float32)}
    return {"layers": layers, "head": head}


def init_params(key, model: dict):
    return _init(key, model_key(model))


def _resample(vol, shape):
    src = vol.shape
    axes = [
        jnp.arange(n, dtype=jnp.float32) - (n - 1) / 2.0 + (s - 1) / 2.0
        for n, s in zip(shape, src)
    ]
    coords = jnp.meshgrid(*axes, indexing="ij")
    return jax.scipy.ndimage.map_coordinates(vol, coords, order=1, mode="nearest")


@functools.partial(jax.jit, static_argnums=1)
def conform(raw, shape: tuple):
    vol = jnp.asarray(raw, jnp.float32)
    if vol.shape != tuple(shape):
        vol = _resample(vol, tuple(shape))
    vol = jnp.where(jnp.isfinite(vol), vol, 0.0)
    lo = jnp.quantile(vol, 0.01)
    hi = jnp.quantile(vol, 0.99)
    return jnp.clip((vol - lo) / jnp.maximum(hi - lo, 1e-6), 0.0, 1.0)


def _conv(x, w, b, d: int):
    """'Same' 3^3 convolution at dilation ``d``; x is (Cin, Z, Y, X) and w
    (k, k, k, Cin, Cout)."""
    k = w.shape[0]
    r = d * (k // 2)
    cin, cout = w.shape[3], w.shape[4]
    zyx = x.shape[1:]
    xp = jnp.pad(x, ((0, 0), (r, r), (r, r), (r, r)))
    taps = w.reshape(k * k * k, cin, cout)

    def tap(t, out):
        # one tap at a time, so only one shifted view is alive at once
        tz, ty, tx = t // (k * k), (t // k) % k, t % k
        s = jax.lax.dynamic_slice(xp, (0, tz * d, ty * d, tx * d), (cin,) + zyx)
        return out + jnp.sum(taps[t][:, :, None, None, None] * s[:, None], axis=0)

    out = jnp.broadcast_to(b[:, None, None, None], (cout,) + zyx)
    return jax.lax.fori_loop(0, k * k * k, tap, out)


@functools.partial(jax.jit, static_argnums=2)
def _logits(params, vol, mk: tuple):
    _, _, _, dilations, _, use_bn = mk
    h = vol[None]
    for layer, d in zip(params["layers"], dilations):
        h = _conv(h, layer["w"], layer["b"], d)
        if use_bn:
            inv = jax.lax.rsqrt(layer["bn_var"] + BN_EPS) * layer["bn_scale"]
            h = (h - layer["bn_mean"][:, None, None, None]) * inv[:, None, None, None]
            h = h + layer["bn_bias"][:, None, None, None]
        h = jnp.maximum(h, 0.0)
    head = params["head"]
    return _conv(h, head["w"], head["b"], 1)


def logits(params, vol, model: dict):
    """Reference logits, (num_classes, Z, Y, X) float32, of a conformed
    volume."""
    return _logits(params, vol, model_key(model))


@jax.jit
def logit_gap(ref_logits, served):
    """How a served (Z, Y, X) segmentation departs from the reference:
    ``widest`` gap, ``mismatch`` (share of voxels whose served class is not
    the reference's argmax), ``mean`` gap over all voxels, and ``margin``,
    the reference's mean top-1 minus top-2 logit (the scale the gaps are
    read against)."""
    served = served.astype(jnp.int32)
    picked = jnp.take_along_axis(ref_logits, served[None], axis=0)[0]
    best = jnp.max(ref_logits, axis=0)
    gap = best - picked
    second = jnp.sort(ref_logits, axis=0)[-2]
    return {"widest": jnp.max(gap), "mismatch": jnp.mean((gap > 0).astype(jnp.float32)),
            "mean": jnp.mean(gap), "margin": jnp.mean(best - second)}
