"""Conform preprocessing — the FastSurfer `conform` step Brainchop runs via
Pyodide (mriconvert.js): reshape the raw T1 to a cubic grid (256^3 in the
paper), resample to 1 mm isotropic, and rescale intensities to uint8-like
[0, 255] with robust quantile clipping.

Pure JAX (trilinear resampling via gather), jit-able with static output
shape, so it can run on-device as stage 1 of the pipeline. The clipping
quantiles are those of ``jnp.quantile``'s ``linear`` method, value for
value, but their order statistics come from an exact selection by
counting (``_kth_smallest``) in place of the full sort ``jnp.quantile``
lowers to: a sort of 16.7M voxels that keeps four of them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.telemetry import spans


def _trilinear_sample(vol: jax.Array, coords: jax.Array) -> jax.Array:
    """Sample `vol` (D,H,W) at float coords (3, N) with edge clamping."""
    d, h, w = vol.shape
    cz, cy, cx = coords
    z0 = jnp.clip(jnp.floor(cz).astype(jnp.int32), 0, d - 1)
    y0 = jnp.clip(jnp.floor(cy).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(cx).astype(jnp.int32), 0, w - 1)
    z1, y1, x1 = jnp.minimum(z0 + 1, d - 1), jnp.minimum(y0 + 1, h - 1), jnp.minimum(x0 + 1, w - 1)
    fz = jnp.clip(cz - z0, 0.0, 1.0)
    fy = jnp.clip(cy - y0, 0.0, 1.0)
    fx = jnp.clip(cx - x0, 0.0, 1.0)

    def at(zi, yi, xi):
        return vol[zi, yi, xi]

    c000, c001 = at(z0, y0, x0), at(z0, y0, x1)
    c010, c011 = at(z0, y1, x0), at(z0, y1, x1)
    c100, c101 = at(z1, y0, x0), at(z1, y0, x1)
    c110, c111 = at(z1, y1, x0), at(z1, y1, x1)
    c00 = c000 * (1 - fx) + c001 * fx
    c01 = c010 * (1 - fx) + c011 * fx
    c10 = c100 * (1 - fx) + c101 * fx
    c11 = c110 * (1 - fx) + c111 * fx
    c0 = c00 * (1 - fy) + c01 * fy
    c1 = c10 * (1 - fy) + c11 * fy
    return c0 * (1 - fz) + c1 * fz


@functools.partial(jax.jit, static_argnames=("out_shape",))
def resample(vol: jax.Array, out_shape: tuple[int, int, int], voxel_size=(1.0, 1.0, 1.0)) -> jax.Array:
    """Trilinearly resample `vol` onto an `out_shape` grid.

    `voxel_size` is the source voxel size in mm; the target grid is 1 mm
    isotropic centred on the source volume (the conform convention).
    """
    d, h, w = out_shape
    src = jnp.asarray(vol, jnp.float32)
    sd, sh, sw = src.shape
    # Target voxel (i,j,k) in mm -> source index = mm / src_voxel_size,
    # with both grids centred.
    zs = (jnp.arange(d) - (d - 1) / 2.0) / voxel_size[0] + (sd - 1) / 2.0
    ys = (jnp.arange(h) - (h - 1) / 2.0) / voxel_size[1] + (sh - 1) / 2.0
    xs = (jnp.arange(w) - (w - 1) / 2.0) / voxel_size[2] + (sw - 1) / 2.0
    zz, yy, xx = jnp.meshgrid(zs, ys, xs, indexing="ij")
    coords = jnp.stack([zz.ravel(), yy.ravel(), xx.ravel()])
    return _trilinear_sample(src, coords).reshape(out_shape)


_SIGN = np.uint32(0x80000000)


def _order_keys(vol: jax.Array) -> jax.Array:
    """float32 -> uint32 keys in the values' order: the bits of a negative
    value flipped, the sign bit of a non-negative one set. A value equal
    to zero is keyed as ``+0.0`` first, as the comparator of the sort in
    ``jnp.quantile`` canonicalizes it: ``-0.0``, and subnormals where the
    backend compares them as zero (the CPU and the TPU do). No NaN may be
    given."""
    bits = lax.bitcast_convert_type(jnp.where(vol == 0.0, 0.0, vol), jnp.uint32)
    return jnp.where(bits >= _SIGN, ~bits, bits | _SIGN)


def _key_values(keys: jax.Array) -> jax.Array:
    """The inverse of ``_order_keys``."""
    bits = jnp.where(keys >= _SIGN, keys ^ _SIGN, ~keys)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _kth_smallest(keys: jax.Array, ranks: jax.Array) -> jax.Array:
    """The ``ranks``-th smallest (0-based int32, shape (T,)) of ``keys``.

    Exact radix selection by counting, from the top bit down: the answer
    for rank r is the largest t with count(keys < t) <= r, and each of the
    32 passes settles one bit of it for every rank at once. A pass is one
    fused reduction over the keys (their count below each candidate): no
    sort, and no shape that depends on the data."""

    def settle(i, prefix):
        cand = prefix + lax.shift_left(np.uint32(1), (31 - i).astype(jnp.uint32))
        below = jnp.stack([jnp.sum(keys < c) for c in cand])
        return jnp.where(below <= ranks, cand, prefix)

    return lax.fori_loop(0, 32, settle, jnp.zeros(ranks.shape, jnp.uint32))


def _linear_positions(q, size: int):
    """``jnp.quantile``'s ``linear`` method for one ``q`` over ``size``
    values, op for op in float32 (jax 0.9.0, ``_quantile``): the ranks of
    the two order statistics it interpolates, and their weights."""
    q = jnp.asarray(q, jnp.float32)
    n = lax.convert_element_type(size, jnp.float32)
    q = lax.mul(q, n - 1)
    low, high = lax.floor(q), lax.ceil(q)
    high_weight = lax.sub(q, low)
    low_weight = lax.sub(np.float32(1), high_weight)
    low = lax.convert_element_type(lax.clamp(np.float32(0), low, n - 1), jnp.int32)
    high = lax.convert_element_type(lax.clamp(np.float32(0), high, n - 1), jnp.int32)
    return low, high, low_weight, high_weight


@jax.jit
def rescale_intensity(vol: jax.Array, lo_q: float = 0.01, hi_q: float = 0.99) -> jax.Array:
    """Robust rescale to [0, 1] by quantile clipping (conform's uint8 rescale,
    kept in float). Also zeroes non-finite voxels ("eliminate noisy voxels").

    The quantiles equal ``jnp.quantile(vol, q)`` (``method="linear"``): the
    same float32 ops give the ranks, weights and interpolation, and the two
    order statistics each needs come from ``_kth_smallest``, exact. Where
    its sort would return ``-0.0`` (or a subnormal that compares as zero)
    this returns ``+0.0``, as ``_order_keys`` folds them, which rescales
    every voxel to the same value."""
    vol = jnp.where(jnp.isfinite(vol), vol, 0.0)
    lo_low, lo_high, lo_low_w, lo_high_w = _linear_positions(lo_q, vol.size)
    hi_low, hi_high, hi_low_w, hi_high_w = _linear_positions(hi_q, vol.size)
    ranks = jnp.stack([lo_low, lo_high, hi_low, hi_high])
    stat = _key_values(_kth_smallest(_order_keys(vol), ranks))
    lo = lax.add(lax.mul(stat[0], lo_low_w), lax.mul(stat[1], lo_high_w))
    hi = lax.add(lax.mul(stat[2], hi_low_w), lax.mul(stat[3], hi_high_w))
    out = (vol - lo) / jnp.maximum(hi - lo, 1e-6)
    return jnp.clip(out, 0.0, 1.0)


class DegenerateVolumeError(ValueError):
    """The input volume has no intensity dynamic range — all-zero, a
    constant fill, or nothing but non-finite voxels. The quantile
    rescale would collapse it to a flat field and the network would
    "segment" pure noise, so conform refuses it with a typed error the
    pipeline converts into a failed telemetry record (never a crash):
    the preprocessing analogue of the serving tier's typed fault
    taxonomy."""

    def __init__(self, lo: float, hi: float):
        super().__init__(
            "degenerate input volume: finite intensity range "
            f"[{lo!r}, {hi!r}] has no dynamic range to conform"
        )
        self.lo = lo
        self.hi = hi


def conform(
    vol: jax.Array,
    out_shape: tuple[int, int, int] = (256, 256, 256),
    voxel_size=(1.0, 1.0, 1.0),
) -> jax.Array:
    """Full conform: resample to cubic isotropic grid + intensity rescale.

    Raises ``DegenerateVolumeError`` (host-side, before any resampling
    compute) when a well-formed 3-D volume is constant / all-zero /
    all-non-finite — the jitted stages stay jit-able; this wrapper is
    the host entry point and may look at values. Malformed (non-3-D)
    payloads are NOT intercepted: they fail in resample exactly as
    before, so the serving tier's garbage-volume classification is
    untouched.

    Spans (telemetry/spans.py): ``conform.upload`` (the host array to the
    device; it can return before the transfer ends), ``conform.range``
    (the range check: its two blocking reads also wait for the transfer)
    and ``conform.rescale`` (the dispatch of resample and rescale: it
    returns before the device work ends)."""
    with spans.span("conform.upload"):
        vol = jnp.asarray(vol, jnp.float32)
    if vol.ndim == 3:
        with spans.span("conform.range"):
            finite = jnp.where(jnp.isfinite(vol), vol, 0.0)
            lo = float(jnp.min(finite))
            hi = float(jnp.max(finite))
        if not (hi - lo > 0.0):
            raise DegenerateVolumeError(lo, hi)
    with spans.span("conform.rescale"):
        if vol.shape != out_shape:
            vol = resample(vol, out_shape, voxel_size)
        return rescale_intensity(vol)
