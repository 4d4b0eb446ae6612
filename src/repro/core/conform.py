"""Conform preprocessing — the FastSurfer `conform` step Brainchop runs via
Pyodide (mriconvert.js): reshape the raw T1 to a cubic grid (256^3 in the
paper), resample to 1 mm isotropic, and rescale intensities to uint8-like
[0, 255] with robust quantile clipping.

Pure JAX (trilinear resampling via gather), jit-able with static output
shape, so it can run on-device as stage 1 of the pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


@jax.jit
def rescale_intensity(vol: jax.Array, lo_q: float = 0.01, hi_q: float = 0.99) -> jax.Array:
    """Robust rescale to [0, 1] by quantile clipping (conform's uint8 rescale,
    kept in float). Also zeroes non-finite voxels ("eliminate noisy voxels")."""
    vol = jnp.where(jnp.isfinite(vol), vol, 0.0)
    lo = jnp.quantile(vol, lo_q)
    hi = jnp.quantile(vol, hi_q)
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
