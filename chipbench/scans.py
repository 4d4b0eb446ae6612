"""Seeded synthetic T1 scans, the benchmark's input data.

A copy of the generator in the program's ``data/mri.py`` (``generate``),
kept here so that the inputs stay the same whatever the program does to
its own: an ellipsoidal head whose deformed radial field defines white
matter, gray matter and a ventricle pair, with T1-like intensities, a
smooth bias field and Gaussian noise, clipped to [0, 1].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _smooth_noise(key, shape, cutoff: int = 6):
    coarse = jax.random.normal(key, tuple(max(2, s // cutoff) for s in shape))
    return jax.image.resize(coarse, shape, method="trilinear")


@functools.partial(jax.jit, static_argnums=1)
def generate(key, shape: tuple, noise_sigma: float = 0.04,
             bias_field_strength: float = 0.15, deform_strength: float = 0.12):
    """One raw (D, H, W) float32 scan in [0, 1]."""
    d, h, w = shape
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    zz, yy, xx = jnp.meshgrid(
        jnp.linspace(-1, 1, d), jnp.linspace(-1, 1, h), jnp.linspace(-1, 1, w),
        indexing="ij")
    axes = 0.78 + 0.12 * jax.random.uniform(k1, (3,))
    r = jnp.sqrt((zz / axes[0]) ** 2 + (yy / axes[1]) ** 2 + (xx / axes[2]) ** 2)
    r = r + deform_strength * _smooth_noise(k2, shape)
    r_wm, r_gm = 0.55, 0.8
    wm = r < r_wm
    gm = (r >= r_wm) & (r < r_gm)
    vz = 0.12 * (jax.random.uniform(k4, ()) - 0.5)
    vent_r = jnp.sqrt(((zz - vz) / 0.18) ** 2 + (yy / 0.28) ** 2 + (xx / 0.12) ** 2)
    vent = (vent_r < 1.0) & wm
    wm = wm & ~vent
    vol = jnp.zeros(shape, jnp.float32)
    vol = jnp.where(gm, 0.45, vol)
    vol = jnp.where(wm, 0.75, vol)
    vol = jnp.where(vent, 0.12, vol)
    skull = (r >= r_gm) & (r < r_gm + 0.08)
    vol = jnp.where(skull, 0.25, vol)
    bias = 1.0 + bias_field_strength * _smooth_noise(k3, shape)
    vol = vol * bias + noise_sigma * jax.random.normal(k5, shape)
    return jnp.clip(vol, 0.0, 1.0)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _stack(key, n: int, shape: tuple):
    return jax.lax.map(lambda k: generate(k, shape), jax.random.split(key, n))


def pool(key, n: int, shape: tuple) -> list[np.ndarray]:
    """``n`` distinct raw scans on the host, as a client holds them: made on
    the device in one call, then copied to the host at once."""
    return list(np.asarray(_stack(key, n, tuple(shape))))
