"""Conform's quantile clipping by exact selection equals ``jnp.quantile``.

``rescale_intensity`` takes its two quantiles from an exact radix
selection (``conform._kth_smallest``) in place of the sort that
``jnp.quantile`` lowers to. The oracle is the formula it replaced, kept
inline: zero the non-finite voxels, ``jnp.quantile`` at ``lo_q`` and
``hi_q``, clip. Outputs must be equal under ``np.array_equal`` (which
holds ``-0.0 == +0.0``: the selection returns ``+0.0`` where the sort may
return ``-0.0``).

The quantiles ``(0.0, 1.0)`` and ``(0.5, 0.5)`` are passed as traced
arguments; the defaults are not passed, as the pipeline calls it. With
other traced ``q`` the CPU compiler may contract a different product of
the interpolation into a fused multiply-add in the two programs, a
difference of the code generated and not of the order statistics, which
``test_selection_equals_the_sorted_volume`` checks exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conform


@jax.jit
def _sorted_quantile_rescale(vol, lo_q=0.01, hi_q=0.99):
    vol = jnp.where(jnp.isfinite(vol), vol, 0.0)
    lo = jnp.quantile(vol, lo_q)
    hi = jnp.quantile(vol, hi_q)
    return jnp.clip((vol - lo) / jnp.maximum(hi - lo, 1e-6), 0.0, 1.0)


SHAPES = [(1, 1, 1), (2, 3, 5), (16, 16, 16), (17, 23, 9), (64, 64, 64)]
KINDS = ["gaussian", "ties", "negative", "signed_zeros", "half_constant", "subnormal",
         "non_finite"]
#: kinds whose order np.sort gives as the sort's comparator sees it: it
#: keys values equal to zero as +0.0, and the CPU and TPU compare
#: subnormals as zero
EXACT_ORDER_KINDS = ["gaussian", "ties", "negative", "signed_zeros", "half_constant"]
QUANTILES = [None, (0.0, 1.0), (0.5, 0.5)]


def _volume(kind: str, shape, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    v = (rng.standard_normal(shape) * 100.0).astype(np.float32)
    if kind == "ties":
        v = np.round(v / 40.0).astype(np.float32)
    elif kind == "negative":
        v = -np.abs(v) - np.float32(1.0)
    elif kind == "signed_zeros":
        v = np.where(rng.random(shape) < 0.5, np.float32(-0.0), np.float32(0.0))
        v[..., ::4] = rng.standard_normal(v[..., ::4].shape)
    elif kind == "half_constant":
        v.reshape(-1)[: v.size // 2] = np.float32(7.25)
    elif kind == "subnormal":
        tiny = np.finfo(np.float32).smallest_subnormal
        flat = v.reshape(-1)
        flat[::3] = tiny * rng.integers(-50, 50, flat[::3].shape)
        flat[1::3] *= np.float32(1e-3)
    elif kind == "non_finite":
        flat = v.reshape(-1)
        flat[::5] = np.nan
        flat[1::7] = np.inf
        flat[2::11] = -np.inf
    return v.astype(np.float32)


@pytest.mark.parametrize("q", QUANTILES, ids=["default", "0-1", "median"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_rescale_equals_the_sorted_quantiles(shape, kind, q):
    v = _volume(kind, shape)
    args = () if q is None else q
    got = np.asarray(conform.rescale_intensity(v, *args))
    want = np.asarray(_sorted_quantile_rescale(v, *args))
    assert np.array_equal(got, want)


def test_rescale_equals_the_sorted_quantiles_128():
    v = _volume("gaussian", (128, 128, 128), seed=3)
    got = np.asarray(conform.rescale_intensity(v))
    want = np.asarray(_sorted_quantile_rescale(v))
    assert np.array_equal(got, want)


def _select(v: np.ndarray, ranks) -> np.ndarray:
    keys = conform._order_keys(jnp.asarray(v))
    picked = conform._kth_smallest(keys, jnp.asarray(ranks, jnp.int32))
    return np.asarray(conform._key_values(picked))


@pytest.mark.parametrize("kind", EXACT_ORDER_KINDS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_selection_equals_the_sorted_volume(shape, kind):
    v = _volume(kind, shape, seed=1)
    n = v.size
    ranks = sorted({0, 1 % n, n // 3, n // 2, max(n - 2, 0), n - 1})
    want = np.sort(v.reshape(-1))[ranks]
    assert np.array_equal(_select(v, ranks), want)


def test_selection_of_extreme_values():
    # the ends of the key range and either side of zero: infinities, the
    # largest finite values and the smallest normal ones
    tiny = np.float32(np.finfo(np.float32).tiny)
    big = np.float32(np.finfo(np.float32).max)
    v = np.array([np.inf, -big, tiny, -np.inf, big, -tiny, 0.0, -0.0, 1.0],
                 np.float32).reshape(1, 3, 3)
    ranks = list(range(v.size))
    assert np.array_equal(_select(v, ranks), np.sort(v.reshape(-1)))


def test_order_keys_keep_the_order_and_invert():
    v = _volume("gaussian", (4, 8, 16), seed=2)
    v.reshape(-1)[:3] = [0.0, -0.0, np.float32(-1e-30)]
    keys = np.asarray(conform._order_keys(jnp.asarray(v))).reshape(-1)
    flat = v.reshape(-1)
    order = np.argsort(flat, kind="stable")
    assert np.all(np.diff(keys[order].astype(np.int64)) >= 0)
    back = np.asarray(conform._key_values(jnp.asarray(keys)))
    assert np.array_equal(back, flat)
    # -0.0 is keyed, and so comes back, as +0.0
    assert keys[0] == keys[1] and not np.signbit(back[1])
