"""Property-based tests (hypothesis) on the system's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional dep: pip install hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import components, cropping, patching, spatial_shard
from repro.core.meshnet import MeshNetConfig
from repro.core import meshnet
from repro.telemetry import traffic
from repro.training import losses

SETTINGS = dict(max_examples=20, deadline=None)


# --------------------------------------------------------------- patching ---


@settings(**SETTINGS)
@given(
    d=st.integers(6, 24),
    h=st.integers(6, 24),
    w=st.integers(6, 24),
    cube=st.integers(3, 10),
    overlap=st.integers(0, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_cubedivider_split_merge_identity(d, h, w, cube, overlap, seed):
    """split -> (identity model) -> merge == identity for ANY geometry."""
    vol = jax.random.normal(jax.random.PRNGKey(seed), (d, h, w))
    divider = patching.CubeDivider((d, h, w), cube=cube, overlap=overlap)
    merged = divider.merge(divider.split(vol))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(vol), atol=0)


@settings(**SETTINGS)
@given(
    cube=st.integers(4, 12),
    overlap=st.integers(0, 8),
)
def test_cubedivider_read_size_static(cube, overlap):
    divider = patching.CubeDivider((16, 16, 16), cube=cube, overlap=overlap)
    rs = divider.read_size
    assert rs == (cube + 2 * overlap,) * 3
    for c in divider.split(jnp.zeros((16, 16, 16))):
        assert c.shape == rs


# ----------------------------------------------------------- halo exchange ---


def _sharded(fn, x):
    """Run fn per-slab over all local devices (1 in tier-1; 8 in the CI
    distributed job, where the multi-hop path is real)."""
    mesh = spatial_shard.mesh_for(jax.device_count())
    from jax.sharding import PartitionSpec as P

    spec = P(None, "z", None, None, None)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,), out_specs=spec
    )(x)


def _valid_tap(y, h):
    """A radius-h two-tap *valid* stencil: the linear, zero-preserving
    stand-in for a dilated conv layer (consumes h context per side)."""
    return y[:, : y.shape[1] - 2 * h] + y[:, 2 * h :]


@settings(max_examples=10, deadline=None)
@given(
    radii=st.lists(st.integers(1, 4), min_size=1, max_size=3),
    dloc=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_halo_exchange_composes(radii, dloc, seed):
    """Layer-wise exchange == one-shot exchange of the summed halo: n
    per-layer exchanges of h_i provide exactly the context of a single
    (multi-hop when sum > slab) exchange of sum(h_i), *provided* the
    one-shot schedule re-zeroes out-of-volume positions after every layer
    — a stencil layer writes combinations of in-volume data into the
    beyond-the-volume halo, which the next layer must read as zeros. This
    is the equivalence the sharded executor family is built on (XLA inner
    = layer-wise, megakernel inner = one-shot + per-layer ``z_bounds``
    masking, core/spatial_shard.py), and both must equal the unsharded
    'same'-padded stencil: pod edges receive zeros == the volume's zero
    padding."""
    n = jax.device_count()
    D = n * dloc
    total = sum(radii)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, D, 2, 2, 1))

    def layerwise(xs):
        # fresh exchange per layer: pod edges are re-zeroed for free
        for h in radii:
            xs = _valid_tap(spatial_shard.halo_exchange_z(xs, h, "z"), h)
        return xs

    def oneshot(xs):
        idx = jax.lax.axis_index("z")
        xs = spatial_shard.halo_exchange_z(xs, total, "z")
        cum = 0
        for h in radii:
            xs = _valid_tap(xs, h)
            cum += h
            # re-zero out-of-volume positions (megakernel z_bounds trick):
            # local j holds global idx*dloc - (total - cum) + j
            g = idx * dloc - (total - cum) + jnp.arange(xs.shape[1])
            mask = (g >= 0) & (g < D)
            xs = xs * mask[None, :, None, None, None]
        return xs

    ref = x
    for h in radii:  # the unsharded 'same'-padded stencil
        ref = _valid_tap(jnp.pad(ref, [(0, 0), (h, h), (0, 0), (0, 0), (0, 0)]), h)

    got_layer = _sharded(layerwise, x)
    got_oneshot = _sharded(oneshot, x)
    np.testing.assert_allclose(np.asarray(got_layer), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_oneshot), np.asarray(ref), atol=1e-5)


@settings(**SETTINGS)
@given(
    h=st.integers(1, 64),
    w=st.integers(1, 64),
    channels=st.integers(1, 32),
    batch=st.integers(1, 4),
)
def test_collective_bytes_monotone_and_zero_at_one(h, w, channels, batch):
    """The sharded family's ICI model (traffic.meshnet_collective_bytes):
    zero on one device, strictly increasing with slab count (each extra
    boundary adds one halo exchange)."""
    cfg = MeshNetConfig(channels=channels)
    vals = [
        traffic.meshnet_collective_bytes(cfg, (64, h, w), n, batch=batch)
        for n in range(1, 10)
    ]
    assert vals[0] == 0
    assert all(b > a for a, b in zip(vals, vals[1:]))


# ------------------------------------------------------------- components ---


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), p=st.floats(0.05, 0.5))
def test_components_idempotent_and_stable(seed, p):
    """Labelling twice gives identical labels; labels are component-minima
    (stable under recomputation)."""
    mask = jax.random.bernoulli(jax.random.PRNGKey(seed), p, (8, 8, 8))
    l1 = components.connected_components(mask)
    l2 = components.connected_components(mask)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    # background stays -1; foreground labels are valid linear indices
    a = np.asarray(l1)
    m = np.asarray(mask)
    assert (a[~m] == -1).all()
    assert (a[m] >= 0).all()


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1))
def test_components_labels_are_component_minima(seed):
    mask = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.3, (6, 6, 6))
    labels = np.asarray(components.connected_components(mask))
    m = np.asarray(mask)
    # every labelled voxel's label equals the min linear index in its label set
    for lbl in np.unique(labels[labels >= 0]):
        voxels = np.nonzero(labels == lbl)
        lin = np.ravel_multi_index(voxels, m.shape)
        assert lin.min() == lbl


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), min_size=st.integers(1, 30))
def test_remove_small_components_monotone(seed, min_size):
    """Output mask is a subset of the input; surviving components are >= min_size."""
    mask = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.25, (8, 8, 8))
    kept = components.remove_small_components(mask, min_size)
    k = np.asarray(kept)
    m = np.asarray(mask)
    assert (k <= m).all()
    labels = np.asarray(components.connected_components(jnp.asarray(k)))
    for lbl in np.unique(labels[labels >= 0]):
        assert (labels == lbl).sum() >= min_size


# ------------------------------------------------------------------- dice ---


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), classes=st.integers(2, 6))
def test_dice_bounds_and_symmetry(seed, classes):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.randint(k1, (6, 6, 6), 0, classes)
    b = jax.random.randint(k2, (6, 6, 6), 0, classes)
    d_ab = float(losses.dice_score(a, b, classes))
    d_ba = float(losses.dice_score(b, a, classes))
    assert 0.0 <= d_ab <= 1.0
    assert abs(d_ab - d_ba) < 1e-6  # symmetric
    assert float(losses.dice_score(a, a, classes)) == 1.0  # reflexive


# ------------------------------------------------------------ dilated conv ---


@settings(**SETTINGS)
@given(
    dilation=st.sampled_from([1, 2, 4]),
    seed=st.integers(0, 2**31 - 1),
)
def test_dilated_conv_linearity(dilation, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x1 = jax.random.normal(k1, (1, 8, 8, 8, 2))
    x2 = jax.random.normal(k2, (1, 8, 8, 8, 2))
    w = jax.random.normal(k3, (3, 3, 3, 2, 3)) * 0.3
    b = jnp.zeros((3,))
    f = lambda x: meshnet.dilated_conv3d(x, w, b, dilation)
    lhs = f(x1 + 2.0 * x2)
    rhs = f(x1) + 2.0 * f(x2) - b  # bias counted once
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-4)


@settings(**SETTINGS)
@given(dilation=st.sampled_from([1, 2]), shift=st.integers(1, 3), seed=st.integers(0, 2**31 - 1))
def test_dilated_conv_translation_equivariance(dilation, shift, seed):
    """Shifting the input shifts the output (away from borders)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (1, 16, 8, 8, 1))
    w = jax.random.normal(k2, (3, 3, 3, 1, 1)) * 0.3
    b = jnp.zeros((1,))
    f = lambda x: meshnet.dilated_conv3d(x, w, b, dilation)
    y = f(x)
    y_shift = f(jnp.roll(x, shift, axis=1))
    margin = shift + dilation
    np.testing.assert_allclose(
        np.asarray(jnp.roll(y, shift, axis=1)[0, margin:-margin]),
        np.asarray(y_shift[0, margin:-margin]),
        atol=1e-4,
    )


# ---------------------------------------------------------------- cropping ---


@settings(**SETTINGS)
@given(
    z0=st.integers(0, 20), y0=st.integers(0, 20), x0=st.integers(0, 20),
    ext=st.integers(1, 8),
)
def test_crop_contains_bbox_when_it_fits(z0, y0, x0, ext):
    n = 32
    z1, y1, x1 = min(z0 + ext, n), min(y0 + ext, n), min(x0 + ext, n)
    mask = jnp.zeros((n, n, n), bool).at[z0:z1, y0:y1, x0:x1].set(True)
    size = (16, 16, 16)
    _, start = cropping.crop_to(jnp.zeros((n, n, n)), mask, size)
    s = np.asarray(start)
    lo, hi = cropping.mask_bounding_box(mask)
    lo, hi = np.asarray(lo), np.asarray(hi)
    if all(hi - lo <= 16):
        assert (lo >= s).all() and (hi <= s + 16).all()


# --------------------------------------------------------------- optimizer ---


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), clip=st.floats(0.1, 10.0))
def test_grad_clip_norm_bound(seed, clip):
    from repro.training.optimizer import clip_by_global_norm, global_norm

    tree = {
        "a": jax.random.normal(jax.random.PRNGKey(seed), (7, 3)) * 10,
        "b": [jax.random.normal(jax.random.PRNGKey(seed + 1), (5,)) * 10],
    }
    clipped, _ = clip_by_global_norm(tree, clip)
    assert float(global_norm(clipped)) <= clip * (1 + 1e-4)
