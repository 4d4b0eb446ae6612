"""The plain reference agrees with the program's float32 MeshNet and
conform at a small size (the reference itself imports nothing of the
program; this test does, to tie the two)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, scans


def _model(dilations=(1, 2, 4, 2, 1), channels=5):
    return {"in_channels": 1, "channels": channels, "num_classes": 3,
            "dilations": list(dilations), "kernel_size": 3, "use_batchnorm": True}


def _nontrivial_bn(params):
    # BatchNorm statistics away from identity, so the test sees them
    key = jax.random.PRNGKey(3)
    for i, layer in enumerate(params["layers"]):
        k = jax.random.fold_in(key, i)
        c = layer["b"].shape[0]
        layer["b"] = 0.1 * jax.random.normal(k, (c,))
        layer["bn_mean"] = 0.2 * jax.random.normal(jax.random.fold_in(k, 1), (c,))
        layer["bn_var"] = 1.0 + jax.random.uniform(jax.random.fold_in(k, 2), (c,))
        layer["bn_scale"] = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(k, 3), (c,))
        layer["bn_bias"] = 0.1 * jax.random.normal(jax.random.fold_in(k, 4), (c,))
    return params


@pytest.mark.parametrize("channels", [5, 10])
def test_logits_match_the_program(channels):
    from repro.core import meshnet

    model = _model(channels=channels)
    params = _nontrivial_bn(reference.init_params(jax.random.PRNGKey(1), model))
    vol = jax.random.uniform(jax.random.PRNGKey(2), (12, 16, 20))
    ref = reference.logits(params, vol, model)
    cfg = meshnet.MeshNetConfig(channels=channels, num_classes=3,
                                dilations=tuple(model["dilations"]))
    with jax.default_matmul_precision("highest"):
        prog = meshnet.apply(params, vol[None], cfg)[0]
    np.testing.assert_allclose(np.moveaxis(np.asarray(ref), 0, -1), np.asarray(prog),
                               rtol=1e-4, atol=1e-4)


def test_init_matches_the_program_layout():
    from repro.core import meshnet

    model = _model()
    ours = reference.init_params(jax.random.PRNGKey(0), model)
    theirs = meshnet.init(jax.random.PRNGKey(0), meshnet.MeshNetConfig(
        dilations=tuple(model["dilations"])))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(ours)] == [a.shape for a in jax.tree.leaves(theirs)]


@pytest.mark.parametrize("raw_shape", [(16, 16, 16), (12, 18, 15)])
def test_conform_matches_the_program(raw_shape):
    from repro.core import conform

    raw = np.asarray(scans.generate(jax.random.PRNGKey(4), raw_shape))
    ours = reference.conform(jnp.asarray(raw), (16, 16, 16))
    theirs = conform.conform(raw, (16, 16, 16))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=1e-5)


def test_scans_match_the_program_generator():
    from repro.data import mri

    key = jax.random.PRNGKey(5)
    ours = scans.generate(key, (16, 16, 16))
    theirs, _ = mri.generate(key, mri.SyntheticMRIConfig(shape=(16, 16, 16)))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=1e-6)


def test_logit_gap():
    ref = jnp.asarray(np.array([[0.0, 1.0], [2.0, 0.5], [1.0, 1.5]], np.float32)
                      ).reshape(3, 1, 1, 2)
    best = jnp.argmax(ref, axis=0)
    got = reference.logit_gap(ref, best)
    assert float(got["widest"]) == 0.0 and float(got["mismatch"]) == 0.0
    assert float(got["margin"]) == pytest.approx((1.0 + 0.5) / 2)
    served = jnp.zeros((1, 1, 2), jnp.int32)  # class 0 everywhere
    got = reference.logit_gap(ref, served)
    assert float(got["widest"]) == pytest.approx(2.0)
    assert float(got["mismatch"]) == 1.0
    assert float(got["mean"]) == pytest.approx((2.0 + 0.5) / 2)
