"""Unit tests for the precision-policy engine (kernels/quantize.py):
pack/unpack round-trip bounds, per-channel scale correctness with and
without BatchNorm folding, params preparation, the analytic weight-
footprint model, and the property that int8w logits converge to fp32 as
weight magnitude shrinks (the quantization step is proportional to the
per-channel max, so the absolute error vanishes with it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import meshnet
from repro.core.meshnet import MeshNetConfig
from repro.kernels import ops, quantize

KEY = jax.random.PRNGKey(7)


class TestSymmetricQuantization:
    def test_roundtrip_error_within_half_step(self):
        w = jax.random.normal(KEY, (3, 3, 3, 5, 8)) * 0.3
        q, scale = quantize.quantize_symmetric(w, axis=-1)
        assert q.dtype == jnp.int8
        assert scale.shape == (8,)
        back = quantize.dequantize(q, scale)
        err = np.abs(np.asarray(back - w))
        bound = np.asarray(quantize.roundtrip_bound(scale))
        assert (err <= bound[None, None, None, None, :] + 1e-7).all()

    def test_per_channel_scale_is_max_over_127(self):
        w = jnp.zeros((3, 3, 3, 2, 3)).at[0, 0, 0, 0, 1].set(2.54)
        w = w.at[1, 1, 1, 1, 0].set(-1.27)
        q, scale = quantize.quantize_symmetric(w, axis=-1)
        np.testing.assert_allclose(
            np.asarray(scale), [1.27 / 127, 2.54 / 127, 1.0], rtol=1e-6
        )
        # extreme values map to exactly +-127
        assert int(q[0, 0, 0, 0, 1]) == 127
        assert int(q[1, 1, 1, 1, 0]) == -127

    def test_zero_channel_roundtrips_exactly(self):
        w = jnp.zeros((3, 3, 3, 2, 2)).at[..., 0].set(0.5)
        q, scale = quantize.quantize_symmetric(w, axis=-1)
        np.testing.assert_array_equal(np.asarray(q[..., 1]), 0)
        np.testing.assert_array_equal(
            np.asarray(quantize.dequantize(q, scale)[..., 1]), 0.0
        )

    def test_input_quantization_fixed_scale(self):
        x = jnp.linspace(0.0, 1.0, 11)
        q = quantize.quantize_input(x)
        assert q.dtype == jnp.int8
        back = q.astype(jnp.float32) * quantize.INPUT_SCALE
        assert float(jnp.max(jnp.abs(back - x))) <= quantize.INPUT_SCALE / 2 + 1e-7


class TestFoldEpilogue:
    def _layer(self, c=5, key=KEY, quantized=False):
        cfg = MeshNetConfig(channels=c, dilations=(1,))
        p = meshnet.init(key, cfg)
        layer = dict(p["layers"][0])
        k1, k2, k3, k4 = jax.random.split(key, 4)
        layer["bn_mean"] = jax.random.normal(k1, (c,)) * 0.3
        layer["bn_var"] = 0.5 + jax.random.uniform(k2, (c,))
        layer["bn_scale"] = 1.0 + 0.2 * jax.random.normal(k3, (c,))
        layer["bn_bias"] = 0.1 * jax.random.normal(k4, (c,))
        if quantized:
            q, scale = quantize.quantize_symmetric(layer["w"], axis=-1)
            layer["w"], layer["wscale"] = q, scale
        return layer

    def test_matches_ops_fold_batchnorm_for_float_layers(self):
        layer = self._layer()
        bias, scale, offset = quantize.fold_epilogue(layer, True)
        s_ref, o_ref = ops.fold_batchnorm(layer)
        np.testing.assert_allclose(np.asarray(bias), np.asarray(layer["b"]))
        np.testing.assert_allclose(np.asarray(scale), np.asarray(s_ref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(offset), np.asarray(o_ref), rtol=1e-6)

    def test_int8_fold_reproduces_dequant_then_bn(self):
        """(acc + 0) * (wscale * bn_s) + (b * bn_s + bn_o) must equal
        BN(conv(x, dequant(q)) + b) for any accumulator value."""
        layer = self._layer(quantized=True)
        bias, scale, offset = quantize.fold_epilogue(layer, True)
        np.testing.assert_array_equal(np.asarray(bias), 0.0)
        acc = jax.random.normal(KEY, (4, layer["w"].shape[-1]))
        got = acc * scale + offset
        # reference: dequant the accumulator, add bias, apply inference BN
        s_ref, o_ref = ops.fold_batchnorm(layer)
        want = (acc * layer["wscale"] + layer["b"]) * s_ref + o_ref
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_no_batchnorm_fold(self):
        layer = self._layer(quantized=True)
        bias, scale, offset = quantize.fold_epilogue(layer, False)
        np.testing.assert_array_equal(np.asarray(bias), 0.0)
        np.testing.assert_allclose(np.asarray(scale), np.asarray(layer["wscale"]))
        np.testing.assert_allclose(np.asarray(offset), np.asarray(layer["b"]))


class TestPrepareParams:
    def test_idempotent_and_dtypes(self):
        cfg = MeshNetConfig(dilations=(1, 2))
        p = meshnet.init(KEY, cfg)
        for prec, wdt in (("bf16", jnp.bfloat16), ("int8w", jnp.int8)):
            prepared = quantize.prepare_params(p, cfg, prec)
            assert prepared["layers"][0]["w"].dtype == wdt
            assert prepared["head"]["w"].dtype == jnp.bfloat16
            again = quantize.prepare_params(prepared, cfg, prec)
            assert again is prepared  # idempotent: no re-quantization
        assert quantize.prepare_params(p, cfg, "fp32") is p

    def test_params_bytes_match_analytic_model(self):
        cfg = MeshNetConfig()  # gwm_light
        p = meshnet.init(KEY, cfg)
        for prec in quantize.PRECISIONS:
            prepared = quantize.prepare_params(p, cfg, prec)
            assert quantize.params_bytes(prepared) == quantize.model_params_bytes(
                cfg, prec
            ), prec
        # the footprint ordering is the whole point: int8w < bf16 < fp32
        sizes = [quantize.model_params_bytes(cfg, pr) for pr in quantize.PRECISIONS]
        assert sizes[2] < sizes[1] < sizes[0]

    def test_unknown_precision_raises(self):
        with pytest.raises(ValueError, match="unknown precision"):
            quantize.validate("fp16")

    def test_resolve_precision_policy(self):
        assert quantize.resolve_precision("int8w") == "int8w"
        assert quantize.resolve_precision(None, backend="cpu") == "fp32"
        assert quantize.resolve_precision("auto", backend="cpu") == "fp32"
        # bf16 at every width: the policy reads no model, and int8w is
        # served only when named
        assert quantize.resolve_precision("auto", backend="tpu") == "bf16"


class TestStagingScales:
    def test_bn_bound_covers_observed_activations(self):
        # with BN stats matching the data, the 6-sigma bound must dominate
        # the observed per-channel maxima (no saturation in practice)
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        p = meshnet.init(KEY, cfg)
        x = jax.random.uniform(jax.random.PRNGKey(5), (1, 12, 12, 12))
        observed = quantize.calibrate(p, cfg, x, margin=1.0)
        bn = quantize.staging_scales_from_bn(p, cfg)
        assert bn is not None and len(bn) == len(observed) == 3
        for o, b in zip(observed, bn):
            assert (np.asarray(o) <= np.asarray(b) + 1e-6).all()

    def test_no_batchnorm_has_no_bn_scales(self):
        cfg = MeshNetConfig(dilations=(1,), use_batchnorm=False)
        p = meshnet.init(KEY, cfg)
        assert quantize.staging_scales_from_bn(p, cfg) is None

    def test_staging_roundtrip_error_bound(self):
        x = jax.nn.relu(jax.random.normal(KEY, (64, 5)))
        scale = jnp.maximum(jnp.max(x, axis=0), 1e-6) / 127.0
        q = quantize.quantize_staging(x, scale)
        back = q.astype(jnp.float32) * scale
        err = np.abs(np.asarray(back - x))
        assert (err <= np.asarray(scale)[None, :] / 2 + 1e-7).all()


class TestConvergenceProperty:
    @pytest.mark.parametrize("shrink", [1.0, 1e-1, 1e-2, 1e-3])
    def test_int8w_logits_converge_to_fp32_as_weights_shrink(self, shrink):
        """The int8 step is max|w|/127 per channel, so the absolute weight
        error — and with it the logit gap — scales linearly with weight
        magnitude. Verified on the xla reference executor (the same
        quantizer feeds every backend)."""
        from repro.core import executors

        cfg = MeshNetConfig(dilations=(1, 2), use_batchnorm=False)
        p = meshnet.init(KEY, cfg)
        p = jax.tree.map(lambda a: a * shrink, p)
        x = jax.random.uniform(jax.random.PRNGKey(9), (1, 8, 8, 8))
        ref = executors.apply("xla", p, x, cfg)
        got = executors.apply("xla", p, x, cfg, precision="int8w")
        gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
        # bf16 activation rounding also scales with the activations, so
        # the whole gap is proportional to the weight scale
        assert gap <= 0.05 * shrink, (shrink, gap)
