"""Precision-policy gates across every executor backend (EXPERIMENTS.md
H11; kernels/quantize.py).

Three families of claims:

  * **Parity** — per policy, every backend computes the same function:
    bf16 logits are bit-close across backends (they share the rounding
    points: bf16 at HBM crossings, fp32 accumulate) and within 1e-2 of
    the fp32 logits on conform-distributed inputs; int8w backends agree
    with the int8w xla oracle, and their *segmentations* track fp32.
  * **Accuracy** — on a briefly *trained* model (real decision margins —
    quantization gates on random-init logits measure coin flips), int8w
    dice >= 0.99x the fp32 dice, for every backend including the
    megakernel with int8 staging forced through a tiny VMEM budget.
  * **Traffic** — the analytic models at the paper volume: megakernel
    int8w <= 0.4x and bf16 <= 0.55x the fp32 bytes for every
    PAPER_MODEL, with the committed fp32 baselines unchanged by the
    precision-aware planner.

Multi-device (sharded family) parity runs wherever >= 2 devices exist —
the CI ``distributed`` job forces 8 host devices and REPRO_SMALL_SHAPES=1.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executors, meshnet, pipeline
from repro.core.meshnet import MeshNetConfig, PAPER_MODELS
from repro.core.pipeline import PipelineConfig
from repro.data import mri
from repro.kernels import megakernel, ops, quantize
from repro.telemetry import traffic

KEY = jax.random.PRNGKey(11)

SMALL = os.environ.get("REPRO_SMALL_SHAPES") == "1"

#: odd (non-block-multiple) spatial shape, conform-distributed data
ODD_SHAPE = (1, 10, 12, 14)

SINGLE_DEVICE_BACKENDS = (
    "xla", "pallas_fused", "pallas_megakernel", "streaming", "sharded_xla@1"
)


def _mri_input(shape=ODD_SHAPE, seed=11):
    vol, _ = mri.generate(
        jax.random.PRNGKey(seed), mri.SyntheticMRIConfig(shape=shape[1:4])
    )
    return vol[None]


def _f32(a):
    return np.asarray(a, np.float32)


class TestBf16Parity:
    """bf16 <= 1e-2 max-abs vs fp32 logits, on every backend."""

    @pytest.mark.parametrize("name", sorted(PAPER_MODELS))
    def test_paper_models_vs_fp32(self, name):
        cfg = PAPER_MODELS[name]
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        ref = _f32(executors.apply("xla", p, x, cfg))
        for backend in SINGLE_DEVICE_BACKENDS:
            got = executors.apply(backend, p, x, cfg, precision="bf16")
            assert got.dtype == jnp.bfloat16
            err = np.max(np.abs(_f32(got) - ref))
            assert err <= 1e-2, (backend, err)

    def test_backends_agree_bitwise_tight(self):
        # all bf16 backends share rounding points -> near-identical logits
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        oracle = _f32(executors.apply("xla", p, x, cfg, precision="bf16"))
        for backend in SINGLE_DEVICE_BACKENDS[1:]:
            got = _f32(executors.apply(backend, p, x, cfg, precision="bf16"))
            np.testing.assert_allclose(got, oracle, atol=1e-3)

    def test_no_batchnorm(self):
        # without BN the activations grow unnormalized layer-over-layer,
        # so the absolute bf16 gap scales with them — the 1e-2 gate is a
        # claim about the (all-BatchNorm) paper zoo; here we only require
        # the same order of magnitude and cross-backend agreement
        cfg = MeshNetConfig(dilations=(1, 2), use_batchnorm=False)
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        ref = _f32(executors.apply("xla", p, x, cfg))
        for backend in SINGLE_DEVICE_BACKENDS:
            got = _f32(executors.apply(backend, p, x, cfg, precision="bf16"))
            assert np.max(np.abs(got - ref)) <= 3e-2, backend


class TestInt8wParity:
    def test_backends_agree_with_oracle(self):
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        oracle = _f32(executors.apply("xla", p, x, cfg, precision="int8w"))
        for backend in SINGLE_DEVICE_BACKENDS[1:]:
            got = _f32(executors.apply(backend, p, x, cfg, precision="int8w"))
            # the megakernel folds the input scale exactly instead of
            # rounding the dequantized input to bf16 — a one-ulp-of-bf16
            # family difference; everything else is bit-close
            np.testing.assert_allclose(got, oracle, atol=2e-2)

    def test_megakernel_int8_staging_matches_oracle(self):
        """Force a multi-segment plan (small VMEM budget) so the int8
        staging write/dequant path is exercised, then check logits stay
        near the (non-staged) oracle and the segmentation tracks fp32."""
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        budget = 2 * 2**20
        pln = megakernel.plan_for_config(
            cfg, x.shape[1:4], vmem_budget=budget, precision="int8w"
        )
        assert len(pln.segments) >= 2, "budget did not force staging"
        got = ops.meshnet_apply_megakernel(
            p, x, cfg, precision="int8w", vmem_budget=budget
        )
        oracle = executors.apply("xla", p, x, cfg, precision="int8w")
        np.testing.assert_allclose(_f32(got), _f32(oracle), atol=8e-2)
        ref = executors.apply("xla", p, x, cfg)
        agree = float(
            jnp.mean(jnp.argmax(got, -1) == jnp.argmax(ref, -1))
        )
        assert agree >= 0.95, agree

    def test_calibrated_scales_tighten_staging(self):
        """quantize.calibrate scales (observed maxima) must not be worse
        than the BN 6-sigma bound on the data they were calibrated on."""
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        budget = 2 * 2**20
        ref = executors.apply("xla", p, x, cfg)

        def staged_err(scales):
            got = ops.meshnet_apply_megakernel(
                p, x, cfg, precision="int8w", vmem_budget=budget,
                staging_scales=scales,
            )
            return float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))

        bn_err = staged_err(quantize.staging_scales_from_bn(p, cfg))
        cal_err = staged_err(quantize.calibrate(p, cfg, x))
        assert cal_err <= bn_err + 1e-3, (cal_err, bn_err)

    def test_no_batchnorm_falls_back_to_bf16_staging(self):
        # without BN stats there is no staging bound: the megakernel must
        # still run (staging stays bf16) and match its oracle
        cfg = MeshNetConfig(dilations=(1, 2), use_batchnorm=False)
        p = meshnet.init(KEY, cfg)
        x = _mri_input()
        got = ops.meshnet_apply_megakernel(
            p, x, cfg, precision="int8w", vmem_budget=2 * 2**20
        )
        oracle = executors.apply("xla", p, x, cfg, precision="int8w")
        np.testing.assert_allclose(_f32(got), _f32(oracle), atol=2e-2)


@pytest.fixture(scope="module")
def trained_gwm():
    """A briefly trained gwm-style model: real decision margins make the
    dice gate meaningful (random-init logits are coin flips at every
    precision). Same deterministic recipe as the tier-1 training test."""
    from repro.training import trainer

    cfg = trainer.TrainConfig(
        model=MeshNetConfig(channels=5, dropout_rate=0.0),
        data=mri.DataLoaderConfig(
            mri=mri.SyntheticMRIConfig(shape=(24, 24, 24)), batch_size=2
        ),
        steps=40,
        eval_subjects=1,
        log_every=1000,
        seed=1,
    )
    res = trainer.train(cfg, verbose=False)
    vol, labels = mri.generate(
        jax.random.PRNGKey(10_000), mri.SyntheticMRIConfig(shape=(24, 24, 24))
    )
    return res.params, cfg.model, vol, labels


def _dice(seg, labels, num_classes):
    from repro.training import losses

    return float(losses.dice_score(seg, labels, num_classes))


class TestInt8wDiceGate:
    """int8w dice >= 0.99x fp32 dice on a trained model — the acceptance
    gate, per backend (megakernel with staging forced)."""

    def test_dice_ratio_every_backend(self, trained_gwm):
        params, cfg, vol, labels = trained_gwm
        x = vol[None]
        ref_seg = jnp.argmax(executors.apply("xla", params, x, cfg), -1)[0]
        d_ref = _dice(ref_seg.astype(jnp.int32), labels, cfg.num_classes)
        assert d_ref > 0.4, f"training failed to produce a usable model: {d_ref}"
        for backend in SINGLE_DEVICE_BACKENDS:
            for prec in ("bf16", "int8w"):
                seg = jnp.argmax(
                    executors.apply(backend, params, x, cfg, precision=prec), -1
                )[0]
                d = _dice(seg.astype(jnp.int32), labels, cfg.num_classes)
                assert d >= 0.99 * d_ref, (backend, prec, d, d_ref)

    def test_dice_ratio_with_forced_int8_staging(self, trained_gwm):
        params, cfg, vol, labels = trained_gwm
        x = vol[None]
        ref_seg = jnp.argmax(executors.apply("xla", params, x, cfg), -1)[0]
        d_ref = _dice(ref_seg.astype(jnp.int32), labels, cfg.num_classes)
        budget = 4 * 2**20
        pln = megakernel.plan_for_config(
            cfg, x.shape[1:4], vmem_budget=budget, precision="int8w"
        )
        assert len(pln.segments) >= 2, "budget did not force staging"
        got = ops.meshnet_apply_megakernel(
            params, x, cfg, precision="int8w", vmem_budget=budget
        )
        seg = jnp.argmax(got, -1)[0]
        d = _dice(seg.astype(jnp.int32), labels, cfg.num_classes)
        assert d >= 0.99 * d_ref, (d, d_ref)


class TestShardedPrecisionParity:
    """The sharded family per policy: bf16 halos / int8 one-shot fetch
    must reproduce the single-device backend per precision. Multi-device
    claims — skipped below 2 devices (the CI distributed job forces 8)."""

    pytestmark = pytest.mark.skipif(
        jax.device_count() < 2,
        reason="sharded precision parity is a multi-device claim",
    )

    VOL = (16, 8, 8) if SMALL else (32, 12, 12)

    def _slab_counts(self):
        n = jax.device_count()
        return [s for s in (2, 4, 8) if s <= n and self.VOL[0] % s == 0]

    @pytest.mark.parametrize("inner", ["xla", "pallas_fused", "pallas_megakernel"])
    def test_sharded_matches_single_device_per_precision(self, inner):
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        p = meshnet.init(KEY, cfg)
        x = _mri_input((1,) + self.VOL)
        for prec in ("bf16", "int8w"):
            want = _f32(executors.apply(inner, p, x, cfg, precision=prec))
            for n in self._slab_counts():
                got = _f32(
                    executors.apply(
                        executors.sharded_name(inner, n), p, x, cfg, precision=prec
                    )
                )
                # slab schedules re-round at exchange boundaries; allow a
                # few bf16 ulps on top of exact fp32 sharded parity
                np.testing.assert_allclose(got, want, atol=2e-2,
                                           err_msg=f"{inner}@{n}@{prec}")

    def test_collective_bytes_shrink_with_precision(self):
        cfg = MeshNetConfig()
        full = traffic.meshnet_collective_bytes(cfg, (64, 16, 16), 4)
        half = traffic.meshnet_collective_bytes(
            cfg, (64, 16, 16), 4, precision="bf16"
        )
        assert half * 2 == full


class TestTrafficGates:
    """The acceptance numbers, from the analytic models (no compute)."""

    @pytest.mark.parametrize("name", sorted(PAPER_MODELS))
    def test_megakernel_gates_at_paper_volume(self, name):
        # bf16 halves the bytes of a schedule, and its smaller working set
        # leaves the time DP at least the fp32 plan's choices, so its plan
        # is modeled no slower; int8w (planned by bytes) moves under 40%
        cfg = PAPER_MODELS[name]
        vol = (256, 256, 256)
        fp32 = megakernel.plan_for_config(cfg, vol)
        bf16 = megakernel.plan_for_config(cfg, vol, precision="bf16")
        int8 = traffic.meshnet_megakernel_bytes(cfg, vol, precision="int8w")
        at_fp32 = dataclasses.replace(bf16, widths=None)
        assert bf16.hbm_bytes() <= 0.55 * at_fp32.hbm_bytes(), name
        assert bf16.seconds() <= fp32.seconds(), name
        assert int8 <= 0.40 * fp32.hbm_bytes(), (name, int8 / fp32.hbm_bytes())

    def test_fp32_baseline_unchanged_by_precision_planner(self):
        """The finer tile grid and per-role widths must not move the
        committed fp32 numbers (the bench regression gate compares
        like-for-like precision keys)."""
        path = os.path.join(os.path.dirname(__file__), "..", "BENCH_2.json")
        with open(path) as f:
            committed = {
                r["name"]: r["hbm_bytes_modeled"]
                for r in json.load(f)["traffic"]
            }
        for name in ("gwm_light", "subvolume_gwm_failsafe"):
            key = f"hbm_{name}_256_pallas_megakernel"
            if key not in committed:  # baseline regenerated without it
                pytest.skip("no committed fp32 megakernel baseline")
            got = traffic.meshnet_megakernel_bytes(
                PAPER_MODELS[name], (256, 256, 256)
            )
            assert got == committed[key], (name, got, committed[key])

    @pytest.mark.parametrize("backend", ["xla", "pallas_fused", "streaming"])
    def test_layerwise_backends_monotone_in_precision(self, backend):
        cfg = PAPER_MODELS["gwm_light"]
        vol = (64, 64, 64)
        fp32 = traffic.executor_hbm_bytes(backend, cfg, vol)
        bf16 = traffic.executor_hbm_bytes(backend, cfg, vol, precision="bf16")
        int8 = traffic.executor_hbm_bytes(backend, cfg, vol, precision="int8w")
        assert int8 <= bf16 < fp32
        assert bf16 <= 0.55 * fp32

    def test_sharded_bytes_precision_aware(self):
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        vol = (32, 16, 16)
        for inner in ("xla", "pallas_megakernel"):
            full = traffic.meshnet_sharded_bytes(inner, cfg, vol, 4)
            red = traffic.meshnet_sharded_bytes(
                inner, cfg, vol, 4, precision="bf16"
            )
            assert red < full

    def test_vmem_model_derives_from_dtypes(self):
        from repro.kernels import dilated_conv3d as conv_kernel

        wide = conv_kernel.vmem_bytes(16, 21, 21, dilation=8, dtype_bytes=4)
        bf16 = conv_kernel.vmem_bytes(16, 21, 21, dilation=8, dtype_bytes=2)
        int8w = conv_kernel.vmem_bytes(
            16, 21, 21, dilation=8, dtype_bytes=2, weight_bytes=1
        )
        assert int8w < bf16 < wide

    def test_precision_plans_cached_separately(self):
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        a = megakernel.plan_for_config(cfg, (32, 32, 32))
        b = megakernel.plan_for_config(cfg, (32, 32, 32), precision="int8w")
        assert a.widths is None and b.widths is not None
        assert b.hbm_bytes() < a.hbm_bytes()


class TestPipelineAndEngine:
    def _setup(self):
        cfg = MeshNetConfig(dilations=(1, 2, 4))
        params = meshnet.init(KEY, cfg)
        vol, _ = mri.generate(KEY, mri.SyntheticMRIConfig(shape=(16, 16, 16)))
        return cfg, params, vol

    @pytest.mark.parametrize("prec", ["fp32", "bf16", "int8w"])
    @pytest.mark.parametrize("mode", ["full", "subvolume", "streaming"])
    def test_pipeline_serves_every_mode_at_every_precision(self, mode, prec):
        cfg, params, vol = self._setup()
        pc = PipelineConfig(
            model=cfg, volume_shape=(16, 16, 16), mode=mode, cube=8, overlap=4,
            min_component_size=4, executor="xla", precision=prec,
        )
        res = pipeline.run(pc, params, vol)
        assert res.record.status == "ok", res.record.fail_type
        assert res.segmentation.shape == (16, 16, 16)
        assert res.record.precision == prec
        assert res.record.params_bytes == quantize.model_params_bytes(cfg, prec)
        assert res.record.hbm_bytes_modeled > 0

    def test_precision_cuts_modeled_bytes_end_to_end(self):
        cfg, params, vol = self._setup()
        bytes_by_prec = {}
        for prec in ("fp32", "bf16"):
            pc = PipelineConfig(
                model=cfg, volume_shape=(16, 16, 16), mode="full",
                min_component_size=4, executor="xla", precision=prec,
            )
            bytes_by_prec[prec] = pipeline.run(pc, params, vol).record.hbm_bytes_modeled
        assert bytes_by_prec["bf16"] * 2 == bytes_by_prec["fp32"]

    def test_auto_resolves_fp32_on_cpu(self):
        cfg, params, vol = self._setup()
        pc = PipelineConfig(
            model=cfg, volume_shape=(16, 16, 16), mode="full",
            min_component_size=4, executor="xla",
        )
        assert pc.precision == "auto"
        res = pipeline.run(pc, params, vol)
        want = quantize.resolve_precision("auto")
        assert res.record.precision == want

    def test_engine_per_request_precision_and_prepared_cache(self):
        from repro.serving.engine import SegmentationEngine
        from repro.telemetry.budget import MemoryBudget

        cfg, params, vol = self._setup()
        pc = PipelineConfig(
            model=cfg, volume_shape=(16, 16, 16), cube=8, overlap=4,
            min_component_size=4,
        )
        engine = SegmentationEngine(
            params, pc, budget=MemoryBudget(8 * 1024 * 1024, name="tight")
        )
        results = engine.submit_many(
            [vol, vol, vol], precisions=[None, "bf16", "int8w"]
        )
        assert [r.record.status for r in results] == ["ok"] * 3
        assert results[1].record.precision == "bf16"
        assert results[2].record.precision == "int8w"
        # prepared-params cache: one pytree per policy, reused on repeat
        assert engine._params_for("int8w") is engine._params_for("int8w")
        again = engine.submit(vol, precision="int8w")
        assert again.record.precision == "int8w"

    def test_precision_summary_rollup(self):
        from repro.serving.engine import SegmentationEngine
        from repro.telemetry import analysis
        from repro.telemetry.budget import MemoryBudget

        cfg, params, vol = self._setup()
        pc = PipelineConfig(
            model=cfg, volume_shape=(16, 16, 16), cube=8, overlap=4,
            min_component_size=4, executor="xla",
        )
        engine = SegmentationEngine(
            params, pc, budget=MemoryBudget(8 * 1024 * 1024, name="tight")
        )
        engine.submit_many([vol, vol], precisions=["bf16", "bf16"])
        engine.submit(vol, precision="int8w")
        cells = {
            (s.executor, s.precision): s
            for s in analysis.precision_summary(engine.log.records)
        }
        assert cells[("xla", "bf16")].runs == 2
        assert cells[("xla", "int8w")].runs == 1
        assert cells[("xla", "int8w")].mean_params_bytes < cells[
            ("xla", "bf16")
        ].mean_params_bytes
        assert cells[("xla", "bf16")].ok_rate == 1.0
