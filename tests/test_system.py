"""End-to-end behaviour tests for the Brainchop/MeshNet system."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import components, conform, cropping, meshnet, patching, pipeline, streaming
from repro.core.meshnet import MeshNetConfig, PAPER_MODELS
from repro.core.pipeline import PipelineConfig
from repro.data import mri
from repro.telemetry.budget import BudgetExceeded, MemoryBudget
from repro.training import losses


KEY = jax.random.PRNGKey(0)


class TestMeshNet:
    def test_paper_param_counts(self):
        # Table IV: GWM light = 5598 params; subvolume failsafe = 96078.
        assert PAPER_MODELS["gwm_light"].param_count() == 5598
        assert PAPER_MODELS["subvolume_gwm_failsafe"].param_count() == 96078

    def test_forward_shape_and_finite(self):
        cfg = MeshNetConfig()
        p = meshnet.init(KEY, cfg)
        x = jax.random.normal(KEY, (2, 16, 16, 16))
        out = meshnet.apply(p, x, cfg)
        assert out.shape == (2, 16, 16, 16, 3)
        assert bool(jnp.isfinite(out).all())

    def test_receptive_field_matches_dilation_schedule(self):
        # A unit impulse must influence exactly +-46 voxels (RF radius =
        # sum(dilations) = 46) along each axis.
        cfg = MeshNetConfig(use_batchnorm=False)
        p = meshnet.init(KEY, cfg)
        n = 96
        x0 = jnp.zeros((1, n, 3, 3))
        x1 = x0.at[0, n // 2, 1, 1].set(1.0)
        d = jnp.abs(meshnet.apply(p, x1, cfg) - meshnet.apply(p, x0, cfg))[0, :, 1, 1, :].sum(-1)
        touched = np.nonzero(np.asarray(d) > 0)[0]
        assert touched.min() >= n // 2 - patching.MESHNET_RF_RADIUS
        assert touched.max() <= n // 2 + patching.MESHNET_RF_RADIUS

    def test_streaming_matches_plain(self):
        cfg = MeshNetConfig()
        p = meshnet.init(KEY, cfg)
        x = jax.random.normal(KEY, (1, 12, 12, 12))
        np.testing.assert_allclose(
            np.asarray(meshnet.apply(p, x, cfg)),
            np.asarray(streaming.streaming_apply(p, x, cfg)),
            atol=1e-4,
        )


class TestPatching:
    def test_subvolume_inference_exact_in_interior(self):
        """Failsafe mode with overlap >= RF radius is numerically exact for
        every voxel at distance >= RF from the VOLUME boundary. (Boundary
        bands differ by 'same'-padding semantics — the paper's sub-volume
        accuracy loss; see core/patching.py.)"""
        cfg = MeshNetConfig(dilations=(1, 2, 4), use_batchnorm=True)
        rf = sum(cfg.dilations)
        p = meshnet.init(KEY, cfg)
        vol = jax.random.normal(KEY, (24, 24, 24))

        @jax.jit
        def infer(c):
            return meshnet.apply(p, c, cfg)

        full = meshnet.apply(p, vol[None], cfg)[0]
        patched = patching.subvolume_inference(vol, infer, cube=8, overlap=rf)
        s = slice(rf, -rf)
        np.testing.assert_allclose(
            np.asarray(full[s, s, s]), np.asarray(patched[s, s, s]), atol=1e-4
        )
        # and the boundary band genuinely differs (the documented loss)
        assert float(jnp.abs(full - patched).max()) > 1e-3

    def test_insufficient_overlap_is_inexact(self):
        """With overlap < RF the merge has border error — the paper's
        observed sub-volume accuracy loss."""
        cfg = MeshNetConfig(dilations=(1, 2, 4), use_batchnorm=False)
        p = meshnet.init(KEY, cfg)
        vol = jax.random.normal(KEY, (24, 24, 24))

        @jax.jit
        def infer(c):
            return meshnet.apply(p, c, cfg)

        full = meshnet.apply(p, vol[None], cfg)[0]
        patched = patching.subvolume_inference(vol, infer, cube=8, overlap=0)
        err = float(jnp.abs(full - patched).max())
        assert err > 1e-3

    def test_memory_model_ordering(self):
        cfg = MeshNetConfig()
        full = patching.memory_bytes_full_volume((256,) * 3, cfg.channels, cfg.num_classes)
        sub = patching.memory_bytes_subvolume(64, 46, cfg.channels, cfg.num_classes)
        assert sub < full  # patching exists to fit smaller budgets


class TestComponents:
    def test_two_components(self):
        mask = np.zeros((10, 10, 10), bool)
        mask[1:3, 1:3, 1:3] = True
        mask[6:9, 6:9, 6:9] = True
        labels = components.connected_components(jnp.asarray(mask))
        ids = np.unique(np.asarray(labels))
        assert (ids >= 0).sum() == 2

    def test_largest_component(self):
        mask = np.zeros((10, 10, 10), bool)
        mask[1:3, 1:3, 1:3] = True  # 8 voxels
        mask[5:9, 5:9, 5:9] = True  # 64 voxels
        big = components.largest_component(jnp.asarray(mask))
        assert int(big.sum()) == 64

    def test_filter_segmentation_removes_noise(self):
        seg = np.zeros((12, 12, 12), np.int32)
        seg[2:8, 2:8, 2:8] = 1  # big region: keep
        seg[10, 10, 10] = 1  # single-voxel noise: drop
        out = components.filter_segmentation(jnp.asarray(seg), num_classes=2, min_size=4)
        assert int(out[10, 10, 10]) == 0
        assert int(out[4, 4, 4]) == 1

    def test_6_connectivity(self):
        # Diagonal voxels are NOT connected under face adjacency.
        mask = np.zeros((4, 4, 4), bool)
        mask[0, 0, 0] = True
        mask[1, 1, 1] = True
        labels = components.connected_components(jnp.asarray(mask))
        assert labels[0, 0, 0] != labels[1, 1, 1]


class TestConformAndCropping:
    def test_conform_output_range_and_shape(self):
        vol = jax.random.normal(KEY, (20, 28, 24)) * 50 + 100
        out = conform.conform(vol, (32, 32, 32))
        assert out.shape == (32, 32, 32)
        assert float(out.min()) >= 0.0 and float(out.max()) <= 1.0

    def test_resample_identity(self):
        vol = jax.random.normal(KEY, (16, 16, 16))
        out = conform.resample(vol, (16, 16, 16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(vol), atol=1e-5)

    def test_crop_uncrop_roundtrip(self):
        vol = jax.random.normal(KEY, (32, 32, 32))
        mask = jnp.zeros((32, 32, 32), bool).at[8:20, 10:22, 6:18].set(True)
        crop, start = cropping.crop_to(vol, mask, (16, 16, 16))
        assert crop.shape == (16, 16, 16)
        back = cropping.uncrop(crop, start, (32, 32, 32))
        s = tuple(int(v) for v in start)
        np.testing.assert_allclose(
            np.asarray(back[s[0] : s[0] + 16, s[1] : s[1] + 16, s[2] : s[2] + 16]),
            np.asarray(crop),
        )

    def test_pick_crop_size_ladder(self):
        mask = jnp.zeros((64, 64, 64), bool).at[20:40, 20:40, 20:40].set(True)
        size = cropping.pick_crop_size(mask, ladder=((16,) * 3, (32,) * 3, (64,) * 3))
        assert size == (32, 32, 32)


class TestPipeline:
    def _setup(self):
        cfg = MeshNetConfig()
        params = meshnet.init(KEY, cfg)
        vol, _ = mri.generate(KEY, mri.SyntheticMRIConfig(shape=(32, 32, 32)))
        return cfg, params, vol

    @pytest.mark.parametrize("mode", ["full", "streaming", "subvolume"])
    def test_modes_produce_segmentation(self, mode):
        cfg, params, vol = self._setup()
        pc = PipelineConfig(
            model=cfg, volume_shape=(32, 32, 32), mode=mode, cube=16, overlap=8,
            min_component_size=4,
        )
        res = pipeline.run(pc, params, vol)
        assert res.record.status == "ok"
        assert res.segmentation.shape == (32, 32, 32)
        assert res.record.times.inference > 0

    def test_budget_failure_recorded_not_raised(self):
        cfg, params, vol = self._setup()
        pc = PipelineConfig(model=cfg, volume_shape=(32, 32, 32), budget=MemoryBudget(1))
        res = pipeline.run(pc, params, vol)
        assert res.record.status == "fail"
        assert res.record.fail_type == "full_volume_oom"
        assert res.segmentation is None

    def test_budget_interventions_match_paper_ordering(self):
        """Tables V/VI: at a budget that kills full-volume, streaming and
        sub-volume (failsafe) still succeed — the patching intervention."""
        cfg = MeshNetConfig()
        shape = (64, 64, 64)
        budget = MemoryBudget(24 * 1024 * 1024)  # 24 MiB
        with pytest.raises(BudgetExceeded):
            budget.charge_inference(shape, cfg)
        assert budget.charge_streaming(shape, cfg) > 0
        assert budget.charge_subvolume(16, 8, cfg) > 0


class TestServingFaults:
    """Fault injection on the serving path (serving/scheduler.py): a bad
    request inside a batch must fail ALONE, with a typed telemetry
    record, while the rest of the batch completes — the serving-tier
    version of the paper's 'telemetry over crashes' stance."""

    def _engine(self):
        from repro.serving.engine import SegmentationEngine

        cfg = MeshNetConfig(dilations=(1, 2, 4), channels=5)
        params = meshnet.init(KEY, cfg)
        pc = PipelineConfig(
            model=cfg, volume_shape=(16, 16, 16), cube=8, overlap=4,
            min_component_size=4, executor="xla",
        )
        return SegmentationEngine(params, pc)

    def _vols(self, n):
        return [
            mri.generate(
                jax.random.PRNGKey(i), mri.SyntheticMRIConfig(shape=(16, 16, 16))
            )[0]
            for i in range(n)
        ]

    def test_executor_raising_mid_batch_fails_only_that_request(self, monkeypatch):
        engine = self._engine()
        vols = self._vols(3)
        poison = vols[1]
        real_run = pipeline.run

        def flaky_run(cfg, params, vol, **kw):
            if vol is poison:
                raise RuntimeError("injected executor fault")
            return real_run(cfg, params, vol, **kw)

        monkeypatch.setattr(pipeline, "run", flaky_run)
        results = engine.submit_many(vols)
        assert [r.record.status for r in results] == ["ok", "fail", "ok"]
        # an unclassified RuntimeError is conservatively permanent
        # (serving/errors.py classify): retrying an unknown fault burns
        # capacity exactly when the service is least healthy
        assert results[1].record.fail_type == "permanent_fault"
        assert "injected executor fault" in results[1].record.extra["error"]
        assert results[1].segmentation is None
        for i in (0, 2):
            assert results[i].segmentation.shape == (16, 16, 16)

    def test_garbage_volume_in_batch_fails_typed(self):
        engine = self._engine()
        vols = self._vols(2)
        batch = [vols[0], jnp.zeros((7,)), vols[1]]  # 1-D garbage mid-batch
        results = engine.submit_many(batch)
        assert [r.record.status for r in results] == ["ok", "fail", "ok"]
        assert results[1].record.fail_type == "permanent_fault"
        # the fleet ledger conserved: all three requests have records
        assert len(engine.log.records) == 3

    def test_geometry_failure_in_batch_is_isolated(self):
        """A request pinning more slab devices than the host has fails
        with the pipeline's typed shard_geometry record (never raises),
        and its batch neighbours complete."""
        if jax.device_count() > 2:
            pytest.skip("needs a host with <= 2 devices to force the failure")
        engine = self._engine()
        vols = self._vols(2)
        results = engine.submit_many(
            [vols[0], vols[1]], devices=[None, 3],
        )
        assert results[0].record.status == "ok"
        assert results[1].record.status == "fail"
        assert results[1].record.fail_type == "shard_geometry"

    def test_queue_full_backpressure_is_typed(self):
        from repro.serving.scheduler import QueueFullError, SchedulerConfig

        engine = self._engine()
        engine.scheduler(SchedulerConfig(max_queue_depth=1))
        engine.submit_async(self._vols(1)[0])
        with pytest.raises(QueueFullError):
            engine.submit_async(self._vols(1)[0])
        comps = engine.drain()
        assert len(comps) == 1 and comps[0].outcome == "completed"
        # the refusal is in the fleet telemetry, typed
        assert any(r.fail_type == "queue_full" for r in engine.log.records)


class TestFleetFaults:
    """Fault injection on the replicated fleet (serving/fleet.py): a
    raising replica must isolate to its own dispatch group, an
    all-draining router must refuse with a TYPED error, and
    scale-to-zero must be rejected at configuration time — the fleet
    tier's version of 'telemetry over crashes'."""

    def _engine(self):
        from repro.serving.engine import SegmentationEngine

        cfg = MeshNetConfig(dilations=(1, 2, 4), channels=5)
        params = meshnet.init(KEY, cfg)
        pc = PipelineConfig(
            model=cfg, volume_shape=(16, 16, 16), cube=8, overlap=4,
            min_component_size=4, executor="xla",
        )
        return SegmentationEngine(params, pc)

    def _fleet(self, replicas=2, execute=False, **cfg_kwargs):
        from repro.serving.fleet import Fleet, FleetConfig

        return Fleet(
            FleetConfig(replicas=replicas, execute=execute, **cfg_kwargs),
            engine_factory=self._engine,
        )

    def test_replica_raising_mid_batch_isolates_to_that_replica(self, monkeypatch):
        """An executor fault on one replica fails ONE request with a
        typed record; its group neighbours and the other replica's
        requests complete — and the fleet ledger still conserves."""
        fleet = self._fleet(replicas=2, execute=True, policy="round_robin")
        vols = [
            mri.generate(
                jax.random.PRNGKey(i), mri.SyntheticMRIConfig(shape=(16, 16, 16))
            )[0]
            for i in range(4)
        ]
        poison = vols[1]
        real_run = pipeline.run

        def flaky_run(cfg, params, vol, **kw):
            if vol is poison:
                raise RuntimeError("injected replica fault")
            return real_run(cfg, params, vol, **kw)

        monkeypatch.setattr(pipeline, "run", flaky_run)
        for v in vols:
            fleet.submit(v)
        fleet.drain()
        assert fleet.conserved()
        served = sorted(
            (e for e in fleet.ledger), key=lambda e: e.fid
        )
        records = [e.completion.record for e in served]
        assert [r.status for r in records] == ["ok", "fail", "ok", "ok"]
        assert records[1].fail_type == "permanent_fault"
        assert "injected replica fault" in records[1].extra["error"]
        # the fault stayed on the replica that served it; both replicas
        # still completed their groups
        assert {r.replica_id for r in records} == {0, 1}

    def test_router_with_all_replicas_draining_refuses_typed(self):
        from repro.serving.fleet import NoReplicaAvailable

        fleet = self._fleet(replicas=2)
        fleet.drain_replica(0)
        fleet.drain_replica(1)
        with pytest.raises(NoReplicaAvailable) as ei:
            fleet.submit(np.zeros((16, 16, 16), np.float32))
        assert ei.value.total == 2
        assert ei.value.draining == 2
        assert ei.value.crashed == 0
        # the refusal is ledgered as a typed terminal outcome
        assert fleet.ledger[-1].outcome == "no_replica"
        assert fleet.no_replica == 1

    def test_autoscaler_scale_to_zero_rejected_typed(self):
        from repro.serving.fleet import (
            AutoscalerConfig,
            Fleet,
            FleetConfig,
            FleetConfigError,
        )

        # at configuration time: a floor below one replica is an outage
        with pytest.raises(FleetConfigError, match="min_replicas"):
            Fleet(
                FleetConfig(
                    replicas=1,
                    autoscaler=AutoscalerConfig(min_replicas=0),
                ),
                engine_factory=self._engine,
            )
        with pytest.raises(FleetConfigError, match=">= 1 replica"):
            Fleet(FleetConfig(replicas=0), engine_factory=self._engine)
        # at runtime: draining the last routable replica is refused
        fleet = self._fleet(replicas=1)
        with pytest.raises(FleetConfigError, match="scale-to-zero"):
            fleet.scale_down()
        assert fleet.replicas[0].routable  # refusal left the fleet intact


class TestLosses:
    def test_dice_perfect_and_disjoint(self):
        a = jnp.ones((8, 8, 8), jnp.int32)
        assert float(losses.dice_score(a, a, 2)) == 1.0
        b = jnp.zeros((8, 8, 8), jnp.int32)
        assert float(losses.dice_score(a, b, 2)) == 0.0

    def test_cross_entropy_matches_manual(self):
        logits = jax.random.normal(KEY, (4, 5))
        labels = jnp.asarray([0, 1, 2, 3])
        manual = -jnp.mean(
            jnp.take_along_axis(jax.nn.log_softmax(logits), labels[:, None], axis=1)
        )
        np.testing.assert_allclose(
            float(losses.cross_entropy(logits, labels)), float(manual), rtol=1e-6
        )

    def test_soft_dice_gradient_direction(self):
        logits = jnp.zeros((4, 4, 4, 2))
        labels = jnp.ones((4, 4, 4), jnp.int32)
        g = jax.grad(lambda l: losses.soft_dice_loss(l, labels, 2))(logits)
        # pushing class-1 logits up must reduce the loss
        assert float(g[..., 1].sum()) < 0


class TestTrainingIntegration:
    def test_meshnet_learns_synthetic_gwm(self):
        """Short CPU training run reaches a meaningful held-out Dice and a
        large improvement over chance; examples/train_meshnet.py runs the
        full few-hundred-step version (Dice keeps climbing past 0.8).

        Fully deterministic: the explicit seed pins init, data order and
        eval subjects, so the Dice trajectory is reproducible run-to-run
        (seed 1 reaches ~0.70 held-out Dice in 60 CPU steps; the bar is
        0.5 to absorb cross-platform float drift). This is what lets CI
        run the test instead of deselecting it."""
        from repro.training import trainer

        cfg = trainer.TrainConfig(
            model=MeshNetConfig(channels=5, dropout_rate=0.0),
            data=mri.DataLoaderConfig(
                mri=mri.SyntheticMRIConfig(shape=(24, 24, 24)), batch_size=2
            ),
            steps=60,
            eval_subjects=2,
            log_every=1000,
            seed=1,
        )
        res = trainer.train(cfg, verbose=False)
        assert res.final_dice > 0.5, res.final_dice
        first_dice = res.history[0]["dice"]
        assert res.final_dice > first_dice + 0.25, (first_dice, res.final_dice)

    def test_checkpoint_roundtrip(self, tmp_path):
        from repro.training import checkpoint as ck
        from repro.training import optimizer as opt

        cfg = MeshNetConfig()
        params = meshnet.init(KEY, cfg)
        state = opt.adamw_init(params, opt.AdamWConfig())
        ck.save(str(tmp_path / "c"), {"params": params, "opt": state}, step=7)
        tree, manifest = ck.restore(str(tmp_path / "c"))
        assert manifest["step"] == 7
        before = jax.tree.leaves({"params": params, "opt": state})
        after = jax.tree.leaves(tree)
        assert len(before) == len(after)
        for a, b in zip(before, after):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert isinstance(tree["opt"], opt.AdamWState)
