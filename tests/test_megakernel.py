"""Depth-first megakernel: parity with the XLA reference across the paper
model zoo (including the volume-boundary band the sub-volume path gets
wrong — the in-tile masking must reproduce per-layer 'same' padding), the
planner's VMEM discipline, and the modeled-traffic claims of
EXPERIMENTS.md §Perf H9."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executors, meshnet
from repro.core.meshnet import MeshNetConfig, PAPER_MODELS
from repro.kernels import megakernel
from repro.telemetry import traffic

KEY = jax.random.PRNGKey(11)

# Small odd (non-block-multiple) spatial shape: exercises tile padding,
# halo masking at every face, and multi-segment staging, while keeping
# interpret-mode Pallas runtime tolerable on CPU.
ODD_SHAPE = (1, 10, 12, 14)

SMALL = MeshNetConfig(dilations=(1, 2, 4))

#: the paper's full Table-I schedule — forces a multi-segment plan on CPU.
FULL_SCHEDULE = (1, 2, 4, 8, 16, 8, 4, 2, 1)


def _parity(cfg: MeshNetConfig, shape=ODD_SHAPE, atol=1e-4, seed=3):
    p = meshnet.init(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), shape)
    got = executors.apply("pallas_megakernel", p, x, cfg)
    expect = executors.apply("xla", p, x, cfg)
    assert got.shape == expect.shape == shape + (cfg.num_classes,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=atol)


class TestParity:
    """ops.meshnet_apply_megakernel == meshnet.apply (eval) to <= 1e-4."""

    @pytest.mark.parametrize("name", sorted(PAPER_MODELS))
    def test_paper_models(self, name):
        _parity(PAPER_MODELS[name])

    def test_full_dilation_schedule_multi_segment(self):
        cfg = MeshNetConfig(dilations=FULL_SCHEDULE)
        pln = megakernel.plan_for_config(cfg, ODD_SHAPE[1:4])
        assert len(pln.segments) > 1  # the halo cannot fit in one segment
        _parity(cfg)

    def test_no_batchnorm(self):
        _parity(MeshNetConfig(use_batchnorm=False))

    def test_nontrivial_bn_stats(self):
        # Fold-correctness is invisible with init stats (mean 0 / var 1).
        cfg = SMALL
        p = meshnet.init(KEY, cfg)
        k = jax.random.PRNGKey(5)
        for layer in p["layers"]:
            k, k1, k2 = jax.random.split(k, 3)
            layer["bn_mean"] = jax.random.normal(k1, layer["bn_mean"].shape) * 0.3
            layer["bn_var"] = 0.5 + jax.random.uniform(k2, layer["bn_var"].shape)
        x = jax.random.normal(jax.random.PRNGKey(6), ODD_SHAPE)
        got = executors.apply("pallas_megakernel", p, x, cfg)
        expect = executors.apply("xla", p, x, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)

    @pytest.mark.parametrize("shape", [(1, 16, 16, 16), (2, 9, 17, 13)])
    def test_block_multiple_and_batched_odd(self, shape):
        _parity(SMALL, shape=shape)

    def test_registry_jitted_dispatch(self):
        # the exact cached callable pipeline/engine serve with
        p = meshnet.init(KEY, SMALL)
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, 8, 8))
        got = executors.jitted_apply("pallas_megakernel")(p, x, SMALL)
        expect = meshnet.apply(p, x, SMALL)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)


class TestPlanner:
    def test_segments_partition_schedule(self):
        cfg = MeshNetConfig(dilations=FULL_SCHEDULE)
        pln = megakernel.plan_for_config(cfg, (256, 256, 256))
        covered = []
        for seg in pln.segments:
            assert seg.start == len(covered)
            covered.extend(seg.dilations)
        assert tuple(covered) == FULL_SCHEDULE
        # only the last segment fuses the head
        assert [s.fuse_head for s in pln.segments] == (
            [False] * (len(pln.segments) - 1) + [True]
        )

    def test_working_sets_fit_budget(self):
        for name in ("gwm_light", "subvolume_gwm_failsafe", "atlas_104"):
            pln = megakernel.plan_for_config(PAPER_MODELS[name], (256, 256, 256))
            for seg in pln.segments:
                assert megakernel._segment_vmem_bytes(seg) <= pln.vmem_budget

    def test_halo_arithmetic_final_tile_exact(self):
        # S_0 = tile + 2*halo shrinks by 2d per layer down to exactly tile
        pln = megakernel.plan_for_config(MeshNetConfig(), (64, 64, 64))
        for seg in pln.segments:
            sizes = seg.buffer_sizes()
            assert sizes[0] == tuple(t + 2 * seg.halo for t in seg.tile)
            assert sizes[-1] == seg.tile

    def test_infeasible_budget_raises_with_hint(self):
        with pytest.raises(ValueError, match="megakernel plan infeasible"):
            megakernel.plan_for_config(
                MeshNetConfig(channels=512), (64, 64, 64), vmem_budget=2**20
            )
        # the hint names the layer that cannot fit (here the d=16 one: its
        # halo, not the first layer's, is what breaks a 4 MiB budget) and
        # a need that really is over the budget
        cfg = PAPER_MODELS["subvolume_gwm_failsafe"]
        with pytest.raises(ValueError, match=r"layer 4 \(dilation 16\)") as e:
            megakernel.plan_for_config(cfg, (40, 40, 40), vmem_budget=4 * 2**20,
                                       precision="int8w")
        need = float(str(e.value).split("needs ")[1].split(" MiB")[0])
        assert need > 4.0

    def test_vmem_model_counts_accumulator(self):
        # The per-iteration temporaries (the stacked f32 im2col operand of
        # a row block and its f32 accumulator) are live alongside the
        # static scratch; a plan priced without them would exceed real
        # VMEM on TPU. Buffers are priced at their padded plane size.
        pln = megakernel.plan_for_config(PAPER_MODELS["gwm_light"], (256, 256, 256))
        xp = megakernel.lanes(256)
        for seg in pln.segments:
            sizes = seg.buffer_sizes()
            window = sizes[0][0] * sizes[0][1] * xp * megakernel.rows(seg.cin) * 4
            rows = megakernel._row_block(sizes[1][1], seg.cin, xp)
            stack = 27 * megakernel.rows(seg.cin) * rows * xp * 4
            acc = megakernel.rows(seg.channels) * rows * xp * 4
            assert megakernel._segment_vmem_bytes(seg) >= window + stack + acc

    def test_pipeline_reports_infeasible_plan_as_failed_run(self):
        # Never-raises contract: an explicitly requested megakernel whose
        # plan cannot fit VMEM yields a status='fail' telemetry record
        # (fail_type vmem_oom), not an exception out of pipeline.run.
        from repro.core import pipeline
        from repro.core.pipeline import PipelineConfig

        # two 4096-wide layers: the second's tap matrix alone is 1.8 GB
        wide = MeshNetConfig(channels=4096, dilations=(16, 16))
        pc = PipelineConfig(
            model=wide, volume_shape=(64, 64, 64), executor="pallas_megakernel"
        )
        res = pipeline.run(pc, None, jnp.zeros((64, 64, 64)))
        assert res.segmentation is None
        assert res.record.status == "fail"
        assert res.record.fail_type == "vmem_oom"

    def test_tiles_need_not_be_cubes(self):
        # at the paper volume the d=16 layer fits best as a non-cubic tile
        pln = megakernel.plan_for_config(PAPER_MODELS["gwm_light"], (256, 256, 256))
        assert any(len(set(seg.tile)) > 1 for seg in pln.segments)


class TestTrafficModel:
    def test_megakernel_under_fused_at_paper_volume(self):
        # EXPERIMENTS.md §Perf H9, as restated for the plane layout:
        # priced as the layout really moves data (channels padded to 8
        # sublanes, rows to 128 lanes, the layout conversions counted,
        # (Z, Y) halos re-read per tile), the depth-first schedule moves
        # >= 1.3x fewer modeled HBM bytes than the per-layer one for the
        # C=5 headline models.
        vol = (256, 256, 256)
        for name in ("gwm_light", "brain_mask_fast", "extract_brain_fast"):
            cfg = PAPER_MODELS[name]
            fused = traffic.meshnet_fused_bytes(cfg, vol)
            mega = traffic.meshnet_megakernel_bytes(cfg, vol)
            assert fused >= 1.3 * mega, (name, fused / mega)

    def test_ordering_views_worst_fused_middle_mega_best(self):
        cfg = PAPER_MODELS["gwm_light"]
        vol = (256, 256, 256)
        views = traffic.meshnet_views_bytes(cfg, vol)
        fused = traffic.meshnet_fused_bytes(cfg, vol)
        mega = traffic.meshnet_megakernel_bytes(cfg, vol)
        assert views > fused > mega

    def test_registry_exposes_bytes_for_all_builtins(self):
        for name in executors.names():
            if "@" in name:
                # pinned sharded specs ("sharded_xla@64") are registered
                # on demand by requests/tests, not builtins — their slab
                # count need not divide this probe volume
                continue
            b = executors.modeled_hbm_bytes(name, SMALL, (32, 32, 32))
            assert b is not None and b > 0, name

    def test_plan_traffic_matches_model(self):
        cfg = PAPER_MODELS["gwm_light"]
        pln = megakernel.plan_for_config(cfg, (256, 256, 256))
        assert pln.hbm_bytes() == traffic.meshnet_megakernel_bytes(cfg, (256, 256, 256))

    def test_batch_is_subadditive(self):
        # a batched launch streams each weight tensor ONCE (batch loop
        # innermost), so bytes(N) < N*bytes(1): the data terms scale,
        # the weight term does not. Strict — SMALL has nonzero weights.
        b1 = traffic.meshnet_megakernel_bytes(SMALL, (32, 32, 32), batch=1)
        b3 = traffic.meshnet_megakernel_bytes(SMALL, (32, 32, 32), batch=3)
        assert b1 < b3 < 3 * b1
