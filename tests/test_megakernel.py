"""Depth-first megakernel: parity with the XLA reference across the paper
model zoo (including the volume-boundary band the sub-volume path gets
wrong — the in-tile masking must reproduce per-layer 'same' padding), the
planner's VMEM discipline, and the modeled-traffic claims of
EXPERIMENTS.md §Perf H9."""

import dataclasses

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
        # The megakernel's plan is the DP's minimum of modeled device time
        # over every segmentation, the per-layer one (pallas_fused)
        # included, so at the paper volume it is modeled no slower than
        # the per-layer plan, for the C=5 headline models.
        vol = (256, 256, 256)
        for name in ("gwm_light", "brain_mask_fast", "extract_brain_fast"):
            cfg = PAPER_MODELS[name]
            fused = megakernel.plan_for_config(cfg, vol, per_layer=True)
            mega = megakernel.plan_for_config(cfg, vol)
            assert mega.seconds() <= fused.seconds(), (name, mega, fused)

    def test_ordering_views_worst_fused_middle_mega_best(self):
        # the 27-view schedule moves the most bytes; the per-layer fused
        # plan is modeled no faster than the megakernel's
        cfg = PAPER_MODELS["gwm_light"]
        vol = (256, 256, 256)
        views = traffic.meshnet_views_bytes(cfg, vol)
        fused = traffic.meshnet_fused_bytes(cfg, vol)
        mega = traffic.meshnet_megakernel_bytes(cfg, vol)
        assert views > fused and views > mega
        fused_s = megakernel.plan_for_config(cfg, vol, per_layer=True).seconds()
        assert fused_s >= megakernel.plan_for_config(cfg, vol).seconds()

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


def _hand_plan(cfg, vol, segments, widths=None):
    """A MegakernelPlan from ``[(dilations, tile), ...]``, the head fused
    into the last segment."""
    segs, start, n = [], 0, len(cfg.dilations)
    for dils, tile in segments:
        stop = start + len(dils)
        segs.append(megakernel.Segment(
            start=start, dilations=tuple(dils),
            cin=cfg.in_channels if start == 0 else cfg.channels,
            channels=cfg.channels, tile=tuple(tile),
            fuse_head=stop == n, num_classes=cfg.num_classes,
        ))
        start = stop
    assert start == n
    return megakernel.MegakernelPlan(
        segments=tuple(segs), vol=vol, vmem_budget=megakernel.VMEM_BUDGET,
        widths=widths,
    )


def _kernel_seconds(pln):
    """Modeled device seconds of the plan's pallas_calls alone (the layout
    conversions left out, as the trace's kernel events leave them out)."""
    return sum(
        megakernel._segment_seconds(
            seg, pln.padded(seg), pln.vol[2],
            megakernel._segment_hbm_bytes(seg, pln.padded(seg), pln.vol[2], 4, pln.widths),
        )
        for seg in pln.segments
    )


#: the cells' configs at their shapes
CELL_SHAPES = (
    ("gwm_light", (256, 256, 256)),
    ("gwm_large", (256, 256, 256)),
    ("atlas_104", (192, 192, 192)),
)


class TestTimeObjective:
    def test_positions_of_a_hand_checked_segment(self):
        # two layers d = 1, 2 on a 16^3 volume, tile (8, 8): halo 3, so
        # the buffers are 14, 12 and 8 rows; 16 voxels ride 128 lanes
        seg = megakernel.Segment(start=0, dilations=(1, 2), cin=1, channels=5, tile=(8, 8))
        conv, head = megakernel._tile_positions(seg, 16)
        # layer 0: 12 planes x 1 block of 12 rows x 128 lanes x 8 sublanes
        # layer 1:  8 planes x 1 block of  8 rows x 128 lanes x 8 sublanes
        assert conv == 12 * 12 * 128 * 8 + 8 * 8 * 128 * 8 == 212_992
        assert head == 0
        cfg = MeshNetConfig(dilations=(1, 2), channels=5)
        pln = _hand_plan(cfg, (16, 16, 16), [((1, 2), (8, 8))])
        # 2 x 2 tiles against 2 layers of 16 x 16 x 128 x 8
        assert pln.recompute == 4 * 212_992 / (2 * 16 * 16 * 128 * 8) == 1.625

    def test_positions_count_the_clamped_row_block(self):
        # 16 sublanes at 256 lanes: a row block holds 4 rows under the
        # 2 MiB stack cap, so 10 rows take 3 blocks, the last clamped
        seg = megakernel.Segment(start=1, dilations=(1,), cin=10, channels=10,
                                 tile=(8, 10), fuse_head=True, num_classes=104)
        assert megakernel._row_block(10, 10, 256) == 4
        conv, head = megakernel._tile_positions(seg, 256)
        assert conv == 8 * 3 * 4 * 256 * 16
        assert head == 8 * 3 * 4 * 256

    def test_seconds_are_positions_and_bytes(self):
        seg = megakernel.Segment(start=0, dilations=(1, 2), cin=1, channels=5,
                                 tile=(8, 8), fuse_head=True, num_classes=104)
        conv, head = megakernel._tile_positions(seg, 16)
        got = megakernel._segment_seconds(seg, (16, 16), 16, 10**9, batch=2)
        work = 2 * 4 * (conv + head * 104 / 27)
        assert got == pytest.approx(work * megakernel.T_POS + 1e9 / megakernel.HBM_BW)

    @pytest.mark.parametrize("name,vol", CELL_SHAPES)
    def test_cell_plans_beat_one_segment_and_per_layer(self, name, vol):
        cfg = PAPER_MODELS[name]
        pln = megakernel.plan_for_config(cfg, vol, precision="bf16")
        per_layer = megakernel.plan_for_config(cfg, vol, precision="bf16", per_layer=True)
        whole = min(
            (_hand_plan(cfg, vol, [(cfg.dilations, (tz, ty))], pln.widths)
             for tz in (8, 16, 32) for ty in (8, 16, 32)),
            key=lambda p: p.seconds(),
        )
        assert pln.seconds() <= per_layer.seconds()
        assert pln.seconds() <= whole.seconds()
        assert pln.recompute <= 1.15, pln

    @pytest.mark.parametrize(
        "name,vol,segments,ms",
        [
            # measured on a v5e, the cohort cells' traced 51 s windows in
            # PERF_LEDGER.jsonl while these plans served: the summed
            # %meshnet_apply.* device seconds over (median volumes_per_s
            # x 51 s)
            ("gwm_light", (256, 256, 256),
             [((1, 2, 4, 8), (32, 56)), ((16,), (64, 64)), ((8, 4, 2, 1), (32, 64))],
             (18.767 + 11.271 + 2.847) / (8.2904 * 51) * 1e3),
            ("gwm_large", (256, 256, 256),
             [((1, 2, 4, 8), (24, 32)), ((16,), (32, 56)), ((8, 4, 2, 1), (24, 32))],
             (22.278 + 15.527 + 3.337) / (4.7489 * 51) * 1e3),
            ("atlas_104", (192, 192, 192),
             [((1, 2, 4, 8), (16, 24)), ((16,), (32, 32)), ((8,), (32, 48)), ((4, 2), (24, 24))],
             (26.177 + 7.215 + 3.262 + 2.821) / (5.6478 * 51) * 1e3),
        ],
    )
    def test_model_fits_the_byte_plans_measured_times(self, name, vol, segments, ms):
        # the plans the bytes objective served before the time objective
        cfg = PAPER_MODELS[name]
        widths = megakernel.plan_widths("bf16")
        pln = _hand_plan(cfg, vol, segments, widths)
        assert _kernel_seconds(pln) * 1e3 == pytest.approx(ms, rel=0.15)

    @pytest.mark.parametrize("name,vol", CELL_SHAPES)
    def test_record_carries_the_served_plans_recompute(self, name, vol):
        # stamped from the cached plan before any device work
        from repro.core import pipeline
        from repro.core.pipeline import PipelineConfig
        from repro.telemetry.record import StageTimes

        cfg = PAPER_MODELS[name]
        pc = PipelineConfig(model=cfg, volume_shape=vol, executor="pallas_megakernel",
                            precision="bf16")
        rec = pipeline._plan(pc, StageTimes(), None)
        pln = megakernel.plan_for_config(cfg, vol, precision="bf16")
        assert rec.recompute_modeled == pln.recompute <= 1.15
        pc = dataclasses.replace(pc, executor="xla")
        assert pipeline._plan(pc, StageTimes(), None).recompute_modeled is None


def test_schedule_does_not_move_a_bf16_logit():
    # Under bf16 every layer's output is rounded to bf16 whether it stays
    # in VMEM (ping/pong) or crosses HBM (staging), so one deep segment
    # and one segment per layer give the same logits.
    cfg = MeshNetConfig(dilations=FULL_SCHEDULE)
    vol = ODD_SHAPE[1:4]
    widths = megakernel.plan_widths("bf16")
    deep = _hand_plan(cfg, vol, [(FULL_SCHEDULE, (16, 16))], widths)
    per_layer = _hand_plan(cfg, vol, [((d,), (16, 16)) for d in FULL_SCHEDULE], widths)
    p = meshnet.init(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), ODD_SHAPE)
    got = [
        megakernel.meshnet_apply(p, x, cfg, interpret=True, precision="bf16", pln=pln)
        for pln in (deep, per_layer)
    ]
    np.testing.assert_array_equal(np.asarray(got[0], np.float32), np.asarray(got[1], np.float32))
