"""The N-volume batch axis, end to end (ISSUE: batched byte-model fix +
one-launch dispatch groups).

Three claims, each pinned here:

  1. **Numerics** — every backend treats the leading dim as independent
     volumes: member ``m`` of a batched forward equals the unbatched
     forward of volume ``m`` (bit-exact for fp32 xla; <= 1e-4 for the
     Pallas backends and reduced precisions). The grid/vmap mechanics of
     batching must be invisible to accuracy.
  2. **Traffic** — the byte models stream each weight tensor ONCE per
     launch (batch loop innermost), so ``bytes(N) < N * bytes(1)`` with
     a batch-invariant amortized weight term, and ``batch=1`` is
     byte-identical to the pre-batching models (the headline bugfix:
     ``Plan.hbm_bytes`` used to return ``batch * total``, double-counting
     the weight stream N times).
  3. **Serving** — under ``SchedulerConfig.batched_dispatch`` a dispatch
     group is ONE launch: admission prices the group with the weights
     charged once, every member shares the launch's service interval
     while ``queue_wait_s + service_s == finish - arrival`` still holds
     exactly per member, and batch-size-1 traces are unchanged.

The Pallas sweeps run in interpret mode on CPU, so the numeric matrix is
covered economically: fp32 xla runs the full model zoo x batch 1/2/4;
the Pallas backends run every model at batch 4 with the precision
rotating through {fp32, bf16, int8w} across the zoo (every cell of the
backend x precision matrix is exercised without running the full cross
product per model), plus an all-precision batch-1/2 pass on one model.
"""

import math

import jax
import numpy as np
import pytest

from repro.core import executors, meshnet
from repro.core.meshnet import PAPER_MODELS, MeshNetConfig
from repro.core.pipeline import PipelineConfig
from repro.core.spatial_shard import (
    ShardGeometryError,
    auto_batch_shards,
    mesh_for_batched,
)
from repro.kernels import megakernel, quantize
from repro.serving.engine import SegmentationEngine
from repro.serving.scheduler import RequestScheduler, SchedulerConfig
from repro.serving.simulator import (
    ServiceModel,
    VirtualClock,
    preset,
    reference_engine,
    simulate,
)
from repro.telemetry import traffic

KEY = jax.random.PRNGKey(0)
PRECS = ("fp32", "bf16", "int8w")
MODEL_NAMES = tuple(sorted(PAPER_MODELS))
#: rotate the precision through the zoo so every (backend, precision)
#: cell runs without the full per-model cross product
PALLAS_CASES = [(n, PRECS[i % len(PRECS)]) for i, n in enumerate(MODEL_NAMES)]
SHAPE = (8, 8, 8)


def _batched_vs_solo(backend, cfg, prec, batch, atol):
    p = meshnet.init(KEY, cfg)
    xb = jax.random.normal(jax.random.PRNGKey(1), (batch,) + SHAPE)
    yb = np.asarray(executors.apply(backend, p, xb, cfg, precision=prec))
    assert yb.shape == (batch,) + SHAPE + (cfg.num_classes,)
    for m in range(batch):
        ys = np.asarray(
            executors.apply(backend, p, xb[m : m + 1], cfg, precision=prec)
        )[0]
        if atol == 0.0:
            assert np.array_equal(yb[m], ys), f"member {m} not bit-exact"
        else:
            np.testing.assert_allclose(
                np.asarray(yb[m], np.float32),
                np.asarray(ys, np.float32),
                atol=atol,
                err_msg=f"member {m}",
            )


class TestBatchedParity:
    """Member m of a batched forward == the unbatched forward of volume m."""

    @pytest.mark.parametrize("prec", PRECS)
    @pytest.mark.parametrize("name", MODEL_NAMES)
    def test_xla_members_match_solo(self, name, prec):
        # fp32 is bit-exact (batch is a parallel axis, not a reduction);
        # reduced precisions round per element, same tolerance as the
        # backend-parity suite
        atol = 0.0 if prec == "fp32" else 1e-4
        for batch in (1, 2, 4):
            _batched_vs_solo("xla", PAPER_MODELS[name], prec, batch, atol)

    @pytest.mark.parametrize("backend", ("pallas_fused", "pallas_megakernel"))
    @pytest.mark.parametrize("name,prec", PALLAS_CASES)
    def test_pallas_batch4_members_match_solo(self, backend, name, prec):
        _batched_vs_solo(backend, PAPER_MODELS[name], prec, 4, 1e-4)

    @pytest.mark.parametrize("backend", ("pallas_fused", "pallas_megakernel"))
    @pytest.mark.parametrize("prec", PRECS)
    def test_pallas_small_batches_match_solo(self, backend, prec):
        cfg = PAPER_MODELS["gwm_light"]
        for batch in (1, 2):
            _batched_vs_solo(backend, cfg, prec, batch, 1e-4)

    @pytest.mark.skipif(
        jax.device_count() < 2,
        reason="sharded parity is a multi-device claim; CI's distributed "
        "job forces 8 host devices",
    )
    @pytest.mark.parametrize("prec", PRECS)
    def test_sharded_members_match_solo(self, prec):
        cfg = PAPER_MODELS["gwm_light"]
        n = 2
        name = executors.ensure_sharded("xla", n)
        p = meshnet.init(KEY, cfg)
        for batch in (1, 2, 4):
            xb = jax.random.normal(jax.random.PRNGKey(1), (batch, 16, 8, 8))
            yb = np.asarray(executors.apply(name, p, xb, cfg, precision=prec))
            for m in range(batch):
                ys = np.asarray(
                    executors.apply(name, p, xb[m : m + 1], cfg, precision=prec)
                )[0]
                np.testing.assert_allclose(
                    np.asarray(yb[m], np.float32),
                    np.asarray(ys, np.float32),
                    atol=1e-4 if prec != "fp32" else 1e-6,
                )


class TestBatchGeometry:
    """The (batch, Z) mesh helpers are pure geometry — testable anywhere."""

    def test_auto_batch_shards_single_device_host_is_legacy(self):
        # no spare devices -> no batch axis -> the legacy 1-D layout
        assert auto_batch_shards(4, jax.device_count()) == 1

    def test_auto_batch_shards_divides_batch(self):
        # auto sharding must pick a divisor of the batch (non-divisors
        # would need padding the executor contract does not allow)
        for batch in (1, 2, 3, 4, 6, 8):
            k = auto_batch_shards(batch, 1)
            assert batch % k == 0

    def test_mesh_for_batched_rejects_oversubscription(self):
        with pytest.raises(ShardGeometryError):
            mesh_for_batched(jax.device_count() + 1, 1)

    @pytest.mark.skipif(
        jax.device_count() < 4, reason="needs >= 4 devices for a 2x2 mesh"
    )
    def test_mesh_for_batched_axes(self):
        m = mesh_for_batched(2, 2)
        assert m.devices.shape == (2, 2)
        assert m.axis_names == ("b", "z")


class TestBatchedTraffic:
    """bytes(N) < N*bytes(1): the weight stream amortizes; data does not."""

    MODELS = {
        "xla": traffic.meshnet_xla_bytes,
        "pallas_fused": traffic.meshnet_fused_bytes,
        "views": traffic.meshnet_views_bytes,
        "streaming": traffic.meshnet_streaming_bytes,
        "pallas_megakernel": traffic.meshnet_megakernel_bytes,
    }

    @pytest.mark.parametrize("backend", sorted(MODELS))
    @pytest.mark.parametrize("name", MODEL_NAMES)
    def test_subadditive_with_batch_invariant_weight_term(self, backend, name):
        fn = self.MODELS[backend]
        cfg = PAPER_MODELS[name]
        b1 = fn(cfg, (32, 32, 32))
        b2 = fn(cfg, (32, 32, 32), batch=2)
        b4 = fn(cfg, (32, 32, 32), batch=4)
        # strict: every paper model has a nonzero weight stream (equality
        # could only occur for a zero-parameter network)
        assert b1 < b2 < 2 * b1
        assert b2 < b4 < 4 * b1
        # bytes(N) = N*data + weights  =>  N*b1 - bN == (N-1)*weights:
        # the amortized weight term must be the SAME whichever batch
        # size you solve it from — the models agree on what amortized
        w2 = 2 * b1 - b2
        w4 = (4 * b1 - b4) / 3
        assert w2 == pytest.approx(w4)
        assert w2 > 0

    @pytest.mark.parametrize("name", MODEL_NAMES)
    def test_megakernel_batch4_strictly_cheaper_than_4x(self, name):
        # the acceptance criterion verbatim: batch-4 megakernel modeled
        # bytes strictly below 4x batch-1 for every paper model
        cfg = PAPER_MODELS[name]
        b1 = traffic.meshnet_megakernel_bytes(cfg, (256, 256, 256), batch=1)
        b4 = traffic.meshnet_megakernel_bytes(cfg, (256, 256, 256), batch=4)
        assert b4 < 4 * b1

    def test_sharded_inherits_amortization(self):
        cfg = PAPER_MODELS["gwm_light"]
        b1 = traffic.meshnet_sharded_bytes("xla", cfg, (256, 256, 256), 4)
        b4 = traffic.meshnet_sharded_bytes("xla", cfg, (256, 256, 256), 4, batch=4)
        assert b1 < b4 < 4 * b1

    def test_plan_hbm_bytes_batch1_identity(self):
        # the headline bugfix regression: hbm_bytes(batch=1) must equal
        # the committed single-volume number (BENCH batch-1 rows are
        # byte-identical), and the traffic facade must agree with the plan
        cfg = PAPER_MODELS["gwm_light"]
        pln = megakernel.plan_for_config(cfg, (256, 256, 256))
        assert pln.hbm_bytes() == pln.hbm_bytes(batch=1)
        assert pln.hbm_bytes(batch=1) == traffic.meshnet_megakernel_bytes(
            cfg, (256, 256, 256)
        )


class TestBatchedPlanner:
    """The DP co-optimizes tile shape against batch size under VMEM."""

    def test_vmem_constrained_plan_trades_tiles_not_refusal(self):
        # a budget tight enough to force small tiles must still plan at
        # batch 4: the grid iterates one (batch element, tile) at a time,
        # so feasibility is batch-independent — the planner trades tile
        # shape, it never refuses a batch it could serve serially
        cfg = PAPER_MODELS["gwm_light"]
        vol = (64, 64, 64)
        tight = 12 * 1024 * 1024  # a fifth of the 64^3 default-budget plan
        p1 = megakernel.plan(
            cfg.dilations, 1, cfg.channels, cfg.num_classes, vol,
            vmem_budget=tight, batch=1,
        )
        p4 = megakernel.plan(
            cfg.dilations, 1, cfg.channels, cfg.num_classes, vol,
            vmem_budget=tight, batch=4,
        )
        assert p1.segments and p4.segments

    @pytest.mark.parametrize("name", ("gwm_light", "atlas_50"))
    def test_batch_aware_plan_never_worse(self, name):
        # the batch-1 plan's modeled time at batch=4 bounds the
        # co-optimized plan from above: the DP that SAW the batch can
        # only do better
        cfg = PAPER_MODELS[name]
        vol = (128, 128, 128)
        base = megakernel.plan_for_config(cfg, vol)
        opt = megakernel.plan_for_config(cfg, vol, batch=4)
        assert opt.seconds(batch=4) <= base.seconds(batch=4)


def _mk_engine():
    cfg = MeshNetConfig(dilations=(1, 2, 4), channels=5)
    params = meshnet.init(KEY, cfg)
    pc = PipelineConfig(
        model=cfg, volume_shape=(16, 16, 16), cube=8, overlap=4,
        min_component_size=4, executor="xla",
    )
    return SegmentationEngine(params, pc)


def _mk_sched(batched, **cfg_kwargs):
    cfg_kwargs.setdefault("native_shapes", True)
    return RequestScheduler(
        _mk_engine(),
        SchedulerConfig(batched_dispatch=batched, **cfg_kwargs),
        clock=VirtualClock(),
        service_model=ServiceModel(),
        execute=False,
    )


def _stub(shape=(16, 16, 16)):
    return np.zeros(shape, np.float32)


class TestBatchedDispatch:
    """A dispatch group under batched_dispatch is ONE launch."""

    def test_group_admission_prices_weights_once(self):
        # cap sits between the batched-group price (3*work + weights)
        # and the per-member sum (3*(work + weights)): the old summing
        # admission would stop growing the group at two members; pricing
        # the group as one launch fits all three
        sched = _mk_sched(True, max_batch_requests=8)
        for _ in range(3):
            sched.submit(_stub(), arrival_s=0.0)
        per = sched.queue[0].bytes_priced
        w = sched._group_weight_bytes(sched.queue[0].key)
        assert w == quantize.model_params_bytes(sched.engine.cfg.model, "fp32")
        cap = 3 * per - 2 * w + 1  # group price + 1, below the member sum
        assert cap < 3 * per
        sched.cfg.admission_hbm_bytes = cap
        batch = sched.next_batch(now=0.0)
        assert len(batch.requests) == 3

    def test_serialized_admission_would_have_shed(self):
        # the same cap WITHOUT group pricing (weights summed per member)
        # only fits two — the contrast that makes the fix observable
        sched = _mk_sched(True, max_batch_requests=8)
        for _ in range(3):
            sched.submit(_stub(), arrival_s=0.0)
        per = sched.queue[0].bytes_priced
        w = sched._group_weight_bytes(sched.queue[0].key)
        sched.cfg.admission_hbm_bytes = 3 * per - 2 * w + 1
        sched.cfg.batched_dispatch = False  # re-run growth with summing
        batch = sched.next_batch(now=0.0)
        assert len(batch.requests) < 3

    def test_members_share_launch_interval_and_identity_holds(self):
        sched = _mk_sched(True, max_batch_requests=8)
        arrivals = (0.0, 0.1, 0.2)
        for a in arrivals:
            sched.submit(_stub(), arrival_s=a)
        batch = sched.next_batch(now=0.5)
        assert len(batch.requests) == 3
        finish = sched.run_batch(batch, now=0.5)
        comps = sched.completions
        assert len(comps) == 3
        services = {c.record.service_s for c in comps}
        assert len(services) == 1, "members must share the launch interval"
        for c in comps:
            assert c.finish_s == finish
            assert c.record.batch_size == 3
            # the SLO identity, exactly, per member
            assert c.record.queue_wait_s + c.record.service_s == pytest.approx(
                c.finish_s - c.arrival_s, abs=1e-12
            )

    def test_launch_service_beats_serialized_sum(self):
        # the throughput cliff mechanism: one batch-3 launch's interval
        # is under the 3 serialized intervals because the weight stream
        # amortizes in the byte model feeding ServiceModel
        def run(batched):
            sched = _mk_sched(batched, max_batch_requests=8)
            for _ in range(3):
                sched.submit(_stub(), arrival_s=0.0)
            b = sched.next_batch(now=0.0)
            assert len(b.requests) == 3
            return sched.run_batch(b, now=0.0)

        assert run(True) < run(False)

    def test_batch_size_one_traces_unchanged(self):
        # max_batch_requests=1 forces singleton groups: the batched
        # branch never takes (len > 1 required), so every percentile in
        # the class summary must be identical with the flag on or off
        def summary(batched):
            cfg = preset("steady", seed=3, horizon_s=120.0)
            cfg.scheduler.max_batch_requests = 1
            cfg.scheduler.batched_dispatch = batched
            return simulate(reference_engine(), cfg).summary()

        a, b = summary(False), summary(True)
        assert a["classes"] == b["classes"]
        assert a["latency_ms"] == b["latency_ms"]

    def test_overload_batched_conserves_and_moves_the_cliff(self):
        # the BENCH acceptance in miniature: same seed/trace, batching
        # on, conservation exact and the overload p99 no worse
        base = simulate(
            reference_engine(), preset("overload", seed=0, horizon_s=150.0)
        ).summary()
        bat = simulate(
            reference_engine(), preset("overload_batched", seed=0, horizon_s=150.0)
        ).summary()
        assert bat["requests"]["conserved"]
        assert bat["latency_ms"]["p99"] <= base["latency_ms"]["p99"]

    def test_execute_true_keeps_serial_members(self):
        # real execution has no batched forward in the engine pipeline
        # (conform/postprocess are per-volume): the flag must not change
        # results, only the modeled path — waits still strictly increase
        sched = RequestScheduler(
            _mk_engine(),
            SchedulerConfig(batched_dispatch=True, native_shapes=True,
                            max_batch_requests=4),
            clock=VirtualClock(),
            execute=True,
        )
        rng = np.random.default_rng(0)
        for a in (0.0, 0.0, 0.0):
            sched.submit(rng.random((16, 16, 16), dtype=np.float32), arrival_s=a)
        batch = sched.next_batch(now=0.0)
        sched.run_batch(batch)
        comps = sched.completions
        assert len(comps) == 3
        assert all(c.record.status == "ok" for c in comps)
