"""The Brainchop pipeline (Fig. 1): conform -> [brain-mask -> crop] ->
inference (full-volume | sub-volume | streamed | sharded) -> connected-
components filtering -> uncrop.

Inference dispatches through the pluggable executor registry
(core/executors.py): ``PipelineConfig.mode`` picks the spatial strategy
(full / subvolume / streaming) and ``PipelineConfig.executor`` picks the
forward implementation that runs on each block of work — ``"xla"`` (the
reference graph), ``"pallas_fused"`` (one fused conv+BN+ReLU Pallas call
per layer), ``"pallas_megakernel"`` (the whole stack per VMEM-resident
tile, the production TPU path), ``"streaming"`` (scan-over-layers), or
the multi-device ``"sharded_<inner>[@n]"`` family (halo-exchange Z-slab
sharding, core/spatial_shard.py; ``PipelineConfig.shard_devices`` pins
the slab count for any executor). The default ``"auto"`` resolves per
host: the sharded megakernel on multi-device TPU when the per-slab tile
plan fits VMEM, the megakernel on one TPU device, else the fused kernel;
XLA on CPU hosts. ``PipelineConfig.precision`` picks the storage policy
(kernels/quantize.py: fp32 | bf16 | int8w; "auto" -> bf16 on TPU, int8w
for wide models, fp32 on CPU) — the conformed volume leaves
preprocessing in the policy's storage dtype and every backend runs its
precision-matched kernels. The executor and precision that actually ran
— plus the modeled HBM, inter-device halo, and streamed-weight bytes
their schedule moves for this volume (telemetry/traffic.py,
quantize.model_params_bytes) — are recorded in the telemetry record.

Each stage is timed into a telemetry record, mirroring Table IV's
per-stage columns (Preprocessing / Cropping / Inference / Merging /
Postprocessing), and the whole run is guarded by the memory-budget model
(telemetry/budget.py) that simulates the browser's failure modes on
TPU-equivalent limits. The stage timers are request-scoped spans
(telemetry/spans.py): the same durations land in ``record.spans`` and, as
``repro.pipeline.*`` annotations, on the profiler's trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import components, conform as conform_mod, cropping, executors, patching
from repro.core.meshnet import MeshNetConfig
from repro.core.spatial_shard import ShardGeometryError
from repro.kernels import quantize
from repro.telemetry import spans
from repro.telemetry.record import StageTimes, TelemetryRecord
from repro.telemetry.budget import MemoryBudget, BudgetExceeded


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """End-to-end pipeline options (one Brainchop 'model card')."""

    name: str = "gwm_light"
    model: MeshNetConfig = dataclasses.field(default_factory=MeshNetConfig)
    volume_shape: tuple[int, int, int] = (256, 256, 256)
    # inference mode: "full" | "subvolume" | "streaming"
    mode: str = "full"
    # forward implementation: "auto" | "xla" | "pallas_fused" |
    # "pallas_megakernel" | "streaming" | "sharded_<inner>[@n]"
    # (core/executors.py; "auto" -> the sharded megakernel on multi-device
    # TPU when the per-slab plan fits VMEM, the megakernel on one TPU
    # device, else pallas_fused; xla on CPU hosts)
    executor: str = executors.AUTO
    # run the (resolved) executor Z-sharded over this many devices
    # (core/spatial_shard.py): the executor is re-wrapped as
    # sharded_<inner>@<n>. None = leave the executor as resolved; 1 =
    # force single-device (unwraps a sharded default). Executors with no
    # sharded form (streaming) keep running single-device.
    shard_devices: Optional[int] = None
    # storage policy (kernels/quantize.py): "fp32" | "bf16" | "int8w" |
    # "auto" ("auto" -> bf16 on TPU, int8w for wide models, fp32 on CPU
    # hosts where the XLA oracle serves). The conformed volume is cast /
    # int8-quantized once at the end of preprocessing, so the inference
    # schedule streams the policy's storage dtypes end to end; the
    # resolved policy and the weight footprint are stamped on telemetry.
    precision: str = quantize.AUTO
    cube: int = 64
    overlap: int = patching.MESHNET_RF_RADIUS
    batch_cubes: int = 1
    use_cropping: bool = False
    crop_margin: int = 4
    min_component_size: int = 64
    postprocess: bool = True
    budget: Optional[MemoryBudget] = None
    # optional content-keyed memo for the conform stage (e.g.
    # serving.cache.ConformMemo): any object with get(vol, out_shape) ->
    # conformed-or-None and put(vol, out_shape, conformed). The memo holds
    # the conformed [0, 1] volume *before* the precision cast, so one
    # conform can feed requests running under different storage policies.
    conform_memo: Optional[Any] = None


@dataclasses.dataclass
class PipelineResult:
    segmentation: Optional[jax.Array]
    record: TelemetryRecord


def _geometry_fail_type(e: ValueError) -> str:
    """Telemetry fail type for a ValueError out of the pre-flight models:
    slab-geometry problems (ShardGeometryError: non-divisible Z, missing
    devices) get their own label; any other planning ValueError is an
    unplannable-VMEM schedule."""
    return "shard_geometry" if isinstance(e, ShardGeometryError) else "vmem_oom"


def run(
    cfg: PipelineConfig,
    params: Any,
    vol: jax.Array,
    *,
    mask_model: Optional[tuple[Any, MeshNetConfig]] = None,
    voxel_size=(1.0, 1.0, 1.0),
) -> PipelineResult:
    """Run the full pipeline on one raw volume. Never raises on budget
    failures — returns a failed TelemetryRecord (status='fail'), matching
    the tool's telemetry semantics. Opens a request scope
    (telemetry/spans.py) when the caller has none open, so
    ``record.spans`` holds this run's spans either way."""
    scope = contextlib.nullcontext() if spans.in_request() else spans.request()
    with scope, spans.span("pipeline.run"):
        return _run(cfg, params, vol, mask_model, voxel_size)


def _plan(cfg, times: StageTimes, mask_model) -> TelemetryRecord:
    """Resolve the executor and precision and run the pre-flight byte
    model: the run's record, failed where the schedule cannot be planned."""
    # Resolve against the geometry each forward actually sees: failsafe
    # mode runs the executor on padded cubes, not the whole volume — so
    # "auto" must judge slab divisibility / VMEM plans on the cube shape
    # (a sharded default that can't slice the cube would fail every
    # failsafe request).
    work_shape = (
        (cfg.cube + 2 * cfg.overlap,) * 3
        if cfg.mode == "subvolume"
        else cfg.volume_shape
    )
    precision = quantize.resolve_precision(cfg.precision, cfg.model)
    exec_name = executors.resolve(cfg.executor, cfg.model, work_shape, precision)
    if cfg.shard_devices is not None:
        inner = executors.inner_of(exec_name)
        parsed = executors.parse_sharded(exec_name)
        already_pinned = parsed is not None and parsed[1] is not None
        if (
            cfg.shard_devices > 1
            and executors.shardable(inner)
            and not already_pinned
        ):
            # per-request slab count: re-wrap the resolved backend (or the
            # sharded family's unpinned form) pinned to this many Z-slabs.
            # An executor name that pins its own count ("sharded_xla@8")
            # is an explicit request and wins over this default.
            exec_name = executors.ensure_sharded(inner, cfg.shard_devices)
        elif cfg.shard_devices <= 1:
            # devices=1 forces single-device, unwrapping a sharded default
            exec_name = inner
        # executors with no sharded form (streaming) keep running
        # single-device rather than failing the request.
    rec = TelemetryRecord(
        model=cfg.name,
        mode=cfg.mode,
        status="ok",
        times=times,
        executor=exec_name,
        precision=precision,
        params_bytes=quantize.model_params_bytes(cfg.model, precision),
        # the simulated device limit this run was admitted against — the
        # column the paper's texture-size tables condition on, and what
        # the serving scheduler's fleet rollups group by. None when the
        # run is unguarded (no budget configured).
        memory_budget_bytes=None if cfg.budget is None else cfg.budget.bytes_limit,
        spans=spans.recorded(),
    )
    try:
        # Pre-flight the sharded family's hard requirements: the host must
        # actually have the slab count's devices (mesh_for raises the same
        # ValueError the forward would, but before any compute).
        parsed = executors.parse_sharded(exec_name)
        if parsed is not None:
            from repro.core import spatial_shard

            spatial_shard.mesh_for(parsed[1])
        # Price the inference schedule's HBM traffic for this request: the
        # per-forward model times the number of forwards the mode implies.
        # For the megakernel this also *plans* the schedule, so an
        # infeasible plan (working set over VMEM at any tile) surfaces
        # here — before any compute — rather than at trace time inside
        # the budget-guarded region below.
        if cfg.mode == "subvolume":
            ncubes = math.prod(
                -(-s // cfg.cube) for s in cfg.volume_shape
            )
            cube_shape = (cfg.cube + 2 * cfg.overlap,) * 3
            per_cube = executors.modeled_hbm_bytes(
                exec_name, cfg.model, cube_shape, precision=precision
            )
            rec.hbm_bytes_modeled = None if per_cube is None else ncubes * per_cube
            rec.collective_bytes_modeled = ncubes * executors.modeled_collective_bytes(
                exec_name, cfg.model, cube_shape, precision=precision
            )
        else:
            rec.hbm_bytes_modeled = executors.modeled_hbm_bytes(
                exec_name, cfg.model, cfg.volume_shape, precision=precision
            )
            rec.collective_bytes_modeled = executors.modeled_collective_bytes(
                exec_name, cfg.model, cfg.volume_shape, precision=precision
            )
        if cfg.use_cropping and mask_model is not None:
            # the mask forward runs under the same executor; probe it too
            executors.modeled_hbm_bytes(
                exec_name, mask_model[1], cfg.volume_shape, precision=precision
            )
    except ValueError as e:
        # Unplannable schedule: the forward itself would raise the same
        # error, so keep the never-raises telemetry contract and report a
        # failed run (the VMEM analogue of the budget fail types). A Z dim
        # that doesn't divide into the requested slabs — or a slab count
        # the host lacks devices for — surfaces the same way, under its
        # own fail type.
        rec.status = "fail"
        rec.fail_type = _geometry_fail_type(e)
    return rec


def _run(cfg, params, vol, mask_model, voxel_size) -> PipelineResult:
    times = StageTimes()
    with spans.span("pipeline.plan"):
        rec = _plan(cfg, times, mask_model)
    if rec.status == "fail":
        return PipelineResult(segmentation=None, record=rec)
    precision, exec_name = rec.precision, rec.executor
    budget = cfg.budget or MemoryBudget.unlimited()

    act_bytes = quantize.act_bytes(precision)
    try:
        # --- Stage 1: preprocessing (conform + precision cast) --------------
        with spans.span("pipeline.preprocess") as stage:
            x = None
            if cfg.conform_memo is not None:
                x = cfg.conform_memo.get(vol, cfg.volume_shape)
            if x is None:
                x = conform_mod.conform(vol, cfg.volume_shape, voxel_size)
                if cfg.conform_memo is not None:
                    cfg.conform_memo.put(vol, cfg.volume_shape, x)
            # The policy cast is conform's output write, not an inference
            # cost: the conformed [0, 1] volume leaves preprocessing in the
            # policy's storage dtype (int8-quantized under int8w — faithful
            # to Brainchop, whose conformed volumes are uint8), so the
            # inference schedule below streams it at that width.
            if precision == "int8w":
                x = quantize.quantize_input(x)
            elif precision == "bf16":
                x = x.astype(quantize.act_dtype(precision))
            with spans.span("pipeline.preprocess.wait"):
                x.block_until_ready()
        times.preprocessing = stage.seconds

        crop_start = None
        full_shape = x.shape
        # --- Stage 2: cropping (optional) ------------------------------------
        if cfg.use_cropping and mask_model is not None:
            with spans.span("pipeline.crop") as stage:
                mparams, mcfg = mask_model
                budget.charge_inference(x.shape, mcfg, dtype_bytes=act_bytes)
                mask_logits = executors.jitted_apply(exec_name, precision=precision)(
                    mparams, x[None], mcfg
                )
                mask = jnp.argmax(mask_logits[0], -1) > 0
                mask = components.largest_component(mask)
                size = cropping.pick_crop_size(mask, margin=cfg.crop_margin)
                x, crop_start = cropping.crop_to(x, mask, size)
                with spans.span("pipeline.crop.wait"):
                    x.block_until_ready()
            times.cropping = stage.seconds
            rec.crop_size = size

        # --- Stage 3: inference ----------------------------------------------
        with spans.span("pipeline.inference") as stage:
            if cfg.mode == "subvolume":
                budget.charge_subvolume(
                    cfg.cube, cfg.overlap, cfg.model, dtype_bytes=act_bytes
                )
                logits = patching.subvolume_inference(
                    x,
                    params=params,
                    model_cfg=cfg.model,
                    executor=exec_name,
                    cube=cfg.cube,
                    overlap=cfg.overlap,
                    batch_cubes=cfg.batch_cubes,
                    precision=precision,
                )
                # The trimmed write-back merge happens inside
                # subvolume_inference (host-side numpy copies, not
                # separately timed); the whole split -> infer -> merge
                # span is attributed to 'inference'.
            elif cfg.mode == "streaming":
                budget.charge_streaming(x.shape, cfg.model, dtype_bytes=act_bytes)
                logits = executors.jitted_apply(exec_name, "streaming", precision)(
                    params, x[None], cfg.model
                )[0]
            else:  # full
                budget.charge_inference(x.shape, cfg.model, dtype_bytes=act_bytes)
                logits = executors.jitted_apply(exec_name, precision=precision)(
                    params, x[None], cfg.model
                )[0]
            with spans.span("pipeline.inference.wait"):
                logits.block_until_ready()
        times.inference = stage.seconds

        with spans.span("pipeline.argmax"):
            seg = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # --- Stage 4: postprocessing (connected components) -------------------
        if cfg.postprocess:
            with spans.span("pipeline.postprocess") as stage:
                seg = components.filter_segmentation(
                    seg, cfg.model.num_classes, cfg.min_component_size
                )
                with spans.span("pipeline.postprocess.wait"):
                    seg.block_until_ready()
            times.postprocessing = stage.seconds

        if crop_start is not None:
            seg = cropping.uncrop(seg, crop_start, full_shape)

        rec.status = "ok"
        return PipelineResult(segmentation=seg, record=rec)

    except BudgetExceeded as e:
        rec.status = "fail"
        rec.fail_type = e.fail_type
        return PipelineResult(segmentation=None, record=rec)
    except conform_mod.DegenerateVolumeError:
        # A well-formed 3-D volume with no intensity dynamic range
        # (all-zero / constant / all-non-finite): conform refuses it
        # host-side before any compute, and the never-raises contract
        # turns that into a typed preprocessing failure. Malformed
        # payloads (wrong rank) are NOT intercepted — they still blow up
        # in resample and propagate, so the serving tier's
        # garbage-volume classification is unchanged.
        times.preprocessing = stage.seconds
        rec.status = "fail"
        rec.fail_type = "degenerate_volume"
        return PipelineResult(segmentation=None, record=rec)
    except ShardGeometryError:
        # The forward can still hit slab geometry the pre-flight could not
        # see — cropping picks its shape at run time, and a crop size need
        # not divide into a sharded executor's slabs. Same contract: a
        # failed record, never an exception. (Other ValueErrors — bad
        # input, bugs — propagate with their tracebacks.)
        rec.status = "fail"
        rec.fail_type = "shard_geometry"
        return PipelineResult(segmentation=None, record=rec)
