"""Batched serving engine — the deployment-side counterpart of Brainchop's
"serve a pre-trained model to whoever shows up" story.

SegmentationEngine batches incoming MRI volumes and runs the Brainchop
pipeline (conform -> crop -> MeshNet -> components), with the memory-budget
guard choosing full-volume vs failsafe sub-volume mode per request —
exactly the tool's client-side adaptation logic, server-side. Inference
dispatches through the executor registry (core/executors.py): the engine's
PipelineConfig carries a default backend ("auto" -> the sharded
depth-first megakernel on multi-device TPU when the per-slab tile plan
fits VMEM, the megakernel on one TPU device, else fused Pallas; XLA on
CPU), and both ``submit`` and the batched ``submit_many`` accept
per-request mode/executor/device-count overrides (the Z-slab count of the
sharded family, core/spatial_shard.py; the engine builds its mesh once at
construction); the chosen triple — plus the modeled HBM and collective
halo bytes the backend's schedule moves (telemetry/traffic.py) — is
recorded in each request's telemetry record. Requests sharing a (mode,
executor, devices, shape) reuse one compiled executable via the
registry's jit cache. The queued path — ``submit_async``/``drain``, and
``submit_many``'s dispatch — goes through the continuous-batching request
scheduler (serving/scheduler.py): bounded queue with typed
``QueueFullError`` backpressure, priority/deadline classes, HBM-priced
admission with shed-to-subvolume demotion, and dynamic grouping of
signature-compatible requests. One engine == one fleet replica: the
replicated serving tier (serving/fleet.py) builds N engines — each with
its own jit caches and prepared weight pytrees — and routes across them
by dispatch-signature cache affinity.
"""

from __future__ import annotations

from typing import Any

import jax


class SegmentationEngine:
    """Server-side Brainchop: picks full-volume vs sub-volume ("failsafe")
    mode per request from the memory budget, then runs the pipeline through
    the chosen executor backend (core/executors.py).

    ``devices`` sets the engine's default Z-slab count for the sharded
    executor family (core/spatial_shard.py) — the mesh is built once at
    engine construction and shared by every request (the registry's mesh
    cache keys on the slab count, so per-request overrides that repeat a
    count also reuse one mesh and one compiled executable).

    ``precision`` sets the engine's default storage policy
    (kernels/quantize.py: "fp32" | "bf16" | "int8w" | "auto"); weights
    are quantized/cast ONCE per policy the first time a request uses it
    and the prepared pytree is cached, so int8w requests stream the same
    4x-smaller weights instead of re-quantizing per request
    (quantize.prepare_params is idempotent — executors accept either
    form)."""

    def __init__(
        self, params, pipeline_cfg, *, mask_model=None, budget=None, devices=None,
        precision=None,
    ):
        from repro.telemetry.budget import MemoryBudget

        self.params = params
        self.cfg = pipeline_cfg
        self.mask_model = mask_model
        self.budget = budget or MemoryBudget.from_device()
        self.devices = devices or getattr(pipeline_cfg, "shard_devices", None)
        self.precision = precision or getattr(pipeline_cfg, "precision", "auto")
        self._prepared: dict[str, Any] = {}
        if self.devices and self.devices > 1:
            # Build (and cache) the engine's Z mesh once, up front — not
            # lazily inside the first request's trace.
            from repro.core import spatial_shard

            spatial_shard.mesh_for(self.devices)
        from repro.telemetry import spans
        from repro.telemetry.record import TelemetryLog

        # collector pauses land on the trace and in the open request's
        # spans ("gc"), so a pause inside a request is seen where it falls
        spans.install_gc_hook()
        self.log = TelemetryLog()
        self._scheduler = None  # lazy RequestScheduler (serving/scheduler.py)

    def _params_for(self, precision: str):
        """The weight pytree in ``precision`` storage, prepared once per
        policy and cached for every later request (the streamed-weight
        footprint is what TelemetryRecord.params_bytes tracks)."""
        from repro.kernels import quantize

        resolved = quantize.resolve_precision(precision)
        if resolved not in self._prepared:
            self._prepared[resolved] = quantize.prepare_params(
                self.params, self.cfg.model, resolved
            )
        return self._prepared[resolved]

    def pick_mode(self, volume_shape, precision: str | None = None) -> str:
        """Budget-driven failsafe selection, priced at the request's
        storage policy: a bf16/int8w request carries half the activation
        bytes, so a budget that demotes fp32 to the sub-volume failsafe
        can still serve it streaming (mirrors pipeline.run's charges)."""
        from repro.kernels import quantize
        from repro.telemetry.budget import BudgetExceeded

        resolved = quantize.resolve_precision(precision or self.precision)
        try:
            self.budget.charge_streaming(
                volume_shape, self.cfg.model,
                dtype_bytes=quantize.act_bytes(resolved),
            )
            return "streaming"
        except BudgetExceeded:
            return "subvolume"

    def submit(
        self,
        vol: jax.Array,
        *,
        mode: str | None = None,
        executor: str | None = None,
        devices: int | None = None,
        precision: str | None = None,
    ):
        """Run one volume synchronously. ``mode``/``executor``/``devices``
        /``precision`` override the engine's defaults for this request
        only; ``mode=None`` keeps the budget-driven failsafe selection,
        ``executor=None`` keeps the engine config's backend (``"auto"``
        resolves per host in the pipeline), ``devices=None`` keeps the
        engine's slab count (``devices=1`` forces single-device for this
        request), and ``precision=None`` keeps the engine's storage
        policy ("auto" resolves per device+model in the pipeline)."""
        return self._run_request(
            vol, mode=mode, executor=executor, devices=devices, precision=precision
        )

    def _run_request(
        self,
        vol: jax.Array,
        *,
        mode: str | None = None,
        executor: str | None = None,
        devices: int | None = None,
        precision: str | None = None,
        volume_shape: tuple | None = None,
    ):
        """The raw serve path behind ``submit`` and the scheduler: resolve
        defaults, run the pipeline, log telemetry. (The scheduler calls
        this per batch member so its typed fault isolation wraps exactly
        one request's execution.) ``volume_shape`` overrides the engine's
        conform target for this request — the scheduler's native-shape
        mode serves each request at its own geometry; ``None`` keeps the
        engine card's shape (every input is conformed to it)."""
        import dataclasses as dc

        from repro.core import pipeline as pl

        prec = precision or self.precision
        shape = tuple(volume_shape) if volume_shape else self.cfg.volume_shape
        mode = mode or self.pick_mode(shape, prec)
        cfg = dc.replace(
            self.cfg,
            volume_shape=shape,
            mode=mode,
            budget=self.budget,
            executor=executor or self.cfg.executor,
            shard_devices=devices if devices is not None else self.devices,
            precision=prec,
        )
        res = pl.run(cfg, self._params_for(prec), vol, mask_model=self.mask_model)
        self.log.append(res.record)
        return res

    # ---- queued serving (serving/scheduler.py) --------------------------

    def scheduler(self, scheduler_cfg=None, **kwargs):
        """The engine's request scheduler, created lazily (pass
        ``scheduler_cfg``/kwargs on FIRST use to configure it; see
        ``RequestScheduler``). ``submit_async``/``drain`` go through it.
        Raises if a configuration is passed after the scheduler already
        exists — silently returning the old instance would leave the
        caller believing their admission limits are active."""
        from repro.serving.scheduler import RequestScheduler

        if getattr(self, "_scheduler", None) is None:
            self._scheduler = RequestScheduler(self, scheduler_cfg, **kwargs)
        elif scheduler_cfg is not None or kwargs:
            raise ValueError(
                "engine.scheduler() was already created (a prior "
                "submit_async/scheduler call); configuration must be "
                "passed on first use"
            )
        return self._scheduler

    def submit_async(
        self,
        vol: jax.Array,
        *,
        priority: str = "standard",
        mode: str | None = None,
        executor: str | None = None,
        devices: int | None = None,
        precision: str | None = None,
    ) -> int:
        """Enqueue one request with the continuous-batching scheduler and
        return its request id — nothing executes until ``drain`` (or an
        explicit ``scheduler().next_batch``/``run_batch`` loop). Raises
        ``QueueFullError`` when the admission queue is at depth."""
        return self.scheduler().submit(
            vol,
            priority=priority,
            mode=mode,
            executor=executor,
            devices=devices,
            precision=precision,
        )

    def drain(self):
        """Serve every queued request (dynamic grouping, HBM-budget
        admission, priority order) and return the id-ordered
        ``Completion`` list — each with its outcome (completed | demoted
        | rejected), stamped telemetry record, and pipeline result."""
        return self.scheduler().drain()

    def submit_many(
        self,
        vols: list[jax.Array],
        *,
        modes: list[str | None] | None = None,
        executors: list[str | None] | None = None,
        devices: list[int | None] | None = None,
        precisions: list[str | None] | None = None,
    ) -> list:
        """Batched multi-volume submission with per-request mode/executor/
        device-count/precision selection.

        Results come back in submission order; a ``None`` entry in
        ``modes`` keeps the budget-driven failsafe selection, a ``None``
        entry in ``executors`` keeps the engine config's backend, a
        ``None`` entry in ``devices`` keeps the engine's slab count, and
        a ``None`` entry in ``precisions`` keeps the engine's storage
        policy.

        Dispatch goes through the request scheduler's grouping
        (serving/scheduler.py): requests sharing a resolved (mode,
        executor, devices, precision, shape) signature are served
        back-to-back as one group — the signature is resolved and priced
        ONCE per unique combination (not once per request), and the
        group shares one compiled executable via the registry's
        ``jitted_apply`` cache, one mesh via the slab-count mesh cache,
        and one prepared weight pytree per policy via the engine's
        cache. A request that *raises* (garbage volume, executor bug)
        yields a failed result typed by the fault taxonomy
        (serving/errors.py — ``transient_fault`` for declared-retryable
        executor errors, ``permanent_fault`` otherwise) while the rest
        of its group completes. Each telemetry record carries
        the mode/executor/precision that served it, the scheduler's
        queue/batch stamps, and the request's submission index in
        ``extra``.
        """
        from repro.core.pipeline import PipelineResult
        from repro.serving.scheduler import RequestScheduler, SchedulerConfig

        n = len(vols)
        if modes is not None and len(modes) != n:
            raise ValueError(f"modes must match len(vols): {len(modes)} != {n}")
        if executors is not None and len(executors) != n:
            raise ValueError(f"executors must match len(vols): {len(executors)} != {n}")
        if devices is not None and len(devices) != n:
            raise ValueError(f"devices must match len(vols): {len(devices)} != {n}")
        if precisions is not None and len(precisions) != n:
            raise ValueError(
                f"precisions must match len(vols): {len(precisions)} != {n}"
            )
        modes = modes if modes is not None else [None] * n
        execs = executors if executors is not None else [None] * n
        devs = devices if devices is not None else [None] * n
        precs = precisions if precisions is not None else [None] * n

        # Legacy semantics preserved: unbounded queue, no batch-level
        # admission budget (mode selection stays per-request via
        # pick_mode), and deadline-FREE classes (the default ladder's
        # wall-clock deadlines would shed the tail of a slow synchronous
        # batch — the old for-loop ran every request, so must this) —
        # the scheduler contributes grouping, resolution dedupe, and
        # fault isolation.
        from repro.serving.scheduler import DEFAULT_CLASSES, PriorityClass

        sched = RequestScheduler(
            self,
            SchedulerConfig(
                max_queue_depth=None,
                admission_hbm_bytes=None,
                max_batch_requests=max(n, 1),
                allow_demotion=False,
                classes={
                    name: PriorityClass(name, c.priority, deadline_s=None)
                    for name, c in DEFAULT_CLASSES.items()
                },
            ),
        )
        for i, vol in enumerate(vols):
            sched.submit(
                vol, mode=modes[i], executor=execs[i], devices=devs[i],
                precision=precs[i],
            )
        completions = sched.drain()
        results = []
        for i, comp in enumerate(completions):
            res = comp.result
            if res is None:  # typed failure synthesized by the scheduler
                res = PipelineResult(segmentation=None, record=comp.record)
            res.record.extra["request_index"] = i
            results.append(res)
        return results
