"""Batched serving engine — the deployment-side counterpart of Brainchop's
"serve a pre-trained model to whoever shows up" story, generalised to the
architecture zoo.

Two engines:

SegmentationEngine — batches incoming MRI volumes and runs the Brainchop
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

LMEngine — continuous-batching text generation for any ModelConfig:
chunked prefill (sequence patching, DESIGN.md §4), ring-buffer KV caches
for sliding-window configs, greedy/temperature sampling, per-slot EOS
retirement and slot reuse.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MD
from repro.models.config import ModelConfig


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    id: int = 0


@dataclasses.dataclass
class Completion:
    id: int
    tokens: list[int]
    prefill_s: float
    decode_s: float


class LMEngine:
    """Static-slot continuous batching engine.

    ``slots`` concurrent sequences share one cache; finished slots are
    refilled from the queue. Prefill runs per-request in chunks of
    ``prefill_chunk`` (compiled once per chunk shape); decode advances all
    live slots in lock-step with a single compiled serve_step.
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        *,
        slots: int = 4,
        max_seq: int = 512,
        prefill_chunk: int = 64,
        eos_id: int | None = None,
        rng: jax.Array | None = None,
    ):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        self.eos_id = eos_id
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.cache = MD.init_cache(cfg, slots, max_seq)
        self.pos = np.zeros((slots,), np.int32)  # per-slot next position
        self.live = np.zeros((slots,), bool)

        cfg_ = cfg

        @jax.jit
        def _decode(params, token, cache, pos):
            logits, cache = MD.decode_step(params, token, cache, pos, cfg_)
            return logits[:, -1], cache

        self._decode = _decode

    # --- prefill ------------------------------------------------------------

    def _prefill_one(self, slot: int, prompt: list[int]) -> None:
        """Feed a prompt token-by-token through decode_step (correct for
        every family incl. recurrent states). Chunk-level batching of the
        token loop is jit'd via lax.scan for throughput."""
        cfg = self.cfg

        @jax.jit
        def run_chunk(params, tokens, cache, start):
            def step(carry, tok):
                cache, pos = carry
                _, cache = MD.decode_step(params, tok[None, None], cache, pos, cfg)
                return (cache, pos + 1), None

            (cache, pos), _ = jax.lax.scan(step, (cache, start), tokens)
            return cache, pos

        # The engine cache is batched over slots; run the scan on a
        # single-slot view then write it back.
        one = jax.tree.map(lambda c: c[:, slot : slot + 1], self.cache)
        pos = jnp.asarray(self.pos[slot], jnp.int32)
        chunk = self.prefill_chunk
        toks = np.asarray(prompt, np.int32)
        for i in range(0, len(toks), chunk):
            part = toks[i : i + chunk]
            if len(part) < chunk:
                pad = np.zeros((chunk - len(part),), np.int32)
                padded = np.concatenate([part, pad])
                # run the valid prefix only, step-by-step for the tail
                for t in part:
                    _, one = self._decode_single(one, int(t), int(pos))
                    pos = pos + 1
            else:
                one, pos = run_chunk(self.params, jnp.asarray(part), one, pos)
        self.cache = jax.tree.map(
            lambda full, o: jax.lax.dynamic_update_slice_in_dim(full, o, slot, axis=1)
            if full.ndim > 1
            else full,
            self.cache,
            one,
        )
        self.pos[slot] = int(pos)

    def _decode_single(self, one_cache, token: int, pos: int):
        logits, cache = self._decode(
            self.params, jnp.asarray([[token]], jnp.int32), one_cache, jnp.asarray(pos, jnp.int32)
        )
        return logits, cache

    # --- main loop ------------------------------------------------------------

    def run(self, requests: list[Request]) -> list[Completion]:
        queue = list(requests)
        active: dict[int, dict] = {}
        done: list[Completion] = []

        def admit():
            for s in range(self.slots):
                if not self.live[s] and queue:
                    req = queue.pop(0)
                    t0 = time.perf_counter()
                    self.pos[s] = 0
                    self._reset_slot(s)
                    self._prefill_one(s, req.prompt[:-1])
                    active[s] = {
                        "req": req,
                        "out": [],
                        "next": req.prompt[-1],
                        "prefill_s": time.perf_counter() - t0,
                        "t0": time.perf_counter(),
                    }
                    self.live[s] = True

        admit()
        while active:
            tokens = np.zeros((self.slots, 1), np.int32)
            for s, st in active.items():
                tokens[s, 0] = st["next"]
            # lock-step decode: one compiled step for all slots. Each slot
            # has its own position; decode_step takes a scalar pos, so we
            # use the max and rely on per-slot ring indexing... positions
            # differ across slots, so instead advance slots individually
            # when their positions diverge, batched when aligned.
            groups: dict[int, list[int]] = {}
            for s in active:
                groups.setdefault(int(self.pos[s]), []).append(s)
            for pos, slot_ids in groups.items():
                logits, new_cache = self._decode(
                    self.params, jnp.asarray(tokens), self.cache, jnp.asarray(pos, jnp.int32)
                )
                # merge only the stepped slots' cache lanes back
                mask = np.zeros((self.slots,), bool)
                mask[slot_ids] = True
                m = jnp.asarray(mask)

                def merge(new, old):
                    bdim = 1 if new.ndim > 1 else 0
                    shape = [1] * new.ndim
                    shape[bdim] = self.slots
                    return jnp.where(m.reshape(shape), new, old) if new.shape[bdim] == self.slots else new

                self.cache = jax.tree.map(merge, new_cache, self.cache)
                lg = np.asarray(logits)
                for s in slot_ids:
                    st = active[s]
                    if st["req"].temperature > 0:
                        self.rng, k = jax.random.split(self.rng)
                        nxt = int(
                            jax.random.categorical(k, jnp.asarray(lg[s]) / st["req"].temperature)
                        )
                    else:
                        nxt = int(np.argmax(lg[s]))
                    st["out"].append(nxt)
                    st["next"] = nxt
                    self.pos[s] += 1
                    if (
                        len(st["out"]) >= st["req"].max_new_tokens
                        or (self.eos_id is not None and nxt == self.eos_id)
                        or self.pos[s] >= self.max_seq - 1
                    ):
                        done.append(
                            Completion(
                                id=st["req"].id,
                                tokens=st["out"],
                                prefill_s=st["prefill_s"],
                                decode_s=time.perf_counter() - st["t0"],
                            )
                        )
                        self.live[s] = False
                        del active[s]
            admit()
        return sorted(done, key=lambda c: c.id)

    def _reset_slot(self, s: int) -> None:
        fresh = MD.init_cache(self.cfg, 1, self.max_seq)
        self.cache = jax.tree.map(
            lambda full, fr: jax.lax.dynamic_update_slice_in_dim(full, fr, s, axis=1)
            if full.ndim > 1
            else full,
            self.cache,
            fresh,
        )


# ---------------------------------------------------------------- MRI side ---


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
