"""Continuous-batching request scheduler in front of ``SegmentationEngine``.

``SegmentationEngine.submit_many`` is a synchronous for-loop: fine for a
notebook, useless as the serving tier the ROADMAP aims at ("heavy traffic
from millions of users"). This module adds the admission layer cloud-side
medical-image services need (CHIPS, arXiv:1710.00734) in front of the
executor stack PR 1-4 built:

  * a **request queue** with arrival timestamps and bounded depth —
    overflow is a *typed* rejection (``QueueFullError``), the serving
    analogue of the paper's "Unable to create WebGL Texture";
  * **priority / deadline classes** (``PriorityClass``): lower priority
    number is served first, FIFO within a class; a class deadline turns
    queue-time overload into typed ``deadline_expired`` shedding;
  * **HBM-budget-aware admission**: every request's working set is priced
    *before* dispatch via the ``telemetry/budget.py`` models at the
    request's resolved precision (bf16 requests cost half the fp32
    bytes), and a dispatch group is only grown while the summed working
    sets fit ``SchedulerConfig.admission_hbm_bytes``. A request too large
    even alone is **demoted** to the sub-volume failsafe (the paper's
    patching intervention, applied as backpressure) or, failing that,
    rejected with ``admission_oom``;
  * **dynamic grouping**: queued requests sharing a resolved
    ``(mode, executor, devices, precision, shape)`` signature are
    dispatched as ONE group — one jit-cache entry, one prepared weight
    pytree, one mesh — so mixed fleets interleave instead of thrashing
    the compile cache. Signatures are resolved once per unique request
    shape/policy and cached (``stats.resolutions`` counts the misses;
    tests assert the dedupe);
  * **per-request telemetry stamping**: arrival, queue wait, service
    time, batch size, priority class and demotion land on the same
    ``TelemetryRecord`` the pipeline already emits, so the fleet rollups
    in ``telemetry/analysis.py`` see scheduling and execution in one
    stream.

The scheduler is clock-agnostic: pass any object with ``now() -> float``.
Production uses the process monotonic clock; the deterministic load
simulator (``serving/simulator.py``) passes a virtual clock and a
byte-deterministic service-time model, which is how every latency number
it reports is bit-reproducible in CI on CPU. DESIGN.md §5.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable, Optional

from repro.serving.errors import (  # noqa: F401  (QueueFullError re-exported)
    EXECUTION_FAULT_TYPES,
    PERMANENT_FAULT,
    QueueFullError,
    RETRYABLE_FAIL_TYPES,
    SERVICE_TIMEOUT,
    TRANSIENT_FAULT,
    PermanentExecutorError,
    ResilienceConfigError,
    TransientExecutorError,
    classify,
)
from repro.telemetry import spans
from repro.telemetry.budget import BudgetExceeded, MemoryBudget
from repro.telemetry.record import StageTimes, TelemetryRecord


@dataclasses.dataclass(frozen=True)
class PriorityClass:
    """One admission class. ``priority`` orders dispatch (lower first);
    ``deadline_s`` bounds *queue* time — a request still queued past its
    deadline is shed with a typed ``deadline_expired`` rejection rather
    than served uselessly late. ``None`` never expires."""

    name: str
    priority: int
    deadline_s: Optional[float] = None


#: default class ladder: interactive requests preempt batch work and are
#: shed rather than served seconds late; batch work waits indefinitely.
DEFAULT_CLASSES = {
    "interactive": PriorityClass("interactive", 0, deadline_s=30.0),
    "standard": PriorityClass("standard", 1, deadline_s=120.0),
    "batch": PriorityClass("batch", 2, deadline_s=None),
}


@dataclasses.dataclass(frozen=True)
class GroupKey:
    """The compatibility signature of a dispatch group: requests sharing
    it hit one compiled executable (the registry's jit cache keys on
    executor/precision + traced shape) and one prepared weight pytree."""

    mode: str
    executor: str
    devices: Optional[int]
    precision: str
    shape: tuple


@dataclasses.dataclass
class ServeRequest:
    """One queued segmentation request (internal to the scheduler)."""

    id: int
    vol: Any
    priority_class: PriorityClass
    arrival_s: float
    deadline_s: Optional[float]  # absolute, on the scheduler's clock
    # raw per-request overrides (None = engine defaults)
    mode: Optional[str]
    executor: Optional[str]
    devices: Optional[int]
    precision: Optional[str]
    # resolved admission signature (None for garbage volumes, which are
    # dispatched solo so their typed failure cannot poison a group)
    key: Optional[GroupKey] = None
    bytes_priced: int = 0
    demoted: bool = False
    # resilience state (serving/resilience.py). ``base_key`` is the
    # signature as admitted, BEFORE any breaker demotion — the breaker's
    # ledger key and the rung half-open probes retry; ``attempt`` counts
    # completed service attempts (0 == first try); ``not_before_s`` is
    # the retry-backoff gate (the request stays queued but is not
    # batchable until then — its ORIGINAL arrival stamp is untouched, so
    # deadlines and FIFO order stay honest); ``probe`` marks a half-open
    # breaker probe serving at the base rung; ``faults`` counts the
    # retryable faults this request has absorbed (recovery accounting).
    base_key: Optional[GroupKey] = None
    base_bytes: int = 0
    attempt: int = 0
    not_before_s: float = 0.0
    probe: bool = False
    faults: int = 0
    # content-addressed cache state (serving/cache.py): the artifact key
    # this request LEADS for — set when the admission consult missed and
    # this request registered the single-flight in-flight entry; its
    # terminal record is stored under this key and its followers complete
    # with it. None for non-leaders (hits, followers, uncacheable).
    cache_key: Optional[str] = None


@dataclasses.dataclass
class SchedulerConfig:
    """Admission policy knobs.

    ``admission_hbm_bytes=None`` disables the batch-level budget (each
    request still gets the engine's per-request budget-driven mode
    selection) — the configuration ``submit_many`` uses to keep its
    legacy semantics. ``max_queue_depth=None`` is an unbounded queue.

    ``native_shapes`` picks the serving geometry: ``False`` (default,
    the engine's legacy contract) conforms every volume to the engine
    card's ``volume_shape``, so admission prices THAT shape — the one
    the pipeline actually serves; ``True`` serves each request at its
    own volume geometry (the simulator's heterogeneous-fleet mode),
    pricing, grouping, and executing per request shape.

    ``batched_dispatch`` turns a dispatch group into ONE batched kernel
    launch instead of back-to-back member forwards (opt-in: the legacy
    serialized semantics — and their golden traces — are the default).
    When on: admission prices a request's working set INCLUDING one
    weight-pytree copy, and group growth charges the weights once per
    group rather than once per member (a single batched launch streams
    them once — the per-member sum double-counts); on the modeled path
    (``execute=False`` + a service model) the whole group serves in one
    launch whose duration comes from the batch-N traffic model (weight
    stream amortized, telemetry/traffic.py), every member stamped with
    the launch's shared service interval while ``queue_wait_s +
    service_s == finish - arrival`` still holds exactly per member.
    With ``execute=True`` members still run serially through the
    pipeline (conform/postprocess are per-volume); the group keeps the
    shared compiled executable, and true batched execution is available
    at the executor layer (``executors.apply`` with a leading batch
    dim).
    """

    max_queue_depth: Optional[int] = 64
    admission_hbm_bytes: Optional[int] = None
    max_batch_requests: int = 8
    classes: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_CLASSES))
    allow_demotion: bool = True
    native_shapes: bool = False
    batched_dispatch: bool = False


@dataclasses.dataclass
class SchedulerStats:
    """Conservation ledger. Terminal states are disjoint:

        admitted == completed + demoted + rejected + evacuated + coalesced
        (after drain)

    ``completed`` counts requests that reached service in their admitted
    mode (whatever their pipeline status — a typed *execution* failure is
    still a served request); ``demoted`` counts requests served after
    shed-to-subvolume demotion; ``rejected`` counts requests shed before
    service, by typed reason. ``refused`` counts ``QueueFullError``
    submissions that were never admitted (outside the conservation sum).
    """

    admitted: int = 0
    completed: int = 0
    demoted: int = 0
    rejected: dict = dataclasses.field(default_factory=dict)
    refused: int = 0
    # requests admitted here but handed BACK to the caller before service
    # (fleet failover / drain re-dispatch, serving/fleet.py) — a fourth
    # terminal state of THIS scheduler; the fleet ledger tracks where the
    # request completed instead.
    evacuated: int = 0
    batches: int = 0
    grouped_requests: int = 0
    resolutions: int = 0
    max_queue_depth: int = 0
    # resilience counters (serving/resilience.py). Retried attempts are
    # NOT terminal states: a request that faults and re-enters its lane
    # is still exactly one of completed/demoted/rejected/evacuated in
    # the conservation sum above — these count events, not requests,
    # except the last pair which counts terminal requests for the
    # recovery rate (recovered/faulted).
    retries: int = 0
    transient_faults: int = 0
    permanent_faults: int = 0
    timeouts: int = 0
    faulted_requests: int = 0
    recovered_requests: int = 0
    # artifact-cache counters (serving/cache.py). ``coalesced`` is a
    # FIFTH terminal state in the conservation sum: a request admitted
    # here that completed by attaching to an identical in-flight
    # leader's artifact (single-flight stampede collapsing) — it never
    # entered the queue and never touched a device. ``cache_hits``
    # counts admission-time completions served straight from a verified
    # (or negative-cached) artifact; those are ordinary ``completed``
    # requests, stamped ``cache_hit`` in telemetry.
    coalesced: int = 0
    cache_hits: int = 0

    def rejected_total(self) -> int:
        return sum(self.rejected.values())

    def conserved(self) -> bool:
        return self.admitted == (
            self.completed
            + self.demoted
            + self.rejected_total()
            + self.evacuated
            + self.coalesced
        )


@dataclasses.dataclass
class Batch:
    """One dispatch group: compatible requests served back-to-back."""

    requests: list
    start_s: float


@dataclasses.dataclass
class Completion:
    """Terminal record of one admitted request."""

    id: int
    outcome: str  # completed | demoted | rejected
    record: TelemetryRecord
    result: Any  # PipelineResult | None (rejections / modeled runs)
    arrival_s: float
    finish_s: float


class _MonotonicClock:
    """Production clock: the process monotonic timer."""

    def now(self) -> float:
        return time.monotonic()


class RequestScheduler:
    """Continuous-batching admission in front of one ``SegmentationEngine``.

    ``clock`` is any object with ``now() -> float`` (default: process
    monotonic time). ``service_model`` maps a finished request's
    telemetry record to a *virtual* service duration (see
    ``simulator.ServiceModel``); without one, service time is measured
    from the clock. ``execute=False`` skips the real pipeline and
    synthesizes records from the analytic models — the pure
    discrete-event mode the load simulator's large sweeps use.
    """

    def __init__(
        self,
        engine,
        cfg: Optional[SchedulerConfig] = None,
        *,
        clock=None,
        service_model=None,
        execute: bool = True,
        resilience=None,
        fault_plan=None,
        replica_id: int = 0,
        cache=None,
    ):
        self.engine = engine
        self.cfg = cfg or SchedulerConfig()
        self.clock = clock or _MonotonicClock()
        self.service_model = service_model
        self.execute = execute
        # content-addressed artifact cache (serving/cache.py), consulted
        # at admission: a verified hit completes in O(hash) without
        # touching a device; a miss may register this request as the
        # single-flight leader; identical concurrent requests attach to
        # the leader as followers (``_followers``) and complete with its
        # artifact. Shared across replicas by the fleet layer — the
        # instance IS the shared tier.
        self.cache = cache
        self._followers: dict[str, list[ServeRequest]] = {}
        self._model_fp: Optional[str] = None
        # resilience policy (serving/resilience.py): retry budgets,
        # per-class service timeouts, and the breaker-driven degradation
        # ladder. ``fault_plan`` is the seeded injector the deterministic
        # fault harness uses; ``replica_id`` keys injection decisions and
        # backoff jitter so fleet replicas de-correlate.
        self.resilience = resilience
        self.fault_plan = fault_plan
        self.replica_id = replica_id
        if resilience is not None:
            resilience.validate_against(self.cfg.classes, fault_plan)
        elif fault_plan is not None and fault_plan.has_stuck():
            raise ResilienceConfigError(
                "FaultPlan injects stuck-forever faults but no "
                "ResiliencePolicy (service timeouts) is configured"
            )
        self.breaker = None
        if resilience is not None and resilience.breaker is not None:
            from repro.serving.resilience import SignatureBreaker

            self.breaker = SignatureBreaker(resilience.breaker)
        self.queue: list[ServeRequest] = []
        self.completions: list[Completion] = []
        self.stats = SchedulerStats()
        self._seq = 0
        self._drained = 0  # completions already handed out by drain()
        # resolved signature cache: (shape, mode, executor, devices,
        # precision) -> (GroupKey, priced bytes). One resolution per
        # unique signature across the scheduler's lifetime — this is the
        # dedupe submit_many lacked (ISSUE 5 satellite).
        self._sig_cache: dict[tuple, tuple[GroupKey, int]] = {}

    # ------------------------------------------------------------ admission

    def submit(
        self,
        vol,
        *,
        priority: str = "standard",
        mode: Optional[str] = None,
        executor: Optional[str] = None,
        devices: Optional[int] = None,
        precision: Optional[str] = None,
        arrival_s: Optional[float] = None,
        force: bool = False,
    ) -> int:
        """Enqueue one request; returns its id. Raises ``QueueFullError``
        at the depth limit (the refusal is counted and a typed telemetry
        record is logged, so the fleet view sees shed load).

        ``force=True`` bypasses the depth limit — the fleet router's
        failover re-dispatch path (serving/fleet.py), where a request
        already admitted by a crashed replica must land SOMEWHERE or the
        exactly-once guarantee becomes at-most-once. The overshoot is
        bounded by the dead replica's in-flight count."""
        now = self.clock.now() if arrival_s is None else float(arrival_s)
        cls = self.cfg.classes[priority]
        rid = self._seq
        self._seq += 1
        if (
            not force
            and self.cfg.max_queue_depth is not None
            and len(self.queue) >= self.cfg.max_queue_depth
        ):
            self.stats.refused += 1
            self._log_shed(rid, cls, now, "queue_full")
            raise QueueFullError(len(self.queue), self.cfg.max_queue_depth)
        req = ServeRequest(
            id=rid,
            vol=vol,
            priority_class=cls,
            arrival_s=now,
            deadline_s=None if cls.deadline_s is None else now + cls.deadline_s,
            mode=mode,
            executor=executor,
            devices=devices,
            precision=precision,
        )
        req.key, req.bytes_priced = self._resolve(req)
        req.base_key, req.base_bytes = req.key, req.bytes_priced
        self.stats.admitted += 1
        if self._consult_cache(req, now, force=force):
            return rid  # terminal at admission: hit, negative, or follower
        self.queue.append(req)
        self.stats.max_queue_depth = max(self.stats.max_queue_depth, len(self.queue))
        return rid

    def _resolve(self, req: ServeRequest) -> tuple[Optional[GroupKey], int]:
        """Resolve the request's admission signature — mode (the engine's
        budget-driven failsafe selection), executor name, device count,
        storage policy, shape — and price its working set at that policy.
        Cached per unique raw signature: N same-shaped requests cost ONE
        mode resolution and ONE budget pricing, not N."""
        shape = getattr(req.vol, "shape", None)
        if shape is None or len(tuple(shape)) != 3:
            # Garbage volume: no signature to group on; dispatched solo so
            # its typed failure is isolated from well-formed requests.
            return None, 0
        shape = tuple(int(s) for s in shape)
        raw = (shape, req.mode, req.executor, req.devices, req.precision)
        hit = self._sig_cache.get(raw)
        if hit is None:
            self.stats.resolutions += 1
            hit = self._resolve_uncached(req, shape)
            self._sig_cache[raw] = hit
        return hit

    def _resolve_uncached(self, req, shape) -> tuple[GroupKey, int]:
        from repro.core import executors
        from repro.kernels import quantize

        eng = self.engine
        # the geometry this request will actually be served at: its own
        # under native_shapes, else the engine card's conform target —
        # admission must price what the pipeline executes, not the raw
        # input (which conform reshapes anyway).
        if not self.cfg.native_shapes:
            shape = tuple(int(s) for s in eng.cfg.volume_shape)
        precision = quantize.resolve_precision(req.precision or eng.precision)
        mode = req.mode or eng.pick_mode(shape, precision)
        work_shape = (
            (eng.cfg.cube + 2 * eng.cfg.overlap,) * 3
            if mode == "subvolume"
            else shape
        )
        exec_name = executors.resolve(
            req.executor or eng.cfg.executor, eng.cfg.model, work_shape, precision
        )
        devices = req.devices if req.devices is not None else eng.devices
        if devices is not None:
            # mirror pipeline.run's device-count rewrap so the admission
            # signature names the backend that will actually execute (an
            # explicitly "@n"-pinned name still wins over the default)
            inner = executors.inner_of(exec_name)
            parsed = executors.parse_sharded(exec_name)
            pinned = parsed is not None and parsed[1] is not None
            if devices > 1 and executors.shardable(inner) and not pinned:
                exec_name = executors.ensure_sharded(inner, devices)
            elif devices <= 1:
                exec_name = inner
        key = GroupKey(
            mode=mode,
            executor=exec_name,
            devices=devices,
            precision=precision,
            shape=shape,
        )
        return key, self._price(mode, shape, precision)

    def _price(self, mode: str, shape, precision: str) -> int:
        """Working-set bytes of one request in ``mode`` at ``precision`` —
        the telemetry/budget.py models charged against an unlimited
        budget (so the *pricing* never raises; the admission comparison
        below is what enforces the configured limit). Under
        ``batched_dispatch`` the price additionally carries one weight-
        pytree copy: a solo launch keeps the weights resident alongside
        the activations, and pricing them here is what lets group growth
        charge them ONCE per group (``_group_weight_bytes``) instead of
        once per member."""
        from repro.kernels import quantize

        unl = MemoryBudget.unlimited()
        ab = quantize.act_bytes(precision)
        cfg = self.engine.cfg
        if mode == "subvolume":
            need = unl.charge_subvolume(
                cfg.cube, cfg.overlap, cfg.model, dtype_bytes=ab
            )
        elif mode == "streaming":
            need = unl.charge_streaming(shape, cfg.model, dtype_bytes=ab)
        else:
            need = unl.charge_inference(shape, cfg.model, dtype_bytes=ab)
        if self.cfg.batched_dispatch:
            need += quantize.model_params_bytes(cfg.model, precision)
        return need

    def _group_weight_bytes(self, key) -> int:
        """The weight-pytree bytes shared by every member of a batched
        dispatch group (all members carry the group key's precision).
        Zero under serialized dispatch, where ``_price`` never charged
        weights in the first place."""
        if not self.cfg.batched_dispatch or key is None:
            return 0
        from repro.kernels import quantize

        return quantize.model_params_bytes(self.engine.cfg.model, key.precision)

    # ------------------------------------------------------- artifact cache

    def _consult_cache(self, req: ServeRequest, now: float, force: bool) -> bool:
        """Admission-time cache consult. Returns True when the request is
        TERMINAL already — served from a verified artifact (``completed``
        + ``cache_hits``), from a negative-cached verdict, or attached as
        a single-flight follower (completes with its leader) — and must
        not enter the queue. Returns False on miss/bypass/unavailable:
        the request serves via compute, fail-open, possibly as the new
        in-flight leader. ``force`` marks failover/hedge copies: they may
        take a clean hit (terminal is safe anywhere) but never lead or
        follow — single-flight coupling across exactly-once copies would
        tangle the fleet ledger's cancellation paths."""
        if self.cache is None or req.key is None:
            return False
        from repro.serving import cache as cache_mod
        from repro.serving.errors import CacheCorruptionError

        content = cache_mod.content_hash(req.vol)
        if content is None:
            return False  # no content identity: uncacheable
        if self._model_fp is None:
            self._model_fp = cache_mod.model_fingerprint(self.engine.cfg.model)
        ckey = cache_mod.artifact_key(
            content, self._model_fp, req.key.precision, req.key.mode
        )
        look = self.cache.lookup(
            ckey,
            now=now,
            replica=self.replica_id,
            request_id=req.id,
            group_key=req.key,
        )
        if look.status in ("unavailable", "bypass"):
            return False  # fail open: compute path, no single-flight
        if look.status == "hit":
            try:
                payload = self.cache.serve_payload(look.entry)
            except CacheCorruptionError:
                # double-guard breach path: recompute instead of serving
                look = cache_mod.Lookup(
                    status="miss", slow_factor=look.slow_factor
                )
            else:
                self._complete_from_cache(
                    req, payload, look, now, result=look.entry.result
                )
                return True
        if look.status == "negative":
            self._complete_from_cache(
                req, None, look, now, fail_type=look.entry.fail_type
            )
            return True
        if look.status == "inflight":
            if not force and look.owner == self.replica_id:
                req.cache_key = ckey
                self._followers.setdefault(ckey, []).append(req)
                return True
            return False  # a peer's leader: compute independently
        if look.status == "miss" and not force:
            self.cache.begin(
                ckey,
                replica=self.replica_id,
                now=now,
                est_bytes=cache_mod.artifact_bytes_modeled(req.key.shape),
            )
            req.cache_key = ckey
        if look.slow_factor > 1.0:
            # a slow consult delays THIS request's batch eligibility by
            # the inflated verify cost — latency degradation, fail-open
            req.not_before_s = max(
                req.not_before_s,
                now + self.cache.cfg.verify_s * look.slow_factor,
            )
        return False

    def _complete_from_cache(
        self,
        req: ServeRequest,
        payload: Optional[dict],
        look,
        now: float,
        *,
        fail_type: Optional[str] = None,
        result=None,
    ) -> None:
        """Terminal completion at admission, O(hash): the verified
        artifact's metadata (or the negative-cached fault verdict)
        becomes this request's record, stamped ``cache_hit`` — no queue,
        no batch, no device. ``wait + service == finish - arrival``
        holds with wait == 0 and service == the (possibly slowed)
        verify cost."""
        service = self.cache.cfg.verify_s * look.slow_factor
        finish = now + service
        negative = payload is None
        rec = TelemetryRecord(
            model=self.engine.cfg.name,
            mode=(payload or {}).get("mode") or req.key.mode,
            status="fail" if negative else "ok",
            times=StageTimes(),
            executor=(payload or {}).get("executor") or req.key.executor,
            precision=(payload or {}).get("precision") or req.key.precision,
            params_bytes=(payload or {}).get("params_bytes"),
            fail_type=fail_type,
            request_id=req.id,
            arrival_s=req.arrival_s,
            queue_wait_s=0.0,
            service_s=service,
            batch_size=1,
            priority_class=req.priority_class.name,
            cache_hit=True,
            extra=(
                {"negative_cache": True}
                if negative
                else {"artifact_checksum": look.entry.checksum[:16]}
            ),
        )
        self.engine.log.append(rec)
        self.stats.completed += 1
        self.stats.cache_hits += 1
        self.completions.append(
            Completion(
                id=req.id,
                outcome="completed",
                record=rec,
                result=result,
                arrival_s=req.arrival_s,
                finish_s=finish,
            )
        )

    def _complete_cache_leader(self, req: ServeRequest, rec, result, finish: float) -> None:
        """Fold a single-flight leader's terminal record into the cache
        and complete every attached follower with the SAME artifact —
        outcome ``coalesced``, stamped ``cache_hit``, byte-identical
        payload (one shared record template, one shared result object,
        one artifact checksum). N identical concurrent requests ==
        1 device execution + N-1 coalesced completions.

        Two guards before anything is stored or coalesced:

        * a record whose (mode, precision) differ from the admission
          form the artifact key was derived from must NOT be stored
          under that key (``_release_stale_lead`` catches the demotion
          and ladder paths at mutation time; this is the backstop for
          any path that changes the effective form later);
        * a retryable-class terminal failure (exhausted transient
          budget, service timeout) is one leader's bad luck, not a
          property of the content — followers re-enter the queue with
          their OWN retry budgets instead of being stamped failed, so
          one unlucky leader cannot amplify into N request failures.
          (A permanent fault DOES coalesce: the verdict is content-
          determined and would be negative-cached for all of them.)"""
        stale = req.base_key is not None and (rec.mode, rec.precision) != (
            req.base_key.mode,
            req.base_key.precision,
        )
        retryable_failure = (
            rec.status == "fail" and rec.fail_type in RETRYABLE_FAIL_TYPES
        )
        if stale or retryable_failure:
            self._release_lead(req)
            return
        ckey = req.cache_key
        checksum = self.cache.complete(
            ckey,
            now=finish,
            record=rec,
            result=result,
            shape=req.key.shape if req.key is not None else (0, 0, 0),
            replica=self.replica_id,
            request_id=req.id,
        )
        if checksum is not None:
            rec.extra = {**rec.extra, "artifact_checksum": checksum[:16]}
        for f in self._followers.pop(ckey, []):
            frec = dataclasses.replace(
                rec,
                request_id=f.id,
                arrival_s=f.arrival_s,
                queue_wait_s=max(0.0, finish - f.arrival_s),
                service_s=0.0,
                cache_hit=True,
                attempt=0,
            )
            self.engine.log.append(frec)
            self.stats.coalesced += 1
            self.completions.append(
                Completion(
                    id=f.id,
                    outcome="coalesced",
                    record=frec,
                    result=result,
                    arrival_s=f.arrival_s,
                    finish_s=finish,
                )
            )

    # ------------------------------------------------------------ dispatch

    def _seed_index(self, ready: list[int]) -> int:
        """Oldest ready request of the highest-priority class (FIFO within
        a class; ids break arrival ties deterministically). ``ready``
        indexes the queue entries not gated by retry backoff."""
        return min(
            ready,
            key=lambda i: (
                self.queue[i].priority_class.priority,
                self.queue[i].arrival_s,
                self.queue[i].id,
            ),
        )

    def _shed_expired(self, now: float) -> None:
        for req in [r for r in self.queue if r.deadline_s is not None and now > r.deadline_s]:
            self.queue.remove(req)
            self._reject(req, "deadline_expired", now)

    def _reject(self, req: ServeRequest, reason: str, now: float) -> None:
        self.stats.rejected[reason] = self.stats.rejected.get(reason, 0) + 1
        rec = self._log_shed(req.id, req.priority_class, req.arrival_s, reason, now=now)
        self.completions.append(
            Completion(
                id=req.id,
                outcome="rejected",
                record=rec,
                result=None,
                arrival_s=req.arrival_s,
                finish_s=now,
            )
        )
        # a shed single-flight leader must not strand its followers: the
        # pin is released and the followers re-enter the queue to serve
        # independently (they may themselves be shed on the next pass)
        self._release_lead(req)

    def _release_lead(self, req: ServeRequest) -> None:
        """Release a leader's single-flight pin without completing it:
        the pending placeholder is abandoned (bytes credited back) and
        every attached follower re-enters the queue as an independent
        compute-path request. Safe to call on non-leaders (no-op)."""
        if req.cache_key is None or self.cache is None:
            return
        ckey, req.cache_key = req.cache_key, None
        if self.cache.inflight_owner(ckey) == self.replica_id:
            self.cache.abandon(ckey)
        for f in self._followers.pop(ckey, []):
            f.cache_key = None
            self.queue.append(f)
        if self.queue:
            self.stats.max_queue_depth = max(
                self.stats.max_queue_depth, len(self.queue)
            )

    def _log_shed(self, rid, cls, arrival, reason, now=None):
        """Typed telemetry for a request shed before service."""
        now = arrival if now is None else now
        rec = TelemetryRecord(
            model=self.engine.cfg.name,
            mode="none",
            status="fail",
            times=StageTimes(),
            fail_type=reason,
            request_id=rid,
            arrival_s=arrival,
            queue_wait_s=max(0.0, now - arrival),
            priority_class=cls.name,
        )
        self.engine.log.append(rec)
        return rec

    def next_batch(self, now: Optional[float] = None) -> Optional[Batch]:
        """Form the next dispatch group at time ``now``: shed expired
        deadlines, pick the seed (priority order, FIFO within class),
        apply HBM admission (demote or reject an over-budget seed), then
        grow the group with same-class, same-signature requests while the
        summed working sets fit the admission budget."""
        now = self.clock.now() if now is None else now
        while True:
            self._shed_expired(now)
            ready = [
                i for i, r in enumerate(self.queue) if r.not_before_s <= now
            ]
            if not ready:
                # empty queue, or every queued request is in retry
                # backoff — next_ready_s() tells event loops when to wake
                return None
            seed = self.queue.pop(self._seed_index(ready))
            self._apply_breaker(seed, now)
            cap = self.cfg.admission_hbm_bytes
            if cap is not None and seed.key is not None and seed.bytes_priced > cap:
                form = self._demoted_form(seed)
                if form is None or form[1] > cap:
                    self._reject(seed, "admission_oom", now)
                    continue  # try the next seed
                self._apply_demotion(seed, *form)
            members = [seed]
            total = seed.bytes_priced
            # Batched dispatch prices the GROUP as one launch: every
            # member's bytes_priced carries one weight-pytree copy (see
            # _price), but a single batched launch streams the weights
            # once, so growth charges each joiner its marginal bytes
            # (bts - w_shared).  The seed's copy stays in ``total``.
            w_shared = self._group_weight_bytes(seed.key)
            if seed.key is not None:
                for req in [r for r in self.queue]:
                    if len(members) >= self.cfg.max_batch_requests:
                        break
                    if req.not_before_s > now:
                        continue  # still gated by retry backoff
                    # a candidate is judged at the form it would actually
                    # serve in: its breaker rung first (PEEKED, so no
                    # probe slot is claimed for a request we may not
                    # take), then — if over the cap — its DEMOTED form,
                    # so the requests an overload demotes still batch
                    # together instead of each paying a solo dispatch
                    key, bts, via_demotion = req.key, req.bytes_priced, False
                    if self.breaker is not None and req.base_key is not None:
                        key, bts = self._breaker_form(
                            req, self.breaker.peek_rung(req.base_key, now)
                        )
                    if cap is not None and key is not None and bts > cap:
                        form = self._demoted_form(req)
                        if form is None or form[1] > cap:
                            continue  # unservable; rejected when seeded
                        key, bts = form
                        via_demotion = True
                    if (
                        key == seed.key
                        and req.priority_class.name == seed.priority_class.name
                        and (cap is None or total + (bts - w_shared) <= cap)
                    ):
                        self.queue.remove(req)
                        self._apply_breaker(req, now)
                        if via_demotion:
                            self._apply_demotion(req, key, bts)
                        members.append(req)
                        total += bts - w_shared
            members.sort(key=lambda r: (r.arrival_s, r.id))
            self.stats.batches += 1
            self.stats.grouped_requests += len(members) - 1
            return Batch(requests=members, start_s=now)

    def _demoted_form(self, req: ServeRequest) -> Optional[tuple[GroupKey, int]]:
        """The request's shed-to-subvolume form — (failsafe GroupKey,
        re-priced bytes) — WITHOUT mutating the request (candidates are
        previewed for grouping and only demoted if actually admitted).
        None when demotion is off or the request already runs
        sub-volume."""
        if not self.cfg.allow_demotion or req.key is None or req.key.mode == "subvolume":
            return None
        from repro.core import executors

        eng = self.engine
        work_shape = (eng.cfg.cube + 2 * eng.cfg.overlap,) * 3
        key = GroupKey(
            mode="subvolume",
            executor=executors.resolve(
                req.executor or eng.cfg.executor,
                eng.cfg.model,
                work_shape,
                req.key.precision,
            ),
            devices=req.key.devices,
            precision=req.key.precision,
            shape=req.key.shape,
        )
        return key, self._price("subvolume", req.key.shape, req.key.precision)

    def _apply_demotion(self, req: ServeRequest, key: GroupKey, bts: int) -> None:
        req.key = key
        req.bytes_priced = bts
        req.demoted = True
        self._release_stale_lead(req)

    def _release_stale_lead(self, req: ServeRequest) -> None:
        """A leader's artifact key was derived at admission from its
        resolved (mode, precision) — the axes cache.artifact_key bakes in
        BECAUSE they change the artifact. Admission demotion and the
        breaker ladder mutate ``req.key`` after that derivation, so a
        demoted or ladder-degraded leader would produce a different
        artifact than the key it pinned promises: release the lead
        (pin abandoned, followers re-queued as independent requests)
        so the wrong-key store can never land. No-op while the
        effective (mode, precision) still match the derivation basis
        (``base_key`` — the signature the admission consult keyed on)."""
        if req.cache_key is None or req.key is None or req.base_key is None:
            return
        if (req.key.mode, req.key.precision) != (
            req.base_key.mode,
            req.base_key.precision,
        ):
            self._release_lead(req)

    def _breaker_form(
        self, req: ServeRequest, rung: int
    ) -> tuple[GroupKey, int]:
        """The (key, priced bytes) ``req`` serves at ``rung`` steps down
        the degradation ladder from its BASE signature, re-resolved
        through the executor registry and re-priced for admission. Rung
        0 is the base form (a restored breaker or a half-open probe);
        the walk caps at the ladder's bottom rung."""
        if rung <= 0:
            return req.base_key, req.base_bytes
        from repro.serving.resilience import demote_rung

        key = req.base_key
        for _ in range(rung):
            nxt = demote_rung(key, self.engine)
            if nxt is None:
                break  # already at the sub-volume failsafe
            key = nxt
        return key, self._price(key.mode, key.shape, key.precision)

    def _apply_breaker(self, req: ServeRequest, now: float) -> None:
        """Pin the request to its breaker-effective form on admission to
        a batch: claims the half-open probe slot when this request is
        the probe, walks the ladder otherwise. ``demoted`` tracks
        whether the EFFECTIVE mode is the sub-volume failsafe, so ladder
        restores un-demote and ladder bottoms count as demotions — same
        outcome vocabulary as admission demotion."""
        if self.breaker is None or req.base_key is None:
            return
        rung, probe = self.breaker.effective_rung(req.base_key, now)
        req.key, req.bytes_priced = self._breaker_form(req, rung)
        req.probe = probe
        req.demoted = (
            req.key.mode == "subvolume" and req.base_key.mode != "subvolume"
        )
        self._release_stale_lead(req)

    # ------------------------------------------------------------ service

    def run_batch(self, batch: Batch, now: Optional[float] = None) -> float:
        """Serve one dispatch group. Members run back-to-back (the
        engine's executors serve one forward at a time; grouping buys the
        shared compile/weights, not parallelism). Each member's telemetry
        is stamped with queue wait, service time, and the group size; a
        member that *raises* (garbage volume, executor bug) gets a typed
        failure record classified along the transient/permanent axis
        (serving/errors.py) while the rest of the group completes.
        Returns the batch finish time."""
        t, unserved = self.run_batch_until(batch, None, now=now)
        assert not unserved  # until=None serves every member
        return t

    def run_batch_until(
        self, batch: Batch, until: Optional[float], now: Optional[float] = None
    ) -> tuple[float, list]:
        """``run_batch`` with a service horizon: serve members in order
        while each would *finish* by ``until`` (virtual seconds), then
        stop. Returns ``(finish_time, unserved_tail)`` — the tail members
        were never executed, logged, or counted (exactly-once safety: the
        fleet layer re-dispatches them after a replica crash, and they
        must not have been served here first; the caller owns their
        ``stats.evacuated`` accounting). ``until=None`` serves everything
        (== ``run_batch``).

        A finite ``until`` requires the modeled path (a service model and
        ``execute=False``): truncation must *predict* each member's
        duration before running it, and only the analytic models can —
        measured execution would have to run the member to time it,
        defeating the exactly-once point."""
        if until is not None and (self.execute or self.service_model is None):
            raise ValueError(
                "run_batch_until with a finite horizon requires the "
                "modeled path (execute=False and a service model)"
            )
        start = batch.start_s if now is None else now
        t = start
        if self.service_model is not None:
            t += self.service_model.batch_overhead_s
        if (
            self.cfg.batched_dispatch
            and self.service_model is not None
            and not self.execute
            and len(batch.requests) > 1
            and batch.requests[0].key is not None
        ):
            return self._run_batched_launch(batch, until, t)
        for idx, req in enumerate(batch.requests):
            if until is not None:
                # preview the member's modeled duration WITHOUT serving
                # it — _attempt_record/_attempt_service are pure, so the
                # preview matches the serve exactly, injected faults,
                # straggler factors and timeouts included
                preview, p_decision = self._attempt_record(req, t)
                p_service, _ = self._attempt_service(preview, p_decision, req)
                if t + p_service > until:
                    return t, list(batch.requests[idx:])
            with self._member_scope(req.id, len(batch.requests)):
                result, rec, decision = self._serve_one(req, t)
                if self.service_model is not None:
                    service, timed_out = self._attempt_service(rec, decision, req)
                    if timed_out:
                        # the attempt is cancelled AT the bound: the member
                        # occupied the replica for exactly the timeout, and
                        # the fault is retryable (a retry lands on a fresh
                        # attempt — the CHIPS stuck-job discipline)
                        rec.status = "fail"
                        rec.fail_type = SERVICE_TIMEOUT
                else:
                    service = max(0.0, self.clock.now() - t)
                finish = t + service
                rec.request_id = req.id
                rec.arrival_s = req.arrival_s
                # wait = until THIS member's forward starts (batch overhead
                # and predecessors' serialized service included), so
                # queue_wait_s + service_s == finish - arrival exactly — the
                # identity the SLO rollups in telemetry/analysis.py rely on.
                # Retried attempts keep the ORIGINAL arrival, so the identity
                # spans every attempt of a request, not just the first.
                rec.queue_wait_s = max(0.0, t - req.arrival_s)
                rec.service_s = service
                rec.batch_size = len(batch.requests)
                rec.priority_class = req.priority_class.name
                rec.demoted = req.demoted
                rec.attempt = req.attempt
                self._finish_attempt(req, rec, result, finish)
            t = finish
        return t, []

    @contextlib.contextmanager
    def _member_scope(self, rid: int, group: int):
        """Real execution: one request scope per member, timed as
        ``sched.member`` (telemetry/spans.py) from its service to its
        terminal bookkeeping. The modeled path runs on a virtual clock
        and opens none."""
        if not self.execute:
            yield
            return
        with spans.request(rid), spans.span("sched.member", group=group):
            yield

    def _run_batched_launch(
        self, batch: Batch, until: Optional[float], t: float
    ) -> tuple[float, list]:
        """Serve a dispatch group as ONE batched kernel launch (modeled
        path, ``batched_dispatch`` only). The launch's service interval
        comes from a single batch-N modeled record — the byte models
        amortize the weight stream across the batch, so the launch is
        strictly cheaper than N serialized dispatches whenever the
        weight term is nonzero. Every member shares that interval:
        ``queue_wait_s = t - arrival`` and ``service_s = launch_service``
        so ``queue_wait_s + service_s == finish - arrival`` holds exactly
        per member, the identity the SLO rollups rely on.

        Fault injection stays per member (a transient flip fails one
        member's record, not the group), but a straggler or stuck member
        slows the WHOLE launch — one kernel finishes when its slowest
        device does. The class service timeout (uniform across the
        group: membership requires equal priority class) clips the
        launch, failing the still-ok members with ``service_timeout``.
        Horizon truncation is all-or-nothing: a single kernel either
        fits before ``until`` or none of it runs, so the unserved tail
        is the entire group."""
        reqs = batch.requests
        n = len(reqs)
        attempts = [self._attempt_record(req, t) for req in reqs]
        launch = self._modeled_record(reqs[0], batch=n)
        service = self.service_model.service_s(launch)
        factor, stuck = 1.0, False
        for rec, decision in attempts:
            if decision is not None and rec.status == "ok":
                if decision.kind == "straggler":
                    factor = max(factor, decision.slow_factor)
                elif decision.kind == "stuck":
                    stuck = True
        service = math.inf if stuck else service * factor
        timeout = (
            None
            if self.resilience is None
            else self.resilience.timeout_for(reqs[0].priority_class.name)
        )
        timed_out = False
        if timeout is not None and service > timeout:
            service, timed_out = timeout, True
        if math.isinf(service):
            raise ResilienceConfigError(
                f"stuck fault on class {reqs[0].priority_class.name!r} "
                "with no service timeout configured"
            )
        finish = t + service
        if until is not None and finish > until:
            return t, list(reqs)
        for req, (rec, decision) in zip(reqs, attempts):
            self.engine.log.append(rec)
            if timed_out and rec.status == "ok":
                rec.status, rec.fail_type = "fail", SERVICE_TIMEOUT
            rec.request_id = req.id
            rec.arrival_s = req.arrival_s
            rec.queue_wait_s = max(0.0, t - req.arrival_s)
            rec.service_s = service
            rec.batch_size = n
            rec.priority_class = req.priority_class.name
            rec.demoted = req.demoted
            rec.attempt = req.attempt
            self._finish_attempt(req, rec, None, finish)
        return finish, []

    def _finish_attempt(self, req, rec, result, finish: float) -> None:
        """Fold one finished service attempt into breaker, retry, and
        conservation state. A retryable fault with budget remaining is
        NON-terminal: the request re-enters its signature lane (original
        arrival stamp, backoff-gated) and no Completion is appended —
        the conservation sum counts requests, not attempts. Everything
        else is terminal exactly as before."""
        is_fault = (
            rec.status == "fail" and rec.fail_type in EXECUTION_FAULT_TYPES
        )
        if is_fault:
            if rec.fail_type == TRANSIENT_FAULT:
                self.stats.transient_faults += 1
            elif rec.fail_type == PERMANENT_FAULT:
                self.stats.permanent_faults += 1
            else:
                self.stats.timeouts += 1
        if self.breaker is not None and req.base_key is not None:
            self.breaker.on_result(
                req.base_key, fault=is_fault, probe=req.probe, now=finish
            )
        retryable = (
            rec.status == "fail" and rec.fail_type in RETRYABLE_FAIL_TYPES
        )
        if retryable:
            req.faults += 1
        if (
            retryable
            and self.resilience is not None
            and req.attempt + 1 < self.resilience.retry.max_attempts
        ):
            req.attempt += 1
            req.probe = False
            req.not_before_s = finish + self.resilience.retry.backoff_s(
                req.attempt, self.replica_id, req.id
            )
            self.stats.retries += 1
            self.queue.append(req)
            self.stats.max_queue_depth = max(
                self.stats.max_queue_depth, len(self.queue)
            )
            return
        outcome = "demoted" if req.demoted else "completed"
        if req.demoted:
            self.stats.demoted += 1
        else:
            self.stats.completed += 1
        if req.faults:
            self.stats.faulted_requests += 1
            if rec.status == "ok":
                self.stats.recovered_requests += 1
        self.completions.append(
            Completion(
                id=req.id,
                outcome=outcome,
                record=rec,
                result=result,
                arrival_s=req.arrival_s,
                finish_s=finish,
            )
        )
        if req.cache_key is not None and self.cache is not None:
            # single-flight leader reached a terminal state: store (or
            # negative-cache) the artifact and coalesce its followers
            self._complete_cache_leader(req, rec, result, finish)

    def _fault_decision(self, req: ServeRequest, t: float):
        """The seeded injector's verdict for this attempt — pure in
        (plan seed, time, replica, effective signature, request id,
        attempt). Keyed on the EFFECTIVE key: a breaker-demoted
        signature escapes rules that match only its faulty rung, which
        is what lets the ladder route around a poisoned executor."""
        if self.fault_plan is None or req.key is None:
            return None
        return self.fault_plan.decide(
            t=t,
            replica=self.replica_id,
            key=req.key,
            request_id=req.id,
            attempt=req.attempt,
            priority=req.priority_class.name,
        )

    def _attempt_record(self, req: ServeRequest, t: float):
        """(modeled record, fault decision) for one attempt at ``t`` —
        no logging, no state: the truncation preview and the actual
        serve call this with identical arguments and must agree."""
        rec = self._modeled_record(req)
        decision = self._fault_decision(req, t)
        if decision is not None and rec.status == "ok":
            if decision.kind == "transient":
                rec.status, rec.fail_type = "fail", TRANSIENT_FAULT
            elif decision.kind == "permanent":
                rec.status, rec.fail_type = "fail", PERMANENT_FAULT
            if rec.status == "fail":
                rec.extra = {
                    "injected": decision.kind,
                    "rule": decision.rule_index,
                }
        return rec, decision

    def _attempt_service(self, rec, decision, req: ServeRequest):
        """(service_s, timed_out) for one modeled attempt: the service
        model's duration, inflated by an injected straggler factor,
        infinite for a stuck fault, then clipped at the class's service
        timeout. The clip IS the cancellation — the attempt holds the
        replica for exactly the bound. A stuck fault with no timeout is
        unservable and raises typed (also rejected at construction)."""
        service = self.service_model.service_s(rec)
        if decision is not None and rec.status == "ok":
            if decision.kind == "straggler":
                service *= decision.slow_factor
            elif decision.kind == "stuck":
                service = math.inf
        timeout = (
            None
            if self.resilience is None
            else self.resilience.timeout_for(req.priority_class.name)
        )
        if timeout is not None and service > timeout:
            return timeout, True
        if math.isinf(service):
            raise ResilienceConfigError(
                f"stuck fault on class {req.priority_class.name!r} with "
                "no service timeout configured"
            )
        return service, False

    def evacuate(self, now: Optional[float] = None) -> list:
        """Hand every queued request back to the caller (fleet failover /
        drain re-dispatch): the queue empties, each popped request counts
        as ``evacuated`` in the conservation ledger — admitted here,
        served elsewhere. Returns the requests in (arrival, id) order so
        re-dispatch preserves FIFO fairness at the target replica.

        Single-flight state is torn down with the queue: every follower
        is popped into the evacuation set (it re-dispatches as an
        independent request), and every in-flight cache pin this replica
        owns is abandoned — including pins of unserved batch-tail
        leaders the fleet evacuates separately — so a crashed replica
        can never leave a pinned placeholder that blocks eviction
        forever."""
        out = list(self.queue)
        self.queue.clear()
        if self.cache is not None:
            for lst in self._followers.values():
                for f in lst:
                    f.cache_key = None
                    out.append(f)
            self._followers.clear()
            for req in out:
                if req.cache_key is not None:
                    self.cache.abandon(req.cache_key)
                    req.cache_key = None
            for ckey, owner in list(self.cache.inflight.items()):
                if owner == self.replica_id:
                    self.cache.abandon(ckey)
        out.sort(key=lambda r: (r.arrival_s, r.id))
        self.stats.evacuated += len(out)
        return out

    def cancel(self, rid: int):
        """Remove ONE queued request before service — the fleet's
        hedge-loser cancellation (serving/fleet.py): its twin completed
        elsewhere, so this copy must never serve. Counted ``evacuated``
        in the conservation ledger (admitted here, resolved elsewhere —
        the same terminal state crash evacuation uses). Returns the
        request, or None when it is not queued (already served, shed,
        or never here) — in which case nothing changes. A cancelled
        single-flight leader releases its pin and re-queues its
        followers; a cancelled follower is plucked from its leader's
        list without disturbing the leader."""
        for req in self.queue:
            if req.id == rid:
                self.queue.remove(req)
                self.stats.evacuated += 1
                self._release_lead(req)
                return req
        for ckey in list(self._followers):
            for f in self._followers[ckey]:
                if f.id == rid:
                    self._followers[ckey].remove(f)
                    if not self._followers[ckey]:
                        del self._followers[ckey]
                    f.cache_key = None
                    self.stats.evacuated += 1
                    return f
        return None

    def next_ready_s(self, now: float) -> Optional[float]:
        """When every queued request is gated by retry backoff, the
        earliest ``not_before_s`` — the wake time event loops must
        advance to (the virtual clock cannot busy-wait). None when the
        queue is empty or some request is ready now."""
        if not self.queue:
            return None
        earliest = min(r.not_before_s for r in self.queue)
        return earliest if earliest > now else None

    def peek_signature(
        self,
        vol,
        *,
        mode: Optional[str] = None,
        executor: Optional[str] = None,
        devices: Optional[int] = None,
        precision: Optional[str] = None,
    ) -> tuple[Optional[GroupKey], int]:
        """Resolve the admission signature + priced bytes a request WOULD
        get, without enqueueing it — the fleet router's affinity key
        (serving/fleet.py steers same-signature requests to replicas with
        warm compiled executables). Shares the scheduler's resolution
        cache, so peeking then submitting costs one resolution."""
        probe = ServeRequest(
            id=-1,
            vol=vol,
            priority_class=PriorityClass("peek", 0),
            arrival_s=0.0,
            deadline_s=None,
            mode=mode,
            executor=executor,
            devices=devices,
            precision=precision,
        )
        return self._resolve(probe)

    def _serve_one(self, req: ServeRequest, t: float):
        """(PipelineResult | None, TelemetryRecord, FaultDecision | None)
        for one service attempt — real execution with typed-failure
        capture, or the modeled record of the pure discrete-event mode.
        Either way, raised exceptions are CLASSIFIED along the
        transient/permanent axis (serving/errors.py) instead of stamped
        with PR 5's blanket ``executor_error``, and the seeded fault
        plan can inject faults on this attempt."""
        key = req.key
        if not self.execute:
            rec, decision = self._attempt_record(req, t)
            self.engine.log.append(rec)
            return None, rec, decision
        decision = self._fault_decision(req, t)
        try:
            if decision is not None and decision.kind in ("transient", "permanent"):
                err = (
                    TransientExecutorError
                    if decision.kind == "transient"
                    else PermanentExecutorError
                )
                raise err(
                    f"injected {decision.kind} fault "
                    f"(rule {decision.rule_index})"
                )
            result = self.engine._run_request(
                req.vol,
                mode=key.mode if key else req.mode,
                executor=key.executor if key else req.executor,
                devices=key.devices if key else req.devices,
                precision=key.precision if key else req.precision,
                # native-shape mode serves the request at its own
                # geometry (the shape admission priced); legacy mode
                # leaves the engine to conform to its card's shape.
                volume_shape=key.shape
                if key and self.cfg.native_shapes
                else None,
            )
            return result, result.record, decision
        except Exception as e:  # fault isolation: one bad request != batch
            rec = TelemetryRecord(
                model=self.engine.cfg.name,
                mode=key.mode if key else "none",
                status="fail",
                times=StageTimes(),
                executor=key.executor if key else None,
                precision=key.precision if key else None,
                fail_type=classify(e),
                spans=spans.recorded(),
                extra={"error": f"{type(e).__name__}: {e}"},
            )
            self.engine.log.append(rec)
            return None, rec, decision

    def _modeled_record(self, req: ServeRequest, batch: int = 1) -> TelemetryRecord:
        """Synthesized telemetry for ``execute=False`` runs: status and
        modeled bytes come from the same pre-flight models the pipeline
        uses, with zero wall-clock compute — the large-sweep mode of the
        load simulator.  ``batch > 1`` models the request as an N-volume
        batched launch: the byte models amortize the weight stream across
        the batch, which is what makes a single batched dispatch cheaper
        than N serialized ones."""
        from repro.core import executors
        from repro.kernels import quantize

        key = req.key
        if key is None:
            return TelemetryRecord(
                model=self.engine.cfg.name,
                mode="none",
                status="fail",
                times=StageTimes(),
                fail_type=PERMANENT_FAULT,
                extra={"error": "garbage volume (modeled)"},
            )
        cfg = self.engine.cfg
        rec = TelemetryRecord(
            model=cfg.name,
            mode=key.mode,
            status="ok",
            times=StageTimes(),
            executor=key.executor,
            precision=key.precision,
            params_bytes=quantize.model_params_bytes(cfg.model, key.precision),
        )
        try:
            if key.devices is not None and key.devices > 1:
                import jax

                if key.devices > jax.device_count():
                    from repro.core.spatial_shard import ShardGeometryError

                    raise ShardGeometryError(
                        f"sharded executor wants {key.devices} devices; "
                        f"host has {jax.device_count()}"
                    )
            if key.mode == "subvolume":
                ncubes = math.prod(-(-s // cfg.cube) for s in key.shape)
                cube_shape = (cfg.cube + 2 * cfg.overlap,) * 3
                per = executors.modeled_hbm_bytes(
                    key.executor,
                    cfg.model,
                    cube_shape,
                    batch=batch,
                    precision=key.precision,
                )
                rec.hbm_bytes_modeled = None if per is None else ncubes * per
                rec.collective_bytes_modeled = (
                    ncubes
                    * executors.modeled_collective_bytes(
                        key.executor,
                        cfg.model,
                        cube_shape,
                        batch=batch,
                        precision=key.precision,
                    )
                )
            else:
                rec.hbm_bytes_modeled = executors.modeled_hbm_bytes(
                    key.executor,
                    cfg.model,
                    key.shape,
                    batch=batch,
                    precision=key.precision,
                )
                rec.collective_bytes_modeled = executors.modeled_collective_bytes(
                    key.executor,
                    cfg.model,
                    key.shape,
                    batch=batch,
                    precision=key.precision,
                )
        except ValueError as e:
            from repro.core.spatial_shard import ShardGeometryError

            rec.status = "fail"
            rec.fail_type = (
                "shard_geometry" if isinstance(e, ShardGeometryError) else "vmem_oom"
            )
        return rec

    # ------------------------------------------------------------ draining

    def has_work(self) -> bool:
        return bool(self.queue)

    def drain(self) -> list[Completion]:
        """Serve until the queue is empty; returns the completions NEW
        since the previous drain (terminal states of every request
        admitted since then), id-ordered — so a submit/drain service
        loop never re-delivers a result. ``self.completions`` keeps the
        full ledger for the simulator and post-hoc analysis."""
        while True:
            batch = self.next_batch()
            if batch is None:
                if not self.queue:
                    break
                # every queued request is in retry backoff: pass the
                # time — a virtual clock jumps, the production clock
                # sleeps (drain is the synchronous service loop; the
                # simulator's event loops advance instead of blocking)
                wake = self.next_ready_s(self.clock.now())
                if wake is None:
                    continue  # raced: something became ready
                if hasattr(self.clock, "advance_to"):
                    self.clock.advance_to(wake)
                else:
                    time.sleep(max(0.0, wake - self.clock.now()))
                continue
            self.run_batch(batch)
        assert self.stats.conserved(), (
            f"conservation violated: {self.stats}"
        )
        fresh = self.completions[self._drained:]
        self._drained = len(self.completions)
        return sorted(fresh, key=lambda c: c.id)
