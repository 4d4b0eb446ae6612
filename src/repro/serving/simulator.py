"""Deterministic discrete-event load simulator for the serving scheduler.

The paper validates Brainchop against a fleet of 1336 heterogeneous
browser sessions; this module is the serving-tier analogue — a seeded,
virtual-clock traffic generator that drives ``RequestScheduler`` through
the load shapes a segmentation service actually sees, so every latency /
throughput / shed-rate number is **bit-reproducible in CI on CPU**:

  * arrivals come from seeded processes on a *virtual* clock —
    ``poisson`` (steady Erlang traffic), ``burst`` (a quiet baseline with
    periodic request storms), ``diurnal`` (a thinned inhomogeneous
    Poisson ramp, the clinic-hours curve);
  * each arrival samples a **scenario mix** entry (shape, precision,
    device count, priority class, deliberately-garbage volumes) from the
    same seeded generator;
  * service time is *modeled*, not measured: ``ServiceModel`` converts
    each request's modeled HBM + collective bytes (telemetry/traffic.py)
    into virtual seconds at configured bandwidths, with a per-batch
    dispatch overhead that makes grouping visible in the numbers — the
    same bytes-are-the-cost methodology as the budget model (DESIGN.md
    §1, §5);
  * the event loop is single-server: batches serve back-to-back, arrivals
    landing mid-service queue behind them, deadlines expire on the
    virtual clock. No wall-clock value enters any decision or summary.

``simulate`` returns a ``SimReport`` whose ``summary()`` dict (rounded,
key-sorted) is what the golden-trace regression tests serialize and the
virtual-hour soaks (tests/test_serving_soak.py) assert on — two runs with
one seed are byte-identical, so scheduler behavior changes show up as
review diffs, never as flakes.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import math
from typing import Optional

import numpy as np

from repro.serving.scheduler import (
    Completion,
    PriorityClass,
    QueueFullError,
    RequestScheduler,
    SchedulerConfig,
)
from repro.telemetry.analysis import nearest_rank


class VirtualClock:
    """A settable clock: ``now()`` is whatever the event loop last set.
    The scheduler only ever *reads* it, so scheduling decisions are pure
    functions of event times."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def now(self) -> float:
        return self.t

    def advance_to(self, t: float) -> None:
        self.t = max(self.t, float(t))


@dataclasses.dataclass(frozen=True)
class ServiceModel:
    """Virtual service time from modeled bytes — deterministic by
    construction. Bandwidths default to v5e-ish magnitudes; the absolute
    scale matters less than being *fixed*, because the gated numbers are
    compared against a committed baseline, not against hardware.

    ``service_s = base + hbm_bytes/hbm_bw + collective_bytes/ici_bw``,
    and a failed request costs ``fail_s`` (admission work, no forward).
    ``batch_overhead_s`` is charged once per dispatch group — the
    compile-cache/dispatch cost grouping amortizes.

    Under ``SchedulerConfig.batched_dispatch`` the scheduler evaluates
    ``service_s`` ONCE per dispatch group, on a single batch-N modeled
    record whose byte models amortize the weight stream across the
    batch (telemetry/traffic.py) — so the launch interval is
    sub-additive in group size and the overload throughput cliff moves.
    That amortization lives in the byte models; no formula here changes.
    """

    hbm_gbps: float = 819.0
    ici_gbps: float = 90.0
    base_s: float = 0.010
    fail_s: float = 0.002
    batch_overhead_s: float = 0.040

    def service_s(self, record) -> float:
        if record.status != "ok":
            return self.fail_s
        hbm = record.hbm_bytes_modeled or 0
        ici = record.collective_bytes_modeled or 0
        return self.base_s + hbm / (self.hbm_gbps * 1e9) + ici / (self.ici_gbps * 1e9)


# ------------------------------------------------------------- arrivals ---


def poisson_arrivals(rate_hz: float, horizon_s: float, rng: np.random.Generator):
    """Homogeneous Poisson process: exponential inter-arrival gaps."""
    t, out = 0.0, []
    while True:
        t += float(rng.exponential(1.0 / rate_hz))
        if t >= horizon_s:
            return out
        out.append(t)


def burst_arrivals(
    base_hz: float,
    burst_hz: float,
    period_s: float,
    burst_len_s: float,
    horizon_s: float,
    rng: np.random.Generator,
):
    """Quiet Poisson baseline plus periodic storms: every ``period_s`` a
    window of ``burst_len_s`` runs at ``burst_hz`` on top of the base."""
    out = list(poisson_arrivals(base_hz, horizon_s, rng))
    start = 0.0
    while start < horizon_s:
        end = min(start + burst_len_s, horizon_s)
        t = start
        while True:
            t += float(rng.exponential(1.0 / burst_hz))
            if t >= end:
                break
            out.append(t)
        start += period_s
    return sorted(out)


def diurnal_arrivals(peak_hz: float, horizon_s: float, rng: np.random.Generator):
    """Inhomogeneous Poisson by thinning: rate ramps 0 -> peak -> 0 over
    the horizon (one 'day' of clinic traffic compressed into it)."""
    out = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / peak_hz))
        if t >= horizon_s:
            return out
        accept = 0.5 * (1.0 - math.cos(2.0 * math.pi * t / horizon_s))
        if float(rng.random()) < accept:
            out.append(t)


ARRIVAL_PROCESSES = {
    "poisson": poisson_arrivals,
    "burst": burst_arrivals,
    "diurnal": diurnal_arrivals,
}


# -------------------------------------------------------- content skew ---

#: memoized Zipf CDFs keyed on (s, n) — the CDF is a pure function of
#: the distribution parameters, so sharing it across runs cannot couple
#: their draws (each draw's coin is an independent unit_hash).
_ZIPF_CDF_CACHE: dict = {}


def zipf_content_id(seed: int, index: int, s: float, n: int) -> int:
    """The ``index``-th arrival's content identity under a Zipf(s)
    popularity law over ``n`` distinct volumes — id 0 is the hottest.

    Deterministic by construction: the uniform coin is
    ``unit_hash("zipf", seed, index)`` (the counter-hash discipline of
    serving/resilience.py), NOT a shared RNG stream — so adding or
    removing OTHER randomness in a scenario cannot perturb which content
    arrives when, and two runs with one seed draw byte-identical content
    traces. Inverse-CDF over the memoized normalized Zipf weights."""
    from repro.serving.resilience import unit_hash

    key = (float(s), int(n))
    cdf = _ZIPF_CDF_CACHE.get(key)
    if cdf is None:
        weights = [1.0 / (k ** float(s)) for k in range(1, int(n) + 1)]
        total = sum(weights)
        acc, cdf = 0.0, []
        for w in weights:
            acc += w / total
            cdf.append(acc)
        _ZIPF_CDF_CACHE[key] = cdf
    u = unit_hash("zipf", seed, index)
    return min(bisect.bisect_left(cdf, u), int(n) - 1)


# ------------------------------------------------------------ scenarios ---


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One entry of the traffic mix: what an arriving request asks for.
    ``weight`` is its sampling probability mass; ``garbage=True`` ships a
    malformed volume (the fault-injection lane — must fail typed, alone)."""

    shape: tuple = (16, 16, 16)
    mode: Optional[str] = None
    executor: Optional[str] = None
    devices: Optional[int] = None
    precision: Optional[str] = None
    priority: str = "standard"
    weight: float = 1.0
    garbage: bool = False


@dataclasses.dataclass
class SimConfig:
    """One simulator run: seeded arrivals over a scenario mix, through a
    scheduler configured for the experiment."""

    name: str = "steady"
    seed: int = 0
    horizon_s: float = 600.0
    process: str = "poisson"
    process_kwargs: dict = dataclasses.field(default_factory=lambda: {"rate_hz": 0.5})
    mix: tuple = (ScenarioSpec(),)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    execute: bool = False
    service: ServiceModel = dataclasses.field(default_factory=ServiceModel)
    # resilience policy + seeded fault injection (serving/resilience.py);
    # both None keeps the PR 5 behavior — and the committed golden
    # traces — bit-for-bit unchanged.
    resilience: Optional[object] = None
    fault_plan: Optional[object] = None
    # content-addressed artifact cache (serving/cache.py): a CacheConfig
    # here puts the cache tier in front of admission; None (default)
    # keeps every pre-cache scenario — and its golden trace — untouched.
    cache: Optional[object] = None
    # Zipf popularity skew over request *content*: ``content_skew`` is
    # the Zipf exponent s (None disables content identity entirely) and
    # ``content_universe`` the number of distinct volumes. Only modeled
    # (stub) volumes get identities — the skew machinery is a cache
    # workload generator, not an MRI synthesizer.
    content_skew: Optional[float] = None
    content_universe: int = 64


@dataclasses.dataclass
class SimReport:
    cfg: SimConfig
    scheduler: RequestScheduler
    completions: list
    arrived: int
    refused: int

    def summary(self) -> dict:
        """The deterministic rollup: counts, conservation, and per-class
        virtual-latency percentiles (nearest-rank; rounded to fixed
        decimals so serialization is byte-stable). This dict IS the
        golden-trace payload."""
        st = self.scheduler.stats
        by_class: dict[str, list[Completion]] = {}
        for c in self.completions:
            by_class.setdefault(c.record.priority_class or "?", []).append(c)
        classes = {}
        for name in sorted(by_class):
            cs = by_class[name]
            served = [
                c
                for c in cs
                if c.outcome in ("completed", "demoted", "coalesced")
            ]
            e2e = [c.finish_s - c.arrival_s for c in served]
            wait = [c.record.queue_wait_s or 0.0 for c in served]
            classes[name] = {
                "requests": len(cs),
                "served": len(served),
                "demoted": sum(1 for c in cs if c.outcome == "demoted"),
                "rejected": sum(1 for c in cs if c.outcome == "rejected"),
                "ok_rate": _round(
                    sum(1 for c in served if c.record.status == "ok")
                    / max(len(served), 1)
                ),
                "latency_ms": _pctls_ms(e2e),
                "queue_wait_ms": _pctls_ms(wait),
            }
        served_all = [
            c
            for c in self.completions
            if c.outcome in ("completed", "demoted", "coalesced")
        ]
        out = {
            "scenario": self.cfg.name,
            "seed": self.cfg.seed,
            "horizon_s": _round(self.cfg.horizon_s),
            "process": self.cfg.process,
            "requests": {
                "arrived": self.arrived,
                "refused": self.refused,
                "admitted": st.admitted,
                "completed": st.completed,
                "demoted": st.demoted,
                "rejected": dict(sorted(st.rejected.items())),
                "conserved": st.conserved(),
            },
            "batches": st.batches,
            "mean_batch_size": _round(len(served_all) / max(st.batches, 1)),
            "max_queue_depth": st.max_queue_depth,
            "throughput_rps": _round(len(served_all) / self.cfg.horizon_s),
            "latency_ms": _pctls_ms([c.finish_s - c.arrival_s for c in served_all]),
            "classes": classes,
        }
        if self.cfg.resilience is not None or self.cfg.fault_plan is not None:
            # only stamped when the resilience layer is configured, so
            # the PR 5 golden summaries stay byte-identical
            out["resilience"] = resilience_block(self.scheduler, served_all)
        if self.cfg.cache is not None:
            # same discipline: the cache rollup exists only for cache
            # scenarios, so pre-cache goldens stay byte-identical
            out["cache"] = cache_block(self.scheduler, served_all)
        return out

    def to_json(self) -> str:
        return json.dumps(self.summary(), indent=1, sort_keys=True)


def _round(x: float, nd: int = 4) -> float:
    return round(float(x), nd)


def _pctls_ms(values) -> dict:
    ms = [v * 1e3 for v in values]
    return {
        "p50": _round(nearest_rank(ms, 50)),
        "p99": _round(nearest_rank(ms, 99)),
        "mean": _round(sum(ms) / len(ms) if ms else 0.0),
        "max": _round(max(ms) if ms else 0.0),
    }


def resilience_block(sched, served) -> dict:
    """The deterministic resilience rollup of ONE scheduler — retry /
    fault / recovery counters, breaker state machine history, and
    per-rung serve counts (which executor rung actually answered each
    served request — the degradation ladder made visible). Shared by the
    single-server summary here and the per-replica aggregation in
    serving/fleet.py."""
    st = sched.stats
    rungs: dict[str, int] = {}
    for c in served:
        label = f"{c.record.mode}/{c.record.executor or '-'}"
        rungs[label] = rungs.get(label, 0) + 1
    br = sched.breaker
    return {
        "retries": st.retries,
        "faults": {
            "transient": st.transient_faults,
            "permanent": st.permanent_faults,
            "timeout": st.timeouts,
        },
        "faulted_requests": st.faulted_requests,
        "recovered_requests": st.recovered_requests,
        "recovery_rate": _round(
            st.recovered_requests / max(st.faulted_requests, 1)
        ),
        "breaker": None
        if br is None
        else {
            "trips": br.trips,
            "restores": br.restores,
            "probes": br.probes,
            "open_signatures": br.open_signature_labels(),
            "transitions": br.transitions,
        },
        "rungs": dict(sorted(rungs.items())),
    }


def cache_block(sched, served) -> dict:
    """The deterministic artifact-cache rollup of ONE scheduler: the
    cache tier's own counters (hits, quarantines, breaker trips, bytes)
    plus the scheduler's terminal cache accounting — admission-time
    hits, single-flight coalesced completions, and how many served
    requests never touched a device. Shared by the single-server
    summary and the fleet aggregation (serving/fleet.py)."""
    st = sched.stats
    out = dict(sched.cache.summary()) if sched.cache is not None else {}
    out["admission_hits"] = st.cache_hits
    out["coalesced"] = st.coalesced
    out["served_from_cache"] = sum(1 for c in served if c.record.cache_hit)
    return out


def _sample_mix(mix, rng: np.random.Generator) -> ScenarioSpec:
    weights = np.array([s.weight for s in mix], dtype=np.float64)
    idx = int(rng.choice(len(mix), p=weights / weights.sum()))
    return mix[idx]


class _ShapeStub:
    """What an ``execute=False`` request carries instead of voxels: the
    modeled path only ever reads ``.shape``, so a 21k-arrival soak must
    not allocate gigabytes of random volumes nobody reads.

    ``content_id`` is the stub's content identity for the artifact cache
    (serving/cache.py): two stubs with equal (shape, content_id) hash to
    the same content — the modeled stand-in for byte-equal volumes.
    ``None`` (the default, and every pre-cache scenario) means "no
    content identity": the cache consult bypasses, so legacy traces are
    untouched."""

    __slots__ = ("shape", "content_id")

    def __init__(self, shape, content_id=None):
        self.shape = tuple(shape)
        self.content_id = content_id


def _make_volume(spec: ScenarioSpec, rng: np.random.Generator, execute: bool):
    """A cheap deterministic volume (numpy, not MRI synthesis — the
    simulator load-tests the scheduler, not the segmenter); a shape-only
    stub when nothing will execute. Garbage specs ship a 1-D payload the
    pipeline cannot conform — the typed-failure lane."""
    if spec.garbage:
        return np.zeros((3,), np.float32) if execute else _ShapeStub((3,))
    if not execute:
        return _ShapeStub(spec.shape)
    return rng.random(spec.shape, dtype=np.float32)


def simulate(engine, cfg: SimConfig) -> SimReport:
    """Drive ``engine`` through one simulated load trace. Single-server
    discrete-event loop: deliver arrivals up to the clock, dispatch the
    next admission group, advance the clock by its modeled service, shed
    whatever expired meanwhile — until both the trace and the queue are
    empty."""
    rng = np.random.default_rng(cfg.seed)
    proc = ARRIVAL_PROCESSES[cfg.process]
    times = proc(horizon_s=cfg.horizon_s, rng=rng, **cfg.process_kwargs)
    arrivals = [(t, _sample_mix(cfg.mix, rng)) for t in times]
    # volumes drawn AFTER the full arrival/mix sequence so request payloads
    # never perturb arrival sampling (keeps traces comparable across mixes
    # and between execute modes — stubs simply skip the unread draws)
    vols = [_make_volume(spec, rng, cfg.execute) for _, spec in arrivals]
    if cfg.content_skew is not None:
        # content identities are per-index counter-hash draws (NOT the
        # shared rng), so enabling skew cannot perturb the arrival or
        # mix sequences above; garbage volumes stay identity-less
        for idx, ((_, spec), v) in enumerate(zip(arrivals, vols)):
            if isinstance(v, _ShapeStub) and not spec.garbage:
                v.content_id = zipf_content_id(
                    cfg.seed, idx, cfg.content_skew, cfg.content_universe
                )

    cache = None
    if cfg.cache is not None:
        from repro.serving.cache import ArtifactCache, CacheConfig

        cache = (
            cfg.cache
            if isinstance(cfg.cache, ArtifactCache)
            else ArtifactCache(
                cfg.cache if isinstance(cfg.cache, CacheConfig) else None,
                fault_plan=cfg.fault_plan,
            )
        )
    clock = VirtualClock()
    sched = RequestScheduler(
        engine,
        cfg.scheduler,
        clock=clock,
        service_model=cfg.service,
        execute=cfg.execute,
        resilience=cfg.resilience,
        fault_plan=cfg.fault_plan,
        cache=cache,
    )
    i = 0
    refused = 0
    n = len(arrivals)
    while i < n or sched.has_work():
        if not sched.has_work():
            # idle: jump to the next arrival
            clock.advance_to(arrivals[i][0])
        # deliver everything that has arrived by now
        while i < n and arrivals[i][0] <= clock.now():
            t, spec = arrivals[i]
            try:
                sched.submit(
                    vols[i],
                    priority=spec.priority,
                    mode=spec.mode,
                    executor=spec.executor,
                    devices=spec.devices,
                    precision=spec.precision,
                    arrival_s=t,
                )
            except QueueFullError:
                refused += 1
            i += 1
        batch = sched.next_batch(now=clock.now())
        if batch is None:
            wake = sched.next_ready_s(clock.now())
            if wake is not None:
                # every queued request is in retry backoff: advance to
                # whichever comes first — the next arrival or the
                # earliest backoff expiry (the virtual clock must jump;
                # it cannot busy-wait)
                if i < n and arrivals[i][0] < wake:
                    clock.advance_to(arrivals[i][0])
                else:
                    clock.advance_to(wake)
            continue  # else: everything queued just expired; next arrival
        finish = sched.run_batch(batch)
        clock.advance_to(finish)
    completions = sorted(sched.completions, key=lambda c: c.id)
    assert sched.stats.conserved(), f"conservation violated: {sched.stats}"
    return SimReport(
        cfg=cfg, scheduler=sched, completions=completions, arrived=n, refused=refused
    )


def reference_engine():
    """The canonical engine the committed traces are generated against:
    a tiny CPU-friendly configuration (the simulator load-tests the
    scheduler, not the kernels). Used by the golden-trace tests and the
    soaks — both MUST price admission off the same model or the
    byte-identical claim breaks."""
    import jax

    from repro.core import meshnet
    from repro.core.meshnet import MeshNetConfig
    from repro.core.pipeline import PipelineConfig
    from repro.serving.engine import SegmentationEngine

    cfg = MeshNetConfig()
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    pc = PipelineConfig(
        model=cfg,
        volume_shape=(16, 16, 16),
        cube=8,
        overlap=4,
        min_component_size=4,
        executor="xla",
    )
    return SegmentationEngine(params, pc)


# ------------------------------------------------------- scenario presets ---

#: heterogeneous mix exercised by every preset (single-server AND fleet,
#: serving/fleet.py): two shapes, two storage policies, all three
#: priority classes, and a garbage lane.
STANDARD_MIX = (
    ScenarioSpec(shape=(16, 16, 16), priority="interactive", weight=3.0),
    ScenarioSpec(shape=(16, 16, 16), precision="bf16", priority="standard", weight=3.0),
    ScenarioSpec(shape=(32, 32, 32), precision="int8w", priority="standard", weight=2.0),
    # the fp32 heavyweight lane: ~1.7 MiB streaming working set — the one
    # the overload preset's 1 MiB admission budget demotes to the failsafe
    ScenarioSpec(shape=(32, 32, 32), priority="standard", weight=1.0),
    ScenarioSpec(shape=(32, 32, 32), mode="subvolume", priority="batch", weight=1.5),
    ScenarioSpec(shape=(16, 16, 16), garbage=True, priority="standard", weight=0.5),
)


def preset(name: str, seed: int = 0, horizon_s: Optional[float] = None) -> SimConfig:
    """The three committed load scenarios (golden traces):

    ``steady``   — Poisson arrivals well under capacity: the queue stays
                   shallow, nothing sheds; the latency floor.
    ``burst``    — quiet baseline with 20x request storms: queues spike,
                   deadlines hold, grouping absorbs most of it.
    ``overload`` — sustained arrivals beyond service capacity into a
                   short queue with a tight admission budget: the
                   scheduler must shed via typed rejection + demotion,
                   and conservation must still hold (zero lost requests).

    Any preset also exists in a ``<name>_batched`` variant: the same
    trace, same seed, same service model, with
    ``SchedulerConfig.batched_dispatch=True`` — each dispatch group
    serves as ONE batched launch whose weight stream amortizes across
    the members. Comparing ``overload`` vs ``overload_batched`` on one
    seed isolates the batching win.
    """
    if name.endswith("_batched"):
        cfg = preset(name[: -len("_batched")], seed=seed, horizon_s=horizon_s)
        cfg.name = name
        cfg.scheduler = dataclasses.replace(cfg.scheduler, batched_dispatch=True)
        return cfg
    if name == "steady":
        return SimConfig(
            name="steady",
            seed=seed,
            horizon_s=horizon_s or 600.0,
            process="poisson",
            process_kwargs={"rate_hz": 0.5},
            mix=STANDARD_MIX,
            scheduler=SchedulerConfig(
                max_queue_depth=64,
                admission_hbm_bytes=512 * 1024 * 1024,
                max_batch_requests=8,
                native_shapes=True,
            ),
        )
    if name == "burst":
        return SimConfig(
            name="burst",
            seed=seed,
            horizon_s=horizon_s or 600.0,
            process="burst",
            process_kwargs={
                "base_hz": 0.2,
                "burst_hz": 20.0,
                "period_s": 120.0,
                "burst_len_s": 15.0,
            },
            mix=STANDARD_MIX,
            scheduler=SchedulerConfig(
                max_queue_depth=64,
                admission_hbm_bytes=512 * 1024 * 1024,
                max_batch_requests=8,
                native_shapes=True,
            ),
        )
    if name == "overload":
        return SimConfig(
            name="overload",
            seed=seed,
            horizon_s=horizon_s or 600.0,
            # the diurnal ramp's midday peak runs well past service
            # capacity (slower service model below), so the scheduler MUST
            # shed: queue-full refusals, expired deadlines, and sub-volume
            # demotions — with conservation still exact.
            process="diurnal",
            process_kwargs={"peak_hz": 12.0},
            mix=STANDARD_MIX,
            scheduler=SchedulerConfig(
                max_queue_depth=32,
                # tight: a 32^3 fp32 streaming working set (~1.7 MiB) does
                # not fit -> those requests demote to the failsafe
                admission_hbm_bytes=1 * 1024 * 1024,
                max_batch_requests=8,
                native_shapes=True,
                # tighter deadlines than the default ladder: midday queue
                # waits overrun them, so expiry shedding is exercised too
                # (interactive stays protected by priority; standard sheds
                # its tail; batch trades starvation for a staleness bound)
                classes={
                    "interactive": PriorityClass("interactive", 0, deadline_s=10.0),
                    "standard": PriorityClass("standard", 1, deadline_s=2.5),
                    "batch": PriorityClass("batch", 2, deadline_s=30.0),
                },
            ),
            service=ServiceModel(base_s=0.1, batch_overhead_s=0.05),
        )
    raise KeyError(f"unknown scenario preset {name!r}: steady | burst | overload")
