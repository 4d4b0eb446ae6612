"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell is made of is found by name in ``BENCHMARK.json``: its
configuration file (``configs/<config>.json``), its traffic file
(``traffic/<traffic>.json``) and one reader per metric
(``metrics/<metric>.py``, a ``read(run)`` that returns a number or None).

The served path is the program's own: ``SegmentationEngine`` ->
``RequestScheduler`` (``submit`` with the due time as ``arrival_s``,
``next_batch``, ``run_batch``) -> ``pipeline.run``; each segmentation is
then fetched to the host, as a client receives it. The run fails, and
prints no result, where a request resolves another executor or precision
than the configuration states, is demoted, shed or faulted, or where
anything compiles inside the window.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
import sys
import time
from contextlib import nullcontext
from typing import Any, Callable, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

#: the compile events JAX reports for a program it has to trace, lower,
#: or compile (or load from the persistent cache) in this process
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


class BenchFailure(Exception):
    """A run that must print no result."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())

    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    layer = [m for m in spec["per_layer"] if workload in m["workloads"]]
    return Cell(workload, config, traffic, int(w["chips"]), e2e, layer)


def seeds(seed: int) -> tuple[Any, np.random.Generator]:
    """(JAX key, NumPy generator) from any whole number: JAX's PRNGKey
    keeps only 32 bits of a larger seed, so both come from one
    ``SeedSequence``."""
    import jax

    ss = np.random.SeedSequence(int(seed))
    word = int(ss.generate_state(1, np.uint32)[0])
    return jax.random.PRNGKey(word), np.random.default_rng(ss)


class CompileCounter:
    """Counts JAX's compile events while armed."""

    def __init__(self):
        self.armed = False
        self.events: list[str] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.events.append(f"{event} {kwargs.get('fun_name', '')}".strip())

    def install(self) -> "CompileCounter":
        from jax._src import monitoring

        monitoring.register_event_duration_secs_listener(self)
        return self


def check_tpu(devices, chips: int) -> None:
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchFailure(f"no TPU: JAX platform is {dev.platform!r}")
    if len(devices) < chips:
        raise BenchFailure(f"the cell needs {chips} chips, JAX sees {len(devices)}")


def check_completion(c, expect: dict) -> None:
    rec = c.record
    if c.outcome != "completed":
        raise BenchFailure(f"request {c.id}: outcome {c.outcome} "
                           f"({rec.fail_type}), demoted or shed by the scheduler")
    if rec.status != "ok":
        raise BenchFailure(f"request {c.id}: status {rec.status} ({rec.fail_type})")
    for field, want in expect.items():
        got = getattr(rec, field)
        if got != want:
            raise BenchFailure(f"request {c.id}: {field} {got!r}, the "
                               f"configuration states {want!r}")


def check_stats(st) -> None:
    bad = {
        "demoted": st.demoted, "rejected": st.rejected_total(),
        "refused": st.refused, "transient_faults": st.transient_faults,
        "permanent_faults": st.permanent_faults, "timeouts": st.timeouts,
        "retries": st.retries,
    }
    bad = {k: v for k, v in bad.items() if v}
    if bad:
        raise BenchFailure(f"the scheduler demoted, shed or faulted: {bad}")


@dataclasses.dataclass
class Delivery:
    id: int
    due_s: float
    done_s: float
    record: Any
    slot: int  # its place in its dispatch group


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    seconds: float
    setup_s: float
    window_s: float  # the measured window: start to its last delivery
    deliveries: list  # Delivery, every request of the window that came
    due: int  # requests of the window (open loop: due in it)
    missing_latency_s: list  # a wait for each request that never came
    trace: Optional[dict] = None  # trace_reduce.reduce() of a traced run
    flops: int = 0  # per forward (work.py)
    bytes: int = 0
    peaks: Optional[dict] = None

    def latencies(self) -> list[float]:
        return [d.done_s - d.due_s for d in self.deliveries] + self.missing_latency_s


class Reservoir:
    """A uniform sample of ``k`` deliveries, drawn from the seed."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.items[j] = item


class SlotSample:
    """At least ``k`` deliveries drawn from the seed, the same number from
    each place in a dispatch group of up to ``slots`` members: a fault
    confined to one member of every group is in the sample."""

    def __init__(self, k: int, slots: int, rng: np.random.Generator):
        per = -(-k // slots)
        self.by_slot = [Reservoir(per, rng) for _ in range(slots)]

    def offer(self, slot: int, item) -> None:
        self.by_slot[slot].offer(item)

    @property
    def items(self) -> list:
        return [it for r in self.by_slot for it in r.items]


def drive(sched, pool, traffic: dict, seconds: float, expect: dict,
          sample: SlotSample, span: Callable, clock=time.monotonic):
    """The measured window. Returns (t0, end, deliveries, due, missing)."""
    from chipbench import load
    from repro.serving.scheduler import QueueFullError

    priority = traffic["priority"]
    open_loop = traffic["loop"] == "open"
    offsets = load.due_offsets(traffic, seconds) if open_loop else []
    drain_s = float(traffic.get("drain_s", 60.0))
    seen = len(sched.completions)
    meta: dict[int, tuple[float, int]] = {}
    deliveries: list[Delivery] = []
    lateness: list[float] = []
    k = 0

    def submit(due):
        nonlocal k
        with span("bench.submit"):
            try:
                rid = sched.submit(pool[k % len(pool)], priority=priority,
                                   arrival_s=due)
            except QueueFullError as e:
                raise BenchFailure(f"request refused: {e}") from None
        meta[rid] = (due, k % len(pool))
        k += 1
        return rid

    t0 = clock()
    end = t0
    if not open_loop:
        for _ in range(int(traffic["outstanding"])):
            submit(t0)
    pending = [t0 + o for o in offsets]
    nxt = 0
    while True:
        now = clock()
        if open_loop:
            while nxt < len(pending) and pending[nxt] <= now:
                lateness.append(now - pending[nxt])
                submit(pending[nxt])
                nxt += 1
            if nxt == len(pending) and not sched.has_work():
                break
            if now > t0 + seconds + drain_s:
                break
        elif now >= t0 + seconds:
            break
        with span("bench.next_batch"):
            batch = sched.next_batch()
        if batch is None:
            if nxt < len(pending):
                with span("bench.wait_arrival"):
                    time.sleep(max(0.0, pending[nxt] - clock()))
            continue
        with span("bench.run_batch"):
            sched.run_batch(batch)
        fresh = sched.completions[seen:]
        seen = len(sched.completions)
        for slot, c in enumerate(fresh):
            check_completion(c, expect)
            with span("bench.fetch"):
                host = np.asarray(c.result.segmentation)
            done = clock()
            c.result.segmentation = None  # the device copy goes with it
            due, idx = meta[c.id]
            deliveries.append(Delivery(c.id, due, done, c.record, slot))
            sample.offer(slot, (idx, host))
            end = done
            if not open_loop:
                submit(done)
    # every request of the window that never came is missing: in the open
    # loop every request due in it, in the closed loop every one that left
    # the queue (those still queued at the close were never attempted)
    gone = clock()
    served = {d.id for d in deliveries}
    queued = set() if open_loop else {r.id for r in sched.queue}
    lost = [rid for rid in meta if rid not in served and rid not in queued]
    missing = [gone - meta[rid][0] for rid in lost]
    if open_loop:
        missing += [gone - p for p in pending[nxt:]]
        due = len(pending)
    else:
        due = len(deliveries) + len(lost)
    late = sorted(lateness) or [0.0]
    # the longest waits between deliveries, at seconds into the window: a
    # stall shows as one gap far above the rest
    times = [t0] + [d.done_s for d in deliveries]
    gaps = sorted(((b - a, a - t0) for a, b in zip(times, times[1:])), reverse=True)
    print(f"load: loop {traffic['loop']} due {due} submitted {k} delivered "
          f"{len(deliveries)} missing {len(missing)} sampled {len(sample.items)} "
          f"submit lateness p50 {late[len(late) // 2]:.6f}s max {late[-1]:.6f}s "
          "longest delivery gaps " + " ".join(f"{g:.3f}s@{at:.1f}s" for g, at in gaps[:3]),
          flush=True)
    return t0, end, deliveries, due, missing


def reference_check(params, model: dict, shape, raw_pool, sample) -> list[dict]:
    """``reference.logit_gap``'s numbers for each sampled delivery."""
    import jax.numpy as jnp

    from chipbench import reference

    each = []
    for idx, served in sample:
        vol = reference.conform(jnp.asarray(raw_pool[idx]), tuple(shape))
        ref = reference.logits(params, vol, model)
        got = reference.logit_gap(ref, jnp.asarray(served))
        each.append({k: float(v) for k, v in got.items()})
        del ref, vol
    return each


def metric_reader(name: str):
    return importlib.import_module(f"chipbench.metrics.{name}").read


def serve(*args, **kwargs) -> dict:
    """``serve_run``'s result line."""
    return serve_run(*args, **kwargs)[0]


def serve_run(cell: Cell, seed: int, seconds: float, trace: bool, *,
              require_chip: bool = True, precision: Optional[str] = None,
              t_start: Optional[float] = None,
              log=print) -> tuple[dict, Run]:
    """Set up, drive the window, check, and return the result line's
    object and the Run its metrics were read from.

    ``precision`` overrides the configuration's (the control of
    calibrate.py); ``require_chip=False`` skips the look for a TPU (the
    CPU tests). Set-up is timed from ``t_start``, the process's first
    statement. Raises BenchFailure where a run must print no result."""
    t_setup = time.monotonic() if t_start is None else t_start
    import jax

    from chipbench import peaks as peaks_mod
    from chipbench import reference, scans, trace_reduce, work

    marks = [("start", t_setup)]
    devices = jax.devices()
    marks.append(("jax devices", time.monotonic()))
    if require_chip:
        check_tpu(devices, cell.chips)
    dev = devices[0]
    cfg = cell.config
    model = cfg["model"]
    shape = tuple(cfg["volume_shape"])
    expect = dict(cfg["expect"])
    pipe = dict(cfg["pipeline"])
    if precision is not None:
        pipe["precision"] = expect["precision"] = precision
    peaks = None
    if trace:
        peaks = peaks_mod.for_device(dev.device_kind)

    from repro.core.meshnet import PAPER_MODELS, MeshNetConfig
    from repro.core.pipeline import PipelineConfig
    from repro.serving.engine import SegmentationEngine
    from repro.serving.scheduler import SchedulerConfig

    mcfg = MeshNetConfig(
        in_channels=model["in_channels"], channels=model["channels"],
        num_classes=model["num_classes"], dilations=tuple(model["dilations"]),
        kernel_size=model["kernel_size"], use_batchnorm=model["use_batchnorm"])
    zoo = cfg.get("zoo")
    if zoo is not None and PAPER_MODELS[zoo] != mcfg:
        raise BenchFailure(f"configuration {cfg['name']} is not the zoo's {zoo}")

    key, rng = seeds(seed)
    k_params, k_pool = jax.random.split(key)
    params = jax.block_until_ready(reference.init_params(k_params, model))
    marks.append(("weights", time.monotonic()))
    raw_pool = scans.pool(k_pool, int(cell.traffic["pool"]), shape)
    marks.append(("scan pool", time.monotonic()))
    engine = SegmentationEngine(params, PipelineConfig(
        name=cfg["name"], model=mcfg, volume_shape=shape, **pipe))
    sched = engine.scheduler(SchedulerConfig(**cell.traffic.get("scheduler", {})))

    counter = CompileCounter().install()
    # warm-up: one request through the served path compiles (or loads
    # from the cache) every program the window runs
    sched.submit(raw_pool[0], priority=cell.traffic["priority"])
    batch = sched.next_batch()
    if batch is not None:  # None: the scheduler shed it, which the check sees
        sched.run_batch(batch)
    (warm,) = sched.completions
    check_completion(warm, expect)
    np.asarray(warm.result.segmentation)
    warm.result.segmentation = None
    marks.append(("engine and warm-up request", time.monotonic()))

    sample = SlotSample(int(cell.traffic["check_sample"]),
                        sched.cfg.max_batch_requests, rng)
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace else (
        lambda name: nullcontext())
    if trace:
        import tempfile

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the bench.* spans, not the runtime's
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        _wrap_pipeline_run(span)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.monotonic() - t_setup
    marks.append(("trace start" if trace else "window", time.monotonic()))
    log("setup: " + ", ".join(f"{name} {t1 - t0:.3f}s" for (_, t0), (name, t1)
                              in zip(marks, marks[1:])))
    counter.armed = True
    window = jax.profiler.TraceAnnotation("bench.window") if trace else nullcontext()
    with window:
        t0, end, deliveries, due, missing = drive(
            sched, raw_pool, cell.traffic, seconds, expect, sample, span)
    counter.armed = False
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce(trace_reduce.extract(
            trace_reduce.find_xplane(trace_dir)))
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    if counter.events:
        raise BenchFailure(f"{len(counter.events)} compiles inside the window: "
                           f"{counter.events[:5]}")
    check_stats(sched.stats)

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    sched.queue.clear()
    del engine, sched
    t_ref = time.monotonic()
    each = reference_check(params, model, shape, raw_pool, sample.items)
    log(f"check: reference over {len(each)} sampled deliveries took "
        f"{time.monotonic() - t_ref:.3f}s")
    # worst over the sample; a window that delivered nothing has no reading
    checks = {k: max(e[k] for e in each) for k in each[0]} if each else {"mean": None}

    run = Run(cell=cell, seconds=seconds, setup_s=setup_s,
              window_s=max(end - t0, 1e-9), deliveries=deliveries, due=due,
              missing_latency_s=missing, trace=reduced,
              flops=work.forward_flops(model, shape),
              bytes=work.forward_bytes(model, shape, expect["precision"]),
              peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    limits = cfg["limits"]
    compared = {
        "mean_logit_gap": {"value": checks["mean"], "limit": limits["mean_logit_gap"]},
        "missing_requests": {"value": len(missing), "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": due, "failed": len(missing),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = trace_reduce.breakdown(reduced)
    for e in each:
        log("check: volume " + " ".join(f"{k} {v!r}" for k, v in e.items()))
    log("check: worst " + " ".join(f"{k} {v!r}" for k, v in checks.items())
        + f" over {len(each)} sampled deliveries")
    out["checks"] = compared
    return out, run


def _wrap_pipeline_run(span) -> None:
    """Give each ``pipeline.run`` call a host span in the trace. The engine
    looks ``pipeline.run`` up on the module at every request."""
    from repro.core import pipeline

    inner = pipeline.run
    if getattr(inner, "_bench_span", False):
        return

    def run(*args, **kwargs):
        with span("bench.pipeline_run"):
            return inner(*args, **kwargs)

    run._bench_span = True
    pipeline.run = run


def main(argv=None, *, root: pathlib.Path = HERE.parent,
         t_start: Optional[float] = None) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(root, args.workload)
        if not (root / "src" / "repro").is_dir():
            raise BenchFailure(f"the program (src/repro) is not in {root}")
        # JAX's persistent compile cache at a fixed path inside the
        # checkout; the program's enable_compile_cache takes it from here
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
        sys.path.insert(0, str(root / "src"))
        from repro.runtime import enable_compile_cache

        enable_compile_cache()
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        out = serve(cell, args.seed, args.seconds, bool(args.trace),
                    t_start=t_start)
    except BenchFailure as e:
        print(f"chipbench: FAILED: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
