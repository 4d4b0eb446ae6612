"""Request-scoped spans (telemetry/spans.py) on the served path: what one
``pipeline.run`` and one scheduler group record, collector pauses, the
profiler's trace, and the spans of a failed request. CPU, xla executor,
32^3."""

import gc
import glob

import jax
import numpy as np
import pytest

from repro.core import meshnet, pipeline
from repro.core.meshnet import MeshNetConfig
from repro.core.pipeline import PipelineConfig
from repro.serving.engine import SegmentationEngine
from repro.serving.scheduler import SchedulerConfig
from repro.telemetry import spans

SMALL = MeshNetConfig(dilations=(1, 2, 4), channels=5)
SHAPE = (32, 32, 32)

#: the spans of one served full-volume request with postprocessing on
STAGES = (
    "pipeline.run", "pipeline.plan",
    "pipeline.preprocess", "pipeline.preprocess.wait",
    "conform.upload", "conform.range", "conform.rescale",
    "pipeline.inference", "pipeline.inference.wait",
    "pipeline.argmax",
    "pipeline.postprocess", "pipeline.postprocess.wait",
)
#: parent -> children whose host time lies inside it
NESTED = {
    "pipeline.run": ("pipeline.plan", "pipeline.preprocess",
                     "pipeline.inference", "pipeline.argmax",
                     "pipeline.postprocess"),
    "pipeline.preprocess": ("pipeline.preprocess.wait", "conform.upload",
                            "conform.range", "conform.rescale"),
    "pipeline.inference": ("pipeline.inference.wait",),
    "pipeline.postprocess": ("pipeline.postprocess.wait",),
}


@pytest.fixture(scope="module")
def params():
    return meshnet.init(jax.random.PRNGKey(0), SMALL)


def _cfg(**kw):
    return PipelineConfig(model=SMALL, volume_shape=SHAPE, executor="xla",
                          min_component_size=4, **kw)


def _vol(seed=0, shape=(28, 32, 30)):
    return np.random.default_rng(seed).random(shape, dtype=np.float32)


def _check_nesting(rec):
    for parent, children in NESTED.items():
        for child in children:
            assert rec.spans[child] <= rec.spans[parent], (child, parent)
        assert sum(rec.spans[c] for c in children) <= rec.spans[parent]


def test_pipeline_run_fills_documented_spans(params):
    res = pipeline.run(_cfg(), params, _vol())
    rec = res.record
    assert rec.status == "ok"
    assert set(rec.spans) >= set(STAGES)
    assert all(rec.spans[name] > 0.0 for name in STAGES)
    _check_nesting(rec)
    # StageTimes keeps the paper's columns, read from the same spans
    assert rec.times.preprocessing == rec.spans["pipeline.preprocess"]
    assert rec.times.inference == rec.spans["pipeline.inference"]
    assert rec.times.postprocessing == rec.spans["pipeline.postprocess"]
    assert not spans.in_request()  # run closed the scope it opened


def test_scheduler_group_gives_each_member_its_spans(params):
    engine = SegmentationEngine(params, _cfg())
    sched = engine.scheduler(SchedulerConfig(max_batch_requests=3))
    ids = [sched.submit(_vol(seed)) for seed in range(3)]
    batch = sched.next_batch()
    assert len(batch.requests) == 3
    sched.run_batch(batch)
    assert [c.id for c in sched.completions] == ids
    records = [c.record for c in sched.completions]
    assert len({id(r.spans) for r in records}) == 3
    for c, rec in zip(sched.completions, records):
        assert rec.status == "ok" and rec.request_id == c.id
        assert rec.batch_size == 3
        assert set(rec.spans) >= set(STAGES) | {"sched.member"}
        assert rec.spans["sched.member"] >= rec.spans["pipeline.run"]
        _check_nesting(rec)


def test_gc_pause_lands_in_the_open_request():
    spans.install_gc_hook()
    spans.install_gc_hook()  # once per process, however often it is asked
    assert gc.callbacks.count(spans._GC_HOOK) == 1
    with spans.request(5) as recorded:
        gc.collect()
    assert recorded["gc"] > 0.0
    gc.collect()  # with no request open, the pause is only annotated
    assert not spans.in_request()


def test_trace_carries_request_id(params, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with spans.request(4242):
            res = pipeline.run(_cfg(postprocess=False), params, _vol())
    assert res.record.status == "ok"
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        e
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name == "repro.pipeline.run"
    ]
    assert len(events) == 1
    assert dict(events[0].stats)["request_id"] == 4242
    assert events[0].duration_ns > 0


def test_degenerate_volume_keeps_closed_spans(params):
    res = pipeline.run(_cfg(), params, np.zeros(SHAPE, np.float32))
    rec = res.record
    assert (rec.status, rec.fail_type) == ("fail", "degenerate_volume")
    # a collection may fall inside the request ("gc")
    assert set(rec.spans) - {"gc"} == {
        "pipeline.run", "pipeline.plan", "pipeline.preprocess",
        "conform.upload", "conform.range",
    }
    assert rec.times.preprocessing == rec.spans["pipeline.preprocess"]
    assert (rec.spans["conform.upload"] + rec.spans["conform.range"]
            <= rec.spans["pipeline.preprocess"] <= rec.spans["pipeline.run"])


def test_raising_member_keeps_its_spans(params):
    engine = SegmentationEngine(params, _cfg())
    sched = engine.scheduler(SchedulerConfig(max_batch_requests=3))
    rid = sched.submit(np.zeros((3,), np.float32))  # garbage: resample raises
    sched.run_batch(sched.next_batch())
    (c,) = sched.completions
    assert c.id == rid and c.record.status == "fail"
    assert {"sched.member", "pipeline.run", "conform.upload"} <= set(c.record.spans)
    assert c.record.spans["sched.member"] >= c.record.spans["pipeline.run"]


def test_span_without_request_only_annotates():
    assert not spans.in_request()
    with spans.span("test.outside") as s:
        pass
    assert s.seconds >= 0.0
    assert spans.recorded() == {} and spans.recorded() is not spans.recorded()


def test_request_scopes_nest():
    with spans.request(1) as outer:
        with spans.span("a"):
            with spans.request(2) as inner:
                with spans.span("b"):
                    pass
        with spans.span("a"):
            pass
    assert set(outer) == {"a"} and set(inner) == {"b"}
    assert outer["a"] >= inner["b"]


def test_modeled_scheduler_records_carry_no_spans(params):
    from repro.serving.simulator import ServiceModel, VirtualClock
    from repro.serving.scheduler import RequestScheduler

    engine = SegmentationEngine(params, _cfg())
    sched = RequestScheduler(engine, SchedulerConfig(), clock=VirtualClock(),
                             service_model=ServiceModel(), execute=False)
    for seed in range(2):
        sched.submit(_vol(seed))
    sched.drain()
    assert sched.completions and all(c.record.spans == {} for c in sched.completions)
