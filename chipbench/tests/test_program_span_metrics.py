"""The readers of the program's own spans (``TelemetryRecord.spans``),
on hand-built runs: no reading without deliveries or without the spans,
the mean over the deliveries that carry them otherwise."""

from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench.metrics import conform_sync_ms, sched_overhead_ms

#: reader, the spans it needs, one delivery's spans, the reading (ms)
CASES = [
    (conform_sync_ms.read, ("conform.upload", "conform.range"),
     {"conform.upload": 0.010, "conform.range": 0.002, "pipeline.run": 0.2},
     12.0),
    (sched_overhead_ms.read, ("sched.member", "pipeline.run"),
     {"sched.member": 0.2015, "pipeline.run": 0.2, "conform.upload": 0.01},
     1.5),
]


def _run(records):
    deliveries = [harness.Delivery(i, 0.0, 1.0, rec, 0) for i, rec in enumerate(records)]
    return harness.Run(cell=None, seconds=1.0, setup_s=0.0, window_s=1.0,
                       deliveries=deliveries, due=len(deliveries),
                       missing_latency_s=[])


@pytest.mark.parametrize("read,needs,spans,want", CASES)
def test_no_reading_without_spans(read, needs, spans, want):
    assert read(_run([])) is None
    # records of a program without spans: no such field, or an empty one
    assert read(_run([SimpleNamespace(), SimpleNamespace()])) is None
    assert read(_run([SimpleNamespace(spans={})])) is None
    for name in needs:
        partial = {k: v for k, v in spans.items() if k != name}
        assert read(_run([SimpleNamespace(spans=partial)])) is None, name


@pytest.mark.parametrize("read,needs,spans,want", CASES)
def test_mean_over_deliveries_with_spans(read, needs, spans, want):
    assert read(_run([SimpleNamespace(spans=spans)])) == pytest.approx(want)
    doubled = {k: 2 * v for k, v in spans.items()}
    records = [SimpleNamespace(spans=spans), SimpleNamespace(spans=doubled),
               SimpleNamespace()]
    assert read(_run(records)) == pytest.approx(1.5 * want)
