"""The reduction from trace to metrics, checked on a hand-made trace and on
a trace recorded on one TPU v5e chip (the first 1.5 s of a traced
gwm_light.cohort window, trimmed to what the reduction reads)."""

import json
import pathlib

import numpy as np
import pytest

from chipbench import trace_reduce

RECORDED = pathlib.Path(__file__).parent / "data" / "trace_gwm_light_cohort.json"
MS = 1_000_000


def test_hand_made_trace():
    trace = {
        "device": {"/device:TPU:0": [
            ["a", 0 * MS, 10 * MS],       # starts before the window
            ["b", 15 * MS, 10 * MS],
            ["a", 20 * MS, 10 * MS],      # overlaps b
            ["c", 95 * MS, 10 * MS],      # ends after the window
        ]},
        "host": [
            ["bench.window", 5 * MS, 95 * MS],
            ["bench.run_batch", 5 * MS, 60 * MS],
            ["bench.fetch", 30 * MS, 10 * MS],  # inside run_batch
            ["bench.next_batch", 65 * MS, 40 * MS],
        ],
    }
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.095)
    # busy: 5-10, 15-30, 95-100 -> 25 ms
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["op_s"] == pytest.approx({"a": 0.015, "b": 0.010, "c": 0.005})
    # gaps 10-15 and 30-95; in the second, run_batch overlaps 35 ms,
    # next_batch 30 and fetch 10: each gap goes to the span that overlaps
    # it most
    assert r["idle_gaps"] == [("bench.run_batch", pytest.approx(0.070))]


def test_innermost_span_wins_a_tie():
    trace = {"device": {"/device:TPU:0": [["a", 0, 10 * MS], ["a", 20 * MS, 10 * MS]]},
             "host": [["bench.window", 0, 30 * MS], ["bench.run_batch", 0, 30 * MS],
                      ["bench.fetch", 10 * MS, 10 * MS]]}
    assert trace_reduce.reduce(trace)["idle_gaps"] == [("bench.fetch", pytest.approx(0.01))]


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_against_a_grid():
    trace = json.loads(RECORDED.read_text())
    r = trace_reduce.reduce(trace)
    (w0, w1), = [(s, s + d) for n, s, d in trace["host"] if n == "bench.window"]
    (ops,) = trace["device"].values()
    assert ops, "the recorded trace holds device operations"
    # busy time on a 1 us grid, independently of the interval union
    us = lambda t: int((t - w0) // 1000)  # noqa: E731
    grid = np.zeros(us(w1) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            grid[us(a):us(b)] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=len(ops) * 2e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])
    b = trace_reduce.breakdown(r)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
