"""A run that resolves another executor or precision, is demoted, shed or
faulted, compiles inside its window, or finds no TPU fails and prints no
result."""

import json
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import ROOT, tiny_cell


def _completion(outcome="completed", status="ok", executor="pallas_megakernel",
                precision="bf16"):
    rec = types.SimpleNamespace(status=status, fail_type=None, executor=executor,
                                precision=precision)
    return types.SimpleNamespace(id=0, outcome=outcome, record=rec)


EXPECT = {"executor": "pallas_megakernel", "precision": "bf16"}


def test_completion_guard():
    harness.check_completion(_completion(), EXPECT)
    for bad in (dict(outcome="demoted"), dict(outcome="rejected"),
                dict(status="fail"), dict(executor="pallas_fused"),
                dict(precision="int8w")):
        with pytest.raises(harness.BenchFailure):
            harness.check_completion(_completion(**bad), EXPECT)


@pytest.mark.parametrize("field,value", [
    ("demoted", 1), ("refused", 1), ("transient_faults", 1),
    ("permanent_faults", 1), ("timeouts", 1), ("retries", 1),
    ("rejected", {"deadline_expired": 1}),
])
def test_scheduler_stats_guard(field, value):
    from repro.serving.scheduler import SchedulerStats

    harness.check_stats(SchedulerStats())
    st = SchedulerStats()
    setattr(st, field, value)
    with pytest.raises(harness.BenchFailure):
        harness.check_stats(st)


def test_compile_counter_sees_a_new_program():
    import jax
    import jax.numpy as jnp

    counter = harness.CompileCounter().install()
    counter.armed = True
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 3))).block_until_ready()
    counter.armed = False
    assert counter.events


def _serve(cell, **kw):
    return harness.serve(cell, 5, 1.5, False, require_chip=False, **kw)


def test_other_executor_fails_the_run():
    cell = tiny_cell("interactive")
    cell.config["expect"] = dict(cell.config["expect"], executor="pallas_fused")
    with pytest.raises(harness.BenchFailure, match="executor"):
        _serve(cell)


def test_other_precision_fails_the_run():
    cell = tiny_cell("interactive")
    cell.config["pipeline"] = dict(cell.config["pipeline"], precision="fp32")
    with pytest.raises(harness.BenchFailure, match="precision"):
        _serve(cell)


def test_shed_request_fails_the_run(monkeypatch):
    from repro.serving import scheduler

    # a deadline no queued request can meet: the scheduler sheds
    monkeypatch.setitem(scheduler.DEFAULT_CLASSES, "batch",
                        scheduler.PriorityClass("batch", 2, deadline_s=1e-6))
    with pytest.raises(harness.BenchFailure, match="shed|rejected"):
        _serve(tiny_cell("cohort"))


def test_compile_inside_the_window_fails_the_run(monkeypatch):
    from chipbench import scans

    real = scans.pool

    def pool(key, n, shape):
        # the second scan has a shape the warm-up never saw: conform
        # compiles a resample for it inside the window
        first = real(key, n, shape)
        return [first[0], np.asarray(scans.generate(key, (15, 16, 17)))]

    monkeypatch.setattr(scans, "pool", pool)
    with pytest.raises(harness.BenchFailure, match="compiles inside the window"):
        _serve(tiny_cell("interactive"))


def _run_py(root, *extra_env):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(root)}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gwm_light.interactive",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_no_tpu_prints_no_result():
    proc = _run_py(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    _no_result(proc)
    assert "not in" in proc.stderr
