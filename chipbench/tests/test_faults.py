"""The harness's look for a chip skipped, a whole run driven at a tiny
size on the CPU, with the served path broken underneath: ``correct`` has
to come out false for each fault a serving cell can have. A sound run is
the control that the faults are what the check sees."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import tiny_cell


def _serve(cell, seed=2**31 + 11):
    return harness.serve(cell, seed, 1.5, False, require_chip=False)


@pytest.mark.parametrize("traffic", ["interactive", "cohort"])
def test_sound_run_is_correct(traffic):
    out = _serve(tiny_cell(traffic))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("traffic", ["interactive", "cohort"])
def test_altered_answer_is_not_correct(traffic, monkeypatch):
    from repro.core import executors

    real = executors.jitted_apply

    def jitted_apply(*args, **kwargs):
        fn = real(*args, **kwargs)

        def altered(params, x, cfg):
            logits = fn(params, x, cfg)
            # the answer changed where it is produced: one plane's classes
            # rotated
            return logits.at[:, 0].set(jnp.roll(logits[:, 0], 1, axis=-1))

        return altered

    monkeypatch.setattr(executors, "jitted_apply", jitted_apply)
    out = _serve(tiny_cell(traffic))
    assert not out["correct"]
    c = out["checks"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.serving.scheduler import RequestScheduler

    real = RequestScheduler.run_batch

    def run_batch(self, batch, now=None):
        batch.requests = batch.requests[:math.ceil(len(batch.requests) / 2)]
        return real(self, batch, now)

    monkeypatch.setattr(RequestScheduler, "run_batch", run_batch)
    out = _serve(tiny_cell("cohort"))
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["checks"]["missing_requests"]["value"] > 0


def test_one_slot_altered_is_not_correct(monkeypatch):
    """Only the second member of every dispatch group gets a wrong answer:
    the sample holds one delivery from each slot, so the check sees it."""
    from repro.serving.scheduler import RequestScheduler

    real = RequestScheduler.run_batch

    def run_batch(self, batch, now=None):
        n = len(batch.requests)
        got = real(self, batch, now)
        if n > 1:
            res = self.completions[-n + 1].result
            # on the host, so the fault compiles nothing inside the window
            res.segmentation = (np.asarray(res.segmentation) + 1) % 3
        return got

    monkeypatch.setattr(RequestScheduler, "run_batch", run_batch)
    out = _serve(tiny_cell("cohort"))
    assert not out["correct"]
    assert out["failed"] == 0
    c = out["checks"]["mean_logit_gap"]
    assert c["value"] > c["limit"]

