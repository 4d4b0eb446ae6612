"""Tests of the benchmark itself, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests

The repository's own test run collects only ``tests/``."""

import copy
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: a cut of gwm_light the Pallas interpreter serves in well under a second
TINY_SHAPE = [16, 16, 16]
TINY_DILATIONS = [1, 2, 1]
TINY_TRAFFIC = {
    "interactive": {"loop": "open", "arrivals": "poisson", "rate_hz": 3.0,
                    "schedule_seed": 0, "priority": "interactive",
                    "scheduler": {"max_batch_requests": 1}, "pool": 2,
                    "check_sample": 2, "drain_s": 30},
    "cohort": {"loop": "closed", "outstanding": 4, "priority": "batch",
               "pool": 2, "check_sample": 2},
}


def tiny_cell(traffic: str, config: str = "gwm_light"):
    """The named cell of BENCHMARK.json, cut to 16^3 and three layers and
    served by the megakernel at bf16 (the Pallas interpreter on a CPU)."""
    from chipbench import harness

    cell = harness.load_cell(ROOT, f"{config}.{traffic}")
    cfg = copy.deepcopy(cell.config)
    cfg["zoo"] = None
    cfg["volume_shape"] = TINY_SHAPE
    cfg["model"]["dilations"] = TINY_DILATIONS
    cfg["pipeline"] = dict(cfg["pipeline"], executor="pallas_megakernel",
                           precision="bf16")
    cell.config = cfg
    cell.traffic = dict(TINY_TRAFFIC[traffic])
    return cell


@pytest.fixture
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())
