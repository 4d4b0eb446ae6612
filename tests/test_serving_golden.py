"""Golden-scenario regression tests for the serving scheduler.

Three committed simulator traces — steady, burst, overload — asserted
EXACTLY against checked-in JSON summaries (tests/golden/serving_*.json).
The simulator is bit-deterministic (virtual clock, seeded arrivals,
modeled service), so any scheduler-behavior change shows up here as a
reviewable golden diff instead of a silent drift. Regenerate a golden
(ONLY on intentional behavior change) from this file's own
``_fresh_summary``:

    PYTHONPATH=src python -c "
    import json, sys; sys.path.insert(0, 'tests')
    from test_serving_golden import _fresh_summary
    for n in ('steady', 'burst', 'overload'):
        s = json.dumps(_fresh_summary(n), indent=1, sort_keys=True)
        open(f'tests/golden/serving_{n}.json', 'w').write(s + '\\n')"

(or just update the failing file with the printed fresh summary).
"""

import json
import os

import pytest

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


def _fresh_summary(name: str) -> dict:
    from repro.serving import simulator as sim

    rep = sim.simulate(sim.reference_engine(), sim.preset(name, seed=0))
    return rep.summary()


@pytest.mark.parametrize("name", ["steady", "burst", "overload"])
def test_golden_trace_matches(name):
    path = os.path.join(GOLDEN_DIR, f"serving_{name}.json")
    with open(path) as f:
        golden = json.load(f)
    fresh = _fresh_summary(name)
    # byte-level comparison via canonical dumps — the strongest claim the
    # virtual clock supports, and the one CI's determinism gate relies on
    assert json.dumps(fresh, sort_keys=True) == json.dumps(golden, sort_keys=True), (
        f"serving scenario {name!r} diverged from its golden trace; "
        f"fresh summary:\n{json.dumps(fresh, indent=1, sort_keys=True)}"
    )


def test_overload_golden_actually_sheds():
    """The committed overload trace must keep exercising every shed lane
    (otherwise the scenario silently stopped testing backpressure)."""
    with open(os.path.join(GOLDEN_DIR, "serving_overload.json")) as f:
        golden = json.load(f)
    req = golden["requests"]
    assert req["conserved"] is True
    assert req["refused"] > 0, "no queue-full backpressure in the overload golden"
    assert req["demoted"] > 0, "no shed-to-subvolume demotion in the overload golden"
    assert sum(req["rejected"].values()) > 0, "no typed rejection in the overload golden"
    # zero lost requests: everything arrived is accounted for
    assert req["arrived"] == req["refused"] + req["admitted"]
    assert req["admitted"] == (
        req["completed"] + req["demoted"] + sum(req["rejected"].values())
    )


def test_steady_golden_is_calm():
    """Steady-state must stay the latency floor: nothing shed, shallow
    queue — so a scheduler change that introduces gratuitous queuing is a
    visible golden diff, not an 'expected' one."""
    with open(os.path.join(GOLDEN_DIR, "serving_steady.json")) as f:
        golden = json.load(f)
    req = golden["requests"]
    assert req["refused"] == 0 and req["demoted"] == 0
    assert golden["requests"]["rejected"] == {}
    assert golden["max_queue_depth"] <= 4
