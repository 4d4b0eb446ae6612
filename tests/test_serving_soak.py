"""Virtual-hour soaks of the serving scheduler: the overload scenario
stretched to 3600 virtual seconds at seed 0, in four variants. The plain
scheduler, ``batched_dispatch``, a transient fault storm under the full
resilience policy, and Zipf-skewed content ids behind the artifact
cache. Each must hold the hard serving invariants over the whole hour:

  * conservation — every arrival ends refused or admitted, and every
    admission in one terminal state;
  * typed shedding under overload (refusals, demotions or rejections);
  * a priority-protected interactive tail (p99 queue wait <= 5 s);
  * with the cache: zero corrupt artifacts served, and hits under skew;
  * with faults: >= 90% of faulted requests recover.

The simulator is deterministic, so these are invariants of one fixed
trace, not statistics. The shorter presets' summaries are pinned byte
for byte in test_serving_golden.py.
"""

import dataclasses

import pytest

from repro.serving import simulator as sim
from repro.serving.cache import CacheConfig
from repro.serving.resilience import (
    BreakerConfig,
    FaultPlan,
    FaultRule,
    ResiliencePolicy,
    RetryPolicy,
)

HORIZON_S = 3600.0
SEED = 0
FAULT_RATE = 0.08
CONTENT_SKEW = 1.1


def _config(variant: str) -> sim.SimConfig:
    name = "overload_batched" if variant == "batched" else "overload"
    cfg = sim.preset(name, seed=SEED, horizon_s=HORIZON_S)
    if variant == "faults":
        cfg = dataclasses.replace(
            cfg,
            resilience=ResiliencePolicy(
                retry=RetryPolicy(max_attempts=3, backoff_base_s=0.1, seed=SEED),
                service_timeout_s={"interactive": 4.0, "standard": 8.0,
                                   "batch": 20.0},
                breaker=BreakerConfig(trip_after=3, cooldown_s=120.0),
            ),
            fault_plan=FaultPlan(
                seed=SEED, rules=(FaultRule(kind="transient", rate=FAULT_RATE),)
            ),
        )
    if variant == "zipf":
        cfg = dataclasses.replace(
            cfg,
            cache=CacheConfig(capacity_bytes=4 * 1024 * 1024),
            content_skew=CONTENT_SKEW,
            content_universe=128,
        )
    return cfg


@pytest.mark.parametrize("variant", ["plain", "batched", "faults", "zipf"])
def test_virtual_hour_soak_holds_the_serving_invariants(variant):
    s = sim.simulate(sim.reference_engine(), _config(variant)).summary()
    req = s["requests"]
    assert s["horizon_s"] == HORIZON_S
    assert req["conserved"]
    assert req["arrived"] == req["refused"] + req["admitted"]
    shed = req["refused"] + req["demoted"] + sum(req["rejected"].values())
    assert shed > 0, "overload produced no shedding"
    assert s["classes"]["interactive"]["queue_wait_ms"]["p99"] <= 5_000.0

    cache = s.get("cache")
    if variant == "zipf":
        assert cache is not None, "content skew ran without a cache block"
        assert cache["quarantined_served"] == 0
        assert cache["hit_rate"] > 0.0
    else:
        assert cache is None

    res = s.get("resilience")
    if variant == "faults":
        assert res is not None, "fault storm ran without a resilience block"
        if res["faulted_requests"] > 0:
            assert res["recovery_rate"] >= 0.9
    else:
        assert res is None
