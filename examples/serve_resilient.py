"""Resilience walkthrough — typed faults, retries, hedges, and the
degradation ladder under a seeded fault storm (src/repro/serving/
errors.py + resilience.py, DESIGN.md §7).

Two acts, both on the deterministic virtual clock so every number
printed here is reproducible to the byte:

  1. a single scheduler under a transient-fault storm: retryable
     faults re-enter their lane with the ORIGINAL arrival stamp and
     backed-off, jittered retry times — near-total recovery, and the
     telemetry reconstruction (telemetry/analysis.resilience_summary)
     agrees with the scheduler's own counters;
  2. the committed acceptance storm (`fleet_faultstorm`): 4 replicas,
     6% transient rate, one 6x-slow straggler, one permanently
     poisoned signature, 0.4% stuck requests — retries recover the
     transients, class timeouts reap the stuck, hedges beat the
     straggler, and the per-(replica, signature) breaker walks the
     poisoned signature down the executor ladder. Zero requests lost,
     zero served twice (EXPERIMENTS.md H14).

    PYTHONPATH=src python examples/serve_resilient.py
"""

import dataclasses

from repro.serving import (
    FaultPlan,
    FaultRule,
    RetryPolicy,
    ResiliencePolicy,
    fleet_preset,
    preset,
    simulate,
    simulate_fleet,
)
from repro.serving.simulator import reference_engine

# --- act 1: retries recover a transient storm ---------------------------
# The steady single-server scenario, with a 10% transient-fault rate
# injected on every dispatch and a 3-attempt retry budget. Faults and
# backoff jitter are counter-hashed, so this whole run is seeded.
cfg = dataclasses.replace(
    preset("steady", horizon_s=300.0, seed=0),
    resilience=ResiliencePolicy(
        retry=RetryPolicy(max_attempts=3, backoff_base_s=0.1, seed=0),
        service_timeout_s={"interactive": 4.0, "standard": 8.0, "batch": 20.0},
    ),
    fault_plan=FaultPlan(
        seed=0, rules=(FaultRule(kind="transient", rate=0.10),)
    ),
)
rep = simulate(reference_engine(), cfg)
s = rep.summary()
r = s["resilience"]
print("== act 1: single scheduler, 10% transient storm ==")
print(f"served={s['requests']['completed'] + s['requests']['demoted']} "
      f"retries={r['retries']} faults={r['faults']}")
print(f"faulted_requests={r['faulted_requests']} "
      f"recovered={r['recovered_requests']} "
      f"recovery_rate={r['recovery_rate']}")
print(f"conserved={s['requests']['conserved']} — retries age in place: "
      f"a retried request keeps its original arrival stamp, so "
      f"wait + service == finish - arrival exactly")

# --- act 2: the committed acceptance storm ------------------------------
# fleet_faultstorm is the golden scenario: every counter printed below
# is asserted byte-exactly in tests/test_resilience.py.
rep = simulate_fleet(fleet_preset("fleet_faultstorm"))
s = rep.summary()
req, r = s["requests"], s["resilience"]
print("\n== act 2: fleet_faultstorm — 4 replicas, every fault kind ==")
print(f"arrived={req['arrived']} conserved={req['conserved']} "
      f"served_twice={req['served_twice']}")
print(f"retries={r['retries']} recovery_rate={r['recovery_rate']} "
      f"(acceptance: >= 0.9) timeouts={r['faults']['timeout']}")
print(f"hedges={r['hedges']} wins={r['hedge_wins']} "
      f"cancelled={r['hedge_cancelled']} — first completion wins, the "
      f"loser cancels through the ledger")
b = r["breaker"]
print(f"breaker: trips={b['trips']} restores={b['restores']} "
      f"probes={b['probes']} open={b['open_signatures']}")
print("rung mix (mode/executor of every served request):")
for rung, n in sorted(s["resilience"]["rungs"].items()):
    print(f"  {rung:<24} {n}")
print("the poisoned xla signature finishes its requests at the demoted "
      "streaming rung — the ladder routes around the permanent fault.")
