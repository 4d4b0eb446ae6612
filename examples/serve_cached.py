"""Artifact-cache walkthrough — content addressing, integrity
quarantine, single-flight coalescing, and fail-open degradation
(src/repro/serving/cache.py, DESIGN.md §8).

Two acts, both deterministic to the byte:

  1. the cache tier in isolation: content addressing (identical voxel
     payloads collide, a one-voxel edit does not), a verified hit, an
     injected bit-flip caught by per-hit re-verification and
     quarantined — the corrupt bytes are NEVER served;
  2. the committed acceptance storm (`fleet_cached`): 4 replicas,
     bursty traffic with Zipf(1.1) content skew over 256 volumes, a
     2 MiB cache, 2% corrupt-entry faults, and a 60 s total outage —
     stampedes collapse onto single-flight leaders, corruption is
     quarantined with zero served, and the outage rides the fail-open
     breaker. Zero requests lost (EXPERIMENTS.md H15).

    PYTHONPATH=src python examples/serve_cached.py
"""

import numpy as np

from repro.serving import (
    ArtifactCache,
    CacheConfig,
    FaultPlan,
    FaultRule,
    artifact_key,
    content_hash,
    fleet_preset,
    simulate_fleet,
)
from repro.telemetry.record import StageTimes, TelemetryRecord

# --- act 1: content addressing + integrity ------------------------------
# Two separate uploads of the SAME voxel payload hash to the same
# artifact key — that collision is the whole point of content
# addressing. A one-voxel edit changes the key.
vol_a = np.random.default_rng(0).normal(size=(16, 16, 16)).astype(np.float32)
vol_b = vol_a.copy()
vol_c = vol_a.copy()
vol_c[3, 4, 5] += 1.0

key = artifact_key(content_hash(vol_a), "gwm_light", "fp32", "full")
print("== act 1: content addressing + integrity ==")
print(f"same payload, same key:    {key == artifact_key(content_hash(vol_b), 'gwm_light', 'fp32', 'full')}")
print(f"one voxel edited, new key: {key != artifact_key(content_hash(vol_c), 'gwm_light', 'fp32', 'full')}")

# Store one artifact, then let a seeded corrupt_entry fault flip a byte
# at rest (t1=0.5 gates the rule to store time only). The next lookup
# re-verifies, catches the flip, quarantines, and reports a plain miss
# — the request recomputes; no caller ever sees corrupt bytes.
cache = ArtifactCache(
    CacheConfig(),
    fault_plan=FaultPlan(
        seed=0, rules=(FaultRule(kind="corrupt_entry", rate=1.0, t1=0.5),)
    ),
)
rec = TelemetryRecord(
    model="gwm_light", mode="full", status="ok", times=StageTimes(),
    executor="xla", precision="fp32", params_bytes=22392, request_id=0,
)
cache.begin(key, replica=0, now=0.0, est_bytes=5000)
cache.complete(key, record=rec, shape=(16, 16, 16), now=0.0)  # poisoned at rest
poisoned = cache.lookup(key, now=1.0)
print(f"lookup after bit-flip:     status={poisoned.status!r} "
      f"(quarantined={cache.stats.quarantined}, "
      f"quarantined_served={cache.stats.quarantined_served})")

# Stored clean (the fault window is over), the hit verifies and serves.
cache.begin(key, replica=0, now=2.0, est_bytes=5000)
cache.complete(key, record=rec, shape=(16, 16, 16), now=2.0)
hit = cache.lookup(key, now=3.0)
print(f"clean store, next lookup:  status={hit.status!r} "
      f"payload_executor={cache.serve_payload(hit.entry)['executor']!r}")

# --- act 2: the committed acceptance storm ------------------------------
# fleet_cached is the golden scenario: every counter printed below is
# asserted byte-exactly in tests/test_fleet_golden.py.
rep = simulate_fleet(fleet_preset("fleet_cached"))
s = rep.summary()
req, c = s["requests"], s["cache"]
print("\n== act 2: fleet_cached — Zipf skew, corruption, and an outage ==")
print(f"arrived={req['arrived']} conserved={req['conserved']} "
      f"served_twice={req['served_twice']} — coalesced is the fifth "
      f"terminal state of the ledger")
print(f"hit_rate={c['hit_rate']} admission_hits={c['admission_hits']} "
      f"evictions={c['evictions']} (2 MiB under real byte pressure)")
print(f"coalesced={c['coalesced']} inflight_hits={c['inflight_hits']} "
      f"content_routes={c['content_routes']} — N identical in-flight "
      f"requests == 1 forward pass + N-1 byte-identical followers")
print(f"quarantined={c['quarantined']} quarantined_served="
      f"{c['quarantined_served']} — every bit-flip caught, corrupt "
      f"bytes NEVER reach a completion")
print(f"outage: unavailable={c['unavailable']} "
      f"breaker_trips={c['breaker_trips']} "
      f"breaker_skips={c['breaker_skips']} — the open breaker stops "
      f"consulting the dark tier; every outage-window request serves "
      f"via compute (fail-open, nothing lost)")
