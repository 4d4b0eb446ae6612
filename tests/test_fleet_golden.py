"""Golden-trace regression tests for the replicated serving fleet.

Four committed fleet traces — fleet_steady, fleet_overload,
fleet_failover, fleet_autoscale — asserted EXACTLY against checked-in
JSON summaries (tests/golden/fleet_*.json). The fleet simulator is
bit-deterministic end to end (one virtual clock across N replicas,
seeded arrivals/mix, modeled service + cold-compile), so router,
failover, or autoscaler behavior changes show up here as reviewable
golden diffs, never as flakes. Regenerate a golden (ONLY on intentional
behavior change) from this file's own ``_fresh_summary``:

    PYTHONPATH=src python -c "
    import json, sys; sys.path.insert(0, 'tests')
    from test_fleet_golden import FLEET_SCENARIOS, _fresh_summary
    for n in FLEET_SCENARIOS + ['fleet_cached']:
        s = json.dumps(_fresh_summary(n), indent=1, sort_keys=True)
        open(f'tests/golden/{n}.json', 'w').write(s + '\\n')"

The semantic tests below pin what each golden must *show* —
the acceptance claims of the fleet tier — so a regenerated golden that
silently stopped exercising failover or autoscaling fails review here.
"""

import json
import os

import pytest

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

FLEET_SCENARIOS = ["fleet_steady", "fleet_overload", "fleet_failover", "fleet_autoscale"]


def _golden(name: str) -> dict:
    with open(os.path.join(GOLDEN_DIR, f"{name}.json")) as f:
        return json.load(f)


def _fresh_summary(name: str) -> dict:
    from repro.serving.fleet import fleet_preset, simulate_fleet

    return simulate_fleet(fleet_preset(name, seed=0)).summary()


def _unique_terminal_total(req: dict) -> int:
    """Arrivals accounted for by a unique terminal outcome (the fleet
    ledger view — per-replica admissions double-count re-dispatches)."""
    return (
        req["refused"]
        + req["no_replica"]
        + req["completed"]
        + req["demoted"]
        + sum(req["rejected"].values())
    )


@pytest.mark.parametrize("name", FLEET_SCENARIOS + ["fleet_cached"])
def test_fleet_golden_trace_matches(name):
    golden = _golden(name)
    fresh = _fresh_summary(name)
    # byte-level comparison via canonical dumps — the strongest claim the
    # virtual clock supports, and the one CI's determinism gate relies on
    assert json.dumps(fresh, sort_keys=True) == json.dumps(golden, sort_keys=True), (
        f"fleet scenario {name!r} diverged from its golden trace; "
        f"fresh summary:\n{json.dumps(fresh, indent=1, sort_keys=True)}"
    )


@pytest.mark.parametrize("name", FLEET_SCENARIOS)
def test_fleet_goldens_conserve(name):
    """Fleet conservation on every committed trace: every arrival has
    exactly one terminal outcome, nothing is served twice, and each
    replica's own ledger balances (evacuations included)."""
    golden = _golden(name)
    req = golden["requests"]
    assert req["conserved"] is True
    assert req["served_twice"] == 0
    assert req["arrived"] == _unique_terminal_total(req)
    for rep in golden["per_replica"]:
        assert rep["admitted"] == (
            rep["completed"] + rep["demoted"] + rep["rejected"] + rep["evacuated"]
        ), f"replica {rep['id']} ledger does not balance"


def test_failover_golden_loses_nothing():
    """The failover trace must show a replica crashing MID-BURST with
    work in hand — and every one of those requests re-dispatched exactly
    once and served elsewhere (zero lost)."""
    golden = _golden("fleet_failover")
    req = golden["requests"]
    assert golden["replicas"]["crashed"] == 1
    crash_events = [e for e in golden["scale_events"] if e["action"] == "crash"]
    assert len(crash_events) == 1
    # mid-burst: the preset's second storm covers [120, 135]
    assert 120.0 < crash_events[0]["t"] < 135.0
    # the crash actually evacuated work (queue + truncated in-flight batch)
    assert req["evacuated"] > 0
    assert req["redispatched"] == req["evacuated"]
    # exactly-once: nothing double-served, nothing lost
    assert req["served_twice"] == 0
    assert req["arrived"] == _unique_terminal_total(req)
    dead = [r for r in golden["per_replica"] if r["crashed"]]
    assert len(dead) == 1 and dead[0]["evacuated"] > 0


def test_autoscale_golden_scales_up_then_down():
    """One compressed virtual day: the autoscaler must ADD capacity on
    the morning ramp and DRAIN it after the evening tail — both
    directions in one committed trace."""
    golden = _golden("fleet_autoscale")
    events = golden["scale_events"]
    adds = [e["t"] for e in events if e["action"] == "add"]
    drains = [e["t"] for e in events if e["action"] == "drain"]
    assert adds, "autoscale golden never scaled up"
    assert drains, "autoscale golden never scaled down"
    assert min(adds) < min(drains), "scale-down before any scale-up"
    assert golden["replicas"]["peak_routable"] > golden["replicas"]["initial"]
    assert golden["replicas"]["drained"] == len(drains)
    # never below the floor, never above the ceiling (preset: 1..6)
    assert 1 <= golden["replicas"]["final_routable"] <= 6
    after = [e["replicas_after"] for e in events]
    assert all(1 <= n <= 6 for n in after)


def test_fleet_overload_beats_single_server_golden():
    """THE acceptance claim of the fleet tier: the same diurnal 12 Hz
    overload that drives the committed single-server golden to hundreds
    of queue-full refusals is absorbed by the 4-replica cache-affinity
    fleet with an interactive-class p99 under 5 virtual seconds and
    strictly fewer refusals."""
    fleet = _golden("fleet_overload")
    with open(os.path.join(GOLDEN_DIR, "serving_overload.json")) as f:
        single = json.load(f)
    # same storm on both sides: the comparison is capacity, not traffic
    assert fleet["process"] == single["process"] == "diurnal"
    assert fleet["requests"]["arrived"] == single["requests"]["arrived"]
    assert single["requests"]["refused"] > 0  # the single server does shed
    assert fleet["requests"]["refused"] < single["requests"]["refused"]
    p99 = fleet["classes"]["interactive"]["latency_ms"]["p99"]
    assert p99 < 5_000.0, f"fleet interactive p99 {p99} ms >= 5 virtual seconds"


class TestCachedGolden:
    """Acceptance claims of the artifact-cache trace (fleet_cached:
    4 replicas, Zipf(1.1) content skew over 256 volumes, 2% corrupt-
    entry faults, a 60-virtual-second cache outage at t=240). The
    byte-exact match lives in test_fleet_golden_trace_matches; these
    tests pin what the committed numbers must SHOW, so a regenerated
    golden that silently stopped exercising the cache fails review."""

    def test_conserves_with_coalesced_fifth_state(self):
        golden = _golden("fleet_cached")
        req = golden["requests"]
        assert req["conserved"] is True
        assert req["served_twice"] == 0
        # coalesced is the fifth terminal state of the cached ledger
        assert req["arrived"] == (
            _unique_terminal_total(req) + golden["cache"]["coalesced"]
        )
        for rep in golden["per_replica"]:
            assert rep["admitted"] == (
                rep["completed"] + rep["demoted"] + rep["rejected"]
                + rep["evacuated"] + rep["coalesced"]
            ), f"replica {rep['id']} ledger does not balance"

    def test_stampedes_actually_collapse(self):
        """N identical concurrent requests == 1 execution + N-1 coalesced:
        the burst storms must produce real single-flight collapsing, and
        the router must have steered identical content to its leader."""
        cache = _golden("fleet_cached")["cache"]
        assert cache["coalesced"] > 0, "no stampede collapsing in the golden"
        assert cache["inflight_hits"] == cache["coalesced"]
        assert cache["content_routes"] > 0, "router never steered to a leader"
        # every served-from-cache answer is an admission hit or a follower
        assert cache["served_from_cache"] == (
            cache["admission_hits"] + cache["coalesced"]
        )

    def test_corruption_is_quarantined_never_served(self):
        """THE integrity claim: the 2% corrupt-entry storm really poisoned
        entries, verification caught every one, and not a single corrupt
        byte reached a completion."""
        cache = _golden("fleet_cached")["cache"]
        assert cache["quarantined"] > 0, "the corruption storm never landed"
        assert cache["quarantined_served"] == 0, "CORRUPT BYTES WERE SERVED"

    def test_outage_fails_open_through_the_breaker(self):
        """The 60 s outage must show the full degradation ladder: consults
        lost, the breaker tripping, open-state skips — and zero lost
        requests (the conservation test above covers the same trace)."""
        cache = _golden("fleet_cached")["cache"]
        assert cache["unavailable"] > 0
        assert cache["breaker_trips"] >= 1
        assert cache["breaker_skips"] > 0, "open breaker never skipped a consult"

    def test_skew_makes_the_cache_earn_its_bytes(self):
        """Zipf(1.1) traffic must produce a real hit rate AND real byte
        pressure: the 2 MiB tier holds ~hundreds of artifacts of a
        256-volume universe, so LRU eviction must actually run."""
        golden = _golden("fleet_cached")
        cache = golden["cache"]
        assert cache["hit_rate"] > 0.3
        assert cache["evictions"] > 0, "capacity never pressured LRU"
        assert cache["bytes_stored"] <= 2 * 1024 * 1024
        # cache-served answers dominate device time saved: more than a
        # third of arrivals never touched (or re-touched) a device
        assert cache["served_from_cache"] > golden["requests"]["arrived"] / 3


def test_steady_golden_affinity_is_warm():
    """Under steady load the cache-affinity router must keep the hit
    rate high and compile each signature roughly once fleet-wide —
    that is the point of affinity over plain load balancing."""
    golden = _golden("fleet_steady")
    aff = golden["affinity"]
    assert aff["policy"] == "cache_affinity"
    assert aff["hit_rate"] > 0.8
    # signatures compile ~once each, not once per (replica, signature):
    # the standard mix resolves 5 signatures across 3 replicas
    assert aff["cold_compiles"] < 3 * 5
    assert golden["requests"]["refused"] == 0
    assert golden["requests"]["rejected"] == {}
