#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep of offered rates in one
process:

    python3 chipbench/sweep.py --workload gwm_light.interactive \
        --rates 4,4.5,5,5.5 --schedule-seeds 1,2,3 --seconds 51

For each rate and each arrival schedule (the traffic file's
``schedule_seed`` replaced by each of ``--schedule-seeds``) it serves the
cell's traffic at that rate and prints the latency median and 95th
percentile, the median latency of the window's first and last quarter of
requests (a backlog that grows all through the window shows as a last
quarter far above the first), and the unloaded service time; then, per
rate, the medians of those over the schedules. The knee is the highest
rate whose median 95th percentile, and median last quarter, meet the
latency limit; the cell's traffic file then fixes its rate at four
fifths of it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--schedule-seeds", default="0")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=31337)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness
    from chipbench.load import nearest_rank

    cell = harness.load_cell(ROOT, args.workload)
    base = dict(cell.traffic, check_sample=1)
    keys = ("latency_p50_s", "latency_p95_s", "first_quarter_p50_s",
            "last_quarter_p50_s", "service_p50_s")
    for rate in [float(r) for r in args.rates.split(",")]:
        rows = []
        for sched_seed in [int(s) for s in args.schedule_seeds.split(",")]:
            cell.traffic = dict(base, rate_hz=rate, schedule_seed=sched_seed)
            out, run = harness.serve_run(cell, args.seed, args.seconds, False,
                                         log=lambda *_: None)
            by_due = sorted(run.deliveries, key=lambda d: d.due_s)
            q = max(1, len(by_due) // 4)
            lat = lambda ds: nearest_rank([d.done_s - d.due_s for d in ds], 50)  # noqa: E731
            row = {
                "rate_hz": rate, "schedule_seed": sched_seed, "due": run.due,
                "delivered": len(run.deliveries),
                "latency_p50_s": nearest_rank(run.latencies(), 50),
                "latency_p95_s": nearest_rank(run.latencies(), 95),
                "first_quarter_p50_s": lat(by_due[:q]),
                "last_quarter_p50_s": lat(by_due[-q:]),
                "service_p50_s": nearest_rank([d.record.service_s for d in run.deliveries], 50),
                "batch_size_max": max(d.record.batch_size for d in run.deliveries),
                "correct": out["correct"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(json.dumps({"rate_hz": rate, "schedules": len(rows), "median": {
            k: statistics.median(r[k] for r in rows) for k in keys}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
