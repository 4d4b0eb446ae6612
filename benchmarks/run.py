# One function per paper table. Print ``name,us_per_call,hbm_bytes_modeled,derived`` CSV.
"""Benchmark harness entrypoint: PYTHONPATH=src python -m benchmarks.run

Sections:
  [kernels]       Pallas vs oracle micro-benchmarks (us_per_call)
  [executors]     registry head-to-head: xla vs pallas_fused vs
                  pallas_megakernel end-to-end MeshNet forward per paper
                  model (core/executors.py), plus megakernel spot rows at
                  each reduced precision policy ("@bf16"/"@int8w" keys)
  [traffic]       modeled HBM bytes per forward at the paper's 256^3
                  volume for every registered executor (EXPERIMENTS.md
                  §Perf H9: megakernel >= 1.3x under pallas_fused) and
                  every precision policy (H11: int8w <= 0.4x, bf16 <=
                  0.55x of fp32 on the megakernel; fp32 keys stay
                  un-suffixed so the gate diffs like-for-like)
  [serving]       virtual-clock p50/p99 latencies of the three committed
                  load scenarios (steady / burst / overload) on the
                  deterministic serving simulator (bench_serving.py) —
                  bit-reproducible, gated absolutely (no machine norm)
  [serving_fleet] virtual-clock p50/p99 of the six committed fleet
                  scenarios (replicated schedulers + cache-affinity
                  router, serving/fleet.py), plus the overload acceptance
                  keys (interactive p99, queue-full refusals) — gated
                  absolutely like [serving]
  [serving_resilience] lower-is-better virtual keys of the fault-storm
                  acceptance scenario (serving/resilience.py): unrecovered
                  faults, timeout reaps, lost/double-served (must stay 0),
                  and the storm's p99 — gated absolutely like [serving]
  [batched]       the N-volume batch axis: modeled bytes per forward at
                  batch 1/2/4 per backend (weight stream amortized — b4
                  strictly under 4x b1), plus virtual-clock p50/p99 of
                  every committed load scenario re-run with batched
                  dispatch on the same seed/trace — gated absolutely
                  like [serving] (bench_serving.bench_batched)
  [serving_cache] lower-is-better virtual keys of the artifact-cache
                  acceptance scenario (serving/cache.py): miss rate under
                  Zipf skew, quarantined-served (must stay 0), uncollapsed
                  stampedes, lost requests, and the cached storm's p99 —
                  gated absolutely like [serving]
  [table2]        MeshNet vs U-Net: size + Dice on the synthetic GWM task
  [table4]        per-model pipeline stage timings
  [interventions] fleet-simulation tables V-VIII (patching/cropping/texture)
  [roofline]      the three-term roofline per (arch x shape), if dry-run
                  results exist (results/dryrun_16x16.json)

Pass section names to run a subset: python -m benchmarks.run table2 roofline
Pass ``--json`` to also write the machine-readable perf trajectory
``BENCH_2.json`` at the repo root: per measured section, a list of
``{name, us_per_call, hbm_bytes_modeled}`` rows. ``--json-out PATH``
writes the trajectory somewhere else — CI's bench-smoke job writes a
fresh file next to the committed baseline and gates the diff with
``benchmarks/check_regression.py`` (>25% us_per_call or any hbm_bytes
growth per key fails the build).
"""

from __future__ import annotations

import json
import os
import sys

#: repo-root path of the machine-readable perf trajectory.
JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_2.json")

#: sections emitting (name, us_per_call, hbm_bytes_modeled, note) rows.
MEASURED_SECTIONS = (
    "kernels",
    "executors",
    "traffic",
    "serving",
    "batched",
    "serving_fleet",
    "serving_resilience",
    "serving_cache",
)


def _csv(name: str, us: float, hbm, derived: str = "") -> None:
    hb = "" if hbm is None else str(int(hbm))
    print(f"{name},{us:.1f},{hb},{derived}")


def _rows_to_json(rows):
    return [
        {
            "name": name,
            "us_per_call": round(us, 1),
            "hbm_bytes_modeled": None if hbm is None else int(hbm),
        }
        for name, us, hbm, _ in rows
    ]


def run_kernels() -> list:
    from benchmarks import bench_kernels

    rows = bench_kernels.bench()
    print("\n[kernels] name,us_per_call,hbm_bytes_modeled,derived")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_executors() -> list:
    from benchmarks import bench_kernels

    rows = bench_kernels.bench_executors()
    print("\n[executors] name,us_per_call,hbm_bytes_modeled,derived")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_traffic() -> list:
    from benchmarks import bench_kernels

    rows = bench_kernels.bench_traffic()
    print("\n[traffic] name,us_per_call,hbm_bytes_modeled,derived")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_serving() -> list:
    from benchmarks import bench_serving

    rows = bench_serving.bench()
    print("\n[serving] name,us_per_call,hbm_bytes_modeled,derived")
    print("# virtual-clock latencies (deterministic discrete-event simulator,")
    print("# seed 0) — gated ABSOLUTELY by check_regression.py, no machine norm")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_batched() -> list:
    from benchmarks import bench_serving

    rows = bench_serving.bench_batched()
    print("\n[batched] name,us_per_call,hbm_bytes_modeled,derived")
    print("# the N-volume batch axis: analytic bytes per forward at batch")
    print("# 1/2/4 (weight stream amortized) + virtual-clock latencies of")
    print("# the batched-dispatch scenarios — gated ABSOLUTELY, no machine norm")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_serving_fleet() -> list:
    from benchmarks import bench_serving

    rows = bench_serving.bench_fleet()
    print("\n[serving_fleet] name,us_per_call,hbm_bytes_modeled,derived")
    print("# virtual-clock fleet latencies (replicated schedulers behind the")
    print("# cache-affinity router, seed 0) — gated ABSOLUTELY, no machine norm")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_serving_resilience() -> list:
    from benchmarks import bench_serving

    rows = bench_serving.bench_resilience()
    print("\n[serving_resilience] name,us_per_call,hbm_bytes_modeled,derived")
    print("# fault-storm acceptance keys (seed 0): every key is lower-is-")
    print("# better virtual-clock, gated ABSOLUTELY — growth means the")
    print("# resilience layer recovers less, reaps later, or loses requests")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_serving_cache() -> list:
    from benchmarks import bench_serving

    rows = bench_serving.bench_cache()
    print("\n[serving_cache] name,us_per_call,hbm_bytes_modeled,derived")
    print("# artifact-cache acceptance keys (seed 0): every key is lower-is-")
    print("# better virtual-clock, gated ABSOLUTELY — growth means the cache")
    print("# misses more, serves corrupt bytes, or stops collapsing stampedes")
    for name, us, hbm, note in rows:
        _csv(name, us, hbm, note)
    return rows


def run_table2() -> None:
    from benchmarks import bench_paper_tables as T

    print("\n[table2] MeshNet vs U-Net (synthetic GWM, short training budget)")
    print("model,params,size_mb,dice,paper_size_mb,paper_dice")
    for r in T.table2_model_size_and_dice():
        print(
            f"{r['model']},{r['params']},{r['size_mb']},{r['dice']},"
            f"{r['paper_size_mb']},{r['paper_dice']}"
        )


def run_table4() -> None:
    from benchmarks import bench_paper_tables as T

    print("\n[table4] pipeline stage timings (s) — 48^3 synthetic volume on CPU")
    print("model,params,preprocess,crop,inference,merge,postprocess,status")
    for r in T.table4_pipeline_stages():
        print(
            f"{r['model']},{r['params']},{r['preprocess_s']},{r['crop_s']},"
            f"{r['inference_s']},{r['merge_s']},{r['postprocess_s']},{r['status']}"
        )


def run_interventions() -> None:
    from benchmarks import bench_paper_tables as T

    print("\n[table5] full-volume vs sub-volume success across simulated fleet")
    t5 = T.table5_fail_types()
    for k, v in t5.items():
        print(f"{k}: {json.dumps(v)}")

    print("\n[table6] patching & cropping treatment effects (chi2 + IPTW)")
    t6 = T.table6_patching_cropping()
    for k, v in t6.items():
        print(
            f"{k}: "
            + json.dumps(
                {kk: round(vv, 4) if isinstance(vv, float) else vv for kk, vv in v.items()}
            )
        )

    print("\n[table7] cropping effect by model size")
    print("model,params,sr_no_crop,sr_crop,chi2_p,power")
    for r in T.table7_cropping_effect():
        print(
            f"{r['model']},{r['params']},{r['sr_no_crop']:.4f},{r['sr_crop']:.4f},"
            f"{r['chi2_p']:.2e},{r['power']:.3f}"
        )

    print("\n[table8] texture-size (memory budget) effect")
    t8 = T.table8_texture_size()
    for k, v in t8.items():
        print(f"{k}: {json.dumps(v)}")

    print("\n[fig7] cohort success-rate trend (fleet budgets drift +2.5%/month)")
    print("month,ok,fail,success_rate,gap")
    for r in T.fig7_cohort_trend():
        print(f"{r['month']},{r['ok']},{r['fail']},{r['success_rate']},{r['gap']}")


def run_roofline() -> None:
    from benchmarks import roofline

    path = os.path.join(roofline.RESULTS_DIR, "dryrun_16x16.json")
    if not os.path.exists(path):
        print("\n[roofline] skipped — run PYTHONPATH=src python -m repro.launch.dryrun first")
        return
    print("\n[roofline] three-term roofline per (arch x shape), single pod v5e-256")
    roofline.print_table("16x16")


SECTIONS = {
    "kernels": run_kernels,
    "executors": run_executors,
    "traffic": run_traffic,
    "serving": run_serving,
    "batched": run_batched,
    "serving_fleet": run_serving_fleet,
    "serving_resilience": run_serving_resilience,
    "serving_cache": run_serving_cache,
    "table2": run_table2,
    "table4": run_table4,
    "interventions": run_interventions,
    "roofline": run_roofline,
}


def main(argv: list[str] | None = None, json_path: str = JSON_PATH) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    emit_json = "--json" in args
    args = [a for a in args if a != "--json"]
    if "--json-out" in args:
        i = args.index("--json-out")
        if i + 1 >= len(args) or args[i + 1].startswith("-"):
            raise SystemExit("--json-out needs a path argument")
        json_path = args[i + 1]
        del args[i : i + 2]
        emit_json = True
    wanted = args or list(SECTIONS)
    from repro.runtime import enable_compile_cache

    enable_compile_cache()  # one process: no section starts a child
    trajectory: dict[str, list] = {}
    for name in wanted:
        rows = SECTIONS[name]()
        if emit_json and name in MEASURED_SECTIONS and rows:
            trajectory[name] = _rows_to_json(rows)
    if emit_json:
        # Merge into the existing trajectory so running a subset of
        # sections refreshes only those sections instead of clobbering
        # the rest of the committed file.
        merged: dict[str, list] = {}
        if os.path.exists(json_path):
            try:
                with open(json_path) as f:
                    merged = json.load(f)
            except (json.JSONDecodeError, OSError):
                merged = {}
        merged.update(trajectory)
        with open(json_path, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"\nwrote {os.path.abspath(json_path)}")


if __name__ == "__main__":
    main()
