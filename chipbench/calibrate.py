#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, at a cell's own
size and load, in one process (set-up compiles once):

    python3 chipbench/calibrate.py --workload gwm_light.cohort \
        --seeds 101,102,...,112 --control-seeds 201,202,203 --seconds 4

For each seed it serves a short window of the cell's traffic and compares
as many sampled deliveries as a run does with the fp32 reference. The
program is read as the configuration states it; the control is the same
served path with the program's int8w policy switched on, the precision
below the configuration's bf16. Prints one JSON line per seed; the runs
of the benchmark itself never run the control.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

CONTROL = "int8w"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    runs = [(int(s), None) for s in args.seeds.split(",") if s]
    runs += [(int(s), CONTROL) for s in args.control_seeds.split(",") if s]
    for seed, precision in runs:
        t0 = time.monotonic()
        try:
            lines = []
            out = harness.serve(cell, seed, args.seconds, False,
                                precision=precision, log=lines.append)
        except harness.BenchFailure as e:
            print(json.dumps({"seed": seed, "precision": precision or "as stated",
                              "failure": str(e)}), flush=True)
            continue
        print(json.dumps({
            "seed": seed, "precision": precision or "as stated",
            "mean_logit_gap": out["checks"]["mean_logit_gap"]["value"],
            "missing": out["checks"]["missing_requests"]["value"],
            "attempted": out["attempted"], "correct": out["correct"],
            "seconds": time.monotonic() - t0,
            "check": [ln for ln in lines if ln.startswith(("check: ", "load: "))]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
