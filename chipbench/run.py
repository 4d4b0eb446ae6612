#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds:

    python3 chipbench/run.py --workload gwm_light.interactive --seed 7 \
        --seconds 40 --trace 0

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` are also the last lines of standard
error. Exits nonzero, printing no result, where JAX finds no TPU, the
program is not beside this directory, or a guard of harness.py trips.
"""

import time

T_START = time.monotonic()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(root=ROOT, t_start=T_START))
