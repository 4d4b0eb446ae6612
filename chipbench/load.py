"""The one load generator: a traffic file's parameters to a schedule of
requests, plus the percentile every latency metric uses.

Open loop (``"loop": "open"``): requests are due on a schedule fixed
before the window starts, whatever the server does. ``"arrivals"``:

* ``"poisson"``: exponential gaps at ``rate_hz``. The gaps are
  stratified: a window of S seconds offers N = round(rate_hz * S)
  requests whose gaps are the N mid-quantiles of the exponential
  distribution, in an order shuffled by the traffic file's
  ``schedule_seed``. Every run replays the same schedule, whatever its
  own seed (which draws the weights and the scans): the i.i.d. gaps of
  the simulator's ``poisson_arrivals``, or even one order per seed, would
  change the busy periods that make the latency tail from run to run.

Closed loop (``"loop": "closed"``): ``outstanding`` requests are kept in
the system; each delivery sends the next.

Every request carries the traffic's ``priority`` class and takes the
next scan of the pool, round-robin.
"""

from __future__ import annotations

import math

import numpy as np


def stratified_gaps(n: int, rate_hz: float, rng: np.random.Generator) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_hz
    rng.shuffle(gaps)
    return gaps


def due_offsets(traffic: dict, seconds: float) -> list[float]:
    """Seconds after the window's start at which each request is due."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    kind = traffic["arrivals"]
    rate = float(traffic["rate_hz"])
    if kind == "poisson":
        n = max(1, round(rate * seconds))
        return [float(t) for t in np.cumsum(stratified_gaps(n, rate, rng))]
    raise ValueError(f"unknown arrival process {kind!r}")


def nearest_rank(values, q: float) -> float:
    """Nearest-rank percentile, no interpolation (a copy of the program's
    ``telemetry/analysis.nearest_rank``)."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return float(s[max(1, math.ceil(q / 100.0 * len(s))) - 1])
