"""From the profiler's trace of one run to the numbers the per-layer
metrics read.

``extract`` reads the ``.xplane.pb`` file the JAX profiler wrote and keeps
two things, on the trace's one clock in nanoseconds:

* the operations each device ran: every event on the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane, as ``[name, start, duration]``. The
  trace names an operation by its whole HLO instruction; the name kept is
  the instruction's name (the text before `` = ``) prefixed by the XLA
  module it ran in (from the ``XLA Modules`` line, its hash cut off):
  ``jit_bound/%meshnet_apply.3``;
* the benchmark's own host spans, the ``jax.profiler.TraceAnnotation``
  events whose names start with ``bench.`` (the harness marks the measured
  window as ``bench.window``).

``reduce`` turns that into:

* ``window_s``: the length of ``bench.window``;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices traced;
* ``op_s``: the summed device time of each operation name in the window;
* ``idle_gaps``: the device's idle time in the window, each gap
  attributed to the innermost benchmark span that overlaps it most
  (``host:none`` where no span does), summed per span name, longest
  first.

A reader that wants a kernel's time sums ``op_s`` over the names that
match it.
"""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def _short(name: str) -> str:
    return name.split(" = ", 1)[0]


def _module(name: str) -> str:
    return name.split("(", 1)[0]


def _device_ops(plane) -> list:
    lines = {line.name: list(line.events) for line in plane.lines}
    modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns), _module(e.name))
                     for e in lines.get(MODULES_LINE, []))
    ops = []
    m = 0
    for e in sorted(lines.get(OPS_LINE, []), key=lambda e: e.start_ns):
        start = int(e.start_ns)
        while m < len(modules) and modules[m][1] <= start:
            m += 1
        inside = m < len(modules) and modules[m][0] <= start
        name = _short(e.name)
        ops.append([f"{modules[m][2]}/{name}" if inside else name, start,
                    int(e.duration_ns)])
    return ops


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device[plane.name] = _device_ops(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce(trace: dict) -> dict:
    windows = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = sorted(((s, s + d, n) for n, s, d in trace["host"] if n != WINDOW))
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    busy_total = 0.0
    op_s: dict[str, float] = {}
    gaps_by: dict[str, float] = {}
    devices = trace["device"]
    for ops in devices.values():
        clipped = []
        for name, s, d in ops:
            cs, ce = _clip(s, s + d, w0, w1)
            if ce > cs:
                clipped.append((cs, ce))
                op_s[name] = op_s.get(name, 0.0) + (ce - cs) * 1e-9
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            best, best_key = None, None
            lo = bisect.bisect_left(starts, gs - longest)
            for s, e, name in spans[lo:bisect.bisect_left(starts, ge)]:
                overlap = min(e, ge) - max(s, gs)
                if overlap <= 0:
                    continue
                key = (overlap, -(e - s))  # most overlap, then innermost
                if best_key is None or key > best_key:
                    best, best_key = name, key
            name = best or "host:none"
            gaps_by[name] = gaps_by.get(name, 0.0) + (ge - gs) * 1e-9
    n = max(1, len(devices))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "devices": len(devices),
        "op_s": op_s,
        "idle_gaps": sorted(gaps_by.items(), key=lambda kv: -kv[1]),
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the operations that took most
    device time, and the longest idle time by what the host was doing."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, s] for name, s in ops],
        "idle_gaps": [[name, s] for name, s in reduced["idle_gaps"][:top]],
    }
