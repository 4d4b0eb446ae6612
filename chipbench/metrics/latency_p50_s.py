"""Median latency over every request due in the window, from its due time
to its segmentation on the host. A request that never came counts with
the time it was waited for. Host clock."""

from chipbench.load import nearest_rank


def read(run):
    lat = run.latencies()
    return nearest_rank(lat, 50) if lat else None
