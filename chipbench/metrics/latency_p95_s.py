"""95th percentile (nearest rank) of the latencies latency_p50_s reads."""

from chipbench.load import nearest_rank


def read(run):
    lat = run.latencies()
    return nearest_rank(lat, 95) if lat else None
