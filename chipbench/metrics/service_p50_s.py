"""Median service time the scheduler stamps (TelemetryRecord.service_s:
one request's pipeline.run, conform to argmax)."""

from chipbench.load import nearest_rank


def read(run):
    service = [d.record.service_s for d in run.deliveries]
    return nearest_rank(service, 50) if service else None
