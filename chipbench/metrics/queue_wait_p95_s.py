"""95th percentile of the scheduler's queue wait (TelemetryRecord
.queue_wait_s: from the due time, passed as arrival_s, to the start of the
request's forward in its dispatch group)."""

from chipbench.load import nearest_rank


def read(run):
    waits = [d.record.queue_wait_s for d in run.deliveries]
    return nearest_rank(waits, 95) if waits else None
