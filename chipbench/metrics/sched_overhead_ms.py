"""Mean host time the scheduler and the engine spend around each member's
pipeline, in ms: span ``sched.member`` (opened per member in
``serving/scheduler.py:run_batch_until``) less ``pipeline.run`` (opened in
``core/pipeline.py:run``), both kept in ``TelemetryRecord.spans``. None
where no delivery carries these spans (a program without them)."""


def read(run):
    t = []
    for d in run.deliveries:
        spans = getattr(d.record, "spans", None) or {}
        if "sched.member" in spans and "pipeline.run" in spans:
            t.append(spans["sched.member"] - spans["pipeline.run"])
    return 1e3 * sum(t) / len(t) if t else None
