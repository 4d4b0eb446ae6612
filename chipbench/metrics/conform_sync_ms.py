"""Mean host time of conform's synchronous start, in ms: the upload of the
raw scan to the device (span ``conform.upload``) and the range check with
its two blocking reads (``conform.range``), both opened in
``core/conform.py:conform`` and kept in ``TelemetryRecord.spans``.
Conform's sort cannot be queued on the device before both end. None
where no delivery carries these spans (a program without them)."""


def read(run):
    t = []
    for d in run.deliveries:
        spans = getattr(d.record, "spans", None) or {}
        if "conform.upload" in spans and "conform.range" in spans:
            t.append(spans["conform.upload"] + spans["conform.range"])
    return 1e3 * sum(t) / len(t) if t else None
