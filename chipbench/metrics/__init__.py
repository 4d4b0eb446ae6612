"""One reader per metric: ``read(run) -> float | None`` (harness.Run).
A reader that finds nothing to read returns None and the metric is left
out of the result line."""
