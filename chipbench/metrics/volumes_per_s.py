"""Segmentations delivered to the host over the whole window, per second.
The window runs from its start to its last delivery. Host clock."""


def read(run):
    return len(run.deliveries) / run.window_s if run.deliveries else None
