"""Device time of the logits-to-labels step per delivery, in ms: the
summed device time of the ops of ``core/pipeline.py:labels``, the one
jitted argmax named ``jit_labels`` in the trace, over the traced window's
deliveries. None where the trace holds no such op (a program without the
named function)."""

PREFIX = "jit_labels/"


def read(run):
    t = run.trace
    if not t or not run.deliveries:
        return None
    labels_s = sum(s for name, s in t["op_s"].items() if name.startswith(PREFIX))
    return 1e3 * labels_s / len(run.deliveries) if labels_s > 0 else None
