"""The whole step's share of the chip's bf16 peak: forward FLOPs (work.py)
x volumes delivered in the traced window / (traced window x peak)."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0 or not run.deliveries:
        return None
    done = len(run.deliveries) * run.flops
    return 100.0 * done / (t["window_s"] * run.peaks["bf16_flops_per_s"])
