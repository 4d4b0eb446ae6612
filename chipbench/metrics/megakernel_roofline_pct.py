"""The megakernel's share of its roofline: forwards x max(FLOPs / peak
FLOP/s, bytes / peak B/s) over the summed device time of the megakernel's
events in the traced window. FLOPs and bytes come from the configuration
alone (work.py); the peak is bf16's, the megakernel's compute type under
the bf16 and int8w policies. Both gwm configurations are compute-bound.

The kernel's events are found by name. The megakernel's pallas_call
carries no name of its own; the trace (read by hand on a v5e) names each
of its segment calls after the jitted function around it, a
``tpu_custom_call`` instruction ``%meshnet_apply.<n>``."""

KERNEL = "/%meshnet_apply."


def read(run):
    t = run.trace
    if not t or not run.deliveries:
        return None
    kernel_s = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    if kernel_s <= 0:
        return None
    bound = max(run.flops / run.peaks["bf16_flops_per_s"],
                run.bytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * len(run.deliveries) * bound / kernel_s
