"""``bin_xorsum_roofline`` read in the outage cell, where round 1 bins
65,536 units at n = 511: the least time binning's bytes need at the chip's
HBM bandwidth (``work/bin_xorsum``, from the reference's per-round shapes
of every reconciliation in the window) over the binning kernel's device
time in the trace."""
from work import bin_xorsum


def read(run):
    if run.device_trace is None or run.work is None:
        return None
    kernel_s = bin_xorsum.device_seconds(run.device_trace["op_s"])
    if kernel_s <= 0:
        return None
    return 100.0 * bin_xorsum.least_seconds(run.work, run.peaks) / kernel_s
