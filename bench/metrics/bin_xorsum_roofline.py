"""Share of its roofline that the binning kernel reaches: the least time
the algorithm's bytes need at the chip's HBM bandwidth (``work/bin_xorsum``,
from the reference's per-round shapes of every reconciliation in the
window) over the kernel's device time in the trace."""
from work import bin_xorsum


def read(run):
    if run.device_trace is None or run.work is None:
        return None
    kernel_s = bin_xorsum.device_seconds(run.device_trace["op_s"])
    if kernel_s <= 0:
        return None
    need_s = bin_xorsum.least_seconds(run.work, run.peaks)
    return 100.0 * need_s / kernel_s
