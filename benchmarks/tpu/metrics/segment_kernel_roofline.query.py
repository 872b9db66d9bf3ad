"""kernels: the Pallas segment kernel's share of its HBM roofline.

Least time: the kernel's operands (stacked float32 columns, int32 ids, the
int32 table) over peak HBM bandwidth, or its operations over peak rate if
that is longer (``cost.py``), for every query of the window; over the
summed device time of the kernel's events.
"""
from benchmarks.tpu import cost

#: the kernel's custom call in the TPU trace takes the name of the jitted
#: wrapper around it (``segment_agg_kernel.1``): the kernel has no name
#: of its own
KERNEL = r"^segment_agg_kernel\b"


def read(run):
    kernel_s = run.device.kernel_seconds(KERNEL)
    if kernel_s <= 0 or not run.work["queries"]:
        return None
    least = cost.least_seconds(run.work["kernel_bytes"],
                               run.work["kernel_flops"], run.peaks)
    return 100.0 * least * run.work["queries"] / kernel_s
