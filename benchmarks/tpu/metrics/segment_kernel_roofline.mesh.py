"""kernels: the Pallas segment kernel's share of its HBM roofline in the
mesh cell.

Least time: the kernel's operands over all the chips' rows (stacked float32
columns, int32 ids, the int32 table; ``cost.py``) at one chip's peak HBM
bandwidth, or its operations at peak rate if that is longer, for every
query of the window; over the kernel's device time summed over the chips.
"""
from benchmarks.tpu import cost

#: the kernel's custom call takes the name of the jitted wrapper around it
#: (``segment_agg_kernel.8`` inside the shard program)
KERNEL = r"^segment_agg_kernel\b"


def read(run):
    kernel_s = run.device.kernel_seconds(KERNEL)
    if kernel_s <= 0 or not run.work["queries"]:
        return None
    least = cost.least_seconds(run.work["kernel_bytes"],
                               run.work["kernel_flops"], run.peaks)
    return 100.0 * least * run.work["queries"] / kernel_s
