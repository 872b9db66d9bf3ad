"""device: the whole query's share of the HBM roofline.

Least time: the bytes the query must move whatever strategy runs it (each
named value column and the int32 key read once, the results written;
``cost.query_bytes``) over peak HBM bandwidth, for every query of the
window; over the device's busy time in the window.
"""
from benchmarks.tpu import cost


def read(run):
    busy = run.device.busy_s
    if busy <= 0 or not run.work["queries"]:
        return None
    least = cost.least_seconds(run.work["query_bytes"], 0, run.peaks)
    return 100.0 * least * run.work["queries"] / busy
