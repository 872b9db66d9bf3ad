"""ops front end: the program's ``groupby.host_sync`` spans per query, in
milliseconds (the host waiting on the device for a value it reads back)."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans
            if r["name"] == "groupby.host_sync"]
    if not durs or not run.work["queries"]:
        return None
    return sum(durs) / run.work["queries"] * 1e-6
