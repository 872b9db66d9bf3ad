"""finalize: the program's ``groupby.finalize`` spans per query, in
milliseconds (the accumulator's conversion to floats and every derived
aggregate)."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans
            if r["name"] == "groupby.finalize"]
    if not durs or not run.work["queries"]:
        return None
    return sum(durs) / run.work["queries"] * 1e-6
