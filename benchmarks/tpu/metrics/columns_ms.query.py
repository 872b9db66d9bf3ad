"""ops front end: the program's ``groupby.columns`` spans per query, in
milliseconds (the eager column build: value matrix, key cast, stacked
accumulator columns)."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans if r["name"] == "groupby.columns"]
    if not durs or not run.work["queries"]:
        return None
    return sum(durs) / run.work["queries"] * 1e-6
