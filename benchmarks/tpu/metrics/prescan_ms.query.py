"""ops front end: the program's ``groupby.prescan`` spans per query, in
milliseconds (the host round trips that decide the level window)."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans if r["name"] == "groupby.prescan"]
    if not durs or not run.work["queries"]:
        return None
    return sum(durs) / run.work["queries"] * 1e-6
