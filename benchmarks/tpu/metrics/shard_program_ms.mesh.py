"""sharded operator: the program's ``groupby.shard_program`` spans per
query, in milliseconds (the host's call of the compiled shard program: its
dispatch, and its build where the call builds it)."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans
            if r["name"] == "groupby.shard_program"]
    if not durs or not run.work["queries"]:
        return None
    return sum(durs) / run.work["queries"] * 1e-6
