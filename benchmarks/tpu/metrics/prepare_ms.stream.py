"""stream store: the program's ``stream.prepare`` spans (one per batch:
column build, prescan round trips, planner and the compiled aggregation),
mean in milliseconds."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans if r["name"] == "stream.prepare"]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e-6
