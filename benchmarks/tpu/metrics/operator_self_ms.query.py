"""ops front end: the self time of the program's ``groupby.query`` spans
per query, in milliseconds: each root's duration less those of its direct
child spans (the planner, the engine counters and the state's assembly)."""


def read(run):
    roots = {r["span_id"]: r["dur_ns"] for r in run.spans
             if r["name"] == "groupby.query"}
    if not roots or not run.work["queries"]:
        return None
    children = sum(r["dur_ns"] for r in run.spans
                   if r.get("parent_id") in roots)
    return (sum(roots.values()) - children) / run.work["queries"] * 1e-6
