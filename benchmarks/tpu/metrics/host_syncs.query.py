"""ops front end: the number of the program's ``groupby.host_sync`` spans
per query (blocking device-to-host reads)."""


def read(run):
    n = sum(1 for r in run.spans if r["name"] == "groupby.host_sync")
    if not n or not run.work["queries"]:
        return None
    return n / run.work["queries"]
