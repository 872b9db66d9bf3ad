"""sharded operator: the shard programs built in the window, the
``groupby.shard_program`` spans with ``built`` 1 (a warm window reads
0)."""


def read(run):
    spans = [r for r in run.spans if r["name"] == "groupby.shard_program"]
    if not spans:
        return None
    return float(sum(1 for r in spans if r["attrs"].get("built")))
