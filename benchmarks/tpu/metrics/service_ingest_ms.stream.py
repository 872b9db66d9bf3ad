"""stream service: the program's ``stream.service_ingest`` spans (one per
batch: prepare on the pool, WAL append and fsync, commit), mean in
milliseconds."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans
            if r["name"] == "stream.service_ingest"]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e-6
