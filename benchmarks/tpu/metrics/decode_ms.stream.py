"""stream service: the program's ``stream.decode`` spans (the JSON decode
of one request line on the event loop; one line a batch in the ingest
mixes), mean in milliseconds."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans if r["name"] == "stream.decode"]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e-6
