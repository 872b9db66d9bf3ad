"""stream service: the program's ``wal.fsync`` spans (the fsync of one WAL
append; one append a batch), mean in milliseconds."""


def read(run):
    durs = [r["dur_ns"] for r in run.spans if r["name"] == "wal.fsync"]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e-6
