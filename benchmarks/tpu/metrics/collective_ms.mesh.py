"""sharded operator: device time of the collective all-reduces per query
(the lattice agreement's ``pmax`` and the merge's ``psum`` of the tables),
mean over the chips, in milliseconds.

The ops are named by their HLO instruction, which takes the name of the
collective that made it (``pmax.14``, ``all-reduce``).
"""
import re

COLLECTIVE = re.compile(r"^(all-reduce|pmax|pmin|psum)\b")


def read(run):
    planes = [ops for ops in run.device.ops.values() if ops]
    if not planes or not run.work["queries"]:
        return None
    ns = sum(e - s for ops in planes for s, e, name in ops
             if COLLECTIVE.search(name))
    if ns <= 0:
        return None
    return ns / len(planes) / run.work["queries"] * 1e-6
