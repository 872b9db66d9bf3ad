"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The traced window is the host annotation ``bench.window`` that the harness
puts around the measured loop; every interval below is clipped to it.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:N`` plane), averaged over the
  devices that ran any;
* operation time by name, and the summed time of the operations whose name
  matches a kernel's pattern;
* idle gaps: each stretch of the window in which no device ran an
  operation, named by the innermost host span that covers its middle (the
  program's spans and the benchmark's own annotations), summed by name.

Operations are named by their HLO instruction (``segment_agg_kernel.1``,
``fusion.13``).

Read with ``jax.profiler.ProfileData``; nothing else is needed.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
#: the program's spans and the benchmark's own annotations: dotted lower
#: case names (``groupby.prescan``, ``stream.prepare``, ``bench.query``);
#: the runtime's own events (``DeferredTpuAllocator::Allocate``) are not
SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
NO_HOST_SPAN = "host.outside_spans"


def op_name(hlo: str) -> str:
    """``%fusion.13 = s32[...] fusion(...)`` -> ``fusion.13``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class DeviceTrace:
    """What the reduction keeps of one trace, in nanoseconds."""

    window: tuple          # (start, end) of the bench.window annotation
    ops: dict              # device plane -> [(start, end, name)], clipped
    host: list             # [(start, end, name)] host annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, plane: str):
        return _union((s, e) for s, e, _ in self.ops[plane])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        planes = [p for p, ops in self.ops.items() if ops]
        if not planes:
            return 0.0
        total = sum(e - s for p in planes for s, e in self.busy_intervals(p))
        return total / len(planes) * 1e-9

    def idle_percent(self):
        """100 (1 - busy / window), or None where no device ran anything."""
        if not any(self.ops.values()):
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_seconds(self) -> dict:
        """Summed device seconds per operation name, over every device."""
        out: dict = {}
        for ops in self.ops.values():
            for s, e, name in ops:
                out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        return out

    def kernel_seconds(self, pattern: str) -> float:
        """Summed device seconds of the operations whose name matches the
        regular expression ``pattern``."""
        rx = re.compile(pattern)
        return sum(sec for name, sec in self.op_seconds().items()
                   if rx.search(name))

    def idle_gaps(self) -> dict:
        """Idle seconds per name of the host span that covered them, on the
        first device that ran anything."""
        planes = sorted(p for p, ops in self.ops.items() if ops)
        w0, w1 = self.window
        busy = self.busy_intervals(planes[0]) if planes else []
        gaps, cur = [], w0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < w1:
            gaps.append((cur, w1))
        host = sorted(self.host)
        out: dict = {}
        active: list = []
        i = 0
        for g0, g1 in gaps:          # gaps come in time order
            mid = (g0 + g1) / 2
            while i < len(host) and host[i][0] <= mid:
                active.append(host[i])
                i += 1
            active = [h for h in active if h[1] >= mid]
            inner = min(active, key=lambda h: h[1] - h[0], default=None)
            name = inner[2] if inner is not None else NO_HOST_SPAN
            out[name] = out.get(name, 0.0) + (g1 - g0) * 1e-9
        return out


def load(trace_dir: str) -> DeviceTrace:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    host, windows, ops = [], [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name))
                               for line in plane.lines
                               if line.name == OPS_LINE
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0 or not SPAN_NAME.match(e.name):
                        continue
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    (windows if e.name == WINDOW else host).append(iv)
    if not windows:
        raise ValueError(f"the trace under {trace_dir} has no {WINDOW!r} "
                         "annotation")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])[:2]
    clipped = {p: [(max(s, w0), min(e, w1), n) for s, e, n in evs
                   if e > w0 and s < w1]
               for p, evs in ops.items()}
    host = [h for h in host if h[1] > w0 and h[0] < w1]
    return DeviceTrace(window=(w0, w1), ops=clipped, host=host)


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of ``{name: seconds}`` as pairs."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
