"""The benchmark's run of one cell: set-up, measured window, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json`` — the deployment: data scale, accumulator
  format, guarantees and the limit of every number compared;
* ``traffic/<mix>.json`` — the mix's parameters, read by the general
  driver of its ``kind`` (``kinds/<kind>.py``);
* ``metrics/<metric>.py`` — one per-layer metric: ``read(run)`` returns
  its number from the traced run, or ``None`` where there is nothing to
  read.

A driver (``kinds/<kind>.py``) has ``Driver(config, mix, seed, spec)`` with
``setup()``, ``window(seconds, traced) -> Window``, ``check() -> {name:
value}`` and ``close()``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"
TRAFFIC = HERE.relative_to(ROOT) / "traffic"
METRICS = HERE.relative_to(ROOT) / "metrics"

#: a compile the persistent cache could not serve (a hit also records a
#: backend compile duration: the time it took to load)
_COMPILE_EVENT = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns."""

    attempted: int
    failed: int
    metrics: dict          # end-to-end metric name -> value
    work: dict             # counts the per-layer readers use
    error: str | None = None   # the first failure, where any failed


@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric reads."""

    spans: list            # the program's span records in the window
    device: object         # trace_reduce.DeviceTrace
    work: dict
    peaks: dict


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: Path = ROOT):
    """(cell, config entry, config, mix) of the named cell, read from the
    files under ``root`` that its names lead to."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(root / cfg_entry["file"])
    mix = load_json(root / TRAFFIC / f"{cell['traffic']}.json")
    return cell, cfg_entry, config, mix


def end_to_end_for(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> list:
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def driver_class(kind: str):
    return importlib.import_module(f"benchmarks.tpu.kinds.{kind}").Driver


def metric_reader(name: str, root: Path = ROOT):
    path = root / METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.tpu.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_spec(config: dict, override: dict | None = None):
    """The accumulator format the program runs: the configuration's,
    unless a control overrides part of it."""
    import jax.numpy as jnp
    from repro.core.types import ReproSpec

    s = dict(config["spec"], **(override or {}))
    return ReproSpec(dtype=jnp.dtype(s["dtype"]).type, L=int(s["L"]),
                     W=int(s["W"]))


class CompileCounter:
    """Counts XLA compiles after ``start()``; a warm window has none.
    One per process: jax keeps every listener registered."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax
            cls._instance = super().__new__(cls)
            cls._instance.n, cls._instance.counting = 0, False
            jax.monitoring.register_event_listener(cls._instance._on)
        return cls._instance

    def _on(self, event: str, **_) -> None:
        if self.counting and event == _COMPILE_EVENT:
            self.n += 1

    def start(self) -> None:
        self.n, self.counting = 0, True

    def stop(self) -> int:
        self.counting = False
        return self.n


@contextlib.contextmanager
def profiled(trace_dir: str):
    """Profile the enclosed window, the program's spans written into the
    profiler's trace."""
    import jax
    from repro.obs import trace as obs_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    obs_trace.configure(jax_annotations=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices, bench: dict | None = None,
             spec_override: dict | None = None,
             keep_trace: str | None = None) -> dict:
    """One run of a cell; returns the result object the command prints."""
    bench = bench or load_json(BENCHMARK)
    cell, _, config, mix = resolve(bench, workload)
    return run_loaded(bench, cell, config, mix, seed, seconds, trace,
                      t_start, devices, spec_override, keep_trace)


def run_loaded(bench: dict, cell: dict, config: dict, mix: dict, seed: int,
               seconds: float, trace: bool, t_start: float, devices,
               spec_override: dict | None = None,
               keep_trace: str | None = None) -> dict:
    """:func:`run_cell` on a cell whose configuration and mix are given."""
    import jax
    from repro.obs import trace as obs_trace

    from benchmarks.tpu import peaks as peaks_mod
    from benchmarks.tpu import trace_reduce

    workload = cell["name"]
    if len(devices) < cell["chips"]:
        raise RuntimeError(f"{workload} needs {cell['chips']} chips, JAX "
                           f"found {len(devices)}")
    device_info = {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": int(cell["chips"])}
    driver = driver_class(mix["kind"])(config, mix, seed,
                                       program_spec(config, spec_override))
    counter = CompileCounter()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        driver.setup()
        setup_s = time.perf_counter() - t_start
        counter.start()
        if trace:
            with profiled(tmp):
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    win = driver.window(seconds, traced=True)
            spans = [r for r in obs_trace.events() if r["kind"] == "span"]
            obs_trace.disable()
        else:
            win = driver.window(seconds, traced=False)
        window_compiles = counter.stop()
        device_info["memory_peak_bytes"] = memory_peak_bytes(
            devices[:cell["chips"]])
        checks = driver.check()
        metrics, breakdown = {}, None
        if trace:
            dev = trace_reduce.load(tmp)
            if keep_trace:
                shutil.copytree(tmp, keep_trace, dirs_exist_ok=True)
            run = TracedRun(spans=spans, device=dev, work=win.work,
                            peaks=peaks_mod.peaks_for(device_info["kind"]))
            device_info["busy_s"] = dev.busy_s
            device_info["window_s"] = dev.window_s
            for m in per_layer_for(bench, workload):
                value = metric_reader(m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            breakdown = {"device_ops": trace_reduce.top(dev.op_seconds()),
                         "idle_gaps": trace_reduce.top(dev.idle_gaps())}
        else:
            values = dict(win.metrics, setup_s=setup_s)
            for m in end_to_end_for(bench, workload):
                if m["name"] in values:     # absent where nothing completed
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    finally:
        driver.close()
        shutil.rmtree(tmp, ignore_errors=True)
    limits = config["limits"]
    missing = set(checks) - set(limits)
    if missing:
        raise KeyError(f"the configuration sets no limit for "
                       f"{sorted(missing)}")
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in checks.items()}
    correct = (win.attempted > 0 and win.failed == 0
               and all(c["value"] <= c["limit"] for c in compared.values()))
    out = {"correct": bool(correct), "attempted": win.attempted,
           "failed": win.failed, "metrics": metrics, "device": device_info,
           "window_compiles": window_compiles}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if win.error:
        out["error"] = win.error
    out["checks"] = compared
    return out


def report(out: dict) -> None:
    """The compared numbers as the last lines on standard error, then the
    result as the last line on standard output."""
    if out.get("error"):
        print(f"first failure: {out['error']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out, allow_nan=False), flush=True)
