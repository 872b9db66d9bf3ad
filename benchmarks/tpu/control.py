#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

For one cell, in one process (its set-up is paid once per seed, its
compiles once): a short window of the cell's own traffic on each of
``--seeds`` with the configuration's accumulator format, and on each of
``--control-seeds`` with the control: the program's own lower-precision
path, ``L`` one less than the configuration states.  The check is the
run's own; its limits always come from the configuration.  One JSON line a
run: the seed, the format, ``correct`` and every number compared.

Usage::

    python3 benchmarks/tpu/control.py --workload q1_power --seconds 3 \\
        --seeds 11 12 13 --control-seeds 21 22 23

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.tpu.cell import CACHE_DIR
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compilation_cache

    from benchmarks.tpu import harness

    enable_compilation_cache()
    bench = harness.load_json(harness.BENCHMARK)
    cell, _, config, mix = harness.resolve(bench, args.workload)
    runs = ([(s, None) for s in args.seeds]
            + [(s, {"L": int(config["spec"]["L"]) - 1})
               for s in args.control_seeds])
    for seed, override in runs:
        t0 = time.perf_counter()
        out = harness.run_loaded(bench, cell, config, mix, seed, args.seconds,
                                 False, t0, devices, spec_override=override)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "format": "control" if override else "stated",
            "L": int((override or config["spec"])["L"]),
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"],
            "seconds": time.perf_counter() - t0,
            "checks": {k: v["value"] for k, v in out["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
