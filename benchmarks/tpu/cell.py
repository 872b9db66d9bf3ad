#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

Usage (from the root of a checkout)::

    python3 benchmarks/tpu/cell.py --workload q1_power --seed 7 \\
        --seconds 30 --trace 0

It loads the cell's configuration, makes its data on the device from
``--seed``, warms up every shape the window uses (all of it counted as
``setup_s``), measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON line: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.  The numbers compared, each
beside its limit, are the last lines on standard error and the ``checks``
key of the result.

It exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or no program (``src/repro``) beside the benchmark.
JAX's persistent compilation cache is kept at ``benchmarks/tpu/.jax_cache``
in the checkout, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: JAX's persistent compilation cache: a fixed path in the checkout (the
#: path is part of every entry's key), the benchmark's own
CACHE_DIR = HERE / ".jax_cache"


def fail(msg: str) -> int:
    print(f"cell: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the profiler trace of a --trace 1 run "
                         "to DIR")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        return fail(f"no program to measure: {ROOT / 'src' / 'repro'} is "
                    "missing")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform} "
                    f"({devices[0].device_kind})")
    from repro.compile_cache import enable_compilation_cache

    from benchmarks.tpu import harness

    CACHE_DIR.mkdir(exist_ok=True)
    enable_compilation_cache()
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, devices,
                           keep_trace=args.keep_trace)
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
