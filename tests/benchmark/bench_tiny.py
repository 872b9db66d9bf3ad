"""Cells of ``BENCHMARK.json`` cut to a size that a CPU test can hold, run
through the harness with the look for a chip skipped."""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.tpu import harness  # noqa: E402

SEED = 3_000_000_019          # past 32 bits, as a run's seed may be
CONFIGS = harness.HERE.relative_to(ROOT) / "configs"


def with_cell(bench: dict, name: str, config: str, traffic: str) -> dict:
    """``bench`` with a cell ``name``, ``traffic/<traffic>.json`` over
    ``configs/<config>.json``, found by their names: the cells that PERF.md
    keeps for later are tested from their files."""
    bench = copy.deepcopy(bench)
    if not any(w["name"] == name for w in bench["workloads"]):
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1})
    if not any(c["name"] == config for c in bench["configs"]):
        bench["configs"].append({"name": config,
                                 "file": str(CONFIGS / f"{config}.json")})
    return bench


BENCH = with_cell(with_cell(
    json.loads((ROOT / "BENCHMARK.json").read_text()),
    "q18_power", "tpch_sf1", "q18_power"),
    "stream_rf1_w4", "stream_q1_wal", "rf1_w4")


def tiny(cell: str, root: Path = ROOT, bench: dict = BENCH):
    """(cell, config, mix) of ``cell`` at 24,000 lineitem rows."""
    entry, _, config, mix = harness.resolve(bench, cell, root=root)
    config, mix = copy.deepcopy(config), copy.deepcopy(mix)
    config["scale"].update(rows=24_000, orders=6_000, parts=2_000)
    cap = mix["query"].get("rows") if mix["kind"] == "power" else None
    if cap is not None:
        config[cap] = min(config[cap], 23_000)
    if mix["kind"] == "ingest":
        mix.update(batch_rows=600, pool_batches=8)
    return entry, config, mix


def run_tiny(cell: str, seconds: float = 1.0, spec_override=None,
             root: Path = ROOT, bench: dict = BENCH) -> dict:
    import jax

    entry, config, mix = tiny(cell, root, bench)
    return harness.run_loaded(bench, entry, config, mix, SEED, seconds,
                              False, time.perf_counter(), jax.devices(),
                              spec_override=spec_override)
