"""The benchmark's layout: ``BENCHMARK.json`` against the files its names
lead to, the contract's character rules, and the command's refusals."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.tpu import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/tpu/cell.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir(), p


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_files_that_exist(cell):
    entry, cfg_entry, config, mix = harness.resolve(BENCH, cell)
    assert (ROOT / cfg_entry["file"]).is_file()
    assert any(cfg_entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert (ROOT / harness.TRAFFIC / f"{entry['traffic']}.json").is_file()
    assert harness.driver_class(mix["kind"]) is not None
    assert entry["chips"] in (1, 4)
    for m in harness.per_layer_for(BENCH, cell):
        assert (ROOT / harness.METRICS / f"{m['name']}.py").is_file()
        assert callable(harness.metric_reader(m["name"]))
    # every number the cell compares has a limit in its configuration
    assert set(config["limits"]) >= {"error_share_of_bound",
                                     "count_mismatches"}
    assert config["name"] == cfg_entry["name"]
    # every cut of the deployment is a key of its file, listed in both
    assert cfg_entry["reduced"] == config["reduced"]
    assert all(k in config for k in config["reduced"])


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w[k] for w in BENCH["workloads"]
              for k in ("name", "config", "traffic")]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in BENCH[group]]
        assert len(seen) == len(set(seen)), group
    for text in ([c["source"] for c in BENCH["configs"]]
                 + [x["why"] for x in BENCH["configs"] + BENCH["workloads"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = {m["name"] for m in harness.end_to_end_for(BENCH, cell)}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert harness.per_layer_for(BENCH, cell), cell


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS, (m["name"], cell)
            reported = {x["name"] for x in harness.end_to_end_for(BENCH, cell)}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def _run_cell(cwd: Path, env_extra: dict):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/tpu/cell.py", "--workload", CELLS[0],
         "--seed", "3000000007", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cell_refuses_a_host_without_a_tpu():
    proc = _run_cell(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_cell_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cell(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no program to measure" in proc.stderr
