"""A cell, a configuration, a traffic mix and a per-layer metric are added
with new files and new entries in ``BENCHMARK.json`` alone: no file of the
harness changes."""
from __future__ import annotations

import json
import shutil
import types

from bench_tiny import BENCH, ROOT, run_tiny
from benchmarks.tpu import harness

Q6 = {"kind": "power",
      "query": {"name": "TPC-H Q6 (section 2.4.6)",
                "where": [["ship", ">=", 731], ["ship", "<", 1096],
                          ["disc_cents", ">=", 5], ["disc_cents", "<=", 7],
                          ["qty_int", "<", 24]],
                "rows": "q6_rows", "group_by": "none",
                "aggs": [["sum_prod", "price", "disc"]]}}

READER = '''"""rows each query of the window read"""


def read(run):
    return run.work["rows"] if run.work["queries"] else None
'''


def test_new_cell_from_files_and_entries_alone(tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "benchmarks/tpu" / sub,
                        tmp_path / "benchmarks/tpu" / sub)
    cfg = json.loads((ROOT / "benchmarks/tpu/configs/tpch_sf1.json")
                     .read_text())
    cfg.update(name="tpch_sf1_q6", q6_rows=300,
               reduced=cfg["reduced"] + ["q6_rows"])
    (tmp_path / "benchmarks/tpu/configs/tpch_sf1_q6.json").write_text(
        json.dumps(cfg))
    (tmp_path / "benchmarks/tpu/traffic/q6_power.json").write_text(
        json.dumps(Q6))
    (tmp_path / "benchmarks/tpu/metrics/rows_read.q6.py").write_text(READER)

    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="tpch_sf1_q6",
                                 file="benchmarks/tpu/configs/"
                                      "tpch_sf1_q6.json",
                                 reduced=cfg["reduced"]))
    bench["workloads"].append({"name": "q6_power", "config": "tpch_sf1_q6",
                               "traffic": "q6_power", "chips": 1,
                               "why": "Q6: one group, a selective filter"})
    bench["end_to_end"][1]["workloads"].append("q6_power")
    bench["per_layer"].append({"name": "rows_read.q6", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "ops front end",
                               "moves": "query_rows_per_s",
                               "workloads": ["q6_power"]})

    metrics = [m["name"] for m in harness.per_layer_for(bench, "q6_power")]
    assert metrics == ["rows_read.q6"]
    reader = harness.metric_reader("rows_read.q6", root=tmp_path)
    assert reader(types.SimpleNamespace(work={"rows": 300,
                                              "queries": 4})) == 300
    out = run_tiny("q6_power", root=tmp_path, bench=bench)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "query_rows_per_s"}
