"""The trace reduction, on intervals made by hand and on a trace of the
``q1_power`` cell recorded on a TPU v5e chip (``data/``)."""
from __future__ import annotations

import lzma
import types
from pathlib import Path

import pytest

from bench_tiny import ROOT
from benchmarks.tpu import harness, trace_reduce

#: 0.19 s of `q1_power` (3 queries), traced by ``cell.py --trace 1
#: --keep-trace`` on a TPU v5 lite
FIXTURE = Path(__file__).parent / "data" / "q1_power.xplane.pb.xz"


def test_busy_is_the_union_and_gaps_take_the_innermost_span():
    dev = "/device:TPU:0"
    t = trace_reduce.DeviceTrace(
        window=(0, 100),
        ops={dev: [(10, 30, "a"), (20, 40, "b"), (60, 70, "a")]},
        host=[(0, 100, "bench.query"), (40, 60, "groupby.prescan"),
              (45, 55, "groupby.finalize")])
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx(40e-9)          # [10,40] and [60,70]
    assert t.idle_percent() == pytest.approx(60.0)
    assert t.op_seconds() == pytest.approx({"a": 30e-9, "b": 20e-9})
    assert t.kernel_seconds("^a$") == pytest.approx(30e-9)
    gaps = t.idle_gaps()
    # [0,10] and [70,100] lie only under bench.query; [40,60] is centred
    # in the finalize span nested in the prescan span
    assert gaps == pytest.approx({"bench.query": 40e-9,
                                  "groupby.finalize": 20e-9})


def test_no_device_operations_gives_no_idle_share():
    t = trace_reduce.DeviceTrace(window=(0, 10), ops={}, host=[])
    assert t.busy_s == 0.0
    assert t.idle_percent() is None


def test_op_name_is_the_instruction():
    assert trace_reduce.op_name(
        "%segment_agg_kernel.1 = (s32[2,6,128]) custom-call(...)") == \
        "segment_agg_kernel.1"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    root = tmp_path_factory.mktemp("trace")
    d = root / "plugins" / "profile" / "chip"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(lzma.decompress(FIXTURE.read_bytes()))
    return trace_reduce.load(str(root))


def test_recorded_trace(recorded):
    t = recorded
    queries = [h for h in t.host if h[2] == "bench.query"]
    kernels = [op for op in t.ops["/device:TPU:0"]
               if op[2].startswith("segment_agg_kernel")]
    assert len(queries) == len(kernels) == 3      # one kernel a query
    # what the run that recorded it reported from the same trace
    assert t.busy_s == pytest.approx(0.066018183)
    assert t.window_s == pytest.approx(0.194840435)
    assert t.kernel_seconds(r"segment_agg_kernel") == pytest.approx(
        sum(e - s for s, e, _ in kernels) * 1e-9)
    gaps = t.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)
    assert "groupby.prescan" in gaps


def test_metrics_read_the_recorded_trace(recorded):
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    nq = sum(1 for h in recorded.host if h[2] == "bench.query")
    spans = [{"name": "groupby.prescan", "dur_ns": 2_000_000}] * nq
    work = {"queries": nq, "rows": 5_900_000,
            "query_bytes": 5_900_000 * 24 + 6 * 8 * 4,
            "kernel_bytes": 5_900_000 * 28 + 2 * 6 * 6 * 2 * 4,
            "kernel_flops": 5_900_000 * 6 * 2 * 5}
    run = harness.TracedRun(spans=spans, device=recorded, work=work,
                            peaks={"hbm_bytes_per_s": 819e9,
                                   "bf16_flops": 197e12})
    read = {m["name"]: harness.metric_reader(m["name"])(run)
            for m in harness.per_layer_for(bench, "q1_power")}
    assert read["prescan_ms.query"] == pytest.approx(2.0)
    assert read["device_idle.query"] == pytest.approx(
        100 * (1 - recorded.busy_s / recorded.window_s))
    for share in ("segment_kernel_roofline.query",
                  "device_hbm_roofline.query"):
        assert 0 < read[share] < 100, share
    empty = types.SimpleNamespace(spans=[], device=recorded,
                                  work=dict(work, queries=0), peaks={})
    assert harness.metric_reader("prescan_ms.query")(empty) is None
