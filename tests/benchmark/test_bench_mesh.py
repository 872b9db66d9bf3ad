"""The four-chip cell ``q1_sf100_mesh4`` at a size a CPU test holds, on 4
host devices (a subprocess, ``_mesh_cell_check.py``): the sound run comes
out correct, the control and each fault planted in the sharded operator do
not.  And its per-layer readers, on spans and device traces made by hand."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.tpu import harness, trace_reduce


@pytest.fixture(scope="module")
def runs():
    script = os.path.join(os.path.dirname(__file__), "_mesh_cell_check.py")
    res = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r
    assert r["checks"]["error_share_of_bound"] < 1.0


def test_control_at_lower_precision_is_not_correct(runs):
    r = runs["control"]
    assert not r["correct"]
    assert r["checks"]["error_share_of_bound"] > 10.0


@pytest.mark.parametrize("fault, check", [
    ("dropped_block", "count_mismatches"),
    ("shard_left_out", "count_mismatches"),
    # a plain float32 merge may land inside the bound; the parts' placement
    # on the chips gives it away
    ("float32_psum", "permuted_not_bit_identical"),
])
def test_planted_fault_is_not_correct(runs, fault, check):
    r = runs[fault]
    assert not r["correct"], r
    assert r["checks"][check] > 0, r


def _spans(built):
    ms = 1_000_000
    return [{"name": "groupby.query", "dur_ns": 300 * ms, "attrs": {}},
            *({"name": "groupby.shard_program", "dur_ns": 2 * ms,
               "attrs": {"built": b}} for b in built),
            {"name": "groupby.finalize", "dur_ns": ms, "attrs": {}}]


def _trace():
    """Two chips over a 100-ns window: a kernel, a ``pmax`` and an
    ``all-reduce`` on each, chip 1's collectives twice as long."""
    ops = {"/device:TPU:0": [(0, 40, "segment_agg_kernel.8"),
                             (40, 45, "pmax.14"), (50, 55, "all-reduce")],
           "/device:TPU:1": [(0, 40, "segment_agg_kernel.8"),
                             (40, 50, "pmax.14"), (50, 60, "all-reduce")]}
    return trace_reduce.DeviceTrace(window=(0, 100), ops=ops, host=[])


WORK = {"queries": 2, "rows": 1000, "chips": 2, "kernel_bytes": 8_190,
        "kernel_flops": 10, "query_bytes": 0}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


@pytest.mark.parametrize("metric, value", [
    ("shard_program_ms.mesh", 3.0),          # three 2-ms calls, 2 queries
    ("shard_programs_built.mesh", 1.0),
    ("collective_ms.mesh", 7.5e-6),          # (10 + 20) ns / 2 chips / 2
    # 8,190 bytes at 819 GB/s is 10 ns a query, over 80 ns of kernel
    ("segment_kernel_roofline.mesh", 25.0),
    ("device_idle.mesh", 45.0),              # chips busy 50 and 60 ns
])
def test_mesh_readers(metric, value):
    read = harness.metric_reader(metric)
    run = types.SimpleNamespace(spans=_spans([1, 0, 0]), device=_trace(),
                                work=WORK, peaks=PEAKS)
    assert read(run) == pytest.approx(value)
    empty = types.SimpleNamespace(
        spans=[], device=trace_reduce.DeviceTrace(window=(0, 100), ops={},
                                                  host=[]),
        work=dict(WORK, queries=0), peaks=PEAKS)
    assert read(empty) is None


def test_a_warm_window_reads_no_build():
    read = harness.metric_reader("shard_programs_built.mesh")
    run = types.SimpleNamespace(spans=_spans([0, 0]), device=None,
                                work=WORK, peaks=PEAKS)
    assert read(run) == 0.0


def test_blocked_reference_reads_as_the_plain_one():
    """Sorted blocks of 700 rows, the last shifted back over its
    predecessor (``_sorted_block``), give ``GroupReference``'s readings."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.tpu.kinds import mesh_power
    from benchmarks.tpu.reference import GroupReference
    from benchmarks.tpu.reference_blocked import BlockedGroupReference

    rng = np.random.default_rng(5)
    n, groups = 2_000, 6
    cols = {"a": rng.random(n).astype(np.float32) * 100,
            "b": (rng.standard_normal(n) * 3).astype(np.float32)}
    keys = rng.integers(0, groups - 1, n).astype(np.int32)  # one empty
    aggs = [("sum", "a"), ("sum_prod", "a", "b"), ("mean", "b"),
            ("count",)]
    plain = GroupReference(cols, keys, groups)
    got = [np.bincount(keys, cols["a"], groups).astype(np.float32),
           np.bincount(keys, cols["a"] * cols["b"], groups),
           np.bincount(keys, cols["b"], groups) / np.maximum(
               np.bincount(keys, minlength=groups), 1),
           np.bincount(keys, minlength=groups)]
    v = jnp.asarray(np.stack([cols["a"], cols["b"]], axis=1))
    blocked = BlockedGroupReference(aggs, groups)
    for start in range(0, n, 700):
        vb, kb = mesh_power._sorted_block(v, jnp.asarray(keys), 700, groups,
                                          start)
        vb, kb = np.asarray(vb), np.asarray(kb)
        assert (np.diff(kb) >= 0).all()
        blocked.add({"a": vb[0], "b": vb[1]}, kb)
    np.testing.assert_array_equal(blocked.count, plain.count)
    want = plain.compare(got, aggs, 23, 2, 18)
    have = blocked.compare(got, aggs, 23, 2, 18)
    assert have["count_mismatches"] == want["count_mismatches"] == 0
    assert have["error_share_of_bound"] == pytest.approx(
        want["error_share_of_bound"], rel=1e-9)
