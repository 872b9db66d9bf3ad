"""Each cell's check at a size a CPU test holds: sound runs come out
correct; the control (the program's own lower-precision path, L=1 where the
configuration states L=2) and each fault planted in the timed path come
out not correct.  A plain float32 operator, whose error lies inside the
bound but whose bits follow the rows' order, is among the faults."""
from __future__ import annotations

import numpy as np
import pytest

from bench_tiny import BENCH, run_tiny

CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["error_share_of_bound"]["value"] < 1.0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_at_lower_precision_is_not_correct(cell):
    out = run_tiny(cell, spec_override={"L": 1})
    assert not out["correct"]
    # the limit comes from the configuration's L=2, not the run's L=1
    assert out["checks"]["error_share_of_bound"]["limit"] == 1.0
    assert out["checks"]["error_share_of_bound"]["value"] > 10.0


def _altered(finalize):
    """An answer altered where it is produced: one group's first result
    off by a part in a hundred thousand."""
    def wrapped(state):
        out = dict(finalize(state))
        name = next(iter(out))
        out[name] = out[name].at[0].multiply(1 + 1e-5)
        return out
    return wrapped


@pytest.mark.parametrize("cell", ["q1_power", "q18_power"])
def test_altered_answer_is_not_correct(cell, monkeypatch):
    import repro.ops.groupby as groupby
    monkeypatch.setattr(groupby, "finalize", _altered(groupby.finalize))
    assert not run_tiny(cell)["correct"]


@pytest.mark.parametrize("cell", ["q1_power", "q18_power"])
def test_half_the_rows_left_out_is_not_correct(cell, monkeypatch):
    import repro.ops.groupby as groupby
    partial_agg = groupby.partial_agg

    def half(values, keys, *a, **kw):
        n = values.shape[0] // 2
        return partial_agg(values[:n], keys[:n], *a, **kw)

    monkeypatch.setattr(groupby, "partial_agg", half)
    out = run_tiny(cell)
    assert not out["correct"]
    c = out["checks"]
    assert (c["count_mismatches"]["value"] > 0
            or c["error_share_of_bound"]["value"] > 1.0)


def _plain_float32(values, keys, groups, aggs=("sum",), **_):
    """GROUP BY as plain float32 ``segment_sum``s, in the rows' order."""
    import jax
    import jax.numpy as jnp
    from benchmarks.tpu.kinds.power import result_names

    def seg(x):
        return jax.ops.segment_sum(x, keys, num_segments=groups)

    count = seg(jnp.ones(values.shape[0], jnp.float32))
    out = {}
    for a, name in zip(aggs, result_names(aggs)):
        if a[0] == "count":
            out[name] = count
        elif a[0] == "sum_prod":
            out[name] = seg(values[:, a[1]] * values[:, a[2]])
        else:
            s = seg(values[:, a[1]])
            out[name] = s / count if a[0] == "mean" else s
    return out


@pytest.mark.parametrize("cell", ["q1_power", "q18_power"])
def test_plain_float32_operator_is_not_correct(cell, monkeypatch):
    import repro.ops
    monkeypatch.setattr(repro.ops, "groupby_agg", _plain_float32)
    out = run_tiny(cell)
    assert not out["correct"]
    # its error may lie inside the bound (a pairwise sum's does); the
    # rows' order gives it away all the same
    assert out["checks"]["permuted_not_bit_identical"]["value"] > 0


def test_stream_merge_that_follows_arrival_order_is_not_correct(
        monkeypatch):
    import repro.stream.store as store
    from repro.ops.partial import PartialState
    merge_all_jit = store.merge_all_jit

    def order_dependent(states):
        # the finest level moves by a few units, by the rows merged so far
        # and the batch merged now: the sum of the moves follows the order
        out = merge_all_jit(states)
        k = out.table.k
        nudge = ((states[0].rows * states[-1].table.k[0, 0, 0]) % 7
                 ).astype(k.dtype)
        table = out.table._replace(k=k.at[0, 0, -1].add(nudge))
        return PartialState(table, out.minv, out.maxv, out.rows,
                            sig=out.sig)

    monkeypatch.setattr(store, "merge_all_jit", order_dependent)
    out = run_tiny("stream_rf1_w4")
    assert not out["correct"]
    c = out["checks"]
    assert c["reordered_not_bit_identical"]["value"] > 0
    assert c["error_share_of_bound"]["value"] <= 1.0


def test_stream_commit_that_leaves_the_state_unchanged_is_not_correct(
        monkeypatch):
    from repro.stream.store import StreamStore
    commit = StreamStore.commit
    calls = {"n": 0}

    def lossy(self, state, rows):
        calls["n"] += 1
        if self.wal is not None and calls["n"] % 5 == 0:
            state = None                  # acknowledged, never applied
        return commit(self, state, rows)

    monkeypatch.setattr(StreamStore, "commit", lossy)
    out = run_tiny("stream_rf1_w4")
    assert not out["correct"]
    assert out["checks"]["count_mismatches"]["value"] > 0


def test_stream_altered_answer_is_not_correct(monkeypatch):
    import repro.stream.store as store
    monkeypatch.setattr(store, "finalize", _altered(store.finalize))
    out = run_tiny("stream_rf1_w4")
    assert not out["correct"]


def test_queries_that_differ_in_bits_are_not_correct(monkeypatch):
    import repro.ops.groupby as groupby
    finalize = groupby.finalize
    calls = {"n": 0}

    def drifting(state):
        calls["n"] += 1
        out = dict(finalize(state))
        if calls["n"] % 2:
            name = next(iter(out))
            v = np.asarray(out[name]).copy()
            v[0] = np.nextafter(v[0], np.float32(np.inf))
            out[name] = v
        return out

    monkeypatch.setattr(groupby, "finalize", drifting)
    out = run_tiny("q1_power")
    assert out["checks"]["queries_not_bit_identical"]["value"] > 0
    assert not out["correct"]


def test_stream_half_of_each_batch_left_out_is_not_correct(monkeypatch):
    from repro.stream.store import StreamStore
    prepare = StreamStore.prepare

    def half(self, values, keys):
        n = len(values) // 2
        return prepare(self, values[:n], keys[:n])

    monkeypatch.setattr(StreamStore, "prepare", half)
    out = run_tiny("stream_rf1_w4")
    assert not out["correct"]
    assert out["checks"]["count_mismatches"]["value"] > 0
