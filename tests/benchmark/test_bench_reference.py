"""The plain reference against ``groupby_agg`` and the stream store on the
CPU at a tiny size, and the comparison's rejection of a lower precision."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import SEED, tiny
from benchmarks.tpu import data_tpch, reference
from benchmarks.tpu.kinds.power import engine_aggs, result_names
from repro.core.types import ReproSpec
from repro.ops import groupby_agg

Q1 = tiny("q1_power")[2]["query"]
STORE = tiny("stream_rf1_w4")[1]["store"]
SPEC = tiny("q1_power")[1]["spec"]


@pytest.fixture(scope="module")
def table():
    return data_tpch.lineitem(SEED, 24_000, 6_000, 2_000)


def _program(table, aggs, group_by, groups, L, where=(), rows=None):
    cols = reference.agg_columns(aggs)
    sel = data_tpch.select(table, list(where), rows, cols + [group_by])
    values = jnp.stack([sel[c] for c in cols], axis=1)
    eng = engine_aggs(aggs, cols)
    res = groupby_agg(values, sel[group_by], groups, aggs=eng,
                      spec=ReproSpec(dtype=jnp.float32, L=L))
    got = [np.asarray(res[k]) for k in result_names(eng)]
    ref = reference.GroupReference({c: np.asarray(sel[c]) for c in cols},
                                   np.asarray(sel[group_by]), groups)
    return ref.compare(got, aggs, SPEC["m"], SPEC["L"], SPEC["W"])


@pytest.mark.parametrize("L, ok", [(2, True), (1, False)])
def test_q1(table, L, ok):
    aggs = [tuple(a) for a in Q1["aggs"]]
    r = _program(table, aggs, "flag", 6, L, Q1["where"], 23_000)
    assert r["count_mismatches"] == 0
    assert (r["error_share_of_bound"] <= 1.0) == ok, r


@pytest.mark.parametrize("L, ok", [(2, True), (1, False)])
def test_q18(table, L, ok):
    r = _program(table, [("sum", "price")], "order", 6_000, L)
    assert (r["error_share_of_bound"] <= 1.0) == ok, r


def test_stream_acknowledged_rows_with_redelivery(table):
    """A store fed pool batches, some of them twice under fresh tags,
    against the reference over that multiset of rows."""
    from repro.stream import StreamStore

    aggs = [tuple(a) for a in STORE["aggs"]]
    cols = reference.agg_columns(aggs)
    v = np.stack([np.asarray(table[c][:4_800]) for c in cols], axis=1)
    k = np.asarray(table["flag"][:4_800])
    store = StreamStore(6, aggs=engine_aggs(aggs, cols),
                        spec=ReproSpec(dtype=jnp.float32, L=2))
    sends = [0, 1, 2, 3, 1, 3, 3]
    for seq, b in enumerate(sends):
        sl = slice(b * 1_200, (b + 1) * 1_200)
        assert not store.ingest(v[sl], k[sl], client="w0",
                                seq=seq).get("duplicate")
    res = store.query()
    got = [np.asarray(res[n]) for n in result_names(engine_aggs(aggs, cols))]
    weights = np.repeat(np.bincount(sends, minlength=4), 1_200)
    ref = reference.GroupReference({c: v[:, i] for i, c in enumerate(cols)},
                                   k, 6, weights=weights)
    r = ref.compare(got, aggs, SPEC["m"], SPEC["L"], SPEC["W"])
    assert r["count_mismatches"] == 0
    assert r["error_share_of_bound"] <= 1.0
    # the same rows counted once each are another multiset
    once = reference.GroupReference({c: v[:, i] for i, c in enumerate(cols)},
                                    k, 6)
    assert once.compare(got, aggs, SPEC["m"], SPEC["L"],
                        SPEC["W"])["count_mismatches"] > 0


@pytest.mark.parametrize("amax", [0.0, 1e-30, 0.07, 1.0, 50.0, 104950.0,
                                  3.3e9, 2.0 ** 20, 3e38])
def test_lattice_matches_the_stated_format(amax):
    spec = ReproSpec(dtype=jnp.float32, L=SPEC["L"], W=SPEC["W"])
    from repro.core import accumulator
    want = int(accumulator.required_e1(jnp.asarray([amax], jnp.float32),
                                       spec))
    assert reference.lattice_e1(amax, SPEC["m"], SPEC["L"],
                                SPEC["W"]) == want


def test_select_keeps_shapes_fixed_across_seeds():
    a = data_tpch.lineitem(SEED, 24_000, 6_000, 2_000)
    b = data_tpch.lineitem(SEED + 1, 24_000, 6_000, 2_000)
    sa = data_tpch.select(a, Q1["where"], 23_000, ["qty", "flag"])
    sb = data_tpch.select(b, Q1["where"], 23_000, ["qty", "flag"])
    assert sa["qty"].shape == sb["qty"].shape == (23_000,)
    assert not np.array_equal(np.asarray(sa["qty"]), np.asarray(sb["qty"]))
    again = data_tpch.lineitem(SEED, 24_000, 6_000, 2_000)
    assert np.array_equal(np.asarray(a["price"]), np.asarray(again["price"]))
    with pytest.raises(ValueError):
        data_tpch.select(a, Q1["where"], 24_000, ["qty"])
