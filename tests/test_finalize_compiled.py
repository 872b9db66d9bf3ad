"""``finalize`` runs compiled, bit-identical to the eager execution of the
same body.

The definition of a finalized result is ``finalize`` under
``jax.disable_jit()``: every primitive dispatched alone, each rounding once.
The compiled programs must reproduce it bit for bit for every aggregate
kind, dtype, level count and magnitude, empty groups included; a second
call of one signature must compile nothing; and the engine counter must
say which path ran.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np
import pytest

from repro.core.types import ReproSpec
from repro.obs import metrics, trace
from repro.ops import partial as partial_mod
from repro.ops.groupby import groupby_agg
from repro.ops.partial import finalize, partial_agg

#: every aggregate kind, VAR/STD on two columns (so a signature holds
#: several of them) and MEAN beside them
AGGS = (("sum", 0), ("count",), ("mean", 0), ("var", 0), ("std", 0),
        ("min", 0), ("max", 0), ("sum_prod", 0, 1), ("var", 1), ("std", 1),
        ("mean", 1), ("min", 1))
G, EMPTY = 384, (7, 200, 383)

#: per dtype, the magnitudes one column spans; the squares VAR/STD
#: accumulate stay finite (DESIGN.md §13.6)
SCALES = {np.float32: {"tiny": (-18, -16), "unit": (-1, 1),
                       "huge": (16, 18), "mixed": (-18, 18)},
          np.float64: {"tiny": (-150, -148), "unit": (-1, 1),
                       "huge": (148, 150), "mixed": (-150, 150)}}

#: TPC-H Q1's aggregates over (qty, price, disc_price, one_plus_tax, disc)
Q1_AGGS = (("sum", 0), ("sum", 1), ("sum", 2), ("sum_prod", 2, 3),
           ("mean", 0), ("mean", 1), ("mean", 4), ("count",))


@pytest.fixture(autouse=True)
def _clean_obs():
    trace.disable()
    metrics.reset()
    yield
    trace.disable()
    metrics.reset()


def _state(dtype, L, scale, seed=0, n=8192):
    lo, hi = SCALES[dtype][scale]
    rng = np.random.default_rng(seed)
    mag = 10.0 ** rng.uniform(lo, hi, size=(n, 1))
    v = (rng.standard_normal((n, 2)) * mag).astype(dtype)
    live = np.setdiff1d(np.arange(G), EMPTY)
    keys = rng.choice(live, n).astype(np.int32)
    return partial_agg(v, keys, G, aggs=AGGS, spec=ReproSpec(dtype=dtype,
                                                             L=L))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


@pytest.mark.parametrize("scale", ["tiny", "unit", "huge", "mixed"])
@pytest.mark.parametrize("L", [1, 2, 3])
@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
def test_compiled_finalize_is_the_eager_bits(dtype, L, scale):
    state = _state(dtype, L, scale, seed=L)
    with jax.disable_jit():
        want = finalize(state)
    got = finalize(state)
    assert list(got) == list(want) == list(state.sig.compiled[0])
    for name in want:
        # COUNT is the exact integer sum of the ones column
        exact = (state.spec.int_dtype if name == "count(*)" else dtype)
        assert got[name].dtype == want[name].dtype == exact, name
        np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]),
                                      err_msg=name)
    for name in ("mean(0)", "var(0)", "std(0)", "mean(1)", "var(1)",
                 "std(1)"):
        r = np.asarray(got[name])
        assert np.isnan(r[list(EMPTY)]).all(), name
        assert not np.isnan(np.delete(r, EMPTY)).any(), name
    assert (np.asarray(got["count(*)"])[list(EMPTY)] == 0).all()


def _contracts() -> bool:
    """Whether this backend fuses ``a * b - c`` into one rounding (FMA)."""
    rng = np.random.default_rng(11)
    a, b, c = rng.standard_normal((3, 4096))
    return bool((np.asarray(jax.jit(lambda a, b, c: a * b - c)(a, b, c))
                 != a * b - c).any())


def test_one_program_would_move_var_bits():
    """The split that pins VAR/STD is load-bearing exactly where the
    backend contracts: there VAR's subtraction compiled beside ``mean *
    mean`` rounds differently somewhere in these states, so the bit
    identity above checks the split and is not vacuous."""
    moved = 0
    for L, scale in ((2, "unit"), (2, "mixed"), (3, "huge")):
        state = _state(np.float64, L, scale, seed=L)
        sig = state.sig
        var = [p for p in sig.compiled[2] if p[0] == "var"]

        @jax.jit
        def fused(table):
            sums = partial_mod.acc_mod.finalize(table, sig.spec)
            terms = partial_mod._finalize_plans(var, sums, {}, {}, sig.spec)
            return partial_mod._finish_spread(["var"] * len(var), terms,
                                              sig.spec)

        with jax.disable_jit():
            want = finalize(state)
        moved += sum(int((_bits(a) != _bits(want[n])).sum())
                     for a, n in zip(fused(state.table), ("var(0)",
                                                          "var(1)")))
    assert (moved > 0) == _contracts()


@contextlib.contextmanager
def _compiles():
    """The XLA compiles of the enclosed block, from a cold finalize cache."""
    seen = []

    def on(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    partial_mod._finalizer.cache_clear()
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def test_second_finalize_of_a_signature_compiles_nothing():
    state = _state(np.float32, 2, "unit")
    with _compiles() as seen:
        first = finalize(state)
        n_first = len(seen)
        second = finalize(state)
    assert n_first == 2                      # the exact part and VAR/STD's
    assert len(seen) == n_first
    for name in first:
        np.testing.assert_array_equal(_bits(second[name]),
                                      _bits(first[name]))


def test_signature_without_var_runs_one_program():
    rng = np.random.default_rng(3)
    v = rng.uniform(1, 1e5, (4000, 5)).astype(np.float32)
    keys = rng.integers(0, 6, 4000).astype(np.int32)
    state = partial_agg(v, keys, 6, aggs=Q1_AGGS)
    with _compiles() as seen:
        finalize(state)
    assert len(seen) == 1


def test_q1_finalize_counts_the_compiled_path():
    rng = np.random.default_rng(5)
    v = rng.uniform(1, 1e5, (4000, 5)).astype(np.float32)
    keys = rng.integers(0, 6, 4000).astype(np.int32)
    trace.configure()
    for _ in range(3):
        groupby_agg(v, keys, 6, aggs=Q1_AGGS)
    with jax.disable_jit():
        finalize(partial_agg(v, keys, 6, aggs=Q1_AGGS))
    rows = metrics.to_dict()["repro_groupby_finalize_total"]
    by_path = {r["labels"]["path"]: r["value"] for r in rows}
    assert by_path == {"compiled": 3.0, "eager": 1.0}
    spans = [r for r in trace.events() if r["name"] == "groupby.finalize"]
    assert [s["attrs"]["path"] for s in spans] == ["compiled"] * 3 + \
        ["eager"]
