"""The ``levels="auto"`` prescan runs as one compiled program and one host
read, with the values of its eager run.

The definition of the prescan's answer is its body under
``jax.disable_jit()``: the per-column ``required_e1``, the level window
``(lo, hi)`` and ``chunk_skip``.  The compiled program must return the
same for every magnitude shape (heterogeneous chunks, all zeros,
subnormals, one column, one row, no rows) and level count; the partial
state built on it must be the eager state bit for bit; a second call of
one shape must compile nothing; and the engine counter must say which
path ran.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import prescan
from repro.core.types import ReproSpec
from repro.obs import metrics, trace
from repro.ops import partial as partial_mod
from repro.ops.partial import partial_agg


@pytest.fixture(autouse=True)
def _clean_obs():
    trace.disable()
    metrics.reset()
    yield
    trace.disable()
    metrics.reset()


def _matrix(case, seed=0):
    """Accumulator-column matrices of the prescan's edge cases."""
    rng = np.random.default_rng(seed)
    if case == "heterogeneous":
        # chunks of wildly different magnitude: one chunk can skip more top
        # levels than the batch's window
        big = (rng.random((4096, 2)) + 1.0) * 2.0 ** 30
        small = (rng.random((8192, 2)) + 1.0) * 2.0 ** -20
        return np.concatenate([big, small]).astype(np.float32)
    if case == "mixed":
        v = (rng.standard_normal((5000, 3))
             * 10.0 ** rng.uniform(-18, 18, (5000, 3))).astype(np.float32)
        v[::53] = 0.0
        return v
    if case == "all_zero":
        return np.zeros((3000, 2), np.float32)
    if case == "subnormal":
        v = np.full((2500, 2), 1e-41, np.float32)
        v[::7, 0] = 3e-39
        v[::5, 1] = 0.0
        return v
    if case == "one_column":
        return (rng.random((4500, 1)) * 1e3).astype(np.float32)
    if case == "one_row":
        return np.array([[3.5, -1e-20, 0.0]], np.float32)
    assert case == "empty"
    return np.zeros((0, 2), np.float32)


CASES = ["heterogeneous", "mixed", "all_zero", "subnormal", "one_column",
         "one_row", "empty"]


def _resolve(X, spec):
    e1, lv, chunk_skip, path = partial_mod._resolve_levels("auto", X, spec)
    return np.asarray(e1), lv, chunk_skip, path


@pytest.mark.parametrize("L", [1, 2, 3])
@pytest.mark.parametrize("case", CASES)
def test_compiled_window_is_the_eager_window(case, L):
    spec = ReproSpec(dtype=jnp.float32, L=L)
    X = jnp.asarray(_matrix(case))
    e1, lv, chunk_skip, path = _resolve(X, spec)
    with jax.disable_jit():
        want_e1, want_lv, want_skip, want_path = _resolve(X, spec)
    np.testing.assert_array_equal(e1, want_e1)
    assert e1.dtype == want_e1.dtype == np.int32
    assert (lv, chunk_skip) == (want_lv, want_skip)
    if case == "empty":
        assert (path, want_path) == ("skipped", "skipped")
    else:
        assert (path, want_path) == ("compiled", "eager")
    # the chunked union is the whole-column window (core/prescan.py)
    assert lv == prescan.static_window(X, e1, spec)
    assert chunk_skip == (case == "heterogeneous" and L > 1)


@pytest.mark.parametrize("L", [1, 2, 3])
@pytest.mark.parametrize("case", ["heterogeneous", "mixed", "subnormal",
                                  "one_row"])
def test_partial_on_the_compiled_window_is_the_eager_state(case, L):
    v = _matrix(case)
    keys = np.random.default_rng(L).integers(0, 11, len(v)).astype(np.int32)
    spec = ReproSpec(dtype=jnp.float32, L=L)
    aggs = [("sum", j) for j in range(v.shape[1])] + [("min", 0)]
    got = partial_agg(v, keys, 11, aggs=aggs, spec=spec, method="scatter")
    with jax.disable_jit():
        want = partial_agg(v, keys, 11, aggs=aggs, spec=spec,
                           method="scatter")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(f"u{a.itemsize}"),
                                      b.view(f"u{b.itemsize}"))


@contextlib.contextmanager
def _compiles():
    """The XLA compiles of the enclosed block."""
    seen = []

    def on(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def test_second_prescan_of_a_shape_builds_nothing():
    spec = ReproSpec(dtype=jnp.float32, L=2)
    X = jnp.asarray(_matrix("mixed", seed=5))
    partial_mod._window_program.cache_clear()
    with _compiles() as seen:
        first = _resolve(X, spec)
        n_first = len(seen)
        second = _resolve(X, spec)
    assert n_first >= 1                              # the window program
    assert len(seen) == n_first
    info = partial_mod._window_program.cache_info()
    assert (info.misses, info.hits) == (1, 1)
    np.testing.assert_array_equal(first[0], second[0])
    assert first[1:] == second[1:]


def _paths():
    rows = metrics.to_dict().get("repro_groupby_prescan_total", [])
    return {r["labels"]["path"]: r["value"] for r in rows}


def test_prescan_counter_counts_each_path():
    v = _matrix("mixed", seed=7)
    keys = np.random.default_rng(7).integers(0, 5, len(v)).astype(np.int32)
    spec = ReproSpec(dtype=jnp.float32, L=2)
    trace.configure()
    for _ in range(2):
        partial_agg(v, keys, 5, spec=spec)                  # compiled
    assert _paths() == {"compiled": 2.0}
    with jax.disable_jit():
        partial_agg(v, keys, 5, spec=spec)                  # eager
    partial_agg(v, keys, 5, spec=spec, levels=None)         # skipped
    partial_agg(v, keys, 5, spec=spec, levels=(0, 1))       # skipped
    partial_agg(v[:0], keys[:0], 5, spec=spec)              # skipped: no rows
    jax.jit(lambda v, k: partial_agg(v, k, 5, spec=spec).table)(v, keys)
    assert _paths() == {"compiled": 2.0, "eager": 1.0, "skipped": 4.0}
    spans = [r["attrs"] for r in trace.events()
             if r["name"] == "groupby.prescan"]
    assert [s["path"] for s in spans] == ["compiled"] * 2 + ["eager"] + \
        ["skipped"] * 4
    syncs = [r["attrs"]["what"] for r in trace.events()
             if r["name"] == "groupby.host_sync"]
    assert syncs == ["window"] * 3


def test_partial_of_no_rows_is_the_empty_partial():
    aggs = (("sum", 0), ("count",), ("var", 1), ("min", 1))
    empty = partial_mod.empty_partial(5, aggs=aggs)
    for levels in ("auto", None, (0, 1)):
        got = partial_agg(np.zeros((0, 2), np.float32),
                          np.zeros(0, np.int32), 5, aggs=aggs, levels=levels)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(empty)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
