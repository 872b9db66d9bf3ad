"""``partial_agg`` runs its strategy compiled, bit-identical to the eager
execution of the same body.

The eager front (column build, prescan, plan) hands one cached program per
plan decision the strategy tail: the accumulator table and the MIN/MAX
columns.  The definition of a partial state is ``partial_agg`` under
``jax.disable_jit()``; the compiled tail must reproduce it bit for bit for
every strategy, and a second call of one shape must compile nothing.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np
import pytest

from repro.core.types import ReproSpec
from repro.obs import metrics, trace
from repro.ops import partial as partial_mod
from repro.ops import plan as plan_mod
from repro.ops.partial import partial_agg

SPEC = ReproSpec(dtype=np.float32, L=3)
AGGS = (("sum", 0), ("count",), ("var", 1), ("sum_prod", 0, 1), ("min", 0),
        ("max", 1))
#: no ones column (COUNT, MEAN, VAR): its level window is every chunk's, so
#: no chunk could prune more than the batch
SKIP_AGGS = (("sum", 0), ("sum_prod", 0, 1), ("min", 0), ("max", 1))

#: (case, method, groups, aggs, rows sorted by magnitude, radix fan-out)
CASES = [
    ("onehot", "onehot", 37, AGGS, False, None),
    ("scatter", "scatter", 37, AGGS, False, None),
    ("scatter_chunk_skip", "scatter", 37, SKIP_AGGS, True, None),
    ("radix", "radix", 37, AGGS, False, 4),
    ("pallas", "pallas", 37, AGGS, False, None),
    ("rsum", "rsum", 1, AGGS, False, None),
    ("minmax_only", "auto", 37, (("min", 0), ("max", 1)), False, None),
]


@pytest.fixture(autouse=True)
def _clean_obs():
    trace.disable()
    metrics.reset()
    yield
    trace.disable()
    metrics.reset()


def _rows(groups, by_magnitude, seed=0):
    """Rows whose magnitudes span 36 decades; sorted by magnitude (and
    then five prescan chunks long), each summation chunk of them can prune
    more top levels than the whole batch's window (``chunk_skip``)."""
    n = 20000 if by_magnitude else 1500
    rng = np.random.default_rng(seed)
    mag = 10.0 ** rng.uniform(-18, 18, size=(n, 1))
    if by_magnitude:
        mag = np.sort(mag, axis=0)
    v = (rng.standard_normal((n, 2)) * mag).astype(np.float32)
    return v, rng.integers(0, groups, n).astype(np.int32)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


def _leaves(state):
    t = state.table
    return {"k": t.k, "C": t.C, "e1": t.e1, "minv": state.minv,
            "maxv": state.maxv, "rows": state.rows}


@pytest.mark.parametrize("case, method, groups, aggs, by_magnitude, fanout",
                         CASES, ids=[c[0] for c in CASES])
def test_compiled_partial_is_the_eager_bits(monkeypatch, case, method,
                                            groups, aggs, by_magnitude,
                                            fanout):
    if fanout is not None:
        # a table this small is cache-resident: plan the partition anyway
        monkeypatch.setattr(plan_mod, "radix_buckets",
                            lambda *a, **kw: fanout)
    v, keys = _rows(groups, by_magnitude)
    trace.configure()
    got = partial_agg(v, keys, groups, aggs=aggs, spec=SPEC, method=method)
    spans = {r["name"]: r["attrs"] for r in trace.events()
             if r["kind"] == "span"}
    trace.disable()
    with jax.disable_jit():
        want = partial_agg(v, keys, groups, aggs=aggs, spec=SPEC,
                           method=method)
    if case != "minmax_only":
        assert spans["groupby.aggregate"]["method"] == method
        assert spans["groupby.prescan"]["chunk_skip"] == by_magnitude
    assert spans["groupby.aggregate"]["buckets"] == (fanout or 1)
    for name, leaf in _leaves(want).items():
        np.testing.assert_array_equal(_bits(_leaves(got)[name]), _bits(leaf),
                                      err_msg=name)


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


def test_second_partial_of_a_shape_compiles_nothing():
    v, keys = _rows(37, True, seed=1)
    partial_mod._tail.cache_clear()
    with _compiles() as seen:
        first = partial_agg(v, keys, 37, aggs=SKIP_AGGS, spec=SPEC,
                            method="scatter")
        n_first = len(seen)
        second = partial_agg(v, keys, 37, aggs=SKIP_AGGS, spec=SPEC,
                             method="scatter")
    assert n_first >= 1                       # the strategy tail
    assert len(seen) == n_first
    for name, leaf in _leaves(first).items():
        np.testing.assert_array_equal(_bits(_leaves(second)[name]),
                                      _bits(leaf), err_msg=name)
