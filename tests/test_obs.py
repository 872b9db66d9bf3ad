"""Observability layer (repro/obs): tracer, metrics, fingerprints, and the
instrumentation contracts the determinism audit relies on (DESIGN.md §13)."""
import json
import logging

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import ReproSpec
from repro.obs import audit as audit_mod
from repro.obs import fingerprint as fp
from repro.obs import metrics
from repro.obs import report
from repro.obs import trace
from repro.ops import calibrate as cal_mod
from repro.ops.groupby import groupby_agg
from repro.ops.plan import plan_groupby


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Each test starts from the disabled-trace / empty-registry state and
    leaves no global observability state behind."""
    monkeypatch.delenv(trace.TRACE_ENV, raising=False)
    monkeypatch.delenv(metrics.METRICS_ENV, raising=False)
    trace.disable()
    metrics.reset()
    yield
    trace.disable()
    metrics.reset()


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl_roundtrip(tmp_path):
    sink = tmp_path / "trace.jsonl"
    trace.configure(path=str(sink))
    with trace.span("outer", phase="demo") as outer:
        with trace.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.depth == 1
            inner.set(rows=7)
        trace.event("tick", k=1)
    trace.flush()

    records = [json.loads(l) for l in sink.read_text().splitlines()]
    by_name = {r["name"]: r for r in records}
    assert set(by_name) == {"outer", "inner", "tick"}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["attrs"] == {"rows": 7}
    assert by_name["tick"]["kind"] == "event"
    assert by_name["tick"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["depth"] == 0 and by_name["outer"]["dur_ns"] > 0
    # the in-memory buffer saw the same records
    assert [r["name"] for r in trace.events()] == \
        [r["name"] for r in records]


def test_span_records_error(tmp_path):
    trace.configure()
    with pytest.raises(ValueError):
        with trace.span("doomed"):
            raise ValueError("boom")
    (rec,) = trace.events()
    assert rec["attrs"]["error"] == "ValueError"


def test_disabled_mode_allocates_nothing():
    trace.disable()
    assert not trace.enabled()
    assert trace._state is None          # no sink/buffer/lock exists
    s1, s2 = trace.span("a", x=1), trace.span("b")
    assert s1 is s2 is trace._NULL_SPAN  # shared null context manager
    with s1 as s:
        s.set(anything=True)
    assert trace.event("e") is None
    assert trace.events() == []
    assert trace._state is None


def test_env_init(monkeypatch, tmp_path):
    sink = tmp_path / "env.jsonl"
    monkeypatch.setenv(trace.TRACE_ENV, str(sink))
    trace._init_from_env()
    assert trace.enabled() and trace.sink_path() == str(sink)
    monkeypatch.setenv(trace.TRACE_ENV, "1")
    trace._init_from_env()
    assert trace.enabled() and trace.sink_path() is None   # buffer only


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram():
    metrics.counter("req_total", route="a").inc()
    metrics.counter("req_total", route="a").inc(2)
    metrics.gauge("depth").set(3.0)
    metrics.gauge("depth").add(-1.0)
    h = metrics.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    d = metrics.to_dict()
    assert d["req_total"][0]["value"] == 3.0
    assert d["req_total"][0]["labels"] == {"route": "a"}
    assert d["depth"][0]["value"] == 2.0
    hist = d["lat_seconds"][0]
    assert hist["count"] == 3 and hist["sum"] == pytest.approx(5.55)
    assert hist["buckets"] == [0.1, 1.0]
    assert hist["counts"] == [1, 2]                  # cumulative

    with pytest.raises(ValueError):
        metrics.counter("req_total", route="a").inc(-1)
    with pytest.raises(TypeError):
        metrics.gauge("req_total", route="a")        # kind conflict


def test_metrics_noop_when_disabled(monkeypatch):
    monkeypatch.setenv(metrics.METRICS_ENV, "0")
    c = metrics.counter("ignored_total")
    c.inc(41)
    assert "ignored_total" not in metrics.to_dict()


def test_prometheus_exposition():
    metrics.counter("jobs_total", kind='we"ird\\la\nbel').inc(2)
    metrics.gauge("temp").set(1.5)
    metrics.histogram("size_bytes", buckets=(10.0,)).observe(3.0)
    text = metrics.to_prometheus()
    assert "# TYPE jobs_total counter" in text
    assert 'jobs_total{kind="we\\"ird\\\\la\\nbel"} 2' in text
    assert "temp 1.5" in text
    assert 'size_bytes_bucket{le="10"} 1' in text
    assert 'size_bytes_bucket{le="+Inf"} 1' in text
    assert "size_bytes_sum 3" in text and "size_bytes_count 1" in text


def test_dump_and_report_cli(tmp_path, capsys):
    metrics.counter("done_total").inc(5)
    mpath = tmp_path / "metrics.json"
    metrics.dump(str(mpath))
    trace.configure(path=str(tmp_path / "t.jsonl"))
    with trace.span("work"):
        pass
    trace.flush()
    assert report.main([str(mpath), str(tmp_path / "t.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "done_total" in out and "work" in out


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

_SPEC = ReproSpec(dtype=jnp.float32, L=2)


def _adversarial(n=2001, g=17, seed=3):
    rng = np.random.default_rng(seed)
    vals = (rng.standard_normal(n)
            * 10.0 ** rng.uniform(-20, 15, n)).astype(np.float32)
    vals[::67] = 0.0
    vals[5::331] = 1e-43                                    # denormals
    return vals, rng.integers(0, g, n).astype(np.int32), g


def test_fingerprint_invariance_across_plans():
    vals, ids, g = _adversarial()
    digests = set()
    perm = np.random.default_rng(0).permutation(len(vals))
    for method, chunk, order in [("scatter", 512, slice(None)),
                                 ("scatter", 4096, slice(None)),
                                 ("onehot", 512, slice(None)),
                                 ("radix", 512, slice(None)),
                                 ("scatter", 512, perm)]:
        res, table = groupby_agg(vals[order], ids[order], g,
                                 aggs=("sum", "count", "mean"), spec=_SPEC,
                                 method=method, chunk=chunk,
                                 return_table=True)
        digests.add((fp.fingerprint_table(table, _SPEC),
                     fp.fingerprint_results(res)))
    assert len(digests) == 1, "plans disagree bitwise"


def test_fingerprint_sensitivity_to_one_bit():
    vals, ids, g = _adversarial()
    _, table = groupby_agg(vals, ids, g, aggs=("sum",), spec=_SPEC,
                           return_table=True)
    ref = fp.fingerprint_table(table, _SPEC)
    k = np.array(table.k)
    k.flat[0] ^= 1                                         # one flipped bit
    assert fp.fingerprint_table(table._replace(k=jnp.asarray(k)),
                                _SPEC) != ref
    # the spec prefix is part of the digest: same bits, different format
    assert fp.fingerprint_table(
        table, ReproSpec(dtype=jnp.float32, L=3)) != ref


def test_fingerprint_pytree_is_path_sensitive():
    a = np.arange(4.0, dtype=np.float32)
    assert fp.fingerprint_pytree({"w": a}) == fp.fingerprint_pytree(
        {"w": a.copy()})
    assert fp.fingerprint_pytree({"w": a}) != fp.fingerprint_pytree(
        {"v": a})
    assert fp.fingerprint_array(a) != fp.fingerprint_array(
        a.astype(np.float64))                              # dtype in layout


def test_run_manifest_and_file_roundtrip(tmp_path):
    man = fp.run_manifest(extra={"tag": "t"})
    for key in ("repro_version", "fingerprint_layout", "jax_version",
                "backend", "x64", "python", "calibration_cache"):
        assert key in man
    assert man["tag"] == "t"

    path = tmp_path / "fp.json"
    fp.write_fingerprints(str(path), {"a": "1", "b": "2"}, manifest=man)
    back = fp.read_fingerprints(str(path))
    assert back["a"] == "1" and back[fp.MANIFEST_KEY]["tag"] == "t"
    assert fp.diff_fingerprints(back, {"a": "1", "b": "X"}) == ["b"]
    assert fp.diff_fingerprints(back, dict(back)) == []    # manifest ignored


# ---------------------------------------------------------------------------
# instrumentation contracts
# ---------------------------------------------------------------------------

def test_plan_groupby_emits_decision_event():
    trace.configure()
    plan = plan_groupby(4096, 16, _SPEC, ncols=2)
    evs = [r for r in trace.events() if r["name"] == "plan.groupby"]
    assert evs and evs[-1]["attrs"]["method"] == plan.method
    assert evs[-1]["attrs"]["source"] == plan.source
    d = metrics.to_dict()
    assert any(row["value"] >= 1 for row in d["repro_plan_total"])


def _query_records(**kw):
    """Every record of one traced ``groupby_agg`` call over adversarial
    rows."""
    trace.configure()
    vals, ids, g = _adversarial(n=1001)
    groupby_agg(vals, ids, g, aggs=("sum",), spec=_SPEC, **kw)
    return trace.events()


def test_groupby_agg_emits_prescan_stats():
    spans = [r for r in _query_records() if r["kind"] == "span"]
    by_name = {r["name"]: r for r in spans}
    at = by_name["groupby.prescan"]["attrs"]
    assert at["n"] == 1001 and at["L"] == _SPEC.L
    assert at["L_eff"] <= at["L"]
    assert not any(r["name"] == "groupby.prescan_stats"
                   for r in trace.events())
    assert {"groupby.query", "groupby.columns", "groupby.prescan",
            "groupby.aggregate", "groupby.finalize"} <= set(by_name)
    root = by_name["groupby.query"]
    assert root["parent_id"] is None and root["depth"] == 0
    assert root["attrs"] == {"G": 17, "method": "auto", "n": 1001,
                             "ncols": 1}
    t0, t1 = root["ts"], root["ts"] + root["dur_ns"] * 1e-9
    for r in spans:
        if r is not root:
            assert r["root_id"] == root["span_id"], r["name"]
            assert t0 <= r["ts"] and \
                r["ts"] + r["dur_ns"] * 1e-9 <= t1 + 1e-6, r["name"]
    for name in ("groupby.columns", "groupby.prescan", "groupby.aggregate",
                 "groupby.finalize"):
        assert by_name[name]["parent_id"] == root["span_id"], name


def test_groupby_records_share_one_root_id():
    records = [r for r in _query_records() if r["name"].startswith(
        ("groupby.", "plan."))]
    (root,) = [r for r in records if r["name"] == "groupby.query"]
    assert {r["root_id"] for r in records} == {root["span_id"]}


@pytest.mark.parametrize("levels, syncs", [("auto", ["window"]),
                                           (None, [])])
def test_host_syncs_of_the_prescan(levels, syncs):
    records = _query_records(levels=levels)
    got = [r for r in records if r["name"] == "groupby.host_sync"]
    assert [r["attrs"]["what"] for r in got] == syncs
    prescan = [r for r in records if r["name"] == "groupby.prescan"][-1]
    assert all(r["parent_id"] == prescan["span_id"] for r in got)


def test_annotations_carry_span_ids(tmp_path):
    import jax
    from jax.profiler import ProfileData

    trace.configure(jax_annotations=True)
    vals, ids, g = _adversarial(n=257)
    jax.profiler.start_trace(str(tmp_path))
    try:
        groupby_agg(vals, ids, g, aggs=("sum",), spec=_SPEC)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    annotated = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "span_id" in st:
                    annotated[st["span_id"]] = (e.name, st["root_id"])
    spans = [r for r in trace.events() if r["kind"] == "span"]
    assert spans
    for r in spans:
        assert annotated[r["span_id"]] == (r["name"], r["root_id"])


def test_concurrent_tasks_keep_their_own_span_stacks():
    import asyncio

    trace.configure()

    async def writer(name):
        with trace.span(name) as outer:
            await asyncio.sleep(0.01)
            with trace.span(name + ".inner") as inner:
                await asyncio.sleep(0.01)
            assert inner.parent_id == outer.span_id

    async def main():
        await asyncio.gather(writer("a"), writer("b"))

    asyncio.run(main())
    by_name = {r["name"]: r for r in trace.events()}
    for name in ("a", "b"):
        outer, inner = by_name[name], by_name[name + ".inner"]
        assert outer["parent_id"] is None
        assert outer["root_id"] == outer["span_id"]
        assert inner["parent_id"] == outer["span_id"]
        assert inner["root_id"] == outer["span_id"]
    assert by_name["a"]["root_id"] != by_name["b"]["root_id"]


def test_compiles_become_events_named_by_their_span():
    import jax

    trace.configure()
    def fresh_probe(x):
        return jax.lax.sin(x)

    x = jnp.ones(13).block_until_ready()
    with trace.span("probe") as probe:
        jax.jit(fresh_probe)(x).block_until_ready()
    compiles = [r for r in trace.events() if r["name"] == "jax.compile"
                and "fresh_probe" in r["attrs"]["fun"]]
    assert sorted(r["attrs"]["phase"] for r in compiles) == sorted(
        trace.COMPILE_PHASES.values())
    for r in compiles:
        assert r["kind"] == "event" and r["parent_id"] == probe.span_id
        assert r["attrs"]["span"] == "probe"
        assert r["attrs"]["seconds"] >= 0
    trace.disable()
    jax.jit(lambda x: x - 2.0)(x).block_until_ready()
    assert trace.events() == []


def test_calibration_cache_env_guard(tmp_path, caplog):
    path = str(tmp_path / "cal.json")
    cal = cal_mod.Calibration(backend="cpu", points=(
        {"backend": "cpu", "spec": cal_mod.spec_key(_SPEC),
         "method": "scatter", "n": 4096, "G": 16, "ncols": 1,
         "ns_per_row": 10.0},))
    cal_mod.save(cal, path)
    assert cal_mod.load(path) is not None                  # stamp matches

    with open(path) as fh:
        payload = json.load(fh)
    payload["env"]["jax_version"] = "0.0.0-other"
    with open(path, "w") as fh:
        json.dump(payload, fh)
    trace.configure()
    with caplog.at_level(logging.WARNING, logger="repro.calibrate"):
        assert cal_mod.load(path) is None                  # refused
    assert any("calibration cache" in m for m in caplog.messages)
    assert [r for r in trace.events()
            if r["name"] == "calibrate.cache_mismatch"]
    assert cal_mod.load(path, check_env=False) is not None # explicit opt-out

    del payload["env"]                                     # pre-stamp cache
    with open(path, "w") as fh:
        json.dump(payload, fh)
    assert cal_mod.load(path) is None


def test_audit_permutation_preserves_groups():
    base_v, base_k = audit_mod._groupby_dataset(1024, permute=False)
    perm_v, perm_k = audit_mod._groupby_dataset(1024, permute=True)
    ref = sorted(map(tuple, np.column_stack(
        [base_k, base_v.view(np.int32)]).tolist()))
    got = sorted(map(tuple, np.column_stack(
        [perm_k, perm_v.view(np.int32)]).tolist()))
    assert ref == got                                      # same multiset
    assert not np.array_equal(base_k, perm_k)              # actually moved


def test_checkpoint_fingerprint_matches_manifest(tmp_path):
    from repro.checkpoint import ckpt as ckpt_mod
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.zeros(3, np.float32)}
    ckpt_mod.save(str(tmp_path), 4, tree, extra={"step": 4})
    info = ckpt_mod.checkpoint_fingerprint(str(tmp_path))
    assert info["step"] == 4
    assert info["tree_fingerprint"] == fp.fingerprint_pytree(tree)
    restored, extra = ckpt_mod.restore(str(tmp_path), tree)
    assert extra["step"] == 4
    assert fp.fingerprint_pytree(
        {k: np.asarray(v) for k, v in restored.items()}) == \
        info["tree_fingerprint"]
