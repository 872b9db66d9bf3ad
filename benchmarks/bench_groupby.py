"""Paper Fig. 7 / Fig. 10 / Table III: GROUPBY across group counts, plus
the unified engine (`groupby_agg`) on a TPC-H-Q1-shaped workload.

Part 1 (``run``) compares float32 (non-reproducible baseline), DECIMAL, and
the repro strategies (scatter = drop-in §IV; sort = radix
PartitionAndAggregate §V-B, counting-sort on the low group-id bits; onehot =
MXU summation-buffer fast path) across a Fig. 7-style group-count sweep
(G = 2^2 .. 2^20), reporting slowdown vs float32 and the geometric-mean
slowdown (Table III analogue).

Part 2 (``run_agg``) benchmarks the multi-aggregate engine across planner
paths on the Q1 shape from examples/groupby_analytics.py — SUM x3, AVG x3,
COUNT over 6 groups — against (a) the float32 multi-pass baseline and
(b) an unfused repro path (one segment_rsum per accumulator column),
showing what the fused table buys.

Part 3 (``run_levels``) measures the exponent-prescan level pruning
(DESIGN.md §11): narrow-dynamic-range data on an L=4 accumulator needs only
2 live levels, and the pruned table is bit-identical to the full one.

``cross_check`` is the CI gate: every path (radix partitions, level-pruned
variants, the Pallas kernel, row permutations) must
reproduce the seed scatter table bit for bit; any mismatch fails the
process, so the benchmark lane doubles as a bitwise acceptance sweep.
Results land in BENCH_groupby.json at the repo root.  ``--autotune`` first
runs the measured autotuner (repro/ops/calibrate.py) so the planner rows
reflect calibrated rather than modeled costs.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import keys, ns_per_elem, save_results, timeit, uniform
from repro.obs import fingerprint as obs_fp
from repro.obs import trace as obs_trace
from repro.core import accumulator as acc_mod
from repro.core import prescan
from repro.core import segment as seg_mod
from repro.core.aggregates import radix_buckets, radix_table, segment_table
from repro.core.types import ReproSpec
from repro.numerics import DecimalSpec, decimal_segment_sum
from repro.ops import groupby_agg, plan_groupby
from repro.ops import calibrate as cal_mod

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_groupby.json")


def _geomean(rows, key):
    xs = [r[key] for r in rows if r.get(key)]
    return math.exp(sum(math.log(x) for x in xs) / len(xs)) if xs else None


def _ab_slowdown(fn, base, *args, rounds: int = 3, iters: int = 2,
                 setup_fn=None, setup_base=None) -> float:
    """Interleaved A/B slowdown: alternate (base, fn) timing rounds and
    ratio the minima.  On a noisy shared machine this is far more stable
    than timing each side once in isolation — load spikes hit both sides,
    and the min discards them.

    ``setup_fn`` / ``setup_base`` run before each side's timing round, for
    comparisons that need process state toggled (e.g. tracing on/off) —
    keeping the toggle *inside* the interleave so both sides see the same
    drift, rather than timing two long unequal phases."""
    tb, tf = [], []
    for _ in range(rounds):
        if setup_base is not None:
            setup_base()
        tb.append(timeit(base, *args, warmup=1, iters=iters, reduce="min"))
        if setup_fn is not None:
            setup_fn()
        tf.append(timeit(fn, *args, warmup=1, iters=iters, reduce="min"))
    return min(tf) / min(tb)


def run(quick: bool = True):
    """Fig. 7 sweep.  The first four group counts are the historical
    comparison points feeding ``fig7_summary`` (kept fixed so its geomeans
    stay comparable across the trajectory); the remaining sweep points
    extend to G = 2^20 and feed the separate ``fig7_sweep`` geomeans, where
    the sort->radix win at large G is visible."""
    n = 2**17 if quick else 2**22
    summary_counts = [2**k for k in (2, 6, 10, 14)]
    # G = 1 is the flat-SUM point where the rsum strategy exists; it feeds
    # the sweep (and the rsum column) but not the historical fig7_summary
    sweep_counts = [1] + summary_counts + (
        [2**k for k in (17, 20)] if quick else
        [2**k for k in range(16, 21, 2)])
    vals = jnp.asarray(uniform(n, seed=4))
    spec = ReproSpec(dtype=jnp.float32, L=2)

    # one throwaway shape first so process-wide warmup (thread pools, XLA
    # autotuning) is not billed to the first measured point
    w_ids = jnp.asarray(keys(n, 16, seed=0))
    timeit(jax.jit(lambda v, i: jax.ops.segment_sum(v, i, num_segments=16)),
           vals, w_ids, iters=1)

    rows = []
    for g in sweep_counts:
        ids = jnp.asarray(keys(n, g, seed=g))
        base = jax.jit(
            lambda v, i: jax.ops.segment_sum(v, i, num_segments=g))
        t_base = timeit(base, vals, ids, iters=5, reduce="min")
        row = {"n_groups": g, "float32_ns": ns_per_elem(t_base, n),
               "sort_buckets": radix_buckets(g, 1, spec)}

        d = DecimalSpec(precision=9, scale=4)
        f = jax.jit(functools.partial(decimal_segment_sum, num_segments=g,
                                      dspec=d))
        row["decimal9_slowdown"] = _ab_slowdown(f, base, vals, ids)

        for method in ("scatter", "sort", "onehot", "rsum"):
            if method == "onehot" and g > 2**12:
                row[f"{method}_slowdown"] = None   # dense matmul impractical
                continue
            if method == "rsum" and g != 1:
                row[f"{method}_slowdown"] = None   # flat kernel: G == 1 only
                continue
            f = jax.jit(functools.partial(
                seg_mod.segment_rsum, num_segments=g, spec=spec,
                method=method))
            row[f"{method}_slowdown"] = _ab_slowdown(f, base, vals, ids)
        rows.append(row)

    head = [r for r in rows if r["n_groups"] in summary_counts]
    summary = {f"geomean_{m}": _geomean(head, f"{m}_slowdown")
               for m in ("scatter", "sort", "onehot", "decimal9")}
    sweep = {f"geomean_{m}": _geomean(rows, f"{m}_slowdown")
             for m in ("scatter", "sort", "decimal9", "rsum")}

    print("\n== Fig. 7/10 analogue: GROUPBY slowdown vs float32 ==")
    print(f"{'groups':>8} {'f32 ns/el':>10} {'decimal':>8} {'scatter':>8} "
          f"{'sort':>8} {'onehot':>8} {'rsum':>8} {'B':>4}")
    for r in rows:
        fmt = lambda v: f"{v:8.2f}" if v else "       -"
        print(f"{r['n_groups']:>8} {r['float32_ns']:>10.2f} "
              f"{fmt(r['decimal9_slowdown'])} {fmt(r['scatter_slowdown'])} "
              f"{fmt(r['sort_slowdown'])} {fmt(r['onehot_slowdown'])} "
              f"{fmt(r['rsum_slowdown'])} {r['sort_buckets']:>4}")
    print("Table III analogue (geomean slowdown):",
          {k: round(v, 2) for k, v in summary.items() if v})
    print("full-sweep geomeans (incl. large G):",
          {k: round(v, 2) for k, v in sweep.items() if v})
    save_results("groupby", {"rows": rows, "summary": summary,
                             "sweep": sweep})
    return rows, summary, sweep


# ---------------------------------------------------------------------------
# Part 2: the unified multi-aggregate engine (TPC-H Q1 shape)
# ---------------------------------------------------------------------------

Q1_AGGS = [("sum", 0), ("sum", 1), ("sum_prod", 1, 2), ("mean", 0),
           ("mean", 1), ("mean", 3), ("count",)]


def _q1_table(n, seed=11):
    rng = np.random.default_rng(seed)
    qty = (rng.integers(1, 51, n) + rng.standard_normal(n) * 1e-3)
    price = rng.lognormal(7, 1.5, n)
    disc = rng.random(n) * 0.1
    vals = np.stack([qty, price, 1.0 - disc, disc], 1).astype(np.float32)
    flag = rng.integers(0, 6, n).astype(np.int32)
    return jnp.asarray(vals), jnp.asarray(flag)


def _float_q1(v, ids, g):
    """Non-reproducible float baseline: one segment_sum per column + count."""
    seg = functools.partial(jax.ops.segment_sum, num_segments=g)
    s_qty, s_price = seg(v[:, 0], ids), seg(v[:, 1], ids)
    s_disc_price = seg(v[:, 1] * v[:, 2], ids)
    cnt = seg(jnp.ones_like(v[:, 0]), ids)
    return (s_qty, s_price, s_disc_price, s_qty / cnt, s_price / cnt,
            seg(v[:, 3], ids) / cnt, cnt)


def _unfused_repro_q1(v, ids, g, spec):
    """The pre-engine pattern: one independent segment_rsum per column."""
    fin = lambda x: acc_mod.finalize(
        seg_mod.segment_rsum(x, ids, g, spec, method="scatter"), spec)
    s_qty, s_price = fin(v[:, 0]), fin(v[:, 1])
    s_dp, s_disc = fin(v[:, 1] * v[:, 2]), fin(v[:, 3])
    cnt = fin(jnp.ones_like(v[:, 0]))
    return (s_qty, s_price, s_dp, s_qty / cnt, s_price / cnt, s_disc / cnt,
            cnt)


def run_agg(quick: bool = True):
    n, g = (2**17, 6) if quick else (2**22, 6)
    spec = ReproSpec(dtype=jnp.float32, L=2)
    v, ids = _q1_table(n)

    base = jax.jit(functools.partial(_float_q1, g=g))
    t_base = timeit(base, v, ids, iters=3)
    rows = {"n": n, "n_groups": g, "aggs": [list(a) for a in Q1_AGGS],
            "float32_ns_per_row": ns_per_elem(t_base, n)}

    f = jax.jit(functools.partial(_unfused_repro_q1, g=g, spec=spec))
    rows["unfused_repro_slowdown"] = timeit(f, v, ids, iters=3) / t_base

    for method in ("scatter", "sort", "onehot", "auto"):
        f = jax.jit(functools.partial(
            groupby_agg, num_segments=g, aggs=Q1_AGGS, spec=spec,
            method=method))
        rows[f"groupby_agg_{method}_slowdown"] = \
            timeit(f, v, ids, iters=3) / t_base
    rows["plan"] = dataclasses.asdict(plan_groupby(n, g, spec, ncols=5))

    # bitwise attestation: the published numbers come with the digests of
    # the tables they were measured on.  Two planner extremes (explicit
    # scatter vs whatever the cost model picked) must digest identically —
    # a bench run that times a non-reproducible configuration fails here.
    fps = {}
    for method in ("scatter", "auto"):
        res, table = groupby_agg(v, ids, g, aggs=Q1_AGGS, spec=spec,
                                 method=method, return_table=True)
        fps[method] = {"table": obs_fp.fingerprint_table(table, spec),
                       "results": obs_fp.fingerprint_results(res)}
    assert fps["scatter"] == fps["auto"], \
        f"bench workload not bit-identical across plans: {fps}"
    rows["fingerprints"] = fps["auto"]

    print(f"\n== groupby_agg: TPC-H Q1 shape, n={n}, {g} groups ==")
    print(f"  float32 multi-pass baseline: "
          f"{rows['float32_ns_per_row']:.2f} ns/row")
    for k in sorted(rows):
        if k.endswith("_slowdown"):
            print(f"  {k:34} {rows[k]:6.2f}x")
    print(f"  planner: {rows['plan']['method']} [{rows['plan']['source']}] "
          f"({rows['plan']['reason']})")
    print(f"  fingerprints (scatter == auto): "
          f"table={rows['fingerprints']['table'][:16]}… "
          f"results={rows['fingerprints']['results'][:16]}…")
    return rows


# ---------------------------------------------------------------------------
# Part 4: observability overhead (DESIGN.md §13.7)
# ---------------------------------------------------------------------------

def run_obs_overhead(quick: bool = True):
    """Cost of the repro.obs instrumentation on the Q1 engine path.

    Host-side spans/events only run when ``groupby_agg`` executes eagerly
    (under jit they fire once at trace time), so this measures *eager*
    calls: tracing-to-JSONL enabled vs disabled, interleaved A/B.  The
    gated figure is the **disabled** overhead — the per-call cost of the
    no-op span/event fast path times the number of instrumentation sites
    on the hot path, as a fraction of an eager engine call.  That is what
    every un-instrumented production run pays; it must stay ≤ 3%.
    """
    import time as _time

    n, g = (2**14, 6) if quick else (2**17, 6)
    spec = ReproSpec(dtype=jnp.float32, L=2)
    v, ids = _q1_table(n)
    call = functools.partial(groupby_agg, num_segments=g, aggs=Q1_AGGS,
                             spec=spec, method="scatter")

    from benchmarks._util import RESULTS_DIR
    os.makedirs(RESULTS_DIR, exist_ok=True)
    trace_path = os.path.join(RESULTS_DIR, "obs_overhead.jsonl")
    was_enabled, old_path = obs_trace.enabled(), obs_trace.sink_path()
    try:
        # the enabled/disabled pair through the same interleaved A/B
        # min-timing harness as the fig7 sweep: the state toggle happens
        # between every round, so noise and drift hit both sides equally
        # (a one-phase-each measurement once produced a nonsensical
        # negative overhead here)
        slowdown = _ab_slowdown(
            call, call, v, ids, rounds=5, iters=3,
            setup_fn=lambda: obs_trace.configure(path=trace_path),
            setup_base=obs_trace.disable)
        obs_trace.disable()
        t_eager = timeit(call, v, ids, warmup=1, iters=3, reduce="min")

        # disabled fast path, measured directly: one no-op span + attr set,
        # one no-op event, times the site count on the engine's hot path
        # (3 spans + 2 set() + 2 events + 4 counter bumps ≈ 11; use 16 for
        # headroom against future instrumentation)
        sites = 16
        reps = 20000
        t0 = _time.perf_counter()
        for _ in range(reps):
            with obs_trace.span("overhead.probe", n=n) as sp:
                sp.set(ok=True)
            obs_trace.event("overhead.probe", n=n)
        noop_cost = (_time.perf_counter() - t0) / (2 * reps)
    finally:
        if was_enabled:
            obs_trace.configure(path=old_path)
        else:
            obs_trace.disable()

    out = {"n": n, "eager_call_s": t_eager,
           "enabled_overhead_frac": slowdown - 1.0,
           "noop_site_cost_ns": noop_cost * 1e9,
           "instr_sites": sites,
           "disabled_overhead_frac": sites * noop_cost / t_eager}
    print(f"\n== observability overhead (eager Q1, n={n}) ==")
    print(f"  tracing enabled (JSONL sink): "
          f"{out['enabled_overhead_frac'] * 100:+.2f}%")
    print(f"  disabled no-op path: {out['noop_site_cost_ns']:.0f} ns/site "
          f"x {sites} sites = "
          f"{out['disabled_overhead_frac'] * 100:.4f}% of a call")
    assert out["disabled_overhead_frac"] <= 0.03, (
        f"disabled-instrumentation overhead "
        f"{out['disabled_overhead_frac']:.4f} exceeds the 3% budget")
    return out


# ---------------------------------------------------------------------------
# Part 3: exponent-prescan level pruning (DESIGN.md §11)
# ---------------------------------------------------------------------------

def run_levels(quick: bool = True):
    """Narrow-range data on a deep accumulator: L_eff < L pays off."""
    n, g = (2**17, 1024) if quick else (2**20, 1024)
    spec = ReproSpec(dtype=jnp.float32, L=4)
    vals = jnp.asarray(uniform(n, seed=9))[:, None]        # U[1,2): ~2 levels
    ids = jnp.asarray(keys(n, g, seed=13))
    e1 = acc_mod.required_e1(vals, spec, axis=0)
    window = prescan.static_window(vals, e1, spec)
    out = {"spec": f"float32/L{spec.L}/W{spec.W}", "n": n, "n_groups": g,
           "window": list(window)}
    for method in ("scatter", "onehot"):
        full = jax.jit(functools.partial(
            segment_table, num_segments=g, spec=spec, method=method,
            e1=e1, levels=None))
        pruned = jax.jit(functools.partial(
            segment_table, num_segments=g, spec=spec, method=method,
            e1=e1, levels=window))
        t_f = timeit(full, vals, ids, iters=3)
        t_p = timeit(pruned, vals, ids, iters=3)
        out[f"{method}_full_ns"] = ns_per_elem(t_f, n)
        out[f"{method}_pruned_ns"] = ns_per_elem(t_p, n)
        out[f"{method}_speedup"] = t_f / t_p

    print(f"\n== level pruning: L={spec.L}, live window {window} ==")
    for method in ("scatter", "onehot"):
        print(f"  {method:8} {out[f'{method}_full_ns']:8.2f} -> "
              f"{out[f'{method}_pruned_ns']:8.2f} ns/el "
              f"({out[f'{method}_speedup']:.2f}x)")
    return out


# ---------------------------------------------------------------------------
# the bitwise cross-check gate (run by the CI bench lane)
# ---------------------------------------------------------------------------

def cross_check():
    """Every execution path must reproduce the seed scatter table bit for
    bit: radix partitions (several fan-outs), level-pruned variants, the
    Pallas kernel (compiled on a TPU, interpreted on the CPU backend), and
    row permutations.  Raises on any
    mismatch, which fails the benchmark lane."""
    from repro.kernels.segment_rsum.ops import segment_agg_kernel

    rng = np.random.default_rng(7)
    n, g = 20001, 129
    spec = ReproSpec(dtype=jnp.float32, L=3)
    vals = np.stack([
        rng.standard_normal(n) * np.exp(rng.standard_normal(n) * 4),
        rng.random(n) + 1.0,
    ], 1).astype(np.float32)
    vals[::101] = 0.0
    vals[3::907] = 1e-41                                   # denormals
    ids = rng.integers(0, g, n).astype(np.int32)
    e1 = acc_mod.required_e1(jnp.asarray(vals), spec, axis=0)
    window = prescan.static_window(jnp.asarray(vals), e1, spec)

    ref = segment_table(vals, ids, g, spec, method="scatter", e1=e1)

    def check(name, acc):
        for a, b in zip(ref, acc):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"cross-check: {name}")

    for method in ("sort", "radix", "onehot"):
        check(method, segment_table(vals, ids, g, spec, method=method, e1=e1))
    for buckets in (2, 8, 64):
        k, C = radix_table(jnp.asarray(vals), jnp.asarray(ids), g, spec, e1,
                           chunk=1024, num_buckets=buckets)
        check(f"radix B={buckets}", (k, C, ref.e1))
    for method in ("scatter", "onehot"):
        check(f"pruned {method} {window}",
              segment_table(vals, ids, g, spec, method=method, e1=e1,
                            levels=window, chunk_skip=True))
    check("pallas",
          segment_agg_kernel(vals, ids, g, spec, e1=e1, levels=window))
    perm = rng.permutation(n)
    check("permuted rows",
          segment_table(vals[perm], ids[perm], g, spec, method="radix",
                        e1=e1))

    # the flat rsum strategy exists only at G == 1: same adversarial values
    # (zeros, denormals, 8-decade magnitude spread) keyed to a single group,
    # full and prescan-pruned windows, against the scatter reference
    ids0 = np.zeros(n, np.int32)
    ref0 = segment_table(vals, ids0, 1, spec, method="scatter", e1=e1)
    for name, kwargs in (("rsum", {}), ("pruned rsum", {"levels": window})):
        acc0 = segment_table(vals, ids0, 1, spec, method="rsum", e1=e1,
                             **kwargs)
        for a, b in zip(ref0, acc0):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"cross-check: {name}")
    print("bitwise cross-check OK (radix, pruned, pallas, rsum, "
          "permutation)")
    return "ok"


def emit_bench_json(quick: bool = True, autotune: bool = False):
    check = cross_check()                  # fail fast, before any timing
    if autotune:
        cal = cal_mod.calibrate(ReproSpec(dtype=jnp.float32, L=2),
                                quick=quick)
        print(f"autotuned: {len(cal.points)} calibration points -> "
              f"{cal_mod.cache_path()}")
    rows, fig7_summary, sweep = run(quick=quick)  # rows: benchmarks/results/
    agg_rows = run_agg(quick=quick)
    level_rows = run_levels(quick=quick)
    obs_rows = run_obs_overhead(quick=quick)
    payload = {"fig7_summary": fig7_summary,
               "fig7_sweep": {"group_counts": [r["n_groups"] for r in rows],
                              **sweep},
               "groupby_agg": agg_rows,
               "level_pruning": level_rows,
               "obs_overhead": obs_rows, "cross_check": check}
    with open(BENCH_JSON, "w") as fh:
        json.dump(payload, fh, indent=1)
    print("wrote", os.path.abspath(BENCH_JSON))
    return payload


if __name__ == "__main__":
    import sys
    emit_bench_json(quick="--quick" in sys.argv,
                    autotune="--autotune" in sys.argv)
