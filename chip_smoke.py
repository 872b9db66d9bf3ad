#!/usr/bin/env python3
"""Bring-up smoke test: the reproducible GROUPBY engine on a TPU.

Drives the system's main path through the entry points a user calls, at
TPC-H scale factor 1 (6,001,215 ``lineitem`` rows, generated on the device
from ``--seed`` as TPC-H spec §4.2.3 describes), and checks every result.

One chip (no arguments) runs these phases:

* ``q1``     — Q1's aggregate list (TPC-H §2.4.1) over ``GROUP BY
  l_returnflag, l_linestatus`` through ``repro.ops.groupby_agg``: once with
  the planner (``auto``), once per forced strategy (onehot, scatter, radix,
  pallas), and once more on a seeded row permutation;
* ``q6``     — ``SUM(l_extendedprice * l_discount)`` over Q6's filter, a
  single group, through ``auto`` and the flat ``rsum`` kernel;
* ``q18``    — ``SUM(l_extendedprice) GROUP BY l_orderkey`` (Q18's inner
  aggregate, 1.5M groups) through ``auto``, ``scatter`` and ``radix``;
* ``stream`` — the NDJSON service in this process over a ``StreamStore``
  with a write-ahead log (``fsync="always"``): 16 tagged batches of 65,536
  rows, a re-sent batch, ``query``, ``fingerprints``, then
  ``StreamStore.recover`` of the log into a fresh store;
* ``cpu_parity`` — whether the chip's Q1 table on the first 1M rows equals
  the CPU backend's, printed and not asserted.

``--chips 4`` runs only ``mesh``: Q1 through ``sharded_partial_agg`` over a
4-device mesh, against ``groupby_agg`` on device 0.

Checks: every strategy's accumulator table and finalized results are
byte-identical to the others and to the permuted run; each group's sums lie
within the accumulator's error bound of a float64 reference and COUNT is
exact; the compiled ``pallas`` and ``rsum`` programs hold a Mosaic kernel
(``tpu_custom_call``); the service, the one-shot operator and the recovered
store fingerprint identically and a re-sent batch is a duplicate; the mesh
table equals the one-chip table.  Any failed check exits non-zero.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or without the repository beside it, the script fails
before it prints any result.

Usage: ``python3 chip_smoke.py [--seed N] [--chips 4]``
"""
from __future__ import annotations

import argparse
import asyncio
import datetime
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

SF1_ROWS = 6_001_215          # lineitem rows at SF1 (TPC-H §4.2.5)
SF1_ORDERS = 1_500_000        # orders at SF1; each holds 1-7 lineitems
SF1_PARTS = 200_000
STREAM_BATCH = 65_536
STREAM_BATCHES = 16
CPU_PARITY_ROWS = 1 << 20


def _day(y: int, m: int, d: int) -> int:
    """Days since TPC-H's STARTDATE, 1992-01-01."""
    return (datetime.date(y, m, d) - datetime.date(1992, 1, 1)).days


ENDDATE = _day(1998, 12, 31)
CURRENTDATE = _day(1995, 6, 17)
Q1_SHIPDATE_MAX = _day(1998, 12, 1) - 90          # Q1 DELTA = 90
Q6_SHIPDATE = (_day(1994, 1, 1), _day(1995, 1, 1))

# value columns of the lineitem matrix
QTY, PRICE, DISC, DISC_PRICE, ONE_PLUS_TAX = range(5)
# dense group ids: returnflag (A, N, R) x linestatus (F, O)
Q1_GROUPS = 6
Q1_AGGS = (("sum", QTY), ("sum", PRICE), ("sum", DISC_PRICE),
           ("sum_prod", DISC_PRICE, ONE_PLUS_TAX), ("mean", QTY),
           ("mean", PRICE), ("mean", DISC), "count")
Q1_METHODS = ("auto", "onehot", "scatter", "radix", "pallas")


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# data: TPC-H lineitem (spec §4.2.3), generated on the default device
# ---------------------------------------------------------------------------

def _lines_per_order(rng, orders: int, rows: int) -> np.ndarray:
    """1-7 lineitems per order, uniform, nudged by one line at randomly
    chosen orders until they add up to ``rows``."""
    counts = rng.integers(1, 8, orders)
    diff = rows - int(counts.sum())
    room = np.flatnonzero(counts < 7 if diff > 0 else counts > 1)
    check(abs(diff) <= room.size, "cannot fit the lineitem count")
    counts[rng.choice(room, abs(diff), replace=False)] += np.sign(diff)
    return counts


def lineitem(seed: int, rows: int = SF1_ROWS, orders: int = SF1_ORDERS,
             parts: int = SF1_PARTS) -> dict:
    """The lineitem columns Q1, Q6 and Q18 read, as device arrays.

    ``values`` is (rows, 5) float32: quantity, extended price, discount,
    price * (1 - discount) and 1 + tax.  ``flag`` is the dense
    (returnflag, linestatus) id in [0, 6), ``order`` the dense order id in
    [0, orders), ``ship`` the ship date in days since 1992-01-01.
    """
    import jax

    counts = _lines_per_order(np.random.default_rng(seed), orders, rows)
    # one bulk transfer: a device-side repeat of data-dependent lengths
    # lowers to cumulative sums that are slow to compile and run on the TPU
    order = jax.device_put(np.repeat(np.arange(orders, dtype=np.int32),
                                     counts))
    # every column in one compiled program, not one compile per operation
    return jax.jit(_lineitem_columns, static_argnums=(2, 3))(
        jax.random.key(seed), order, orders, parts)


def _lineitem_columns(key, order, orders: int, parts: int) -> dict:
    import jax
    import jax.numpy as jnp

    rows = order.shape[0]
    k = jax.random.split(key, 8)
    orderdate = jax.random.randint(k[0], (orders,), 0, ENDDATE - 151 + 1)
    qty = jax.random.randint(k[1], (rows,), 1, 51)
    partkey = jax.random.randint(k[2], (rows,), 1, parts + 1)
    retail_cents = (90000 + (partkey // 10) % 20001
                    + 100 * (partkey % 1000))
    price = (qty * retail_cents).astype(jnp.float32) / 100
    disc = jax.random.randint(k[3], (rows,), 0, 11)          # 0.00-0.10
    tax = jax.random.randint(k[4], (rows,), 0, 9)            # 0.00-0.08
    ship = orderdate[order] + jax.random.randint(k[5], (rows,), 1, 122)
    receipt = ship + jax.random.randint(k[6], (rows,), 1, 31)
    returned = jax.random.bernoulli(k[7], 0.5, (rows,))
    # R or A once received by CURRENTDATE, else N; O if shipped after it
    returnflag = jnp.where(receipt <= CURRENTDATE,
                           jnp.where(returned, 2, 0), 1)
    linestatus = (ship > CURRENTDATE).astype(jnp.int32)
    d = disc.astype(jnp.float32) / 100
    values = jnp.stack([qty.astype(jnp.float32), price, d, price * (1 - d),
                        1 + tax.astype(jnp.float32) / 100], axis=1)
    return {"values": values,
            "flag": (returnflag * 2 + linestatus).astype(jnp.int32),
            "order": order, "ship": ship, "qty": qty, "disc_cents": disc}


# ---------------------------------------------------------------------------
# measurement and checks
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


def timed(fn):
    """(fn(), seconds until the device finished, compile seconds in it)."""
    import jax
    c0, t0 = _compile_s[0], time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0, _compile_s[0] - c0


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def run_groupby(values, keys, groups: int, aggs, spec, method: str):
    """``groupby_agg`` with its table; returns a record of the run and the
    outputs.  The plan is read back from the engine's own trace records."""
    from repro.obs import fingerprint as obs_fp
    from repro.obs import trace as obs_trace
    from repro.ops import groupby_agg

    obs_trace.configure()                     # fresh in-memory buffer
    try:
        (res, tab), sec, comp = timed(lambda: groupby_agg(
            values, keys, groups, aggs=aggs, spec=spec, method=method,
            return_table=True))
        events = obs_trace.events()
    finally:
        obs_trace.disable()
    plan = [e["attrs"] for e in events if e["name"] == "plan.groupby"][-1]
    stats = [e["attrs"] for e in events
             if e["name"] == "groupby.prescan"][-1]
    rec = {"method": method, "plan": plan["method"], "chunk": plan["chunk"],
           "levels": stats["levels"], "seconds": sec, "compile_s": comp,
           "table_fp": obs_fp.fingerprint_table(tab),
           "results_fp": obs_fp.fingerprint_results(res)}
    return rec, res, tab


def kernel_program(kind: str, values, keys, groups: int, aggs, spec,
                   rec: dict) -> str:
    """Text of the compiled kernel program a run used (same arguments,
    hence the same jit cache entry)."""
    import jax.numpy as jnp
    from repro.core import accumulator as acc_mod
    from repro.kernels.rsum.ops import rsum_table
    from repro.kernels.segment_rsum.ops import segment_agg_kernel
    from repro.ops.partial import AggSignature, _as_matrix, _build_columns

    sig = AggSignature.build(aggs, groups, spec)
    X = _build_columns(_as_matrix(values, sig.spec), sig.compiled[1],
                       sig.spec)
    e1 = acc_mod.required_e1(X, sig.spec, axis=0)
    keys = jnp.asarray(keys, jnp.int32)
    levels = tuple(rec["levels"]) if rec["levels"] is not None else None
    if kind == "pallas":
        fn, kw = segment_agg_kernel, {"block_n": rec["chunk"]}
    else:
        fn, kw = rsum_table, {"block_rows": rec["chunk"]}
    return fn.lower(X, keys, groups, sig.spec, e1=e1, levels=levels,
                    **kw).compile().as_text()


def column_reference(values, keys, groups: int, aggs, spec):
    """float64 per-group sums, absolute sums and counts of the accumulator
    columns, built on the device exactly as the engine builds them."""
    from repro.ops.partial import AggSignature, _as_matrix, _build_columns

    sig = AggSignature.build(aggs, groups, spec)
    X = np.asarray(_build_columns(_as_matrix(values, sig.spec),
                                  sig.compiled[1], sig.spec), np.float64)
    g = np.asarray(keys)
    count = np.bincount(g, minlength=groups)
    sums = np.stack([np.bincount(g, X[:, c], groups)
                     for c in range(X.shape[1])], axis=1)
    abs_sums = np.stack([np.bincount(g, np.abs(X[:, c]), groups)
                         for c in range(X.shape[1])], axis=1)
    return sums, abs_sums, count


def check_bounds(name: str, table, res: dict, values, keys, groups: int,
                 aggs, spec) -> float:
    """Every group within the accumulator's error bound of the float64
    reference (DESIGN.md §3), COUNT exact.  Returns the largest error as a
    share of its bound.

    Bound per group and column: extraction drops below the finest level a
    residual under half its ulp per row, ``n * 2^(e_L - m - 1)``; finalize
    rounds each of its L level values and L - 1 additions, under
    ``2L * 2^-24`` of the absolute sum; the float64 reference adds
    ``n * 2^-52`` of it.
    """
    from repro.core import accumulator as acc_mod

    sig_spec = spec
    sums, abs_sums, count = column_reference(values, keys, groups, aggs,
                                             sig_spec)
    got = np.asarray(acc_mod.finalize(table, sig_spec), np.float64)
    e1 = np.asarray(table.e1, np.int64)
    e_last = e1 - (sig_spec.L - 1) * sig_spec.W
    n = count[:, None].astype(np.float64)
    bound = (n * np.exp2(e_last - sig_spec.m - 1)
             + (2 * sig_spec.L * 2.0 ** -24 + n * 2.0 ** -52) * abs_sums)
    err = np.abs(got - sums)
    worst = float(np.max(err / np.maximum(bound, np.finfo(float).tiny)))
    check(bool(np.all(err <= bound)),
          f"{name}: a group sum is outside the accumulator's error bound "
          f"(worst {worst:.3g} of the bound)")
    if "count(*)" in res:
        check(np.array_equal(np.asarray(res["count(*)"], np.float64), count),
              f"{name}: COUNT(*) differs from the exact row count")
    return worst


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_groupby(name: str, values, keys, groups: int, aggs, spec,
                  methods, permute_seed=None, kernels=()) -> dict:
    """One aggregate through several strategies: identical bytes from each,
    within bound of the reference, kernels compiled for the chip."""
    import jax.numpy as jnp

    runs, first = [], None
    for method in methods:
        rec, res, tab = run_groupby(values, keys, groups, aggs, spec, method)
        if method in kernels:
            text = kernel_program(method, values, keys, groups, aggs, spec,
                                  rec)
            rec["tpu_custom_call"] = "tpu_custom_call" in text
            check(rec["tpu_custom_call"],
                  f"{name}/{method}: the compiled program has no Mosaic "
                  "kernel")
        runs.append(rec)
        if first is None:
            first = (rec, res, tab)
    if permute_seed is not None:
        perm = jnp.asarray(np.random.default_rng(permute_seed).permutation(
            values.shape[0]))
        rec, _, _ = run_groupby(values[perm], keys[perm], groups, aggs, spec,
                                "auto")
        rec["method"] = "auto/permuted"
        runs.append(rec)
    rec0, res0, tab0 = first
    for rec in runs:
        check(rec["table_fp"] == rec0["table_fp"],
              f"{name}: {rec['method']} table differs from "
              f"{rec0['method']}'s")
        check(rec["results_fp"] == rec0["results_fp"],
              f"{name}: {rec['method']} results differ from "
              f"{rec0['method']}'s")
    worst = check_bounds(name, tab0, res0, values, keys, groups, aggs, spec)
    out = {"phase": name, "rows": int(values.shape[0]), "G": groups,
           "plan": runs[0]["plan"],
           "seconds": {r["method"]: r["seconds"] for r in runs},
           "compile_s": {r["method"]: r["compile_s"] for r in runs},
           "table_fingerprint": rec0["table_fp"],
           "worst_error_share_of_bound": worst,
           "peak_bytes_in_use": peak_bytes()}
    for k in kernels:
        out[f"{k}_tpu_custom_call"] = next(
            r["tpu_custom_call"] for r in runs if r["method"] == k)
    return out


async def _drive_service(values: np.ndarray, keys: np.ndarray, aggs,
                         spec, wal_path: str) -> dict:
    from repro.stream import StreamStore, WriteAheadLog, serve
    from repro.stream.service import LINE_LIMIT
    from repro.ops.partial import AggSignature

    sig = AggSignature.build(aggs, Q1_GROUPS, spec)
    wal = WriteAheadLog(wal_path, sig=sig, fsync="always")
    store = StreamStore(Q1_GROUPS, aggs=aggs, spec=spec, wal=wal)
    server = await serve(store, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    out = {}
    try:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port, limit=LINE_LIMIT)

        async def call(req: dict) -> dict:
            writer.write(json.dumps(req).encode() + b"\n")
            await writer.drain()
            resp = json.loads(await reader.readline())
            check(resp.get("ok") is True, f"stream: {req['op']} failed: "
                  f"{resp.get('error')}")
            return resp

        def batch(i: int) -> dict:
            sl = slice(i * STREAM_BATCH, (i + 1) * STREAM_BATCH)
            return {"op": "ingest", "values": values[sl].tolist(),
                    "keys": keys[sl].tolist(), "client": "chip-smoke",
                    "seq": i}

        t0 = time.perf_counter()
        for i in range(STREAM_BATCHES):
            resp = await call(batch(i))
            check(not resp.get("duplicate"),
                  f"stream: fresh batch {i} reported as a duplicate")
        out["ingest_s"] = time.perf_counter() - t0
        resp = await call(batch(STREAM_BATCHES - 1))
        out["resend_duplicate"] = resp.get("duplicate") is True
        check(out["resend_duplicate"],
              "stream: a re-sent batch was not reported as a duplicate")
        t0 = time.perf_counter()
        query = (await call({"op": "query"}))["results"]
        out["query_s"] = time.perf_counter() - t0
        check(query["count(*)"] == np.bincount(
            keys, minlength=Q1_GROUPS).astype(float).tolist(),
            "stream: query COUNT(*) differs from the rows sent")
        out["fingerprints"] = (await call({"op": "fingerprints"}))[
            "fingerprints"]
        writer.close()
        await writer.wait_closed()
    finally:
        server.close()
        await server.wait_closed()
        wal.close()
    return out


def phase_stream(data: dict, spec) -> dict:
    from repro.obs import fingerprint as obs_fp
    from repro.ops import groupby_agg
    from repro.ops.plan import plan_partial
    from repro.stream import StreamStore

    rows = STREAM_BATCH * STREAM_BATCHES
    values = np.asarray(data["values"][:rows])
    keys = np.asarray(data["flag"][:rows])
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        wal_path = str(Path(d) / "stream.wal")
        c0 = _compile_s[0]
        t0 = time.perf_counter()
        out = asyncio.run(_drive_service(values, keys, Q1_AGGS, spec,
                                         wal_path))
        seconds = time.perf_counter() - t0
        compile_s = _compile_s[0] - c0
        res, tab = groupby_agg(values, keys, Q1_GROUPS, aggs=Q1_AGGS,
                               spec=spec, return_table=True)
        oneshot = {"stream/table": obs_fp.fingerprint_table(tab),
                   "stream/results": obs_fp.fingerprint_results(res)}
        check(out["fingerprints"] == oneshot,
              "stream: service fingerprints differ from the one-shot "
              "groupby_agg over the same rows")
        t0 = time.perf_counter()
        recovered = StreamStore.recover(wal_path)
        recover_s = time.perf_counter() - t0
        try:
            check(recovered.fingerprints() == oneshot,
                  "stream: the store recovered from the WAL fingerprints "
                  "differently")
        finally:
            recovered.wal.close()
    return {"phase": "stream", "rows": rows, "G": Q1_GROUPS,
            "batches": STREAM_BATCHES, "batch_rows": STREAM_BATCH,
            "plan": plan_partial(STREAM_BATCH, Q1_GROUPS, spec,
                                 ncols=recovered.sig.ncols).agg.method,
            "seconds": seconds, "ingest_s": out["ingest_s"],
            "query_s": out["query_s"], "recover_s": recover_s,
            "compile_s": compile_s,
            "resend_duplicate": out["resend_duplicate"],
            "table_fingerprint": oneshot["stream/table"],
            "peak_bytes_in_use": peak_bytes()}


def phase_cpu_parity(data: dict, spec) -> dict:
    """The chip's Q1 table on the first 1M rows beside the CPU backend's.
    Cross-backend parity is not part of the contract yet: printed only."""
    import jax
    from repro.obs import fingerprint as obs_fp
    from repro.ops import groupby_agg

    values = data["values"][:CPU_PARITY_ROWS]
    keys = data["flag"][:CPU_PARITY_ROWS]
    _, tab = groupby_agg(values, keys, Q1_GROUPS, aggs=Q1_AGGS, spec=spec,
                         method="scatter", return_table=True)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        _, tab_cpu = groupby_agg(jax.device_put(values, cpu),
                                 jax.device_put(keys, cpu), Q1_GROUPS,
                                 aggs=Q1_AGGS, spec=spec, method="scatter",
                                 return_table=True)
    return {"phase": "cpu_parity", "rows": CPU_PARITY_ROWS, "G": Q1_GROUPS,
            "plan": "scatter",
            "tpu_table_fingerprint": obs_fp.fingerprint_table(tab),
            "cpu_table_fingerprint": obs_fp.fingerprint_table(tab_cpu),
            "equal": obs_fp.fingerprint_table(tab)
            == obs_fp.fingerprint_table(tab_cpu)}


def phase_mesh(data: dict, spec, chips: int) -> dict:
    """Q1 over the mesh of every device against one device: same bytes.
    ``sharded_partial_agg`` builds its own mesh, as a user's call does."""
    import jax
    from repro.obs import fingerprint as obs_fp
    from repro.ops import groupby_agg
    from repro.ops.partial import finalize
    from repro.ops.sharded import sharded_partial_agg

    check(jax.device_count() == chips,
          f"mesh: {chips} devices wanted, JAX has {jax.device_count()}")
    values, keys = q1_rows(data)
    (res1, tab1), sec1, comp1 = timed(lambda: groupby_agg(
        values, keys, Q1_GROUPS, aggs=Q1_AGGS, spec=spec,
        return_table=True))
    state, sec, comp = timed(lambda: sharded_partial_agg(
        values, keys, Q1_GROUPS, aggs=Q1_AGGS, spec=spec))
    fp1 = obs_fp.fingerprint_table(tab1)
    fp = obs_fp.fingerprint_table(state.table)
    check(fp == fp1, f"mesh: the {chips}-device table differs from the "
          "one-device table")
    check(obs_fp.fingerprint_results(finalize(state))
          == obs_fp.fingerprint_results(res1),
          f"mesh: the {chips}-device results differ from one device's")
    worst = check_bounds("mesh", state.table, finalize(state), values, keys,
                         Q1_GROUPS, Q1_AGGS, spec)
    return {"phase": "mesh", "rows": int(values.shape[0]), "G": Q1_GROUPS,
            "chips": chips, "seconds": {"one_device": sec1, "mesh": sec},
            "compile_s": {"one_device": comp1, "mesh": comp},
            "table_fingerprint": fp, "one_device_table_fingerprint": fp1,
            "worst_error_share_of_bound": worst,
            "peak_bytes_in_use": peak_bytes()}


def q1_rows(data: dict):
    """Q1's WHERE l_shipdate <= date '1998-12-01' - interval '90' day."""
    keep = data["ship"] <= Q1_SHIPDATE_MAX
    return data["values"][keep], data["flag"][keep]


def q6_rows(data: dict):
    """Q6's filter: shipped in 1994, discount 0.06 +- 0.01, quantity < 24
    (on the integer columns, so no float comparison decides a row)."""
    lo, hi = Q6_SHIPDATE
    keep = ((data["ship"] >= lo) & (data["ship"] < hi)
            & (data["disc_cents"] >= 5) & (data["disc_cents"] <= 7)
            & (data["qty"] < 24))
    values = data["values"][keep]
    return values, np.zeros(values.shape[0], np.int32)


def run_one_chip(data: dict, spec, seed: int) -> None:
    values, keys = q1_rows(data)
    emit(phase_groupby("q1", values, keys, Q1_GROUPS, Q1_AGGS, spec,
                       Q1_METHODS, permute_seed=seed + 1,
                       kernels=("pallas",)))
    values, keys = q6_rows(data)
    emit(phase_groupby("q6", values, keys, 1,
                       (("sum_prod", PRICE, DISC),), spec, ("auto", "rsum"),
                       kernels=("rsum",)))
    orders = int(data["order"][-1]) + 1
    emit(phase_groupby("q18", data["values"][:, PRICE:PRICE + 1],
                       data["order"], orders, (("sum", 0),), spec,
                       ("auto", "scatter", "radix")))
    emit(phase_stream(data, spec))
    emit(phase_cpu_parity(data, spec))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        print("chip_smoke: the repro package is not beside this script "
              f"({ROOT / 'src'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from repro.compile_cache import enable_compilation_cache
    from repro.core.types import ReproSpec

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    enable_compilation_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    spec = ReproSpec(dtype=jnp.float32, L=2)

    try:
        data, setup_s, setup_compile_s = timed(lambda: lineitem(args.seed))
        emit({"phase": "setup", "rows": SF1_ROWS, "orders": SF1_ORDERS,
              "seconds": setup_s, "compile_s": setup_compile_s,
              "peak_bytes_in_use": peak_bytes()})
        if args.chips == 4:
            emit(phase_mesh(data, spec, args.chips))
        else:
            run_one_chip(data, spec, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
