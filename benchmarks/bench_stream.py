"""Streaming aggregation benchmark: TTFR and sustained ingest throughput.

DAT300-style serving harness for the stream engine (ROADMAP: streaming /
incremental aggregation).  Parts:

* **TTFR** — first delta in -> first finalized result out, in-process
  (cold / warm / restored-from-snapshot) and in *fresh subprocesses* with
  the two cold-start mitigations toggled: ``StreamStore.warmup`` and the
  persistent XLA compilation cache (:mod:`repro.compile_cache`).  The
  subprocesses run before this process touches a device, because a chip
  belongs to one process at a time;
* **sustained** — direct in-process ingest, then concurrent writers
  through the asyncio NDJSON service (prepare on a thread pool outside the
  locks, commit serialized per store), over one store, over a
  :class:`ShardedStreamStore` and over a store restored from a snapshot.
  Each path is gated on its own bits.

* **durability** — WAL-on vs WAL-off sustained ingest (gated: fsync'd
  logging within :data:`MAX_WAL_OVERHEAD` of WAL-off), recovery time from
  the bare log, and bit-verified failover timing
  (detect -> promote -> first verified query) on a
  :class:`~repro.stream.ReplicatedStore`.

``cross_check`` runs FIRST: the streamed state (1, 7 and 64 permuted
micro-batches, a snapshot/restart mid-stream, the service with concurrent
writers, and the sharded store under both policies) must fingerprint
bit-identically to the one-shot ``groupby_agg`` before any number is
recorded — a benchmark of a non-reproducible stream would be measuring
the wrong engine.  Each sustained configuration is *additionally* gated
on its own fingerprints after the timed run.  Results land in
BENCH_stream.json at the repo root.
"""
from __future__ import annotations

import asyncio
import json
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks._util import timeit  # noqa: F401  (kept for parity/imports)
from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compilation_cache
from repro.obs import fingerprint as obs_fp
from repro.ops import groupby_agg
from repro.stream import (ReplicatedStore, ShardedStreamStore, StreamStore,
                          WriteAheadLog, serve)
from repro.stream.service import LINE_LIMIT

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_stream.json")

#: the cold-start probes' own compilation cache, emptied before they run so
#: that the first probe populates it and the next ones hit
PROBE_CACHE_DIR = CHECKOUT_CACHE_DIR.with_name(".jax_cache_ttfr_probe")

G = 129
AGGS = ("sum", "count", "mean", "var", "min", "max", ("sum", 1))


def _dataset(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    mag = 10.0 ** rng.uniform(-20.0, 15.0, size=n)
    vals = np.stack([rng.standard_normal(n) * mag,
                     rng.standard_normal(n)], 1).astype(np.float32)
    keys = rng.integers(0, G, size=n).astype(np.int32)
    return vals, keys


def _want(v, k) -> dict:
    ref, tab = groupby_agg(v, k, G, aggs=AGGS, return_table=True)
    return {"stream/table": obs_fp.fingerprint_table(tab),
            "stream/results": obs_fp.fingerprint_results(ref)}


# ---------------------------------------------------------------------------
# step 1: the bitwise gate
# ---------------------------------------------------------------------------

def _service_fingerprints(store, v, k, writers: int, batch: int) -> dict:
    """Drive every row through an in-process service with ``writers``
    concurrent tasks; return the store fingerprints."""
    from repro.stream import StreamService

    async def run():
        service = StreamService(store, max_workers=writers)
        spans = np.array_split(np.arange(v.shape[0]), writers)

        async def writer(rows):
            for lo in range(0, len(rows), batch):
                sel = rows[lo:lo + batch]
                await service.ingest(v[sel], k[sel])

        await asyncio.gather(*(writer(s) for s in spans))
        fps = await service.fingerprints()
        service.close()
        return fps

    return asyncio.run(run())


def cross_check(n: int = 20001) -> str:
    """Streamed == one-shot, bit for bit, before anything is timed."""
    v, k = _dataset(n)
    want = _want(v, k)
    rng = np.random.default_rng(1)
    for nb in (1, 7, 64):
        store = StreamStore(G, aggs=AGGS)
        idx = np.array_split(np.arange(n), nb)
        for b in rng.permutation(nb):
            store.ingest(v[idx[b]], k[idx[b]])
        got = store.fingerprints()
        assert got == want, \
            f"stream({nb} batches) != one-shot: {got} vs {want}"
    with tempfile.TemporaryDirectory() as d:
        store = StreamStore(G, aggs=AGGS)
        idx = np.array_split(np.arange(n), 7)
        for b in range(3):
            store.ingest(v[idx[b]], k[idx[b]])
        store.snapshot(d)
        store = StreamStore.restore(d)
        for b in range(3, 7):
            store.ingest(v[idx[b]], k[idx[b]])
        got = store.fingerprints()
        assert got == want, \
            f"stream(restart) != one-shot: {got} vs {want}"
    # the service: concurrent prepares, scrambled commit order
    got = _service_fingerprints(StreamStore(G, aggs=AGGS), v, k, writers=4,
                                batch=1024)
    assert got == want, f"service != one-shot: {got} vs {want}"
    # sharded stores, both assignment policies
    for shards, policy in ((2, "round_robin"), (4, "key_hash")):
        store = ShardedStreamStore(G, aggs=AGGS, num_shards=shards,
                                   policy=policy)
        idx = np.array_split(np.arange(n), 16)
        for b in rng.permutation(16):
            store.ingest(v[idx[b]], k[idx[b]])
        got = store.fingerprints()
        assert got == want, (f"sharded({shards},{policy}) != one-shot: "
                             f"{got} vs {want}")
    print("bitwise cross-check OK (1/7/64 permuted batches, restart, "
          "service, sharded x2 policies)")
    return "ok"


# ---------------------------------------------------------------------------
# TTFR: first delta in -> first finalized result out
# ---------------------------------------------------------------------------

def _ttfr_once(v, k, batch: int, restore_from: str | None = None) -> float:
    if restore_from is not None:
        store = StreamStore.restore(restore_from)
    else:
        store = StreamStore(G, aggs=AGGS)
    t0 = time.perf_counter()
    store.ingest(v[:batch], k[:batch])
    store.query()
    return time.perf_counter() - t0


def _ttfr_probe(batch: int, warmup: bool) -> dict:
    """Child-process body for the fresh-process TTFR probes (the parent
    process has warm XLA caches, so true cold numbers need a subprocess)."""
    v, k = _dataset(2 * batch, seed=3)
    store = StreamStore(G, aggs=AGGS)
    out = {"warmup_s": store.warmup(batch) if warmup else 0.0}
    t0 = time.perf_counter()
    store.ingest(v[:batch], k[:batch])
    store.query()
    out["ttfr_s"] = time.perf_counter() - t0
    return out


def _spawn_ttfr_probe(batch: int, warmup: bool, cache: bool) -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    if cache:
        env["JAX_COMPILATION_CACHE_DIR"] = str(PROBE_CACHE_DIR)
    else:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    argv = [sys.executable, os.path.abspath(__file__),
            "--ttfr-probe", str(batch)] + (["--warmup"] if warmup else [])
    proc = subprocess.run(argv, env=env, capture_output=True, text=True,
                          timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"ttfr probe failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _ttfr_batch(quick: bool) -> int:
    return 2048 if quick else 16384


def run_fresh_probes(quick: bool = True) -> dict:
    """Fresh-process TTFR probes: the cold-start mitigations, measured where
    cold actually happens.  Each child needs the device to itself, so this
    runs before the calling process touches one.  The compilation-cache
    probe runs twice in the same emptied cache dir — the first populates,
    the second is the steady state an operator sees."""
    batch = _ttfr_batch(quick)
    probes = {}
    probes["fresh"] = _spawn_ttfr_probe(batch, warmup=False, cache=False)
    probes["fresh_warmup"] = _spawn_ttfr_probe(batch, warmup=True,
                                               cache=False)
    shutil.rmtree(PROBE_CACHE_DIR, ignore_errors=True)
    _spawn_ttfr_probe(batch, warmup=True, cache=True)          # populate
    probes["fresh_warmup_cache"] = _spawn_ttfr_probe(batch, warmup=True,
                                                     cache=True)
    probes["fresh_cache"] = _spawn_ttfr_probe(batch, warmup=False,
                                              cache=True)
    return probes


def run_ttfr(probes: dict, quick: bool = True) -> dict:
    """In-process TTFR (cold / warm / persistent) beside the fresh-process
    ``probes`` that :func:`run_fresh_probes` took earlier."""
    batch = _ttfr_batch(quick)
    v, k = _dataset(4 * batch, seed=3)
    out = {"batch_rows": batch}
    # cold: the first streamed batch this process ever aggregates at this
    # shape — XLA compile and planner warmup are billed to it
    out["cold_ttfr_s"] = _ttfr_once(v, k, batch)
    out["warm_ttfr_s"] = min(_ttfr_once(v, k, batch) for _ in range(5))
    with tempfile.TemporaryDirectory() as d:
        seed_store = StreamStore(G, aggs=AGGS)
        seed_store.ingest(v[batch:], k[batch:])
        seed_store.snapshot(d)
        # persistent: restore (verified) + first delta + first query
        out["persistent_ttfr_s"] = min(
            _ttfr_once(v, k, batch, restore_from=d) for _ in range(3))
    print(f"\n== TTFR (batch={batch} rows) ==")
    for m in ("cold", "warm", "persistent"):
        print(f"  {m:10} {out[f'{m}_ttfr_s'] * 1e3:9.1f} ms")
    out["fresh_process"] = probes
    print(f"  -- fresh subprocesses (cold-start mitigations) --")
    for name, p in probes.items():
        extra = (f" (+{p['warmup_s'] * 1e3:.0f} ms warmup)"
                 if p["warmup_s"] else "")
        print(f"  {name:20} TTFR {p['ttfr_s'] * 1e3:9.1f} ms{extra}")
    return out


# ---------------------------------------------------------------------------
# sustained ingest: concurrent writers through the asyncio service
# ---------------------------------------------------------------------------

def _run_service_ingest(store, v, k, writers: int, batch: int,
                        **service_kwargs) -> float:
    """Stream every row through the NDJSON service with ``writers``
    concurrent connections; returns elapsed seconds."""

    async def run():
        server = await serve(store, port=0, **service_kwargs)
        port = server.sockets[0].getsockname()[1]
        spans = np.array_split(np.arange(v.shape[0]), writers)

        async def writer(rows):
            r, w = await asyncio.open_connection("127.0.0.1", port,
                                                 limit=LINE_LIMIT)
            for lo in range(0, len(rows), batch):
                sel = rows[lo:lo + batch]
                req = {"op": "ingest", "values": v[sel].tolist(),
                       "keys": k[sel].tolist()}
                w.write(json.dumps(req).encode() + b"\n")
                await w.drain()
                resp = json.loads(await r.readline())
                assert resp["ok"], resp
            w.close()
            await w.wait_closed()

        t0 = time.perf_counter()
        await asyncio.gather(*(writer(s) for s in spans))
        dt = time.perf_counter() - t0
        server.close()
        await server.wait_closed()
        return dt

    return asyncio.run(run())


def run_sustained(quick: bool = True, writers: int = 4) -> dict:
    n = 2**17 if quick else 2**21
    batch = 2048 if quick else 8192
    v, k = _dataset(n, seed=5)
    want = _want(v, k)
    out = {"rows": n, "batch_rows": batch, "writers": writers}

    def gate(store, label) -> str:
        got = store.fingerprints()
        assert got == want, f"{label} != one-shot: {got} vs {want}"
        return "ok"

    # direct in-process ingest (engine cost, no protocol)
    store = StreamStore(G, aggs=AGGS)
    t0 = time.perf_counter()
    for lo in range(0, n, batch):
        store.ingest(v[lo:lo + batch], k[lo:lo + batch])
    store.query()
    out["direct_rows_per_s"] = n / (time.perf_counter() - t0)
    gate(store, "direct")

    # the service configurations, same rows, same writers, same machine,
    # one run.  Each is gated on its own bits.
    # one store: prepare on the pool, one commit lock
    store = StreamStore(G, aggs=AGGS)
    dt = _run_service_ingest(store, v, k, writers, batch)
    out["service_store_rows_per_s"] = n / dt
    out["service_store_cross_check"] = gate(store, "service")

    # sharded: per-shard commit locks
    store = ShardedStreamStore(G, aggs=AGGS, num_shards=4,
                               policy="round_robin")
    dt = _run_service_ingest(store, v, k, writers, batch)
    out["service_sharded_rows_per_s"] = n / dt
    out["service_sharded_cross_check"] = gate(store, "sharded service")

    # persistent: writers stream into a store restored from a snapshot
    with tempfile.TemporaryDirectory() as d:
        seed_store = StreamStore(G, aggs=AGGS)
        seed_store.ingest(v, k)
        seed_store.snapshot(d)
        restored = StreamStore.restore(d)
        dt = _run_service_ingest(restored, v, k, writers, batch)
        out["service_persistent_rows_per_s"] = n / dt
        restored.query()

    out["peak_rss_mb"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1024.0

    print(f"\n== sustained ingest (n={n}, batch={batch}, "
          f"{writers} writers) ==")
    print(f"  direct              {out['direct_rows_per_s']:12,.0f} rows/s")
    for m in ("store", "sharded", "persistent"):
        key = f"service_{m}_rows_per_s"
        check = out.get(f"service_{m}_cross_check", "-")
        print(f"  service {m:11} {out[key]:12,.0f} rows/s  "
              f"[cross-check {check}]")
    print(f"  peak RSS {out['peak_rss_mb']:.0f} MB")
    return out


# ---------------------------------------------------------------------------
# durability: WAL overhead and bit-verified failover time
# ---------------------------------------------------------------------------

#: acceptance gate (ISSUE 10): fsync'd write-ahead logging may cost at most
#: this factor of sustained direct-ingest throughput
MAX_WAL_OVERHEAD = 1.5


def run_durability(quick: bool = True) -> dict:
    """WAL-on vs WAL-off sustained ingest, recovery, and failover timing.

    Every timed configuration is gated on bits: the WAL-on store, the
    store recovered from its log, and the promoted post-failover replica
    must all fingerprint identically to the WAL-off run.
    """
    n = 2**17 if quick else 2**20
    batch = 2048 if quick else 8192
    v, k = _dataset(n, seed=7)
    want = _want(v, k)
    out = {"rows": n, "batch_rows": batch}

    def timed_ingest(store) -> float:
        t0 = time.perf_counter()
        for lo in range(0, n, batch):
            store.ingest(v[lo:lo + batch], k[lo:lo + batch])
        store.query()
        return n / (time.perf_counter() - t0)

    # warm the compile caches so the WAL-off baseline isn't billed for XLA
    warm = StreamStore(G, aggs=AGGS)
    warm.ingest(v[:batch], k[:batch])
    warm.query()

    out["wal_off_rows_per_s"] = timed_ingest(StreamStore(G, aggs=AGGS))

    with tempfile.TemporaryDirectory() as d:
        for policy in ("always", "never"):
            path = os.path.join(d, f"bench-{policy}.wal")
            probe = StreamStore(G, aggs=AGGS)
            wal = WriteAheadLog(path, sig=probe.sig, fsync=policy)
            store = StreamStore(G, aggs=AGGS, wal=wal)
            out[f"wal_{policy}_rows_per_s"] = timed_ingest(store)
            assert store.fingerprints() == want, f"wal({policy}) != one-shot"
            wal.close()
            if policy == "always":
                # recovery gate + timing: rebuild from the log alone
                t0 = time.perf_counter()
                rec = StreamStore.recover(path)
                out["recover_s"] = time.perf_counter() - t0
                assert rec.fingerprints() == want, "recovered != one-shot"
                rec.wal.close()

        out["wal_overhead_x"] = (out["wal_off_rows_per_s"] /
                                 out["wal_always_rows_per_s"])

        # failover: half the rows in, snapshot + replicate, primary dies,
        # bit-verified promotion, remaining rows land on the new primary
        rep = ReplicatedStore(G, aggs=AGGS,
                              wal_path=os.path.join(d, "rep.wal"),
                              snapshot_dir=os.path.join(d, "snaps"))
        half = n // 2
        tail = half - 4 * batch        # batches the follower hasn't seen
        for lo in range(0, tail, batch):
            rep.ingest(v[lo:lo + batch], k[lo:lo + batch])
        rep.snapshot()
        rep.replicate()
        for lo in range(tail, half, batch):
            rep.ingest(v[lo:lo + batch], k[lo:lo + batch])
        rep.crash_primary()
        report = rep.promote()
        out["failover"] = report["seconds"]
        out["failover"]["caught_up_records"] = report["caught_up_records"]
        for lo in range(half, n, batch):
            rep.ingest(v[lo:lo + batch], k[lo:lo + batch])
        assert rep.fingerprints() == want, "post-failover != one-shot"
        rep.primary.wal.close()

    print(f"\n== durability (n={n}, batch={batch}) ==")
    print(f"  WAL off              {out['wal_off_rows_per_s']:12,.0f} rows/s")
    print(f"  WAL fsync=always     "
          f"{out['wal_always_rows_per_s']:12,.0f} rows/s")
    print(f"  WAL fsync=never      "
          f"{out['wal_never_rows_per_s']:12,.0f} rows/s")
    print(f"  overhead (always):   {out['wal_overhead_x']:.2f}x  "
          f"[gate {MAX_WAL_OVERHEAD}x]")
    print(f"  recover from log:    {out['recover_s'] * 1e3:9.1f} ms")
    fo = out["failover"]
    print(f"  failover: detect->promoted {fo['detect_to_promoted'] * 1e3:.1f}"
          f" ms (promote {fo['promote'] * 1e3:.1f} ms, first verified query "
          f"{fo['first_query'] * 1e3:.1f} ms, "
          f"{fo['caught_up_records']} records caught up)")
    assert out["wal_overhead_x"] <= MAX_WAL_OVERHEAD, (
        f"WAL-on ingest is {out['wal_overhead_x']:.2f}x slower than "
        f"WAL-off (gate: {MAX_WAL_OVERHEAD}x)")
    return out


def emit_bench_json(quick: bool = True):
    # the probes' children need the device to themselves: spawn them while
    # this process is still off it; their numbers are recorded only once
    # the gate below has passed
    probes = run_fresh_probes(quick=quick)
    check = cross_check()                  # the gate: fail before recording
    ttfr = run_ttfr(probes, quick=quick)
    sustained = run_sustained(quick=quick)
    durability = run_durability(quick=quick)
    payload = {"cross_check": check, "G": G,
               "aggs": [a if isinstance(a, str) else list(a) for a in AGGS],
               "ttfr": ttfr, "sustained": sustained,
               "durability": durability}
    with open(BENCH_JSON, "w") as fh:
        json.dump(payload, fh, indent=1)
    print("wrote", os.path.abspath(BENCH_JSON))
    return payload


if __name__ == "__main__":
    if "--ttfr-probe" in sys.argv:
        enable_compilation_cache()
        i = sys.argv.index("--ttfr-probe")
        probe = _ttfr_probe(int(sys.argv[i + 1]),
                            warmup="--warmup" in sys.argv)
        print(json.dumps(probe))
        raise SystemExit(0)
    try:
        emit_bench_json(quick="--quick" in sys.argv)
    except AssertionError as e:
        print(f"FAIL: {e}")
        raise SystemExit(1)
