"""Traffic kind ``ingest``: writers send tagged NDJSON batches to the
stream service, closed loop: each sends one batch, waits for its
acknowledgement, and sends the next.

The configuration's ``store`` names the store's key column and aggregate
list and the WAL's flush policy; the mix gives ``writers``, ``batch_rows``
and ``pool_batches``.  The pool is that many ``batch_rows``-row slices of
the configuration's ``lineitem``, drawn from the seed; its NDJSON lines are
encoded in set-up, and only the ``(client, seq)`` tag is spliced in during
the window.  Writer ``w`` sends pool batches ``w, w + writers, ...``,
wrapping round, each time under a fresh ``seq``.

Set-up starts the service over a ``StreamStore`` with a write-ahead log and
opens one connection per writer.  It warms a scratch store on every pool
batch first, so no shape of the window compiles there.  A batch's latency
runs from the write of its line to the read of its reply.

The check, after the window: the service's ``query`` against the plain
reference over every acknowledged row; a re-sent last batch of each writer
must come back a duplicate; a store recovered from the WAL alone must
fingerprint as the service's does; and a scratch store fed the same
acknowledged batches in another order, drawn from the seed, must too (the
results may not depend on the order in which batches arrive).
"""
from __future__ import annotations

import asyncio
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks.tpu import data_tpch, reference
from benchmarks.tpu.harness import Window
from benchmarks.tpu.kinds.power import (engine_aggs, group_count,
                                       result_names)


def _line(writer: int, seq: int, body: bytes) -> bytes:
    return b'{"op": "ingest", "client": "w%d", "seq": %d, ' % (writer,
                                                               seq) + body


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, spec):
        self.config, self.mix, self.seed, self.spec = config, mix, seed, spec
        store = config["store"]
        self.aggs = [tuple(a) for a in store["aggs"]]
        self.columns = reference.agg_columns(self.aggs)
        self.group_by = store["group_by"]
        self.groups = group_count(self.group_by, config["scale"])
        self.writers = int(mix["writers"])
        self.batch_rows = int(mix["batch_rows"])
        self.pool = int(mix["pool_batches"])
        self.loop = asyncio.new_event_loop()
        self.tmp = tempfile.mkdtemp(prefix="bench_wal_")
        self.server = self.wal = None
        self.conns: list = []
        self.acked: list = []          # (writer, seq, pool batch)

    # -- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax

        scale = self.config["scale"]
        table = data_tpch.lineitem(self.seed, scale["rows"], scale["orders"],
                                   scale["parts"])
        rng = np.random.default_rng(self.seed)
        slots = scale["rows"] // self.batch_rows
        starts = np.sort(rng.choice(slots, self.pool, replace=False))
        idx = (starts[:, None] * self.batch_rows
               + np.arange(self.batch_rows)).reshape(-1)
        host = jax.device_get({c: table[c][idx]
                               for c in self.columns + [self.group_by]})
        del table
        self.values = np.stack([host[c] for c in self.columns], axis=1)
        self.keys = np.asarray(host[self.group_by], np.int32)
        self.bodies = []
        for b in range(self.pool):
            sl = slice(b * self.batch_rows, (b + 1) * self.batch_rows)
            self.bodies.append(
                b'"values": ' + json.dumps(self.values[sl].tolist()).encode()
                + b', "keys": ' + json.dumps(self.keys[sl].tolist()).encode()
                + b"}\n")
        self._warm()
        self.loop.run_until_complete(self._start())

    def _batch(self, b: int):
        sl = slice(b * self.batch_rows, (b + 1) * self.batch_rows)
        return self.values[sl], self.keys[sl]

    def _store(self, wal=None):
        from repro.stream import StreamStore
        return StreamStore(self.groups, aggs=engine_aggs(self.aggs,
                                                         self.columns),
                           spec=self.spec, wal=wal)

    def _warm(self) -> None:
        """Every pool batch through a scratch store's ``prepare`` (each
        distinct level window compiles here), one coalescing-depth merge
        and finalize, and a few batches through a scratch service."""
        scratch = self._store()
        scratch.warmup(self.batch_rows)
        for b in range(self.pool):
            scratch.prepare(*self._batch(b))

        async def session():
            from repro.stream import serve
            from repro.stream.service import LINE_LIMIT
            server = await serve(scratch, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port,
                                                     limit=LINE_LIMIT)
                for b in range(2 * self.writers):
                    w.write(_line(0, b, self.bodies[b % self.pool]))
                    await w.drain()
                    json.loads(await r.readline())
                w.write(b'{"op": "query"}\n')
                await w.drain()
                json.loads(await r.readline())
                w.close()
                await w.wait_closed()
            finally:
                server.close()
                await server.wait_closed()

        self.loop.run_until_complete(session())

    async def _start(self) -> None:
        from repro.ops.partial import AggSignature
        from repro.stream import WriteAheadLog, serve
        from repro.stream.service import LINE_LIMIT

        self.wal_path = str(Path(self.tmp) / "stream.wal")
        sig = AggSignature.build(engine_aggs(self.aggs, self.columns),
                                 self.groups, self.spec)
        self.wal = WriteAheadLog(self.wal_path, sig=sig,
                                 fsync=self.config["store"]["wal_fsync"])
        self.store = self._store(self.wal)
        self.server = await serve(self.store, "127.0.0.1", 0)
        port = self.server.sockets[0].getsockname()[1]
        for _ in range(self.writers):
            self.conns.append(await asyncio.open_connection(
                "127.0.0.1", port, limit=LINE_LIMIT))
        self.next_seq = [0] * self.writers

    # -- the window ---------------------------------------------------------

    async def _send(self, w: int, b: int):
        reader, writer = self.conns[w]
        seq = self.next_seq[w]
        self.next_seq[w] += 1
        writer.write(_line(w, seq, self.bodies[b]))
        await writer.drain()
        return seq, json.loads(await reader.readline())

    async def _writer(self, w: int, deadline: float, lat: list,
                      errors: list) -> None:
        j = 0
        while time.perf_counter() < deadline:
            b = (w + self.writers * j) % self.pool
            j += 1
            t = time.perf_counter()
            seq, resp = await self._send(w, b)
            t1 = time.perf_counter()
            if resp.get("ok") and not resp.get("duplicate"):
                self.acked.append((w, seq, b))
                lat.append(t1 - t)
                self.last_end = max(self.last_end, t1)
            else:
                errors.append(resp)

    def window(self, seconds: float, traced: bool) -> Window:
        lat: list = []
        errors: list = []
        t0 = time.perf_counter()
        self.last_end = t0

        async def run():
            await asyncio.gather(*(self._writer(w, t0 + seconds, lat, errors)
                                   for w in range(self.writers)))

        self.loop.run_until_complete(run())
        n = len(self.acked)
        elapsed = self.last_end - t0
        metrics = {}
        if n:
            metrics = {
                "ingest_rows_per_s": n * self.batch_rows / elapsed,
                "ack_p95_ms": float(np.percentile(lat, 95)) * 1e3}
        return Window(attempted=n + len(errors), failed=len(errors),
                      metrics=metrics,
                      work={"batches": n, "rows": n * self.batch_rows},
                      error=json.dumps(errors[0]) if errors else None)

    # -- the check ----------------------------------------------------------

    async def _ask(self, op: str) -> dict:
        reader, writer = self.conns[0]
        writer.write(json.dumps({"op": op}).encode() + b"\n")
        await writer.drain()
        return json.loads(await reader.readline())

    async def _after(self) -> dict:
        results = (await self._ask("query"))["results"]
        fps = (await self._ask("fingerprints"))["fingerprints"]
        last = {}
        for w, seq, b in self.acked:
            last[w] = (seq, b)
        not_dup = 0
        for w, (seq, b) in sorted(last.items()):
            reader, writer = self.conns[w]
            writer.write(_line(w, seq, self.bodies[b]))
            await writer.drain()
            resp = json.loads(await reader.readline())
            not_dup += not (resp.get("ok") and resp.get("duplicate"))
        await self._stop()
        return {"results": results, "fingerprints": fps,
                "resends_not_duplicate": not_dup}

    async def _stop(self) -> None:
        for _, writer in self.conns:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass
        self.conns = []
        if self.server is not None:
            self.server.close()
            await self.server.wait_closed()
            self.server = None

    def _reordered(self, mult) -> dict:
        """Fingerprints of a scratch store fed every acknowledged batch,
        ``mult[b]`` times pool batch ``b``, in an order drawn from the
        seed."""
        scratch = self._store()
        states = [scratch.prepare(*self._batch(b)) for b in range(self.pool)]
        order = np.random.default_rng([self.seed, 1]).permutation(
            np.repeat(np.arange(self.pool), mult))
        for b in order:
            scratch.commit(states[b], self.batch_rows)
        return scratch.fingerprints()

    def check(self) -> dict:
        from repro.stream import StreamStore

        after = self.loop.run_until_complete(self._after())
        self.wal.close()
        recovered = StreamStore.recover(self.wal_path)
        try:
            same = recovered.fingerprints() == after["fingerprints"]
        finally:
            recovered.wal.close()
        self.store = recovered = None
        mult = np.bincount([b for _, _, b in self.acked],
                           minlength=self.pool)
        reordered = self._reordered(mult)
        weights = np.repeat(mult, self.batch_rows)
        ref = reference.GroupReference(
            {c: self.values[:, i] for i, c in enumerate(self.columns)},
            self.keys, self.groups, weights=weights)
        res = after["results"]
        got = [res[k] for k in result_names(engine_aggs(self.aggs,
                                                        self.columns))]
        s = self.config["spec"]
        readings = ref.compare(got, self.aggs, s["m"], s["L"], s["W"])
        readings["resends_not_duplicate"] = after["resends_not_duplicate"]
        readings["recovered_mismatch"] = int(not same)
        readings["reordered_not_bit_identical"] = sum(
            reordered[k] != v for k, v in after["fingerprints"].items())
        return readings

    def close(self) -> None:
        if self.conns or self.server is not None:
            self.loop.run_until_complete(self._stop())
        if self.wal is not None:
            self.wal.close()
        self.loop.close()
        shutil.rmtree(self.tmp, ignore_errors=True)
