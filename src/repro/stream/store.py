"""Persistent streaming aggregation store: ingest micro-batches, query
anytime, snapshot/restore bit-exactly.

The store is the thinnest possible client of the partial/merge/finalize
algebra (:mod:`repro.ops.partial`, DESIGN.md §14): it holds one
:class:`PartialState` plus a small coalescing buffer of not-yet-merged
batch partials.  Every invariant the stream needs is inherited, not
re-proved:

* **micro-batch-size invariance** — ``merge(partial(A), partial(B)) ==
  partial(A ++ B)`` bit for bit, so splitting the rows into 1, 7 or 64
  deltas leaves the queryable state unchanged;
* **ingest-order invariance** — the merge is commutative, so permuting
  the deltas leaves it unchanged too;
* **restart invariance** — the state is a plain pytree of integer tables
  and exact MIN/MAX floats; a snapshot stores its bytes, restore verifies
  them against the manifest's byte-layout fingerprint
  (:func:`repro.checkpoint.ckpt.verify_value`), and merging is a function
  of those bytes only — so *snapshot + restart + remaining deltas* equals
  the uninterrupted run bit for bit.

Coalescing (``coalesce="auto"``): a store merge prices a full
``(G, ncols, L_eff)`` demote + integer add + renorm regardless of the
delta's size, so a trickle of tiny deltas into a big table should buffer
several partials per merge.  :func:`repro.ops.plan.plan_partial` picks the
buffer depth so merge overhead stays a bounded fraction of aggregation
work; since buffered partials are merged with the same exact ``merge_all``,
the knob moves throughput only — never bits.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

from repro.checkpoint import ckpt
from repro.core.types import ReproSpec
from repro.obs import fingerprint as obs_fp
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.ops.partial import (AggSignature, PartialState, empty_partial,
                               finalize, merge_all_jit, partial_agg,
                               state_nbytes)
from repro.ops.plan import PartialPlan, plan_partial
from repro.runtime import faultinject
from repro.stream.wal import (DedupIndex, WalUnavailable, WriteAheadLog,
                              pack_parts, unpack_parts)

__all__ = ["StreamStore"]


def _delivery_meta(client, seq) -> Optional[dict]:
    if client is None or seq is None:
        return None
    return {"client": str(client), "cseq": int(seq)}


class _DurableMixin:
    """WAL logging + exactly-once delivery shared by the flat and sharded
    stores (DESIGN.md §16).  The owning class provides ``sig``,
    ``num_shards`` and the ``_commit_part`` shard interface; this mixin
    provides the write-ahead step, the read-only degradation latch and
    the replay application helper."""

    _wal_kind = "stream"

    def _wal_params(self) -> dict:
        return {}

    def _init_durability(self, wal) -> None:
        if wal is not None and not isinstance(wal, WriteAheadLog):
            wal = WriteAheadLog(wal, sig=self.sig, kind=self._wal_kind,
                                params=self._wal_params())
        if wal is not None:
            if wal.sig != self.sig:
                raise ValueError("WAL belongs to a different store "
                                 "signature")
            if wal.last_seq > 0:
                raise ValueError(
                    f"WAL {wal.path} already holds {wal.last_seq} records; "
                    "rebuild the store with recover() instead of attaching "
                    "a non-empty log to a fresh one")
        self._wal: Optional[WriteAheadLog] = wal
        self.wal_seq = 0 if wal is None else wal.last_seq
        self.dedup = DedupIndex()
        self.read_only = False

    @property
    def wal(self) -> Optional[WriteAheadLog]:
        return self._wal

    def _check_writable(self) -> None:
        if self.read_only:
            raise WalUnavailable(
                "store is serving read-only: its WAL became unavailable "
                "and unlogged ingest would be lost on the next crash")

    def _log_record(self, arrays, kind: str, rec_meta: dict,
                    meta: Optional[dict]) -> bool:
        """The write-ahead step: reserve the delivery tag (False — a
        duplicate, don't log or apply anything), then append one record if
        there is anything to log and a WAL is attached.  Must run before
        the batch is applied.  On storage failure the store latches
        read-only and raises :class:`WalUnavailable` — the batch was
        neither logged nor applied (the failed tag reservation is moot:
        every later ingest is refused, and recovery rebuilds the index
        from the log, which does not hold the failed record)."""
        self._check_writable()
        if meta is not None and \
                not self.dedup.reserve(meta["client"], meta["cseq"]):
            return False
        if self._wal is not None and arrays:
            try:
                self.wal_seq = self._wal.append(arrays, kind=kind,
                                                meta=rec_meta)
            except (WalUnavailable, OSError) as e:
                self.read_only = True
                obs_metrics.counter("stream_wal_degraded_total").inc()
                obs_trace.event("stream.wal_degraded", error=str(e))
                if isinstance(e, WalUnavailable):
                    raise
                raise WalUnavailable(str(e)) from e
        return True

    def _log_parts(self, parts, meta: Optional[dict] = None) -> bool:
        """One ``"parts"`` record covering *every* prepared part of a batch
        (atomic in the log, however many shards the batch split into).
        False when the delivery tag turned out to be a duplicate."""
        states = [s for _, s, _ in parts if s is not None]
        rec_meta = dict(meta or {})
        rec_meta["shards"] = [int(i) for i, s, _ in parts if s is not None]
        return self._log_record(pack_parts(states) if states else {},
                                "parts", rec_meta, meta)

    def _apply_record(self, rec) -> None:
        """Replay one WAL record into the store, without re-logging it."""
        if rec.kind != "parts":
            raise ValueError(f"cannot replay record kind {rec.kind!r} "
                             "into a stream store")
        shards = rec.meta.get("shards") or [0]
        parts = unpack_parts(rec.arrays, self.sig)
        for orig_idx, st in zip(shards, parts):
            self._commit_part(int(orig_idx) % self.num_shards, st,
                              int(np.asarray(st.rows)))

    def _replay(self, wal: WriteAheadLog, from_seq: int) -> int:
        """Apply every record with ``seq > from_seq``; absorb *every*
        record's delivery tag (duplicate suppression must cover retries of
        batches that are already inside the snapshot).  Replay never
        appends, so running it twice is idempotent by the seq cut."""
        applied = 0
        with obs_trace.span("stream.wal_replay", from_seq=from_seq):
            for rec in wal.records():
                self.dedup.absorb_meta(rec.meta)
                if rec.seq > from_seq:
                    self._apply_record(rec)
                    applied += 1
        obs_metrics.counter("stream_wal_replayed_records_total").inc(applied)
        return applied

    def _attach_wal(self, wal: WriteAheadLog) -> None:
        self._wal = wal
        self.wal_seq = wal.last_seq


def _state_tree(state: PartialState) -> dict:
    """The state as the plain-dict pytree checkpoints understand (the
    :class:`PartialState` pytree registration is for jax transforms;
    ``ckpt._flatten`` walks dict/list/tuple only)."""
    return {"table": {"k": state.table.k, "C": state.table.C,
                      "e1": state.table.e1},
            "minv": state.minv, "maxv": state.maxv, "rows": state.rows}


def _tree_state(tree: dict, sig: AggSignature) -> PartialState:
    from repro.core.accumulator import ReproAcc
    t = tree["table"]
    return PartialState(table=ReproAcc(k=t["k"], C=t["C"], e1=t["e1"]),
                        minv=tree["minv"], maxv=tree["maxv"],
                        rows=tree["rows"], sig=sig)


class StreamStore(_DurableMixin):
    """Incrementally aggregated GROUPBY state over an unbounded row stream.

    Args:
      num_segments / aggs / spec / method / levels / check_finite: as in
        :func:`repro.ops.groupby_agg`; fixed for the store's lifetime and
        recorded in its :class:`AggSignature` (states with equal signatures
        merge; snapshot manifests carry the signature so a restore rebuilds
        an identical store).
      coalesce: micro-batches to buffer per store merge.  ``"auto"``
        (default) lets :func:`plan_partial` pick from the first batch's
        size; an int pins it.  Throughput knob only — any value yields
        bit-identical query results.
      wal: a :class:`~repro.stream.wal.WriteAheadLog` (or a path to
        create/open one) that every ingested delta is appended to *before*
        it is applied.  With a WAL, ``recover(wal, snapshot_dir)`` rebuilds
        the store bit-exactly from (snapshot + replayed deltas) after a
        crash, and client-tagged deliveries (``ingest(..., client=...,
        seq=...)``) commit exactly once across crashes.  An attached log
        must be empty — a non-empty one means there is durable state to
        rebuild first, which is :meth:`recover`'s job.
    """

    def __init__(self, num_segments: int, aggs=("sum",),
                 spec: Optional[ReproSpec] = None, method: str = "auto",
                 levels="auto", check_finite: bool = False,
                 coalesce="auto", wal=None):
        self.sig = AggSignature.build(aggs, num_segments, spec)
        self.method = method
        self.levels = tuple(levels) if isinstance(levels, list) else levels
        self.check_finite = check_finite
        self._coalesce = coalesce
        self._state = empty_partial(num_segments, self.sig.aggs,
                                    self.sig.spec)
        self._pending: list[PartialState] = []
        self._plan = None
        self.batches = 0
        self.merged_batches = 0
        self._t_first_ingest: Optional[float] = None
        self._t_first_result: Optional[float] = None
        self._init_durability(wal)

    # -- ingest ------------------------------------------------------------

    def _ensure_plan(self, n: int) -> PartialPlan:
        if self._plan is None:
            self._plan = plan_partial(
                max(n, 1), self.sig.num_segments, self.sig.spec,
                ncols=max(self.sig.ncols, 1), method=self.method)
        return self._plan

    def _coalesce_target(self, n: int) -> int:
        if self._coalesce != "auto":
            return max(int(self._coalesce), 1)
        return self._ensure_plan(n).coalesce

    def pipeline_width(self, n: int) -> int:
        """Concurrent ``prepare`` workers worth running for ``n``-row
        batches (the planner's Amdahl bound; see ``PartialPlan.pipeline``)."""
        return self._ensure_plan(n).pipeline

    def prepare(self, values, keys) -> Optional[PartialState]:
        """Stage 1 of ingest: aggregate one micro-batch into a mergeable
        :class:`PartialState` — **pure**, touches no store state, safe to
        run on any number of threads concurrently.  Returns ``None`` for an
        empty batch (the merge identity)."""
        v = np.asarray(values)
        n = int(v.shape[0]) if v.ndim else 0
        if not n:
            return None
        t0 = time.perf_counter()
        with obs_trace.span("stream.prepare", rows=n):
            st = partial_agg(values, keys, self.sig.num_segments,
                             aggs=self.sig.aggs, spec=self.sig.spec,
                             method=self.method, levels=self.levels,
                             check_finite=self.check_finite)
        obs_metrics.histogram("stream_prepare_seconds").observe(
            time.perf_counter() - t0)
        return st

    def commit(self, state: Optional[PartialState], rows: int) -> dict:
        """Stage 2 of ingest: append a prepared partial to the coalescing
        buffer and flush when the planner's depth is reached.  This is the
        only stage that mutates the store — callers running ``prepare``
        concurrently must serialize ``commit`` (the service's per-store
        lock).  The serialization order is irrelevant to the result bits:
        the merge is commutative and associative, so the lock picks an
        order and the algebra erases it."""
        self._check_writable()
        faultinject.fire("store.commit")
        t0 = time.perf_counter()
        n = int(rows)
        with obs_trace.span("stream.commit", rows=n) as sp:
            if state is not None:
                self._pending.append(state)
                if len(self._pending) >= self._coalesce_target(n):
                    self.flush()
            self.batches += 1
            if self._t_first_ingest is None:
                self._t_first_ingest = t0
            sp.set(pending=len(self._pending))
        obs_metrics.counter("stream_batches_total").inc()
        obs_metrics.counter("stream_rows_total").inc(n)
        obs_metrics.histogram("stream_commit_seconds").observe(
            time.perf_counter() - t0)
        obs_metrics.gauge("stream_pending_partials").set(len(self._pending))
        return {"rows": n, "batches": self.batches,
                "pending": len(self._pending),
                "merged": self.merged_batches}

    def ingest(self, values, keys, client=None, seq=None) -> dict:
        """Aggregate one micro-batch (delta table) into the store.

        ``commit(prepare(values, keys))`` — the serial composition of the
        two pipeline stages, with the write-ahead log step between them
        when a WAL is attached.  Returns ingest stats ``{rows, batches,
        pending, merged}``.  Empty deltas are accepted and ignored (a
        zero-row batch is the merge identity).  Any sequence of ``ingest``
        calls that delivers the same multiset of rows leaves the store in
        the bit-identical state.

        ``client``/``seq`` tag the delivery for exactly-once commit: a
        batch redelivered with a tag the store has seen (in memory, or in
        a replayed WAL record after a crash) is acknowledged as
        ``{"duplicate": True}`` without touching the state.
        """
        meta = _delivery_meta(client, seq)
        if meta is not None and self.dedup.seen(meta["client"],
                                                meta["cseq"]):
            obs_metrics.counter("stream_duplicate_deliveries_total").inc()
            return {"rows": 0, "duplicate": True, "batches": self.batches,
                    "pending": len(self._pending),
                    "merged": self.merged_batches}
        with obs_trace.span("stream.ingest"):
            st = self.prepare(values, keys)
            n = int(np.asarray(values).shape[0]) if st is not None else 0
            if not self._log_parts([(0, st, n)], meta):
                obs_metrics.counter(
                    "stream_duplicate_deliveries_total").inc()
                return {"rows": 0, "duplicate": True,
                        "batches": self.batches,
                        "pending": len(self._pending),
                        "merged": self.merged_batches}
            return self.commit(st, n)

    # Uniform shard interface (the service drives stores through these,
    # so a plain store is the one-shard case of ShardedStreamStore).

    num_shards = 1

    def _prepare_parts(self, values, keys):
        """``[(shard_index, prepared_state_or_None, rows)]`` — pure."""
        v = np.asarray(values)
        n = int(v.shape[0]) if v.ndim else 0
        return [(0, self.prepare(values, keys), n)]

    def _commit_part(self, idx: int, state: Optional[PartialState],
                     rows: int) -> dict:
        assert idx == 0
        return self.commit(state, rows)

    def flush(self) -> None:
        """Merge every buffered partial into the persistent state."""
        if not self._pending:
            return
        t0 = time.perf_counter()
        with obs_trace.span("stream.merge", pending=len(self._pending)):
            states = [self._state] + self._pending
            self._state = merge_all_jit(states)
        self.merged_batches += len(self._pending)
        self._pending = []
        obs_metrics.histogram("stream_merge_seconds").observe(
            time.perf_counter() - t0)

    @property
    def pending_bytes(self) -> int:
        """Host bytes held by not-yet-merged partials.  Bounded by design:
        the coalescing buffer flushes at the planner's depth, so the
        unbounded-burst risk lives in the *service's* in-flight queue —
        which is what its backpressure budget meters (DESIGN.md §15.3)."""
        return sum(state_nbytes(s) for s in self._pending)

    def warmup(self, batch_rows: int) -> float:
        """Pre-trace the ingest path for ``batch_rows``-sized batches;
        returns seconds spent.

        Runs ``prepare`` on a synthetic full-magnitude-spread batch (so the
        prescan proves the widest level window), one coalescing-depth merge
        and one ``finalize`` — all into throwaways, so the store's state,
        counters and fingerprints are untouched.  Where the process has
        turned on the persistent compilation cache (see
        :mod:`repro.compile_cache`), the XLA executables persist, and a
        *fresh process* skips compilation too.
        Batches whose prescan proves a narrower window still pay their own
        (cheaper) specialization on first sight.
        """
        n = max(int(batch_rows), 1)
        t0 = time.perf_counter()
        rng = np.random.default_rng(0)
        nvals = max((int(c) + 1 for a in self.sig.aggs for c in a[1:]),
                    default=1)
        # magnitudes span wide but square-safely (var's sq column stays
        # finite in float32), signs mixed, every group id exercised
        mag = 10.0 ** rng.uniform(-18.0, 15.0, size=(n, nvals))
        v = (rng.standard_normal((n, nvals)) * mag).astype(
            np.dtype(self.sig.spec.dtype))
        k = (np.arange(n) % self.sig.num_segments).astype(np.int32)
        st = self.prepare(v, k)
        if st is not None:
            depth = self._coalesce_target(n)
            scratch = empty_partial(self.sig.num_segments, self.sig.aggs,
                                    self.sig.spec)
            states = [scratch] + [st] * depth
            finalize(merge_all_jit(states))
        dt = time.perf_counter() - t0
        obs_trace.event("stream.warmup", rows=n, seconds=dt)
        obs_metrics.gauge("stream_warmup_seconds").set(dt)
        return dt

    # -- query -------------------------------------------------------------

    def state(self) -> PartialState:
        """The merged :class:`PartialState` over every ingested row."""
        self.flush()
        return self._state

    def query(self) -> dict:
        """Finalized results over everything ingested so far.

        ``finalize`` is a pure function of the canonical state, so a query
        never perturbs the stream, and two stores whose states are
        bit-identical answer bit-identically — mid-stream queries keep the
        full reproducibility contract.
        """
        with obs_trace.span("stream.query"):
            out = finalize(self.state())
        if self._t_first_result is None and self._t_first_ingest is not None:
            self._t_first_result = time.perf_counter()
            ttfr = self._t_first_result - self._t_first_ingest
            obs_metrics.gauge("stream_ttfr_seconds").set(ttfr)
            obs_trace.event("stream.ttfr", seconds=ttfr)
        obs_metrics.counter("stream_queries_total").inc()
        return out

    def fingerprints(self) -> dict:
        """Byte-layout digests of the current state and its finalized
        results — directly comparable against a one-shot
        ``groupby_agg(..., return_table=True)`` over the same rows."""
        st = self.state()
        return {"stream/table": obs_fp.fingerprint_table(st.table),
                "stream/results": obs_fp.fingerprint_results(finalize(st))}

    @property
    def rows(self) -> int:
        return int(self.state().rows)

    # -- snapshot / restore ------------------------------------------------

    def snapshot(self, directory: str, step: Optional[int] = None,
                 keep: int = 3) -> str:
        """Atomic checkpoint of the merged state.  The manifest carries the
        store's :class:`AggSignature` and the state's byte-layout
        fingerprint, so a restore is self-describing and verifiable."""
        st = self.state()
        if step is None:
            latest = ckpt.latest_step(directory)
            step = 0 if latest is None else latest + 1
        extra = {"kind": "stream_store",
                 "sig": self.sig.to_json(),
                 "batches": self.batches,
                 "wal_seq": self.wal_seq,
                 "fingerprints": self.fingerprints()}
        path = ckpt.save(directory, step, _state_tree(st), extra=extra,
                         keep=keep)
        obs_metrics.counter("stream_snapshots_total").inc()
        return path

    @classmethod
    def restore(cls, directory: str, step: Optional[int] = None,
                method: str = "auto", levels="auto",
                check_finite: bool = False, coalesce="auto",
                verify: bool = True) -> "StreamStore":
        """Rebuild a store from a snapshot, bit-exactly.

        The signature comes from the manifest (no caller-side schema to get
        wrong); with ``verify=True`` (default) the restored pytree is
        re-fingerprinted and checked against the manifest's
        ``tree_fingerprint`` — the restart provably resumes from the very
        bytes the snapshot froze, so *snapshot + restart + remaining
        deltas* == the uninterrupted run.
        """
        manifest = ckpt.read_manifest(directory, step)
        extra = manifest["extra"]
        if extra.get("kind") != "stream_store":
            raise ValueError(f"checkpoint in {directory} is not a stream "
                             f"store snapshot (kind={extra.get('kind')!r})")
        sig = AggSignature.from_json(extra["sig"])
        store = cls(sig.num_segments, aggs=sig.aggs, spec=sig.spec,
                    method=method, levels=levels, check_finite=check_finite,
                    coalesce=coalesce)
        skeleton = _state_tree(store._state)
        tree, _ = ckpt.restore(directory, skeleton, step=manifest["step"])
        if verify:
            ckpt.verify_value(tree, directory, step=manifest["step"])
        store._state = _tree_state(tree, sig)
        store.batches = int(extra.get("batches", 0))
        store.merged_batches = store.batches
        store.wal_seq = int(extra.get("wal_seq", 0))
        obs_metrics.counter("stream_restores_total").inc()
        return store

    @classmethod
    def recover(cls, wal, snapshot_dir: Optional[str] = None,
                method: str = "auto", levels="auto",
                check_finite: bool = False, coalesce="auto"
                ) -> "StreamStore":
        """Rebuild a crashed store from durable state only: the newest
        *verifiable* snapshot (value-fingerprint checked; corrupt or torn
        snapshots are skipped, falling back to older ones or to an empty
        store) plus an idempotent replay of every strictly newer WAL
        record.  Opening the log truncates any torn tail first — with
        ``fsync="always"`` a torn record was never acknowledged, so the
        retrying client redelivers it and the dedup index (rebuilt from
        record metas) keeps the commit exactly-once.  The result is
        bit-identical to the uninterrupted run over the same acknowledged
        batches (DESIGN.md §16.2), and the WAL stays attached for new
        ingest."""
        if not isinstance(wal, WriteAheadLog):
            wal = WriteAheadLog(wal)
        with obs_trace.span("stream.recover", wal_last_seq=wal.last_seq):
            store = None
            if snapshot_dir is not None:
                store = _restore_best_snapshot(
                    cls, snapshot_dir, wal.sig,
                    dict(method=method, levels=levels,
                         check_finite=check_finite, coalesce=coalesce))
            if store is None:
                store = cls(wal.sig.num_segments, aggs=wal.sig.aggs,
                            spec=wal.sig.spec, method=method, levels=levels,
                            check_finite=check_finite, coalesce=coalesce)
            store._replay(wal, from_seq=store.wal_seq)
            store._attach_wal(wal)
        obs_metrics.counter("stream_recoveries_total").inc()
        return store


def _restore_best_snapshot(cls, directory: str, sig, kwargs):
    """Newest snapshot in ``directory`` that restores *and* verifies, or
    None.  A corrupted snapshot (bad npz sha, bad value fingerprint,
    unreadable manifest) is skipped loudly, not trusted silently."""
    import os
    if not os.path.isdir(directory):
        return None
    steps = sorted((int(d.split("_")[1]) for d in os.listdir(directory)
                    if d.startswith("step_")), reverse=True)
    for step in steps:
        try:
            store = cls.restore(directory, step=step, verify=True, **kwargs)
        except Exception as e:  # corrupt/partial/foreign: fall back
            obs_metrics.counter("stream_snapshot_rejects_total").inc()
            obs_trace.event("stream.snapshot_rejected", step=step,
                            error=f"{type(e).__name__}: {e}")
            continue
        if store.sig != sig:
            obs_metrics.counter("stream_snapshot_rejects_total").inc()
            continue
        return store
    return None
