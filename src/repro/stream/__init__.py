"""Streaming incremental aggregation on the partial/merge/finalize algebra.

The paper proves the accumulator associative and commutative; this package
cashes that in for unbounded streams (DESIGN.md §14.3–§14.5):

* :mod:`repro.stream.store` — :class:`StreamStore`: a persistent merged
  :class:`~repro.ops.partial.PartialState` ingesting micro-batch deltas,
  queryable anytime, snapshot/restore verifiably bit-exact;
* :mod:`repro.stream.window` — :class:`WindowedStore`: tumbling/sliding
  event-time windows as a ring of mergeable partials, out-of-order and
  late arrivals handled by the same exact merge;
* :mod:`repro.stream.sharded` — :class:`ShardedStreamStore`: N independent
  shard stores (round-robin or key-hash batch assignment) whose query-time
  ``merge_all`` is bit-identical to a single store, by the same algebra;
* :mod:`repro.stream.service` — an asyncio NDJSON ingest/query endpoint
  with concurrent ingest: the pure ``prepare`` stage runs on a thread pool
  outside the locks, only the tiny ``commit`` serializes (per shard), and
  backpressure bounds in-flight memory.  Any interleaving of concurrent
  writers yields the bit-identical state — the lock picks an order, the
  algebra erases it (DESIGN.md §15);
* :mod:`repro.stream.wal` — :class:`WriteAheadLog`: an append-only,
  framed, sha256-verified delta log.  Every acknowledged batch is durable
  before the ack; ``recover(wal, snapshot_dir)`` rebuilds a crashed store
  bit-exactly, and client delivery tags make commits exactly-once across
  crashes (DESIGN.md §16);
* :mod:`repro.stream.replica` — :class:`ReplicatedStore`: a logging
  primary plus WAL-tailing followers, with failover gated on bitwise
  fingerprint agreement against the recovered durable state.

The headline invariant, checked end-to-end by ``repro.obs.audit`` and
``tests/test_stream.py``: the same rows delivered as 1, 7, or 64 permuted
micro-batches — with or without a snapshot/restart in the middle — produce
a store whose table and results fingerprints equal the one-shot
``groupby_agg`` over the concatenated rows.
"""
from repro.stream.store import StreamStore  # noqa: F401
from repro.stream.sharded import ShardedStreamStore  # noqa: F401
from repro.stream.window import WindowedStore  # noqa: F401
from repro.stream.service import (  # noqa: F401
    Backpressure, StreamService, serve,
)
from repro.stream.wal import (  # noqa: F401
    DedupIndex, WalError, WalReader, WalUnavailable, WriteAheadLog,
)
from repro.stream.replica import (  # noqa: F401
    Follower, PromotionError, ReplicatedStore,
)

__all__ = ["StreamStore", "ShardedStreamStore", "WindowedStore",
           "StreamService", "Backpressure", "serve",
           "WriteAheadLog", "WalReader", "WalError", "WalUnavailable",
           "DedupIndex", "Follower", "ReplicatedStore", "PromotionError"]
