"""The per-layer readers of program spans (``groupby.*`` of a query,
``stream.decode`` and ``wal.fsync`` of an ingest batch), on span lists
made by hand."""
from __future__ import annotations

import types

import pytest

from benchmarks.tpu import harness


def _query_spans(q: int) -> list:
    """The spans of one query, ids from ``10 * q``: a 20-ms root over
    5 ms of columns, an 8-ms prescan holding three 1-ms host syncs,
    0.5 ms of aggregate and 4 ms of finalize."""
    ms = 1_000_000
    root = 10 * q

    def rec(i, name, dur, parent):
        return {"name": name, "span_id": root + i, "parent_id": parent,
                "root_id": root, "dur_ns": int(dur * ms)}

    return [rec(0, "groupby.query", 20, None),
            rec(1, "groupby.columns", 5, root),
            rec(2, "groupby.prescan", 8, root),
            *(rec(3 + k, "groupby.host_sync", 1, root + 2)
              for k in range(3)),
            rec(6, "groupby.aggregate", 0.5, root),
            rec(7, "groupby.finalize", 4, root)]


def _stream_spans() -> list:
    return [{"name": name, "dur_ns": int(ms * 1_000_000)}
            for name, ms in (("stream.decode", 1), ("stream.decode", 3),
                             ("wal.fsync", 4), ("wal.fsync", 6),
                             ("stream.prepare", 50))]


@pytest.mark.parametrize("metric, spans, value", [
    ("columns_ms.query", "query", 5.0),
    ("host_sync_ms.query", "query", 3.0),
    ("host_syncs.query", "query", 3.0),
    ("finalize_ms.query", "query", 4.0),
    ("operator_self_ms.query", "query", 2.5),
    ("decode_ms.stream", "stream", 2.0),
    ("wal_fsync_ms.stream", "stream", 5.0),
])
def test_span_metrics_read_synthetic_spans(metric, spans, value):
    read = harness.metric_reader(metric)
    records = (_query_spans(1) + _query_spans(2) if spans == "query"
               else _stream_spans())
    work = {"queries": 2, "batches": 2}
    run = types.SimpleNamespace(spans=records, device=None, work=work,
                                peaks={})
    assert read(run) == pytest.approx(value)
    assert read(types.SimpleNamespace(spans=[], device=None, work=work,
                                      peaks={})) is None
