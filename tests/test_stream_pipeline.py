"""Pipelined-ingest stress tests (DESIGN.md §15).

The claim under test: moving the pure ``prepare`` stage out of the lock —
onto a thread pool, across shards, behind backpressure — changes
throughput only, never bits.  Every assertion is a fingerprint equality
against the one-shot ``groupby_agg`` or against a differently-configured
store over the same rows.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.obs.fingerprint import fingerprint_results, fingerprint_table
from repro.ops import groupby_agg
from repro.ops.partial import merge_all, merge_all_jit, partial_agg
from repro.ops.plan import plan_partial
from repro.core.types import ReproSpec
from repro.stream import (Backpressure, ShardedStreamStore, StreamService,
                          StreamStore)

G = 29
AGGS = ("sum", "count", "mean", "var", "min", "max", ("sum", 1))


def _data(n=3000, seed=0, spread=15.0):
    rng = np.random.default_rng(seed)
    v = (rng.standard_normal((n, 2)) *
         np.exp(rng.uniform(-spread, spread, (n, 2)))).astype(np.float32)
    k = rng.integers(0, G, n).astype(np.int32)
    return v, k


@pytest.fixture(scope="module")
def dataset():
    v, k = _data()
    ref, tab = groupby_agg(v, k, G, aggs=AGGS, return_table=True)
    return v, k, {"stream/table": fingerprint_table(tab),
                  "stream/results": fingerprint_results(ref)}


def _random_batches(v, k, seed, writers):
    """Split the rows into ``writers`` disjoint spans, each chopped into
    randomized batch sizes — per-writer work lists for the stress tests."""
    rng = np.random.default_rng(seed)
    bounds = np.linspace(0, v.shape[0], writers + 1).astype(int)
    work = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        cuts, a = [], int(lo)
        while a < hi:
            b = min(a + int(rng.integers(1, 400)), int(hi))
            cuts.append((v[a:b], k[a:b]))
            a = b
        work.append(cuts)
    return work


async def _drive(service, work, seed):
    """Run one asyncio writer task per work list, with randomized yields so
    prepares genuinely overlap and commit order is scrambled."""
    rng = np.random.default_rng(seed)
    jitter = [rng.random(len(w)) for w in work]

    async def writer(i):
        for j, (bv, bk) in enumerate(work[i]):
            if jitter[i][j] < 0.4:
                await asyncio.sleep(0)
            out = await service.ingest(bv, bk)
            assert out["rows"] == bv.shape[0]

    await asyncio.gather(*(writer(i) for i in range(len(work))))


# ---------------------------------------------------------------------------
# the tentpole invariant: pipelined / sharded concurrency never moves bits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_pipelined_concurrent_writers_match_one_shot(dataset, seed):
    v, k, want = dataset

    async def run():
        service = StreamService(StreamStore(G, aggs=AGGS), max_workers=4)
        await _drive(service, _random_batches(v, k, seed, writers=5), seed)
        fps = await service.fingerprints()
        stats = await service.stats()
        service.close()
        return fps, stats

    fps, stats = asyncio.run(run())
    assert fps == want
    assert stats["rows"] == v.shape[0]


@pytest.mark.parametrize("shards,policy", [(2, "round_robin"),
                                           (4, "key_hash")])
def test_sharded_pipelined_service_matches_one_shot(dataset, shards, policy):
    v, k, want = dataset

    async def run():
        store = ShardedStreamStore(G, aggs=AGGS, num_shards=shards,
                                   policy=policy)
        service = StreamService(store, max_workers=4)
        await _drive(service, _random_batches(v, k, 3, writers=4), 3)
        fps = await service.fingerprints()
        stats = await service.stats()
        service.close()
        return fps, stats

    fps, stats = asyncio.run(run())
    assert fps == want
    assert stats["rows"] == v.shape[0]


@pytest.mark.parametrize("shards", [1, 2, 8])
@pytest.mark.parametrize("policy", ["round_robin", "key_hash"])
def test_sharded_store_bitwise_equals_single(dataset, shards, policy):
    v, k, want = dataset
    store = ShardedStreamStore(G, aggs=AGGS, num_shards=shards,
                               policy=policy)
    for bv, bk in _random_batches(v, k, 7, writers=1)[0]:
        store.ingest(bv, bk)
    assert store.fingerprints() == want
    assert store.rows == v.shape[0]


# ---------------------------------------------------------------------------
# snapshot mid-ingest: drain means whole batches, bit-exact restore
# ---------------------------------------------------------------------------

def test_snapshot_mid_ingest_drains_and_restores_bit_exactly(dataset,
                                                             tmp_path):
    v, k, want = dataset
    # fixed batch size dividing each writer's span: torn batches detectable
    n, step = v.shape[0], 75

    async def run():
        service = StreamService(StreamStore(G, aggs=AGGS), max_workers=4)

        async def writer(lo):
            for a in range(lo, lo + n // 4, step):
                await service.ingest(v[a:a + step], k[a:a + step])

        async def snapper():
            await asyncio.sleep(0)
            return await service.snapshot(str(tmp_path))

        results = await asyncio.gather(
            *(writer(int(a)) for a in np.linspace(0, n, 5)[:-1].astype(int)),
            snapper())
        fps = await service.fingerprints()
        service.close()
        return results[-1], fps

    _, final_fps = asyncio.run(run())
    # every acknowledged row made it, concurrency and the snapshot included
    assert final_fps == want

    manifest = ckpt.read_manifest(str(tmp_path))
    extra = manifest["extra"]
    restored = StreamStore.restore(str(tmp_path))  # verify=True: byte check
    # drain semantics: the snapshot holds whole batches only — a torn batch
    # would leave a row count not divisible by the batch size
    assert restored.rows % step == 0
    assert restored.rows == extra["batches"] * step
    # and the restored store reproduces the snapshot's fingerprints exactly
    assert restored.fingerprints() == extra["fingerprints"]


# ---------------------------------------------------------------------------
# backpressure: admitted exactly once or not at all
# ---------------------------------------------------------------------------

def test_backpressure_reject_loses_nothing(dataset):
    v, k, _ = dataset

    async def run():
        service = StreamService(StreamStore(G, aggs=AGGS),
                                inflight_budget=1024, backpressure="reject")
        # simulate a concurrent in-flight batch holding the whole budget
        await service._admit(1024)
        with pytest.raises(Backpressure):
            await service.ingest(v[:200], k[:200])
        await service._release(1024)  # stats drains in-flight: release first
        stats0 = await service.stats()
        out = await service.ingest(v[:200], k[:200])
        stats1 = await service.stats()
        service.close()
        return stats0, out, stats1

    stats0, out, stats1 = asyncio.run(run())
    assert stats0["rows"] == 0 and stats0["batches"] == 0  # nothing lost...
    assert out["rows"] == 200
    assert stats1["rows"] == 200 and stats1["batches"] == 1  # ...or doubled


def test_backpressure_wait_blocks_then_completes(dataset):
    v, k, _ = dataset

    async def run():
        service = StreamService(StreamStore(G, aggs=AGGS),
                                inflight_budget=1024, backpressure="wait")
        await service._admit(1024)
        task = asyncio.ensure_future(service.ingest(v[:200], k[:200]))
        await asyncio.sleep(0.05)
        assert not task.done()  # blocked on the budget, not failed
        await service._release(1024)
        out = await task
        stats = await service.stats()
        service.close()
        return out, stats

    out, stats = asyncio.run(run())
    assert out["rows"] == 200
    assert stats["rows"] == 200 and stats["batches"] == 1


def test_oversized_batch_admitted_when_queue_empty(dataset):
    v, k, _ = dataset

    async def run():
        # a single batch larger than the whole budget must still run
        service = StreamService(StreamStore(G, aggs=AGGS),
                                inflight_budget=8, backpressure="reject")
        out = await service.ingest(v[:500], k[:500])
        service.close()
        return out

    assert asyncio.run(run())["rows"] == 500


# ---------------------------------------------------------------------------
# stats consistency (the satellite race fix): reads are quiesced
# ---------------------------------------------------------------------------

def test_stats_consistent_under_concurrent_ingest(dataset):
    v, k, _ = dataset
    step = 75  # divides each writer's span: partition, no overlap

    async def run():
        service = StreamService(StreamStore(G, aggs=AGGS), max_workers=4)

        async def writer(lo, hi):
            for a in range(lo, hi, step):
                await service.ingest(v[a:a + step], k[a:a + step])

        async def poller(out):
            for _ in range(10):
                out.append(await service.stats())
                await asyncio.sleep(0)

        polled = []
        bounds = np.linspace(0, v.shape[0] // step * step, 5).astype(int)
        await asyncio.gather(*(writer(int(a), int(b)) for a, b in
                               zip(bounds[:-1], bounds[1:])),
                             poller(polled))
        service.close()
        return polled

    for s in asyncio.run(run()):
        # quiesced reads: the three counters form one consistent snapshot —
        # rows always a whole number of batches, merges never exceed commits
        assert s["rows"] == s["batches"] * step
        assert s["merged_batches"] <= s["batches"]


# ---------------------------------------------------------------------------
# building blocks: each throughput knob is bit-free on its own
# ---------------------------------------------------------------------------

def test_prepare_commit_composes_to_ingest(dataset):
    v, k, want = dataset
    a, b = StreamStore(G, aggs=AGGS), StreamStore(G, aggs=AGGS)
    for bv, bk in _random_batches(v, k, 11, writers=1)[0]:
        a.ingest(bv, bk)
        b.commit(b.prepare(bv, bk), bv.shape[0])
    assert a.fingerprints() == b.fingerprints() == want
    assert a.batches == b.batches


def test_compiled_store_bitwise_equals_eager(dataset):
    v, k, want = dataset
    eager, comp = StreamStore(G, aggs=AGGS), StreamStore(G, aggs=AGGS)
    for bv, bk in _random_batches(v, k, 13, writers=1)[0]:
        with jax.disable_jit():
            eager.ingest(bv, bk)
        comp.ingest(bv, bk)
    with jax.disable_jit():
        fps = eager.fingerprints()
    assert fps == comp.fingerprints() == want


def test_merge_all_jit_bitwise_equals_eager(dataset):
    v, k, _ = dataset
    states = [partial_agg(bv, bk, G, aggs=AGGS)
              for bv, bk in _random_batches(v, k, 17, writers=1)[0][:6]]
    a, b = merge_all(states), merge_all_jit(states)
    assert fingerprint_table(a.table) == fingerprint_table(b.table)
    assert np.array_equal(np.asarray(a.minv), np.asarray(b.minv))
    assert np.array_equal(np.asarray(a.maxv), np.asarray(b.maxv))
    assert int(a.rows) == int(b.rows)


def test_warmup_is_state_neutral(dataset):
    v, k, _ = dataset
    store = StreamStore(G, aggs=AGGS)
    store.ingest(v[:500], k[:500])
    before = store.fingerprints()
    batches = store.batches
    dt = store.warmup(512)
    assert dt > 0
    assert store.fingerprints() == before
    assert store.batches == batches


def test_plan_partial_reports_pipeline_width():
    spec = ReproSpec(dtype=np.float32)
    plan = plan_partial(4096, 64, spec, ncols=4)
    assert plan.pipeline >= 1
    import os
    assert plan.pipeline <= (os.cpu_count() or 1)
    # a store exposes the same width (and a sharded store scales it)
    store = StreamStore(64, aggs=("sum",))
    assert store.pipeline_width(4096) == plan.pipeline
    sharded = ShardedStreamStore(64, aggs=("sum",), num_shards=4)
    assert sharded.pipeline_width(4096) >= plan.pipeline


def test_service_rejects_bad_backpressure_mode():
    with pytest.raises(ValueError, match="backpressure"):
        StreamService(StreamStore(G, aggs=("sum",)), backpressure="drop")
