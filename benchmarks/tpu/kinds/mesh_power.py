"""Traffic kind ``mesh_power``: the ``power`` kind's single stream, over a
table range-partitioned across the chips of a mesh.

The mix's ``query`` is read as ``power`` reads it (``where``, ``rows``,
``group_by``, ``aggs``).  The configuration's ``partitions`` chips each
hold one part of ``lineitem`` (``data_tpch_mesh``), and each reads
``rows // partitions`` of its part's rows that pass the filter: the first,
in generation order, so that every seed runs the same shapes.  The filter
runs in set-up.  A configuration whose table is cut below ``rows`` (the CPU
tests run it at thousands of rows) reads from each part as many rows as
the part with the fewest passing rows has.

A query runs from the call of ``repro.ops.sharded_groupby_agg(values,
keys, G, aggs, method="auto", mesh=<1-D mesh of the chips>)`` on the
device-resident sharded rows to its finalized results on the host.  Set-up
makes the data and runs ``WARMUP_QUERIES`` queries.  The check, after the
window: the first result against the blocked plain reference
(``reference_blocked``, fed sorted blocks pulled from each chip); every
other result against the first, bit for bit; and one more query on the same
rows with each chip's rows permuted from the seed and the parts moved to
the chips of a seeded rotation, against the first, bit for bit.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.tpu import data_tpch, data_tpch_mesh
from benchmarks.tpu.kinds import power
from benchmarks.tpu.reference_blocked import BlockedGroupReference

#: rows a chip sorts and hands to the host reference at a time
REF_ROWS = 1 << 23


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sorted_block(v, k, rows: int, groups: int, start):
    """``rows`` rows from ``start``, sorted by key (stable), the values as
    ``(C, rows)``: each column contiguous on the host.  A block that would
    run past the end ends there instead, and the rows it shares with the
    block before it take the key ``groups``, past every group."""
    at = jnp.minimum(start, k.shape[0] - rows)
    vb = jax.lax.dynamic_slice_in_dim(v, at, rows)
    kb = jnp.where(at + jnp.arange(rows) >= start,
                   jax.lax.dynamic_slice_in_dim(k, at, rows), groups)
    order = jnp.argsort(kb, stable=True)
    return vb[order].T, kb[order]


@jax.jit
def _permuted(v, k, key):
    """The rows in the order of a sort by random 32-bit keys (one sort)."""
    perm = jnp.argsort(jax.random.bits(key, k.shape, jnp.uint32))
    return v[perm], k[perm]


class Driver(power.Driver):
    """``power``'s driver (its window, its results), with the rows on the
    chips of a mesh and the sharded operator in place of ``groupby_agg``."""

    def __init__(self, config: dict, mix: dict, seed: int, spec):
        super().__init__(config, mix, seed, spec)
        self.parts = int(config["partitions"])

    def _query(self, values=None, keys=None):
        from repro.ops import sharded_groupby_agg

        res = sharded_groupby_agg(
            self.values if values is None else values,
            self.keys if keys is None else keys, self.groups,
            aggs=self._aggs, spec=self.spec, method="auto", mesh=self.mesh)
        return jax.device_get(res)

    def _global(self, shards):
        """The parts, one a chip in mesh order, as global row-sharded
        arrays."""
        n = shards[0][0].shape[0] * self.parts
        sh = NamedSharding(self.mesh, P("data"))
        return (jax.make_array_from_single_device_arrays(
                    (n, len(self.columns)), sh, [v for v, _ in shards]),
                jax.make_array_from_single_device_arrays(
                    (n,), sh, [k for _, k in shards]))

    def setup(self) -> None:
        scale, q = self.config["scale"], self.mix["query"]
        devices = jax.devices()[:self.parts]
        self.mesh = jax.make_mesh((self.parts,), ("data",), devices=devices)
        rows = int(self.config[q["rows"]])
        chip_rows = rows // self.parts if rows <= scale["rows"] else None
        self.values, self.keys = self._global(data_tpch_mesh.parts_q1(
            self.seed, scale, list(self.mesh.devices.flat), q["where"],
            chip_rows, self.columns, self.group_by))
        self.rows = int(self.values.shape[0])
        self._aggs = power.engine_aggs(self.aggs, self.columns)
        self.names = power.result_names(self._aggs)
        for _ in range(power.WARMUP_QUERIES):
            self._query()

    def _shards(self):
        """Each chip's ``(values, keys)``, in mesh order."""
        by_dev = {s.device: s.data for s in self.keys.addressable_shards}
        return [(s.data, by_dev[s.device])
                for s in sorted(self.values.addressable_shards,
                                key=lambda s: s.index[0].start or 0)]

    def _reference(self) -> BlockedGroupReference:
        """The plain reference over every chip's rows, a sorted block at a
        time, the chips' rows in threads of their own."""
        def part(shard):
            v, k = shard
            ref = BlockedGroupReference(self.aggs, self.groups)
            rows = min(REF_ROWS, k.shape[0])
            for start in range(0, k.shape[0], rows):
                vb, kb = jax.device_get(
                    _sorted_block(v, k, rows, self.groups, start))
                ref.add(dict(zip(self.columns, vb)), kb)
            return ref

        with ThreadPoolExecutor(self.parts) as pool:
            refs = list(pool.map(part, self._shards()))
        for r in refs[1:]:
            refs[0].merge(r)
        return refs[0]

    def _permuted(self) -> list:
        """The query's results with each chip's rows permuted from the seed
        and part ``p`` moved to chip ``p + r`` of the mesh, ``r`` in ``[1,
        parts)`` drawn from the seed."""
        base = jax.random.fold_in(data_tpch.seed_key(self.seed), 1)
        shards = self._shards()
        self.values = self.keys = None      # a chip holds two parts at most

        def permute(p):
            v, k = shards[p]
            shards[p] = None
            key = jax.device_put(jax.random.fold_in(base, p), v.device)
            return jax.block_until_ready(_permuted(v, k, key))

        with ThreadPoolExecutor(self.parts) as pool:
            parts = list(pool.map(permute, range(self.parts)))
        r = (1 + int(np.random.default_rng([self.seed, 1]).integers(
            self.parts - 1))) if self.parts > 1 else 0
        devices = list(self.mesh.devices.flat)
        moved = [None] * self.parts
        for p in range(self.parts):
            q = (p + r) % self.parts
            moved[q] = tuple(jax.device_put(x, devices[q]) for x in parts[p])
            parts[p] = None
        values, keys = self._global(moved)
        del moved
        out = self._query(values, keys)
        return [out[k] for k in self.names]

    def check(self) -> dict:
        if not self.results:
            return {"error_share_of_bound": float(np.finfo(float).max),
                    "count_mismatches": self.groups,
                    "queries_not_bit_identical": 0,
                    "permuted_not_bit_identical": len(self.names)}
        first = self.results[0]
        differing = sum(1 for r in self.results[1:]
                        if power._differing(r, first))
        s = self.config["spec"]
        readings = self._reference().compare(first, self.aggs, s["m"],
                                             s["L"], s["W"])
        readings["queries_not_bit_identical"] = differing
        readings["permuted_not_bit_identical"] = power._differing(
            self._permuted(), first)
        return readings
