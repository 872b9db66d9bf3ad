"""TPC-H ``lineitem`` range-partitioned by ``l_orderkey`` over the chips of
a mesh, each part generated on its own chip from (seed, part), in blocks.

The benchmark's own copy of ``data_tpch``'s value distributions (spec
§4.2.3), for a table too large for one chip: part ``p`` of ``parts`` holds
the orders ``[p O / parts, (p + 1) O / parts)`` (``O`` the scale's orders)
and ``rows // parts`` lineitems (one more for the first ``rows % parts``
parts), 1-7 a order, drawn on the host from ``(seed, p)`` and nudged to that
count as ``data_tpch.lines_per_order`` does.  The order dates are drawn on
the part's chip; every other column is drawn there too, one block of
``GEN_ROWS`` lineitems at a time, and only the rows that pass the query's
filter are kept, in generation order, written into the part's output
arrays as they come: the set-up's transient is one block, beside the
output.

Only the columns a query reads are kept: the float32 value columns it
names, stacked as one ``(rows, C)`` matrix, and the int32 group key.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.tpu.data_tpch import (CURRENTDATE, ENDDATE, lines_per_order,
                                      seed_key, where_mask)

#: lineitems a generation block draws on a chip at once
GEN_ROWS = 1 << 23


def part_rows(rows: int, parts: int, p: int) -> int:
    return rows // parts + (p < rows % parts)


def part_orders(orders: int, parts: int, p: int) -> int:
    return (p + 1) * orders // parts - p * orders // parts


def _block_columns(key, orderdate, order, parts: int) -> dict:
    """``data_tpch``'s columns for one block of lineitems whose orders (the
    part's own order indices) are ``order``."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    n = order.shape[0]
    k = jax.random.split(key, 7)
    qty = jax.random.randint(k[0], (n,), 1, 51, dtype=i32)
    partkey = jax.random.randint(k[1], (n,), 1, parts + 1, dtype=i32)
    retail_cents = (90000 + (partkey // 10) % 20001
                    + 100 * (partkey % 1000))
    price = (qty * retail_cents).astype(jnp.float32) / 100
    disc = jax.random.randint(k[2], (n,), 0, 11, dtype=i32)     # 0.00-0.10
    tax = jax.random.randint(k[3], (n,), 0, 9, dtype=i32)       # 0.00-0.08
    ship = orderdate[order] + jax.random.randint(k[4], (n,), 1, 122,
                                                 dtype=i32)
    receipt = ship + jax.random.randint(k[5], (n,), 1, 31, dtype=i32)
    returned = jax.random.bernoulli(k[6], 0.5, (n,))
    returnflag = jnp.where(receipt <= CURRENTDATE,
                           jnp.where(returned, 2, 0), 1).astype(i32)
    linestatus = (ship > CURRENTDATE).astype(i32)
    d = disc.astype(jnp.float32) / 100
    return {"qty": qty.astype(jnp.float32), "price": price, "disc": d,
            "disc_price": price * (1 - d),
            "one_plus_tax": 1 + tax.astype(jnp.float32) / 100,
            "flag": returnflag * 2 + linestatus, "order": order,
            "ship": ship, "qty_int": qty, "disc_cents": disc,
            "tax_cents": tax}


@functools.lru_cache(maxsize=8)
def _filler(block: int, cap: int, values: tuple, key_col: str, where: tuple,
            parts: int):
    """One jitted step: draw a block, keep the rows passing ``where``, and
    append them to the part's ``(cap, C)`` values and ``(cap,)`` keys at
    ``offset``.  A write that would run past ``cap`` is moved back to end
    at ``cap`` and keeps the rows already there, so rows past ``cap`` are
    dropped; the returned offset counts every passing row."""
    def fill(out_v, out_k, offset, key, orderdate, order, nvalid):
        cols = _block_columns(key, orderdate, order, parts)
        idx = jnp.arange(block)
        mask = where_mask(cols, [list(w) for w in where]) & (idx < nvalid)
        # passing rows first, in generation order (a stable sort in place
        # of jnp.nonzero, which scatters)
        take = jnp.argsort(~mask, stable=True)
        start = jnp.minimum(offset, cap - block)
        d = offset - start                      # rows kept before the new
        src = take[jnp.clip(idx - d, 0, block - 1)]
        keep = idx < d
        v = jnp.stack([cols[c] for c in values], axis=1)[src]
        old_v = lax.dynamic_slice_in_dim(out_v, start, block)
        old_k = lax.dynamic_slice_in_dim(out_k, start, block)
        out_v = lax.dynamic_update_slice_in_dim(
            out_v, jnp.where(keep[:, None], old_v, v), start, 0)
        out_k = lax.dynamic_update_slice_in_dim(
            out_k, jnp.where(keep, old_k, cols[key_col][src]), start, 0)
        return out_v, out_k, offset + jnp.sum(mask, dtype=jnp.int32)

    return jax.jit(fill, donate_argnums=(0, 1))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _part_start(key, orders: int, cap: int, ncols: int):
    """A part's row key, order dates and empty output, all made on the
    chip that holds ``key``."""
    k_orders, k_rows = jax.random.split(key)
    return (k_rows, jax.random.randint(k_orders, (orders,), 0,
                                       ENDDATE - 151 + 1, dtype=jnp.int32),
            jnp.zeros((cap, ncols), jnp.float32), jnp.zeros((cap,), jnp.int32),
            jnp.zeros((), jnp.int32))


def parts_q1(seed: int, scale: dict, devices, where, chip_rows: int | None,
             values, key_col: str):
    """Each part's passing rows on its own device: a list, one entry a
    device, of ``(values (r, C) float32, keys (r,) int32)``.

    ``chip_rows`` rows a part, the first that pass ``where`` in generation
    order; a part with fewer passing rows is an error.  ``None`` takes from
    every part as many rows as the part with the fewest passing rows has.
    Each part is made by a thread of its own, so that the chips compile and
    work at once.
    """
    parts = len(devices)
    rows, orders = int(scale["rows"]), int(scale["orders"])
    sizes = [part_rows(rows, parts, p) for p in range(parts)]
    cap = chip_rows if chip_rows is not None else min(sizes)
    block = min(GEN_ROWS, cap, min(sizes))
    fill = _filler(block, cap, tuple(values), key_col,
                   tuple(tuple(w) for w in where), int(scale["parts"]))
    base = seed_key(seed)

    def make(p):
        dev = devices[p]
        counts = lines_per_order(np.random.default_rng([seed, p]),
                                 part_orders(orders, parts, p), sizes[p])
        # each lineitem's order, as an index into the part's orders
        order = np.repeat(np.arange(counts.size, dtype=np.int32), counts)
        k_rows, odate, out_v, out_k, offset = _part_start(
            jax.device_put(jax.random.fold_in(base, p), dev), counts.size,
            cap, len(values))
        for b in range(-(-sizes[p] // block)):
            ids = order[b * block:(b + 1) * block]
            out_v, out_k, offset = fill(
                out_v, out_k, offset, jax.random.fold_in(k_rows, b), odate,
                jax.device_put(np.pad(ids, (0, block - ids.size)), dev),
                jax.device_put(np.int32(ids.size), dev))
        return out_v, out_k, int(offset)

    with ThreadPoolExecutor(parts) as pool:
        made = list(pool.map(make, range(parts)))
    passing = [n for _, _, n in made]
    if chip_rows is None:
        take = min(passing)
        return [(v[:take], k[:take]) for v, k, _ in made]
    short = [(p, n) for p, n in enumerate(passing) if n < chip_rows]
    if short:
        raise ValueError(f"parts {short} have fewer than the {chip_rows} "
                         f"rows passing {where} that a chip reads")
    return [(v, k) for v, k, _ in made]
