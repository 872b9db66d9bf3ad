"""TPC-H ``lineitem`` generated on the device from a seed (spec §4.2.3).

The benchmark's own copy of the generator, so that no change to the program
can move the yardstick.  Every column is made by one jitted program from the
seed; only the lines-per-order counts (1-7 per order, nudged to the scale's
exact row count) are drawn on the host, because a device-side repeat of
data-dependent lengths is slow to compile on the TPU.

Columns, each ``(rows,)``:

* float32 value columns: ``qty``, ``price`` (extended price), ``disc``,
  ``disc_price`` (price * (1 - disc)), ``one_plus_tax``;
* int32 columns: ``flag`` (the dense (returnflag, linestatus) id in
  [0, 6)), ``order`` (dense order id in [0, orders)), ``ship`` (ship date in
  days since 1992-01-01), ``qty_int``, ``disc_cents``, ``tax_cents``.
"""
from __future__ import annotations

import datetime

import numpy as np

VALUE_COLUMNS = ("qty", "price", "disc", "disc_price", "one_plus_tax")
INT_COLUMNS = ("flag", "order", "ship", "qty_int", "disc_cents",
               "tax_cents")


def day(y: int, m: int, d: int) -> int:
    """Days since TPC-H's STARTDATE, 1992-01-01."""
    return (datetime.date(y, m, d) - datetime.date(1992, 1, 1)).days


ENDDATE = day(1998, 12, 31)
CURRENTDATE = day(1995, 6, 17)


def lines_per_order(rng, orders: int, rows: int) -> np.ndarray:
    """1-7 lineitems per order, uniform, nudged by one line at randomly
    chosen orders until they add up to ``rows``."""
    counts = rng.integers(1, 8, orders)
    diff = rows - int(counts.sum())
    room = np.flatnonzero(counts < 7 if diff > 0 else counts > 1)
    if abs(diff) > room.size:
        raise ValueError(f"cannot fit {rows} lineitems into {orders} orders")
    counts[rng.choice(room, abs(diff), replace=False)] += np.sign(diff)
    return counts


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits make the key and
    the rest is folded in, so seeds past 32 bits stay distinct."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def lineitem(seed: int, rows: int, orders: int, parts: int) -> dict:
    """The ``lineitem`` columns as device arrays (see the module doc)."""
    import jax

    counts = lines_per_order(np.random.default_rng(seed), orders, rows)
    order = jax.device_put(np.repeat(np.arange(orders, dtype=np.int32),
                                     counts))
    return jax.jit(_columns, static_argnums=(2, 3))(
        seed_key(seed), order, orders, parts)


def _columns(key, order, orders: int, parts: int) -> dict:
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    rows = order.shape[0]
    k = jax.random.split(key, 8)
    orderdate = jax.random.randint(k[0], (orders,), 0, ENDDATE - 151 + 1,
                                   dtype=i32)
    qty = jax.random.randint(k[1], (rows,), 1, 51, dtype=i32)
    partkey = jax.random.randint(k[2], (rows,), 1, parts + 1, dtype=i32)
    retail_cents = (90000 + (partkey // 10) % 20001
                    + 100 * (partkey % 1000))
    price = (qty * retail_cents).astype(jnp.float32) / 100
    disc = jax.random.randint(k[3], (rows,), 0, 11, dtype=i32)  # 0.00-0.10
    tax = jax.random.randint(k[4], (rows,), 0, 9, dtype=i32)    # 0.00-0.08
    ship = orderdate[order] + jax.random.randint(k[5], (rows,), 1, 122,
                                                 dtype=i32)
    receipt = ship + jax.random.randint(k[6], (rows,), 1, 31, dtype=i32)
    returned = jax.random.bernoulli(k[7], 0.5, (rows,))
    # R or A once received by CURRENTDATE, else N; O if shipped after it
    returnflag = jnp.where(receipt <= CURRENTDATE,
                           jnp.where(returned, 2, 0), 1).astype(i32)
    linestatus = (ship > CURRENTDATE).astype(i32)
    d = disc.astype(jnp.float32) / 100
    return {"qty": qty.astype(jnp.float32), "price": price, "disc": d,
            "disc_price": price * (1 - d),
            "one_plus_tax": 1 + tax.astype(jnp.float32) / 100,
            "flag": returnflag * 2 + linestatus, "order": order,
            "ship": ship.astype(i32), "qty_int": qty, "disc_cents": disc,
            "tax_cents": tax}


_OPS = {"<=": "less_equal", "<": "less", ">=": "greater_equal",
        ">": "greater", "==": "equal"}


def where_mask(table: dict, where):
    """Rows passing every ``[column, op, integer]`` condition (integer
    columns only, so no float comparison decides a row)."""
    import jax.numpy as jnp

    n = next(iter(table.values())).shape[0]
    mask = jnp.ones(n, bool)
    for col, op, val in where:
        if col not in INT_COLUMNS:
            raise ValueError(f"filter on {col!r}: only the integer columns "
                             f"{INT_COLUMNS} may be filtered")
        mask = mask & getattr(jnp, _OPS[op])(table[col], int(val))
    return mask


def select(table: dict, where, rows: int | None, columns) -> dict:
    """The ``columns`` of the rows passing ``where``; with ``rows`` set, the
    first ``rows`` of them in generation order, so that every seed gives the
    same shapes.  Fails where fewer rows than that pass."""
    import jax
    import jax.numpy as jnp

    columns = tuple(columns)
    if not where and rows is None:
        return {c: table[c] for c in columns}
    mask = where_mask(table, where)
    passing = int(jnp.sum(mask))
    if rows is None:
        return {c: table[c][mask] for c in columns}
    if passing < rows:
        raise ValueError(f"only {passing} rows pass {where}, fewer than the "
                         f"{rows} the traffic asks for")

    def take(cols, mask):
        idx = jnp.nonzero(mask, size=rows)[0]
        return {c: v[idx] for c, v in cols.items()}

    return jax.jit(take)({c: table[c] for c in columns}, mask)
