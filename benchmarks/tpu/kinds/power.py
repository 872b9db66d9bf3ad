"""Traffic kind ``power``: one client sends one query, closed loop, back to
back, as the TPC-H power test's single stream does.

The mix's ``query`` names, over the configuration's ``lineitem``:

* ``where`` — ``[column, op, integer]`` filters on integer columns, and
  ``rows`` — the configuration's key (listed in its ``reduced``) that says
  how many of the passing rows, in generation order, the query reads, so
  that every seed runs the same shapes; ``null`` reads them all.  The
  filter runs in set-up;
* ``group_by`` — the key column (``flag``: 6 groups; ``order``: one group
  per order; ``none``: one group over all rows);
* ``aggs`` — ``[kind, column...]``: ``sum``, ``sum_prod`` (of two
  columns), ``mean``, ``count``.

A query starts when the client calls ``repro.ops.groupby_agg(...,
method="auto")`` on the device-resident rows and ends when its finalized
results are on the host.  Set-up makes the data and runs
``WARMUP_QUERIES`` queries.  Every result of the window is kept.  The check,
after the window: the first result against the plain reference; every
other result against the first, bit for bit; and one more query, the same
call on a permutation of the same device rows drawn from the seed, against
the first, bit for bit (the results may not depend on the rows' order).
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmarks.tpu import cost, data_tpch, reference
from benchmarks.tpu.harness import Window

#: queries run in set-up before the window: every program the query uses
#: is compiled or loaded there
WARMUP_QUERIES = 2


def group_count(column: str, scale: dict) -> int:
    if column == "none":
        return 1
    if column == "flag":
        return 6
    if column == "order":
        return int(scale["orders"])
    raise ValueError(f"no group count for key column {column!r}")


def engine_aggs(aggs, columns):
    """The query's aggregates over the stacked value matrix's indices."""
    out = []
    for a in aggs:
        if a[0] == "count":
            out.append(("count",))
        else:
            out.append((a[0], *(columns.index(c) for c in a[1:])))
    return tuple(out)


def result_names(engine):
    """The program's key for each aggregate's result, in the query's order
    (``groupby_agg``'s canonical names: ``sum(0)``, ``sum_prod(2,3)``,
    ``count(*)``)."""
    return [f"{a[0]}({','.join(map(str, a[1:]))})" if a[0] != "count"
            else "count(*)" for a in engine]


def accumulator_columns(aggs) -> int:
    """Distinct summed inputs the query needs (a column, a product, or the
    ones column under COUNT and MEAN)."""
    need = set()
    for a in aggs:
        if a[0] == "count":
            need.add(("ones",))
        elif a[0] == "mean":
            need.update({("col", a[1]), ("ones",)})
        elif a[0] == "sum_prod":
            need.add(("prod", a[1], a[2]))
        else:
            need.add(("col", a[1]))
    return len(need)


def _differing(got, want) -> int:
    """How many aggregates of ``got`` differ from ``want`` in any bit."""
    return sum(np.asarray(a).tobytes() != np.asarray(b).tobytes()
               for a, b in zip(got, want))


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, spec):
        self.config, self.mix, self.seed, self.spec = config, mix, seed, spec
        q = mix["query"]
        self.aggs = [tuple(a) for a in q["aggs"]]
        self.columns = reference.agg_columns(self.aggs)
        self.group_by = q["group_by"]
        self.groups = group_count(self.group_by, config["scale"])
        self.results: list = []

    def _query(self, values=None, keys=None):
        import jax
        from repro.ops import groupby_agg

        res = groupby_agg(self.values if values is None else values,
                          self.keys if keys is None else keys, self.groups,
                          aggs=self._aggs, spec=self.spec, method="auto")
        return jax.device_get(res)

    def setup(self) -> None:
        import jax.numpy as jnp

        scale, q = self.config["scale"], self.mix["query"]
        table = data_tpch.lineitem(self.seed, scale["rows"], scale["orders"],
                                   scale["parts"])
        keyed = [] if self.group_by == "none" else [self.group_by]
        rows = q.get("rows")
        sel = data_tpch.select(table, q.get("where", []),
                               None if rows is None else
                               int(self.config[rows]), self.columns + keyed)
        del table
        self.values = jnp.stack([sel[c] for c in self.columns], axis=1)
        self.rows = int(self.values.shape[0])
        self.keys = (sel[self.group_by] if keyed
                     else jnp.zeros(self.rows, jnp.int32))
        self._aggs = engine_aggs(self.aggs, self.columns)
        self.names = result_names(self._aggs)
        for _ in range(WARMUP_QUERIES):
            self._query()

    def window(self, seconds: float, traced: bool) -> Window:
        import jax

        note = (jax.profiler.TraceAnnotation if traced
                else lambda _: contextlib.nullcontext())
        lat, failed, error = [], 0, None
        t0 = time.perf_counter()
        deadline, end = t0 + seconds, t0
        while time.perf_counter() < deadline:
            t = time.perf_counter()
            try:
                with note("bench.query"):
                    out = self._query()
            except Exception as e:  # a query that fails is counted
                failed, error = failed + 1, f"{type(e).__name__}: {e}"
                break
            end = time.perf_counter()
            lat.append(end - t)
            self.results.append([out[k] for k in self.names])
        n = len(lat)
        elapsed = end - t0
        metrics = {}
        if n:
            metrics = {"query_rows_per_s": n * self.rows / elapsed,
                       "query_p95_ms": float(np.percentile(lat, 95)) * 1e3}
        nacc = accumulator_columns(self.aggs)
        work = {"queries": n, "rows": self.rows,
                "query_bytes": cost.query_bytes(
                    self.rows, len(self.columns), self.groups,
                    len(self.aggs)),
                "kernel_bytes": cost.segment_kernel_bytes(
                    self.rows, nacc, self.groups, self.spec.L),
                "kernel_flops": cost.segment_kernel_flops(
                    self.rows, nacc, self.spec.L)}
        return Window(attempted=n + failed, failed=failed, metrics=metrics,
                      work=work, error=error)

    def _permuted(self) -> list:
        """The query's results on a permutation of the same device rows,
        drawn from the seed."""
        import jax

        key = jax.random.fold_in(data_tpch.seed_key(self.seed), 1)
        perm = jax.jit(jax.random.permutation, static_argnums=1)(key,
                                                                 self.rows)
        out = self._query(self.values[perm], self.keys[perm])
        return [out[k] for k in self.names]

    def check(self) -> dict:
        import jax

        if not self.results:
            return {"error_share_of_bound": float(np.finfo(float).max),
                    "count_mismatches": self.groups,
                    "queries_not_bit_identical": 0,
                    "permuted_not_bit_identical": len(self.names)}
        first = self.results[0]
        permuted = _differing(self._permuted(), first)
        cols = jax.device_get({c: self.values[:, i]
                               for i, c in enumerate(self.columns)})
        keys = np.asarray(jax.device_get(self.keys))
        self.values = self.keys = None
        differing = sum(1 for r in self.results[1:] if _differing(r, first))
        s = self.config["spec"]
        ref = reference.GroupReference(cols, keys, self.groups)
        readings = ref.compare(first, self.aggs, s["m"], s["L"], s["W"])
        readings["queries_not_bit_identical"] = differing
        readings["permuted_not_bit_identical"] = permuted
        return readings

    def close(self) -> None:
        self.values = self.keys = None
        self.results = []
