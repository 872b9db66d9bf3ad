"""Subprocess helper: the sharded GROUPBY over shard and block counts.

Run as:  python tests/_sharded_blocks_check.py

Forces 4 CPU devices and runs TPC-H Q1's aggregate list (and one list with
VAR, MIN and MAX) on 6,007 seeded rows through ``sharded_groupby_agg`` on
meshes of 1, 2 and 4 devices, with the planner's block budget set so that
each shard takes one block, three blocks (the last ragged) or one block a
row.  Prints one JSON object: for each case, whether every result equals
``groupby_agg`` on one device bit for bit, the worst error as a share of
the DESIGN §3 bound against the plain float64 reference, the COUNT
mismatches, and the shard and block counts the call's ``groupby.query``
span reports; and what a repeated call built.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.tpu.reference import GroupReference  # noqa: E402
from repro.core.types import ReproSpec  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.ops import groupby_agg, plan  # noqa: E402
from repro.ops.partial import agg_name  # noqa: E402
from repro.ops.sharded import sharded_groupby_agg  # noqa: E402

assert jax.device_count() == 4, jax.devices()

SPEC = ReproSpec(dtype=jnp.float32, L=2)
M, L, W = 23, 2, 18
N, G = 6_007, 6           # 6,007 rows: no shard count above 1 divides it
COLS = ["qty", "price", "disc_price", "one_plus_tax", "disc"]
Q1 = [("sum", "qty"), ("sum", "price"), ("sum", "disc_price"),
      ("sum_prod", "disc_price", "one_plus_tax"), ("mean", "qty"),
      ("mean", "price"), ("mean", "disc"), ("count",)]
SPREAD = [("var", "price"), ("min", "disc"), ("max", "qty"),
          ("sum", "disc")]

rng = np.random.default_rng(15)
qty = rng.integers(1, 51, N).astype(np.float32)
price = (qty * rng.integers(90_000, 210_000, N) / 100).astype(np.float32)
disc = (rng.integers(0, 11, N) / 100).astype(np.float32)
tax = (rng.integers(0, 9, N) / 100).astype(np.float32)
data = {"qty": qty, "price": price, "disc": disc,
        "disc_price": (price * (1 - disc)).astype(np.float32),
        "one_plus_tax": (1 + tax).astype(np.float32)}
VALUES = np.stack([data[c] for c in COLS], axis=1)
KEYS = rng.integers(0, G, N).astype(np.int32)


def engine(aggs):
    return [(a[0], *(COLS.index(c) for c in a[1:])) for a in aggs]


def budget_for(blocks: str, rows: int) -> int:
    """The planner's budget that gives each ``rows``-row shard the asked
    block count, found through the planner itself."""
    if blocks == "one":
        return plan.BLOCK_BUDGET_BYTES
    if blocks == "row":
        return 1
    chunk = plan.plan_groupby(rows, G + 1, SPEC, ncols=6).chunk
    lo, hi = 1, 1 << 30
    while lo < hi:          # the least budget giving at most three blocks
        mid = (lo + hi) // 2
        r = plan.block_rows(rows, 6, SPEC, chunk, budget=mid)
        lo, hi = (lo, mid) if -(-rows // r) <= 3 else (mid + 1, hi)
    return lo


def run(aggs, shards, blocks, perm=None):
    mesh = jax.make_mesh((shards,), ("data",), devices=jax.devices()[:shards])
    default = plan.BLOCK_BUDGET_BYTES
    plan.BLOCK_BUDGET_BYTES = budget_for(blocks, -(-N // shards))
    try:
        v, k = (VALUES, KEYS) if perm is None else (VALUES[perm], KEYS[perm])
        obs_trace.configure()
        out = sharded_groupby_agg(v, k, G, engine(aggs), SPEC, mesh=mesh)
        root = next(r for r in obs_trace.events()
                    if r["name"] == "groupby.query")
        prog = next(r for r in obs_trace.events()
                    if r["name"] == "groupby.shard_program")
        obs_trace.disable()
    finally:
        plan.BLOCK_BUDGET_BYTES = default
    return out, root["attrs"], prog["attrs"]


def case(aggs, shards, blocks, perm=None):
    out, attrs, _ = run(aggs, shards, blocks, perm)
    names = [agg_name(a) for a in engine(aggs)]
    one = groupby_agg(VALUES, KEYS, G, engine(aggs), SPEC)
    same = all(np.asarray(out[n]).tobytes() == np.asarray(one[n]).tobytes()
               for n in names)
    ref = GroupReference({c: data[c] for c in COLS}, KEYS, G)
    sums = [a for a in aggs if a[0] in ("sum", "sum_prod", "mean", "count")]
    got = [np.asarray(out[agg_name(e)]) for a, e in zip(aggs, engine(aggs))
           if a in sums]
    cmp = ref.compare(got, sums, M, L, W)
    return {"bit_identical": same, "share": cmp["error_share_of_bound"],
            "count_mismatches": cmp["count_mismatches"],
            "shards": attrs["shards"], "blocks": attrs["blocks"]}


result = {}
for shards in (1, 2, 4):
    for blocks in ("one", "three", "row"):
        result[f"q1-{shards}-{blocks}"] = case(Q1, shards, blocks)
perm = np.random.default_rng(16).permutation(N)
result["q1-4-three-permuted"] = case(Q1, 4, "three", perm)
result["spread-2-three"] = case(SPREAD, 2, "three")

built = obs_metrics.counter("repro_groupby_shard_programs_total")
before = built.value
_, _, first = run(Q1, 4, "three")
result["repeat"] = {"built": first["built"],
                    "programs_built": built.value - before}
print(json.dumps(result))
