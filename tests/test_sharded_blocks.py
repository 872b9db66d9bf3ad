"""The sharded GROUPBY's one compiled program, aggregating each shard in
row blocks: bit-identical to ``groupby_agg`` on one device for every shard
count, block count and row order (a subprocess with 4 CPU devices,
``_sharded_blocks_check.py``), built once per signature, and exact at the
carry counts of TPC-H SF100 on four chips."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from fractions import Fraction

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accumulator as acc_mod
from repro.core.types import ReproSpec
from repro.ops import plan

SPEC = ReproSpec(dtype=jnp.float32, L=2)
CASES = [f"q1-{s}-{b}" for s in (1, 2, 4) for b in ("one", "three", "row")]
CASES += ["q1-4-three-permuted", "spread-2-three"]


@pytest.fixture(scope="module")
def sweep():
    script = os.path.join(os.path.dirname(__file__),
                          "_sharded_blocks_check.py")
    res = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_sharded_matches_one_device_and_the_reference(sweep, case):
    r = sweep[case]
    shards, blocks = int(case.split("-")[1]), case.split("-")[2]
    assert r["bit_identical"], r
    assert r["share"] <= 1.0 and r["count_mismatches"] == 0, r
    assert r["shards"] == shards
    rows = -(-6_007 // shards)
    assert r["blocks"] == {"one": 1, "three": 3, "row": rows}[blocks]


def test_a_repeated_call_builds_no_program(sweep):
    assert sweep["repeat"] == {"built": 0, "programs_built": 0}


def test_block_rows_fit_the_budget_and_cover_the_shard():
    p = plan.plan_groupby(147_500_000, 7, SPEC, ncols=6, backend="tpu",
                          calibration=None)
    assert p.method == "pallas"
    r = p.block_rows
    nblocks = -(-147_500_000 // r)
    assert nblocks > 1 and r % p.chunk == 0
    assert (nblocks - 1) * r < 147_500_000       # no empty block
    # a block row: 6 columns twice, 2 levels of int32s, 2 keys: 104 bytes
    assert (r - p.chunk) * 104 <= plan.BLOCK_BUDGET_BYTES
    assert plan.block_rows(1000, 6, SPEC, 128, budget=1) == 1
    assert plan.block_rows(1000, 6, SPEC, 128) == 1000


def test_carries_at_sf100_counts_are_exact():
    """The largest group of TPC-H SF100 Q1 on four chips: the planner's
    block count times its block rows on each of four shards, every row a
    float32 at the top of its lattice.  The block tables add as the shard
    program adds them and the shards merge as ``repro_psum`` does
    (integer sum, renorm); the table must hold the exact sum."""
    rows = 147_500_000
    p = plan.plan_groupby(rows, 7, SPEC, ncols=6, backend="tpu",
                          calibration=None)
    per_block = p.block_rows
    nblocks = -(-rows // per_block)
    v = np.nextafter(np.float32(2.0 ** 30), np.float32(0))  # lattice top
    one = acc_mod.from_values(jnp.asarray([v]), SPEC)
    e1 = int(one.e1)
    assert e1 - SPEC.m + SPEC.W - 1 == 30            # v is admitted, barely
    shift = SPEC.m - 2
    # one block's canonical table: per_block rows of v, exactly
    k_row = [int(x) + (int(c) << shift) for x, c in zip(one.k, one.C)]
    blk = [per_block * x for x in k_row]
    kb = jnp.asarray([x & ((1 << shift) - 1) for x in blk], jnp.int32)
    cb = jnp.asarray([x >> shift for x in blk], jnp.int32)
    k, C = jnp.zeros_like(kb), jnp.zeros_like(cb)
    for _ in range(nblocks):
        k, C = acc_mod.renorm(k + kb, C + cb, SPEC)
    shard = acc_mod.ReproAcc(k=k, C=C, e1=jnp.asarray(e1, jnp.int32))
    total = acc_mod.merge_all([shard] * 4, SPEC)
    n = 4 * nblocks * per_block
    assert n >= 590_000_000
    ulps = [Fraction(2) ** (e1 - lvl * SPEC.W - SPEC.m)
            for lvl in range(SPEC.L)]
    held = sum((int(kk) + (int(cc) << shift)) * u
               for kk, cc, u in zip(total.k, total.C, ulps))
    assert held == n * Fraction(float(v))
    assert int(jnp.max(total.C)) < 2 ** 31 - 1
    got = float(acc_mod.finalize(total, SPEC))
    exact = float(n * Fraction(float(v)))
    assert abs(got - exact) <= 2 * SPEC.L * 2.0 ** -(SPEC.m + 1) * exact


def test_count_past_float32_is_exact():
    """COUNT of the largest SF100 group after the merge (more rows than
    float32 holds exactly: 2^24) comes back as the exact integer."""
    from repro.ops.partial import AggSignature, PartialState, finalize

    n = 590_000_001                              # odd, past 2^29
    one = acc_mod.from_values(jnp.ones(1, jnp.float32), SPEC)
    shift = SPEC.m - 2
    total = [n * (int(x) + (int(c) << shift)) for x, c in zip(one.k, one.C)]
    table = acc_mod.ReproAcc(
        k=jnp.asarray([[[x & ((1 << shift) - 1) for x in total]]],
                      jnp.int32),
        C=jnp.asarray([[[x >> shift for x in total]]], jnp.int32),
        e1=jnp.full((1, 1), one.e1, jnp.int32))
    sig = AggSignature.build([("count",)], 1, SPEC)
    state = PartialState(table=table, minv=jnp.zeros((1, 0), jnp.float32),
                         maxv=jnp.zeros((1, 0), jnp.float32),
                         rows=jnp.asarray(n, jnp.int32), sig=sig)
    out = finalize(state)
    assert out["count(*)"].dtype == jnp.int32
    assert int(out["count(*)"][0]) == n
    assert float(jnp.float32(n)) != n            # float32 would round it
