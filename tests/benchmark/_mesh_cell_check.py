"""Subprocess helper: the ``q1_sf100_mesh4`` cell at a size a CPU test holds,
on 4 host devices, sound and with faults planted in the sharded operator.

Run as:  python tests/benchmark/_mesh_cell_check.py

Prints one JSON object: for each run, ``correct`` and the numbers compared.
The runs: the sound program; the control (L=1 where the configuration
states L=2); a shard program that drops the last of its row blocks; a
merge that leaves shard 0's table out; and a merge that adds the shards'
finalized float32 sums with a plain ``psum`` in place of ``repro_psum``.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", ".."),
                os.path.join(HERE, "..", "..", "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from bench_tiny import run_tiny  # noqa: E402
from repro.core import accumulator as acc_mod  # noqa: E402
from repro.core import collectives  # noqa: E402
from repro.ops import plan, sharded  # noqa: E402

assert jax.device_count() == 4, jax.devices()
CELL = "q1_sf100_mesh4"


class _DropsLastBlock:
    """``jax.lax`` whose ``fori_loop`` over more than one block skips the
    last."""

    def __getattr__(self, name):
        return getattr(lax, name)

    @staticmethod
    def fori_loop(lo, hi, body, init):
        return lax.fori_loop(lo, hi - 1 if hi - lo > 1 else hi, body, init)


def _without_shard0(acc, spec, axes):
    zero = lax.axis_index(axes[0]) == 0
    acc = acc._replace(k=jnp.where(zero, 0, acc.k),
                       C=jnp.where(zero, 0, acc.C))
    return REPRO_PSUM(acc, spec, axes)


def _float_psum(acc, spec, axes):
    """The shards' finalized float32 sums added by ``lax.psum``, put back
    into a table that finalizes to them."""
    s = lax.psum(acc_mod.finalize(acc, spec), axes)
    return acc_mod.from_values(s[..., None], spec, axis=-1)


REPRO_PSUM = collectives.repro_psum
#: a block budget that cuts each shard's ~5,900 tiny-cell rows into 4 blocks
FOUR_BLOCKS = 200_000


def run(name, patches=()):
    """One tiny run of the cell, ``patches`` ``(module, attribute, value)``
    set for its length; the shard programs are built afresh."""
    sharded._shard_program.cache_clear()
    saved = {}
    for mod, attr, value in patches:
        saved[(mod, attr)] = getattr(mod, attr)
        setattr(mod, attr, value)
    try:
        out = run_tiny(CELL, spec_override={"L": 1}
                       if name == "control" else None)
    finally:
        for (mod, attr), value in saved.items():
            setattr(mod, attr, value)
    return {"correct": out["correct"],
            "checks": {k: v["value"] for k, v in out["checks"].items()}}


result = {"sound": run("sound"), "control": run("control")}
result["dropped_block"] = run(
    "dropped_block", [(plan, "BLOCK_BUDGET_BYTES", FOUR_BLOCKS),
                      (sharded, "lax", _DropsLastBlock())])
result["shard_left_out"] = run(
    "shard_left_out", [(collectives, "repro_psum", _without_shard0)])
result["float32_psum"] = run(
    "float32_psum", [(collectives, "repro_psum", _float_psum)])
print(json.dumps(result))
