"""The main path's kernels compile for a TPU v5e chip.

No chip is attached: the TPU compiler is installed and compiles for a chip
that is only described (``jax.experimental.topologies``).  That catches what
interpret mode cannot: block shapes off the (8, 128) tiling, layouts Mosaic
cannot lower, more VMEM than a kernel may use, and an MXU contraction at
the default precision, which rounds f32 operands to bf16 on the chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  The persistent compilation
cache is off around these compiles, because what they write cannot be read
back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.aggregates import segment_table
from repro.core.types import ReproSpec
from repro.kernels.rsum.ops import rsum_table
from repro.kernels.segment_rsum.ops import segment_agg_kernel

Q1_ROWS = 1 << 20        # a Q1-sized batch
Q1_COLS = 6              # Q1's accumulator columns
HBM_BYTES = 16 * 2**30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    x64_was_on = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the chip runs these kernels in 32-bit mode (the suite turns x64 on)
    jax.config.update("jax_enable_x64", False)
    yield desc
    jax.config.update("jax_enable_x64", x64_was_on)
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    assert mem is not None
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES


@pytest.mark.parametrize("groups", [6, 4096])
@pytest.mark.parametrize("L", [2, 3])
def test_segment_kernel_compiles_for_v5e(one_chip, groups, L):
    compiled = segment_agg_kernel.lower(
        _shape(one_chip, (Q1_ROWS, Q1_COLS), jnp.float32),
        _shape(one_chip, (Q1_ROWS,), jnp.int32), groups,
        ReproSpec(dtype=jnp.float32, L=L), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("ncols", [1, Q1_COLS])
@pytest.mark.parametrize("L", [2, 3])
def test_rsum_kernel_compiles_for_v5e(one_chip, ncols, L):
    compiled = rsum_table.lower(
        _shape(one_chip, (Q1_ROWS, ncols), jnp.float32), None, 1,
        ReproSpec(dtype=jnp.float32, L=L), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_onehot_contraction_is_highest_precision(one_chip):
    """The MXU sums the extracted integers exactly only at HIGHEST
    precision; DEFAULT would round them to bf16 on the chip."""
    spec = ReproSpec(dtype=jnp.float32, L=2)

    def onehot(values, ids):
        return segment_table(values, ids, 6, spec, method="onehot")

    lowered = jax.jit(onehot).lower(
        _shape(one_chip, (Q1_ROWS, Q1_COLS), jnp.float32),
        _shape(one_chip, (Q1_ROWS,), jnp.int32))
    dots = [line for line in lowered.as_text().splitlines()
            if "dot_general" in line]
    assert dots
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots)
    compiled = lowered.compile()
    assert "tpu_custom_call" not in compiled.as_text()
    _fits_one_chip(compiled)


def test_sharded_shard_program_compiles_for_v5e_2x2(topo, monkeypatch):
    """The sharded GROUPBY's shard program at TPC-H SF100 Q1's size (590M
    rows over the four chips of a 2x2 host): the Pallas kernel inside its
    row-block loop, the exact all-reduce, and a working set that fits
    beside the resident rows."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import repro.kernels.segment_rsum.ops as kernel_ops
    from repro.ops import plan as plan_mod
    from repro.ops import sharded
    from repro.ops.partial import AggSignature

    # the planner and the kernel ask the CPU backend; steer them to the chip
    monkeypatch.setattr(kernel_ops, "resolve_interpret", lambda _: False)
    spec = ReproSpec(dtype=jnp.float32, L=2)
    sig = AggSignature.build(
        [("sum", 0), ("sum", 1), ("sum", 2), ("sum_prod", 2, 3),
         ("mean", 0), ("mean", 1), ("mean", 4), ("count",)], 6, spec)
    n = 590_000_000
    plan = plan_mod.plan_groupby(n // 4, 7, sig.spec, ncols=sig.ncols,
                                 backend="tpu", calibration=None)
    assert plan.method == "pallas"
    mesh = Mesh(topo.devices[:4], ("data",))
    program, blocks = sharded._shard_program(sig, mesh, "data", n, plan,
                                             None)
    rows = NamedSharding(mesh, PartitionSpec("data"))
    compiled = program.lower(_shape(rows, (n, 5), jnp.float32),
                             _shape(rows, (n,), jnp.int32)).compile()
    text = compiled.as_text()
    assert blocks > 1
    assert "tpu_custom_call" in text and "all-reduce(" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    sharded._shard_program.cache_clear()
