"""Subprocess helper: prove mesh-shape invariance of repro reductions.

Run as:  XLA_FLAGS=--xla_force_host_platform_device_count=<N> \
         python tests/_mesh_invariance_check.py <ndev> [packed]

Prints the finalized sums' raw bytes (hex) — the parent test asserts the hex
is identical across device counts, which plain float psum cannot guarantee.
"""
import os
import sys

ndev = int(sys.argv[1])
packed = len(sys.argv) > 2 and sys.argv[2] == "packed"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import accumulator as acc_mod  # noqa: E402
from repro.core import collectives  # noqa: E402
from repro.core.types import ReproSpec  # noqa: E402

assert jax.device_count() == ndev, jax.devices()

SPEC = ReproSpec(dtype=jnp.float32, L=2)
N_TOTAL, D = 1024, 16     # 1024 microbatch quanta of a 16-dim "gradient"

rng = np.random.default_rng(42)
grads = (rng.standard_normal((N_TOTAL, D)) * np.exp(
    rng.standard_normal((N_TOTAL, 1)) * 3)).astype(np.float32)

mesh = jax.make_mesh((ndev,), ("data",))


def local_reduce(g):
    # per-device: accumulate local quanta into an elementwise accumulator
    acc = acc_mod.from_values(g, SPEC, axis=0)            # batch shape (D,)
    fn = collectives.repro_psum_packed if packed else collectives.repro_psum
    acc = fn(acc, SPEC, ("data",))
    return acc_mod.finalize(acc, SPEC)


out = jax.jit(
    jax.shard_map(local_reduce, mesh=mesh, in_specs=P("data", None),
                     out_specs=P(), check_vma=False),
)(grads)

print(np.asarray(out).tobytes().hex())
