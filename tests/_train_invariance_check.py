"""Subprocess helper: bitwise mesh-invariance of the FULL training step.

Usage: python tests/_train_invariance_check.py <ndev_data> <grad_mode> [steps]
Prints a hex digest of the final parameters.
"""
import hashlib
import os
import sys

ndev = int(sys.argv[1])
grad_mode = sys.argv[2]
steps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs as registry  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.launch.train_step import TrainConfig  # noqa: E402
from repro.models.config import ShapeConfig  # noqa: E402
from repro.optim import adamw as adamw_mod  # noqa: E402

cfg = registry.get_config("smollm-135m").reduced()
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
mesh = make_host_mesh(data=ndev, model=1)
tc = TrainConfig(grad_mode=grad_mode, mb_size=1,
                 adamw=adamw_mod.AdamWConfig(lr=1e-3, warmup_steps=1,
                                             total_steps=steps))

import jax.numpy as jnp
from repro.launch.train import build_batch
from repro.data.pipeline import DataConfig
from repro.launch.train_step import make_train_step
from repro.launch import shardings as shd, specs as specs_mod
from repro.launch.mesh import dp_axes
from repro.models import lm
from repro.optim import adamw as adamw_mod2
from jax.sharding import PartitionSpec as P, NamedSharding

# one explicit step, hash params (isolates metric-vs-param divergence)
dcfg = DataConfig(seed=7, global_batch=8, seq_len=32, vocab=cfg.vocab)
local_step, batch_specs_fn = make_train_step(cfg, tc, mesh, shape)
with jax.set_mesh(mesh):
    params = lm.init_params(jax.random.PRNGKey(7), cfg)
    opt = adamw_mod2.init(params)
    b = build_batch(dcfg, cfg, 0, 8, 1)
    manual = set(dp_axes(mesh))
    o_pspecs = shd.tree_manual_only(specs_mod.opt_pspecs(cfg, mesh,
        zero=(grad_mode == "repro_zero2")), manual)
    p_pspecs = jax.tree.map(lambda _: P(), params)
    fn = jax.jit(jax.shard_map(local_step, mesh=mesh,
        in_specs=(p_pspecs, o_pspecs, batch_specs_fn(b)),
        out_specs=(p_pspecs, o_pspecs, P()), axis_names=manual,
        check_vma=False))
    for step_i in range(3):
        b = build_batch(dcfg, cfg, step_i, 8, 1)
        params, opt, metrics = fn(params, opt, b)
        hp = hashlib.sha256()
        for leaf in jax.tree.leaves(params):
            hp.update(np.asarray(leaf).tobytes())
        ho = hashlib.sha256()
        for leaf in jax.tree.leaves(opt):
            ho.update(np.asarray(leaf).tobytes())
        print(f"STEP{step_i} P={hp.hexdigest()[:12]} O={ho.hexdigest()[:12]} "
              f"loss={float(metrics['loss'])!r}")

losses = train_loop(cfg, shape, tc, mesh, steps=steps, seed=7,
                    log_every=10**9)
h = hashlib.sha256()
for _, l in losses:
    h.update(np.float64(l).tobytes())
print("LOSSES", h.hexdigest())
