"""End-to-end behaviour tests for the whole system."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as registry
from repro.launch import serve
from repro.launch.mesh import make_host_mesh
from repro.launch.train import train_loop
from repro.launch.train_step import TrainConfig
from repro.models import lm
from repro.models.config import ShapeConfig
from repro.optim import adamw as adamw_mod


def test_training_reduces_loss():
    """The full production pipeline (repro_zero2) actually learns."""
    cfg = registry.get_config("smollm-135m").reduced()
    shape = ShapeConfig("t", seq_len=64, global_batch=8, kind="train")
    mesh = make_host_mesh(1, 1)
    tc = TrainConfig(grad_mode="repro_zero2", mb_size=1,
                     adamw=adamw_mod.AdamWConfig(lr=3e-3, warmup_steps=5,
                                                 total_steps=40))
    losses = train_loop(cfg, shape, tc, mesh, steps=40, log_every=10**9)
    first = np.mean([l for _, l in losses[:5]])
    last = np.mean([l for _, l in losses[-5:]])
    assert last < first, (first, last)


def test_generation_end_to_end():
    cfg = registry.get_config("smollm-135m").reduced()
    mesh = make_host_mesh(1, 1)
    with jax.set_mesh(mesh):
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
        toks = serve.generate(params, cfg, prompts, max_seq=24, gen_steps=8)
    assert toks.shape == (2, 8)
    assert np.all(np.asarray(toks) >= 0)
    assert np.all(np.asarray(toks) < cfg.vocab)


def test_repro_embed_training_step():
    """Reproducible embedding gradients (the GROUPBY inside the trainer)."""
    cfg = registry.get_config("smollm-135m").reduced()
    shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")
    mesh = make_host_mesh(1, 1)
    tc = TrainConfig(grad_mode="repro", mb_size=1, repro_embed=True,
                     adamw=adamw_mod.AdamWConfig(total_steps=3))
    losses = train_loop(cfg, shape, tc, mesh, steps=3, log_every=10**9)
    assert all(np.isfinite(l) for _, l in losses)
