"""Tests for time-chunked recurrent checkpointing and attention TP modes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import configs as cfg_registry
from repro.models import lm
from repro.models.recurrence import chunked_time_scan


def _step(h, x):
    h = h * 0.9 + x
    return h, h * 2.0


@pytest.mark.parametrize("S", [1, 7, 64, 130, 256])
def test_chunked_scan_matches_plain(S):
    rng = np.random.default_rng(S)
    xs = jnp.asarray(rng.standard_normal((S, 3)).astype(np.float32))
    h0 = jnp.zeros((3,), jnp.float32)
    ref_h, ref_ys = lax.scan(_step, h0, xs)
    got_h, got_ys = chunked_time_scan(_step, h0, xs, chunk=64)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(ref_h))
    np.testing.assert_array_equal(np.asarray(got_ys), np.asarray(ref_ys))


def test_chunked_scan_gradients_match():
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((100, 4)).astype(np.float32))
    h0 = jnp.asarray(rng.standard_normal(4).astype(np.float32))

    def loss_plain(h0, xs):
        _, ys = lax.scan(_step, h0, xs)
        return jnp.sum(ys ** 2)

    def loss_chunk(h0, xs):
        _, ys = chunked_time_scan(_step, h0, xs, chunk=16)
        return jnp.sum(ys ** 2)

    g1 = jax.grad(loss_plain)(h0, xs)
    g2 = jax.grad(loss_chunk)(h0, xs)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-6)


@pytest.mark.parametrize("mode", ["replicate", "heads"])
def test_attn_shard_modes_smoke(mode):
    """attn_shard constraints must not change results on a 1-device mesh."""
    cfg = cfg_registry.get_config("smollm-135m").reduced()
    cfg2 = dataclasses.replace(cfg, attn_shard=mode)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                               jnp.int32),
    }
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        l_auto = float(lm.loss_fn(params, batch, cfg)[0])
        l_mode = float(lm.loss_fn(params, batch, cfg2)[0])
    assert np.float32(l_auto).tobytes() == np.float32(l_mode).tobytes()
