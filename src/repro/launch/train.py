"""End-to-end trainer: config system, checkpoint/restart, elastic resume.

CLI:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --reduced --data 1 --model 1 \
      --ckpt-dir /tmp/run1 [--grad-mode repro_zero2] [--resume]

``--reduced`` swaps in the smoke-scale config so the driver runs on CPU;
on real hardware the same driver drives the full config on the production
mesh.  The loop is wrapped in the failure supervisor: any step may raise,
and the run resumes from the last checkpoint with a bitwise-identical
trajectory (the paper's reproducibility guarantee doing systems work).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import logging
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as registry
from repro.checkpoint import ckpt as ckpt_mod
from repro.data.pipeline import DataConfig, synth_batch
from repro.launch import shardings as sh
from repro.launch import specs as specs_mod
from repro.launch.mesh import dp_axes, dp_size, make_host_mesh, \
    make_production_mesh
from repro.launch.train_step import TrainConfig, make_train_step
from repro.models import lm
from repro.models.config import ModelConfig, ShapeConfig
from repro.obs import fingerprint as obs_fp
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import adamw as adamw_mod
from repro.runtime.failures import run_supervised, SimulatedFailure
from repro.runtime.stragglers import StragglerMonitor

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class RunState:
    params: object
    opt: object
    step: int


def build_batch(dcfg: DataConfig, model_cfg: ModelConfig, step: int,
                n_quanta: int, mb_size: int):
    """Global batch tensor tree: (n_quanta, mb, ...)."""
    batch = synth_batch(dcfg, step, 0, n_quanta * mb_size)
    out = {}
    for k, v in batch.items():
        out[k] = v.reshape(n_quanta, mb_size, *v.shape[1:])
    if model_cfg.rope_kind == "mrope" and "positions" not in out:
        S = dcfg.seq_len
        out["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (n_quanta, mb_size, 3, S))
    return out


def train_loop(model_cfg: ModelConfig, shape: ShapeConfig,
               train_cfg: TrainConfig, mesh, *, steps: int,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
               resume: bool = False, seed: int = 0,
               fail_at: Optional[int] = None, log_every: int = 10,
               fingerprint_path: Optional[str] = None):
    """Returns the list of (step, loss).

    ``fingerprint_path``: when set, the run's determinism attestation is
    written there on completion — a chained digest of the per-step
    (loss, grad_norm) pairs plus bitwise fingerprints of the final params
    and optimizer state, with the run manifest (DESIGN.md §13.3).  Two runs
    whose files agree took bit-identical trajectories; the CI
    determinism-audit lane diffs these files across reruns and mesh widths.
    """
    dcfg = DataConfig(seed=seed, global_batch=shape.global_batch,
                      seq_len=shape.seq_len, vocab=model_cfg.vocab,
                      embed_dim=(model_cfg.d_model
                                 if model_cfg.embed_frontend == "stub"
                                 else 0),
                      mrope=model_cfg.rope_kind == "mrope")
    n_quanta = shape.global_batch // train_cfg.mb_size

    local_step, batch_specs_fn = make_train_step(model_cfg, train_cfg,
                                                 mesh, shape)
    p_shardings = sh.param_shardings(mesh, jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(seed), model_cfg)))
    zero = train_cfg.grad_mode == "repro_zero2"
    o_specs_tree = specs_mod.opt_pspecs(model_cfg, mesh, zero=zero)
    o_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               o_specs_tree, is_leaf=lambda x: isinstance(x, P))
    manual = set(dp_axes(mesh))
    b0 = build_batch(dcfg, model_cfg, 0, n_quanta, train_cfg.mb_size)

    p_pspecs = jax.tree.map(lambda _: P(), p_shardings)
    o_pspecs = sh.tree_manual_only(o_specs_tree, manual)
    step_fn = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(p_pspecs, o_pspecs, batch_specs_fn(b0)),
        out_specs=(p_pspecs, o_pspecs, P()),
        axis_names=manual, check_vma=False), donate_argnums=(0, 1))

    def fresh() -> RunState:
        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda: lm.init_params(jax.random.PRNGKey(seed), model_cfg),
                out_shardings=p_shardings)()
            opt = jax.jit(adamw_mod.init,
                          out_shardings=o_shardings)(params)
        return RunState(params=params, opt=opt, step=0)

    def restore() -> Optional[RunState]:
        if not (ckpt_dir and resume):
            return None
        latest = ckpt_mod.latest_step(ckpt_dir)
        if latest is None:
            return None
        skeleton = {
            "params": jax.eval_shape(
                lambda: lm.init_params(jax.random.PRNGKey(seed), model_cfg)),
            "opt": jax.eval_shape(
                adamw_mod.init, jax.eval_shape(
                    lambda: lm.init_params(jax.random.PRNGKey(seed),
                                           model_cfg))),
        }
        shardings = {"params": p_shardings, "opt": o_shardings}
        tree, extra = ckpt_mod.restore(ckpt_dir, skeleton,
                                       shardings=shardings)
        log.info("restored step %d from %s", extra["step"], ckpt_dir)
        return RunState(params=tree["params"], opt=tree["opt"],
                        step=int(extra["step"]))

    losses = []
    fail_armed = [fail_at]
    final_state: dict = {}
    # chained per-step fingerprint: order-sensitive by construction (a
    # trajectory is a sequence), bitwise-sensitive via the array digests
    traj = hashlib.sha256(obs_fp.MAGIC + b"trajectory\0")
    monitor = StragglerMonitor([f"host{jax.process_index()}"])

    def one_step(state: RunState, step: int) -> RunState:
        if fail_armed[0] is not None and step == fail_armed[0]:
            fail_armed[0] = None          # fire once, then recover
            raise SimulatedFailure(f"injected failure at step {step}")
        t0 = time.perf_counter()
        with obs_trace.span("train.step", step=step) as sp:
            with obs_trace.span("train.build_batch", step=step):
                batch = build_batch(dcfg, model_cfg, step, n_quanta,
                                    train_cfg.mb_size)
            with jax.set_mesh(mesh):
                params, opt, metrics = step_fn(state.params, state.opt,
                                               batch)
            loss_arr = np.asarray(metrics["loss"])
            gnorm_arr = np.asarray(metrics["grad_norm"])
            sp.set(loss=float(loss_arr), grad_norm=float(gnorm_arr))
        dt = time.perf_counter() - t0
        loss = float(loss_arr)
        traj.update(np.int64(step).tobytes())
        traj.update(obs_fp.fingerprint_array(loss_arr, "loss").encode())
        traj.update(obs_fp.fingerprint_array(gnorm_arr, "gnorm").encode())
        obs_metrics.histogram("train_step_seconds").observe(dt)
        obs_metrics.counter("train_steps_total").inc()
        obs_metrics.gauge("train_loss").set(loss)
        obs_metrics.gauge("train_grad_norm").set(float(gnorm_arr))
        monitor.record_step({f"host{jax.process_index()}": dt})
        losses.append((step, loss))
        if step % log_every == 0:
            log.info("step %d loss %.4f gnorm %.3f", step, loss,
                     float(gnorm_arr))
        new_state = RunState(params=params, opt=opt, step=step + 1)
        final_state["state"] = new_state
        return new_state

    def save(state: RunState, step: int):
        if ckpt_dir:
            ckpt_mod.save(ckpt_dir, step,
                          {"params": jax.tree.map(np.asarray, state.params),
                           "opt": jax.tree.map(np.asarray, state.opt)},
                          extra={"step": step})

    run_supervised(fresh, restore if resume else lambda: None,
                   one_step, save, total_steps=steps,
                   ckpt_every=ckpt_every)
    if fingerprint_path and "state" in final_state:
        st = final_state["state"]
        fps = {
            "loss_trajectory": traj.hexdigest(),
            "params": obs_fp.fingerprint_pytree(
                jax.tree.map(np.asarray, st.params)),
            "opt": obs_fp.fingerprint_pytree(
                jax.tree.map(np.asarray, st.opt)),
        }
        obs_fp.write_fingerprints(
            fingerprint_path, fps,
            manifest=obs_fp.run_manifest(extra={
                "steps": len(losses), "grad_mode": train_cfg.grad_mode,
                "mb_size": train_cfg.mb_size,
                "mesh": {k: int(v) for k, v in mesh.shape.items()},
                "seed": seed}))
        log.info("wrote run fingerprints to %s", fingerprint_path)
    obs_metrics.dump()
    obs_trace.flush()
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pod", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mb-size", type=int, default=1)
    ap.add_argument("--grad-mode", default="repro_zero2",
                    choices=["repro_zero2", "repro", "baseline"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--fingerprints", default=None, metavar="PATH",
                    help="write the run's determinism fingerprints "
                         "(loss trajectory + final params/opt) to PATH")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh
            else make_host_mesh(args.data, args.model, args.pod))
    tc = TrainConfig(grad_mode=args.grad_mode, mb_size=args.mb_size,
                     adamw=adamw_mod.AdamWConfig(
                         lr=args.lr, total_steps=args.steps,
                         warmup_steps=max(1, args.steps // 10)))
    t0 = time.time()
    losses = train_loop(cfg, shape, tc, mesh, steps=args.steps,
                        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                        resume=args.resume, seed=args.seed,
                        fail_at=args.fail_at,
                        fingerprint_path=args.fingerprints)
    dt = time.time() - t0
    print(f"trained {len(losses)} steps in {dt:.1f}s; "
          f"first loss {losses[0][1]:.4f} -> last {losses[-1][1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
