"""The production training step: shard_map (manual DP axes) + GSPMD TP.

Data/pod axes are *manual* (shard_map) so the gradient reduction is under
our control — that is where the paper's technique lives.  The model axis
stays *auto*: Megatron-style TP comes from the parameter shardings and
GSPMD.  Three gradient paths, selectable per run (the §Perf comparisons):

  repro+zero2 (default) — per-microbatch exact integer reduce-scatter of
      accumulators; optimizer state, master weights and gradient shards all
      live on (data x model)-sharded 1/N slices; bf16 params all-gathered
      after the update.  Bitwise mesh-invariant AND memory-minimal.
  repro (simple)        — accumulate full-shape accumulator trees locally,
      one exact all-reduce at the end.  Bitwise mesh-invariant.
  baseline              — conventional float accumulate + psum (the paper's
      "built-in float" baseline; NOT mesh-invariant).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import accumulator as acc_mod
from repro.core import collectives
from repro.core.accumulator import ReproAcc
from repro.core.types import ReproSpec
from repro.launch import shardings as sh
from repro.launch.mesh import dp_axes, dp_size
from repro.models import lm
from repro.models.config import ModelConfig, ShapeConfig
from repro.obs import trace as obs_trace
from repro.optim import adamw as adamw_mod
from repro.optim import grad as grad_mod


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    grad_mode: str = "repro_zero2"   # repro_zero2 | repro | baseline
    repro_L: int = 2
    repro_W: Optional[int] = None
    mb_size: int = 1                 # sequences per microbatch quantum
    remat: str = "nothing"
    repro_embed: bool = False        # reproducible embedding grads
    packed_wire: bool = False        # packed all-gather wire format
    adamw: adamw_mod.AdamWConfig = adamw_mod.AdamWConfig()
    xent_chunk: int = 512
    embed_chunk: int = 4096          # repro embed-grad GROUPBY chunk

    @property
    def spec(self) -> Optional[ReproSpec]:
        if self.grad_mode == "baseline":
            return None
        return ReproSpec(dtype=jnp.float32, L=self.repro_L, W=self.repro_W)


def _zero_axes(params, data_size: int, dp=("data",), axis_sizes=None):
    """Per-leaf: the tensor dim carrying the ZeRO shard (None = replicated)."""
    def pick(path, leaf):
        spec = sh.zero_pspec(path, leaf, data_size, dp, axis_sizes)
        base = sh.param_pspec(path, leaf)
        if axis_sizes is not None:
            base = sh.validate_pspec(base, leaf.shape, axis_sizes)
        base_entries = list(base) + [None] * (leaf.ndim - len(base))
        for i, (e, b) in enumerate(zip(list(spec) + [None] * leaf.ndim,
                                       base_entries)):
            if e is not None and b is None:
                return i
        return None
    return jax.tree_util.tree_map_with_path(pick, params)


class TrainState:
    """Bundled pytree: params + optimizer + master shards."""
    def __init__(self, params, opt):
        self.params = params
        self.opt = opt


def make_train_step(model_cfg: ModelConfig, train_cfg: TrainConfig,
                    mesh, shape: ShapeConfig):
    """Returns (step_fn, in_specs, out_specs) — step_fn(params, opt, batch)
    -> (params, opt, metrics); wrap in jit with shard_map applied."""
    dpx = dp_axes(mesh)
    dsize = dp_size(mesh)
    axis_sizes = dict(mesh.shape)
    spec = train_cfg.spec
    n_quanta = shape.global_batch // train_cfg.mb_size
    assert shape.global_batch % (train_cfg.mb_size * dsize) == 0, (
        "global batch must divide over DP x microbatch")
    repro_embed = ReproSpec(jnp.float32, L=train_cfg.repro_L) \
        if train_cfg.repro_embed else None

    def grad_fn(params, mb):
        def loss_f(p):
            return lm.loss_fn(p, mb, model_cfg,
                              remat_policy=train_cfg.remat,
                              repro_embed=repro_embed,
                              xent_chunk=train_cfg.xent_chunk,
                              embed_chunk=train_cfg.embed_chunk)
        (loss, aux), grads = jax.value_and_grad(loss_f, has_aux=True)(params)
        return grads, {"loss": loss, "xent": aux["xent"]}

    def _metric_zero():
        """Per-metric accumulator: in repro modes even the *local* sum over
        microbatches is a ReproAcc — a plain float += would round
        differently for different DP widths (caught bitwise by
        test_train_step_dp_width_invariance: params matched, metric did
        not)."""
        return acc_mod.zeros(spec) if spec is not None else \
            jnp.zeros((), jnp.float32)

    def _metric_add(macc, x):
        if spec is None:
            return macc + x
        return acc_mod.merge(macc, acc_mod.from_values(
            x.astype(spec.dtype)[None], spec), spec)

    def _metrics_reduce(m_local_sums):
        """Reproducible global mean of per-quantum metrics; the single
        division is by the static global quantum count."""
        if spec is None:
            return jax.tree.map(
                lambda x: lax.psum(x, dpx) / n_quanta, m_local_sums)

        def red(acc):
            acc = collectives.repro_psum(acc, spec, dpx)
            return acc_mod.finalize(acc, spec) / n_quanta
        return jax.tree.map(red, m_local_sums,
                            is_leaf=lambda x: isinstance(x, ReproAcc))

    def _update(params, opt_state, grads_or_shards, zero_axes, sharded):
        """AdamW with optional ZeRO sharding of moments/master."""
        gnorm = grad_mod.repro_global_norm(
            grads_or_shards, spec) if not sharded else None
        return adamw_mod.update(grads_or_shards, opt_state, params,
                                train_cfg.adamw, grad_norm=gnorm)

    # ------------------------------------------------------------------
    # local step (inside shard_map; data/pod manual, model auto)
    # ------------------------------------------------------------------

    def local_step(params, opt_state, batch):
        # batch leaves: (n_local_micro, mb, ...) after manual sharding.
        # Tracing happens once per compile: the event records the step
        # configuration, and the named scopes label each phase in XLA
        # profiler timelines (zero runtime cost in compiled code).
        obs_trace.event("train.step_config", grad_mode=train_cfg.grad_mode,
                        n_quanta=n_quanta, mb_size=train_cfg.mb_size,
                        dp_size=dsize, repro_L=train_cfg.repro_L,
                        embed_chunk=train_cfg.embed_chunk)
        if train_cfg.grad_mode == "repro_zero2":
            return _zero2_step(params, opt_state, batch)
        with jax.named_scope("repro_grad_accumulate"):
            accs, metrics = grad_mod.accumulate_microbatches(
                grad_fn, params, batch, spec)
        with jax.named_scope("repro_grad_reduce"):
            grads = grad_mod.reduce_grads(accs, spec, dpx, n_quanta,
                                          packed=train_cfg.packed_wire)
            gnorm = grad_mod.repro_global_norm(grads, spec)
        with jax.named_scope("optimizer_update"):
            new_params, new_opt = adamw_mod.update(
                grads, opt_state, params, train_cfg.adamw, grad_norm=gnorm)
        metrics = _metrics_reduce(metrics)
        metrics["grad_norm"] = gnorm
        return new_params, new_opt, metrics

    def _zero2_step(params, opt_state, batch):
        zero_axes = _zero_axes(params, dsize, dpx, axis_sizes)
        # Model-axis pspecs per leaf (+ trailing None for the L dim).
        # Without these constraints GSPMD all-gathers the model dim of the
        # int accumulators before the manual data-axis reduce-scatter
        # (measured +820 GB/dev/step on llama3.2-3b; EXPERIMENTS.md §Perf).

        def _model_pspec(path, leaf):
            base = sh.validate_pspec(sh.param_pspec(path, leaf), leaf.shape,
                                     axis_sizes)
            ent = [e if e == "model" else None for e in
                   list(base) + [None] * (leaf.ndim - len(base))]
            return P(*ent, None)                    # + L dim
        model_pspecs = jax.tree_util.tree_map_with_path(_model_pspec, params)

        def scatter_one(acc, zdim, mspec):
            # Nested shard_map: the model axis becomes *manual* for the
            # reduction, so the data-axis reduce-scatter runs per model
            # shard with replica groups — no mixed-mode GSPMD fallback.
            # (with_sharding_constraint inside partial-manual context was
            # measured to be a no-op; see EXPERIMENTS.md §Perf iter.2.)
            def inner(a):
                if zdim is None:
                    return collectives.repro_psum(a, spec, dpx)
                return collectives.repro_psum_scatter(a, spec, dpx,
                                                      dim=zdim)
            # (the mesh comes from the context: inside the outer shard_map
            # it is the one whose data axes are already manual)
            f = jax.shard_map(
                inner,
                in_specs=(ReproAcc(k=mspec, C=mspec, e1=P()),),
                out_specs=ReproAcc(k=mspec, C=mspec, e1=P()),
                axis_names={"model"}, check_vma=False)
            return f(acc)

        def body(carry, mb):
            shard_accs, msum = carry
            g, m = grad_fn(params, mb)
            accs = grad_mod.tree_to_acc(g, spec)
            accs = jax.tree.map(scatter_one, accs, zero_axes, model_pspecs,
                                is_leaf=lambda x: isinstance(x, ReproAcc))
            shard_accs = grad_mod.acc_merge_tree(shard_accs, accs, spec)
            msum = jax.tree.map(_metric_add, msum, m,
                                is_leaf=lambda x: isinstance(x, ReproAcc))
            return (shard_accs, msum), None

        mb0 = jax.tree.map(lambda x: x[0], batch)
        acc_shapes, m_shapes = jax.eval_shape(
            lambda: (jax.tree.map(
                scatter_one, grad_mod.tree_to_acc(
                    grad_fn(params, mb0)[0], spec), zero_axes, model_pspecs,
                is_leaf=lambda x: isinstance(x, ReproAcc)),
                grad_fn(params, mb0)[1]))
        accs0 = jax.tree.map(
            lambda a: ReproAcc(
                k=jnp.zeros(a.k.shape, a.k.dtype),
                C=jnp.zeros(a.C.shape, a.C.dtype),
                e1=jnp.full(a.e1.shape, spec.lattice_lo, jnp.int32)),
            acc_shapes, is_leaf=lambda x: isinstance(x, ReproAcc))
        m0 = jax.tree.map(lambda _s: _metric_zero(), m_shapes)
        n_local = jax.tree.leaves(batch)[0].shape[0]
        with jax.named_scope("repro_zero2_accumulate_scatter"):
            (shard_accs, msum), _ = lax.scan(body, (accs0, m0), batch)

        # finalize shard grads; update shard master/moments; gather params
        with jax.named_scope("repro_zero2_finalize"):
            g_shards = grad_mod.acc_finalize_tree(shard_accs, spec)
            g_shards = jax.tree.map(lambda g: g / n_quanta, g_shards)
            gnorm = _shard_global_norm(g_shards, zero_axes)

        def slice_shard(p, zdim):
            if zdim is None:
                return p
            nsh = p.shape[zdim] // dsize
            idx = _dp_index()
            return lax.dynamic_slice_in_dim(p, idx * nsh, nsh, axis=zdim)

        p_shards = jax.tree.map(slice_shard, params, zero_axes)
        with jax.named_scope("optimizer_update"):
            new_p_shards, new_opt = adamw_mod.update(
                g_shards, opt_state, p_shards, train_cfg.adamw,
                grad_norm=gnorm)

        def gather(pnew, zdim):
            if zdim is None:
                return pnew
            out = pnew
            for ax in reversed(dpx):
                out = lax.all_gather(out, ax, axis=zdim, tiled=True)
            return out

        with jax.named_scope("zero2_param_allgather"):
            new_params = jax.tree.map(gather, new_p_shards, zero_axes)
        metrics = _metrics_reduce(msum)
        metrics["grad_norm"] = gnorm
        return new_params, new_opt, metrics

    def _dp_index():
        idx = lax.axis_index(dpx[0])
        for ax in dpx[1:]:
            idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
        return idx

    def _shard_global_norm(g_shards, zero_axes):
        """Norm over ZeRO shards.  Replicated (unsharded) leaves contribute
        from device 0 only — multiplying by an index mask keeps the summed
        *values* independent of the DP width (a /dsize rescale would not)."""
        acc = acc_mod.zeros(spec) if spec is not None else None
        total = jnp.zeros((), jnp.float32)
        first = (_dp_index() == 0).astype(jnp.float32)
        for (g, z) in zip(jax.tree.leaves(g_shards),
                          jax.tree.leaves(
                              zero_axes, is_leaf=lambda x: x is None)):
            sq = jnp.square(g.astype(jnp.float32)).reshape(-1)
            if z is None:
                sq = sq * first          # replicated: count exactly once
            if spec is None:
                total = total + jnp.sum(sq)
            else:
                acc = acc_mod.merge(acc, grad_mod.flat_sum_acc(
                    sq.astype(spec.dtype), spec), spec)
        if spec is None:
            return jnp.sqrt(lax.psum(total, dpx))
        acc = collectives.repro_psum(acc, spec, dpx)
        return jnp.sqrt(acc_mod.finalize(acc, spec))

    # ------------------------------------------------------------------
    # shard_map specs
    # ------------------------------------------------------------------

    def batch_specs(batch_tree):
        dp = dpx if len(dpx) > 1 else dpx[0]
        return jax.tree.map(lambda x: P(dp), batch_tree)

    return local_step, batch_specs


def wrap_train_step(local_step, batch_specs_fn, mesh, params_tree,
                    opt_tree, batch_tree, opt_specs=None):
    """Build the jitted shard_map train step with explicit specs."""
    p_specs = jax.tree.map(lambda _: P(), params_tree)
    o_specs = opt_specs if opt_specs is not None else jax.tree.map(
        lambda _: P(), opt_tree)
    b_specs = batch_specs_fn(batch_tree)
    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs, P()),
        axis_names=set(dp_axes(mesh)),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))
