import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: shardings
propagate, collectives partition, and the compiled artifact yields the
memory/cost/collective numbers for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse       # noqa: E402
import json           # noqa: E402
import re             # noqa: E402
import sys            # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import configs as registry                     # noqa: E402
from repro.launch import specs as specs_mod               # noqa: E402
from repro.launch import shardings as sh                  # noqa: E402
from repro.launch.mesh import make_production_mesh, dp_axes  # noqa: E402
from repro.launch.train_step import TrainConfig, make_train_step  # noqa: E402
from repro.models import lm                               # noqa: E402
from repro.models.config import SHAPES                    # noqa: E402

COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\b")
SHAPE_RE = re.compile(r"^\s*%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the optimized HLO.

    Operands are looked up from their defining lines' result shapes.
    Returns {collective_kind: bytes} (global, all devices of one module)."""
    defs = {}
    for line in hlo_text.splitlines():
        m = SHAPE_RE.match(line)
        if m:
            defs[m.group(1)] = _shape_bytes(m.group(2), m.group(3))
    out = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        if m.group(2):  # -start op; the -done line would double count
            pass
        args = re.findall(r"%?([\w.\-]+)", line.split("(", 1)[1]) \
            if "(" in line else []
        n = 0
        for a in args:
            if a in defs:
                n += defs[a]
        if n == 0:
            sm = SHAPE_RE.match(line)
            if sm:
                n = _shape_bytes(sm.group(2), sm.group(3))
        out[kind] = out.get(kind, 0) + n
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               grad_mode: str = "repro_zero2", remat: str = "dots"):
    cfg = registry.get_config(arch)
    if shape_name not in registry.applicable_shapes(cfg):
        return {"arch": arch, "shape": shape_name, "skipped":
                "long_500k requires sub-quadratic decode (DESIGN.md §6)"}
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    # attention TP layout (EXPERIMENTS.md §Perf iter.4): shard KV heads
    # when they divide the model axis; replicate attention otherwise
    import dataclasses as _dc
    if cfg.attn_shard == "auto":
        msize = mesh.shape["model"]
        cfg = _dc.replace(cfg, attn_shard=(
            "heads" if cfg.n_kv_heads % msize == 0 else "replicate"))
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tc = TrainConfig(grad_mode=grad_mode, remat=remat)
            local_step, batch_specs_fn = make_train_step(cfg, tc, mesh, shape)
            p_specs = specs_mod.param_specs(cfg, mesh)
            o_specs = specs_mod.opt_specs(cfg, mesh,
                                          zero=grad_mode == "repro_zero2")
            b_specs = specs_mod.train_batch_specs(cfg, shape, tc, mesh)
            manual = set(dp_axes(mesh))
            o_pspecs = sh.tree_manual_only(
                specs_mod.opt_pspecs(cfg, mesh,
                                     zero=grad_mode == "repro_zero2"),
                manual)
            fn = jax.shard_map(
                local_step, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), p_specs),
                          o_pspecs, batch_specs_fn(b_specs)),
                out_specs=(jax.tree.map(lambda _: P(), p_specs),
                           o_pspecs, P()),
                axis_names=manual, check_vma=False)
            lowered = jax.jit(fn).lower(p_specs, o_specs, b_specs)
        elif shape.kind == "prefill":
            p_specs = specs_mod.param_specs(cfg, mesh)
            b_specs = specs_mod.prefill_batch_specs(cfg, shape, mesh)

            def prefill(params, batch):
                return lm.prefill_step(params, batch, cfg, shape.seq_len)

            # pin the returned caches' shardings: otherwise GSPMD
            # replicates the (units, B, S, KV, hd) fill (see §Perf log)
            out_sh = (specs_mod.logits_sharding(cfg, shape, mesh),
                      specs_mod.cache_shardings(cfg, shape, mesh))
            lowered = jax.jit(prefill, out_shardings=out_sh).lower(
                p_specs, b_specs)
        else:  # decode
            p_specs = specs_mod.param_specs(cfg, mesh)
            c_specs = specs_mod.decode_cache_specs(cfg, shape, mesh)
            b_specs = specs_mod.decode_batch_specs(cfg, shape, mesh)

            def decode(params, caches, batch):
                return lm.decode_step(params, caches, batch, cfg)

            lowered = jax.jit(decode).lower(p_specs, c_specs, b_specs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        coll = collective_bytes(hlo_text)
        try:
            from benchmarks.hlo_cost import analyze_hlo
            corrected = analyze_hlo(hlo_text)
        except Exception as e:   # pragma: no cover — keep raw numbers
            corrected = {"error": repr(e)}

    n_dev = mesh.devices.size
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "grad_mode": grad_mode if shape.kind == "train" else None,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_total": float(cost.get("flops", -1)),
        "bytes_total": float(cost.get("bytes accessed", -1)),
        "collective_bytes": coll,
        "corrected": corrected,      # trip-count-corrected (hlo_cost.py)
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        },
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--grad-mode", default="repro_zero2",
                    choices=["repro_zero2", "repro", "baseline"])
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        archs = [args.arch] if args.arch else registry.list_archs()
        for arch in archs:
            for shape_name in SHAPES:
                cells.append((arch, shape_name, False))
                cells.append((arch, shape_name, True))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape, args.multi_pod))

    results = []
    for arch, shape_name, mp in cells:
        tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
        try:
            rec = lower_cell(arch, shape_name, mp,
                             grad_mode=args.grad_mode, remat=args.remat)
            status = "SKIP" if "skipped" in rec else "OK"
            print(f"[{status}] {tag}: "
                  f"{json.dumps(rec.get('memory', {}))}", flush=True)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if mp else "16x16",
                   "error": repr(e)}
            print(f"[FAIL] {tag}: {e!r}", flush=True)
        results.append(rec)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    failed = [r for r in results if "error" in r]
    print(f"\n{len(results) - len(failed)}/{len(results)} cells OK")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
