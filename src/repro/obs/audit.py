"""Determinism audit: fresh-process fingerprint attestation (DESIGN.md §13.5).

The reproducibility claims in this repo are enforced in-process by the test
suite; this driver re-checks them the way an operator would — separate OS
processes, adversarial inputs, and the observability layer's *persisted*
fingerprints as the only channel of comparison:

* **GROUPBY family** — one fixed adversarial workload (denormals, exact
  zeros, 60-decade magnitude spread, duplicate-heavy keys) run under
  several execution plans that the paper proves bit-compatible: a fresh
  rerun, a row permutation, a different summation-buffer chunk, and
  explicit strategies overriding the planner.  Every variant runs in its
  own process (fresh XLA compilation cache, fresh RNG state) and writes
  ``fp_groupby_<tag>.json``.
* **Stream family** — the same adversarial rows delivered as 1, 7 and 64
  micro-batches (the 64-batch variant in permuted order) into a
  :class:`repro.stream.StreamStore`, plus a variant that snapshots after
  three batches, restores into a fresh store (restore re-verifies the
  state bytes against the manifest fingerprint) and streams the rest.
  Every variant must fingerprint identically to a one-shot
  ``groupby_agg`` over the concatenated rows — micro-batch count, ingest
  order and restarts are all invisible in the bits.
* **Train family** — a short training run fingerprinted end-to-end
  (chained per-step loss/grad-norm digests + final params/opt), repeated
  in fresh processes, across data-parallel mesh widths
  (``--xla_force_host_platform_device_count``, so every worker of this
  family runs on the CPU backend, ``JAX_PLATFORMS=cpu``), and across the
  reproducible embedding-gradient GROUPBY chunk (``TrainConfig.embed_chunk``
  — the chunk knob that *is* bitwise-invariant, unlike ``xent_chunk``).

Workers that may use an accelerator run one at a time, since a chip
belongs to one process: only where ``JAX_PLATFORMS=cpu`` do they run
concurrently.  The parent never touches a device itself.

The parent diffs the fingerprint files with
:func:`repro.obs.fingerprint.diff_fingerprints` and exits non-zero on any
mismatch.  Each worker also writes its trace (JSONL) and metrics (JSON)
into the output directory, so a CI failure ships the full flight record.

CLI::

  PYTHONPATH=src python -m repro.obs.audit --out audit_out [--quick]
                                           [--skip-train] [--skip-groupby]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# ---------------------------------------------------------------------------
# workload definitions (shared between parent and workers)

GROUPBY_SEED = 0
GROUPBY_G = 129
GROUPBY_L = 3

# (tag, {overrides}) — the base variant comes first; every other variant
# must fingerprint identically to it.
GROUPBY_VARIANTS = [
    ("base", {}),
    ("rerun", {}),                       # fresh process, same plan
    ("permuted", {"permute": True}),     # row order must not matter
    ("chunk8192", {"chunk": 8192}),      # summation-buffer size must not
    ("radix", {"method": "radix"}),      # planner choice must not
    ("onehot", {"method": "onehot"}),
]

# (tag, {overrides}) — ``batches=0`` is the one-shot groupby_agg reference;
# every streamed variant must fingerprint identically to it.
STREAM_VARIANTS = [
    ("oneshot", {"batches": 0}),
    ("batches1", {"batches": 1}),
    ("batches7", {"batches": 7}),
    ("batches64perm", {"batches": 64, "permute_batches": True}),
    ("restart", {"batches": 7, "permute_batches": True,
                 "restart_after": 3}),
]

TRAIN_STEPS = 2
TRAIN_VARIANTS = [
    ("base", {"dp": 1, "embed_chunk": 4096}),
    ("rerun", {"dp": 1, "embed_chunk": 4096}),   # fresh process
    ("dp2", {"dp": 2, "embed_chunk": 4096}),     # mesh width
    ("chunk64", {"dp": 1, "embed_chunk": 64}),   # embed-grad chunk
]


def _groupby_dataset(n: int, permute: bool):
    """Fixed adversarial (values, keys): exact zeros, float32 denormals,
    and magnitudes spanning ~50 decades — the inputs where naive float
    summation is most order-sensitive.  The magnitude ceiling is 1e15, not
    float32-max: ``var`` squares the column, and the reproducibility
    contract covers *finite* accumulator inputs only — a derived column
    that overflows to inf is outside it (DESIGN.md §13.6)."""
    import numpy as np
    rng = np.random.default_rng(GROUPBY_SEED)
    mag = 10.0 ** rng.uniform(-35.0, 15.0, size=n)
    vals = (rng.standard_normal(n) * mag).astype(np.float32)
    vals[rng.integers(0, n, size=n // 16)] = 0.0
    vals[rng.integers(0, n, size=n // 16)] = np.float32(1e-45)  # denormal
    col1 = rng.standard_normal(n).astype(np.float32)
    keys = rng.integers(0, GROUPBY_G, size=n).astype(np.int32)
    if permute:
        # rows move together (key stays with its value): the per-group
        # multisets — and therefore the reproducible result — are unchanged
        perm = np.random.default_rng(GROUPBY_SEED + 1).permutation(n)
        vals, col1, keys = vals[perm], col1[perm], keys[perm]
    return np.stack([vals, col1], axis=1), keys


# ---------------------------------------------------------------------------
# workers (run in fresh subprocesses)

def _worker_groupby(args) -> int:
    import jax.numpy as jnp
    from repro.core.types import ReproSpec
    from repro.obs import fingerprint as obs_fp
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.ops.groupby import groupby_agg

    values, keys = _groupby_dataset(args.n, args.permute)
    spec = ReproSpec(dtype=jnp.float32, L=GROUPBY_L)
    aggs = ("sum", "count", "mean", "var", "min", "max", ("sum", 1))
    results, table = groupby_agg(values, keys, GROUPBY_G, aggs=aggs,
                                 spec=spec, method=args.method,
                                 chunk=args.chunk, return_table=True)
    fps = {
        "groupby/table": obs_fp.fingerprint_table(table, spec),
        "groupby/results": obs_fp.fingerprint_results(results),
    }
    obs_fp.write_fingerprints(
        os.path.join(args.out, f"fp_groupby_{args.tag}.json"), fps,
        manifest=obs_fp.run_manifest(extra={
            "tag": args.tag, "n": args.n, "G": GROUPBY_G,
            "method": args.method, "chunk": args.chunk,
            "permuted": bool(args.permute)}))
    obs_metrics.dump()
    obs_trace.flush()
    return 0


def _worker_stream(args) -> int:
    import jax.numpy as jnp
    import numpy as np
    from repro.core.types import ReproSpec
    from repro.obs import fingerprint as obs_fp
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.ops.groupby import groupby_agg
    from repro.stream import StreamStore

    values, keys = _groupby_dataset(args.n, args.permute)
    spec = ReproSpec(dtype=jnp.float32, L=GROUPBY_L)
    aggs = ("sum", "count", "mean", "var", "min", "max", ("sum", 1))
    if args.batches == 0:
        # one-shot reference: no stream machinery on this path at all
        results, table = groupby_agg(values, keys, GROUPBY_G, aggs=aggs,
                                     spec=spec, return_table=True)
        fps = {"stream/table": obs_fp.fingerprint_table(table),
               "stream/results": obs_fp.fingerprint_results(results)}
    else:
        order = list(range(args.batches))
        if args.permute_batches:
            order = np.random.default_rng(
                GROUPBY_SEED + 2).permutation(args.batches).tolist()
        idx = np.array_split(np.arange(values.shape[0]), args.batches)
        store = StreamStore(GROUPBY_G, aggs=aggs, spec=spec)
        ckdir = os.path.join(args.out, f"ckpt_stream_{args.tag}")
        for pos, b in enumerate(order):
            store.ingest(values[idx[b]], keys[idx[b]])
            if args.restart_after and pos + 1 == args.restart_after:
                store.snapshot(ckdir)
                # a fresh store from the snapshot — restore verifies the
                # state bytes against the manifest fingerprint, then the
                # remaining deltas continue as if nothing happened
                store = StreamStore.restore(ckdir)
        store.query()
        fps = store.fingerprints()
    obs_fp.write_fingerprints(
        os.path.join(args.out, f"fp_stream_{args.tag}.json"), fps,
        manifest=obs_fp.run_manifest(extra={
            "tag": args.tag, "n": args.n, "G": GROUPBY_G,
            "batches": args.batches,
            "permute_batches": bool(args.permute_batches),
            "restart_after": args.restart_after}))
    obs_metrics.dump()
    obs_trace.flush()
    return 0


def _worker_train(args) -> int:
    from repro import configs as registry
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_loop
    from repro.launch.train_step import TrainConfig
    from repro.models.config import ShapeConfig
    from repro.optim import adamw as adamw_mod

    cfg = registry.get_config("smollm-135m").reduced()
    shape = ShapeConfig("audit", seq_len=32, global_batch=4, kind="train")
    mesh = make_host_mesh(args.dp, 1)
    tc = TrainConfig(grad_mode="repro", mb_size=1, repro_embed=True,
                     embed_chunk=args.embed_chunk,
                     adamw=adamw_mod.AdamWConfig(
                         lr=1e-3, total_steps=args.steps, warmup_steps=1))
    train_loop(cfg, shape, tc, mesh, steps=args.steps, seed=0,
               fingerprint_path=os.path.join(
                   args.out, f"fp_train_{args.tag}.json"))
    return 0


# ---------------------------------------------------------------------------
# parent: spawn, collect, diff

def _worker_env(out: str, tag: str, dp: int | None = None) -> dict:
    """The environment of one worker.  ``dp`` (a forced host device count)
    pins the worker to the CPU backend: forced host devices exist only
    there, and every worker of a family that needs them must share one
    backend for its fingerprints to compare."""
    env = dict(os.environ)
    env["REPRO_TRACE"] = os.path.join(out, f"trace_{tag}.jsonl")
    env["REPRO_METRICS"] = os.path.join(out, f"metrics_{tag}.json")
    # isolate (and share among workers) the calibration cache: plan choice
    # may differ with calibration, results must not
    env["REPRO_CALIBRATION_CACHE"] = os.path.join(out, "calibration.json")
    env["REPRO_AUTOTUNE"] = "0"
    if dp is not None:
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={dp}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def _cpu_only(env: dict) -> bool:
    """Whether a worker with this environment is held to the CPU backend
    (decided from the environment alone: the parent stays off JAX)."""
    return env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _job(worker: str, out: str, tag: str, extra_args: list,
         dp: int | None = None) -> tuple:
    """One worker run: (tag, argv, env)."""
    cmd = [sys.executable, "-m", "repro.obs.audit", "--worker", worker,
           "--out", out, "--tag", tag] + extra_args
    return tag, cmd, _worker_env(out, f"{worker}_{tag}", dp)


def _run_family(family: str, jobs: list, serial: bool) -> list:
    """jobs: (tag, argv, env).  Returns failed tags.  CPU-only workers run
    concurrently unless ``serial``; a worker that may use an accelerator
    ends before the next one starts, because a chip belongs to one
    process at a time."""
    failed = []
    procs = []
    for tag, cmd, env in jobs:
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        output = None
        if serial or not _cpu_only(env):
            output = p.communicate()[0]
        procs.append((tag, p, output))
    for tag, p, output in procs:
        if output is None:
            output = p.communicate()[0]
        if p.returncode != 0:
            print(f"[{family}] worker {tag} FAILED (exit {p.returncode}):")
            print(output[-4000:] if output else "  <no output>")
            failed.append(tag)
        else:
            print(f"[{family}] worker {tag} ok")
    return failed


def _diff_family(family: str, out: str, tags: list) -> list:
    from repro.obs.fingerprint import MANIFEST_KEY, diff_fingerprints, \
        read_fingerprints
    base_tag = tags[0]
    base = read_fingerprints(os.path.join(out, f"fp_{family}_{base_tag}.json"))
    man = base.get(MANIFEST_KEY, {})
    print(f"[{family}] base={base_tag} backend={man.get('backend')} "
          f"x64={man.get('x64')} jax={man.get('jax_version')}")
    for name, digest in sorted(base.items()):
        if name != MANIFEST_KEY:
            print(f"[{family}]   {name} = {digest[:16]}…")
    mismatches = []
    for tag in tags[1:]:
        other = read_fingerprints(os.path.join(out, f"fp_{family}_{tag}.json"))
        bad = diff_fingerprints(base, other)
        if bad:
            print(f"[{family}] {base_tag} vs {tag}: MISMATCH on {bad}")
            for k in bad:
                print(f"[{family}]   {k}: {base.get(k)} != {other.get(k)}")
            mismatches.append((tag, bad))
        else:
            print(f"[{family}] {base_tag} vs {tag}: identical")
    return mismatches


def _audit(args) -> int:
    os.makedirs(args.out, exist_ok=True)
    n = 4001 if args.quick else 20001
    t0 = time.time()
    summary = {"groupby": None, "stream": None, "train": None}
    failures = []

    if not args.skip_groupby:
        jobs = []
        for tag, ov in GROUPBY_VARIANTS:
            extra = ["--n", str(n), "--method", ov.get("method", "auto")]
            if ov.get("chunk"):
                extra += ["--chunk", str(ov["chunk"])]
            if ov.get("permute"):
                extra += ["--permute"]
            jobs.append(_job("groupby", args.out, tag, extra))
        failed = _run_family("groupby", jobs, serial=args.serial)
        if failed:
            failures.append(f"groupby workers failed: {failed}")
            summary["groupby"] = "worker_failure"
        else:
            mism = _diff_family("groupby", args.out,
                                [t for t, _ in GROUPBY_VARIANTS])
            summary["groupby"] = "mismatch" if mism else "identical"
            if mism:
                failures.append(f"groupby fingerprints diverged: {mism}")

    if not args.skip_stream:
        jobs = []
        for tag, ov in STREAM_VARIANTS:
            extra = ["--n", str(n), "--batches", str(ov.get("batches", 0))]
            if ov.get("permute_batches"):
                extra += ["--permute-batches"]
            if ov.get("restart_after"):
                extra += ["--restart-after", str(ov["restart_after"])]
            jobs.append(_job("stream", args.out, tag, extra))
        failed = _run_family("stream", jobs, serial=args.serial)
        if failed:
            failures.append(f"stream workers failed: {failed}")
            summary["stream"] = "worker_failure"
        else:
            mism = _diff_family("stream", args.out,
                                [t for t, _ in STREAM_VARIANTS])
            summary["stream"] = "mismatch" if mism else "identical"
            if mism:
                failures.append(f"stream fingerprints diverged: {mism}")

    if not args.skip_train:
        jobs = []
        for tag, ov in TRAIN_VARIANTS:
            extra = ["--steps", str(TRAIN_STEPS), "--dp", str(ov["dp"]),
                     "--embed-chunk", str(ov["embed_chunk"])]
            jobs.append(_job("train", args.out, tag, extra, dp=ov["dp"]))
        # train workers each compile a model: run serially to bound memory
        failed = _run_family("train", jobs, serial=True)
        if failed:
            failures.append(f"train workers failed: {failed}")
            summary["train"] = "worker_failure"
        else:
            mism = _diff_family("train", args.out,
                                [t for t, _ in TRAIN_VARIANTS])
            summary["train"] = "mismatch" if mism else "identical"
            if mism:
                failures.append(f"train fingerprints diverged: {mism}")

    summary["elapsed_s"] = round(time.time() - t0, 1)
    summary["status"] = "fail" if failures else "pass"
    summary["failures"] = failures
    with open(os.path.join(args.out, "audit_summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(f"determinism audit: {summary['status'].upper()} "
          f"in {summary['elapsed_s']}s "
          f"(groupby={summary['groupby']}, stream={summary['stream']}, "
          f"train={summary['train']})")
    if failures:
        for f in failures:
            print(f"  {f}")
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.obs.audit")
    ap.add_argument("--out", required=True)
    ap.add_argument("--quick", action="store_true",
                    help="smaller GROUPBY workload")
    ap.add_argument("--skip-train", action="store_true")
    ap.add_argument("--skip-groupby", action="store_true")
    ap.add_argument("--skip-stream", action="store_true")
    ap.add_argument("--serial", action="store_true",
                    help="run CPU workers one at a time too (workers "
                         "that may use an accelerator always do)")
    # worker mode (internal)
    ap.add_argument("--worker", choices=["groupby", "stream", "train"])
    ap.add_argument("--tag", default="base")
    ap.add_argument("--n", type=int, default=20001)
    ap.add_argument("--method", default="auto")
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--permute", action="store_true")
    ap.add_argument("--batches", type=int, default=0,
                    help="stream worker: micro-batch count (0 = one-shot)")
    ap.add_argument("--permute-batches", action="store_true")
    ap.add_argument("--restart-after", type=int, default=0,
                    help="stream worker: snapshot+restore after this many "
                         "ingested batches")
    ap.add_argument("--steps", type=int, default=TRAIN_STEPS)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--embed-chunk", type=int, default=4096)
    args = ap.parse_args(argv)
    if args.worker == "groupby":
        return _worker_groupby(args)
    if args.worker == "stream":
        return _worker_stream(args)
    if args.worker == "train":
        return _worker_train(args)
    return _audit(args)


if __name__ == "__main__":
    raise SystemExit(main())
