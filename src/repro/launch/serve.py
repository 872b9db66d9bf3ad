"""Batched serving driver: prefill + decode loop with continuous batching.

CLI (CPU demo):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --batch 4 --prompt-len 16 --gen 16

Serving reproducibility note: decode is deterministic per (params, prompt,
positions) by construction (greedy argmax, fixed-shape steps).  The repro
aggregation layer matters on the *training* side; in serving it guarantees
that logits/metrics aggregated across replicas (e.g. eval-loss sweeps)
are replica-count-independent.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as registry
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def generate_with_stats(params, cfg, prompts, max_seq: int, gen_steps: int):
    """Greedy generation for a fixed batch of token prompts (B, P).

    Returns ``(tokens (B, gen_steps), stats)`` where ``stats`` carries the
    serving numbers that matter — TTFT (prompt in to first token out,
    prefill + first argmax, compile included on a cold call) and the decode
    rate over the remaining steps.  Both are also published to
    ``repro.obs.metrics`` (``serve_ttft_seconds``, ``serve_decode_tok_per_s``)
    so a scrape of the registry sees the latest request.
    """
    B, PL = prompts.shape
    t0 = time.perf_counter()
    with obs_trace.span("serve.prefill", batch=int(B), prompt_len=int(PL)):
        logits, caches = jax.jit(
            lambda p, b: lm.prefill_step(p, b, cfg, max_seq))(
                params, {"tokens": prompts})
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        tok.block_until_ready()
    ttft = time.perf_counter() - t0
    out = [tok]

    @jax.jit
    def step(params, caches, tok, pos):
        batch = {"tokens": tok, "positions": pos}
        lg, caches = lm.decode_step(params, caches, batch, cfg)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, caches

    t1 = time.perf_counter()
    with obs_trace.span("serve.decode", batch=int(B),
                        steps=int(gen_steps - 1)):
        for i in range(gen_steps - 1):
            pos = jnp.full((B, 1), PL + i, jnp.int32)
            tok, caches = step(params, caches, tok, pos)
            out.append(tok)
        tok.block_until_ready()
    decode_s = time.perf_counter() - t1
    decode_toks = B * max(gen_steps - 1, 0)
    stats = {"ttft_s": ttft, "decode_s": decode_s,
             "decode_tok_per_s": decode_toks / decode_s if decode_s else 0.0,
             "batch": int(B), "gen_steps": int(gen_steps)}
    obs_metrics.gauge("serve_ttft_seconds").set(ttft)
    obs_metrics.gauge("serve_decode_tok_per_s").set(
        stats["decode_tok_per_s"])
    obs_metrics.histogram("serve_ttft_seconds_hist").observe(ttft)
    obs_metrics.counter("serve_tokens_total").inc(B * gen_steps)
    obs_trace.event("serve.request", **stats)
    return jnp.concatenate(out, axis=1), stats


def generate(params, cfg, prompts, max_seq: int, gen_steps: int):
    """Greedy generation; see :func:`generate_with_stats`."""
    return generate_with_stats(params, cfg, prompts, max_seq, gen_steps)[0]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.embed_frontend == "stub":
        raise SystemExit("serve CLI demo supports token-frontend archs")
    mesh = make_host_mesh(args.data, args.model)
    with jax.set_mesh(mesh):
        params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
        rng = np.random.default_rng(args.seed)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
            jnp.int32)
        t0 = time.time()
        toks, stats = generate_with_stats(params, cfg, prompts,
                                          max_seq=args.prompt_len + args.gen,
                                          gen_steps=args.gen)
        dt = time.time() - t0
    print(f"generated {toks.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(f"TTFT {stats['ttft_s'] * 1e3:.1f}ms (prefill+compile) | decode "
          f"{stats['decode_tok_per_s']:.1f} tok/s over "
          f"{stats['gen_steps'] - 1} steps x batch {stats['batch']}")
    print(np.asarray(toks[0]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
