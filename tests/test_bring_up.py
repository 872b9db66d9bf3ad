"""Where kernels run and where compiled programs are cached.

* A Pallas kernel is interpreted only off the TPU: on the CPU backend by
  default or where the caller asks; on a TPU it is always compiled.
* The persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
  where that is set, else in ``<checkout>/.jax_cache``; importing
  ``repro`` turns nothing on.  Each cache case runs in a fresh process,
  since the cache is process-wide configuration.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.kernels import mode

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("asked,got", [(None, True), (True, True),
                                       (False, False)])
def test_interpret_on_cpu(monkeypatch, asked, got):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert mode.resolve_interpret(asked) is got


def test_never_interpreted_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mode.resolve_interpret(None) is False
    assert mode.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="never interpreted"):
        mode.resolve_interpret(True)


def test_other_backends_compile(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert mode.resolve_interpret(None) is False


_PROBE = """
import json, jax, jax.numpy as jnp
import repro
before = jax.config.jax_compilation_cache_dir
from repro.compile_cache import enable_compilation_cache
where = enable_compilation_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"before": before, "where": where,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "xla_cache"
    got = _probe(cache)
    assert got["where"] == got["config"] == str(cache)
    assert any(cache.iterdir()), "no compiled program was cached"


def test_cache_defaults_to_the_checkout():
    from repro.compile_cache import CHECKOUT_CACHE_DIR
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    got = _probe(None)
    assert got["before"] is None, "importing repro turned the cache on"
    assert got["where"] == got["config"] == str(CHECKOUT_CACHE_DIR)
    assert any(CHECKOUT_CACHE_DIR.iterdir())


_WORKER = """
import sys, time
t0 = time.time(); time.sleep(1.0)
open(sys.argv[1], "w").write(f"{t0} {time.time()}")
"""


@pytest.mark.parametrize("cpu_only", [False, True])
def test_audit_runs_accelerator_workers_one_at_a_time(tmp_path, cpu_only):
    """Audit workers that may hold a chip never overlap; CPU-only ones
    run concurrently."""
    from repro.obs import audit
    env = audit._worker_env(str(tmp_path), "probe",
                            dp=1 if cpu_only else None)
    env["JAX_PLATFORMS"] = "cpu" if cpu_only else ""
    spans = [tmp_path / f"{t}.txt" for t in "ab"]
    jobs = [(p.stem, [sys.executable, "-c", _WORKER, str(p)], env)
            for p in spans]
    assert audit._run_family("probe", jobs, serial=False) == []
    (a0, a1), (b0, b1) = (map(float, p.read_text().split()) for p in spans)
    overlap = a0 < b1 and b0 < a1
    assert overlap == cpu_only


def test_audit_forced_devices_pin_the_cpu(tmp_path):
    from repro.obs import audit
    env = audit._worker_env(str(tmp_path), "train", dp=4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert audit._cpu_only(env)
