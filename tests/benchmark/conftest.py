"""A test parametrised by a ``cell`` that asks for more chips than this
process has devices runs in a child process that has as many host devices
as the cell has chips (``--xla_force_host_platform_device_count``): the
harness refuses a cell on fewer devices than its chips, and one process
cannot change its device count once JAX has started."""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
_FLAG = "--xla_force_host_platform_device_count"


@functools.cache
def _chips() -> dict:
    """Chips of each cell of ``BENCHMARK.json``."""
    return {w["name"]: w["chips"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]}


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    spec = getattr(pyfuncitem, "callspec", None)
    chips = _chips().get(spec.params.get("cell")) if spec else None
    if not chips or chips <= 1:
        return None
    import jax
    if jax.device_count() >= chips:
        return None
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        [f for f in env.get("XLA_FLAGS", "").split()
         if not f.startswith(_FLAG)] + [f"{_FLAG}={chips}"])
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         pyfuncitem.nodeid], cwd=pyfuncitem.config.rootpath, env=env,
        capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        pytest.fail(f"{pyfuncitem.nodeid} on {chips} host devices:\n"
                    f"{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}",
                    pytrace=False)
    return True
