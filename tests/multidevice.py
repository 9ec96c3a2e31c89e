"""Run a test script in a fresh Python process with 8 virtual CPU devices.

The device count is fixed when JAX starts, and the test process itself must
keep the single real CPU device (see conftest.py), so the multi-device
tests run their bodies this way.
"""
import os
import subprocess
import sys
import textwrap

# Prepended to every script.  `jitted(fn, **fixed)` is ``fn`` with its
# configs, sizes and ctx held fixed, compiled as one program per call site —
# as the services run the sharded paths.  Called eagerly, a shard_map body
# runs op by op, each op compiled for the mesh on its own.
_PRELUDE = """\
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import functools
import jax


def jitted(fn, **fixed):
    return jax.jit(functools.partial(fn, **fixed))
"""

# Below the per-test limit in conftest.py: a stuck child is killed, and the
# test fails on its timeout, before the test's own alarm fires.
TIMEOUT_S = 240


def run_script(body: str) -> str:
    """Run ``body`` (dedented) after the prelude; return its stdout, or fail
    with the tail of its output if it exits non-zero."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _PRELUDE + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    return r.stdout
