"""Placement of the persistent compilation cache (launch/compile_cache.py).

Each case runs in a fresh interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` once, at import.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
returned = enable_compile_cache()
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(json.dumps([returned, jax.config.jax_compilation_cache_dir]))
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """The environment's directory wins and receives the compiled
    programs; without it the cache is the one fixed in-repo path."""
    env_dir = tmp_path / "cache" if from_env else None
    # only the environment's case compiles: the default case would write
    # into the checkout
    returned, configured = _probe(env_dir, compile_=from_env)
    want = str(env_dir) if from_env else REPO_CACHE_DIR
    assert returned == configured == want
    if from_env:
        assert any(env_dir.iterdir())
    else:
        assert REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
