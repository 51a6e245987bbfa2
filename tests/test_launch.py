"""Entry-point plumbing: where the persistent compile cache is written.

Each case compiles in a child process, so the process-wide cache state
of the test worker is never touched."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CHILD = """
import sys
import repro.launch.compile_cache as cc
cc.REPO_CACHE = sys.argv[1]
print(cc.enable())
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_placement(tmp_path, from_env):
    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CHILD, str(repo_dir)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    want, other = (env_dir, repo_dir) if from_env else (repo_dir, env_dir)
    assert r.stdout.split()[0] == str(want)
    assert any(want.iterdir()) and not other.exists()
