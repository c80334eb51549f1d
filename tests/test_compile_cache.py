"""Placement of JAX's persistent compilation cache by the entry points.

Each case runs in a fresh interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` when it is imported, and the cache settings
are process-wide.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
returned = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"returned": returned,
                  "configured": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir, action):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        # Cache even a sub-second compile, so that the entry is visible.
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = subprocess.run([sys.executable, "-c", _PROBE, action],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_lands_in_the_directory_the_environment_names(tmp_path):
    where = str(tmp_path / "cc")
    res = _probe(where, "compile")
    assert res == {"returned": where, "configured": where}
    assert os.listdir(where), "no cache entry was written"


def test_cache_defaults_to_a_fixed_directory_in_the_checkout():
    res = _probe(None, "configure")
    expected = os.path.join(REPO, ".jax_cache")
    assert res == {"returned": expected, "configured": expected}
