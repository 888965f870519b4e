"""Entry points keep JAX's compile cache in one fixed place, so a second
process finds what the first compiled (``repro.cache``)."""
import os
import subprocess
import sys

import pytest

from repro.cache import REPO_CACHE

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PROBE = """
import jax, jax.numpy as jnp
from repro.cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_dir_is_the_env_or_the_repo(tmp_path, env_set):
    cache = tmp_path / "jaxcache"
    # compile only where the cache is a temporary directory; the repo
    # default is checked by path, without writing into the checkout
    returned, configured = _probe(cache if env_set else None, env_set)
    want = str(cache) if env_set else str(REPO_CACHE)
    assert returned == configured == want
    if env_set:
        assert any(cache.iterdir()), "the compiled program was not cached"
