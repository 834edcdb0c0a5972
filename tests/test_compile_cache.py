"""The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
else to one fixed, git-ignored directory inside the checkout."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

from quantum_resistant_p2p_tpu.utils.compile_cache import DEFAULT_DIR

REPO = Path(__file__).resolve().parents[1]

#: enable the cache, compile one small program with the size floor at zero,
#: print where JAX was told to keep it
_CHILD = """
import jax
from quantum_resistant_p2p_tpu.utils.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _child(env_dir: str | None) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


def test_cache_follows_the_environment(tmp_path):
    assert _child(str(tmp_path)) == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_cache_defaults_to_the_checkout():
    assert DEFAULT_DIR == REPO / ".jax_cache"
    assert _child(None) == [str(DEFAULT_DIR)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
