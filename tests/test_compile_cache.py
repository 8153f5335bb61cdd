"""Where the persistent compilation cache lands (repro.runtime.compile_cache).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX config, and a compile cached here must not leak into other tests.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

from repro.runtime.compile_cache import CACHE_DIR

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.runtime import compile_cache
if len(sys.argv) > 1:
    compile_cache.CACHE_DIR = sys.argv[1]
where = compile_cache.enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((64, 64))).compile()
print(where)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir: str | None, *args: str) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE, *args], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def _entries(d) -> set[str]:
    d = pathlib.Path(d)
    return set(os.listdir(d)) if d.is_dir() else set()


def test_env_dir_is_jax_own_and_only_target(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting stands: the
    helper sets nothing and every entry lands there, none at the repo."""
    target = tmp_path / "jcc"
    before = _entries(CACHE_DIR)
    where, configured = _probe(str(target))
    assert where == configured == str(target)
    assert _entries(target)
    assert _entries(CACHE_DIR) == before


def test_unset_env_uses_fixed_repo_dir(tmp_path):
    """Unset, the cache goes to the fixed ``.jax_cache/`` at the repo root
    (the probe redirects that constant to a temp dir to keep the checkout
    clean, and checks the helper's own default separately)."""
    assert CACHE_DIR == SRC.parent / ".jax_cache"
    target = tmp_path / "repo_cache"
    where, configured = _probe(None, str(target))
    assert where == configured == str(target)
    assert _entries(target)
