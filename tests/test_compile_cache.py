"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh CPU process: JAX reads the cache directory once,
at its first compile.
"""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import use_compile_cache
used = use_compile_cache()
assert jax.config.jax_compilation_cache_dir == used, jax.config.jax_compilation_cache_dir
{compile}
print(used)
"""

COMPILE = """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = PROBE.format(compile=COMPILE if compile_ else "")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=ROOT, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


def test_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "jax-cache"
    assert _probe(cache, compile_=True) == str(cache)
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_cache_defaults_to_fixed_checkout_dir():
    # no compile: the probe must not write into the checkout
    first, second = _probe(None, compile_=False), _probe(None, compile_=False)
    assert first == second == str(ROOT / ".jax_cache")
