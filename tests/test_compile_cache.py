"""The entry points' persistent compilation cache placement."""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_BODY = """
    import os, sys, jax, jax.numpy as jnp
    from repro.launch.compile_cache import place_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(place_compile_cache(sys.argv[1]))
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(checkout, env_dir=None):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_BODY), str(checkout)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip().splitlines()[-1]


def test_cache_lands_in_fixed_checkout_dir(tmp_path):
    where = _run(tmp_path)
    assert where == os.path.join(str(tmp_path), ".jax_cache")
    assert os.listdir(where), "no compiled entry was written"


def test_cache_env_var_wins(tmp_path):
    env_dir = tmp_path / "from_env"
    where = _run(tmp_path / "checkout", env_dir=env_dir)
    assert where == str(env_dir)
    assert os.listdir(env_dir), "no compiled entry was written"
    assert not (tmp_path / "checkout" / ".jax_cache").exists()
