"""repro.enable_compile_cache: JAX_COMPILATION_CACHE_DIR wins where it
is set; otherwise the cache sits at the checkout's fixed .jax_cache.
Each case runs in a child process, so this process's JAX config is
never touched."""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_CHILD = """
import jax, jax.numpy as jnp, repro
print(repro.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
"""


def _run(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(compile=compile_)], env=env,
        capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.split()


def test_cache_lands_in_the_environment_directory(tmp_path):
    returned, configured = _run(tmp_path, True)
    assert returned == configured == str(tmp_path)
    assert os.listdir(tmp_path), "nothing was cached there"


def test_cache_defaults_to_the_checkout():
    # Config only: compiling here would write into the checkout.
    returned, configured = _run(None, False)
    assert returned == configured == os.path.join(ROOT, ".jax_cache")
