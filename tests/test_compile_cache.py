"""launch/compile_cache.py: where the persistent compilation cache lands.

Each case runs in a child process (pinned to the CPU) so the parent's
jax config, which other tests share, is never touched.
"""
import os
import pathlib
import subprocess
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
CHECKOUT_CACHE = SRC.parent / ".jax_cache"

CHILD = """
import jax
import jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("BEFORE", jax.config.jax_compilation_cache_dir)
where = enable_compile_cache()
print("RETURNED", where)
print("CONFIG", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(8)).block_until_ready()
"""


def _child(env_dir, compile_=False):
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CHILD.format(compile=compile_)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_cache_defaults_to_checkout_dir():
    out = _child(None)
    assert out["BEFORE"] == "None"          # importing sets nothing
    assert out["RETURNED"] == str(CHECKOUT_CACHE)
    assert out["CONFIG"] == str(CHECKOUT_CACHE)


def test_cache_follows_env_var(tmp_path):
    out = _child(tmp_path, compile_=True)
    assert out["RETURNED"] == str(tmp_path)
    assert out["CONFIG"] == str(tmp_path)   # JAX's own reading, unchanged
    assert any(tmp_path.iterdir())          # the compile landed there
