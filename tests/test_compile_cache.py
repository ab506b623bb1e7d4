"""Where the persistent compilation cache goes (repro.utils.compile_cache).

Each case runs in a child process, because JAX reads
``JAX_COMPILATION_CACHE_DIR`` once, when it is imported.
"""

from _subproc import run_with_devices

_COMPILE = """
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.utils.compile_cache import DEFAULT_DIR, enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


def test_env_dir_is_used_and_nothing_else(tmp_path):
    out = run_with_devices(f"""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
{_COMPILE}
before = set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else set()
assert path == {str(tmp_path)!r}, path
assert jax.config.jax_compilation_cache_dir == path
after = set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else set()
assert after == before
print("ENTRIES", len(os.listdir(path)))
""", n_devices=1)
    assert "ENTRIES" in out
    assert int(out.split("ENTRIES")[1].split()[0]) > 0


def test_default_dir_is_fixed_inside_the_checkout():
    out = run_with_devices("""
import os
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax
from repro.utils.compile_cache import DEFAULT_DIR, enable_compile_cache
path = enable_compile_cache()
assert path == str(DEFAULT_DIR) == jax.config.jax_compilation_cache_dir
assert DEFAULT_DIR.name == ".jax_cache"
assert (DEFAULT_DIR.parent / "chip_smoke.py").exists(), DEFAULT_DIR
assert enable_compile_cache() == path  # the same path on every call
print("DEFAULT-OK")
""", n_devices=1)
    assert "DEFAULT-OK" in out
