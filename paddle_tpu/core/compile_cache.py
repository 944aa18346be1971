"""Where the persistent XLA compilation cache lives — ONE place.

The cache's path is part of its key, so every process of a checkout
must agree on it: `JAX_COMPILATION_CACHE_DIR` when the environment sets
it (jax reads the variable itself; nothing here overrides it), else
`<checkout>/.jax_cache` — never a temp name, a pid or a time. Entry
points call `enable()` once before their first compile
(`chip_smoke.py`, `bench.py` workers, `tools/`, `tests/conftest.py`).
"""
import os

__all__ = ["enable", "cache_dir"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir():
    """The directory `enable()` uses (resolved, not created)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache")


def enable():
    """Turn the persistent compilation cache on and return its path.
    Every compile is kept, however small or quick: a cold machine pays
    each of them again, and the eager path compiles one small program
    per (op, shape)."""
    import jax

    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
