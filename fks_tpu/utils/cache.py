"""Where the persistent XLA compilation cache lives.

The cache directory is part of the cache key's lookup path, so it is
placed ONCE per process, from outside the program where possible: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
touches it; otherwise every entry point (``cli.main``, ``chipbench.run``,
``chip_smoke.py``) lands on the same fixed directory inside the checkout,
so a second run of any of them finds what the first compiled.
"""
from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the fixed fallback (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(REPO, "benchmarks", "results", ".jax_cache")


def place_compile_cache() -> str:
    """Point this process at the compile cache and return its directory.
    Call before the first compile: JAX binds the cache on first use.

    Wherever the directory comes from, every program is persisted: JAX's
    defaults keep only programs that took over 1 s to compile, and this
    system's are mostly below that (the serve tier's bucket programs
    compile in ~0.3 s each on a v5e), so with the defaults a reloaded
    artifact would re-compile nearly all of them."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
