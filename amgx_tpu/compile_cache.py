"""Where JAX's persistent compilation cache lives: decided outside.

One rule for chip_smoke.py, bench.py, the examples and tests/conftest.py.
If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing
is set in code — a path set in code would win over the environment and
whoever placed the cache (a chip tool that keeps it between calls)
would find it empty. Otherwise the cache is `<checkout>/.jax_cache`
(ignored by git): a fixed path, because the path is part of the cache
key — a directory named after a pid, a time or a tempfile never hits.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable(every_program: bool = False) -> str:
    """Turn the persistent cache on by the rule above; returns the
    directory in force. `every_program` also caches what compiles in
    under a second (JAX's default skips those): the test suite's eager
    setup ops are thousands of such programs, and its xdist workers
    share them through the cache."""
    import jax
    if every_program:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
