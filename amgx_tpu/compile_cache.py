"""Where JAX's persistent compilation cache lives: decided outside.

One rule for chip_smoke.py, the benchmark, the examples and
tests/conftest.py.
If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing
is set in code — a path set in code would win over the environment and
whoever placed the cache (a chip tool that keeps it between calls)
would find it empty. Otherwise the cache is `<checkout>/.jax_cache`
(ignored by git): a fixed path, because the path is part of the cache
key — a directory named after a pid, a time or a tempfile never hits.
"""
from __future__ import annotations

import contextlib
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


@contextlib.contextmanager
def op_names_in_key():
    """Trace, lower and compile, on this thread, a program whose op
    names are read back from the executable that runs
    (`telemetry.programs`: the solve program and its
    `jax.named_scope`s).

    JAX leaves a program's metadata out of the persistent cache's key,
    so that a cached executable survives moved code, and says what that
    costs: "executables loaded from the cache may have stale metadata".
    Such a program cannot take an executable compiled from other names,
    so here the metadata is part of the key; and no Python frames go
    into it, so that the key holds the op names alone: with frames in,
    the same program lowered from another call site (the benchmark's
    warm-up, then its window) is another key, and compiles cold inside
    the window.

    Both settings are taken through JAX's thread-local context
    managers: what other threads trace and compile meanwhile (the
    serving build threads, the thread pool) keeps JAX's defaults, and
    nothing is left changed afterwards. Neither setting is part of
    JAX's trace context, so nothing is retraced for them."""
    from jax._src import config as jax_config
    with jax_config.compilation_cache_include_metadata_in_key(True), \
            jax_config.traceback_in_locations_limit(0):
        yield
