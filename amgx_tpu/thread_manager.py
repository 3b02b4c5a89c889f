"""Host worker threads for asynchronous setup (ThreadManager analog).

The reference's ThreadManager (src/thread_manager.cu) runs smoother
setup as `AsyncSolverSetupTask`s on worker threads so independent level
setups overlap (include/amg_level.h:25-39). The TPU-native analog uses a
shared thread pool: JAX dispatch is thread-safe and asynchronous, so a
background thread can drive the host-orchestration of one solver's
setup (eager dispatches, host syncs) while the caller keeps working —
the device work itself is serialized by the XLA runtime either way, but
the host-side work and the host<->device syncs overlap.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Optional

_lock = threading.Lock()
_pool: Optional[ThreadPoolExecutor] = None


def _get_pool() -> ThreadPoolExecutor:
    global _pool
    with _lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="amgx-setup")
        return _pool


class AsyncSetupTask:
    """Handle to an in-flight setup (AsyncSolverSetupTask analog):
    `wait()` joins and re-raises any setup exception."""

    def __init__(self, future: Future, solver):
        self._future = future
        self.solver = solver

    def done(self) -> bool:
        return self._future.done()

    def wait(self):
        self._future.result()
        return self.solver


def setup_async(solver, A) -> AsyncSetupTask:
    """Run `solver.setup(A)` on a worker thread; returns a task handle.
    The solver must not be used until wait() returns."""
    return AsyncSetupTask(_get_pool().submit(solver.setup, A), solver)


def shutdown():
    global _pool
    with _lock:
        if _pool is not None:
            _pool.shutdown(wait=True)
            _pool = None
