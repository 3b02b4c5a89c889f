"""A probe of the fine-level SpMV, outside the window of a traced run.

Twenty calls of the program's jitted `spmv` on the deepest operator of
the solver tree that carries a DIA value slab, under the `bench.probe`
annotation, so the trace reduction can read their device time. It is a
probe, not a reading of the solve: where the solve's fine level runs
the matrix-free stencil kernels, this still times `_dia_spmv_call`.

`dia_spmv_bytes` is the yardstick's byte count: the arrays the probed
call streams from and to HBM once each (the value slab, x, y), from
their shapes. The padded copy of x the program makes on the way is not
counted: it is the program's own extra traffic, and counting it would
flatter the share.
"""
from __future__ import annotations

from .trace_reduce import PROBE

CALLS = 20


def dia_spmv_bytes(dia_vals, x, y) -> int:
    return int(dia_vals.nbytes + x.nbytes + y.nbytes)


def fine_spmv_probe(tree):
    """{"layout", "k", "n", "bytes"} after running the probe, or None
    where the tree holds no DIA operator."""
    import jax
    import jax.numpy as jnp
    A, node = None, tree
    while node is not None:
        a = getattr(node, "A", None)
        if a is not None and getattr(a, "dia_vals", None) is not None:
            A = a
        node = getattr(node, "preconditioner", None)
    if A is None:
        return None
    from amgx_tpu.ops.spmv import spmv
    f = jax.jit(spmv)
    x = jnp.ones((A.num_cols,), A.dia_vals.dtype)
    y = jax.block_until_ready(f(A, x))       # compiles outside the probe
    with jax.profiler.TraceAnnotation(PROBE):
        for _ in range(CALLS):
            y = f(A, x)
        jax.block_until_ready(y)
    return {"layout": "dia", "k": int(A.dia_vals.shape[0]),
            "n": int(A.num_rows),
            "bytes": dia_spmv_bytes(A.dia_vals, x, y)}
