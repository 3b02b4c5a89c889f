"""Jaxpr census: count what a traced program contains.

The fusion suites prove their HBM-pass claims by *counting*: how many
Pallas kernels of which kind, which XLA primitives run standalone
between them, and which reductions touch full-length vectors outside
any kernel. The walk lives in the package so the tests
(tests/_census.py re-exports it) and chip_smoke.py count the same way.
"""
import re

import numpy as np
from jax.extend import core as jcore

KERNEL_NAME_RE = re.compile(r"name=\"?([A-Za-z_0-9]+)\"?")

# the package's fused Pallas entry points, as their names appear on
# pallas_call eqns (ops/pallas_spmv.py); extend here when a PR adds a
# kernel so every suite's counts see it
KERNEL_KEYS = (
    "_dia_geo_restrict_call",
    "_dia_geo_prolong_call",
    "_dia_smooth_call",
    "_dia_spmv_call",
    "_dia_spmv_dot_call",
    "_cg_update_call",
    "_basis_pass_call",
    "_swell_spmv_call",
    "_swell_smooth_call",
)


def kernel_names(jaxpr):
    """Every `name=...` occurrence in the stringified jaxpr, in trace
    order (pallas_call kernel names plus any other named eqns)."""
    return KERNEL_NAME_RE.findall(str(jaxpr))


def kernel_counts(jaxpr, keys=KERNEL_KEYS):
    """{kernel name: count} over `keys` (exact matches only; names not
    present are absent from the dict, so use .get(k, 0))."""
    out = {}
    for nm in kernel_names(jaxpr):
        if nm in keys:
            out[nm] = out.get(nm, 0) + 1
    return out


def subjaxprs(eqn):
    """Jaxprs nested in an eqn's params (pjit/scan/cond/while bodies)."""
    for p in eqn.params.values():
        for q in (p if isinstance(p, (tuple, list)) else (p,)):
            if isinstance(q, jcore.ClosedJaxpr):
                yield q.jaxpr
            elif isinstance(q, jcore.Jaxpr):
                yield q


def pallas_calls(closed_jaxpr):
    """One {"interpret": bool} per pallas_call eqn of the trace, nested
    jaxprs included — a program compiled for the chip has none in
    interpret mode."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append({"interpret": bool(eqn.params["interpret"])})
                continue
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return out


def outer_prims(closed_jaxpr):
    """All primitive names reachable from the trace WITHOUT descending
    into pallas_call bodies — what runs as standalone XLA ops between
    the kernels."""
    prims = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            prims.append(eqn.primitive.name)
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return prims


def full_vector_reductions(closed_jaxpr, n,
                           prims=("reduce_sum", "reduce_max",
                                  "reduce_min", "dot_general")):
    """Reduction/contraction eqns OUTSIDE pallas_call bodies that
    consume an operand of at least `n` elements — the standalone
    full-vector HBM passes the Krylov-shell fusion removes. Returns
    [(prim_name, [operand shapes])]."""
    hits = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name in prims and any(
                    getattr(v, "aval", None) is not None
                    and v.aval.size >= n for v in eqn.invars):
                hits.append((eqn.primitive.name,
                             [tuple(v.aval.shape) for v in eqn.invars
                              if hasattr(v, "aval")]))
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return hits


def slab_consts(jaxpr, k, lanes=128):
    """Constants shaped like a k-diagonal DIA value slab (k, rows,
    lanes) — the operand a matrix-free trace must not carry."""
    return [v.aval.shape for v in jaxpr.consts
            if np.ndim(v) == 3 and np.shape(v)[0] == k
            and np.shape(v)[-1] == lanes]
