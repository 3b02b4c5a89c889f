"""The plain reference: what `correct` is decided against.

The system's promise is "x with ||b - A x|| / ||b|| under the stated
limit, status success". The reference is that sentence computed
directly: A rebuilt with scipy in float64 from the CSR arrays the
benchmark made itself, b as it was handed over, x as it came back.
Nothing of `amgx_tpu` is imported here.

`ReferenceCG` is the plain solver that stands in the program's place in
the lower-precision controls (control.py) and in the tests: textbook
conjugate gradients with every array held in one dtype, stopped by its
own recurrence residual or an iteration limit. Its status is always
"success": only the true residual judges it.
"""
from __future__ import annotations

import sys

import numpy as np

from .entries import Solved


def host_matrix(ro, ci, vals):
    import scipy.sparse as sp
    n = ro.shape[0] - 1
    return sp.csr_matrix((np.asarray(vals, np.float64), ci, ro),
                         shape=(n, n))


def true_relres(M, x, b) -> float:
    """||b - M x||_2 / ||b||_2 in float64."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - M @ x) / np.linalg.norm(b))


def decide(records, log, host_op, inputs, op_dtype, vector_dtype,
           guarantees, out=print):
    """Holds every sampled operation to the configuration's guarantees
    and prints each number compared beside its limit. Returns
    (checked, failed, compared): an operation whose status is not
    success, or whose true residual is over the limit, has failed;
    `compared` is each number the check held to a limit, by a short
    name, with that limit (the largest residual of the sample; the
    operations of the whole window whose status was not success)."""
    ro, ci, base = host_op
    limit = float(guarantees["true_relative_residual"])
    failed = {r["op"] for r in log if not r["ok"]}
    not_success = len(failed)
    for r in log:
        if not r["ok"]:
            out(f"check op={r['op']} status not success FAILED")
    worst, M, M_factor = 0.0, None, None
    for r in records:
        if M is None or M_factor != r["factor"]:
            # the values as the solver got them: scaled, then rounded
            # to the operator's dtype
            M = host_matrix(ro, ci, (base * r["factor"]).astype(op_dtype))
            M_factor = r["factor"]
        b = inputs.rhs[r["rhs"]].astype(vector_dtype)
        x = r["x"]
        finite = bool(np.all(np.isfinite(x))) and x.shape == b.shape
        rr = true_relres(M, x, b) if finite else float("inf")
        good = r["ok"] and rr <= limit
        if not good:
            failed.add(r["op"])
        worst = max(worst, rr)
        out(f"check op={r['op']} rhs={r['rhs']} factor={r['factor']:.6f} "
            f"true_relres={rr:.6e} limit={limit:.1e} "
            f"iterations={r['iterations']} "
            f"{'ok' if good else 'FAILED'}")
    out(f"checked {len(records)} of {len(log)} operations; largest "
        f"true_relres {worst:.6e} against {limit:.1e}")
    compared = {
        # a residual that is not finite is written as the largest float:
        # the result line stays JSON
        "true_relres_max": {"value": min(worst, sys.float_info.max),
                            "limit": limit},
        "status_not_success": {"value": not_success, "limit": 0}}
    return len(records), len(failed), compared


class ReferenceCG:
    """Plain CG on the CSR arrays, every array in `dtype`; an entry like
    those of entries.py, so a control runs through the same harness."""

    def __init__(self, solver: dict, operator: dict):
        self.dtype = solver["dtype"]
        self.max_iters = int(solver["max_iters"])
        self.tol = float(solver["tolerance"])
        self.vector_dtype = np.dtype(operator["dtype"])

    def upload(self, ro, ci, vals, rhs):
        import jax
        import jax.numpy as jnp
        dt = jnp.dtype(self.dtype)
        n = ro.shape[0] - 1
        # by diagonals: the product is one shifted multiply-add for each
        # distinct col - row, which a stencil operator has a handful of
        row = np.repeat(np.arange(n), np.diff(ro))
        offsets, which = np.unique(ci - row, return_inverse=True)
        if offsets.size > 64:
            raise ValueError(f"{offsets.size} diagonals: ReferenceCG is "
                             f"for banded operators")
        diags = np.zeros((offsets.size, n), np.float64)
        diags[which, row] = vals
        diags = jnp.asarray(diags).astype(dt)
        reach = int(np.abs(offsets).max())
        self.rhs = [jnp.asarray(b.astype(self.vector_dtype)).astype(dt)
                    for b in rhs]

        def matvec(v):
            vp = jnp.pad(v, reach)
            y = jnp.zeros_like(v)
            for k, o in enumerate(offsets.tolist()):
                y = y + diags[k] * vp[reach + o:reach + o + n]
            return y

        def cg(b):
            def cond(st):
                k, _x, _r, _p, rr = st
                return (k < self.max_iters) & (
                    jnp.sqrt(rr / rr0).astype(jnp.float32) > self.tol)

            def body(st):
                k, x, r, p, rr = st
                Ap = matvec(p)
                alpha = rr / jnp.vdot(p, Ap)
                x = x + alpha * p
                r = r - alpha * Ap
                rr_new = jnp.vdot(r, r)
                p = r + (rr_new / rr) * p
                return k + 1, x, r, p, rr_new

            rr0 = jnp.vdot(b, b)
            k, x, _r, _p, _rr = jax.lax.while_loop(
                cond, body, (jnp.int32(0), jnp.zeros_like(b), b, b, rr0))
            return x, k

        self._cg = jax.jit(cg)

    def setup(self):
        pass

    def solve(self, i: int):
        import jax
        self.res = jax.block_until_ready(self._cg(self.rhs[i]))

    def last(self) -> Solved:
        x, k = self.res
        return Solved(np.asarray(x).astype(np.float64), int(k), True)

    def solver_tree(self):
        return None

    def close(self):
        pass
