"""GMRES / FGMRES with restart.

Analogs of src/solvers/gmres_solver.cu (407 LoC) and fgmres_solver.cu
(585 LoC; the reference's workhorse outer solver). Design notes for the
TPU re-formulation:

- one `solve_iteration` = one Arnoldi step (iteration-count parity with
  the reference, which counts inner steps);
- the Krylov basis V lives as an (m+1, R, 128) slab, a row one n-vector
  as R rows of 128 lanes (ops/blas.py: dense and contiguous on the
  chip, the leading index a plain address). A step WRITES ONE ROW of it
  (and, flexible, one row of Z) with `dynamic_update_slice` on the
  loop's own buffer, so the write is in place: V and Z are returned by
  no `lax.cond`, and a restart writes row 0 and resets the small state
  (R, cs, sn, g, i), never the slabs;
- step i READS ROWS 0..i and no other: classical Gram-Schmidt with
  reorthogonalisation (CGS2) in three readings of the live rows
  (`blas.cgs2_step`: h = V w; w' = w - V^T h with h2 = V w' from the
  same reading; w'' = w' - V^T h2 with its norm). The live-row count
  is a traced value; on the chip in f32 each reading is one Pallas
  kernel that fetches the live rows of a column block by DMA
  (ops/pallas_spmv.py `_basis_pass_call`), elsewhere its plain twin
  masks the rows. Rows beyond the live ones are stale, never read;
- the Hessenberg column is rotated by all m stored Givens rotations
  (identity-initialized, so "not yet created" rotations are no-ops);
- the estimated residual |g[i+1]| drives convergence (exact for the
  true residual in exact arithmetic), so no extra SpMV per step;
- x is reconstructed only at restart boundaries and once after the loop
  (`finalize`), via a masked m x m triangular solve (R is identity-
  initialized; y is cut to the live columns) and one reading of the
  live rows (`blas.basis_combine`).

GMRES applies the preconditioner at reconstruction time (right
preconditioning with a fixed linear M: x = x0 + M (V^T y)); FGMRES stores
the preconditioned vectors Z (flexible: M may vary per step).

The state counts the basis rows its steps read and wrote
(`basis_rows`) and its restarts; with the step count they ride the
packed stats vector (`_extra_stats_spec`) to the counters
`krylov.arnoldi_steps`, `krylov.basis_rows` and `krylov.restarts`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from .. import registry
from ..ops import blas
from ..ops.spmv import spmv, residual
from .base import Solver


class _GmresBase(Solver):
    uses_preconditioner = True
    flexible = False

    def __init__(self, cfg, scope="default", name="GMRES"):
        super().__init__(cfg, scope, name)
        self.m = int(cfg.get("gmres_n_restart", scope))
        # gmres_krylov_dim caps the stored Krylov basis (reference
        # semantics: 0 = match the restart length)
        kdim = int(cfg.get("gmres_krylov_dim", scope))
        if kdim > 0:
            self.m = min(self.m, kdim)

    def _precond(self, data, r):
        if self.preconditioner is not None:
            return self.preconditioner.apply(data["precond"], r)
        return r

    def computes_residual(self):
        return False

    def internal_res_norm(self, state):
        return state["est_res"]

    # -- state -----------------------------------------------------------
    def _cycle_start(self, beta):
        """The small state of a restart cycle that starts from a
        residual of norm beta."""
        m, dt = self.m, beta.dtype
        return {
            "R": jnp.eye(m, dtype=dt),
            "cs": jnp.ones((m,), dt),
            "sn": jnp.zeros((m,), dt),
            "g": jnp.zeros((m + 1,), dt).at[0].set(beta),
            "i": jnp.zeros((), jnp.int32),
            "est_res": beta,
        }

    def solve_init(self, data, b, x, r):
        m, n = self.m, x.shape[0]
        dt = x.dtype
        rows128 = blas.basis_rows128(m + 1, n)
        beta = blas.nrm2(r)
        # the one whole-slab write of a solve: a step writes one row
        V = jnp.zeros((m + 1, rows128, 128), dt).at[0].set(
            blas.to_slab(r / jnp.where(beta == 0, 1.0, beta), rows128))
        st = {"V": V, "basis_rows": jnp.zeros((), jnp.float32),
              "restarts": jnp.zeros((), jnp.float32)}
        st.update(self._cycle_start(beta))
        st.update(self._guard_init())
        if self.flexible:
            st["Z"] = jnp.zeros((m, rows128, 128), dt)
        return st

    # -- helpers ---------------------------------------------------------
    def _basis_of_x(self, st):
        """The slab the way back to x reads: Z, flexible, else V."""
        return st["Z"] if self.flexible else st["V"]

    def _reconstruct(self, data, x, basis, R, g, nlive):
        """x + the correction of the cycle's first nlive columns. R y =
        g[:m] is solved whole (R is the identity beyond the live
        columns) and y cut to them: the rows behind are stale."""
        m, n = self.m, x.shape[0]
        y = jsl.solve_triangular(R, g[:m], lower=False)
        y = jnp.where(jnp.arange(m) < nlive, y, jnp.zeros((), y.dtype))
        if self.flexible:
            return blas.from_slab(blas.basis_combine(
                basis, y, nlive, blas.to_slab(x, basis.shape[1])), n)
        u = blas.basis_combine(basis, jnp.pad(y, (0, 1)), nlive,
                               jnp.zeros(basis.shape[1:], x.dtype))
        return x + self._precond(data, blas.from_slab(u, n))

    # -- one Arnoldi step -------------------------------------------------
    def solve_iteration(self, data, b, st):
        A = data["A"]
        m = self.m
        i = st["i"]
        V = st["V"]
        n, rows128 = st["x"].shape[0], V.shape[1]
        v_i = blas.from_slab(
            jax.lax.dynamic_index_in_dim(V, i, 0, keepdims=False), n)
        z = self._precond(data, v_i)
        new = dict(st)
        if self.flexible:
            new["Z"] = jax.lax.dynamic_update_index_in_dim(
                st["Z"], blas.to_slab(z, rows128), i, 0)
        w = spmv(A, z)

        # CGS2 against the i + 1 rows built so far, in three readings
        # of them — the TPU-native reformulation of the reference's MGS
        # loop (fgmres_solver.cu), with the second pass restoring
        # MGS-level orthogonality
        h, w, h_last = blas.cgs2_step(V, blas.to_slab(w, rows128), i + 1)
        h = h.at[i + 1].set(h_last)

        # previously stored rotations (identity where not yet created)
        def rot_body(j, h):
            c, s = st["cs"][j], st["sn"][j]
            hj, hj1 = h[j], h[j + 1]
            return h.at[j].set(c * hj + s * hj1).at[j + 1].set(
                -s * hj + c * hj1)

        h = jax.lax.fori_loop(0, m, rot_body, h)

        # new rotation zeroing h[i+1]
        hi = h[i]
        hi1 = h[i + 1]
        denom = jnp.sqrt(hi * hi + hi1 * hi1)
        c = jnp.where(denom == 0, 1.0, hi / jnp.where(denom == 0, 1.0, denom))
        s = jnp.where(denom == 0, 0.0, hi1 / jnp.where(denom == 0, 1.0, denom))
        h = h.at[i].set(c * h[i] + s * h[i + 1]).at[i + 1].set(0.0)
        g = st["g"]
        gi = g[i]
        # a degenerate rotation (rotated Hessenberg column entirely
        # zero) reduces nothing: keep |g| at its old magnitude instead
        # of the identity rotation's -s*gi = 0, which would read as
        # instant (false) convergence
        g = g.at[i].set(c * gi).at[i + 1].set(
            jnp.where(denom == 0, gi, -s * gi))
        small = {
            "R": jax.lax.dynamic_update_slice_in_dim(
                st["R"], h[:m][:, None], i, axis=1),
            "cs": st["cs"].at[i].set(c),
            "sn": st["sn"].at[i].set(s),
            "g": g,
            "i": i + 1,
            "est_res": jnp.abs(g[i + 1]),
        }
        if self.health_guards:
            # Givens/Hessenberg degeneracy with an unconverged residual:
            # the Arnoldi process produced a zero column — exit cleanly
            new["breakdown"] = (denom == 0) & (jnp.abs(gi) > 0)

        # cycle boundary: reconstruct x and start the next cycle from
        # its residual. The branches hand back x, the small state and
        # the ONE row the step writes (each normalises its own, so
        # the row is made where it is handed back); the slabs are read
        # here and returned by neither branch
        restart = i + 1 >= m

        def at_restart(x, basis, w):
            x_new = self._reconstruct(
                data, x, basis, small["R"], small["g"], m)
            r = residual(A, x_new, b)
            beta = blas.nrm2(r)
            return (x_new, self._cycle_start(beta), blas.to_slab(
                r / jnp.where(beta == 0, 1.0, beta), rows128))

        def mid_cycle(x, basis, w):
            return x, small, w / jnp.where(h_last == 0, 1.0, h_last)

        x, small, row = jax.lax.cond(
            restart, at_restart, mid_cycle,
            st["x"], self._basis_of_x(new), w)
        new.update(small)
        new["x"] = x
        new["V"] = jax.lax.dynamic_update_index_in_dim(V, row, small["i"], 0)
        # rows this step read (the three readings of CGS2, at a restart
        # the way back) and wrote
        new["basis_rows"] = st["basis_rows"] + (
            blas.CGS2_BASIS_READS * (i + 1) + (2 if self.flexible else 1)
            + jnp.where(restart, m, 0)).astype(jnp.float32)
        new["restarts"] = st["restarts"] + restart.astype(jnp.float32)
        return new

    # -- the counters' source -------------------------------------------
    def _extra_stats_spec(self):
        return ("arnoldi_steps", "basis_rows", "restarts")

    def _extra_stats(self, final_state):
        return (final_state["iters"], final_state["basis_rows"],
                final_state["restarts"])

    def finalize(self, data, b, state):
        # mid-cycle exit: reconstruct from the live Krylov data; exactly at
        # a restart boundary i==0 and x is the reconstruction itself.
        return jax.lax.cond(
            state["i"] > 0,
            lambda st: self._reconstruct(
                data, st["x"], self._basis_of_x(st), st["R"], st["g"],
                st["i"]),
            lambda st: st["x"],
            state)


@registry.solvers.register("GMRES")
class GMRESSolver(_GmresBase):
    flexible = False


@registry.solvers.register("FGMRES")
class FGMRESSolver(_GmresBase):
    flexible = True
