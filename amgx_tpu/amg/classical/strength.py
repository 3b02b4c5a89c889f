"""Strength of connection.

Analog of src/classical/strength/ (strength_base.cu AHAT, ALL,
affinity.cu). AHAT marks a_ij strong when it is a sufficiently large
negative coupling relative to the row's largest one:

    -a_ij >= theta * max_k(-a_ik),   k != i

with the reference's `max_row_sum` weakening: rows whose |row sum| /
|diagonal| exceeds max_row_sum get ALL their connections weakened to
nothing (they are essentially Dirichlet rows). Returns a boolean mask
over the CSR entries — pure segment ops, fully deterministic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import registry
from ...matrix import CsrMatrix
from ...telemetry import metrics as _tm


class Strength:
    def __init__(self, cfg, scope):
        self.theta = float(cfg.get("strength_threshold", scope))
        self.max_row_sum = float(cfg.get("max_row_sum", scope))

    def strong_mask(self, A: CsrMatrix):
        raise NotImplementedError


@registry.strength.register("AHAT")
class AhatStrength(Strength):
    def strong_mask(self, A: CsrMatrix):
        from ...matrix import host_resident
        if not A.is_block and host_resident(
                A.row_offsets, A.col_indices, A.values, A.diag):
            return self._strong_mask_host(A)
        rows, cols, vals = A.coo()
        n = A.num_rows
        offdiag = rows != cols
        # sign convention: couplings opposite in sign to the diagonal are
        # "negative" couplings
        diag = A.diagonal()
        sgn = jnp.sign(jnp.where(diag == 0, 1.0, diag))
        coupling = -vals * sgn[rows]          # >0 for strong-type entries
        coupling = jnp.where(offdiag, coupling, 0.0)
        row_max = jax.ops.segment_max(coupling, rows, num_segments=n,
                                      indices_are_sorted=True)
        row_max = jnp.maximum(row_max, 0.0)
        strong = offdiag & (coupling >= self.theta * row_max[rows]) \
            & (coupling > 0)
        if self.max_row_sum < 1.0:
            rowsum = jax.ops.segment_sum(vals, rows, num_segments=n,
                                         indices_are_sorted=True)
            if A.has_external_diag:
                rowsum = rowsum + A.diag
            weak_row = jnp.abs(rowsum) > self.max_row_sum * jnp.abs(diag)
            # one scalar fetch a level, and only where the rule is on
            _tm.inc("amg.strength.weakened_rows", int(jnp.sum(weak_row)))
            strong = strong & ~weak_row[rows]
        return strong

    def _strong_mask_host(self, A: CsrMatrix):
        """Numpy form of the same mask for host-resident matrices (the
        host-setup path; avoids ~20 eager XLA:CPU dispatches/level).
        The in-line-diagonal case runs as ONE native C++ sweep
        (amgx_strength_ahat) — this is a per-level O(nnz) hot path."""
        import numpy as np
        from ...matrix import _np_row_reduce
        n = A.num_rows
        if not A.has_external_diag and \
                np.asarray(A.values).dtype.kind == "f":
            from ... import native
            out = native.strength_ahat_native(
                n, np.asarray(A.row_offsets), np.asarray(A.col_indices),
                np.asarray(A.values), self.theta, self.max_row_sum)
            if out is not None:
                _tm.inc("amg.strength.weakened_rows", out[1])
                return out[0]
        ro = np.asarray(A.row_offsets)
        cols = np.asarray(A.col_indices)
        vals = np.asarray(A.values)
        rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(ro))
        if A.has_external_diag:
            diag = np.asarray(A.diag)
        else:
            diag = np.zeros(n, vals.dtype)
            dmask = rows == cols
            # reverse order so the FIRST diagonal occurrence wins
            # (padded-duplicate CSR stores the coalesced sum first)
            diag[rows[dmask][::-1]] = vals[dmask][::-1]
        sgn = np.where(diag < 0, -1.0, 1.0)
        offdiag = rows != cols
        coupling = np.where(offdiag, -vals * sgn[rows], 0.0)
        row_max = np.maximum(
            _np_row_reduce(np.maximum, coupling, ro, n, 0.0), 0.0)
        strong = offdiag & (coupling >= self.theta * row_max[rows]) \
            & (coupling > 0)
        if self.max_row_sum < 1.0:
            rowsum = np.bincount(rows, weights=vals, minlength=n)
            if A.has_external_diag:
                rowsum = rowsum + diag
            weak_row = np.abs(rowsum) > self.max_row_sum * np.abs(diag)
            _tm.inc("amg.strength.weakened_rows",
                    int(np.count_nonzero(weak_row)))
            strong = strong & ~weak_row[rows]
        return strong


@registry.strength.register("ALL")
class AllStrength(Strength):
    def strong_mask(self, A: CsrMatrix):
        rows, cols, _ = A.coo()
        return rows != cols


@registry.strength.register("AFFINITY")
class AffinityStrength(Strength):
    """Affinity strength (affinity.cu): smoothed-test-vector affinity
    between neighbors. K test vectors are relaxed a few Jacobi sweeps on
    A z = 0; the affinity |<z_i, z_j>|^2 / (<z_i,z_i><z_j,z_j>) replaces
    the coefficient-based coupling."""

    def __init__(self, cfg, scope):
        super().__init__(cfg, scope)
        self.iters = int(cfg.get("affinity_iterations", scope))
        self.k = int(cfg.get("affinity_vectors", scope))

    def strong_mask(self, A: CsrMatrix):
        import numpy as np
        from ...ops.spmv import spmv
        n = A.num_rows
        rng = np.random.default_rng(12345)
        Z = jnp.asarray(rng.uniform(-1, 1, (self.k, n)), dtype=A.dtype)
        d = A.diagonal()
        dinv = jnp.where(d == 0, 0.0, 1.0 / jnp.where(d == 0, 1.0, d))

        def sweep(_, Z):
            return Z - 0.7 * jax.vmap(lambda z: dinv * spmv(A, z))(Z)

        Z = jax.lax.fori_loop(0, self.iters, sweep, Z)
        rows, cols, _ = A.coo()
        zi = Z[:, rows]
        zj = Z[:, cols]
        num = jnp.sum(zi * zj, axis=0) ** 2
        den = jnp.sum(zi * zi, axis=0) * jnp.sum(zj * zj, axis=0)
        aff = num / jnp.where(den == 0, 1.0, den)
        aff = jnp.where(rows != cols, aff, 0.0)
        row_max = jax.ops.segment_max(aff, rows, num_segments=n,
                                      indices_are_sorted=True)
        return (rows != cols) & (aff >= self.theta * row_max[rows]) \
            & (aff > 0)
