"""Distributed AMG: shard a built hierarchy for SPMD cycles over a mesh.

The reference distributes AMG by making every rank build its partition of
every level (distributed Galerkin RAP with halo-row exchange,
src/classical/classical_amg_level.cu:297-315) and consolidating small
coarse levels onto fewer ranks (include/distributed/glue.h:200), with the
coarsest solve replicated via all_gather
(src/solvers/dense_lu_solver.cu:783-930 `exact_coarse_solve`).

TPU-native redesign: setup is a once-per-structure host-orchestrated
phase on the single controller — the hierarchy (levels, transfer
operators, smoother data) is built globally, then *every level is
partitioned into row-block shards with halo maps*:

- each level's A becomes a square ShardMatrix (halo exchange per SpMV);
- P (fine x coarse) and R (coarse x fine) become rectangular
  ShardMatrices, so restriction/prolongation perform the same
  halo-exchange + local SpMV — the communication pattern of the
  reference's distributed transfer operators;
- smoother device data (Jacobi/L1 dinv, DILU Einv, colorings, CF masks)
  is partitioned row-wise; the masked-SpMV sweeps then execute
  identically per shard, so iteration counts match the single-device
  hierarchy exactly;
- the coarsest level is REPLICATED: the rhs is all_gathered, every shard
  applies the same dense LU redundantly and keeps its slice — precisely
  the reference's exact_coarse_solve.

The multigrid cycle itself (amg/cycles.py) is unchanged: inside
shard_map its SpMVs dispatch to ShardMatrix, its reductions finish with
psum, and the whole V-cycle traces into one SPMD XLA program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import BadParametersError
from ..matrix import CsrMatrix
from ..ops.transpose import transpose
from .dist_matrix import ShardMatrix, shard_matrix_from_partition
from .partition import partition_matrix

# smoother solve-data keys that partition row-wise (leading dim = rows);
# CsrMatrix-valued entries (the ILU factors) shard like the level
# operator itself; _REPLICATED_KEYS are small row-independent arrays
# (polynomial coefficients) that tile across the mesh. Any other key
# (nested preconditioners, global permutations) marks the smoother as
# not distribution-aware.
_ROWWISE_KEYS = {"dinv", "Einv", "colors", "is_coarse", "gs_diag",
                 "u_diag"}
_REPLICATED_KEYS = {"taus"}


def _partition_rowwise(arr, n_ranks: int, n_local: int):
    """Stack a (n, ...) per-row array into (n_ranks, n_local, ...) with
    zero padding on the last shard."""
    a = np.asarray(arr)
    pad = n_ranks * n_local - a.shape[0]
    if pad:
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
    return jnp.asarray(a.reshape((n_ranks, n_local) + a.shape[1:]))


def _shard(A: CsrMatrix, n_ranks: int, axis: str) -> ShardMatrix:
    return shard_matrix_from_partition(partition_matrix(A, n_ranks), axis)


def _replicate(tree, n_ranks: int):
    """Tile every leaf with a leading mesh axis (replicated data). A
    host-built hierarchy (amg_host_setup) holds CPU-committed arrays;
    normalize to the default device so the shard_mapped solve does not
    mix committed placements."""
    def rep(a):
        # host round trip drops any committed placement (host-built
        # hierarchies commit to cpu:0, which jit would refuse to mix
        # with mesh-sharded arguments); replicated levels are small
        a = jnp.asarray(np.asarray(a))
        return jnp.broadcast_to(a[None], (n_ranks,) + a.shape)
    return jax.tree.map(rep, tree)


def gather_global(v_local, axis: str, n_global: int):
    """Shard-local -> replicated global vector (drop padding)."""
    return jax.lax.all_gather(v_local, axis, tiled=True)[:n_global]


def keep_local_slice(v_global, axis: str, n_ranks: int, n_local: int,
                     n_global: int):
    """Replicated global vector -> this shard's padded local slice (the
    single implementation of the replicate-then-keep-local ritual used
    by the consolidation boundary, the exact coarse solve and the
    K-cycle's coarsest matvec)."""
    pad = n_ranks * n_local - n_global
    vp = jnp.pad(v_global, (0, pad))
    r = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice(vp, (r * n_local,), (n_local,))


def _transfer_ops(level):
    """Global P/R of a level. Classical levels carry them; aggregation
    levels materialize P[i, agg[i]] = 1 and R = P^T (the CSR view of the
    aggregate map, aggregation_amg_level.cu:238). Block levels expand
    P to the scalar unknown space (P (x) I_b), matching the
    scalar-expanded distributed operators."""
    if hasattr(level, "P"):
        return level.P, level.R
    agg = np.asarray(level.aggregates)
    n, nc = agg.shape[0], level.coarse_size
    bx = level.A.block_dimx
    if bx > 1:
        # block form P_block[i, agg[i]] = I_b: partition_matrix then
        # scalar-expands P/R with the SAME block-aligned row rounding as
        # the level operators, keeping per-shard vector layouts aligned
        eye = np.broadcast_to(np.eye(bx, dtype=level.A.dtype),
                              (n, bx, bx))
        P = CsrMatrix.from_scipy_like(
            np.arange(n + 1, dtype=np.int32), agg.astype(np.int32),
            jnp.asarray(eye), n, nc, block_dims=(bx, bx))
        order = np.argsort(agg, kind="stable")
        counts = np.bincount(agg, minlength=nc)
        ro = np.zeros(nc + 1, np.int32)
        np.cumsum(counts, out=ro[1:])
        Rm = CsrMatrix.from_scipy_like(
            ro, order.astype(np.int32), jnp.asarray(eye), nc, n,
            block_dims=(bx, bx))
        return P, Rm
    P = CsrMatrix.from_scipy_like(
        np.arange(n + 1, dtype=np.int32), agg.astype(np.int32),
        np.ones(n, level.A.dtype), n, nc)
    return P, transpose(P)


def _shard_smoother_data(sm, A_sh: ShardMatrix, n_ranks: int, axis: str):
    """Partition a smoother's solve-data pytree row-wise; CsrMatrix
    entries (triangular ILU factors) become halo-exchanging shards."""
    data = sm.solve_data_part()
    out = {"A": A_sh}
    # smoother per-row arrays are per BLOCK row (dinv (nb,bx,by),
    # colors (nb,)); the shard stores scalar-expanded rows
    n_local = A_sh.n_local // A_sh.bdimx
    for k, v in data.items():
        if k in ("A", "precond"):
            # 'precond' is rebuilt by the distributed chain walk
            # (solver.py chain_data) — every chain member is admitted
            # and sharded individually
            continue
        if k == "fused":
            # the SINGLE-CHIP quota-padded operand slabs (ops/smooth.py
            # solver_fused_slabs) are global-layout; the sharded fused
            # path carries its own halo-folded per-shard form instead
            # ("dist_fused", attach_shard_fused below the caller)
            continue
        if k == "parity":
            # MULTICOLOR_GS's row-set slabs (ops/parity_sweep.py)
            # are cut from the global grid; a shard sweeps by the row
            # colors, which partition row-wise
            continue
        if isinstance(v, CsrMatrix):
            out[k] = _shard(v, n_ranks, axis)
            continue
        if k in _REPLICATED_KEYS:
            out[k] = _replicate(v, n_ranks)
            continue
        if k not in _ROWWISE_KEYS:
            raise BadParametersError(
                f"distributed AMG: smoother {sm.name} is not "
                f"distribution-aware (data key {k!r}); use BLOCK_JACOBI, "
                f"JACOBI_L1, MULTICOLOR_GS, MULTICOLOR_DILU, "
                f"MULTICOLOR_ILU or CF_JACOBI")
        out[k] = _partition_rowwise(v, n_ranks, n_local)
    return out


class _ConsolidationBoundaryLevel:
    """Wraps the last SHARDED level when coarse-level consolidation is
    on (glue_matrices analog, include/distributed/glue.h:200): its
    restriction all_gathers the coarse rhs so every deeper level runs
    REPLICATED on full vectors (no halo traffic — the right trade once
    a level's per-shard row count is small enough that latency
    dominates), and its prolongation slices the local piece back out.
    The reference merges shards onto sub-communicators; on a TPU mesh
    the latency-optimal merge target is full replication, which is also
    what its exact_coarse_solve does one level further down."""

    def __init__(self, level, axis: str, n_ranks: int, nc_global: int,
                 bx: int = 1):
        self._level = level
        self._axis = axis
        self._n_ranks = n_ranks
        self._nc_global = nc_global
        # per-shard slice must match the block-aligned row rounding of
        # the sharded transfer operators (block rows never split)
        self._nc_local = -(-(nc_global // bx) // n_ranks) * bx

    def __getattr__(self, name):
        return getattr(self._level, name)

    def restrict(self, data, r):
        bc_local = self._level.restrict(data, r)[: self._nc_local]
        return gather_global(bc_local, self._axis, self._nc_global)

    def prolongate(self, data, xc):
        xc_local = keep_local_slice(xc, self._axis, self._n_ranks,
                                    self._nc_local, self._nc_global)
        return self._level.prolongate(data, xc_local)

    # No `prolongate_correct` here, and the wrapped level's must never
    # be reached through __getattr__ delegation — it would correct in
    # ITS (shard-local) space, skipping this wrapper's gather into the
    # replicated-tail numbering. The cycle resolves that one optional
    # method through the CLASS (amg/cycles.py _prolongate_correct), so
    # the plain x + prolongate runs, with the smoother's "dist_fused"
    # payload fusing the sweeps.


class DistributedCoarseSolver:
    """exact_coarse_solve analog (dense_lu_solver.cu:783-930): all_gather
    the coarse rhs, apply the replicated inner solver redundantly on
    every shard, keep the local slice."""

    is_smoother = False

    def __init__(self, inner, axis: str, n_ranks: int, nc_global: int,
                 nc_local: int, coarsest_sweeps: int):
        self.inner = inner
        self.name = "DIST_" + inner.name
        self.axis = axis
        self.n_ranks = n_ranks
        self.nc_global = nc_global
        self.nc_local = nc_local
        self.coarsest_sweeps = coarsest_sweeps

    def gather_apply_slice(self, fn, v):
        """Replicated apply: gather v, run fn on the global vector on
        every shard, keep the local slice."""
        vg = gather_global(v, self.axis, self.nc_global)
        yg = fn(vg)
        return keep_local_slice(yg, self.axis, self.n_ranks,
                                self.nc_local, self.nc_global)

    def apply(self, data, rhs):
        from ..amg.cycles import apply_coarse_solver
        return self.gather_apply_slice(
            lambda bc: apply_coarse_solver(self.inner, data, bc,
                                           jnp.zeros_like(bc),
                                           self.coarsest_sweeps), rhs)


def shard_amg(amg, n_ranks: int, axis: str):
    """Convert a set-up (global) AMG hierarchy for SPMD solving: returns
    the stacked solve-data pytree and rewires the hierarchy's coarse
    solver + transfer dispatch for mesh execution."""
    if isinstance(amg.coarse_solver, DistributedCoarseSolver) or any(
            isinstance(lv, _ConsolidationBoundaryLevel)
            for lv in amg.levels):
        raise BadParametersError(
            "shard_amg: hierarchy is already sharded; re-run setup() "
            "before sharding again")
    # coarse-level consolidation (amg_consolidation_flag +
    # matrix_consolidation_lower_threshold, src/core.cu:316-322): once a
    # level's per-shard row count falls below the threshold, that level
    # and everything deeper run replicated
    boundary = len(amg.levels)
    if bool(amg.cfg.get("amg_consolidation_flag", amg.scope)):
        lower = int(amg.cfg.get("matrix_consolidation_lower_threshold",
                                amg.scope))
        if lower > 0:
            for k, lvl in enumerate(amg.levels):
                if lvl.A.num_rows / n_ranks < lower:
                    boundary = max(k, 1)     # finest level stays sharded
                    break
    levels_data = []
    for k, lvl in enumerate(amg.levels):
        if k >= boundary:                    # replicated (glued) level
            levels_data.append(_replicate(lvl.level_data(), n_ranks))
            continue
        A_sh = _shard(lvl.A, n_ranks, axis)
        P, R = _transfer_ops(lvl)
        ld = {
            "A": A_sh,
            "P": _shard(P, n_ranks, axis),
            "R": _shard(R, n_ranks, axis),
        }
        if lvl.smoother is not None:
            ld["smoother"] = _shard_smoother_data(lvl.smoother, A_sh,
                                                  n_ranks, axis)
            # halo-folded fused-smoother payload (distributed/fused.py):
            # sharded DIA levels run all sweeps + the cycle residual in
            # ONE per-shard kernel with one edge-window exchange;
            # dist_cycle_fusion=0 (or an ineligible layout/smoother)
            # attaches nothing and changes nothing
            from .fused import attach_shard_fused
            attach_shard_fused(ld["smoother"], lvl.A, lvl.smoother,
                               n_ranks, A_sh.n_local, amg.cfg, amg.scope)
        levels_data.append(ld)
    # vectors in the sharded cycle are scalar-expanded: size counts are
    # in scalar unknowns (block rows never split across shards, so the
    # equal-block slicing stays block-aligned)
    nc = amg.coarsest_A.num_rows * amg.coarsest_A.block_dimx
    coarse_data = _replicate(amg.coarse_solver.solve_data_part(), n_ranks)
    if boundary < len(amg.levels):
        # vectors are already global below the boundary: the coarse
        # solver applies directly, and the boundary level's transfers
        # gather/slice across the mesh
        Ab = amg.levels[boundary].A
        nb = Ab.num_rows * Ab.block_dimx
        amg.levels[boundary - 1] = _ConsolidationBoundaryLevel(
            amg.levels[boundary - 1], axis, n_ranks, nb, Ab.block_dimx)
    else:
        bx = amg.coarsest_A.block_dimx
        nc_local = -(-(nc // bx) // n_ranks) * bx
        amg.coarse_solver = DistributedCoarseSolver(
            amg.coarse_solver, axis, n_ranks, nc, nc_local,
            amg.coarsest_sweeps)
    return {"levels": levels_data, "coarse": coarse_data}
