"""Distributed solver wrapper: run any supported solver tree SPMD over a
device mesh.

The reference runs one MPI rank per GPU, each executing the same solver
code against its partition (SURVEY §2.6). Here a single program is
shard_mapped over a 1-D `jax.sharding.Mesh` axis: the *same* solver
classes trace their solve loop per shard, `ops.spmv` dispatches to the
halo-exchanging ShardMatrix, and the BLAS reductions finish with psum via
the collective-axis context — the MPI_Allreduce analog. Host code stays
single-controller (no mpirun).

Round-1 scope: Krylov solvers (CG/BiCGSTAB/GMRES/FGMRES/PCG/PCGF/
PBICGSTAB) with NOSOLVER / BLOCK_JACOBI / JACOBI_L1 preconditioning.
Distributed AMG arrives with the coarse-consolidation layer.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..config import Config
from ..errors import BadParametersError
from ..matrix import CsrMatrix
from ..solvers.base import SolveResult, make_solver
from . import comms
from .dist_matrix import ShardMatrix, shard_matrix_from_partition
from .partition import (partition_matrix, partition_vector,
                        unpartition_vector)

# preconditioners with hand-built per-shard data (diagonal-derived);
# ANY other solver is admitted when its solve-data partitions row-wise
# (the same data-driven test the distributed AMG smoother sharding
# uses, amg.py _shard_smoother_data) — matching the reference's
# any-tree-any-rank-count composability (include/solvers/solver.h:271)
_DIAG_PRECONDS = {"NOSOLVER", "DUMMY", "BLOCK_JACOBI", "JACOBI",
                  "JACOBI_L1", "AMG"}


def default_mesh(n_devices: Optional[int] = None, axis: str = "p",
                 devices=None) -> Mesh:
    devs = devices if devices is not None else jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise BadParametersError(
            f"default_mesh: {n} devices requested but only {len(devs)} "
            f"visible ({devs[0].platform}); on CPU force virtual devices "
            "before any jax call (see _cpu_backend.force_cpu)")
    return Mesh(np.array(devs[:n]), (axis,))


class DistributedSolver:
    """Solve A x = b with row-block domain decomposition over a mesh."""

    def __init__(self, cfg: Config, mesh: Mesh, scope: str = "default"):
        self.cfg = cfg
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_ranks = mesh.devices.size
        name, sscope = cfg.get_solver("solver", scope)
        # process-wide span-fencing mode, latched both ways like
        # create_solver (env toggle ORed in; see telemetry/spans.py)
        from ..telemetry import spans as _spans
        _spans.set_sync(bool(int(cfg.get("telemetry_sync", sscope)))
                        or _spans.env_sync())
        self.solver = make_solver(name, cfg, sscope)
        if self.solver.scaling not in ("NONE", ""):
            raise BadParametersError(
                "distributed solve: scaling is not yet supported (the "
                "distributed path bypasses Solver.setup; scale the system "
                "before partitioning)")
        # non-diagonal preconditioners are validated data-driven at
        # setup time (their solve-data must partition row-wise)
        self._fn = None

    # -- setup -----------------------------------------------------------
    def setup(self, A: CsrMatrix):
        if not A.initialized:
            A = A.init()
        return self.setup_from_partition(
            partition_matrix(A, self.n_ranks), _global_A=A)

    def setup_from_partition(self, part, _global_A: Optional[CsrMatrix]
                             = None):
        """Set up from per-rank pieces (a DistPartition built by
        partition_from_pieces — the AMGX_matrix_upload_distributed
        path). With the sharded hierarchy build no global matrix is
        needed; configs that fall back to the controller-global setup
        require one and raise without it."""
        t0 = time.perf_counter()
        A = _global_A
        if part.n_ranks != self.n_ranks:
            raise BadParametersError(
                f"partition has {part.n_ranks} ranks, mesh has "
                f"{self.n_ranks}")
        self.shard_A = shard_matrix_from_partition(part, self.axis)
        self.part = part
        self._upload_user_colors = (A is not None
                                    and A.user_colors is not None)
        # wire the solver chain: A views + per-shard Jacobi data. AMG
        # members build their hierarchy SHARDED when the config supports
        # it (distributed/setup.py — per-rank level build, no global
        # coarse operator); otherwise the hierarchy is built on the
        # GLOBAL matrix on the controller, then every level is sharded
        # (distributed/amg.py — the round-2 fallback path).
        self._sharded_amg = {}
        self._precond_shard_data = {}
        s = self.solver
        while s is not None:
            if s.name not in _DIAG_PRECONDS and s is not self.solver:
                # data-driven admission: set up on the global matrix,
                # shard the solve-data row-wise (raises when a data key
                # does not partition by rows)
                if A is None:
                    raise BadParametersError(
                        f"distributed preconditioner {s.name} from "
                        "per-rank pieces is not supported (its setup "
                        "needs the global matrix on the controller)")
                from .amg import _shard_smoother_data
                s._owns_scaling = False
                s.setup(A)
                self._precond_shard_data[id(s)] = _shard_smoother_data(
                    s, self.shard_A, self.n_ranks, self.axis)
            if s.name == "AMG":
                data = self._try_sharded_setup(s, A)
                if data is not None:
                    self._sharded_amg[id(s)] = data
                elif A is not None:
                    s.amg.setup(A)
                else:
                    raise BadParametersError(
                        "distributed AMG from per-rank pieces requires "
                        "the sharded setup (this config fell back to "
                        "the controller-global path, which needs the "
                        "global matrix); see distributed_setup_mode")
            s.A = self.shard_A           # duck-typed operator view
            s = s.preconditioner
        self._data = self._place(self._build_data())
        self._fn = None
        self._comms_table = None      # filled at first (re)trace
        self._shard_stats = self._compute_shard_stats(part)
        self.setup_time = time.perf_counter() - t0
        return self

    def _compute_shard_stats(self, part):
        """Per-shard rows/nnz tallies + imbalance gauges (host
        arithmetic on the partition's index metadata, setup-time
        only). max/mean imbalance is the load-balance number the
        per-chip-throughput attribution reads: a shard at 1.3x mean
        nnz IS a 1.3x per-chip gate on a bandwidth-bound sweep."""
        from ..telemetry import metrics as _tm
        R, nl, n = part.n_ranks, part.n_local, part.n_global
        rows = [min((r + 1) * nl, n) - min(r * nl, n) for r in range(R)]
        rid_own = np.asarray(part.rid_own)
        rid_halo = np.asarray(part.rid_halo)
        nnz = (np.sum(rid_own < nl, axis=1)
               + (np.sum(rid_halo < nl, axis=1)
                  if rid_halo.size else np.zeros(R, np.int64)))
        nnz = [int(v) for v in nnz]
        rows_imb = max(rows) / max(np.mean(rows), 1e-300)
        nnz_imb = max(nnz) / max(np.mean(nnz), 1e-300) if max(nnz) \
            else 1.0
        _tm.set_gauge("dist.shard.rows_imbalance", round(rows_imb, 4))
        _tm.set_gauge("dist.shard.nnz_imbalance", round(nnz_imb, 4))
        return {"rows": rows, "nnz": nnz,
                "rows_imbalance": round(float(rows_imb), 4),
                "nnz_imbalance": round(float(nnz_imb), 4)}

    def _try_sharded_setup(self, s, global_A=None):
        """Run the per-shard hierarchy build when the config supports it
        (distributed_setup_mode=auto|sharded). Returns the stacked AMG
        solve-data, or None to fall back to the global-setup path.
        `global_A` (absent on the pieces path) only feeds the finest
        level's halo-folded fused-smoother payload."""
        from .setup import build_sharded_hierarchy, sharded_eligible
        mode = str(self.cfg.get("distributed_setup_mode", s.amg.scope))
        if mode == "global":
            return None
        reason = sharded_eligible(s.amg, self.shard_A)
        if reason is None and getattr(self, "_upload_user_colors", False):
            names = {s.amg.cfg.get_solver(k, s.amg.scope)[0].upper()
                     for k in ("smoother", "fine_smoother",
                               "coarse_smoother")}
            if any(n.startswith("MULTICOLOR") or n == "FIXCOLOR_GS"
                   for n in names):
                # a user-attached coloring (AMGX_matrix_attach_coloring)
                # must drive the color-sweep smoothers; the sharded
                # setup always runs its own JPL — fall back so the
                # attached colors are honored (single-device _color()
                # semantics). Jacobi-family smoothers never read
                # colors, so they stay sharded-eligible.
                reason = ("user-attached matrix coloring requires the "
                          "global setup")
        # aggregation decisions need |a_ji| == |a_ij|; the classical
        # reverse-edge strength additionally uses the owned value's
        # SIGN as the transpose proxy, so it needs signed symmetry
        if reason is None and not self._value_symmetry_probe(
                signed=s.amg.algorithm == "CLASSICAL"):
            # the sharded selectors assume |a_ji| = |a_ij| (setup.py
            # module docs); on value-asymmetric matrices their decisions
            # would silently diverge from the single-device path —
            # fail fast / fall back instead
            reason = ("matrix is not value-symmetric (sharded setup "
                      "decisions assume |a_ji| = |a_ij|)")
        if reason is not None:
            if mode == "sharded":
                raise BadParametersError(
                    f"distributed_setup_mode=sharded: {reason}")
            return None
        data = build_sharded_hierarchy(s.amg, self.shard_A, self.mesh,
                                       self.axis, global_A=global_A)
        if data is None and mode == "sharded":
            raise BadParametersError(
                "distributed_setup_mode=sharded: problem too small for "
                "one sharded level (fits a single shard's budget)")
        return data

    def _value_symmetry_probe(self, signed: bool = False) -> bool:
        """Randomized on-device symmetry check: <y, A x> == <x, A y>
        for symmetric A (shard_mapped SpMVs + psum dots — no global
        matrix is ever materialized, preserving the pieces path's
        contract). The sharded selectors assume value symmetry
        (setup.py module docs; the classical reverse-edge strength
        additionally relies on signs), and a generically asymmetric
        matrix fails this probe with probability ~1 — it then falls
        back to the global setup (auto) or raises (sharded). The probe
        is signed-strict, so a |.|-symmetric sign-flipped matrix also
        falls back: conservative, and correct for the Notay weights
        which read signed values.

        TWO independent probe pairs must both agree, and the dots
        accumulate in f64 regardless of the value dtype: with f64
        accumulation the probe's own rounding no longer grows with
        sqrt(n) (only the SpMV's per-row rounding in the value dtype
        remains), so the tolerance is a small dtype-eps multiple instead
        of the old 100*sqrt(n)*eps — at 128^3/f32 that was ~2e-2
        relative slack, wide enough to wave through mildly nonsymmetric
        f32 matrices whose selector decisions then silently diverged."""
        from . import comms
        from ..ops.spmv import spmv
        del signed    # the dot probe is signed-strict for all callers
        n = self.part.n_global
        R = self.n_ranks
        axis = self.axis

        def body(M, xs, ys):
            Ml = jax.tree.map(lambda a: a[0], M)
            x64 = xs[0].astype(jnp.float64)
            y64 = ys[0].astype(jnp.float64)
            with comms.collective_axis(axis):
                ax = spmv(Ml, xs[0]).astype(jnp.float64)
                ay = spmv(Ml, ys[0]).astype(jnp.float64)
                s1 = jax.lax.psum(jnp.vdot(y64, ax), axis)
                s2 = jax.lax.psum(jnp.vdot(x64, ay), axis)
                norms2 = jax.lax.psum(jnp.stack([
                    jnp.vdot(x64, x64), jnp.vdot(y64, y64),
                    jnp.vdot(ax, ax), jnp.vdot(ay, ay)]), axis)
            return jnp.concatenate([jnp.stack([s1, s2]), norms2])

        pspec = jax.tree.map(lambda _: P(axis), self.shard_A)
        fn = jax.jit(shard_map(
            body, mesh=self.mesh, in_specs=(pspec, P(axis), P(axis)),
            out_specs=P(), check_vma=False))
        vdt = np.dtype(self.shard_A.va_own.dtype)
        if vdt.kind != "f":
            vdt = np.dtype(np.float64)
        tol = max(1e-12, 100.0 * np.finfo(vdt).eps)
        for seed in (0xA317, 0x5C12):
            rng = np.random.default_rng(seed)
            xl = partition_vector(rng.standard_normal(n), R,
                                  self.part.n_local)
            yl = partition_vector(rng.standard_normal(n), R,
                                  self.part.n_local)
            s1, s2, nx2, ny2, nax2, nay2 = (
                float(v) for v in fn(self.shard_A, xl, yl))
            scale = max(abs(s1), abs(s2), 1e-300)
            # the probe's own noise floor: the value-dtype SpMV rounding
            # reaches the f64 dots as |y^T δ(Ax)| <~ eps_v * ||y||*||Ax||
            # — without this term a symmetric matrix whose quadratic
            # form happens to cancel (|s1| << ||y||*||Ax||) would be
            # misclassified as asymmetric
            floor = 100.0 * np.finfo(vdt).eps * max(
                np.sqrt(ny2 * nax2), np.sqrt(nx2 * nay2))
            if abs(s1 - s2) > max(tol * scale, floor):
                return False
        return True

    def _place(self, tree):
        """Put a stacked (n_ranks, ...) pytree on the mesh, slice r of
        every leaf on device r. Without it the stacks sit where they
        were built — the default device — and every solve re-shards
        them: invisible on virtual CPU devices, one chip's HBM holding
        all four chips' data on a real host."""
        return jax.device_put(
            tree, NamedSharding(self.mesh, P(self.axis)))

    def _build_data(self):
        """Hand-build the solve-data pytree (stacked arrays); per-shard
        Jacobi inverses come from the partitioned diagonal."""
        def chain_data(s):
            d = {"A": self.shard_A}
            if s.name in ("BLOCK_JACOBI", "JACOBI"):
                if self.part.diag_block is not None:
                    # block-exact Jacobi: batched inverse of the block
                    # diagonal, partitioned by block rows
                    from ..ops.dense import safe_inverse
                    d["dinv"] = safe_inverse(self.part.diag_block)
                else:
                    d["dinv"] = _dinv(self.part.diag)
            elif s.name == "JACOBI_L1":
                if self.part.diag_block is not None:
                    raise BadParametersError(
                        "distributed JACOBI_L1: scalar matrices only; "
                        "use BLOCK_JACOBI for block systems")
                d["dinv"] = _dinv_l1(self.part)
            elif s.name == "AMG":
                if id(s) in self._sharded_amg:
                    d["amg"] = self._sharded_amg[id(s)]
                else:
                    from .amg import shard_amg
                    d["amg"] = shard_amg(s.amg, self.n_ranks, self.axis)
            elif id(s) in self._precond_shard_data:
                d.update({k: v for k, v in
                          self._precond_shard_data[id(s)].items()
                          if k != "A"})
            if s.preconditioner is not None:
                d["precond"] = chain_data(s.preconditioner)
            return d

        return chain_data(self.solver)

    # -- solve -----------------------------------------------------------
    def _build_fn(self):
        # diag=False: a sharded probe would record per-shard norms
        # (needs a psum to mean anything); the stats unpack below
        # assumes the bare layout
        raw = self.solver._build_solve_fn(diag=False)
        axis = self.axis

        def shard_fn(data, b, x0):
            local = jax.tree.map(lambda a: a[0], data)
            with comms.collective_axis(axis):
                x, stats = raw(local, b[0], x0[0])
                # all-reduce the SolveStatus (packed at stats[2]) so
                # every shard reports the same outcome: the codes are
                # severity-ordered (resilience/status.py), so pmax
                # picks the worst — e.g. one shard's corrupted halo
                # NaN beats a neighbor's locally-converged view. The
                # converged flag (stats[1]) is re-derived from the
                # reduced code: a shard-local converged=1 must not
                # survive a peer's failure (SolveResult treats
                # converged as authoritative)
                # reduced as int32: the chip's compiler has no f64 max
                # all-reduce ("UNIMPLEMENTED: Supported lowering only
                # of Sum all reduce", compiling for v5e:2x2), and the
                # packed stats of an f64 solve are f64
                worst = jax.lax.pmax(stats[2].astype(jnp.int32), axis)
                stats = stats.at[2].set(worst.astype(stats.dtype)).at[
                    1].set((worst == 0).astype(stats.dtype))
            return x[None], stats

        pspec = jax.tree.map(lambda _: P(axis), self._data)
        mapped = shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(pspec, P(axis), P(axis)),
            out_specs=(P(axis), P()),
            check_vma=False)
        return jax.jit(mapped)

    def solve(self, b, x0=None) -> SolveResult:
        from ..resilience import faultinject as _fi
        n = self.part.n_global
        bl = partition_vector(np.asarray(b), self.n_ranks,
                              self.part.n_local)
        xl = partition_vector(
            np.zeros(n, bl.dtype) if x0 is None else np.asarray(x0),
            self.n_ranks, self.part.n_local)
        bl, xl = self._place((bl, xl))
        fresh_trace = self._fn is None or \
            getattr(self, "_fn_epoch", 0) != _fi.epoch()
        if fresh_trace:
            # the faultinject epoch invalidates the cached shard_map
            # program (same contract as the base solver's jit key)
            from ..telemetry import metrics as _tm
            _tm.inc("solver.retrace.distributed")
            self._fn = self._build_fn()
            self._fn_epoch = _fi.epoch()
        t0 = time.perf_counter()
        if fresh_trace:
            # tracing happens on this first call: collect the exchange
            # sites it contains (comms.record_exchange) into the
            # per-site comms table report.distributed carries
            with comms.collect_exchanges() as tbl:
                x, stats = jax.block_until_ready(
                    self._fn(self._data, bl, xl))
            if tbl:
                self._comms_table = tbl
        else:
            x, stats = jax.block_until_ready(
                self._fn(self._data, bl, xl))
        solve_time = time.perf_counter() - t0
        iters_i, conv, status, n0, rn, hist = self.solver.unpack_stats(
            stats, self.solver.max_iters + 1)
        res = SolveResult(
            x=unpartition_vector(x, n), iterations=iters_i,
            converged=conv, res_norm=np.asarray(rn),
            norm0=np.asarray(n0),
            res_history=np.asarray(hist)
            if self.solver.store_res_history else None,
            setup_time=self.setup_time, solve_time=solve_time,
            status_code=status)
        if getattr(self.solver, "telemetry", False):
            # controller = rank-0 analog: ONE report per solve, with
            # the per-shard tallies (already on the controller via the
            # partition metadata) gathered into the distributed block
            from ..telemetry import build_report, spans as _spans
            res.report = build_report(
                self.solver, res, hist=np.asarray(hist),
                distributed={
                    "n_ranks": int(self.n_ranks),
                    "axis": str(self.axis),
                    "n_global": int(n),
                    "rows_per_shard": int(self.part.n_local),
                    # comms table: every exchange site the traced
                    # program contains, with modeled per-direction
                    # bytes (comms.record_exchange docs)
                    "comms": self._comms_table,
                    "shards": dict(self._shard_stats)
                    if getattr(self, "_shard_stats", None) else None,
                })
            # one Perfetto track per shard: the per-shard tallies as
            # synthetic solve-length slices (record_span tid override)
            # so the trace viewer shows the mesh, not just the
            # controller thread
            stats_tbl = getattr(self, "_shard_stats", None)
            for r in range(self.n_ranks):
                _spans.record_span(
                    "shard.solve", t0, solve_time,
                    args={"shard": r,
                          "rows": None if stats_tbl is None
                          else stats_tbl["rows"][r],
                          "nnz": None if stats_tbl is None
                          else stats_tbl["nnz"][r]},
                    tid=1_000_000 + r)
        return res


def _dinv(diag):
    safe = jnp.where(diag == 0, 1.0, diag)
    return jnp.where(diag == 0, 0.0, 1.0 / safe)


def _dinv_l1(part):
    """Per-shard L1-strengthened diagonal inverse. The off-diagonal row L1
    sums include halo columns — matching the reference's OWNED-view
    semantics."""
    R, n_local = part.diag.shape

    def one(vo, ro, co, vh, rh):
        off = jnp.where(co == ro, 0.0, jnp.abs(vo))
        return jax.ops.segment_sum(off, ro, num_segments=n_local) + \
            jax.ops.segment_sum(jnp.abs(vh), rh, num_segments=n_local)

    l1 = jax.vmap(one)(part.va_own, part.rid_own, part.ci_own,
                       part.va_halo, part.rid_halo)
    d = part.diag
    dl1 = d + jnp.sign(d) * l1
    return _dinv(dl1)
