"""Solver base: the composable solver tree.

TPU-native analog of Solver<TConfig> + SolverFactory
(include/solvers/solver.h:22,271; src/solvers/solver.cu). The reference
architecture is kept — any solver can own a preconditioner child solver,
configured per scope, built by a string-keyed factory — but the execution
model is redesigned for XLA:

- `setup(A)` runs once per matrix structure (host-orchestrated, device
  math) and produces a *solve-data pytree*;
- `solve()` compiles ONE XLA program: a `lax.while_loop` whose body is
  the solver's `solve_iteration`, with convergence/divergence checks as
  traced predicates — no host round-trips inside the iteration loop;
- a preconditioner application is a pure function (fixed sweep count via
  `lax.fori_loop`), so nesting solvers composes into a single fused
  program instead of the reference's nested kernel launches.

State is a plain dict pytree; the base manages the keys `x`, `r`,
`iters`, `done`, `converged`, `res_norm`, `norm0`, `res_hist`.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import registry
from ..config import Config
from ..errors import BadParametersError
from ..matrix import CsrMatrix
from ..ops import blas
from ..ops.spmv import residual as _residual
from ..output import amgx_printf
from ..resilience import faultinject as _fi
from ..resilience.status import RUNNING as _ST_RUNNING
from ..resilience.status import SolveStatus, status_string
from ..solve_data import SolveDataOwner

# ---------------------------------------------------------------------------
# convergence criteria (src/convergence/, registry src/core.cu:680-685)
# ---------------------------------------------------------------------------


class Convergence:
    """Predicate deciding convergence from (res_norm, norm0)."""

    def __init__(self, cfg: Config, scope: str):
        self.tolerance = float(cfg.get("tolerance", scope))
        self.alt_rel_tolerance = float(cfg.get("alt_rel_tolerance", scope))

    def check(self, res_norm, norm0):
        raise NotImplementedError


@registry.convergence.register("ABSOLUTE")
class AbsoluteConvergence(Convergence):
    def check(self, res_norm, norm0):
        return jnp.all(res_norm <= self.tolerance)


@registry.convergence.register("RELATIVE_INI")
@registry.convergence.register("RELATIVE_INI_CORE")
class RelativeIniConvergence(Convergence):
    def check(self, res_norm, norm0):
        return jnp.all(res_norm <= self.tolerance * norm0)


@registry.convergence.register("RELATIVE_MAX")
@registry.convergence.register("RELATIVE_MAX_CORE")
class RelativeMaxConvergence(Convergence):
    """Relative to the max initial-residual component (block norms)."""

    def check(self, res_norm, norm0):
        return jnp.all(res_norm <= self.tolerance * jnp.max(norm0))


@registry.convergence.register("COMBINED_REL_INI_ABS")
class CombinedRelIniAbsConvergence(Convergence):
    def check(self, res_norm, norm0):
        return jnp.all((res_norm <= self.tolerance)
                       | (res_norm <= self.alt_rel_tolerance * norm0))


# ---------------------------------------------------------------------------
# solve result
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SolveResult:
    x: jax.Array
    iterations: int
    converged: bool
    res_norm: float | np.ndarray
    norm0: float | np.ndarray
    res_history: Optional[np.ndarray] = None
    setup_time: float = 0.0
    solve_time: float = 0.0
    # structured status (resilience/status.py SolveStatus; mirrors
    # AMGX_SOLVE_*): the in-trace health guards classify NaN storms,
    # Krylov breakdowns, stalls and divergence instead of collapsing
    # every failure into one bool
    status_code: int = int(SolveStatus.MAX_ITERS)
    # structured telemetry (telemetry/report.py SolveReport): attached
    # by the solve paths when the `telemetry` knob is on; built
    # host-side from the stats already transferred, zero added syncs
    report: Optional[Any] = None
    # solver-specific scalar stats packed onto the stats vector
    # (Solver._extra_stats_spec; e.g. REFINEMENT's accumulated inner
    # iteration count under an active solve_precision policy). None
    # when the solver declared none — the packed layout is unchanged
    extra_stats: Optional[Dict[str, float]] = None

    def __post_init__(self):
        if self.converged:
            self.status_code = int(SolveStatus.CONVERGED)

    @property
    def status(self) -> str:
        return status_string(self.status_code)


# ---------------------------------------------------------------------------
# solver base
# ---------------------------------------------------------------------------


class Solver(SolveDataOwner):
    """Base solver. Subclasses implement `solver_setup`, `solve_init`,
    `solve_iteration`, and may override `apply` (preconditioner action);
    what the jitted solve reads of them goes into `_build_solve_data`
    (solve_data.py: the tree is assembled once a (re)setup).

    Reference skeleton: include/solvers/solver.h:126-156.
    """

    # does this solver read the "preconditioner" parameter?
    uses_preconditioner = False
    # smoothers can be used by AMG levels; they expose smooth()
    is_smoother = False
    # True when solve_iteration bakes VALUE-derived Python scalars into
    # the trace as constants (CHEBYSHEV's _d/_c): such a solver cannot
    # serve per-system coefficients from ONE trace, so the batched
    # multi-matrix path (batch/core.py) refuses it up front
    trace_bakes_values = False
    # True when solve-phase code only SpMVs against data["A"], so a
    # layout-only slim view may replace it (KACZMARZ reads COO structure
    # per sweep and opts out)
    slim_A_ok = True
    # solve_data key under which this solver stores its preconditioner's
    # subtree (REFINEMENT overrides: it names the child "inner") — the
    # diagnostics probe walks it to reach the AMG hierarchy's data at
    # any nesting depth
    _child_data_key = "precond"

    def __init__(self, cfg: Config, scope: str = "default",
                 name: str = "?"):
        self.cfg = cfg
        self.scope = scope
        self.name = name
        self.A: Optional[CsrMatrix] = None
        self.max_iters = int(cfg.get("max_iters", scope))
        self.monitor_residual = bool(cfg.get("monitor_residual", scope))
        self.norm_type = str(cfg.get("norm", scope))
        self.use_scalar_norm = bool(cfg.get("use_scalar_norm", scope))
        self.store_res_history = bool(cfg.get("store_res_history", scope))
        self.print_solve_stats = bool(cfg.get("print_solve_stats", scope))
        self.obtain_timings = bool(cfg.get("obtain_timings", scope))
        self.rel_div_tolerance = float(cfg.get("rel_div_tolerance", scope))
        # resilience guards (resilience/): classification rides the
        # residual already computed by the monitor — zero extra syncs
        self.health_guards = bool(int(cfg.get("health_guards", scope)))
        self.stall_window = int(cfg.get("stall_detection_window", scope))
        self.stall_tolerance = float(cfg.get("stall_tolerance", scope))
        # telemetry (telemetry/): report construction + watermark
        # sampling are gated per solver. telemetry_sync is a PROCESS
        # mode (span fencing is global by nature), latched — both ways
        # — by the root-construction entry points (create_solver /
        # DistributedSolver), not here: a tree's child nodes reading
        # the default would otherwise flap the flag per node
        self.telemetry = bool(int(cfg.get("telemetry", scope)))
        self.scaling = str(cfg.get("scaling", scope)).upper()
        self.scaler = None
        # Only the tree ROOT applies equation scaling: children receive
        # the already-scaled matrix, and apply()/smooth() exchange
        # vectors in the parent's (scaled) coordinates. Creation sites of
        # child solvers clear this flag. (The reference routes nested
        # solves through Solver::solve which re-scales per level —
        # consistent but redundant; here the scaled system is built once.)
        self._owns_scaling = True
        # shared precision policy (precision.py): resolves
        # solve_precision/amg_precision/tpu_dtype and rejects
        # contradictory combinations at construction time. Unset is
        # bitwise-off — nothing below reads it unless .active
        from ..precision import resolve_precision
        self._precision_policy = resolve_precision(cfg, scope)
        conv_name = str(cfg.get("convergence", scope))
        self.convergence: Convergence = registry.convergence.create(
            conv_name, cfg, scope)
        self.preconditioner: Optional[Solver] = None
        if self.uses_preconditioner:
            pname, pscope = cfg.get_solver("preconditioner", scope)
            if pname.upper() != "NOSOLVER":
                self.preconditioner = make_solver(pname, cfg, pscope)
                self.preconditioner._owns_scaling = False
        self._jit_cache: Dict[Any, Any] = {}
        self.setup_time = 0.0

    # -- norm ------------------------------------------------------------
    def _norm(self, v, axis_name=None, num_owned=None):
        bs = self.A.block_dimx if self.A is not None else 1
        return blas.norm(v, self.norm_type, block_size=bs,
                         use_scalar_norm=self.use_scalar_norm,
                         axis_name=axis_name, num_owned=num_owned)

    # -- setup -----------------------------------------------------------
    def setup(self, A: CsrMatrix):
        """Build solver state for matrix A (Solver::setup analog)."""
        return self._setup_impl(A, reuse=False)

    def resetup(self, A: CsrMatrix):
        """Rebuild coefficients keeping structure where possible
        (AMGX_solver_resetup analog). Mirrors setup but routes into
        solver_resetup so subsystems with reusable structure (AMG with
        structure_reuse_levels) can keep it."""
        return self._setup_impl(A, reuse=True)

    def setup_async(self, A: CsrMatrix):
        """Run setup on a worker thread (AsyncSolverSetupTask analog,
        include/amg_level.h:25-39); returns a task whose wait() joins
        and re-raises. The solver must not be used before wait()."""
        from ..thread_manager import setup_async
        return setup_async(self, A)

    def _setup_impl(self, A: CsrMatrix, reuse: bool):
        from ..profiling import trace_region
        # two literal span names (not one computed string) so the
        # static registry check (tools/check_spans.py) covers them
        if reuse:
            # filled by the body where the resetup drops the cached
            # solve programs; the span reads it as it closes
            span_args: Dict[str, Any] = {}
            # the outermost resetup span of the tree keeps the
            # re-setup's account (telemetry/spans.py)
            with trace_region(f"{self.name}.resetup", args=span_args,
                              account=True):
                out = self.__setup_impl(A, reuse, span_args)
        else:
            with trace_region(f"{self.name}.setup"):
                out = self.__setup_impl(A, reuse)
        if self.telemetry:
            from ..memory_info import peak_bytes
            from ..telemetry import metrics as _tm
            _tm.max_gauge("memory.setup_peak_bytes", peak_bytes())
        return out

    def __setup_impl(self, A: CsrMatrix, reuse: bool,
                     span_args: Optional[Dict[str, Any]] = None):
        from ..profiling import trace_region
        t0 = time.perf_counter()
        snap = self._resetup_debug_snapshot() if reuse else None
        # the tree of the old coefficients goes before the new leaves
        # are made (top-down through the chain: nothing of it outlives
        # the build)
        self.drop_solve_data()
        if not A.initialized:
            A = A.init()
        if self._owns_scaling and self.scaling not in ("NONE", ""):
            # scale the equations before the tree is built; the whole
            # solver (incl. nested preconditioners) then works on L A R
            # (Solver::setup scaler path, src/solvers/solver.cu:465-476)
            from ..scalers import make_scaler
            self.scaler = make_scaler(self.scaling, self.cfg, self.scope)
            self.scaler.setup(A)
            A = self.scaler.scale_matrix(A)
            if not A.initialized:
                A = A.init()
        if reuse:
            # the last reference to the operator of the call before
            # goes here: its arrays are released, and with them the
            # host mirrors matrix.py kept of what was uploaded (at
            # 256^3 the munmap of 1.9 GB: a leaf of the re-setup's
            # account, telemetry/spans.py)
            with trace_region("solver.release_operator"):
                self.A = A
        else:
            self.A = A
        # preconditioner first: solvers whose setup probes the
        # preconditioned operator (e.g. Chebyshev eigen-estimation) need it
        if self.preconditioner is not None:
            (self.preconditioner.resetup if reuse
             else self.preconditioner.setup)(self.precond_operator(A))
        (self.solver_resetup if reuse else self.solver_setup)()
        # a resetup that changed no static input of the traced solve
        # functions (shapes, level counts, color counts: all derived
        # from the structure where that was kept, and compared with
        # what they were where a hierarchy was rebuilt) leaves them
        # valid, and the new coefficients flow through as arguments;
        # clearing would force a full Python re-trace and lowering per
        # coefficient cycle
        if not (reuse and self._resetup_kept_static()):
            if reuse:
                cause = self._retrace_cause()
                span_args["retrace_cause"] = cause
                if self._jit_cache:
                    # this resetup costs the next solve a retrace
                    from ..telemetry import metrics as _tm
                    _tm.inc(f"resetup.retrace_cause.{cause}")
            self._jit_cache.clear()
            # batched wrappers close over this tree's traces, so they
            # go stale together (same-structure replays would serve
            # stale baked constants — Chebyshev spectra, color counts).
            # A wrapper suppresses this during its own multi-matrix
            # resetup loop, where structure reuse is enforced and
            # trace-baking solvers are rejected (batch/core.py).
            for b in tuple(getattr(self, "_batched_wrappers", ())):
                if not b._suppress_invalidation:
                    b._jit_cache.clear()
        else:
            if self._jit_cache:
                # the next solve runs the program it has
                from ..telemetry import metrics as _tm
                _tm.inc("resetup.program_kept")
                if self._resetup_rebuilt_same():
                    # ... across a rebuild of a hierarchy, where the
                    # span named a retrace_cause before
                    span_args["program_kept"] = True
            if snap is not None:
                self._assert_resetup_contract(snap)
        self.setup_time = time.perf_counter() - t0
        return self

    def _resetup_kept_static(self) -> bool:
        """Did the last resetup keep every static ingredient of this
        (sub)tree's traced solve functions? What decides is whether the
        program's static input is the same, not which route the
        re-setup took. Standard solvers' static state derives from the
        matrix PATTERN (shapes, colorings, ELL widths), which
        replace_coefficients keeps by contract — so the default is True
        and the question recurses down the chain. The AMG wrapper
        overrides: its hierarchy's depth and level shapes depend on the
        VALUES, so it answers True where the fused value-only resetup
        ran (the levels themselves were kept) and, where levels were
        rebuilt (a full re-setup under structure_reuse_levels=0
        included), where the rebuilt hierarchy's static signature
        (amg/signature.py: the solve_data treedef with its meta fields,
        every leaf's shape and dtype, and what the cycle reads from the
        level, smoother and coarse-solver objects at trace time) equals
        the one the cached programs were traced against.

        CONTRACT (load-bearing for resetup trace reuse AND for the
        batched subsystem's per-system value splice, batch/core.py):
        when this returns True after a resetup, the cached jitted solve
        functions are replayed with the NEW solve_data() as arguments —
        so every value-derived quantity `solve_iteration` reads must
        flow through `solve_data()` leaves. A solver that bakes
        value-derived Python scalars into its trace (CHEBYSHEV's _d/_c)
        must override this to return False, or the replayed trace serves
        stale coefficients; inside a hierarchy (as a smoother or coarse
        solver) its answer drops the program just the same. Debug
        builds verify the observable half of the contract on every
        route that keeps the programs (set AMGX_TPU_DEBUG_RESETUP=1):
        solve_data's pytree structure/shapes/dtypes must survive
        unchanged, and new coefficients must surface as new leaves."""
        return (self.preconditioner is None
                or self.preconditioner._resetup_kept_static())

    def _resetup_rebuilt_same(self) -> bool:
        """Did the last resetup REBUILD a hierarchy of this (sub)tree
        to the static signature it had (the AMG wrapper's answer;
        passed up the chain)? Only names the `program_kept` arg of the
        resetup span; `_resetup_kept_static` decides."""
        return (self.preconditioner is not None
                and self.preconditioner._resetup_rebuilt_same())

    def _retrace_cause(self) -> str:
        """Which solver of this (sub)tree answered
        `_resetup_kept_static()` with False: the deepest one that says
        so (a solver above it only passes the answer up). Its name
        where `resetup.retrace_cause.<name>` is a declared counter,
        else "other"."""
        from ..telemetry import metrics as _tm
        cause, node = self, self.preconditioner
        while node is not None and not node._resetup_kept_static():
            cause, node = node, node.preconditioner
        name = cause.name.upper()
        return name if f"resetup.retrace_cause.{name}" in _tm.COUNTERS \
            else "other"

    # -- resetup contract checking (AMGX_TPU_DEBUG_RESETUP=1) ------------
    @staticmethod
    def _debug_resetup_enabled() -> bool:
        return os.environ.get("AMGX_TPU_DEBUG_RESETUP", "0") not in (
            "", "0", "false", "False")

    def _resetup_debug_snapshot(self):
        """Pre-resetup snapshot of the solve_data pytree (debug mode
        only): treedef + per-leaf (shape, dtype) + leaf ids + the old
        coefficient array's id."""
        if not self._debug_resetup_enabled() or self.A is None:
            return None
        leaves, treedef = jax.tree_util.tree_flatten(self.solve_data())
        # the snapshot RETAINS the leaf objects (not just their ids):
        # holding them alive is what makes the post-resetup id
        # comparison sound — a freed array's address can be reused by a
        # new allocation, which would both mask real violations and
        # fire spurious ones
        return {
            "treedef": treedef,
            "shapes": [(getattr(l, "shape", None),
                        str(getattr(l, "dtype", ""))) for l in leaves],
            "leaves": leaves,
            "values": self.A.values,
        }

    def _assert_resetup_contract(self, snap):
        """After a resetup that kept the traced solves (jit cache NOT
        cleared), the new solve_data must be a drop-in argument for the
        cached traces: identical treedef and per-leaf shapes/dtypes.
        Additionally, if the coefficients changed, at least one leaf
        must be a NEW array — an id-identical leaf set means the new
        values never reached solve_data and the replayed trace would
        serve stale coefficients (the failure mode the
        _resetup_kept_static contract exists to prevent)."""
        leaves, treedef = jax.tree_util.tree_flatten(self.solve_data())
        if treedef != snap["treedef"]:
            raise AssertionError(
                f"solver {self.name}: resetup kept the traced solves but "
                f"changed the solve_data pytree structure")
        shapes = [(getattr(l, "shape", None),
                   str(getattr(l, "dtype", ""))) for l in leaves]
        if shapes != snap["shapes"]:
            bad = [i for i, (a, b) in enumerate(zip(shapes,
                                                    snap["shapes"]))
                   if a != b][:5]
            raise AssertionError(
                f"solver {self.name}: resetup kept the traced solves but "
                f"changed solve_data leaf shapes/dtypes at flat indices "
                f"{bad}")
        if self.A.values is not snap["values"] and \
                {id(l) for l in leaves} == {id(l) for l in snap["leaves"]}:
            raise AssertionError(
                f"solver {self.name}: coefficients changed on resetup "
                f"but every solve_data leaf is the pre-resetup object — "
                f"value-derived state is not flowing through solve_data")

    def precond_operator(self, A: CsrMatrix) -> CsrMatrix:
        """The operator the preconditioner tree is set up against
        (REFINEMENT overrides this with the reduced-precision cast)."""
        return A

    def solver_setup(self):
        """Build solver-specific state for self.A.

        _resetup_kept_static contract: anything computed here from the
        matrix VALUES (diagonal inverses, factors, eigen estimates) that
        the solve phase reads must be stored so `solve_data()` exposes it
        as a pytree leaf — a value-only resetup then reruns this method
        and the refreshed leaves flow into the CACHED jitted solve as
        arguments. Value-derived state kept as Python scalars (baked
        into the trace as constants) breaks that replay; such solvers
        must override `_resetup_kept_static` to return False."""
        pass

    def solver_resetup(self):
        self.solver_setup()

    # -- functional pieces (pure, jittable) ------------------------------
    def _build_solve_data(self) -> Dict[str, Any]:
        """The pytree of device data the jitted solve needs, as
        `solve_data()` keeps and serves it. Includes the
        preconditioner's data under 'precond'. Solvers whose iterations
        only SpMV against A (slim_A_ok) pass a layout-only view so
        unused CSR payloads stay out of the solve program's HBM."""
        A = self.A
        if self.slim_A_ok and hasattr(A, "slim_for_spmv"):
            A = A.slim_for_spmv()
        d: Dict[str, Any] = {"A": A}
        if self.preconditioner is not None:
            d["precond"] = self.preconditioner.solve_data_part()
        return d

    def _solve_data_children(self) -> tuple:
        pc = self.preconditioner
        return () if pc is None else (pc,)

    def solve_init(self, data, b, x, r) -> Dict[str, Any]:
        """Extra solver state (beyond x/r) before the first iteration."""
        return {}

    def _guard_init(self) -> Dict[str, Any]:
        """Initial breakdown flag for the health guards: solvers that
        classify recurrence breakdowns set state['breakdown'] each
        iteration and the driver folds it into SolveStatus, exiting
        the loop cleanly instead of propagating NaNs. The key exists
        only when guards are on, so the guard-off trace carries no
        dead state. Call from solve_init and merge into the state."""
        return {"breakdown": jnp.asarray(False)} if self.health_guards \
            else {}

    def solve_iteration(self, data, b, state) -> Dict[str, Any]:
        """One iteration as a pure function of (data, b, state).

        _resetup_kept_static contract: read value-derived quantities
        from `data` (the solve_data pytree), never from `self` — self
        attributes trace as compile-time constants, which is only sound
        for PATTERN-derived state (shapes, colorings, sweep counts).
        The iteration must also be `jax.vmap`-compatible (no host
        round-trips, no shape-dependent Python branching on values) —
        the batched subsystem (batch/core.py) maps it over a leading
        system axis."""
        raise NotImplementedError

    def _diag_probe_spec(self):
        """(amg, data_keys) when this solver tree owns an AMG hierarchy
        with convergence diagnostics ON (telemetry/diagnostics.py) —
        `data_keys` is the solve_data path from this tree's root to the
        hierarchy's subtree, so the traced driver can hand the probe
        cycle its data at any preconditioner nesting depth. None when
        the knob is off, the hierarchy is empty (no smoothed levels to
        attribute), or the levels are not plain single-chip AMGLevels
        (sharded hierarchies record per-shard norms that would need a
        psum — the distributed path builds with diag=False anyway)."""
        s, keys = self, []
        for _ in range(8):
            if s is None:
                return None
            amg = getattr(s, "amg", None)
            if amg is not None:
                from ..amg.hierarchy import AMGLevel
                if (getattr(amg, "diagnostics", False) and amg.levels
                        and all(isinstance(lv, AMGLevel)
                                for lv in amg.levels)):
                    return amg, keys + ["amg"]
                return None
            keys.append(s._child_data_key)
            s = s.preconditioner
        return None

    _color_steps = 0     # color_steps_per_iteration() of the cached programs
    _geo_transfers = None   # geo_transfers_per_iteration(), likewise

    def color_steps_per_iteration(self) -> int:
        """Ordered color steps of the colored smoothers one iteration
        of this solver runs (its preconditioner's, applied once an
        iteration); 0 where the tree has none. Static after setup."""
        pc = self.preconditioner
        return 0 if pc is None else pc.color_steps_per_iteration()

    def swell_vreg_steps_per_iteration(self) -> int:
        """Vreg-steps of the SWELL gather one iteration's cycle is made
        of (the preconditioner's: AMG.swell_vreg_steps_per_cycle); 0
        where the tree has no multigrid cycle. Kept by the hierarchy as
        its set-up ends, so it follows a resetup whose program is kept."""
        pc = self.preconditioner
        return 0 if pc is None else pc.swell_vreg_steps_per_iteration()

    def dia_smooth_per_iteration(self):
        """(`_dia_smooth_call` launches, lane-rows x applications they
        compute) of one iteration's cycle (the preconditioner's:
        AMG.dia_smooth_per_cycle, kept while the cycle is traced);
        (0, 0) where the tree has no multigrid cycle."""
        pc = self.preconditioner
        return (0, 0) if pc is None else pc.dia_smooth_per_iteration()

    def swell_model_s_per_iteration(self) -> float:
        """Seconds the layout choice's model puts on the SWELL gather
        of one iteration's cycle (the preconditioner's:
        AMG.swell_model_s_per_cycle), kept as the vreg-steps are."""
        pc = self.preconditioner
        return 0.0 if pc is None else pc.swell_model_s_per_iteration()

    def csr_road_nnz_per_iteration(self) -> int:
        """Non-zeros one iteration's cycle sends down the XLA gather +
        segment-sum road (the preconditioner's:
        AMG.csr_road_nnz_per_cycle); 0 where the tree has no multigrid
        cycle or every operator of it has a layout."""
        pc = self.preconditioner
        return 0 if pc is None else pc.csr_road_nnz_per_iteration()

    # preconditioner applications one iteration makes (PBICGSTAB: two)
    precond_applications_per_iteration = 1
    _fused_sites = 0     # fused shell call sites one iteration's trace
    #                      routed to the Pallas kernels (cached programs)

    def geo_transfers_per_iteration(self):
        """(one-pass, XLA) GEO levels one iteration's cycle runs its
        transfers through (the preconditioner's), or None where the
        tree has no multigrid cycle. Static after setup."""
        pc = self.preconditioner
        return None if pc is None else pc.geo_transfers_per_iteration()

    def _extra_stats_spec(self) -> tuple:
        """Names of solver-specific SCALARS appended to the packed
        stats vector, in order (after res_hist, before the diagnostics
        probe tail). Default empty: the packed layout — and therefore
        every traced solve program — is unchanged. REFINEMENT declares
        ("inner_iters",) when the solve_precision policy is active so
        per-precision iteration counts reach SolveReport with zero
        extra device->host transfers (they ride the stats buffer)."""
        return ()

    def _extra_stats(self, final_state) -> tuple:
        """The scalar values matching _extra_stats_spec, read from the
        final while_loop state."""
        return ()

    def _precision_block(self, res) -> Optional[Dict[str, Any]]:
        """SolveReport.precision payload, or None when the
        solve_precision policy is inactive (the bitwise-off default).
        Subclasses with per-precision accounting (REFINEMENT) extend
        the base block with inner-loop counts."""
        pol = getattr(self, "_precision_policy", None)
        if pol is None or not pol.active:
            return None
        return {
            "solve_precision": pol.name,
            "cycle_dtype": pol.cast_dtype or "native",
            "outer_dtype": None if self.A is None else str(self.A.dtype),
            "outer_iterations": int(res.iterations),
        }

    def computes_residual(self) -> bool:
        """True when solve_iteration maintains state['r'] itself; else the
        driver recomputes r = b - Ax for monitoring."""
        return True

    def internal_res_norm(self, state):
        """Optional cheap residual-norm estimate maintained by the solver
        (e.g. GMRES |g[i+1]|). Return None to let the driver compute it."""
        return None

    def finalize(self, data, b, state):
        """Post-loop fixup returning the final x (GMRES reconstructs x
        from the Krylov basis here)."""
        return state["x"]

    def apply(self, data, rhs):
        """Preconditioner action M^{-1} rhs: zero-init solve with a fixed
        number of iterations (no convergence monitoring), fully traced."""
        x0 = jnp.zeros_like(rhs)
        r0 = rhs
        st = {"x": x0, "r": r0}
        st.update(self.solve_init(data, rhs, x0, r0))

        def body(_, s):
            return self.solve_iteration(data, rhs, s)

        st = jax.lax.fori_loop(0, self.max_iters, body, st)
        return st["x"]

    # -- the jitted driver ----------------------------------------------
    def _build_solve_fn(self, diag: bool = True, extras=None):
        """Return the raw (unjitted) solve function; jit happens in
        solve(), and the distributed layer shard_maps it instead.
        `extras` (default: as `diag`) appends the solver's
        `_extra_stats` to the packed stats; an outer shell that sums
        its inner solver's asks for them without the probe.

        Health guards (resilience/): the convergence check folds NaN
        detection, breakdown classification, divergence and stall
        detection into ONE int32 `status` carried in the while_loop
        state — everything derives from the residual norm the monitor
        already computed (plus the solver-maintained `breakdown` flag),
        so guarded solves add no device->host synchronization per
        iteration.

        Convergence diagnostics (telemetry/diagnostics.py): with the
        `diagnostics=1` knob on an AMG member of the tree, ONE
        instrumented probe cycle on the final residual is appended to
        the traced program and its per-level stage norms ride the SAME
        packed stats vector — no extra output buffers, no extra
        transfers. `diag=False` opts a consumer out (the batched vmap
        and shard_map wrappers, and REFINEMENT's inner fn, whose stats
        unpacking assumes the bare layout); with the knob off the
        emitted jaxpr is identical either way."""
        diag_spec = self._diag_probe_spec() if diag else None
        extras = diag if extras is None else extras
        max_iters = self.max_iters
        monitor = self.monitor_residual
        hist_len = max_iters + 1
        div_tol = self.rel_div_tolerance
        conv = self.convergence
        guards = self.health_guards
        stall_w = self.stall_window if guards else 0
        stall_tol = self.stall_tolerance
        S = SolveStatus
        # device-side stage names (metadata only: telemetry/programs.py
        # reads them back from the compiled program's op_names). `init`
        # and `finalize` are here so that the shell's share is whole:
        # the first residual (an f64 SpMV under REFINEMENT), its norm
        # and the way back to x are the shell's device time, and would
        # read as unscoped without a name
        scope = f"krylov.{self.name}"
        from ..telemetry import metrics as _tm

        def solve_fn(data, b, x0):
            A = data["A"]
            with jax.named_scope(f"{scope}.init"):
                r0 = _residual(A, x0, b)
                norm0 = self._norm(r0)
                state = {"x": x0, "r": r0}
                state.update(self.solve_init(data, b, x0, r0))
            state["iters"] = jnp.asarray(0, jnp.int32)
            # zero RHS / zero initial residual: x0 solves the system
            # exactly — CONVERGED at 0 iterations instead of feeding
            # norm0 == 0 into the relative-tolerance arithmetic
            zero0 = jnp.all(norm0 == 0)
            conv0 = conv.check(norm0, norm0) if monitor \
                else jnp.asarray(False)
            done0 = conv0 | zero0
            state["done"] = done0
            state["converged"] = done0
            state["status"] = jnp.where(done0, jnp.int32(S.CONVERGED),
                                        jnp.int32(_ST_RUNNING))
            state["res_norm"] = norm0
            state["res_hist"] = jnp.zeros(
                (hist_len,) + np.shape(norm0), norm0.dtype
            ).at[0].set(norm0)

            def cond(st):
                return (~st["done"]) & (st["iters"] < max_iters)

            def body(st):
                iters = st["iters"]
                core = {k: v for k, v in st.items()
                        if k not in ("iters", "done", "converged",
                                     "res_norm", "res_hist", "status")}
                routed = _tm.get("krylov.fused_dispatch")
                with _fi.iteration_scope(iters), \
                        jax.named_scope(f"{scope}.iter"):
                    core = self.solve_iteration(data, b, core)
                # trace time: static like the program it is kept with
                self._fused_sites = \
                    _tm.get("krylov.fused_dispatch") - routed
                new = dict(st)
                new.update(core)
                new["iters"] = iters + 1
                if monitor:
                    rn_int = self.internal_res_norm(core)
                    if rn_int is not None:
                        # internal estimates (GMRES |g[i+1]|) are scalar;
                        # broadcast to the monitored norm's shape (block
                        # norms are per-component vectors)
                        rn = jnp.broadcast_to(jnp.asarray(rn_int),
                                              np.shape(norm0))
                    else:
                        with jax.named_scope(f"{scope}.monitor"):
                            if self.computes_residual():
                                rn = self._norm(core["r"])
                            else:
                                rn = self._norm(
                                    _residual(A, core["x"], b))
                    new["res_norm"] = rn
                    new["res_hist"] = st["res_hist"].at[iters + 1].set(rn)
                    cvg = conv.check(rn, norm0)
                    false_ = jnp.asarray(False)
                    diverged = false_
                    if div_tol > 0:
                        diverged = jnp.any(rn > div_tol * norm0)
                    bad = ~jnp.all(jnp.isfinite(rn)) if guards else false_
                    brk = core.get("breakdown", false_) if guards \
                        else false_
                    stalled = false_
                    if stall_w > 0:
                        # sliding window over the history already being
                        # recorded: stalled when the norm failed to drop
                        # by stall_tolerance over the last stall_w steps
                        past = jax.lax.dynamic_index_in_dim(
                            new["res_hist"],
                            jnp.maximum(iters + 1 - stall_w, 0),
                            axis=0, keepdims=False)
                        stalled = (iters + 1 >= stall_w) & jnp.all(
                            rn >= (1.0 - stall_tol) * past)
                    # first terminal condition wins; convergence beats
                    # the failure classes (an exactly-converged CG also
                    # trips p.Ap == 0). BREAKDOWN outranks NAN: the
                    # Krylov breakdown flags are NaN-comparison-False
                    # under a NaN storm (so NaN storms still classify
                    # NAN_DETECTED), while AMG's non-finite-cycle flag
                    # must not be drowned by the NaN its own breakdown
                    # put into the residual
                    status_now = jnp.where(
                        cvg, jnp.int32(S.CONVERGED),
                        jnp.where(brk, jnp.int32(S.BREAKDOWN),
                        jnp.where(bad, jnp.int32(S.NAN_DETECTED),
                        jnp.where(diverged, jnp.int32(S.DIVERGED),
                        jnp.where(stalled, jnp.int32(S.STALLED),
                                  jnp.int32(_ST_RUNNING))))))
                    new["status"] = jnp.where(
                        st["status"] == _ST_RUNNING, status_now,
                        st["status"])
                    new["converged"] = \
                        new["status"] == jnp.int32(S.CONVERGED)
                    new["done"] = new["status"] != jnp.int32(_ST_RUNNING)
                return new

            final = jax.lax.while_loop(cond, body, state)
            if _fi.any_loop_fault_armed():
                # one poisoned trace per armed firing: the retry after a
                # transient fault compiles clean (epoch is in the jit
                # cache keys)
                _fi.consume_loop_faults()
            with jax.named_scope(f"{scope}.finalize"):
                x_final = self.finalize(data, b, final)
            status = jnp.where(final["status"] == _ST_RUNNING,
                               jnp.int32(S.MAX_ITERS), final["status"])
            # pack every scalar/stat output into ONE auxiliary array:
            # the caller awaits and copies out two buffers (x, stats)
            # instead of six
            # at least f32 so iteration counts survive the cast exactly
            # even for bf16/f16 solves
            rdt = jnp.promote_types(jnp.asarray(norm0).dtype, jnp.float32)
            pieces = [
                jnp.reshape(final["iters"].astype(rdt), (1,)),
                jnp.reshape(final["converged"].astype(rdt), (1,)),
                jnp.reshape(status.astype(rdt), (1,)),
                jnp.ravel(jnp.asarray(norm0)),
                jnp.ravel(jnp.asarray(final["res_norm"])),
                jnp.ravel(jnp.asarray(final["res_hist"]))]
            # solver-declared extra scalars (e.g. REFINEMENT's inner
            # iteration count under an active solve_precision policy)
            # ride the same packed buffer — zero added transfers; the
            # spec is empty by default so the layout is unchanged.
            # Off with the probe tail by default: the batched and
            # distributed consumers (diag=False) unpack the BARE stats
            # layout
            if extras:
                for v in self._extra_stats(final):
                    pieces.append(jnp.reshape(
                        jnp.asarray(v).astype(rdt), (1,)))
            if diag_spec is not None:
                # diagnostics probe: one instrumented cycle on the
                # residual equation A d = r_final, appended INSIDE the
                # traced program; its stage norms pack onto the stats
                # tail (_solve_traced strips them by the same spec)
                from ..telemetry import diagnostics as _dg
                amg_, keys_ = diag_spec
                sub = data
                for k_ in keys_:
                    sub = sub[k_]
                r_fin = _residual(A, x_final, b)
                pieces.append(jnp.ravel(
                    _dg.probe_cycle(amg_, sub, r_fin, rdt)))
            stats = jnp.concatenate(pieces)
            return x_final, stats

        return solve_fn

    # -- chunked stepping (serving/engine.py continuous batching) --------
    def _build_chunk_fns(self, chunk: int):
        """Resumable chunked-iteration solve entry — the substrate of the
        serving layer's continuous batching (serving/engine.py). Returns
        three pure, jittable, vmap-compatible functions::

            init_fn(data, b, x0)      -> state
            step_fn(data, b, state)   -> state   # <= `chunk` more iters
            finish_fn(data, b, state) -> (x, stats)

        The state is the SAME recurrence `_build_solve_fn`'s while_loop
        carries, with `norm0` carried as an explicit state leaf so
        stepping can resume across host boundaries: a system stepped in
        chunks visits bit-identical iterates to a one-shot solve, and a
        converged/terminal system's state is frozen by the loop
        predicate — so a drained batch slot costs nothing while its
        neighbors finish, and the scheduler can refill it at the next
        cycle boundary instead of waiting for the whole batch. The
        chunk window is per-system relative (`iters < entry_iters +
        chunk`), so freshly admitted systems and veterans advance the
        same number of iterations per engine cycle. `finish_fn` packs
        the identical stats vector `unpack_stats` inverts."""
        max_iters = self.max_iters
        monitor = self.monitor_residual
        hist_len = max_iters + 1
        div_tol = self.rel_div_tolerance
        conv = self.convergence
        guards = self.health_guards
        stall_w = self.stall_window if guards else 0
        stall_tol = self.stall_tolerance
        S = SolveStatus
        chunk = int(chunk)

        def init_fn(data, b, x0):
            A = data["A"]
            r0 = _residual(A, x0, b)
            norm0 = self._norm(r0)
            state = {"x": x0, "r": r0}
            state.update(self.solve_init(data, b, x0, r0))
            state["iters"] = jnp.asarray(0, jnp.int32)
            zero0 = jnp.all(norm0 == 0)
            conv0 = conv.check(norm0, norm0) if monitor \
                else jnp.asarray(False)
            done0 = conv0 | zero0
            state["done"] = done0
            state["converged"] = done0
            state["status"] = jnp.where(done0, jnp.int32(S.CONVERGED),
                                        jnp.int32(_ST_RUNNING))
            state["res_norm"] = norm0
            state["norm0"] = norm0
            state["res_hist"] = jnp.zeros(
                (hist_len,) + np.shape(norm0), norm0.dtype
            ).at[0].set(norm0)
            return state

        # mirror of _build_solve_fn's loop body, reading norm0 from the
        # carried state instead of a closure (bit-identical per-system
        # iterates is the chunked/one-shot parity contract test_serving
        # checks)
        def body(data, b, st):
            norm0 = st["norm0"]
            iters = st["iters"]
            core = {k: v for k, v in st.items()
                    if k not in ("iters", "done", "converged",
                                 "res_norm", "res_hist", "status",
                                 "norm0")}
            with _fi.iteration_scope(iters):
                core = self.solve_iteration(data, b, core)
            new = dict(st)
            new.update(core)
            new["iters"] = iters + 1
            if monitor:
                rn_int = self.internal_res_norm(core)
                if rn_int is not None:
                    rn = jnp.broadcast_to(jnp.asarray(rn_int),
                                          np.shape(norm0))
                elif self.computes_residual():
                    rn = self._norm(core["r"])
                else:
                    rn = self._norm(_residual(data["A"], core["x"], b))
                new["res_norm"] = rn
                new["res_hist"] = st["res_hist"].at[iters + 1].set(rn)
                cvg = conv.check(rn, norm0)
                false_ = jnp.asarray(False)
                diverged = false_
                if div_tol > 0:
                    diverged = jnp.any(rn > div_tol * norm0)
                bad = ~jnp.all(jnp.isfinite(rn)) if guards else false_
                brk = core.get("breakdown", false_) if guards \
                    else false_
                stalled = false_
                if stall_w > 0:
                    past = jax.lax.dynamic_index_in_dim(
                        new["res_hist"],
                        jnp.maximum(iters + 1 - stall_w, 0),
                        axis=0, keepdims=False)
                    stalled = (iters + 1 >= stall_w) & jnp.all(
                        rn >= (1.0 - stall_tol) * past)
                status_now = jnp.where(
                    cvg, jnp.int32(S.CONVERGED),
                    jnp.where(brk, jnp.int32(S.BREAKDOWN),
                    jnp.where(bad, jnp.int32(S.NAN_DETECTED),
                    jnp.where(diverged, jnp.int32(S.DIVERGED),
                    jnp.where(stalled, jnp.int32(S.STALLED),
                              jnp.int32(_ST_RUNNING))))))
                new["status"] = jnp.where(
                    st["status"] == _ST_RUNNING, status_now,
                    st["status"])
                new["converged"] = \
                    new["status"] == jnp.int32(S.CONVERGED)
                new["done"] = new["status"] != jnp.int32(_ST_RUNNING)
            return new

        def step_fn(data, b, state):
            entry = state["iters"]

            def cond(st):
                return ((~st["done"]) & (st["iters"] < max_iters)
                        & (st["iters"] < entry + chunk))

            out = jax.lax.while_loop(
                cond, lambda st: body(data, b, st), state)
            if _fi.any_loop_fault_armed():
                _fi.consume_loop_faults()
            return out

        def finish_fn(data, b, state):
            norm0 = state["norm0"]
            x_final = self.finalize(data, b, state)
            status = jnp.where(state["status"] == _ST_RUNNING,
                               jnp.int32(S.MAX_ITERS), state["status"])
            rdt = jnp.promote_types(jnp.asarray(norm0).dtype,
                                    jnp.float32)
            stats = jnp.concatenate([
                jnp.reshape(state["iters"].astype(rdt), (1,)),
                jnp.reshape(state["converged"].astype(rdt), (1,)),
                jnp.reshape(status.astype(rdt), (1,)),
                jnp.ravel(jnp.asarray(norm0)),
                jnp.ravel(jnp.asarray(state["res_norm"])),
                jnp.ravel(jnp.asarray(state["res_hist"]))])
            return x_final, stats

        return init_fn, step_fn, finish_fn

    @staticmethod
    def unpack_stats(stats, hist_len: int):
        """Invert the stats packing of _build_solve_fn: returns
        (iters, converged, status, norm0, res_norm, res_hist) as numpy
        values. The norm width (1, or block_size for per-component block
        norms) is recovered from the packed length. res_hist is trimmed
        to the actual iteration count (iters + 1 entries), so the
        post-exit zero padding of the fixed-length history buffer never
        reaches callers or plots."""
        stats = np.asarray(stats)
        nb = (stats.size - 3) // (2 + hist_len)
        iters = int(stats[0])
        converged = bool(stats[1])
        status = int(stats[2])
        norm0 = stats[3:3 + nb]
        res_norm = stats[3 + nb:3 + 2 * nb]
        hist = stats[3 + 2 * nb:].reshape(hist_len, nb)[: iters + 1]
        if nb == 1:
            norm0, res_norm, hist = norm0[0], res_norm[0], hist[:, 0]
        return iters, converged, status, norm0, res_norm, hist

    def solve(self, b, x0=None, zero_initial_guess: bool = False
              ) -> SolveResult:
        """Solve A x = b (Solver::solve analog, include/solvers/solver.h)."""
        from ..profiling import trace_region
        with trace_region(f"{self.name}.solve"):
            return self._solve_traced(b, x0, zero_initial_guess)

    def _solve_traced(self, b, x0=None, zero_initial_guess: bool = False
                      ) -> SolveResult:
        """The host side of a solve, in four disjoint stages that are
        spans under `<NAME>.solve` and counters of seconds
        (`solve.stage_s.<stage>`): prepare, run, readback, report.
        Nested solvers are traced into the one program and never come
        through here, so the stages are the called solver's own."""
        from ..telemetry import metrics as _tm
        from ..telemetry.spans import span
        if self.A is None:
            raise BadParametersError(
                f"solver {self.name}: solve() before setup()")
        with span("solve.prepare", counter="solve.stage_s.prepare"):
            b = jnp.asarray(b)
            if x0 is None or zero_initial_guess:
                x0 = jnp.zeros_like(b)
            else:
                x0 = jnp.asarray(x0)
            if self.scaler is not None:
                # solve (LAR) x' = L b, return x = R x' (monitored
                # residuals are in the scaled system — reference caveat
                # solver.cu:449)
                b = self.scaler.scale_rhs(b)
                x0 = self.scaler.to_scaled_x(x0)
            data = self.solve_data()
            # the faultinject epoch keys the cache so arming/consuming
            # a fault retraces instead of replaying a (possibly
            # poisoned) cached program; it is 0 forever when injection
            # is unused
            key = (b.shape, str(b.dtype), _fi.epoch())
            new_program = key not in self._jit_cache
            if new_program:
                _tm.inc("solver.retrace.solve")
                _fi.evict_stale_epochs(self._jit_cache, key[-1])
                self._jit_cache[key] = jax.jit(self._build_solve_fn())
                # static like the program: read once with it
                self._color_steps = self.color_steps_per_iteration()
                self._geo_transfers = self.geo_transfers_per_iteration()
            solve_fn = self._jit_cache[key]
        with span("solve.run", counter="solve.stage_s.run"):
            t0 = time.perf_counter()
            if new_program:
                x, stats = self._first_solve(solve_fn, key, data, b, x0)
            else:
                x, stats = jax.block_until_ready(solve_fn(data, b, x0))
        with span("solve.readback", counter="solve.stage_s.readback"):
            if self.scaler is not None:
                x = self.scaler.from_scaled_x(x)
            solve_time = time.perf_counter() - t0
            # diagnostics probe output rides the stats tail (same
            # buffer, no extra transfer); strip it by the same spec the
            # trace used before the bare-layout unpack
            diag_spec = self._diag_probe_spec()
            diag_raw = None
            stats = np.asarray(stats)
            if diag_spec is not None:
                from ..telemetry import diagnostics as _dg
                dlen = _dg.slots_len(diag_spec[0])
                if dlen:
                    diag_raw = stats[stats.size - dlen:]
                    stats = stats[:stats.size - dlen]
            # solver-declared extras sit just before the diagnostics
            # tail; strip by the same spec the trace packed them with
            extra_names = self._extra_stats_spec()
            extras = None
            if extra_names:
                raw = stats[stats.size - len(extra_names):]
                stats = stats[:stats.size - len(extra_names)]
                extras = {k: float(v) for k, v in zip(extra_names, raw)}
            iters_i, converged, status, norm0, res_norm, hist = \
                self.unpack_stats(stats, self.max_iters + 1)
            res = SolveResult(
                x=x, iterations=iters_i, converged=converged,
                res_norm=np.asarray(res_norm), norm0=np.asarray(norm0),
                res_history=np.asarray(hist)
                if self.store_res_history else None,
                setup_time=self.setup_time, solve_time=solve_time,
                status_code=status, extra_stats=extras)
            if self._color_steps:
                # the iterations that ran the colored cycle: an outer
                # shell's inner count where it keeps one, else its own
                _tm.inc("smoother.color_steps", self._color_steps * int(
                    round((extras or {}).get("inner_iters", iters_i))))
            # the cycles a solve ran: FGMRES's Arnoldi steps (its
            # own or summed by the shell round it), else the
            # inner count where one is kept, else this solver's
            ex = extras or {}
            cycles = int(round(ex.get(
                "arnoldi_steps", ex.get("inner_iters", iters_i)))) \
                * self.precond_applications_per_iteration
            if self._geo_transfers is not None:
                for road, levels in zip(("onepass", "xla"),
                                        self._geo_transfers):
                    _tm.inc(f"amg.geo_transfer.{road}", cycles * levels)
            swell_steps = self.swell_vreg_steps_per_iteration()
            if swell_steps:
                _tm.inc("swell.vreg_steps", cycles * swell_steps)
                _tm.add("swell.model_s",
                        cycles * self.swell_model_s_per_iteration())
            dia_calls, dia_rows = self.dia_smooth_per_iteration()
            if dia_calls:
                _tm.inc("smoother.dia_calls", cycles * dia_calls)
                _tm.inc("smoother.dia_row_apps", cycles * dia_rows)
            csr_nnz = self.csr_road_nnz_per_iteration()
            if csr_nnz:
                _tm.inc("cycle.csr_road_nnz", cycles * csr_nnz)
            if self._fused_sites:
                _tm.inc("krylov.fused_calls", iters_i * self._fused_sites)
            for name, value in (extras or {}).items():
                # an extra stat that a counter is named after (GMRES /
                # FGMRES's account of a solve, its own or summed by
                # the shell round it) is raised once a solve
                if f"krylov.{name}" in _tm.COUNTERS:
                    _tm.inc(f"krylov.{name}", int(round(value)))
        if self.telemetry or self.print_solve_stats:
            with span("solve.report", counter="solve.stage_s.report"):
                if self.telemetry:
                    # structured report (telemetry/report.py): built
                    # from the stats numpy already unpacked above +
                    # static hierarchy metadata — no device data is
                    # touched
                    from ..memory_info import peak_bytes
                    from ..telemetry import build_report
                    diag_struct = None
                    if diag_raw is not None:
                        from ..telemetry import diagnostics as _dg
                        diag_struct = _dg.derive(
                            diag_raw, len(diag_spec[0].levels),
                            res_hist=np.asarray(hist))
                    res.report = build_report(
                        self, res, hist=np.asarray(hist),
                        diagnostics=diag_struct,
                        precision=self._precision_block(res))
                    _tm.max_gauge("memory.solve_peak_bytes",
                                  peak_bytes())
                if self.print_solve_stats:
                    self._print_stats(res, np.asarray(hist))
        return res

    def _first_solve(self, solve_fn, key, data, b, x0):
        """A solve program's first call, which traces, lowers and
        compiles it, and the hand-over of what ran to
        `telemetry.programs`, which names the stages of its
        instructions later.

        The call is `jax.jit`'s own, so the program compiles once and
        dispatches as ever. `lower().compile()` afterwards finds the
        jaxpr, the lowering and the executable of that call in JAX's
        caches (about 2 ms; tests/test_stage_scopes.py holds it to no
        compile event) and returns the executable that runs."""
        from ..compile_cache import op_names_in_key
        from ..telemetry import programs
        with op_names_in_key():
            out = jax.block_until_ready(solve_fn(data, b, x0))
            programs.register(f"{self.name}.solve", key,
                              solve_fn.lower(data, b, x0).compile())
        return out

    def _print_stats(self, res: SolveResult, hist):
        from ..memory_info import update_max_memory_usage
        mem_gb = update_max_memory_usage() / 2**30
        amgx_printf(f"    iter      Mem Usage (GB)       residual           rate")
        amgx_printf(f"    {'-' * 62}")
        for i in range(res.iterations + 1):
            rate = ""
            if i > 0 and np.all(hist[i - 1] > 0):
                rate = f"{float(np.max(hist[i] / hist[i - 1])):14.4f}"
            tag = "Ini" if i == 0 else f"{i - 1:4d}"
            amgx_printf(f"    {tag}         {mem_gb:10.4f}      "
                  f"{float(np.max(hist[i])):14.6e} {rate}")
        amgx_printf(f"    {'-' * 62}")
        status = res.status if not res.converged else "success"
        amgx_printf(f"    Total Iterations: {res.iterations}")
        amgx_printf(f"    Avg Convergence Rate: "
              f"{float((np.max(hist[res.iterations]) / max(np.max(hist[0]), 1e-300)) ** (1.0 / max(res.iterations, 1))):10.4f}")
        amgx_printf(f"    Final Residual: {float(np.max(res.res_norm)):.6e}")
        amgx_printf(f"    Solve Status: {status}")
        if self.obtain_timings:
            amgx_printf(f"    Setup Time: {res.setup_time:.4f}s")
            amgx_printf(f"    Solve Time: {res.solve_time:.4f}s")

    # -- batched solves ---------------------------------------------------
    def solve_many(self, bs, matrices=None, x0s=None,
                   zero_initial_guess: bool = False):
        """Solve many systems in ONE jitted program (batch/core.py):
        `bs` stacks the right-hand sides along a leading batch axis.
        With matrices=None this is multi-RHS against the set-up matrix;
        with a list of same-pattern matrices each system gets its own
        coefficients (hierarchy structure reused, values spliced via the
        resetup path). Returns a BatchedSolveResult. The wrapped batched
        state is cached on the solver, so repeat calls with the same
        batch geometry reuse one trace."""
        if getattr(self, "_batched", None) is None:
            from ..batch import BatchedSolver
            self._batched = BatchedSolver(solver=self)
        return self._batched.solve_many(
            bs, matrices=matrices, x0s=x0s,
            zero_initial_guess=zero_initial_guess)

    # -- smoother interface (AMG levels) ---------------------------------
    def smooth(self, data, b, x, sweeps: int):
        """Apply `sweeps` relaxation sweeps to x (pure function). Default:
        run solve_iteration with monitoring off."""
        st = {"x": x, "r": _residual(data["A"], x, b)}
        st.update(self.solve_init(data, b, x, st["r"]))

        def body(_, s):
            return self.solve_iteration(data, b, s)

        st = jax.lax.fori_loop(0, sweeps, body, st)
        return st["x"]

    def smooth_residual(self, data, b, x, sweeps: int):
        """(x', r) after `sweeps` smoothing sweeps plus the residual
        r = b - A x' — the V-cycle's presmooth->restrict hot pair
        (amg/cycles.py). The default composes smooth() with one extra
        SpMV, so every smoother keeps working; the damped-relaxation
        smoothers (relaxation.py, polynomial.py) override with the
        fused single-pass kernels (ops/smooth.py) when the level's
        layout supports them."""
        x = self.smooth(data, b, x, sweeps)
        return x, _residual(data["A"], x, b)


def make_solver(name: str, cfg: Config, scope: str = "default") -> Solver:
    """SolverFactory::allocate analog."""
    cls = registry.solvers.get(name)
    return cls(cfg, scope, name=name.upper())
