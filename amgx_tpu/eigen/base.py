"""EigenSolver base: the eigensolver skeleton.

TPU-native analog of EigenSolver<TConfig>
(include/eigensolvers/eigensolver.h:25, src/eigensolvers/eigensolver.cu):
reads the eig_* parameter family, applies the spectral shift, runs a
jitted iteration loop with traced convergence checks, and postprocesses
(un-shift, optional eigenvector extraction).

Execution model mirrors solvers/base.py: `setup(A)` is host-orchestrated
once per structure; `solve()` compiles one XLA program — a
`lax.while_loop` whose body is `solve_iteration` — with no host
round-trips inside the loop. Small dense eigenproblems (tridiagonal T,
Hessenberg H, Rayleigh-Ritz Gram matrices) use `jnp.linalg.eigh` in-trace
for symmetric cases; the nonsymmetric Hessenberg eigenproblem is solved
on the host after the device loop (the reference likewise defers it to
LAPACK geev, src/amgx_lapack.cu).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import registry
from ..config import Config
from ..errors import BadParametersError
from ..matrix import CsrMatrix
from .operators import MatrixOperator, Operator, ShiftedOperator


@dataclasses.dataclass
class EigenResult:
    """Result of an eigensolve (AMGX_eigensolver_solve analog)."""
    eigenvalues: np.ndarray            # (k,)
    eigenvectors: Optional[np.ndarray]  # (n, k) or None
    iterations: int
    converged: bool
    residuals: np.ndarray              # (k,) final eigenpair residuals
    setup_time: float = 0.0
    solve_time: float = 0.0


class EigenSolver:
    """Base eigensolver (include/eigensolvers/eigensolver.h:25).

    Subclasses implement `solver_setup`, `solve_init`, `solve_iteration`,
    `finalize`; the base provides the shift, the jitted driver, and the
    convergence plumbing."""

    def __init__(self, cfg: Config, scope: str = "default", name: str = "?"):
        self.cfg = cfg
        self.scope = scope
        self.name = name
        self.max_iters = int(cfg.get("eig_max_iters", scope))
        self.tolerance = float(cfg.get("eig_tolerance", scope))
        self.shift = float(cfg.get("eig_shift", scope))
        self.which = str(cfg.get("eig_which", scope)).lower()
        self.wanted_count = int(cfg.get("eig_wanted_count", scope))
        self.subspace_size = int(cfg.get("eig_subspace_size", scope))
        self.check_freq = max(1, int(cfg.get("eig_convergence_check_freq",
                                             scope)))
        self.want_vectors = bool(int(cfg.get("eig_eigenvector", scope)))
        self.damping = float(cfg.get("eig_damping_factor", scope))
        self.A: Optional[CsrMatrix] = None
        self.op: Optional[Operator] = None
        self.setup_time = 0.0
        self._jit_cache: Dict[Any, Any] = {}

    # -- setup -----------------------------------------------------------
    def make_operator(self) -> Operator:
        """The operator the iteration applies. Default: (A - shift I)."""
        op: Operator = MatrixOperator(self.A)
        if self.shift != 0.0:
            op = ShiftedOperator(op, self.shift)
        return op

    def setup(self, A: CsrMatrix):
        t0 = time.perf_counter()
        if not A.initialized:
            A = A.init()
        if A.block_size != 1:
            raise BadParametersError(
                f"eigensolver {self.name}: block matrices not supported")
        self.A = A
        self.op = self.make_operator()
        self.solver_setup()
        self._jit_cache.clear()
        self.setup_time = time.perf_counter() - t0
        return self

    def solver_setup(self):
        pass

    # -- pure pieces -----------------------------------------------------
    def solve_data(self) -> Dict[str, Any]:
        return {"op": self.op.data()}

    def solve_init(self, data, x0) -> Dict[str, Any]:
        """Initial state. Must contain 'lambdas' (k,) and 'resid' (k,)."""
        raise NotImplementedError

    def solve_iteration(self, data, state) -> Dict[str, Any]:
        raise NotImplementedError

    def finalize(self, data, state):
        """Return (lambdas (k,), vectors (n,k) or None, resid (k,))."""
        raise NotImplementedError

    def unshift(self, lam):
        return lam + self.shift if self.shift != 0.0 else lam

    # -- driver ----------------------------------------------------------
    def _build_solve_fn(self):
        max_iters = self.max_iters
        tol = self.tolerance
        freq = self.check_freq

        def solve_fn(data, x0):
            state = self.solve_init(data, x0)
            state["iters"] = jnp.asarray(0, jnp.int32)
            state["done"] = jnp.asarray(False)

            def cond(st):
                return (~st["done"]) & (st["iters"] < max_iters)

            def body(st):
                iters = st["iters"]
                core = {k: v for k, v in st.items()
                        if k not in ("iters", "done")}
                core = self.solve_iteration(data, core)
                new = dict(core)
                new["iters"] = iters + 1
                scale = jnp.maximum(jnp.max(jnp.abs(core["lambdas"])), 1e-30)
                conv = jnp.all(core["resid"] <= tol * scale)
                new["done"] = conv & (((iters + 1) % freq) == 0)
                return new

            final = jax.lax.while_loop(cond, body, state)
            lam, vec, resid = self.finalize(data, final)
            scale = jnp.maximum(jnp.max(jnp.abs(lam)), 1e-30)
            conv = jnp.all(resid <= tol * scale)
            # pack scalars/small stats into ONE auxiliary output
            # (see solvers/base.py)
            rdt = jnp.promote_types(jnp.asarray(lam).dtype, jnp.float32)
            if jnp.issubdtype(rdt, jnp.complexfloating):
                rdt = jnp.float64
                lam_flat = jnp.concatenate([jnp.real(lam), jnp.imag(lam)])
                complex_lam = True
            else:
                lam_flat = jnp.ravel(lam)
                complex_lam = False
            stats = jnp.concatenate([
                jnp.reshape(final["iters"].astype(rdt), (1,)),
                jnp.reshape(conv.astype(rdt), (1,)),
                lam_flat.astype(rdt), jnp.ravel(resid).astype(rdt)])
            if vec is None:
                vec = jnp.zeros((0,), stats.dtype)
            self._complex_lam = complex_lam
            return vec, stats

        return solve_fn

    def solve(self, x0=None) -> EigenResult:
        if self.A is None:
            raise BadParametersError(
                f"eigensolver {self.name}: solve() before setup()")
        n = self.A.num_rows
        if x0 is None:
            # deterministic pseudo-random start (reference seeds its RNG)
            x0 = jnp.asarray(
                np.random.default_rng(42).standard_normal(n),
                dtype=self.A.dtype)
        else:
            x0 = jnp.asarray(x0, dtype=self.A.dtype)
        key = (x0.shape, str(x0.dtype))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(self._build_solve_fn())
        t0 = time.perf_counter()
        vec, stats = jax.block_until_ready(self._jit_cache[key](
            self.solve_data(), x0))
        solve_time = time.perf_counter() - t0
        stats = np.asarray(stats)                   # one host fetch
        iters = int(stats[0])
        conv = bool(stats[1])
        body = stats[2:]
        if getattr(self, "_complex_lam", False):
            m = body.size // 3
            lam = body[:m] + 1j * body[m:2 * m]
            resid = body[2 * m:]
        else:
            m = body.size // 2
            lam, resid = body[:m], body[m:]
        if vec.size == 0:
            vec = None
        lam, vec, resid, iters, conv = self.postprocess(
            lam, vec, resid, iters, conv)
        return EigenResult(
            eigenvalues=np.atleast_1d(np.asarray(self.unshift(lam))),
            eigenvectors=None if vec is None else np.asarray(vec),
            iterations=int(iters), converged=bool(conv),
            residuals=np.atleast_1d(np.asarray(resid)),
            setup_time=self.setup_time, solve_time=solve_time)

    def postprocess(self, lam, vec, resid, iters, conv):
        """Host-side post-loop hook (Arnoldi solves its Hessenberg
        eigenproblem here, the way the reference calls LAPACK)."""
        return lam, vec, resid, iters, conv


def make_eigensolver(name: str, cfg: Config, scope: str = "default"
                     ) -> EigenSolver:
    """EigenSolverFactory::allocate analog."""
    cls = registry.eigensolvers.get(name)
    return cls(cfg, scope, name=name.upper())


def create_eigensolver(cfg: Config, scope: str = "default") -> EigenSolver:
    """AMG_EigenSolver analog (src/amg_eigensolver.cu): build the
    eigensolver named by eig_solver."""
    return make_eigensolver(str(cfg.get("eig_solver", scope)), cfg, scope)
