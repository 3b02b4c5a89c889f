"""Matrix-free stencil operators for constant-coefficient GEO levels.

A GEO hierarchy built from a constant-coefficient grid operator (the
structured-gallery Poisson family and everything the structured
Galerkin pair-sum derives from it) stores a DIA value slab that is
pure redundancy: every diagonal holds ONE scalar repeated across its
in-grid rows and zeros where the geometric shift exits the grid. On a
memory-bound TPU that slab is the LARGEST stream in every fused
smoother/residual kernel — k value floats per output element versus
~2 vector floats — so dropping it roughly halves the solve-phase HBM
traffic and removes the O(nnz) term from the operator's solve-data
footprint (O(levels) coefficient vectors remain).

This module is the matrix-free core:

- `StencilOperator`: the solve-data payload — a (k,) coefficient
  vector plus static geometry (offsets, grid shifts, grid shape) and
  the smoother's diagonal-inverse mode. Registered as a pytree so it
  rides solve_data like any other leaf; the coefficients are the only
  device data.
- `detect_stencil`: the setup-time constant-coefficient check — one
  jitted compare per level (every in-grid entry equals its diagonal's
  anchor value, every off-grid entry is zero; the anchor row is the
  first row where the shift is in-grid, so the check subsumes the
  GEO wrap check) and one tiny transfer (a bool + k scalars).
- XLA composes (`stencil_spmv`, `stencil_fused_smooth`): masked
  shifted adds `y = sum_t where(ok_t, c_t * shift(x)), 0)` — the f64 /
  batched / non-TPU route, and the route the paired CPU bench
  measures. The per-offset masks are the same static-bound
  grid comparisons the Pallas kernels evaluate in-register
  (ops/pallas_spmv.py `_mf_*` helpers).
- Pallas dispatch: the fused kernels' `coeffs` mode reads the k
  scalars from SMEM and synthesizes the value rows from the masks, so
  the A-operand stream (and its VMEM window) vanishes; plan math in
  `dia_smooth_plan(..., coeffs=True)`.
- `stencil_dia_vals` / `stencil_matrix`: in-trace materialization of
  the equivalent DIA slab — the escape hatch for consumers that
  genuinely need a matrix (residual monitoring, K-cycle coarse SpMV,
  diagnostics probes), costing VPU work instead of resident HBM.

Routing policy lives in amg/hierarchy.py (`matrix_free=auto|0|1`):
variable-coefficient operators fail the detector and keep the slab
path; `0` never calls the detector, so the slab build is bit-for-bit
untouched.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_spmv as _ps
from ..profiling import trace_region

# Hashable static twin of a StencilOperator (everything but the
# coefficients) — the lru/jit cache key for the kernel factories and
# custom_vmap wrappers. `dinv` is None | "jacobi" | "l1";
# `diag_rank` is the index of offset 0 (-1 when absent).
StencilSpec = collections.namedtuple(
    "StencilSpec", "offsets shifts shape n dinv diag_rank")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["coeffs"],
    meta_fields=["offsets", "shifts", "shape", "num_rows", "dinv_mode",
                 "diag_rank"],
)
@dataclasses.dataclass(frozen=True)
class StencilOperator:
    """Constant-coefficient grid operator: A[i, i+offsets[t]] =
    coeffs[t] wherever the grid shift stays in-grid, 0 elsewhere.
    The ONLY device payload is `coeffs` (k,) — O(levels) operator
    memory across a hierarchy instead of O(nnz)."""

    coeffs: jax.Array                  # (k,)
    offsets: tuple                     # linear DIA offsets, ascending
    shifts: tuple                      # ((dx, dy, dz),) per offset
    shape: tuple                       # (nx, ny, nz), x fastest
    num_rows: int
    dinv_mode: Optional[str] = None    # None | "jacobi" | "l1"
    diag_rank: int = -1

    @property
    def k(self) -> int:
        return len(self.offsets)

    def spec(self) -> StencilSpec:
        return StencilSpec(self.offsets, self.shifts, self.shape,
                           self.num_rows, self.dinv_mode, self.diag_rank)


def _anchor_index(shift, shape) -> int:
    """First linear row index where `shift` stays in-grid — the row the
    detector reads each diagonal's candidate coefficient from."""
    nx, ny, _nz = shape
    dx, dy, dz = shift
    return (max(0, -dz) * ny + max(0, -dy)) * nx + max(0, -dx)


@functools.partial(jax.jit, static_argnames=("shifts", "shape"))
def stencil_candidate(vals2d, shifts, shape):
    """(is_const, coeffs) for a (k, n) DIA value table: coeffs[t] is
    the anchor-row value of diagonal t; is_const is True iff every
    in-grid entry equals it AND every off-grid entry is zero (which
    subsumes the GEO wrap check — a wrapped nonzero sits off-grid)."""
    nx, ny, nz = shape
    n = vals2d.shape[1]
    ix = jnp.arange(n, dtype=jnp.int32)
    gx = ix % nx
    gy = (ix // nx) % ny
    gz = ix // (nx * ny)
    coeffs, flags = [], []
    for t, (dx, dy, dz) in enumerate(shifts):
        ok = ((gx + dx >= 0) & (gx + dx < nx) & (gy + dy >= 0)
              & (gy + dy < ny) & (gz + dz >= 0) & (gz + dz < nz))
        c = vals2d[t, _anchor_index((dx, dy, dz), shape)]
        coeffs.append(c)
        flags.append(jnp.all(jnp.where(ok, vals2d[t] == c,
                                       vals2d[t] == 0)))
    return jnp.stack(flags).all(), jnp.stack(coeffs)


def stencil_shifts(offsets, shape):
    """Per-offset (dx, dy, dz) grid shifts, or None when any offset is
    not a small stencil shift of `shape`."""
    from ..amg.aggregation.galerkin import _decompose
    nx, ny, nz = shape
    shifts = []
    for d in offsets:
        g = _decompose(int(d), nx, ny, nz)
        if g is None:
            return None
        shifts.append(g)
    return tuple(shifts)


def detect_stencil(A, dinv_mode: Optional[str] = None,
                   coeffs_hint=None, why: Optional[dict] = None):
    """StencilOperator for a constant-coefficient DIA grid operator,
    or None (variable coefficients, no DIA/grid annotation, blocks,
    external diagonals, non-stencil offsets). One jitted compare +
    one tiny transfer per level. `coeffs_hint` (a (k,) device array,
    e.g. from GeoRapPlan.coarse_coeffs) skips the extraction and only
    runs the constancy compare against it. A caller that hands in a
    dict `why` finds the reason of a None under its `declined` key."""
    def declined(reason):
        if why is not None:
            why["declined"] = reason
        return None

    if A.is_block or A.has_external_diag or A.num_rows != A.num_cols:
        return declined("not a square scalar matrix with its diagonal")
    if getattr(A, "dia_offsets", None) is None \
            or getattr(A, "dia_vals", None) is None:
        return declined("no DIA layout")
    if getattr(A, "grid_shape", None) is None:
        return declined("no grid annotation")
    shape = tuple(int(s) for s in A.grid_shape)
    if len(shape) != 3 or int(np.prod(shape)) != A.num_rows:
        return declined("grid annotation is not the rows'")
    shifts = stencil_shifts(A.dia_offsets, shape)
    if shifts is None:
        return declined("offsets are no stencil's")
    k = len(A.dia_offsets)
    vals2d = A.dia_vals.reshape(k, -1)[:, :A.num_rows]
    ok, coeffs = stencil_candidate(vals2d, shifts, shape)
    if coeffs_hint is not None:
        coeffs = coeffs_hint
    # the flag's fetch: the host waits here for the level's values
    with trace_region("mf_detect.sync"):
        ok = bool(ok)
    if not ok:
        return declined("values vary")
    offsets = tuple(int(d) for d in A.dia_offsets)
    return StencilOperator(
        coeffs=coeffs, offsets=offsets, shifts=shifts, shape=shape,
        num_rows=int(A.num_rows), dinv_mode=dinv_mode,
        diag_rank=offsets.index(0) if 0 in offsets else -1)


def mf_slim(A):
    """Solve-phase view of a matrix-free level's operator: the SpMV
    slim form with the DIA value slab dropped entirely. The result
    supports NOTHING by itself — every solve-phase consumer must route
    through the level's StencilOperator (or `stencil_matrix`); a stray
    spmv() against it fails loudly instead of serving garbage."""
    s = A.slim_for_spmv() if hasattr(A, "slim_for_spmv") else A
    if getattr(s, "dia_vals", None) is None:
        return s
    return dataclasses.replace(s, dia_vals=None)


# ---------------------------------------------------------------------------
# XLA masked-coefficient forms (vector layout)
# ---------------------------------------------------------------------------


def _vec_masks(spec):
    """Per-offset in-grid masks on the (n,) vector layout — the same
    static-bound comparisons the Pallas coeffs mode evaluates on its
    (rows, 128) windows."""
    nx, ny, nz = spec.shape
    ix = jnp.arange(spec.n, dtype=jnp.int32)
    gx = ix % nx
    gy = (ix // nx) % ny
    gz = ix // (nx * ny)
    masks = []
    for (dx, dy, dz) in spec.shifts:
        ok = None

        def conj(a, b):
            return b if a is None else a & b

        if dx < 0:
            ok = conj(ok, gx >= -dx)
        if dx > 0:
            ok = conj(ok, gx < nx - dx)
        if dy < 0:
            ok = conj(ok, gy >= -dy)
        if dy > 0:
            ok = conj(ok, gy < ny - dy)
        if dz < 0:
            ok = conj(ok, gz >= -dz)
        if dz > 0:
            ok = conj(ok, gz < nz - dz)
        masks.append(ok)          # None == everywhere in-grid
    return masks


def _apply_vec(spec, coeffs, x):
    """y = A x as masked shifted adds — no materialized values."""
    masks = _vec_masks(spec)
    y = jnp.zeros_like(x)
    zero = jnp.zeros((), x.dtype)
    for t, d in enumerate(spec.offsets):
        xs = jnp.roll(x, -d) if d else x
        term = coeffs[t].astype(x.dtype) * xs
        y = y + (term if masks[t] is None
                 else jnp.where(masks[t], term, zero))
    return y


def _dinv_vec(spec, coeffs, dtype):
    """The smoother's diagonal-inverse vector synthesized from the
    coefficients: matches safe_recip(diagonal) ("jacobi") or
    safe_recip(l1_strengthened_diag) ("l1") on the materialized
    matrix; None when the smoother carries no dinv (Chebyshev)."""
    if spec.dinv is None:
        return None
    c0 = coeffs[spec.diag_rank].astype(dtype)
    if spec.dinv == "jacobi":
        den = jnp.full((spec.n,), c0, dtype)
    else:                               # "l1"
        masks = _vec_masks(spec)
        l1 = jnp.zeros((spec.n,), dtype)
        for t in range(len(spec.offsets)):
            if t == spec.diag_rank:
                continue
            a = jnp.abs(coeffs[t].astype(dtype))
            l1 = l1 + (jnp.full((spec.n,), a, dtype)
                       if masks[t] is None
                       else jnp.where(masks[t], a, 0))
        den = c0 + jnp.sign(c0) * l1
    return jnp.where(den == 0, jnp.zeros((), dtype),
                     1 / jnp.where(den == 0, jnp.ones((), dtype), den))


def stencil_spmv(st: StencilOperator, x):
    """y = A x from coefficients only (all dtypes, all backends)."""
    return _apply_vec(st.spec(), st.coeffs, x)


def _xla_smooth(spec, coeffs, taus, b, x, with_residual):
    """Damped-relaxation sweeps + optional residual, accumulated at
    the kernel's compute dtype (f32 for bf16 vectors) so the XLA and
    Pallas routes agree to rounding."""
    cdt = _ps.compute_dtype(x.dtype)
    xs = x.astype(cdt)
    bs = b.astype(cdt)
    cc = coeffs.astype(cdt)
    dinv = _dinv_vec(spec, cc, cdt)
    for t in range(int(taus.shape[0])):
        corr = taus[t].astype(cdt) * (bs - _apply_vec(spec, cc, xs))
        if dinv is not None:
            corr = corr * dinv
        xs = xs + corr
    y = xs.astype(x.dtype)
    if with_residual:
        r = bs - _apply_vec(spec, cc, xs)
        return y, r.astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# dispatch (Pallas coeffs mode with XLA fallback under one custom_vmap)
# ---------------------------------------------------------------------------


def _runtime_on() -> bool:
    return _ps.pallas_backend() is not None


def _dtype_ok(x_dtype) -> bool:
    return _ps.kernel_dtype_ok(x_dtype)


def stencil_smooth_supported(spec, x_dtype, n_steps: int,
                             with_residual: bool) -> bool:
    """Trace-time gate for the fused coeffs-mode smoother kernel."""
    if not _runtime_on() or not _dtype_ok(x_dtype):
        return False
    return _ps.dia_smooth_plan(
        spec.offsets, len(spec.offsets), spec.n, n_steps, with_residual,
        itemsize=jnp.dtype(x_dtype).itemsize, coeffs=True) is not None


@functools.lru_cache(maxsize=None)
def _smooth_fn(spec, with_residual: bool):
    """custom_vmap-wrapped matrix-free smoother for one static spec:
    the primal runs the fused coeffs-mode Pallas kernel when supported
    and the XLA masked compose otherwise (f64, CPU, oversized plans);
    any vmapped batch (batched coefficients AND plain multi-RHS) takes
    the vmapped XLA compose — the masks broadcast, so no per-system
    value stream ever materializes."""
    tu = jax.tree_util

    @jax.custom_batching.custom_vmap
    def call(coeffs, taus, b, x):
        n_steps = int(taus.shape[0])
        if stencil_smooth_supported(spec, x.dtype, n_steps,
                                    with_residual):
            return _ps._dia_stencil_smooth_call(
                coeffs, taus, b, x, spec, with_residual,
                interpret=_ps._FORCE_INTERPRET)
        return _xla_smooth(spec, coeffs, taus, b, x, with_residual)

    @call.def_vmap
    def _rule(axis_size, in_batched, coeffs, taus, b, x):
        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        y = jax.vmap(
            lambda c_, t_, b_, x_: _xla_smooth(spec, c_, t_, b_, x_,
                                               with_residual),
            in_axes=axes, axis_size=axis_size)(coeffs, taus, b, x)
        return y, ((True, True) if with_residual else True)

    return call


def stencil_fused_smooth(st: StencilOperator, taus, b, x,
                         with_residual=True):
    """Matrix-free smoother dispatch: x' (and r) after len(taus)
    damped sweeps. ALWAYS produces a result — there is no slab to fall
    back to: one fused coeffs-mode pallas_call when the schedule has a
    plan (ops/pallas_spmv.dia_smooth_plan), the XLA masked compose
    otherwise (f64, CPU, a schedule longer than SMOOTH_MAX_APPS)."""
    spec = st.spec()
    coeffs = st.coeffs
    cdt = _ps.compute_dtype(x.dtype)
    taus = jnp.asarray(taus, cdt)
    if int(taus.shape[0]) < 1:
        if with_residual:
            cc = coeffs.astype(cdt)
            r = b.astype(cdt) - _apply_vec(spec, cc, x.astype(cdt))
            return x, r.astype(x.dtype)
        return x
    return _smooth_fn(spec, with_residual)(coeffs, taus, b, x)


# ---------------------------------------------------------------------------
# Krylov shell fusion: coeffs-mode SpMV + dot twin
# ---------------------------------------------------------------------------


def stencil_spmv_dot_supported(spec, x_dtype) -> bool:
    """Trace-time gate for the coeffs-mode SpMV+dot shell kernel: the
    slab gate's VMEM model minus the vanished values stream, plus the
    mask/coordinate working set."""
    if not _runtime_on() or not _dtype_ok(x_dtype):
        return False
    k = len(spec.offsets)
    left, halo_rows, br = _ps._layout(spec.offsets, k, spec.n)
    ib = jnp.dtype(x_dtype).itemsize
    win = br + halo_rows
    vmem = 2 * 2 * win * _ps.LANES * ib \
        + 2 * 3 * br * _ps.LANES * ib \
        + _ps._MF_WORK_ROWS * br * _ps.LANES * 4
    if ib < 4:
        vmem += (2 * win + 2 * br) * _ps.LANES * 4
    return vmem <= _ps._VMEM_BUDGET + 4 * 1024 * 1024


def _xla_spmv_dot(spec, coeffs, p, z, beta, d, self_dot):
    """Unfused masked-coefficient compose of the shell kernel — the
    f64 / batched route; the dots are plain vdots, so the f64 parity
    reference is the exact unfused arithmetic."""
    if z is not None:
        p = (z + beta * p).astype(p.dtype)
    ap = _apply_vec(spec, coeffs, p)
    dvec = p if d is None else d
    out = (ap, jnp.vdot(dvec, ap)) if z is None \
        else (p, ap, jnp.vdot(dvec, ap))
    if self_dot:
        out = out + (jnp.vdot(ap, ap),)
    return out


@functools.lru_cache(maxsize=None)
def _spmv_pdot_mf_fn(spec):
    tu = jax.tree_util

    @jax.custom_batching.custom_vmap
    def call(coeffs, p, z, beta):
        if stencil_spmv_dot_supported(spec, p.dtype):
            return _ps._dia_spmv_dot_call(
                None, p, z, beta, None, spec.offsets, spec.n,
                mf=spec, coeffs=coeffs,
                interpret=_ps._FORCE_INTERPRET)
        return _xla_spmv_dot(spec, coeffs, p, z, beta, None, False)

    @call.def_vmap
    def _rule(axis_size, in_batched, coeffs, p, z, beta):
        # no value stream exists to share, so every batch (coefficient
        # or vector) takes the vmapped masked compose
        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        fn = lambda c_, p_, z_, b_: _xla_spmv_dot(  # noqa: E731
            spec, c_, p_, z_, b_, None, False)
        y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
            coeffs, p, z, beta)
        return y, (True, True, True)

    return call


@functools.lru_cache(maxsize=None)
def _spmv_ddot_mf_fn(spec, self_dot: bool):
    tu = jax.tree_util
    ob = (True,) * (3 if self_dot else 2)

    @jax.custom_batching.custom_vmap
    def call(coeffs, p, d):
        if stencil_spmv_dot_supported(spec, p.dtype):
            return _ps._dia_spmv_dot_call(
                None, p, None, None, d, spec.offsets, spec.n,
                self_dot=self_dot, mf=spec, coeffs=coeffs,
                interpret=_ps._FORCE_INTERPRET)
        return _xla_spmv_dot(spec, coeffs, p, None, None, d, self_dot)

    @call.def_vmap
    def _rule(axis_size, in_batched, coeffs, p, d):
        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        fn = lambda c_, p_, d_: _xla_spmv_dot(  # noqa: E731
            spec, c_, p_, None, None, d_, self_dot)
        y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
            coeffs, p, d)
        return y, ob

    return call


def stencil_spmv_pdot(st: StencilOperator, p, z, beta):
    """Coeffs-mode twin of ops.spmv.spmv_pdot: p' = z + beta p,
    Ap' and the LOCAL p'.Ap' scalar with NO A value stream at all
    (masks synthesized from k SMEM scalars)."""
    return _spmv_pdot_mf_fn(st.spec())(st.coeffs, p, z, beta)


def stencil_spmv_ddot(st: StencilOperator, p, d, self_dot: bool = False):
    """Coeffs-mode twin of ops.spmv.spmv_ddot: Ap and the LOCAL d.Ap
    (and Ap.Ap when `self_dot`) from the kernel epilogue."""
    return _spmv_ddot_mf_fn(st.spec(), self_dot)(st.coeffs, p, d)


# ---------------------------------------------------------------------------
# materialization escape hatch
# ---------------------------------------------------------------------------


def stencil_dia_vals(st: StencilOperator, dtype=None):
    """Traced (k, rows_pad, 128) DIA slab equivalent to the stencil —
    the escape hatch for consumers that need a matrix (residual
    monitoring, K-cycle coarse SpMV, diagnostics). Recomputed per use:
    VPU work instead of resident HBM."""
    spec = st.spec()
    dt = jnp.dtype(dtype) if dtype is not None else st.coeffs.dtype
    k = st.k
    rows_pad = _ps.dia_padded_rows(k, spec.n)
    idx = jnp.arange(rows_pad * _ps.LANES, dtype=jnp.int32)
    nx, ny, nz = spec.shape
    gx = idx % nx
    gy = (idx // nx) % ny
    gz = idx // (nx * ny)
    valid = idx < spec.n
    rows = []
    for t, (dx, dy, dz) in enumerate(spec.shifts):
        ok = (valid & (gx + dx >= 0) & (gx + dx < nx)
              & (gy + dy >= 0) & (gy + dy < ny)
              & (gz + dz >= 0) & (gz + dz < nz))
        rows.append(jnp.where(ok, st.coeffs[t].astype(dt),
                              jnp.zeros((), dt)))
    return jnp.stack(rows).reshape(k, rows_pad, _ps.LANES)


def stencil_matrix(A_slim, st: StencilOperator):
    """Rebuild a usable slim DIA matrix around materialized values
    (in-trace; pairs with `mf_slim`)."""
    return dataclasses.replace(
        A_slim, dia_vals=stencil_dia_vals(st, A_slim.dtype))


def level_operator(data):
    """The solve-phase operator of a level-data dict: matrix-free
    levels (slab dropped by `mf_slim`) rebuild it in-trace from the
    stencil payload; everything else passes through. The single entry
    amg/cycles.py routes its residual/K-cycle/diagnostics matrix
    reads through."""
    A = data.get("A")
    st = data.get("stencil")
    if st is not None and getattr(A, "dia_vals", None) is None \
            and getattr(A, "dia_offsets", None) is not None:
        return stencil_matrix(A, st)
    return A


def solve_data_stencil(data):
    """The StencilOperator of a level-data dict (level or smoother
    scope), or None."""
    st = data.get("stencil")
    if st is None:
        smd = data.get("smoother")
        if isinstance(smd, dict):
            st = smd.get("stencil")
    return st
