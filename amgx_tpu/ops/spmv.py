"""Sparse matrix-vector product.

TPU-native analog of the reference SpMV stack (src/multiply.cu:74-121,
block dispatch :50, cuSPARSE wrappers src/amgx_cusparse.cu). Two execution
shapes, both fully jittable with static shapes:

- CSR + segmented-sum: gather x at col_indices, multiply, segment-sum by
  precomputed per-nnz row ids (`indices_are_sorted=True` — CSR order).
- padded ELL: dense (n, k) gather + row reduction. For stencil-like
  matrices (bounded row length) this is the fast path on TPU: it is pure
  dense vector-unit work with no scatter.

The choice is made at Matrix.init() time; `spmv` dispatches on which
auxiliaries are present. Block (bxb) matrices contract each block with an
einsum so XLA can batch them onto the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..matrix import CsrMatrix
from ..resilience import faultinject as _fault


def _ensure_init(A: CsrMatrix, x: jax.Array) -> CsrMatrix:
    if not A.initialized:
        raise ValueError(
            "spmv requires an initialized matrix (call A.init() at setup "
            "time; inside jit, pass the initialized matrix in)")
    expect = A.num_cols * A.block_dimy
    if x.shape != (expect,):
        raise ValueError(
            f"spmv: x has shape {x.shape}, expected ({expect},) for a "
            f"{A.num_rows}x{A.num_cols} matrix with block_dimy="
            f"{A.block_dimy} (JAX would silently clamp the gather)")
    return A


def spmv_csr_segsum(A: CsrMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x via gather + segmented sum over CSR order."""
    n = A.num_rows
    if A.is_block:
        bx, by = A.block_dimx, A.block_dimy
        xb = x.reshape(-1, by)
        prod = jnp.einsum("nxy,ny->nx", A.values, xb[A.col_indices])
        y = jax.ops.segment_sum(prod, A.row_ids, num_segments=n,
                                indices_are_sorted=True)
        if A.has_external_diag:
            y = y + jnp.einsum("nxy,ny->nx", A.diag, xb[:n])
        return y.reshape(-1)
    prod = A.values * x[A.col_indices]
    y = jax.ops.segment_sum(prod, A.row_ids, num_segments=n,
                            indices_are_sorted=True)
    if A.has_external_diag:
        y = y + A.diag * x[:n]
    return y


def spmv_ell(A: CsrMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x via the padded-ELL layout (dense gather + reduce)."""
    n = A.num_rows
    if A.is_block:
        by = A.block_dimy
        xb = x.reshape(-1, by)
        y = jnp.einsum("nkxy,nky->nx", A.ell_vals, xb[A.ell_cols])
        if A.has_external_diag:
            y = y + jnp.einsum("nxy,ny->nx", A.diag, xb[:n])
        return y.reshape(-1)
    y = (A.ell_vals * x[A.ell_cols]).sum(axis=1)
    if A.has_external_diag:
        y = y + A.diag * x[:n]
    return y


def _spmv_dia_xla(A: CsrMatrix, x: jax.Array) -> jax.Array:
    """XLA form of the DIA SpMV (f64/CPU/batched fallback) — the
    single-vector view of the multi-RHS slab form, so the DIA
    padding/shift arithmetic lives in exactly one place."""
    from .batched import spmv_dia_multi
    return spmv_dia_multi(A, x[None])[0]


@jax.custom_batching.custom_vmap
def _spmv_dia_pallas(A: CsrMatrix, x: jax.Array) -> jax.Array:
    from .pallas_spmv import dia_spmv
    return dia_spmv(A, x)


@_spmv_dia_pallas.def_vmap
def _spmv_dia_pallas_vmap(axis_size, in_batched, A, x):
    """pallas_call has no batching rule for ANY-space operands; batched
    SpMV (AffinityStrength, eigen block solvers, the batch/ subsystem's
    vmapped solves) takes the XLA form. When only the vector is batched
    (multi-RHS against one matrix — the batch subsystem's shared-pattern
    shape) the dedicated multi-RHS slab form avoids restreaming the
    diagonal values per system."""
    A_b, x_b = in_batched
    if x_b and not any(jax.tree_util.tree_leaves(A_b)):
        from .batched import spmv_dia_multi
        return spmv_dia_multi(A, x), True
    in_axes = (jax.tree_util.tree_map(lambda b: 0 if b else None, A_b),
               0 if x_b else None)
    y = jax.vmap(_spmv_dia_xla, in_axes=in_axes,
                 axis_size=axis_size)(A, x)
    return y, True


@jax.custom_batching.custom_vmap
def _spmv_swell_pallas(A: CsrMatrix, x: jax.Array) -> jax.Array:
    from .pallas_swell import swell_spmv
    return swell_spmv(A, x)


@_spmv_swell_pallas.def_vmap
def _spmv_swell_pallas_vmap(axis_size, in_batched, A, x):
    from .pallas_swell import swell_spmv_xla
    A_b, x_b = in_batched
    in_axes = (jax.tree_util.tree_map(lambda b: 0 if b else None, A_b),
               0 if x_b else None)
    y = jax.vmap(swell_spmv_xla, in_axes=in_axes, axis_size=axis_size)(A, x)
    return y, True


def spmv_swell(A: CsrMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x in the windowed-ELL (SWELL) layout: the Pallas
    lane-gather kernel on TPU/f32 (ops/pallas_swell.py — the unstructured
    analog of the DIA fast path), the XLA gather form elsewhere."""
    from .pallas_swell import swell_spmv_supported, swell_spmv_xla
    if swell_spmv_supported(A, x.dtype):
        y = _spmv_swell_pallas(A, x)
    else:
        y = swell_spmv_xla(A, x)
    if A.has_external_diag:
        y = y + A.diag * x[: A.num_rows]
    return y


def spmv_split(A: CsrMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x in the row-split SWELL form, A = S A': the pieces'
    products through the SWELL kernel, then each row's pieces summed by
    the same kernel (ops/pallas_swell.split_rows_host)."""
    Ap, S = A.split
    y = spmv_swell(S, spmv_swell(Ap, x))
    if A.has_external_diag:
        y = y + A.diag * x[: A.num_rows]
    return y


def spmv_dia(A: CsrMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x in DIA (diagonal) storage: for each stored diagonal with
    offset d, y += vals_d * shift(x, d). Pure dense vector multiply-adds
    with static slices — the TPU roofline layout for stencil matrices
    (no gather; ~2 HBM streams per diagonal). On TPU/f32 the fused
    Pallas kernel (ops/pallas_spmv.py) does the whole reduction in one
    HBM pass; the XLA form covers f64, CPU, and vmapped callers."""
    from .pallas_spmv import dia_spmv_supported
    if dia_spmv_supported(A, x.dtype):
        return _spmv_dia_pallas(A, x)
    return _spmv_dia_xla(A, x)


def spmv(A, x: jax.Array) -> jax.Array:
    """y = A @ x; dispatches on the layout chosen at init
    (multiply_block_size analog, src/multiply.cu:50). Non-CsrMatrix
    operands (distributed shard matrices, solve-operators) provide their
    own .spmv — the Operator abstraction of include/operators/operator.h.

    The resilience fault harness hooks the output here: a trace-time
    no-op unless an `spmv_nan` fault is armed AND a solve-loop
    iteration scope is active (resilience/faultinject.py)."""
    if not isinstance(A, CsrMatrix):
        return _fault.corrupt_spmv(A.spmv(x))
    _ensure_init(A, x)
    if A.dia_offsets is not None:
        return _fault.corrupt_spmv(spmv_dia(A, x))
    if A.swell_cols is not None:
        return _fault.corrupt_spmv(spmv_swell(A, x))
    if A.split is not None:
        return _fault.corrupt_spmv(spmv_split(A, x))
    if A.ell_cols is not None:
        return _fault.corrupt_spmv(spmv_ell(A, x))
    return _fault.corrupt_spmv(spmv_csr_segsum(A, x))


# ---------------------------------------------------------------------------
# Krylov shell fusion dispatch: SpMV with dot epilogue (+ optional
# direction-update prologue). The Pallas kernel runs under the same
# custom_vmap contract as the fused smoother suite: vector-only vmap
# batches (solve_many) take the multi-RHS slab forms in ops/batched.py,
# batched matrices take the vmapped XLA compose. The returned dot
# scalars are LOCAL sums — distributed callers psum them (packed,
# blas.psum_bundle).
# ---------------------------------------------------------------------------


def _spmv_pdot_xla(A, p, z, beta):
    """Unfused XLA compose of the prologue variant — exactly the
    pre-fusion expressions, so the f64 route of a `krylov_fusion=1`
    solver reproduces the unfused arithmetic identically."""
    p = (z + beta * p).astype(p.dtype)
    ap = spmv(A, p)
    return p, ap, jnp.vdot(p, ap)


def _spmv_ddot_xla(A, p, d, self_dot):
    ap = spmv(A, p)
    out = (ap, jnp.vdot(d, ap))
    if self_dot:
        out = out + (jnp.vdot(ap, ap),)
    return out


def _bcast(v, batched, axis_size):
    return v if batched else jnp.broadcast_to(
        v, (axis_size,) + jnp.shape(v))


@functools.lru_cache(maxsize=None)
def _spmv_pdot_fn():
    tu = jax.tree_util

    @jax.custom_batching.custom_vmap
    def call(A, p, z, beta):
        from .pallas_spmv import dia_spmv_dot
        return dia_spmv_dot(A, p, z=z, beta=beta)

    @call.def_vmap
    def _rule(axis_size, in_batched, A, p, z, beta):
        mat_b = any(tu.tree_leaves(in_batched[0]))
        if not mat_b:
            from .batched import spmv_dot_multi
            return (spmv_dot_multi(
                A, _bcast(p, in_batched[1], axis_size),
                _bcast(z, in_batched[2], axis_size),
                _bcast(beta, in_batched[3], axis_size)),
                (True, True, True))
        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        y = jax.vmap(_spmv_pdot_xla, in_axes=axes,
                     axis_size=axis_size)(A, p, z, beta)
        return y, (True, True, True)

    return call


@functools.lru_cache(maxsize=None)
def _spmv_ddot_fn(self_dot: bool):
    tu = jax.tree_util
    ob = (True,) * (3 if self_dot else 2)

    @jax.custom_batching.custom_vmap
    def call(A, p, d):
        from .pallas_spmv import dia_spmv_dot
        return dia_spmv_dot(A, p, d=d, self_dot=self_dot)

    @call.def_vmap
    def _rule(axis_size, in_batched, A, p, d):
        mat_b = any(tu.tree_leaves(in_batched[0]))
        if not mat_b:
            from .batched import spmv_dot_multi
            return (spmv_dot_multi(
                A, _bcast(p, in_batched[1], axis_size),
                D=_bcast(d, in_batched[2], axis_size),
                self_dot=self_dot), ob)
        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        fn = lambda A_, p_, d_: _spmv_ddot_xla(A_, p_, d_, self_dot)  # noqa: E731
        y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(A, p, d)
        return y, ob

    return call


def _shell_kernel_ok(A, dtype) -> bool:
    from .pallas_spmv import dia_spmv_dot_supported
    return (isinstance(A, CsrMatrix) and not A.is_block
            and getattr(A, "dia_vals", None) is not None
            and dia_spmv_dot_supported(A, dtype))


def spmv_pdot(A, p, z, beta):
    """Fused direction-update + SpMV + dot: p' = z + beta p,
    Ap' = A @ p', and the LOCAL p'.Ap' scalar — one HBM pass over p/z
    plus the values stream when the Pallas shell kernel applies, the
    exact unfused XLA compose otherwise (f64, CPU, non-DIA layouts,
    distributed operators)."""
    from ..telemetry import metrics as _tm
    if _shell_kernel_ok(A, p.dtype):
        _tm.inc("krylov.fused_dispatch")
        return _spmv_pdot_fn()(A, p, z, beta)
    _tm.inc("krylov.fused_declined")
    return _spmv_pdot_xla(A, p, z, beta)


def spmv_ddot(A, p, d, self_dot: bool = False):
    """Fused SpMV + dot against a streamed operand: Ap = A @ p with
    the LOCAL d.Ap scalar (and Ap.Ap when `self_dot` — BiCGStab's
    t.s / t.t pair) from the kernel epilogue; the exact unfused XLA
    compose otherwise."""
    from ..telemetry import metrics as _tm
    if _shell_kernel_ok(A, p.dtype):
        _tm.inc("krylov.fused_dispatch")
        return _spmv_ddot_fn(self_dot)(A, p, d)
    _tm.inc("krylov.fused_declined")
    return _spmv_ddot_xla(A, p, d, self_dot)


def multiply(A: CsrMatrix, x: jax.Array, view: str = "OWNED") -> jax.Array:
    """`multiply` entry point (src/multiply.cu:74). For local matrices the
    view argument is inert; the distributed overlap path lives in
    distributed/dist_spmv.py and is selected by the DistMatrix type."""
    return spmv(A, x)


def axmb(A: CsrMatrix, x: jax.Array, b: jax.Array) -> jax.Array:
    """r = A@x - b (reference blas axmb, include/blas.h)."""
    return spmv(A, x) - b


def residual(A: CsrMatrix, x: jax.Array, b: jax.Array) -> jax.Array:
    """r = b - A@x (the sign convention used by the solve loops)."""
    return b - spmv(A, x)
