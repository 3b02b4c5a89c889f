"""Multi-RHS sparse matrix products: Y = A @ X for X of shape (B, n).

The batched-solve subsystem (amgx_tpu/batch/) drives the existing
solver/cycle code under `jax.vmap`; most ops batch through their standard
batching rules, but the SpMV layouts have better shapes available when
only the *vector* carries the batch axis and the matrix is shared:

- DIA: each stored diagonal multiplies a shifted (B, n) slab — the whole
  batch is one dense multiply-add per diagonal (the batch axis rides the
  sublane dimension for free; no per-system re-streaming of the values);
- ELL: one (n, k) gather of X produces (B, n, k); the reduction is an
  einsum the MXU handles as a batched matvec;
- CSR/SWELL: fall back to `jax.vmap` of the single-vector form.

These are also the implementations the Pallas kernels' `custom_vmap`
rules route to when the matrix operand is unbatched, so a vmapped solve
over many RHS against one matrix never pays a per-system values stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..matrix import CsrMatrix


def _cdt(dtype):
    """Accumulation dtype of the slab forms: sub-f32 (bf16) slabs
    upcast and accumulate in f32, mirroring the fused Pallas kernels
    (identity for f32/f64 — the casts fold away)."""
    from .pallas_spmv import compute_dtype
    return compute_dtype(dtype)


def spmv_dia_multi(A: CsrMatrix, X: jax.Array) -> jax.Array:
    """Y = A @ X for DIA-layout A and X of shape (B, n): one shifted
    dense multiply-add per stored diagonal, batch axis untouched."""
    n = A.num_rows
    offs = A.dia_offsets
    vals = A.dia_vals.reshape(len(offs), -1)[:, :n]
    left = max(0, -min(offs))
    right = max(0, n - A.num_cols + max(offs))
    Xp = jnp.pad(X, ((0, 0), (left, right)))
    Y = jnp.zeros((X.shape[0], n), X.dtype)
    for i, d in enumerate(offs):
        Y = Y + vals[i][None, :] * jax.lax.dynamic_slice_in_dim(
            Xp, left + d, n, axis=1)
    return Y


def spmv_ell_multi(A: CsrMatrix, X: jax.Array) -> jax.Array:
    """Y = A @ X for padded-ELL A and X of shape (B, n)."""
    Y = jnp.einsum("nk,bnk->bn", A.ell_vals, X[:, A.ell_cols])
    if A.has_external_diag:
        Y = Y + A.diag[None, :] * X[:, : A.num_rows]
    return Y


def spmv_multi(A: CsrMatrix, X: jax.Array) -> jax.Array:
    """Y = A @ X with X of shape (B, num_cols): the multi-RHS form of
    ops.spmv.spmv, dispatching on the layout chosen at init. Scalar
    matrices only (block batching goes through jax.vmap)."""
    from .spmv import spmv
    if X.ndim != 2:
        raise ValueError(f"spmv_multi: X must be (batch, n), got {X.shape}")
    if isinstance(A, CsrMatrix) and not A.is_block:
        if A.dia_offsets is not None and not A.has_external_diag:
            return spmv_dia_multi(A, X)
        if A.ell_cols is not None and A.swell_cols is None:
            return spmv_ell_multi(A, X)
    return jax.vmap(lambda x: spmv(A, x))(X)


def residual_multi(A: CsrMatrix, X: jax.Array, B: jax.Array) -> jax.Array:
    """R = B - A @ X, row per system."""
    return B - spmv_multi(A, X)


def smooth_dia_multi(A: CsrMatrix, B: jax.Array, X: jax.Array, taus,
                     dinv=None, with_residual: bool = True):
    """Multi-RHS form of the fused smoother (+ residual epilogue):
    X' = X after len(taus) damped sweeps

        X <- X + tau_s * dinv . (B - A X)

    and, when `with_residual`, R = B - A X'. Each sweep's SpMV is one
    shifted dense multiply-add per stored diagonal over the whole (B, n)
    slab — this is the route the fused Pallas kernels' custom_vmap rules
    take when only the vectors carry the batch axis (solve_many's
    shared-matrix shape), so a vmapped cycle's presmooth+residual pair
    streams A's values once per slab pass instead of once per system.
    The update order matches the Pallas kernel: (tau * residual) * dinv.
    bf16 slabs accumulate in f32 like the kernels (only the values
    stream stays narrow; outputs round back to the input dtype)."""
    dt = X.dtype
    cdt = _cdt(dt)
    X = X.astype(cdt)
    B = B.astype(cdt)
    for t in range(taus.shape[0]):
        upd = taus[t].astype(cdt) * (B - spmv_dia_multi(A, X))
        if dinv is not None:
            upd = upd * dinv[None, :].astype(cdt)
        X = X + upd
    if with_residual:
        return X.astype(dt), (B - spmv_dia_multi(A, X)).astype(dt)
    return X.astype(dt)


def affine_window_sweeps(offsets, vals_w, b_w, x_w, taus, dinv_w,
                         W: int, with_residual: bool):
    """Damped-relaxation sweeps on a contiguous 1-D element window —
    the XLA mirror, in ELEMENT units, of the fused Pallas kernel's
    temporal blocking (ops/pallas_spmv.py `_dia_smooth_kernel`).

    Computes x' (and r when `with_residual`) EXACTLY for the W target
    elements [t0, t0 + W) of a DIA operator, given windows wide enough
    for the full dependence cone (m = max(0, -min(offsets)),
    M = max(0, max(offsets)), n_app = len(taus) + residual):

      x_w    (Wx,)   covering [t0 - n_app*m,       t0 + W + n_app*M)
      vals_w (k, Wv), b_w / dinv_w (Wv,)
                     covering [t0 - (n_app-1)*m,   t0 + W + (n_app-1)*M)

    Out-of-range window elements must be ZERO-filled (the DIA
    zero-padding semantics — a matrix edge and a zero-filled window
    edge are indistinguishable). Each sweep recomputes the Wv interior
    and zero-fills the shrinking cone edges, exactly like the kernel,
    so the W target elements come out bit-exact in exact arithmetic.

    This is the distributed fused path's workhorse (boundary-strip
    completion next to the per-shard kernel, and the whole-shard f64 /
    non-Pallas route — distributed/fused.py) and the parity reference
    the kernel tests compare against. bf16 windows upcast and the
    sweeps accumulate in f32, exactly like the kernel's per-block
    upcast — so the spliced boundary strips and the kernel interior
    share one arithmetic."""
    n_steps = int(taus.shape[0])
    n_app = n_steps + (1 if with_residual else 0)
    m = max(0, -min(offsets))
    M = max(0, max(offsets))
    Wv = W + (n_app - 1) * (m + M)
    out_dt = x_w.dtype
    dt = _cdt(out_dt)
    x_w = x_w.astype(dt)
    b_w = b_w.astype(dt)
    dinv_w = None if dinv_w is None else dinv_w.astype(dt)

    def apply_a(s):
        acc = jnp.zeros((Wv,), dt)
        for i, d in enumerate(offsets):
            acc = acc + vals_w[i].astype(dt) * jax.lax.slice_in_dim(
                s, m + d, m + d + Wv, 1, 0)
        return acc

    s = x_w
    for t in range(n_steps):
        corr = taus[t].astype(dt) * (b_w - apply_a(s))
        if dinv_w is not None:
            corr = corr * dinv_w
        mid = jax.lax.slice_in_dim(s, m, m + Wv, 1, 0) + corr
        pieces = [mid]
        if m:
            pieces.insert(0, jnp.zeros((m,), dt))
        if M:
            pieces.append(jnp.zeros((M,), dt))
        s = jnp.concatenate(pieces) if len(pieces) > 1 else mid
    y = jax.lax.slice_in_dim(s, n_app * m, n_app * m + W,
                             1, 0).astype(out_dt)
    if not with_residual:
        return y
    r = b_w - apply_a(s)
    return y, jax.lax.slice_in_dim(r, (n_app - 1) * m,
                                   (n_app - 1) * m + W, 1, 0
                                   ).astype(out_dt)


# ---------------------------------------------------------------------------
# Krylov shell slab forms (the custom_vmap fallbacks of the fused
# SpMV+dot / cg_update kernels in ops/pallas_spmv.py — and the f64
# parity reference; solve_many's vector-only batches land here)
# ---------------------------------------------------------------------------


def spmv_dot_multi(A: CsrMatrix, P: jax.Array, Z=None, beta=None,
                   D=None, self_dot: bool = False):
    """Multi-RHS form of the fused SpMV + dot shell kernel
    (`_dia_spmv_dot_call`): optional direction-update prologue
    P' = Z + beta*P (beta per-system), AP = A @ P', the paired dot
    sum(d . AP) per system (d = D when a separate dot operand is
    streamed, else P'), and optionally AP . AP (BiCGStab's t.t).
    Returns the kernel call's tuple layout with a leading batch axis:
    (AP, pdot[, sdot]) or, with the prologue, (P', AP, pdot[, sdot]).
    bf16 slabs accumulate the prologue and the dots in f32 like the
    kernel; for f32/f64 the casts fold away, making this the f64
    parity reference."""
    dt = P.dtype
    cdt = _cdt(dt)
    if Z is not None:
        P = (Z.astype(cdt)
             + beta[..., None].astype(cdt) * P.astype(cdt)).astype(dt)
    AP = spmv_dia_multi(A, P)
    dvec = (P if D is None else D).astype(cdt)
    pdot = jnp.sum(dvec * AP.astype(cdt), axis=1)
    out = (AP, pdot) if Z is None else (P, AP, pdot)
    if self_dot:
        out = out + (jnp.sum(AP.astype(cdt) ** 2, axis=1),)
    return out


def cg_update_multi(X: jax.Array, P: jax.Array, R: jax.Array,
                    AP: jax.Array, alpha):
    """Multi-RHS form of the single-pass CG update kernel
    (`_cg_update_call`): X' = X + alpha P, R' = R - alpha AP, and the
    per-system r'.r' dot (alpha per-system). The dot reduces the
    UNROUNDED accumulation-dtype R' exactly like the kernel's f32
    epilogue; outputs round back to the input dtype."""
    dt = X.dtype
    cdt = _cdt(dt)
    a = alpha[..., None].astype(cdt)
    Xn = X.astype(cdt) + a * P.astype(cdt)
    Rn = R.astype(cdt) - a * AP.astype(cdt)
    rr = jnp.sum(Rn * Rn, axis=1)
    return Xn.astype(dt), Rn.astype(dt), rr
