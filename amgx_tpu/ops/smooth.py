"""Fused smoother+residual dispatch for the V-cycle hot path.

The multigrid solve phase spends its time in presmooth -> residual ->
restrict and prolongate -> postsmooth; on a memory-bound TPU each
smoother sweep and the residual is a separate HBM pass over A. This
module routes the damped-relaxation smoother family

    x_{s+1} = x_s + tau_s * dinv . (b - A x_s)        (dinv optional)

(BLOCK_JACOBI / JACOBI_L1: tau_s = relaxation_factor, dinv = D^{-1};
CHEBYSHEV_POLY: tau_s = the magic-damping taus, no dinv) through the
fused Pallas kernels:

- DIA: all sweeps AND the trailing residual in ONE pallas_call
  (ops/pallas_spmv.py: one block with its halo window, or row blocks
  in order with each sweep's edge rows carried in VMEM) — A's diagonal
  slab, x and b stream from HBM once instead of sweeps+1 times, at
  any size.
- SWELL: each sweep is one pallas_call with the Jacobi update in the
  kernel epilogue (ops/pallas_swell.py) — the lane-gather layout cannot
  temporally block (window reach is unbounded), but fusing the update
  removes the separate elementwise pass and its 4 HBM streams; the
  final residual stays a plain SpMV pass.

Every entry point returns None when no fused plan applies, and the
calling smoother falls back to its unfused compose — so `fused_smoother=0`
(or any unsupported layout/dtype/backend) reproduces the pre-fusion
computation exactly. All Pallas routes are wrapped in `custom_vmap`
like `spmv_dia`: under `jax.vmap` (the batched-solve subsystem) the
multi-RHS slab forms in ops/batched.py run instead, so `solve_many`
gets the same fused-epilogue semantics without a per-system values
stream.

The DIA kernel needs its values/dinv operands with front-halo padding
the tile-aligned dia_vals store does not carry; `solver_fused_slabs`
builds those quota-padded slabs ONCE per (re)setup and the smoother
carries them in its solve_data pytree (so a value-only resetup refreshes
them and no per-cycle re-layout of A ever happens).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_spmv as _ps


def fused_runtime_on() -> bool:
    """Would the fused Pallas kernels run here (compiled on a TPU, or
    under the interpreter-forcing test hook)?"""
    return _ps.pallas_backend() is not None


# ---------------------------------------------------------------------------
# setup-time payloads (carried in smoother solve_data)
# ---------------------------------------------------------------------------


def _slab_eligible(A) -> bool:
    return (getattr(A, "dia_vals", None) is not None
            and not A.is_block and not A.has_external_diag
            and A.num_rows == A.num_cols)


def build_fused_slabs(A, dinv=None, dtype=None):
    """Quota-padded DIA operand slabs {vals_q[, dinv_q]} for the fused
    smoother kernel (eager device ops; see smooth_quota_rows for the
    layout). `dtype` emits the slabs in the hierarchy's EFFECTIVE
    precision (precision.py policy — e.g. bf16 slabs at half the HBM
    bytes) instead of A's native dtype, so the solve-data cast later
    finds them already narrow and never materializes a second copy.
    Returns None when A has no eligible DIA layout."""
    if not _slab_eligible(A):
        return None
    qf, qc, qb = _ps.smooth_quota_rows(A.dia_offsets, A.num_rows)
    k, rows_pad, _ = A.dia_vals.shape
    src = A.dia_vals[:, :qc] if rows_pad >= qc else jnp.pad(
        A.dia_vals, ((0, 0), (0, qc - rows_pad), (0, 0)))
    if dtype is not None:
        src = src.astype(dtype)
    out = {"vals_q": jnp.pad(src, ((0, 0), (qf, qb), (0, 0)))}
    if dinv is not None:
        dt = dinv.dtype if dtype is None else dtype
        d = jnp.zeros((qc * _ps.LANES,), dt)
        d = jax.lax.dynamic_update_slice(d, dinv.astype(dt), (0,))
        out["dinv_q"] = jnp.pad(d.reshape(qc, _ps.LANES),
                                ((qf, qb), (0, 0)))
    return out


def solver_fused_slabs(solver, A, dinv=None):
    """Memoized per-solver fused-operand slabs, or None. Built only
    when the fused kernels can actually run (TPU backend, or the
    interpret-forcing test hook) so CPU rigs pay nothing. The memo key
    is the identity of the value-carrying arrays, so a resetup (full or
    value-only splice) that swaps in new coefficients rebuilds the
    slabs and the solve-data contract (fresh leaves after a value
    change) holds. `solver._slab_dtype` (set by the hierarchy from the
    precision policy when the smoother attaches to a level) emits the
    slabs directly in the effective precision."""
    if not fused_runtime_on() or not _slab_eligible(A):
        return None
    dtype = getattr(solver, "_slab_dtype", None)
    memo = getattr(solver, "_fused_slab_memo", None)
    # the memo RETAINS the source arrays and compares by `is`: a key of
    # bare id()s could alias a freed-then-reallocated array address and
    # silently serve slabs built from the previous coefficients
    if memo is not None and memo[0] is A.dia_vals and memo[1] is dinv \
            and memo[2] == dtype:
        return memo[3]
    slabs = build_fused_slabs(A, dinv, dtype=dtype)
    solver._fused_slab_memo = (A.dia_vals, dinv, dtype, slabs)
    return slabs


def _fused_dtype_ok(A, x_dtype) -> bool:
    """Dtype gate that COUNTS its declines: a level carrying a fused
    payload whose effective dtype is off the kernel whitelist is the
    exact silent reroute that used to drop `amg_precision=bfloat16`
    configs back to the unfused composition with no trace. Returns
    True when the dtype is fine; False — after counting
    `fusion.declined_dtype` (trace-time host work only) — when the
    caller must fall back. SolveReport's kernel-activity table
    surfaces the same routing per level."""
    if _ps.smooth_dtype_ok(A, x_dtype):
        return True
    from ..telemetry import metrics as _tm
    _tm.inc("fusion.declined_dtype")
    return False


# ---------------------------------------------------------------------------
# custom_vmap-wrapped fused calls (DIA)
# ---------------------------------------------------------------------------


def _out_batched(with_residual):
    return (True, True) if with_residual else True


def _xla_single(A, taus, b, x, dinv, with_residual):
    """XLA single-vector form (vmap fallback): the slab form with a
    unit batch, so the DIA shift arithmetic lives in one place."""
    from .batched import smooth_dia_multi
    out = smooth_dia_multi(A, b[None], x[None], taus, dinv,
                           with_residual)
    if with_residual:
        return out[0][0], out[1][0]
    return out[0]


@functools.lru_cache(maxsize=None)
def _fused_dia_fn(with_residual: bool, has_dinv: bool):
    """custom_vmap-wrapped fused DIA call. Batched matrices / taus /
    dinv take the vmapped XLA form; a batch that only carries the
    vectors (multi-RHS against one matrix — the batch subsystem's
    shared-pattern shape) takes the multi-RHS slab form so the values
    stream once per slab pass."""
    tu = jax.tree_util

    if has_dinv:
        @jax.custom_batching.custom_vmap
        def call(A, vals_q, dinv_q, dinv, taus, b, x):
            return _ps._dia_smooth_call(vals_q, dinv_q, taus, b, x,
                                        A.dia_offsets, A.num_rows,
                                        with_residual,
                                        interpret=_ps._FORCE_INTERPRET)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, vals_q, dinv_q, dinv, taus,
                  b, x):
            mat_b = any(tu.tree_leaves(in_batched[:5]))
            b_b, x_b = in_batched[5], in_batched[6]
            if not mat_b:
                from .batched import smooth_dia_multi
                B = b if b_b else jnp.broadcast_to(
                    b, (axis_size,) + b.shape)
                X = x if x_b else jnp.broadcast_to(
                    x, (axis_size,) + x.shape)
                return (smooth_dia_multi(A, B, X, taus, dinv,
                                         with_residual),
                        _out_batched(with_residual))
            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            fn = lambda A_, vq_, dq_, dv_, t_, b_, x_: _xla_single(  # noqa: E731
                A_, t_, b_, x_, dv_, with_residual)
            y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
                A, vals_q, dinv_q, dinv, taus, b, x)
            return y, _out_batched(with_residual)
    else:
        @jax.custom_batching.custom_vmap
        def call(A, vals_q, taus, b, x):
            return _ps._dia_smooth_call(vals_q, None, taus, b, x,
                                        A.dia_offsets, A.num_rows,
                                        with_residual,
                                        interpret=_ps._FORCE_INTERPRET)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, vals_q, taus, b, x):
            mat_b = any(tu.tree_leaves(in_batched[:3]))
            b_b, x_b = in_batched[3], in_batched[4]
            if not mat_b:
                from .batched import smooth_dia_multi
                B = b if b_b else jnp.broadcast_to(
                    b, (axis_size,) + b.shape)
                X = x if x_b else jnp.broadcast_to(
                    x, (axis_size,) + x.shape)
                return (smooth_dia_multi(A, B, X, taus, None,
                                         with_residual),
                        _out_batched(with_residual))
            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            fn = lambda A_, vq_, t_, b_, x_: _xla_single(  # noqa: E731
                A_, t_, b_, x_, None, with_residual)
            y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
                A, vals_q, taus, b, x)
            return y, _out_batched(with_residual)

    return call


def _dia_call(A, fused, taus, b, x, dinv, with_residual):
    if dinv is not None:
        return _fused_dia_fn(with_residual, True)(
            A, fused["vals_q"], fused["dinv_q"], dinv, taus, b, x)
    return _fused_dia_fn(with_residual, False)(
        A, fused["vals_q"], taus, b, x)


def dia_fused_smooth(A, fused, b, x, taus, dinv=None,
                     with_residual=True):
    """Fused DIA smoother dispatch: x' (and r when `with_residual`)
    after len(taus) damped sweeps in ONE pallas_call, or None when no
    fused plan applies (no slab, an off-whitelist dtype, a schedule
    longer than SMOOTH_MAX_APPS, a body VMEM has no room for): the
    caller falls back to its unfused compose."""
    if fused is None or getattr(A, "dia_vals", None) is None:
        return None
    if dinv is not None and "dinv_q" not in fused:
        return None
    n_steps = int(taus.shape[0])
    if n_steps < 1:
        return None
    if not _fused_dtype_ok(A, x.dtype):
        return None
    if not _ps.dia_smooth_supported(A, x.dtype, n_steps, with_residual):
        return None
    return _dia_call(A, fused, taus, b, x, dinv, with_residual)


# ---------------------------------------------------------------------------
# SWELL fused sweep (partial fusion: update in the kernel epilogue)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _fused_swell_fn(has_dinv: bool):
    tu = jax.tree_util

    def _xla_step(A, b, x, tau, dinv):
        from .pallas_swell import swell_spmv_xla
        upd = tau * (b - swell_spmv_xla(A, x))
        if dinv is not None:
            upd = upd * dinv
        # round back to the vector dtype: bf16 states with f32 taus
        # would otherwise drift the state dtype across sweeps
        return (x + upd).astype(x.dtype)

    if has_dinv:
        @jax.custom_batching.custom_vmap
        def call(A, b, x, tau, dinv):
            from .pallas_swell import swell_smooth_step
            return swell_smooth_step(A, b, x, tau, dinv)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, b, x, tau, dinv):
            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            y = jax.vmap(lambda A_, b_, x_, t_, d_: _xla_step(
                A_, b_, x_, t_, d_), in_axes=axes,
                axis_size=axis_size)(A, b, x, tau, dinv)
            return y, True
    else:
        @jax.custom_batching.custom_vmap
        def call(A, b, x, tau):
            from .pallas_swell import swell_smooth_step
            return swell_smooth_step(A, b, x, tau, None)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, b, x, tau):
            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            y = jax.vmap(lambda A_, b_, x_, t_: _xla_step(
                A_, b_, x_, t_, None), in_axes=axes,
                axis_size=axis_size)(A, b, x, tau)
            return y, True

    return call


def swell_fused_smooth(A, b, x, taus, dinv=None, with_residual=True):
    """Fused-epilogue SWELL smoother: each sweep is one kernel pass
    computing x' directly (no separate elementwise pass); the trailing
    residual — which needs A applied to the fully-updated x' — stays a
    plain SpMV pass. None when the SWELL fused path does not apply."""
    from .pallas_swell import swell_smooth_supported
    if not swell_smooth_supported(A, x.dtype):
        return None
    n_steps = int(taus.shape[0])
    if n_steps < 1:
        return None
    for t in range(n_steps):
        if dinv is not None:
            x = _fused_swell_fn(True)(A, b, x, taus[t], dinv)
        else:
            x = _fused_swell_fn(False)(A, b, x, taus[t])
    if not with_residual:
        return x
    from .spmv import spmv
    return x, b - spmv(A, x)


# ---------------------------------------------------------------------------
# solver-facing entry
# ---------------------------------------------------------------------------


def fused_smooth(data, b, x, taus, dinv=None, with_residual=True):
    """Try every fused route for the smoother data pytree: DIA first
    (full fusion), then SWELL (epilogue fusion). Returns x' (, r) or
    None — callers keep their unfused compose as the fallback, so a
    missing layout/backend/dtype changes nothing.

    Distributed (ShardMatrix) levels route through the halo-folded
    per-shard form when the setup attached a "dist_fused" payload
    (distributed/fused.py): one edge-window exchange + one fused kernel
    per shard instead of a full halo exchange per sweep."""
    A = data["A"]
    from ..matrix import CsrMatrix
    # taus carry at the ACCUMULATION dtype (f32 for bf16 operands):
    # a bf16-rounded damping schedule would waste precision the f32
    # in-kernel arithmetic keeps; identity for f32/f64 vectors
    taus = jnp.asarray(taus, _ps.compute_dtype(x.dtype))
    if not isinstance(A, CsrMatrix) or A.is_block:
        fd = data.get("dist_fused")
        if fd is not None:
            from ..distributed.fused import dist_fused_smooth
            return dist_fused_smooth(fd, b, x, taus, dinv,
                                     with_residual)
        return None
    out = dia_fused_smooth(A, data.get("fused"), b, x, taus, dinv,
                           with_residual)
    if out is not None:
        return out
    return swell_fused_smooth(A, b, x, taus, dinv, with_residual)
