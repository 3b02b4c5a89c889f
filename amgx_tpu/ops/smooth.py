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


flat_gather_ok = _ps.flat_gather_ok


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


# ---------------------------------------------------------------------------
# cycle fusion: grid-transfer epilogues + VMEM-resident coarse tail
# ---------------------------------------------------------------------------


def _coarse_window_tables(crmin, crmax, n: int, ncr: int, offsets):
    """Per-candidate-block-size coarse window sizes + base tables from
    per-128-lane-row coarse-ROW min/max reach arrays (sentinel `big`
    min / -1 max for rows referencing nothing). Shared by the
    aggregation and the general-CSR slab builders so the window math
    the plans budget against can never fork."""
    import numpy as np
    L = _ps.LANES
    rows128 = max(1, -(-n // L))
    big = np.int64(1) << 60
    mr0, Mr0 = _ps.smooth_halo_rows(offsets)
    K1 = _ps.SMOOTH_MAX_APPS * mr0
    K2 = _ps.SMOOTH_MAX_APPS * Mr0

    def _block_minmax(lo_off, hi_off, br, nb):
        mn = np.full(nb, big)
        mx = np.full(nb, np.int64(-1))
        for i in range(nb):
            lo = max(0, i * br + lo_off)
            hi = min(rows128, i * br + br + hi_off)
            if hi > lo:
                mn[i] = crmin[lo:hi].min()
                mx[i] = crmax[lo:hi].max()
        return mn, mx

    windows = []
    bases = {}
    for br in _ps.smooth_br_candidates(n):
        nb = -(-rows128 // br)
        if nb > 4096:
            continue        # base-table build cost guard (tiny brs at
            # huge n are never picked by the plans anyway)
        mn, mx = _block_minmax(0, 0, br, nb)
        mn = np.where(mx < 0, 0, np.minimum(mn, ncr - 1))
        mx = np.maximum(mx, mn)
        cw = int(min(ncr, -(-int((mx - mn).max() + 1) // 8) * 8))
        cb = np.clip(mn, 0, ncr - cw).astype(np.int32)
        mn2, mx2 = _block_minmax(-K1, K2, br, nb)
        mn2 = np.where(mx2 < 0, 0, np.minimum(mn2, ncr - 1))
        mx2 = np.maximum(mx2, mn2)
        pcw = int(min(ncr, -(-int((mx2 - mn2).max() + 1) // 8) * 8))
        pcb = np.clip(mn2, 0, ncr - pcw).astype(np.int32)
        windows.append((br, cw, pcw))
        bases[br] = (jnp.asarray(cb), jnp.asarray(pcb))
    return tuple(windows), bases


def build_transfer_slabs(A, agg, nc: int):
    """Structure-only transfer payloads for the fused grid-transfer
    kernels (host numpy build, one device upload per (re)setup):
    child-index slab ctab[j][c] = fine slot of aggregate c's j-th
    child (-1 absent), aggregate-id slab atab[slot] = coarse id (-1 at
    padding), and the per-candidate-block-size coarse window bases the
    kernels DMA coarse rows through. Returns None when A has no
    eligible DIA layout or an aggregate exceeds TRANSFER_MAX_CHILD."""
    import numpy as np
    if not _slab_eligible(A) or A.dia_offsets is None:
        return None
    offsets = A.dia_offsets
    n = A.num_rows
    agg = np.asarray(agg).ravel().astype(np.int64)
    if agg.shape[0] != n or nc < 1:
        return None
    counts = np.bincount(agg, minlength=nc)
    m = int(counts.max()) if n else 0
    if m < 1 or m > _ps.TRANSFER_MAX_CHILD:
        return None
    ncr = _ps.coarse_pad_rows(nc)
    L = _ps.LANES
    order = np.argsort(agg, kind="stable")
    starts = np.zeros(nc + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    pos = np.arange(n, dtype=np.int64) - starts[agg[order]]
    ctab = np.full((m, ncr * L), -1, np.int32)
    ctab[pos, agg[order]] = order.astype(np.int32)
    ctab = ctab.reshape(m, ncr, L)
    aqf, aqc, aqb = _ps.transfer_quota_rows(offsets, n)
    atab = np.full(((aqf + aqc + aqb) * L,), -1, np.int32)
    atab[aqf * L: aqf * L + n] = agg
    atab = atab.reshape(-1, L)
    # per-fine-row coarse row min/max -> per-block window bases for
    # every block size the plans could pick
    rows128 = max(1, -(-n // L))
    aggp = np.full((rows128 * L,), -1, np.int64)
    aggp[:n] = agg
    a2 = aggp.reshape(rows128, L)
    big = np.int64(1) << 60
    crmin = np.where(a2 >= 0, a2 // L, big).min(axis=1)
    crmax = np.where(a2 >= 0, a2 // L, -1).max(axis=1)
    windows, bases = _coarse_window_tables(crmin, crmax, n, ncr,
                                           offsets)
    if not windows:
        return None
    return _ps.TransferSlabs(jnp.asarray(ctab), jnp.asarray(atab),
                             bases, int(nc), ncr, m, windows)


def build_csr_transfer_slabs(A, P, R, dtype=None):
    """WEIGHTED row-segment transfer payloads for the fused
    grid-transfer kernels over general CSR interpolation (classical
    Ruge-Stuben levels; host numpy build, one device upload). `dtype`
    emits the weight slabs (cwt/pwt) in the hierarchy's effective
    precision (precision.py) — the index tables stay int32 either way.
    The aggregation slabs generalize entrywise:

    - restriction (R = P^T, nc x n): ctab[j][c] = fine slot of R row
      c's j-th entry (-1 absent), cwt[j][c] = its weight — the kernel
      epilogue computes bc[c] = sum_j cwt[j][c] * r[ctab[j][c]];
    - prolongation (P, n x nc): ptab[j][slot] / pwt[j][slot] = the
      j-th (coarse id, weight) entry of P's row at that fine slot,
      quota-padded like atab — the prologue folds
      x += sum_j pwt[j] * xc[ptab[j]] into the postsmoother's first
      application.

    Classical structure reuse keeps P/R (values included) across
    value resetups, so these slabs are structure-lifetime payloads
    exactly like the aggregation child tables. Returns None when A
    has no eligible DIA layout, P/R shapes disagree with A, or a row
    exceeds the child caps (CSR_TRANSFER_MAX_CHILD restriction /
    TRANSFER_MAX_CHILD prolongation)."""
    import numpy as np
    if not _slab_eligible(A) or A.dia_offsets is None:
        return None
    if P is None or R is None or getattr(P, "is_block", True):
        return None
    offsets = A.dia_offsets
    n = A.num_rows
    nc = int(P.num_cols)
    if int(P.num_rows) != n or nc < 1 or int(R.num_rows) != nc \
            or int(R.num_cols) != n:
        return None
    pro = np.asarray(P.row_offsets).astype(np.int64)
    pci = np.asarray(P.col_indices).astype(np.int64)
    pv = np.asarray(P.values)
    rro = np.asarray(R.row_offsets).astype(np.int64)
    rci = np.asarray(R.col_indices).astype(np.int64)
    rv = np.asarray(R.values)
    rlen = np.diff(rro)
    plen = np.diff(pro)
    m = int(rlen.max()) if nc else 0
    mp = int(plen.max()) if n else 0
    if m < 1 or m > _ps.CSR_TRANSFER_MAX_CHILD \
            or mp < 1 or mp > _ps.TRANSFER_MAX_CHILD:
        return None
    ncr = _ps.coarse_pad_rows(nc)
    L = _ps.LANES
    # restriction row segments, entry j of R row c
    jpos = np.arange(rci.shape[0], dtype=np.int64) \
        - np.repeat(rro[:-1], rlen)
    crow = np.repeat(np.arange(nc, dtype=np.int64), rlen)
    ctab = np.full((m, ncr * L), -1, np.int32)
    cwt = np.zeros((m, ncr * L), rv.dtype)
    ctab[jpos, crow] = rci.astype(np.int32)
    cwt[jpos, crow] = rv
    ctab = ctab.reshape(m, ncr, L)
    cwt = cwt.reshape(m, ncr, L)
    # prolongation row segments, entry j of P row i, quota-padded
    aqf, aqc, aqb = _ps.transfer_quota_rows(offsets, n)
    rows_q = aqf + aqc + aqb
    jp = np.arange(pci.shape[0], dtype=np.int64) \
        - np.repeat(pro[:-1], plen)
    prow = np.repeat(np.arange(n, dtype=np.int64), plen)
    ptab = np.full((mp, rows_q * L), -1, np.int32)
    pwt = np.zeros((mp, rows_q * L), pv.dtype)
    ptab[jp, aqf * L + prow] = pci.astype(np.int32)
    pwt[jp, aqf * L + prow] = pv
    ptab = ptab.reshape(mp, rows_q, L)
    pwt = pwt.reshape(mp, rows_q, L)
    # per-fine-slot coarse reach (min/max coarse id P's row touches)
    # -> per-128-row coarse-ROW reach -> per-block window bases
    big = np.int64(1) << 60
    minc = np.full(n, big, np.int64)
    maxc = np.full(n, np.int64(-1), np.int64)
    np.minimum.at(minc, prow, pci)
    np.maximum.at(maxc, prow, pci)
    rows128 = max(1, -(-n // L))
    minp = np.full((rows128 * L,), big, np.int64)
    maxp = np.full((rows128 * L,), np.int64(-1), np.int64)
    minp[:n] = minc
    maxp[:n] = maxc
    mn2 = minp.reshape(rows128, L)
    mx2 = maxp.reshape(rows128, L)
    crmin = np.where(mx2 >= 0, mn2 // L, big).min(axis=1)
    crmax = np.where(mx2 >= 0, mx2 // L, -1).max(axis=1)
    windows, bases = _coarse_window_tables(crmin, crmax, n, ncr,
                                           offsets)
    if not windows:
        return None
    wavg = max(1, -(-int(rlen.sum()) // max(nc, 1)))
    pavg = max(1, -(-int(plen.sum()) // max(n, 1)))
    if dtype is not None:
        # numpy-side cast (ml_dtypes covers bfloat16): the weight
        # slabs upload already-narrow, no full-precision twin
        cwt = cwt.astype(jnp.dtype(dtype))
        pwt = pwt.astype(jnp.dtype(dtype))
    return _ps.TransferSlabs(
        jnp.asarray(ctab), None, bases, int(nc), ncr, m, windows,
        cwt=jnp.asarray(cwt), ptab=jnp.asarray(ptab),
        pwt=jnp.asarray(pwt), mp=mp, wavg=wavg, pavg=pavg)


def _xla_restrict_single(A, taus, b, x, dinv, xfer):
    from .batched import smooth_restrict_dia_multi
    X, BC = smooth_restrict_dia_multi(A, b[None], x[None], taus, dinv,
                                      xfer)
    return X[0], BC[0]


def _xla_corr_single(A, taus, b, x, xc, dinv, xfer):
    from .batched import corr_smooth_dia_multi
    return corr_smooth_dia_multi(A, b[None], x[None], xc[None], taus,
                                 dinv, xfer)[0]


@functools.lru_cache(maxsize=None)
def _fused_restrict_fn(has_dinv: bool):
    """custom_vmap-wrapped fused presmooth+restrict call: vector-only
    batches (solve_many) take the multi-RHS slab form in ops/batched.py;
    batched matrices take the vmapped XLA compose."""
    tu = jax.tree_util

    if has_dinv:
        @jax.custom_batching.custom_vmap
        def call(A, xfer, vals_q, dinv_q, dinv, taus, b, x):
            return _ps._dia_smooth_restrict_call(
                vals_q, dinv_q, taus, b, x, xfer, A.dia_offsets,
                A.num_rows, interpret=_ps._FORCE_INTERPRET)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, xfer, vals_q, dinv_q, dinv,
                  taus, b, x):
            mat_b = any(tu.tree_leaves(in_batched[:6]))
            b_b, x_b = in_batched[6], in_batched[7]
            if not mat_b:
                from .batched import smooth_restrict_dia_multi
                B = b if b_b else jnp.broadcast_to(
                    b, (axis_size,) + b.shape)
                X = x if x_b else jnp.broadcast_to(
                    x, (axis_size,) + x.shape)
                return (smooth_restrict_dia_multi(A, B, X, taus, dinv,
                                                  xfer), (True, True))
            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            fn = lambda A_, xf_, vq_, dq_, dv_, t_, b_, x_: \
                _xla_restrict_single(A_, t_, b_, x_, dv_, xf_)  # noqa: E731
            y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
                A, xfer, vals_q, dinv_q, dinv, taus, b, x)
            return y, (True, True)
    else:
        @jax.custom_batching.custom_vmap
        def call(A, xfer, vals_q, taus, b, x):
            return _ps._dia_smooth_restrict_call(
                vals_q, None, taus, b, x, xfer, A.dia_offsets,
                A.num_rows, interpret=_ps._FORCE_INTERPRET)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, xfer, vals_q, taus, b, x):
            mat_b = any(tu.tree_leaves(in_batched[:4]))
            b_b, x_b = in_batched[4], in_batched[5]
            if not mat_b:
                from .batched import smooth_restrict_dia_multi
                B = b if b_b else jnp.broadcast_to(
                    b, (axis_size,) + b.shape)
                X = x if x_b else jnp.broadcast_to(
                    x, (axis_size,) + x.shape)
                return (smooth_restrict_dia_multi(A, B, X, taus, None,
                                                  xfer), (True, True))
            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            fn = lambda A_, xf_, vq_, t_, b_, x_: \
                _xla_restrict_single(A_, t_, b_, x_, None, xf_)  # noqa: E731
            y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
                A, xfer, vals_q, taus, b, x)
            return y, (True, True)

    return call


def _xb_dot(y, b):
    """The x'.b dot epilogue's XLA twin (the cycle-borne r.z of the
    Krylov shell): accumulation-dtype reduction over the last axis, so
    the batched/vmapped routes agree with the kernel's f32 partials."""
    cdt = _ps.compute_dtype(y.dtype)
    return jnp.sum(y.astype(cdt) * b.astype(cdt), axis=-1)


@functools.lru_cache(maxsize=None)
def _fused_corr_fn(has_dinv: bool, with_dot: bool = False):
    """custom_vmap-wrapped prolongation-prologue+postsmooth call.
    `with_dot` appends the x'.b dot epilogue (the Krylov shell's
    cycle-borne r.z reduction — b IS the preconditioner rhs r and x'
    IS z, so x'.b = r.z) and makes every route return (x', dot)."""
    tu = jax.tree_util
    ob = (True, True) if with_dot else True

    if has_dinv:
        @jax.custom_batching.custom_vmap
        def call(A, xfer, vals_q, dinv_q, dinv, taus, b, x, xc):
            return _ps._dia_prolong_smooth_call(
                vals_q, dinv_q, taus, b, x, xc, xfer, A.dia_offsets,
                A.num_rows, with_dot=with_dot,
                interpret=_ps._FORCE_INTERPRET)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, xfer, vals_q, dinv_q, dinv,
                  taus, b, x, xc):
            mat_b = any(tu.tree_leaves(in_batched[:6]))
            b_b, x_b, xc_b = in_batched[6], in_batched[7], in_batched[8]
            if not mat_b:
                from .batched import corr_smooth_dia_multi
                B = b if b_b else jnp.broadcast_to(
                    b, (axis_size,) + b.shape)
                X = x if x_b else jnp.broadcast_to(
                    x, (axis_size,) + x.shape)
                XC = xc if xc_b else jnp.broadcast_to(
                    xc, (axis_size,) + xc.shape)
                y = corr_smooth_dia_multi(A, B, X, XC, taus, dinv,
                                          xfer)
                return ((y, _xb_dot(y, B)) if with_dot else y), ob

            def fn(A_, xf_, vq_, dq_, dv_, t_, b_, x_, xc_):
                y_ = _xla_corr_single(A_, t_, b_, x_, xc_, dv_, xf_)
                return (y_, _xb_dot(y_, b_)) if with_dot else y_

            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
                A, xfer, vals_q, dinv_q, dinv, taus, b, x, xc)
            return y, ob
    else:
        @jax.custom_batching.custom_vmap
        def call(A, xfer, vals_q, taus, b, x, xc):
            return _ps._dia_prolong_smooth_call(
                vals_q, None, taus, b, x, xc, xfer, A.dia_offsets,
                A.num_rows, with_dot=with_dot,
                interpret=_ps._FORCE_INTERPRET)

        @call.def_vmap
        def _rule(axis_size, in_batched, A, xfer, vals_q, taus, b, x,
                  xc):
            mat_b = any(tu.tree_leaves(in_batched[:4]))
            b_b, x_b, xc_b = in_batched[4], in_batched[5], in_batched[6]
            if not mat_b:
                from .batched import corr_smooth_dia_multi
                B = b if b_b else jnp.broadcast_to(
                    b, (axis_size,) + b.shape)
                X = x if x_b else jnp.broadcast_to(
                    x, (axis_size,) + x.shape)
                XC = xc if xc_b else jnp.broadcast_to(
                    xc, (axis_size,) + xc.shape)
                y = corr_smooth_dia_multi(A, B, X, XC, taus, None,
                                          xfer)
                return ((y, _xb_dot(y, B)) if with_dot else y), ob

            def fn(A_, xf_, vq_, t_, b_, x_, xc_):
                y_ = _xla_corr_single(A_, t_, b_, x_, xc_, None, xf_)
                return (y_, _xb_dot(y_, b_)) if with_dot else y_

            axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                         for ib in in_batched)
            y = jax.vmap(fn, in_axes=axes, axis_size=axis_size)(
                A, xfer, vals_q, taus, b, x, xc)
            return y, ob

    return call


def _restrict_call(A, fused, xfer, taus, b, x, dinv):
    if dinv is not None:
        return _fused_restrict_fn(True)(
            A, xfer, fused["vals_q"], fused["dinv_q"], dinv, taus, b, x)
    return _fused_restrict_fn(False)(A, xfer, fused["vals_q"], taus,
                                     b, x)


def _corr_call(A, fused, xfer, taus, b, x, xc, dinv, with_dot=False):
    if dinv is not None:
        return _fused_corr_fn(True, with_dot)(
            A, xfer, fused["vals_q"], fused["dinv_q"], dinv, taus, b,
            x, xc)
    return _fused_corr_fn(False, with_dot)(A, xfer, fused["vals_q"],
                                           taus, b, x, xc)


def _transfer_ready(data, xfer, dinv):
    A = data["A"]
    from ..matrix import CsrMatrix
    if not isinstance(A, CsrMatrix) or A.is_block:
        return None
    fused = data.get("fused")
    if xfer is None or fused is None \
            or getattr(A, "dia_vals", None) is None:
        return None
    if dinv is not None and "dinv_q" not in fused:
        return None
    return A, fused


def fused_smooth_restrict(data, b, x, taus, xfer, dinv=None):
    """Fused presmooth + restriction: (x', bc) after len(taus) damped
    sweeps with bc = R (b - A x') emitted by the kernel epilogue, or
    None when no fused plan applies (caller composes smooth_residual +
    level.restrict). Oversized schedules chain plain fused sweep
    chunks, with the restriction riding the final chunk's epilogue."""
    ready = _transfer_ready(data, xfer, dinv)
    if ready is None:
        return None
    A, fused = ready
    taus = jnp.asarray(taus, _ps.compute_dtype(x.dtype))
    n_steps = int(taus.shape[0])
    if n_steps < 1:
        return None
    if not _fused_dtype_ok(A, x.dtype):
        return None
    sup_r = functools.partial(_ps.dia_restrict_supported, A, x.dtype,
                              xfer=xfer)
    if sup_r(n_steps):
        return _restrict_call(A, fused, xfer, taus, b, x, dinv)
    tail = next((c for c in range(
        min(n_steps - 1, _ps.SMOOTH_MAX_APPS - 1), 0, -1)
        if sup_r(c)), 0)
    if not tail or not _ps.dia_smooth_supported(
            A, x.dtype, n_steps - tail, False):
        return None
    head = dia_fused_smooth(A, fused, b, x, taus[:n_steps - tail],
                            dinv=dinv, with_residual=False)
    if head is None:
        return None
    return _restrict_call(A, fused, xfer, taus[n_steps - tail:], b,
                          head, dinv)


def fused_corr_smooth(data, b, x, xc, taus, xfer, dinv=None,
                      want_dot=False):
    """Fused prolongation/correction + postsmooth: x' after len(taus)
    damped sweeps starting from x + P xc (the correction folded into
    the first kernel's prologue), or None when no fused plan applies.
    Oversized schedules run the prologue chunk first, then chain plain
    fused sweep chunks. `want_dot` asks for the cycle-borne x'.b dot
    (PCG's r.z) from the LAST kernel's epilogue: the single-call route
    returns (x', dot); the chunked route returns (x', None) — the dot
    would have to ride a mid-chain kernel, so the caller reduces it
    with one standalone pass instead."""
    ready = _transfer_ready(data, xfer, dinv)
    if ready is None:
        return None
    A, fused = ready
    taus = jnp.asarray(taus, _ps.compute_dtype(x.dtype))
    n_steps = int(taus.shape[0])
    if n_steps < 1:
        return None
    if not _fused_dtype_ok(A, x.dtype):
        return None
    sup_p = functools.partial(_ps.dia_prolong_supported, A, x.dtype,
                              xfer=xfer)
    if sup_p(n_steps):
        return _corr_call(A, fused, xfer, taus, b, x, xc, dinv,
                          with_dot=want_dot)
    head = next((c for c in range(
        min(n_steps - 1, _ps.SMOOTH_MAX_APPS), 0, -1) if sup_p(c)), 0)
    if not head or not _ps.dia_smooth_supported(
            A, x.dtype, n_steps - head, False):
        return None
    x = _corr_call(A, fused, xfer, taus[:head], b, x, xc, dinv)
    x = dia_fused_smooth(A, fused, b, x, taus[head:], dinv=dinv,
                         with_residual=False)
    return (x, None) if want_dot else x


# ---------------------------------------------------------------------------
# VMEM-resident coarse-tail dispatch
# ---------------------------------------------------------------------------


def _tail_single_xla(arrs, b, x, spec):
    from .batched import tail_cycle_multi
    return tail_cycle_multi(arrs, b[None], x[None], spec)[0]


@functools.lru_cache(maxsize=None)
def _tail_fn(spec, with_dot: bool = False):
    """custom_vmap-wrapped coarse-tail call for one static TailSpec:
    vector-only batches (solve_many's shared-hierarchy shape) take the
    slab form in ops/batched.py; batched hierarchies (multi-matrix
    solves) take the vmapped XLA compose. `with_dot` appends the x'.b
    dot epilogue (cycle-borne r.z) on every route."""
    tu = jax.tree_util
    ob = (True, True) if with_dot else True

    @jax.custom_batching.custom_vmap
    def call(arrs, b, x):
        return _ps._dia_coarse_tail_call(arrs, b, x, spec,
                                         with_dot=with_dot,
                                         interpret=_ps._FORCE_INTERPRET)

    @call.def_vmap
    def _rule(axis_size, in_batched, arrs, b, x):
        mat_b = any(tu.tree_leaves(in_batched[0]))
        b_b, x_b = in_batched[1], in_batched[2]
        if not mat_b:
            from .batched import tail_cycle_multi
            B = b if b_b else jnp.broadcast_to(b, (axis_size,) + b.shape)
            X = x if x_b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            y = tail_cycle_multi(arrs, B, X, spec)
            return ((y, _xb_dot(y, B)) if with_dot else y), ob

        def one(a_, b_, x_):
            y_ = _tail_single_xla(a_, b_, x_, spec)
            return (y_, _xb_dot(y_, b_)) if with_dot else y_

        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        y = jax.vmap(one, in_axes=axes, axis_size=axis_size)(arrs, b, x)
        return y, ob

    return call


def _tail_taus(taus, dtype):
    """(padded taus array, static application count): zero-sweep levels
    carry a 1-entry dummy the kernel never reads (0-sized VMEM operands
    are not portable)."""
    n = int(taus.shape[0])
    if n == 0:
        return jnp.zeros((1,), dtype), 0
    return taus.astype(dtype), n


def coarse_tail_cycle(amg, shape: str, data, lvl: int, b, x,
                      want_dot=False):
    """Run the whole sub-cycle at levels >= lvl as ONE pallas_call with
    every intermediate vector VMEM-resident, or None when the tail is
    ineligible (caller recurses per level). Eligible when: fixed cycle
    shape, f32, every tail level is an aggregation/DIA level with
    transfer+fused slabs and a fused-capable smoother, the coarse
    solver is NOSOLVER or exposes its dense inverse, the entry level is
    under cycle_fusion_tail_rows, and everything fits the VMEM budget
    together. `want_dot` (Krylov shell) makes the megakernel also emit
    the x'.b dot — the whole-cycle-resident case's cycle-borne r.z —
    and the return becomes (x', dot)."""
    if shape not in ("V", "W", "F") or not _ps.flat_gather_ok():
        return None
    if jnp.dtype(x.dtype).name not in _ps.SMOOTH_DTYPES:
        return None
    levels = amg.levels
    nlv = len(levels)
    if lvl >= nlv:
        return None
    if levels[lvl].A.num_rows > int(
            getattr(amg, "cycle_fusion_tail_rows", 0)):
        return None
    specs = []
    arrs = []
    total = 0
    for i in range(lvl, nlv):
        lv = levels[i]
        ld = data["levels"][i]
        if "R" in ld or "P" in ld:
            return None
        xfer = ld.get("xfer")
        smd = ld.get("smoother")
        if xfer is None or smd is None:
            return None
        if xfer.ptab is not None:
            # weighted (classical) slabs: _tail_compute's gathers are
            # unit-weight — those levels keep per-level kernels
            return None
        fused = smd.get("fused")
        mfst = smd.get("stencil")
        A = ld["A"]
        if mfst is None and (fused is None
                             or not _ps.smooth_dtype_ok(A, x.dtype)):
            return None
        spec_fn = getattr(lv.smoother, "fused_tail_spec", None)
        if spec_fn is None:
            return None
        cdt = _ps.compute_dtype(x.dtype)
        pre = spec_fn(smd, amg._sweeps(i, pre=True), cdt)
        post = spec_fn(smd, amg._sweeps(i, pre=False), cdt)
        if pre is None or post is None:
            return None
        taus_pre, n_pre = _tail_taus(pre[0], cdt)
        taus_post, n_post = _tail_taus(post[0], cdt)
        dinv = pre[1]
        offsets = A.dia_offsets
        qf, qc, _ = _ps.smooth_quota_rows(offsets, A.num_rows)
        aqf = _ps.transfer_quota_rows(offsets, A.num_rows)[0]
        ar = {
            "taus_pre": taus_pre,
            "taus_post": taus_post,
            "ctab": xfer.ctab,
            "atab_c": jax.lax.slice_in_dim(xfer.atab, aqf, aqf + qc,
                                           1, 0),
        }
        if mfst is not None:
            # matrix-free level: k coefficients instead of the value
            # slab; dinv is synthesized in-kernel from the stencil
            ar["coeffs"] = mfst.coeffs.astype(cdt)
            specs.append(_ps.TailLevelSpec(
                offsets=tuple(int(o) for o in offsets), n=A.num_rows,
                qc=qc, has_dinv=False, n_pre=n_pre, n_post=n_post,
                nc=xfer.nc, ncr=xfer.ncr, m=xfer.m, mf=mfst.spec()))
            total += sum(v.size * v.dtype.itemsize
                         for v in jax.tree_util.tree_leaves(ar))
            arrs.append(ar)
            continue
        ar["vals"] = jax.lax.slice_in_dim(fused["vals_q"], qf, qf + qc,
                                          1, 1)
        if dinv is not None:
            if "dinv_q" not in fused:
                return None
            ar["dinv"] = jax.lax.slice_in_dim(fused["dinv_q"], qf,
                                              qf + qc, 1, 0)
        specs.append(_ps.TailLevelSpec(
            offsets=tuple(int(o) for o in offsets), n=A.num_rows,
            qc=qc, has_dinv=dinv is not None, n_pre=n_pre,
            n_post=n_post, nc=xfer.nc, ncr=xfer.ncr, m=xfer.m))
        total += sum(v.size * v.dtype.itemsize
                     for v in jax.tree_util.tree_leaves(ar))
        arrs.append(ar)
    cd = data["coarse"]
    cs = amg.coarse_solver
    nz = specs[-1].nc
    ncrz = _ps.coarse_pad_rows(nz)
    if getattr(cs, "name", "") in ("NOSOLVER", "DUMMY"):
        coarse = ("none", nz, ncrz)
    elif "inv" in cd and cd["inv"].shape == (nz, nz) \
            and cd["inv"].dtype == jnp.float32:
        F = ncrz * _ps.LANES
        invT = jnp.zeros((F, F), jnp.float32)
        invT = jax.lax.dynamic_update_slice(invT, cd["inv"].T, (0, 0))
        arrs.append({"invT": invT})
        total += F * F * 4
        coarse = ("inv", nz, ncrz)
    else:
        return None
    # all slabs + ~2x working vectors must co-reside in VMEM
    if 2 * total > _ps._SMOOTH_VMEM_BUDGET:
        return None
    spec = _ps.TailSpec(shape, tuple(specs), coarse)
    # telemetry: remember (at trace time, zero solve-phase cost) the
    # outermost level the VMEM tail megakernel absorbed — SolveReport's
    # per-level activity table reads it back (telemetry/report.py)
    prev = getattr(amg, "_tail_entry_level", None)
    amg._tail_entry_level = lvl if prev is None else min(prev, lvl)
    return _tail_fn(spec, want_dot)(tuple(arrs), b, x)
