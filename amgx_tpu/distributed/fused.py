"""Halo-folded fused smoother path for sharded (distributed) DIA levels.

Everything PRs 4-5 fused — all smoother sweeps + the trailing cycle
residual in ONE Pallas kernel per level — was single-chip only: a
distributed level smooths through `ShardMatrix.spmv`, paying one full
halo exchange AND one HBM pass over A per sweep. This module brings the
fused kernels under `shard_map` (ROADMAP item 1; the AmgX distributed
SpMV latency-hiding pattern of src/multiply.cu:95-110 generalized to
the whole fused sweep chain; JAXMg, arXiv:2601.14466 shows the same
structure in JAX).

The key observation: a contiguous equal-block row partition of a DIA
(banded) operator preserves the band per shard — shard r's rows
[r*nl, r*nl + nl) only reference global elements in
[r*nl - m, r*nl + nl + M) (m/M = the band reach below/above the
diagonal). And the quota-padded operand slabs the single-chip fused
kernel already DMAs row windows from (`ops/pallas_spmv.smooth_quota_rows`)
reserve exactly (SMOOTH_MAX_APPS-1)*mr0 front rows of ZERO padding for
the temporal-blocking halo. The per-shard slabs built here FILL that
quota with the neighbor shards' rows instead — the "halo-folded" slab —
so every remote coefficient a temporally-blocked sweep chain can reach
is already inside the kernel's row-window DMA.

Per fused smoother call (k sweeps + optional residual = n_app
applications) each shard then runs:

1. ONE packed edge-window exchange: the x window (n_app*m / n_app*M
   elements) and b window ((n_app-1)*m / (n_app-1)*M) ride a single
   `lax.ppermute` per direction — versus one full halo exchange per
   sweep in the unfused composition, and hop-free (only +/-1 neighbors
   hold a banded shard's halo).
2. The UNMODIFIED single-chip fused kernel on the shard's local
   operands with zero pads. Every row further than n_app*m (n_app*M)
   elements from the shard's lower (upper) boundary is exact, and the
   call has NO data dependence on the collective — XLA's latency-hiding
   scheduler runs the exchange concurrently with the interior kernel
   (the interior/boundary overlap, now covering the whole sweep chain
   instead of one SpMV).
3. Exact boundary strips recomputed in XLA once the exchange lands:
   `ops.batched.affine_window_sweeps` (the kernel's temporal blocking
   in element units) over the received windows + the folded slab's halo
   rows, spliced over the kernel's boundary rows. Strip cost is
   O(n_app * band) elements per side — negligible against nl.

Off the Pallas runtime (f64 solves; the CPU bench mesh) the same
exchange feeds `affine_window_sweeps` over the WHOLE shard — still one
collective per fused call and dense shifted adds instead of per-sweep
gather/segment-sum SpMVs, so `dist_cycle_fusion` pays on every backend.
`dist_cycle_fusion=0` builds no payloads and restores the per-sweep
halo-exchange composition bit-for-bit.

Payloads attach wherever a level's global DIA operator is visible at
setup: every sharded DIA level of the controller-global path
(distributed/amg.py `shard_amg`) and the finest level of the per-shard
setup (distributed/setup.py — coarse sharded levels are COO-built with
no DIA view, they keep the unfused path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import comms
from ..ops import batched as _bt
from ..ops import pallas_spmv as _ps


@jax.tree_util.register_pytree_node_class
class DistFusedSlabs:
    """Per-shard halo-folded fused-smoother payload of one distributed
    DIA level (leaves stacked (n_ranks, ...) outside shard_map; inside
    the shard_mapped solve the leading mesh axis is stripped with the
    rest of the solve-data pytree).

    Children: `vals_q` ((R,) k, Q, 128) quota-padded value slabs with
    the quota rows carrying the NEIGHBOR shards' rows (zero only where
    the global matrix ends); `dinv_q` ((R,) Q, 128) likewise, or None
    for smoothers without a diagonal scaling (CHEBYSHEV_POLY). Static
    aux: the DIA `offsets`, the per-shard row count `n_local`, and
    `n_ranks`."""

    def __init__(self, vals_q, dinv_q, offsets, n_local, n_ranks):
        self.vals_q = vals_q
        self.dinv_q = dinv_q
        self.offsets = offsets
        self.n_local = n_local
        self.n_ranks = n_ranks

    def tree_flatten(self):
        return ((self.vals_q, self.dinv_q),
                (self.offsets, self.n_local, self.n_ranks))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


def band_reach(offsets):
    """(m, M): band reach in elements below/above the diagonal."""
    return max(0, -min(offsets)), max(0, max(offsets))


def build_dist_fused(A, n_ranks: int, n_local: int, dinv=None):
    """Stacked halo-folded quota slabs from the GLOBAL DIA operator of
    a contiguous equal-block row partition (shard r owns rows
    [r*n_local, (r+1)*n_local); the partition_matrix / sharded-setup
    level-0 layout). Host numpy build, one device upload per (re)setup.
    Returns None when A has no eligible DIA layout or the shards are
    too narrow for even a single fused application's halo."""
    from ..ops import smooth as fsm
    if not fsm._slab_eligible(A):
        return None
    offsets = A.dia_offsets
    k = len(offsets)
    m, M = band_reach(offsets)
    # narrowest useful schedule: 1 sweep + residual (n_app = 2)
    if n_local < 2 * (m + M) or n_local < 1:
        return None
    qf, qc, qb = _ps.smooth_quota_rows(offsets, n_local)
    L = _ps.LANES
    span = (qf + qc + qb) * L
    gv = np.asarray(A.dia_vals).reshape(k, -1)
    idx = (np.arange(n_ranks)[:, None] * n_local - qf * L
           + np.arange(span)[None, :])
    valid = (idx >= 0) & (idx < gv.shape[1])
    idxc = np.clip(idx, 0, gv.shape[1] - 1)
    # (k, R, span) -> (R, k, rows, 128); elements past the matrix end
    # stay zero (dia_vals tile padding is already zero past num_rows)
    vq = np.where(valid[None], gv[:, idxc], 0).transpose(1, 0, 2)
    vals_q = jnp.asarray(
        np.ascontiguousarray(vq.reshape(n_ranks, k, qf + qc + qb, L)))
    dinv_q = None
    if dinv is not None:
        d = np.asarray(dinv).reshape(-1)
        gd = np.zeros(n_ranks * n_local, d.dtype)
        gd[: d.shape[0]] = d
        validd = (idx >= 0) & (idx < gd.shape[0])
        dq = np.where(validd, gd[np.clip(idx, 0, gd.shape[0] - 1)], 0)
        dinv_q = jnp.asarray(
            np.ascontiguousarray(dq.reshape(n_ranks, qf + qc + qb, L)))
    return DistFusedSlabs(vals_q, dinv_q, tuple(int(o) for o in offsets),
                          int(n_local), int(n_ranks))


def fusion_gates(cfg, scope: str, smoother) -> bool:
    """The cheap (no-array-touching) gates of `attach_shard_fused`:
    the `dist_cycle_fusion` knob, the fused runtime (non-TPU rigs
    build no payloads unless knob=2 opts into the XLA window route),
    and the smoother family. Callers with an EXPENSIVE operand to
    materialize (e.g. a device->host dinv pull) check this first so a
    declined attach costs nothing."""
    from ..ops import smooth as fsm
    knob = int(cfg.get("dist_cycle_fusion", scope))
    if knob == 0:
        return False
    # knob=2: attach even off the fused Pallas runtime — the solve then
    # takes the pure-XLA window-sweep route (one collective per fused
    # call instead of one per sweep; the CPU bench-mesh opt-in)
    if knob < 2 and not fsm.fused_runtime_on():
        return False
    if smoother is None or not getattr(smoother, "fused_smoother", False):
        return False
    if getattr(smoother, "_fused_taus", None) is None:
        return False          # not a damped-relaxation-family smoother
    return True


def attach_shard_fused(smd: dict, A, smoother, n_ranks: int,
                       n_local: int, cfg, scope: str,
                       dinv_global=None, dinv_key=None) -> bool:
    """Attach the halo-folded payload to a sharded level's smoother
    solve-data dict (key "dist_fused"), or do nothing. Gated on
    `fusion_gates` (knob / runtime / smoother family — non-TPU rigs
    build no payloads and change nothing, same contract as
    fused_smoother). Memoized on the identity of the
    value-carrying arrays, so a value resetup that swaps in new
    coefficients rebuilds the halo-extended slabs while repeated
    setups on the same values reuse them. A caller whose dinv is
    EXPENSIVE to materialize (the setup.py device->host slice) passes
    a zero-arg callable as `dinv_global` plus the stable source array
    as `dinv_key`: the callable runs only on a memo MISS, so a memo
    hit costs no transfer at all."""
    if not fusion_gates(cfg, scope, smoother):
        return False
    if dinv_global is None:
        dinv_global = getattr(smoother, "_dinv", None)
    if dinv_key is None:
        dinv_key = dinv_global
    memo = getattr(smoother, "_dist_fused_memo", None)
    # the memo RETAINS the source arrays and compares by `is` (see
    # ops/smooth.solver_fused_slabs for why id() alone is unsafe)
    if memo is not None and memo[0] is A.dia_vals \
            and memo[1] is dinv_key \
            and memo[2] == (n_ranks, n_local):
        fd = memo[3]
    else:
        if callable(dinv_global):
            dinv_global = dinv_global()
        if dinv_global is not None \
                and np.asarray(dinv_global).ndim != 1:
            return False      # block diagonal: not a scalar DIA level
        fd = build_dist_fused(A, n_ranks, n_local, dinv=dinv_global)
        smoother._dist_fused_memo = (A.dia_vals, dinv_key,
                                     (n_ranks, n_local), fd)
    if fd is None:
        return False
    smd["dist_fused"] = fd
    return True


# ---------------------------------------------------------------------------
# solve-phase entry (runs inside the shard_mapped trace)
# ---------------------------------------------------------------------------


def _exchange_windows(x, b, fx, bx, fb, bb, axis, n_ranks):
    """One packed ppermute per direction: my tail (x[-fx:], b[-fb:]) to
    the next rank (its front halo), my head (x[:bx], b[:bb]) to the
    previous rank (its back halo). Edge ranks receive zeros — the DIA
    zero-padding semantics at the global matrix boundary. The received
    buffers pass through the resilience link-fault hook, matching
    ShardMatrix.exchange_halo."""
    from ..resilience import faultinject as _fault
    nl = x.shape[0]
    fwd, bwd = comms.edge_permutes(n_ranks)
    # trace-time site report: the packed (x window + b window) buffer
    # each direction's single ppermute ships per fused call — the
    # exact bytes the halo-folded path pays instead of one full halo
    # per sweep. Both-windows-empty emits NO collective below, so it
    # reports no site either (a counted site must mean real traffic)
    if fx + fb > 0 or bx + bb > 0:
        comms.record_exchange(
            f"edge/{nl}", "edge_fused", fx + fb, bx + bb,
            jnp.dtype(x.dtype).itemsize, n_ranks)
    hx_f = hb_f = hx_b = hb_b = None
    if fx + fb > 0:
        send_f = jnp.concatenate([x[nl - fx:], b[nl - fb:]]) \
            if fb else x[nl - fx:]
        got_f = _fault.corrupt_halo(jax.lax.ppermute(send_f, axis, fwd))
        hx_f, hb_f = got_f[:fx], got_f[fx:]
    if bx + bb > 0:
        send_b = jnp.concatenate([x[:bx], b[:bb]]) if bb else x[:bx]
        got_b = _fault.corrupt_halo(jax.lax.ppermute(send_b, axis, bwd))
        hx_b, hb_b = got_b[:bx], got_b[bx:]
    return hx_f, hb_f, hx_b, hb_b


def dist_fused_smooth(fd: DistFusedSlabs, b, x, taus, dinv,
                      with_residual: bool):
    """x' (and r when `with_residual`) after len(taus) damped sweeps of
    this shard's rows, or None when the fused distributed path does not
    apply (caller falls back to the per-sweep halo-exchange compose).

    Routes: f32 with a feasible kernel plan -> the single-chip fused
    Pallas kernel on zero-padded local operands (overlapped with the
    edge-window exchange) + exact XLA boundary strips; otherwise (f64,
    no plan) -> `affine_window_sweeps` over the whole halo-extended
    shard — one exchange either way."""
    axis = comms.active_axis()
    if axis is None or fd is None:
        return None
    if (dinv is None) != (fd.dinv_q is None):
        return None
    n_steps = int(taus.shape[0])
    if n_steps < 1:
        return None
    offsets = fd.offsets
    k = len(offsets)
    nl = fd.n_local
    if x.shape[0] != nl or b.shape[0] != nl:
        return None
    m, M = band_reach(offsets)
    n_app = n_steps + (1 if with_residual else 0)
    if n_app > _ps.SMOOTH_MAX_APPS or n_app * (m + M) > nl:
        return None           # shard too narrow for the halo cone
    if fd.vals_q.dtype != x.dtype:
        return None
    from ..ops import smooth as fsm
    # bf16 shards ride the same kernel (per-block upcast, f32
    # accumulation) AND halve the packed edge-window exchange bytes —
    # the comms site below models the narrower itemsize automatically
    use_kernel = (
        jnp.dtype(x.dtype).name in _ps.SMOOTH_DTYPES
        and fsm.fused_runtime_on()
        and _ps.dia_smooth_plan(
            offsets, k, nl, n_steps, with_residual,
            itemsize=jnp.dtype(x.dtype).itemsize) is not None)

    # 1. edge-window exchange (the only collective of the fused call)
    fx, bx = n_app * m, n_app * M
    fb, bb = (n_app - 1) * m, (n_app - 1) * M
    hx_f, hb_f, hx_b, hb_b = _exchange_windows(
        x, b, fx, bx, fb, bb, axis, fd.n_ranks)

    qf, _, _ = _ps.smooth_quota_rows(offsets, nl)
    base = qf * _ps.LANES     # flat slab index of local element 0
    vflat = fd.vals_q.reshape(k, -1)
    dflat = fd.dinv_q.reshape(-1) if fd.dinv_q is not None else None

    def win(flat, lo, ln):
        return jax.lax.slice_in_dim(flat, base + lo, base + lo + ln,
                                    1, flat.ndim - 1)

    if not use_kernel:
        # XLA route: the whole shard is one window sweep over the
        # halo-extended arrays (exact; same math as the kernel)
        Wv = nl + (n_app - 1) * (m + M)
        vals_w = win(vflat, -(n_app - 1) * m, Wv)
        dinv_w = win(dflat, -(n_app - 1) * m, Wv) \
            if dflat is not None else None
        b_w = _cat(hb_f, b, hb_b)
        x_w = _cat(hx_f, x, hx_b)
        return _bt.affine_window_sweeps(offsets, vals_w, b_w, x_w, taus,
                                        dinv_w, nl, with_residual)

    # 2. Pallas route: the fused kernel on zero-padded local operands —
    # no data dependence on the exchange, so the collective overlaps
    out = _ps._dia_smooth_call(fd.vals_q, fd.dinv_q, taus, b, x,
                               offsets, nl, with_residual,
                               interpret=_ps._FORCE_INTERPRET)
    xk, rk = out if with_residual else (out, None)

    # 3. exact boundary strips from the received windows + the folded
    # slab halo rows (rows within n_app*m / n_app*M elements of a
    # shard boundary are the only ones whose cone left the shard)
    def splice(y, r, strip, at):
        ys = jax.lax.dynamic_update_slice(y, strip[0] if r is not None
                                          else strip, (at,))
        if r is None:
            return ys, None
        return ys, jax.lax.dynamic_update_slice(r, strip[1], (at,))

    if fx:                    # front strip: target [0, n_app*m)
        W = fx
        Wv = W + (n_app - 1) * (m + M)
        strip = _bt.affine_window_sweeps(
            offsets, win(vflat, -(n_app - 1) * m, Wv),
            _cat(hb_f, b[: W + (n_app - 1) * M], None),
            _cat(hx_f, x[: W + n_app * M], None),
            taus,
            win(dflat, -(n_app - 1) * m, Wv) if dflat is not None
            else None,
            W, with_residual)
        xk, rk = splice(xk, rk, strip, 0)
    if bx:                    # back strip: target [nl - n_app*M, nl)
        W = bx
        t0 = nl - W
        Wv = W + (n_app - 1) * (m + M)
        strip = _bt.affine_window_sweeps(
            offsets, win(vflat, t0 - (n_app - 1) * m, Wv),
            _cat(None, b[t0 - (n_app - 1) * m:], hb_b),
            _cat(None, x[t0 - n_app * m:], hx_b),
            taus,
            win(dflat, t0 - (n_app - 1) * m, Wv) if dflat is not None
            else None,
            W, with_residual)
        xk, rk = splice(xk, rk, strip, t0)
    return (xk, rk) if with_residual else xk


def _cat(front, mid, back):
    parts = [p for p in (front, mid, back) if p is not None
             and p.shape[0]]
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]
