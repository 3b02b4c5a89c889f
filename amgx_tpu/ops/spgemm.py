"""Sparse general matrix-matrix multiply and the Galerkin triple product.

TPU-native analog of CSR_Multiply / csr_galerkin_product
(include/csr_multiply.h:78-96, src/csr_multiply.cu,
src/csr_multiply_detail.cu). The reference uses GPU hash tables; hash
tables do not map onto the TPU vector units, so this implementation is the
sort-based expand/coalesce formulation:

  expand:   every (i,k,a) of A pairs with every (k,j,b) of row k of B,
            producing candidate triplets (i, j, a*b) — pure gathers with a
            repeat-by-row-length index expansion;
  coalesce: sort candidates by (i,j) and segment-sum duplicates.

This is a *setup-time* operation (Galerkin products happen once per
hierarchy build); it runs eagerly with concrete shapes so the output nnz
can be data-dependent, every step dispatching XLA sort/gather/segment
kernels on device.

PLAN SPLIT (device-SpGEMM strategies, arXiv:1606.00545; SParSH-AMG's
symbolic/numeric setup split, arXiv:2007.00056): the sparsity pattern of
a Galerkin product is identical across every warm setup and resetup of
the same problem, yet the eager formulation re-dispatches the whole
sort/gather/segment chain each time. `RapPlan` separates the two
phases: the STRUCTURE phase runs once per pattern (host numpy: the
(A·P) expansion gather indices, the lexsorted coalesce order, segment
boundaries, and the output CSR pattern, memoized in a digest-keyed
cache) and the VALUE phase recomputes all numerics from the current
coefficients through those static indices — a sort-free
gather/segment-sum XLA program on the device, or the native flat-FMA
sweep (a reduceat pass without the toolchain) on host numpy
hierarchies. `spgemm_plan=0`
short-circuits before any plan machinery runs, restoring the eager
composition bit-for-bit.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from ..matrix import CsrMatrix, lexsort_rc


def _fold_diag(A: CsrMatrix) -> CsrMatrix:
    """Fold an externally-stored diagonal (DIAG property) back into the
    CSR entries so the expand/coalesce formulation sees the full matrix."""
    if not A.has_external_diag:
        return A
    rows, cols, vals = A.coo()
    n = A.num_rows
    d_rows = jnp.arange(n, dtype=jnp.int32)
    return CsrMatrix.from_coo(
        jnp.concatenate([rows, d_rows]),
        jnp.concatenate([cols, d_rows]),
        jnp.concatenate([vals, A.diag]),
        n, A.num_cols, block_dims=(A.block_dimx, A.block_dimy))


def _expand(A: CsrMatrix, B: CsrMatrix):
    """Candidate COO triplets of A@B (indices only + source pointers)."""
    a_rows, a_cols, _ = A.coo()
    b_row_nnz = jnp.diff(B.row_offsets)
    counts = b_row_nnz[a_cols]                       # per-A-nnz expansion
    total = int(jnp.sum(counts))
    cum = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])
    src_a = jnp.repeat(jnp.arange(A.nnz, dtype=jnp.int32), counts,
                       total_repeat_length=total)
    offset_in_row = jnp.arange(total, dtype=jnp.int32) - \
        cum[src_a].astype(jnp.int32)
    src_b = B.row_offsets[a_cols[src_a]] + offset_in_row
    out_rows = a_rows[src_a]
    out_cols = B.col_indices[src_b]
    return out_rows, out_cols, src_a, src_b


def _on_host(A: CsrMatrix) -> bool:
    import numpy as np
    from ..matrix import device_setup_forced
    if device_setup_forced():
        return False             # setup_backend=device: jnp pipeline
    if isinstance(A.values, np.ndarray):
        return True
    try:
        return next(iter(A.values.devices())).platform == "cpu"
    except Exception:
        return False


def csr_multiply(A: CsrMatrix, B: CsrMatrix) -> CsrMatrix:
    """C = A @ B for scalar or block CSR (block: bxb @ bxb -> bxb).

    On the host backend the product runs through the native Gustavson
    sweep (native/src/spgemm.cpp — the csr_multiply.h analog): the
    sort-based jnp formulation below is shaped for accelerators, where
    it is the only option, but costs ~1 s per product at 32^3 scale on
    a single CPU thread."""
    assert A.num_cols == B.num_rows, (A.shape, B.shape)
    A, B = _fold_diag(A), _fold_diag(B)
    if not A.is_block and _on_host(A) and _on_host(B):
        from .. import native
        import numpy as np
        out = native.spgemm_native(
            A.num_rows, B.num_cols, np.asarray(A.row_offsets),
            np.asarray(A.col_indices), np.asarray(A.values),
            np.asarray(B.row_offsets), np.asarray(B.col_indices),
            np.asarray(B.values))
        if out is not None:
            cp, cc, cv = out
            return CsrMatrix.from_scipy_like(
                cp.astype(np.int32), cc,
                jnp.asarray(cv.astype(np.asarray(A.values).dtype)),
                A.num_rows, B.num_cols)
    out_rows, out_cols, src_a, src_b = _expand(A, B)
    if A.is_block:
        prods = jnp.einsum("nxk,nky->nxy", A.values[src_a], B.values[src_b])
    else:
        prods = A.values[src_a] * B.values[src_b]
    order = lexsort_rc(out_rows, out_cols)
    out_rows, out_cols, prods = (out_rows[order], out_cols[order],
                                 prods[order])
    if out_rows.shape[0] == 0:
        return CsrMatrix.from_scipy_like(
            jnp.zeros(A.num_rows + 1, jnp.int32), out_cols, prods,
            A.num_rows, B.num_cols, (A.block_dimx, B.block_dimy))
    newseg = jnp.concatenate(
        [jnp.ones((1,), bool),
         (out_rows[1:] != out_rows[:-1]) | (out_cols[1:] != out_cols[:-1])])
    seg = jnp.cumsum(newseg) - 1
    nuniq = int(seg[-1]) + 1
    first = jnp.nonzero(newseg, size=nuniq)[0]
    vals = jax.ops.segment_sum(prods, seg, num_segments=nuniq,
                               indices_are_sorted=True)
    rows_u, cols_u = out_rows[first], out_cols[first]
    counts = jnp.bincount(rows_u, length=A.num_rows)
    row_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    return CsrMatrix.from_scipy_like(
        row_offsets, cols_u, vals, A.num_rows, B.num_cols,
        (A.block_dimx, B.block_dimy))


def csr_add(A: CsrMatrix, B: CsrMatrix) -> CsrMatrix:
    """C = A + B by COO concatenation + coalesce (csr_RAP_sparse_add
    analog, include/csr_multiply.h)."""
    assert A.shape == B.shape
    ar, ac, av = _fold_diag(A).coo()
    br, bc, bv = _fold_diag(B).coo()
    rows = jnp.concatenate([ar, br])
    cols = jnp.concatenate([ac, bc])
    vals = jnp.concatenate([av, bv])
    return CsrMatrix.from_coo(rows, cols, vals, A.num_rows, A.num_cols,
                              block_dims=(A.block_dimx, A.block_dimy))


def galerkin_rap(R: CsrMatrix, A: CsrMatrix, P: CsrMatrix) -> CsrMatrix:
    """Coarse operator A_c = R @ A @ P (csr_galerkin_product analog,
    include/csr_multiply.h:96).

    Host path: ONE fused native sweep (native/src/rap.cpp) — the R*A
    intermediate never materializes or crosses the Python boundary, and
    the result stays numpy-backed so the rest of the host hierarchy
    build (amg_host_setup) never round-trips through XLA:CPU arrays."""
    import numpy as np
    if not (A.is_block or R.has_external_diag or A.has_external_diag
            or P.has_external_diag) and _on_host(A) and _on_host(R) \
            and _on_host(P) and np.asarray(A.values).dtype.kind == "f" \
            and np.asarray(P.values).dtype.kind == "f" \
            and np.asarray(R.values).dtype.kind == "f":
        from .. import native
        out = native.rap_native(
            R.num_rows, A.num_rows, P.num_cols,
            np.asarray(R.row_offsets), np.asarray(R.col_indices),
            np.asarray(R.values),
            np.asarray(A.row_offsets), np.asarray(A.col_indices),
            np.asarray(A.values),
            np.asarray(P.row_offsets), np.asarray(P.col_indices),
            np.asarray(P.values))
        if out is not None:
            cp, cc, cv = out
            return CsrMatrix(
                row_offsets=cp.astype(np.int32), col_indices=cc,
                values=cv.astype(np.asarray(A.values).dtype, copy=False),
                num_rows=R.num_rows, num_cols=P.num_cols)
    return csr_multiply(csr_multiply(R, A), P)


# ---------------------------------------------------------------------------
# plan-split RAP: the structure phase (RapPlan) + value-phase dispatch
# ---------------------------------------------------------------------------


def plan_enabled(cfg, scope) -> bool:
    """`spgemm_plan` knob gate: '0' restores the eager composition
    (no plan machinery runs at all); 'auto'/'1' take the plan split."""
    return str(cfg.get("spgemm_plan", scope)) != "0"


class RapPlan:
    """Static recipe for one Galerkin product's numerics.

    Built once per sparsity pattern from the operand STRUCTURES only
    (host numpy); the value phase then reads the current coefficients
    through precomputed gather indices and sorted-segment boundaries —
    no sort, argsort, unique, or data-dependent shape anywhere.

    Two forms share the class:

    - kind="agg" (piecewise-constant P): the product collapses to
      relabeling A's entries by aggregate id. `st` is the lexsorted
      candidate permutation into the (diag-folded) value vector and
      `seg2`/`starts2` the coalesce segments. `sr` is None (unit
      weights); the output mirrors `_compact_coarse` (structure-
      complete, initialized).
    - kind="rap" (general CSR R/A/P): stage 1 expands T = A·P
      (`sa`/`sp` candidate gathers + `seg1`), stage 2 expands
      C = R·T (`sr`/`st` + `seg2`); the output mirrors the eager
      `galerkin_rap` CSR (the caller init()s it).

    Index arrays live as host numpy (the numpy route reads them);
    `dev()` uploads device twins once per plan (the slab route),
    exactly like the GEO structure cache — a warm setup re-uploads
    nothing."""

    kind = "rap"

    def __init__(self, kind, stage1, sr, st, seg2, starts2, nU,
                 fold_diag, row_offsets, col_indices, row_ids,
                 diag_idx, num_rows, num_cols):
        self.kind = kind
        self.stage1 = stage1      # None | dict(sa, sp, seg1, starts1, nT)
        self.sr = sr
        self.st = st
        self.seg2 = seg2
        self.starts2 = starts2
        self.nU = int(nU)
        self.fold_diag = bool(fold_diag)
        self.row_offsets = row_offsets
        self.col_indices = col_indices
        self.row_ids = row_ids
        self.diag_idx = diag_idx
        self.num_rows = int(num_rows)
        self.num_cols = int(num_cols)
        self._dev = None

    def nbytes(self) -> int:
        total = 0
        for a in (self.sr, self.st, self.seg2, self.starts2,
                  self.row_offsets, self.col_indices, self.row_ids,
                  self.diag_idx):
            if a is not None:
                total += int(a.nbytes)
        if self.stage1 is not None:
            for k in ("sa", "sp", "seg1", "starts1"):
                total += int(self.stage1[k].nbytes)
        return total

    def dev(self):
        """Device twins of the gather/segment arrays (uploaded once)."""
        if self._dev is None:
            d = {"st": jnp.asarray(self.st),
                 "seg2": jnp.asarray(self.seg2)}
            if self.sr is not None:
                d["sr"] = jnp.asarray(self.sr)
            if self.stage1 is not None:
                d["sa"] = jnp.asarray(self.stage1["sa"])
                d["sp"] = jnp.asarray(self.stage1["sp"])
                d["seg1"] = jnp.asarray(self.stage1["seg1"])
            self._dev = d
        return self._dev

    def dev_structure(self):
        """Device twins of the output CSR structure (uploaded once)."""
        d = self.dev()
        if "row_offsets" not in d:
            d["row_offsets"] = jnp.asarray(self.row_offsets)
            d["col_indices"] = jnp.asarray(self.col_indices)
            d["row_ids"] = jnp.asarray(self.row_ids)
            d["diag_idx"] = jnp.asarray(self.diag_idx)
        return d


def _np_expand_pattern(a_ro, a_ci, b_ro, b_ci):
    """Candidate COO triplets of A@B from patterns (numpy mirror of
    `_expand`): (out_rows, out_cols, src_a, src_b), int64."""
    a_rows = np.repeat(np.arange(a_ro.shape[0] - 1, dtype=np.int64),
                       np.diff(a_ro))
    counts = np.diff(b_ro)[a_ci]
    total = int(counts.sum())
    src_a = np.repeat(np.arange(a_ci.shape[0], dtype=np.int64), counts)
    cum = np.concatenate([np.zeros(1, np.int64),
                          np.cumsum(counts, dtype=np.int64)])
    off = np.arange(total, dtype=np.int64) - cum[src_a]
    src_b = b_ro[a_ci[src_a]].astype(np.int64) + off
    return a_rows[src_a], b_ci[src_b].astype(np.int64), src_a, src_b


def _np_coalesce(rows, cols):
    """Lexsorted coalesce of candidate coordinates: (order, seg,
    starts, rows_u, cols_u). `order` is the stable (row, col) sort of
    the candidates, `seg` the segment id per sorted candidate, `starts`
    the (nU+1,) segment boundaries."""
    order = np.lexsort((cols, rows))
    r_s, c_s = rows[order], cols[order]
    if r_s.shape[0] == 0:
        return (order, np.zeros(0, np.int32), np.zeros(1, np.int64),
                r_s, c_s)
    first = np.concatenate(
        [np.ones(1, bool), (r_s[1:] != r_s[:-1]) | (c_s[1:] != c_s[:-1])])
    seg = (np.cumsum(first) - 1).astype(np.int32)
    # int32 boundaries: candidate totals are guarded < 2^31 by the
    # builders, and halving these arrays matters — a 128^3 classical
    # L0 plan is GB-scale
    starts = np.concatenate([np.flatnonzero(first).astype(np.int32),
                             np.asarray([r_s.shape[0]], np.int32)])
    return order, seg, starts, r_s[first], c_s[first]


def _np_csr_structure(rows_u, cols_u, num_rows):
    """Output CSR structure of the coalesced entries (sorted by
    (row, col)): row_offsets, col_indices, row_ids, diag_idx — the
    same fields the eager `_compact_coarse` emits."""
    counts = np.bincount(rows_u, minlength=num_rows)
    row_offsets = np.zeros(num_rows + 1, np.int32)
    row_offsets[1:] = np.cumsum(counts).astype(np.int32)
    diag_idx = np.full(num_rows, -1, np.int32)
    is_diag = cols_u == rows_u
    diag_idx[rows_u[is_diag].astype(np.int64)] = \
        np.flatnonzero(is_diag).astype(np.int32)
    return (row_offsets, cols_u.astype(np.int32),
            rows_u.astype(np.int32), diag_idx)


def _host_pattern(*arrays):
    """Host numpy views of pattern arrays regardless of backend
    forcing (the plan is a host-side artifact; `host_arrays` respects
    the device forcing, `np.asarray` is the fallback pull)."""
    return [None if a is None else np.asarray(a) for a in arrays]


def _plan_stage(a_ro, a_ci, b_ro, b_ci):
    """One stage of the general plan's structure phase: the candidates
    of A @ B from the two patterns, coalesced in the stable (row,
    column) order. Returns (sa, sb, seg, starts, rows_u, cols_u): int32
    arrays of each candidate's A and B entry and segment in coalesce
    order, the (nU + 1,) segment boundaries, and the output entries'
    coordinates; None where the candidates do not fit int32. The native
    sweep (native/src/rap_plan.cpp) sorts row by row; the numpy form
    expands five int64 arrays of candidate length and lexsorts them
    (most of a classical set-up's wall where coarse rows are long).
    Both give the same arrays to the last tie."""
    from .. import native
    limit = int(np.iinfo(np.int32).max)
    out = native.rap_plan_stage_native(a_ro, a_ci, b_ro, b_ci, limit)
    if out is False:
        return None
    if out is not None:
        sa, sb, seg, urow = out
        if seg.shape[0]:
            starts = np.flatnonzero(np.concatenate(
                [np.ones(1, bool), seg[1:] != seg[:-1],
                 np.ones(1, bool)])).astype(np.int32)
        else:
            starts = np.zeros(1, np.int32)
        rows_u = np.repeat(np.arange(urow.shape[0], dtype=np.int64), urow)
        cols_u = np.asarray(b_ci)[sb[starts[:-1]]].astype(np.int64)
        return sa, sb, seg, starts, rows_u, cols_u
    rows_c, cols_c, src_a, src_b = _np_expand_pattern(a_ro, a_ci, b_ro, b_ci)
    if rows_c.shape[0] >= limit:
        return None
    order, seg, starts, rows_u, cols_u = _np_coalesce(rows_c, cols_c)
    return (src_a[order].astype(np.int32), src_b[order].astype(np.int32),
            seg, starts.astype(np.int32), rows_u, cols_u)


def build_agg_plan(A: CsrMatrix, agg, nc: int):
    """Structure phase of the aggregation relabel Galerkin: candidates
    are A's (diag-folded) entries relabeled by aggregate id, in the
    lexsorted coalesce order. Returns None for block matrices."""
    if A.is_block:
        return None
    ro, ci, ri = _host_pattern(A.row_offsets, A.col_indices, A.row_ids)
    aggv = np.asarray(agg).ravel().astype(np.int64)
    if ri is not None and ri.shape[0] == ci.shape[0]:
        rows = ri.astype(np.int64)
    else:
        rows = np.repeat(np.arange(A.num_rows, dtype=np.int64),
                         np.diff(ro))
    cols = ci.astype(np.int64)
    r2 = aggv[rows]
    c2 = aggv[cols]
    fold = A.has_external_diag
    if fold:
        r2 = np.concatenate([r2, aggv])
        c2 = np.concatenate([c2, aggv])
    if r2.shape[0] >= np.iinfo(np.int32).max:
        return None
    order, seg, starts, rows_u, cols_u = _np_coalesce(r2, c2)
    structure = _np_csr_structure(rows_u, cols_u, int(nc))
    return RapPlan("agg", None, None, order.astype(np.int32), seg,
                   starts, rows_u.shape[0], fold, *structure,
                   num_rows=int(nc), num_cols=int(nc))


def build_rap_plan(R: CsrMatrix, A: CsrMatrix, P: CsrMatrix):
    """Structure phase of the general Galerkin triple product: stage 1
    expands/coalesces T = A·P, stage 2 expands/coalesces C = R·T.
    Returns None for block matrices or external diagonals on R/P (the
    eager path handles those; A's external diagonal folds in)."""
    if A.is_block or R.is_block or P.is_block or \
            R.has_external_diag or P.has_external_diag:
        return None
    a_ro, a_ci = _host_pattern(A.row_offsets, A.col_indices)
    p_ro, p_ci = _host_pattern(P.row_offsets, P.col_indices)
    r_ro, r_ci = _host_pattern(R.row_offsets, R.col_indices)
    fold = A.has_external_diag
    if fold:
        n = A.num_rows
        rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(a_ro))
        cols = a_ci.astype(np.int64)
        rows = np.concatenate([rows, np.arange(n, dtype=np.int64)])
        cols = np.concatenate([cols, np.arange(n, dtype=np.int64)])
        order = np.lexsort((cols, rows))
        # folded pattern, sorted: entry e reads value vector slot
        # fold_src[e] of concat(values, diag)
        fold_src = order.astype(np.int64)
        rows, cols = rows[order], cols[order]
        counts = np.bincount(rows, minlength=n)
        a_ro = np.zeros(n + 1, np.int64)
        a_ro[1:] = np.cumsum(counts)
        a_ci = cols
    else:
        fold_src = None
    # stage 1: T = A @ P
    one = _plan_stage(a_ro, a_ci, p_ro, p_ci)
    if one is None:
        return None
    sa, sp, seg1, starts1, t_rows, t_cols = one
    if fold_src is not None:
        sa = fold_src[sa]
    nT = t_rows.shape[0]
    t_counts = np.bincount(t_rows, minlength=A.num_rows)
    t_ro = np.zeros(A.num_rows + 1, np.int64)
    t_ro[1:] = np.cumsum(t_counts)
    # stage 2: C = R @ T
    two = _plan_stage(r_ro, r_ci, t_ro, t_cols)
    if two is None:
        return None
    sr, st, seg2, starts2, c_rows, c_cols = two
    stage1 = {"sa": sa.astype(np.int32, copy=False), "sp": sp,
              "seg1": seg1, "starts1": starts1, "nT": int(nT)}
    structure = _np_csr_structure(c_rows, c_cols, R.num_rows)
    return RapPlan("rap", stage1, sr, st, seg2, starts2,
                   c_rows.shape[0], fold, *structure,
                   num_rows=R.num_rows, num_cols=P.num_cols)


# -- plan cache (digest-keyed; survives level objects across warm
#    setups of the same pattern) ---------------------------------------------

_PLAN_CACHE = {}                        # digest -> RapPlan, LRU order
# sized so one 128^3-grade classical hierarchy's plans (L0 alone is
# GB-scale index arrays) co-reside with headroom; host RAM, not HBM
_PLAN_CACHE_MAX_BYTES = 6 << 30


def _pattern_digest(meta, *arrays) -> bytes:
    h = hashlib.blake2b(repr(meta).encode(), digest_size=16)
    for a in arrays:
        if a is None:
            h.update(b"<none>")
            continue
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(memoryview(a))
    return h.digest()


def _cache_get(key):
    from ..telemetry import metrics as _tm
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_CACHE[key] = _PLAN_CACHE.pop(key)       # LRU bump
        _tm.inc("amg.spgemm.plan_hit")
    return hit


def _cache_put(key, plan):
    from ..telemetry import metrics as _tm
    _tm.inc("amg.spgemm.plan_build")
    _PLAN_CACHE[key] = plan
    total = 0
    for k in reversed(list(_PLAN_CACHE)):
        total += _PLAN_CACHE[k].nbytes()
        if total > _PLAN_CACHE_MAX_BYTES and k != key:
            del _PLAN_CACHE[k]


def get_agg_plan(A: CsrMatrix, agg, nc: int):
    """Digest-cached relabel plan for (A pattern, aggregates map)."""
    key = _pattern_digest(
        ("agg", A.num_rows, A.num_cols, int(nc), A.has_external_diag),
        A.row_offsets, A.col_indices, np.asarray(agg))
    plan = _cache_get(key)
    if plan is None:
        plan = build_agg_plan(A, agg, nc)
        if plan is not None:
            _cache_put(key, plan)
    return plan


def get_rap_plan(R: CsrMatrix, A: CsrMatrix, P: CsrMatrix):
    """Digest-cached triple-product plan for (R, A, P) patterns."""
    if A.is_block or R.is_block or P.is_block:
        return None
    key = _pattern_digest(
        ("rap", R.num_rows, A.num_rows, P.num_cols,
         A.has_external_diag),
        R.row_offsets, R.col_indices, A.row_offsets, A.col_indices,
        P.row_offsets, P.col_indices)
    plan = _cache_get(key)
    if plan is None:
        plan = build_rap_plan(R, A, P)
        if plan is not None:
            _cache_put(key, plan)
    return plan


# -- value phase --------------------------------------------------------------


def _np_reduce_segments(cand, starts):
    if cand.shape[0] == 0:
        return cand
    return np.add.reduceat(cand, starts[:-1])


def _rap_values_numpy(plan: RapPlan, af, r_vals, p_vals):
    """Host value phase: the native flat-FMA sweep through the plan's
    precomputed indices (native/src/rap_values.cpp — the route
    host-built hierarchies take, keeping the result numpy-backed like
    the native RAP it replaces), or two numpy reduceat passes when the
    toolchain is unavailable. Both sum each segment strictly
    left-to-right, so the routes agree to the last bit."""
    if af.dtype in (np.float64, np.float32) \
            and (r_vals is None or r_vals.dtype == af.dtype) \
            and (p_vals is None or p_vals.dtype == af.dtype):
        from .. import native
        out = native.rap_plan_values_native(
            plan.stage1, plan.sr, plan.st, plan.starts2, plan.nU,
            af, p_vals, r_vals)
        if out is not None:
            return out
    if plan.stage1 is not None:
        s1 = plan.stage1
        cand1 = af[s1["sa"]] * p_vals[s1["sp"]]
        base = _np_reduce_segments(cand1, s1["starts1"])
    else:
        base = af
    cand2 = base[plan.st]
    if plan.sr is not None:
        cand2 = r_vals[plan.sr] * cand2
    return _np_reduce_segments(cand2, plan.starts2)


@functools.partial(jax.jit, static_argnames=("nT", "nU", "has1",
                                             "has_r"))
def _rap_values_slab(af, r_vals, p_vals, sa, sp, seg1, sr, st, seg2,
                     nT: int, nU: int, has1: bool, has_r: bool):
    """XLA value phase (every device-resident operand): gathers +
    sorted segment-sums through the static plan indices — zero sort /
    argsort / unique primitives in the jaxpr (the acceptance contract
    of the plan split's CPU route)."""
    if has1:
        cand1 = af[sa] * p_vals[sp]
        base = jax.ops.segment_sum(cand1, seg1, num_segments=nT,
                                   indices_are_sorted=True)
    else:
        base = af
    cand2 = base[st]
    if has_r:
        cand2 = r_vals[sr] * cand2
    return jax.ops.segment_sum(cand2, seg2, num_segments=nU,
                               indices_are_sorted=True)


def _fold_values(plan, A: CsrMatrix, np_route: bool):
    vals = A.values
    if not plan.fold_diag:
        return np.asarray(vals) if np_route else vals
    if np_route:
        return np.concatenate([np.asarray(vals), np.asarray(A.diag)])
    return jnp.concatenate([jnp.asarray(vals), jnp.asarray(A.diag)])


def rap_values(plan: RapPlan, A: CsrMatrix, R=None, P=None):
    """Value phase dispatch: recompute the product's numerics from the
    CURRENT coefficients through the plan. Two roads: host numpy
    (host-resident operands outside a forced-device setup), the XLA
    slab program otherwise."""
    r_vals = None if R is None else R.values
    p_vals = None if P is None else P.values
    if _on_host(A) and (R is None or _on_host(R)) \
            and (P is None or _on_host(P)):
        af = _fold_values(plan, A, np_route=True)
        return _rap_values_numpy(
            plan, af,
            None if r_vals is None else np.asarray(r_vals),
            None if p_vals is None else np.asarray(p_vals))
    af = _fold_values(plan, A, np_route=False)
    d = plan.dev()
    s1 = plan.stage1
    return _rap_values_slab(
        af,
        None if r_vals is None else jnp.asarray(r_vals),
        None if p_vals is None else jnp.asarray(p_vals),
        d.get("sa"), d.get("sp"), d.get("seg1"), d.get("sr"),
        d["st"], d["seg2"],
        0 if s1 is None else s1["nT"], plan.nU,
        s1 is not None, plan.sr is not None)


def plan_coarse_matrix(plan: RapPlan, A: CsrMatrix, R=None,
                       P=None) -> CsrMatrix:
    """Value phase + output assembly. kind="agg" emits the structure-
    complete initialized CSR `_compact_coarse` emits (the hierarchy
    builds the SpMV layout on top); kind="rap" emits the plain CSR the
    eager `galerkin_rap` emits (the caller init()s it). The structure
    arrays come from the plan (device twins uploaded once per plan on
    jnp routes — only the VALUES are new work per setup)."""
    vals = rap_values(plan, A, R, P)
    target = A.values
    if hasattr(vals, "dtype") and vals.dtype != target.dtype:
        vals = vals.astype(target.dtype)
    if isinstance(vals, np.ndarray):
        ro, ci, ri, di = (plan.row_offsets, plan.col_indices,
                          plan.row_ids, plan.diag_idx)
    else:
        d = plan.dev_structure()
        ro, ci, ri, di = (d["row_offsets"], d["col_indices"],
                          d["row_ids"], d["diag_idx"])
    if plan.kind == "agg":
        return CsrMatrix(
            row_offsets=ro, col_indices=ci, values=vals, diag=None,
            row_ids=ri, diag_idx=di, ell_cols=None, ell_vals=None,
            dia_offsets=None, dia_vals=None, num_rows=plan.num_rows,
            num_cols=plan.num_cols, block_dimx=1, block_dimy=1,
            initialized=True)
    return CsrMatrix(row_offsets=ro, col_indices=ci, values=vals,
                     num_rows=plan.num_rows, num_cols=plan.num_cols)
