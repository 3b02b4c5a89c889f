"""Classical interpolation operators.

Analogs of src/classical/interpolators/ (distance1.cu 900 LoC,
distance2.cu 2274 LoC, multipass.cu). Round-1 surface:

- D1: Ruge-Stuben *direct* interpolation with positive-coupling lumping.
  For a fine point i with strong coarse neighbors C_i:

      w_ij = -alpha_i * a_ij / ~a_ii        for j in C_i (a_ij < 0)
      alpha_i = sum_{k != i, a_ik<0} a_ik / sum_{j in C_i, a_ij<0} a_ij
      ~a_ii   = a_ii + sum_{k != i, a_ik>0, k not in C_i} a_ik

  Coarse points interpolate by injection (P row = e_c). All assembled
  with COO masks + segment sums (no per-row loops).
- Truncation (interp_truncation_factor / interp_max_elements) trims P
  and rescales rows to preserve the row sum (truncate analog); the rows
  that lost an entry are counted in amg.interp.truncated_rows, and where
  it is a pass of its own (every road but the native D2 sweep, which
  fuses it) it runs under the leaf amg.L<k>.truncate.
- MULTIPASS: real Stuben multipass interpolation (multipass.cu analog)
  via filtered SpGEMM passes — F-points acquire weights pass by pass
  through already-interpolated neighbors (see MultipassInterpolator).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import registry
from ...matrix import CsrMatrix
from ...profiling import trace_region
from ...telemetry import metrics as _tm


def _coarse_index(cf_map):
    """coarse id per vertex (valid where cf_map==COARSE); nc."""
    is_c = cf_map == 1
    cidx = jnp.cumsum(is_c.astype(jnp.int32)) - 1
    nc = int(cidx[-1]) + 1 if cf_map.shape[0] else 0
    return jnp.where(is_c, cidx, -1), nc


def _compact_coo(rows, cols, vals, mask, n, num_cols=None):
    """Device compaction of masked COO entries into an exact-size CSR:
    one host scalar sync (the count) + a sized nonzero gather — the
    static-shape idiom the aggregation Galerkin uses, replacing the
    round-1 host-numpy compress."""
    u = int(jnp.sum(mask))                       # one sync
    m = num_cols if num_cols is not None else n
    if u == 0:
        return CsrMatrix.from_scipy_like(
            jnp.zeros((n + 1,), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), vals.dtype), n, m)
    idx = jnp.nonzero(mask, size=u)[0]           # ascending -> CSR order
    r = rows[idx].astype(jnp.int32)
    c = cols[idx].astype(jnp.int32)
    v = vals[idx]
    counts = jnp.bincount(r, length=n)
    ro = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(counts).astype(jnp.int32)])
    return CsrMatrix.from_scipy_like(ro, c, v, n, m)


def _coo_member(keys_sorted, key_vals, ri, cj, n):
    """(ri, cj) membership against a row-major-sorted COO whose value is
    a positive indicator — binary search, no compaction or sort. Entries
    with non-positive values (masked/padded) never match because
    searchsorted('left') lands on the first occurrence of a key, which
    holds the coalesced sum."""
    key = ri.astype(jnp.int64) * n + cj.astype(jnp.int64)
    if keys_sorted.shape[0] == 0:
        return jnp.zeros(key.shape, bool)
    pos = jnp.clip(jnp.searchsorted(keys_sorted, key), 0,
                   keys_sorted.shape[0] - 1)
    return (keys_sorted[pos] == key) & (key_vals[pos] > 0)


class Interpolator:
    def __init__(self, cfg, scope):
        self.cfg = cfg
        self.scope = scope
        self.trunc_factor = float(cfg.get("interp_truncation_factor", scope))
        self.max_elements = int(cfg.get("interp_max_elements", scope))
        self.level_index = None     # the level that made it says which

    def generate(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        raise NotImplementedError

    def _cut(self, P: CsrMatrix) -> CsrMatrix:
        """P cut to the truncation keys, as a pass of its own."""
        if self.trunc_factor > 1.0 and self.max_elements <= 0:
            return P
        with trace_region(f"amg.L{self.level_index}.truncate"):
            return _truncate(P, self.trunc_factor, self.max_elements)


@registry.interpolators.register("D2")
class Distance2Interpolator(Interpolator):
    """Extended+i distance-two interpolation (distance2.cu analog; the
    formula of De Sterck/Falgout/Nolting/Yang, "Distance-two
    interpolation for parallel algebraic multigrid", 2008):

        w_ij = -(1/D_i) [ a_ij 1{j in C^_i}
                          + sum_{k in F_i^s} a_ik abar_kj / d_ik ]
        d_ik = sum_{l in C^_i + {i}} abar_kl
        D_i  = a_ii + sum_{n weak, n not in C^_i} a_in
                    + sum_{k in F_i^s} a_ik abar_ki / d_ik

    with C^_i = C_i + union of strong-C neighbors of i's strong-F
    neighbors, and abar the negative-coupling part of A. Everything is
    COO expands + segment sums: the two-hop triple expansion reuses the
    SpGEMM machinery, membership tests are sorted-key searches. This is
    what makes PMIS-coarsened V-cycles scalable (the D1 rate degrades
    with depth)."""

    def generate(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        from ...ops.spgemm import _on_host
        if _on_host(A):
            return self._generate_host(A, cf_map, strong)
        return self._generate_jnp(A, cf_map, strong)

    def _generate_host(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        """Numpy formulation of the same formula for the host-setup
        path: eager accelerator-shaped gathers cost ~10 ms each in
        dispatch on CPU; the identical index math in numpy runs the
        whole interpolation in tens of milliseconds."""
        from ... import native
        n = A.num_rows
        if not A.has_external_diag:
            # native C++ row sweep (the distance2.cu host analog): same
            # formula, stamp-array C-hat membership instead of sorted-key
            # searches — this is the classical-setup hot path
            out = native.d2_interp_native(
                n, np.asarray(A.row_offsets), np.asarray(A.col_indices),
                np.asarray(A.values), np.asarray(strong, np.uint8),
                np.asarray(cf_map, np.int32), self.trunc_factor,
                self.max_elements)
            if out is not None:
                # truncation is fused into the native sweep; numpy-backed
                # on purpose: the host hierarchy build stays off the
                # XLA:CPU array path end to end
                p_ptr, p_col, p_val, lost = out
                _tm.inc("amg.interp.truncated_rows", lost)
                nc = int(np.sum(np.asarray(cf_map) == 1))
                return CsrMatrix(
                    row_offsets=p_ptr.astype(np.int32), col_indices=p_col,
                    values=p_val.astype(np.asarray(A.values).dtype,
                                        copy=False), num_rows=n,
                    num_cols=nc)
        ro = np.asarray(A.row_offsets)
        cols = np.asarray(A.col_indices)
        vals = np.asarray(A.values)
        rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(ro))
        cf_map = np.asarray(cf_map)
        strong = np.asarray(strong)
        diag = np.asarray(A.diagonal())
        sgn = np.sign(np.where(diag == 0, 1.0, diag))
        offd = rows != cols
        neg = offd & (vals * sgn[rows] < 0)
        is_C = cf_map == 1
        cidx = np.cumsum(is_C.astype(np.int64)) - 1
        cidx = np.where(is_C, cidx, -1)
        nc = int(is_C.sum())
        strongC = strong & is_C[cols]
        strongF = strong & ~is_C[cols] & offd

        def compact_csr(mask):
            r, c, v = rows[mask], cols[mask], vals[mask]
            counts = np.bincount(r, minlength=n)
            rp = np.zeros(n + 1, np.int64)
            np.cumsum(counts, out=rp[1:])
            return rp, c, v

        f_ptr, f_col, f_val = compact_csr(strongF)
        a_ptr, a_col, a_val = compact_csr(neg)
        sc_ptr, sc_col, sc_val = compact_csr(strongC)
        # C-hat membership: strong C neighbors + two-hop through F
        out = native.spgemm_native(
            n, n, f_ptr.astype(np.int32), f_col,
            np.ones_like(f_val), sc_ptr.astype(np.int32), sc_col,
            np.ones_like(sc_val))
        if out is not None:
            hp, hc, _hv = out
            h_rows = np.repeat(np.arange(n, dtype=np.int64),
                               np.diff(hp))
            keys_h = h_rows * n + hc.astype(np.int64)
        else:       # no toolchain: use the accelerator-shaped path
            return self._generate_jnp(A, cf_map, strong)
        sc_rows = rows[strongC].astype(np.int64)
        keys_sc = sc_rows * n + cols[strongC].astype(np.int64)

        def member(ri, cj):
            key = ri.astype(np.int64) * n + cj.astype(np.int64)
            out_m = np.zeros(key.shape, bool)
            for ks in (keys_sc, keys_h):
                if ks.shape[0]:
                    pos = np.clip(np.searchsorted(ks, key), 0,
                                  ks.shape[0] - 1)
                    out_m |= ks[pos] == key
            return out_m

        # two-hop triples (i -k-> m): expand F against Abar
        f_rows = np.repeat(np.arange(n, dtype=np.int32),
                           np.diff(f_ptr))
        a_row_nnz = np.diff(a_ptr)
        counts = a_row_nnz[f_col]
        src_f = np.repeat(np.arange(f_col.shape[0]), counts)
        cum = np.zeros(f_col.shape[0] + 1, np.int64)
        np.cumsum(counts, out=cum[1:])
        offset_in_row = np.arange(int(cum[-1]), dtype=np.int64) - \
            cum[src_f]
        src_b = a_ptr[f_col[src_f]] + offset_in_row
        t_i = f_rows[src_f]
        t_m = a_col[src_b]
        t_aik = f_val[src_f]
        t_abar = a_val[src_b]
        keep = member(t_i, t_m) | (t_m == t_i)
        denom = np.zeros(f_col.shape[0])
        np.add.at(denom, src_f, np.where(keep, t_abar, 0.0))
        bad = denom == 0
        dsafe = np.where(bad, 1.0, denom)
        contrib = t_aik * t_abar / dsafe[src_f]
        contrib = np.where(bad[src_f], 0.0, contrib)

        m_is_entry = keep & is_C[t_m] & (t_m != t_i)
        e_rows = t_i[m_is_entry]
        e_cols = t_m[m_is_entry]
        e_vals = contrib[m_is_entry]
        in_chat = member(rows, cols)
        dmask = offd & is_C[cols] & in_chat
        fb = np.zeros(n)
        np.add.at(fb, t_i, np.where(keep & (t_m == t_i), contrib, 0.0))
        lump_mask = offd & ~in_chat & ~strongF
        lump = np.zeros(n)
        np.add.at(lump, rows, np.where(lump_mask, vals, 0.0))
        bad_f = np.zeros(n)
        np.add.at(bad_f, f_rows, np.where(bad, f_val, 0.0))
        D = diag + lump + fb + bad_f

        all_rows = np.concatenate([rows[dmask], e_rows])
        all_cols = np.concatenate([cols[dmask], e_cols])
        all_vals = np.concatenate([vals[dmask], e_vals])
        f_row = (cf_map == 0)[all_rows]
        w = -all_vals / np.where(D[all_rows] == 0, 1.0, D[all_rows])
        c_rows = np.nonzero(cf_map == 1)[0].astype(np.int32)
        p_rows = np.concatenate([all_rows[f_row], c_rows])
        p_cols = np.concatenate([cidx[all_cols[f_row]], cidx[c_rows]])
        p_vals = np.concatenate([w[f_row], np.ones(nc, vals.dtype)])
        order = np.lexsort((p_cols, p_rows))
        p_rows, p_cols, p_vals = (p_rows[order], p_cols[order],
                                  p_vals[order])
        # coalesce duplicates (from_coo semantics)
        first = np.concatenate([[True], (p_rows[1:] != p_rows[:-1])
                                | (p_cols[1:] != p_cols[:-1])])
        seg = np.cumsum(first) - 1
        vsum = np.zeros(int(seg[-1]) + 1 if seg.size else 0,
                        p_vals.dtype)
        np.add.at(vsum, seg, p_vals)
        pr, pc = p_rows[first], p_cols[first]
        counts = np.bincount(pr, minlength=n)
        pp = np.zeros(n + 1, np.int32)
        np.cumsum(counts, out=pp[1:])
        P = CsrMatrix.from_scipy_like(pp, pc.astype(np.int32),
                                      jnp.asarray(vsum), n, nc)
        return self._cut(P)

    def _generate_jnp(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        from ...ops.spgemm import _expand, csr_multiply
        n = A.num_rows
        rows, cols, vals = A.coo()
        rows64 = rows.astype(jnp.int64)
        cols64 = cols.astype(jnp.int64)
        diag = A.diagonal()
        sgn = jnp.sign(jnp.where(diag == 0, 1.0, diag))
        offd = rows != cols
        neg = offd & (vals * sgn[rows] < 0)      # abar pattern
        is_C = cf_map == 1
        cidx, nc = _coarse_index(cf_map)
        strongC = strong & is_C[cols]
        strongF = strong & ~is_C[cols] & offd

        Fmat = _compact_coo(rows, cols, vals, strongF, n)  # i -> k
        Abar = _compact_coo(rows, cols, vals, neg, n)      # k -> m

        # C-hat membership set: strong C neighbors + two-hop through F
        Sc01 = _compact_coo(rows, cols, jnp.ones_like(vals), strongC, n)
        Sf01 = CsrMatrix.from_scipy_like(
            Fmat.row_offsets, Fmat.col_indices,
            jnp.ones_like(Fmat.values), n, n)
        H = csr_multiply(Sf01, Sc01)
        hr, hc, hv = H.coo()
        scr, scc, scv = Sc01.coo()
        # both COO sets are row-major sorted: membership = binary search
        # in either (no host unique/merge)
        keys_sc = scr.astype(jnp.int64) * n + scc.astype(jnp.int64)
        keys_h = hr.astype(jnp.int64) * n + hc.astype(jnp.int64)

        def member(ri, cj):
            return (_coo_member(keys_sc, scv, ri, cj, n)
                    | _coo_member(keys_h, hv, ri, cj, n))

        # two-hop triples (i -k-> m)
        t_rows, t_m, src_f, src_b = _expand(Fmat, Abar)
        t_i = t_rows
        t_k = Fmat.col_indices[src_f]
        t_aik = Fmat.values[src_f]
        t_abar = Abar.values[src_b]
        keep = member(t_i, t_m) | (t_m == t_i)
        denom = jax.ops.segment_sum(jnp.where(keep, t_abar, 0.0), src_f,
                                    num_segments=Fmat.nnz)
        bad = denom == 0                          # k distributes nowhere
        dsafe = jnp.where(bad, 1.0, denom)
        contrib = t_aik * t_abar / dsafe[src_f]
        contrib = jnp.where(bad[src_f], 0.0, contrib)

        # interpolatory entries: triples landing on C points in C-hat
        m_is_entry = keep & is_C[t_m] & (t_m != t_i)
        e_rows = t_i[m_is_entry]
        e_cols = t_m[m_is_entry]
        e_vals = contrib[m_is_entry]
        # direct part: a_ij for neighbors j in C-hat (evaluated once,
        # shared with the weak-lumping mask below)
        in_chat = member(rows, cols)
        dmask = offd & is_C[cols] & in_chat
        # diagonal D_i: weak lumping + the "+i" feedback terms
        fb = jax.ops.segment_sum(
            jnp.where(keep & (t_m == t_i), contrib, 0.0), t_i,
            num_segments=n)
        lump_mask = offd & ~in_chat & ~strongF
        lump = jax.ops.segment_sum(jnp.where(lump_mask, vals, 0.0), rows,
                                   num_segments=n, indices_are_sorted=True)
        # strong-F neighbors whose denominator collapsed: lump them too
        f_row_ids = Fmat.coo()[0]
        bad_f = jax.ops.segment_sum(jnp.where(bad, Fmat.values, 0.0),
                                    f_row_ids, num_segments=n)
        D = diag + lump + fb + bad_f

        all_rows = jnp.concatenate([rows[dmask], e_rows])
        all_cols = jnp.concatenate([cols[dmask], e_cols])
        all_vals = jnp.concatenate([vals[dmask], e_vals])
        f_row = (cf_map == 0)[all_rows]
        w = -all_vals / jnp.where(D[all_rows] == 0, 1.0, D[all_rows])
        c_rows = jnp.where(cf_map == 1)[0].astype(jnp.int32)
        p_rows = jnp.concatenate([all_rows[f_row], c_rows])
        p_cols = jnp.concatenate([cidx[all_cols[f_row]], cidx[c_rows]])
        p_vals = jnp.concatenate([w[f_row],
                                  jnp.ones((nc,), vals.dtype)])
        P = CsrMatrix.from_coo(p_rows, p_cols, p_vals, n, nc)
        return self._cut(P)


@registry.interpolators.register("D1")
class Distance1Interpolator(Interpolator):
    def generate(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        n = A.num_rows
        rows, cols, vals = A.coo()
        diag = A.diagonal()
        cidx, nc = _coarse_index(cf_map)
        is_f_row = (cf_map == 0)[rows]
        neg = vals < 0
        offd = rows != cols
        in_Ci = strong & (cidx[cols] >= 0) & neg & offd

        sum_neg = jax.ops.segment_sum(jnp.where(offd & neg, vals, 0.0),
                                      rows, num_segments=n,
                                      indices_are_sorted=True)
        sum_Ci = jax.ops.segment_sum(jnp.where(in_Ci, vals, 0.0),
                                     rows, num_segments=n,
                                     indices_are_sorted=True)
        # positive off-diagonals not interpolated from: lump into diagonal
        pos_lump = jax.ops.segment_sum(
            jnp.where(offd & ~neg, vals, 0.0), rows, num_segments=n,
            indices_are_sorted=True)
        dmod = diag + pos_lump
        alpha = sum_neg / jnp.where(sum_Ci == 0, 1.0, sum_Ci)
        alpha = jnp.where(sum_Ci == 0, 0.0, alpha)
        w = -alpha[rows] * vals / jnp.where(dmod[rows] == 0, 1.0, dmod[rows])

        # P entries: F rows interpolate from C_i; C rows inject
        mask = in_Ci & is_f_row
        p_rows = jnp.concatenate([rows[mask],
                                  jnp.where(cf_map == 1)[0].astype(jnp.int32)])
        p_cols = jnp.concatenate([cidx[cols[mask]],
                                  cidx[jnp.where(cf_map == 1)[0]]])
        p_vals = jnp.concatenate([w[mask],
                                  jnp.ones((nc,), vals.dtype)])
        P = CsrMatrix.from_coo(p_rows, p_cols, p_vals, n, nc)
        return self._cut(P)


@registry.interpolators.register("MULTIPASS")
class MultipassInterpolator(Interpolator):
    """Multipass interpolation for aggressive coarsening
    (multipass.cu:1, 2557 LoC; Stuben's multipass scheme). F-points are
    ranked by their strong-connection distance to the C-set ("pass"
    number); pass-1 points interpolate directly from strong C neighbors
    (the D1 formula), and pass-p points substitute the already-built P
    rows of their pass<p strong neighbors:

        w_i = -(alpha_i / ~a_ii) * sum_{j in J_i} a_ij P_j,
        alpha_i = sum_{k != i, a_ik<0} a_ik / sum_{j in J_i} a_ij,
        J_i = strong negative neighbors with pass < p

    so each pass is one filtered-SpGEMM (A restricted to pass-p rows and
    pass<p columns, times the current P) — the reference's per-pass
    kernel sweeps become a handful of sort-based SpGEMM calls.
    """

    def generate(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        from ...ops.spgemm import _on_host
        if _on_host(A) and not A.has_external_diag:
            # host-setup path: one native sweep a pass. The jnp form
            # below runs there as hundreds of eager device programs
            # whose shapes are the data's own counts (731 of them, 13
            # minutes of compiling, on SPE10's first level: PR 47)
            from ... import native
            out = native.multipass_native(
                A.num_rows, np.asarray(A.row_offsets),
                np.asarray(A.col_indices), np.asarray(A.values),
                np.asarray(strong, np.uint8), np.asarray(cf_map, np.int32))
            if out is not None:
                p_ptr, p_col, p_val = out
                return self._cut(CsrMatrix(
                    row_offsets=p_ptr.astype(np.int32), col_indices=p_col,
                    values=p_val.astype(np.asarray(A.values).dtype,
                                        copy=False),
                    num_rows=A.num_rows,
                    num_cols=int(np.sum(np.asarray(cf_map) == 1))))
        return self._generate_jnp(A, cf_map, strong)

    def _generate_jnp(self, A: CsrMatrix, cf_map, strong) -> CsrMatrix:
        from ...ops.spgemm import csr_multiply
        n = A.num_rows
        rows, cols, vals = A.coo()
        diag = A.diagonal()
        cidx, nc = _coarse_index(cf_map)
        is_C = cf_map == 1
        offd = rows != cols
        neg = vals < 0
        strong_neg = strong & offd & neg
        # ~a_ii: positive off-diagonals lumped into the diagonal (D1
        # semantics)
        pos_lump = jax.ops.segment_sum(
            jnp.where(offd & ~neg, vals, 0.0), rows, num_segments=n,
            indices_are_sorted=True)
        dmod = diag + pos_lump
        sum_neg = jax.ops.segment_sum(jnp.where(offd & neg, vals, 0.0),
                                      rows, num_segments=n,
                                      indices_are_sorted=True)

        # pass numbers: BFS distance to C through strong edges
        BIG = np.int32(2 ** 30)
        pnum = jnp.where(is_C, 0, BIG).astype(jnp.int32)
        for _ in range(64):
            nbr_min = jax.ops.segment_min(
                jnp.where(strong_neg, pnum[cols], BIG), rows,
                num_segments=n, indices_are_sorted=True)
            new = jnp.where(is_C, 0, jnp.minimum(pnum, nbr_min + 1))
            if bool(jnp.all(new == pnum)):
                break
            pnum = new
        max_pass = int(jnp.max(jnp.where(pnum < BIG, pnum, 0)))

        # accumulate P rows pass by pass (C rows: injection)
        nc_i = int(jnp.sum(is_C))
        c_rows = jnp.nonzero(is_C, size=max(nc_i, 1))[0].astype(jnp.int32)
        p_rows = [c_rows[:nc_i]]
        p_cols = [cidx[c_rows[:nc_i]]]
        p_vals = [jnp.ones((nc_i,), vals.dtype)]

        for p in range(1, max_pass + 1):
            in_pass = pnum == p
            emask = strong_neg & in_pass[rows] & (pnum[cols] < p)
            denom = jax.ops.segment_sum(jnp.where(emask, vals, 0.0), rows,
                                        num_segments=n,
                                        indices_are_sorted=True)
            alpha = jnp.where(denom != 0,
                              sum_neg / jnp.where(denom == 0, 1.0, denom),
                              0.0)
            scale = -alpha / jnp.where(dmod == 0, 1.0, dmod)
            Ap = _compact_coo(rows, cols, vals, emask, n)
            # current P (global-column space n x nc)
            P_cur = CsrMatrix.from_coo(
                jnp.concatenate(p_rows), jnp.concatenate(p_cols),
                jnp.concatenate(p_vals), n, nc)
            raw = csr_multiply(Ap, P_cur)
            rr, rc, rv = raw.coo()
            u = int(jnp.sum(rv != 0))            # one sync per pass
            idx = jnp.nonzero(rv != 0, size=max(u, 1))[0]
            p_rows.append(rr[idx][:u])
            p_cols.append(rc[idx][:u])
            p_vals.append((rv * scale[rr])[idx][:u])

        P = CsrMatrix.from_coo(
            jnp.concatenate(p_rows), jnp.concatenate(p_cols),
            jnp.concatenate(p_vals), n, nc)
        return self._cut(P)


def _truncate(P: CsrMatrix, factor: float, max_elements: int) -> CsrMatrix:
    """Drop small interpolation entries / cap per-row count, rescaling to
    preserve row sums (src/truncate.cu semantics for P)."""
    if factor > 1.0 and max_elements <= 0:
        return P
    from ...matrix import host_resident
    if host_resident(P.row_offsets, P.col_indices, P.values):
        return _truncate_host(P, factor, max_elements)
    rows, cols, vals = P.coo()
    n = P.num_rows
    absv = jnp.abs(vals)
    keep = jnp.ones_like(vals, bool)
    if factor <= 1.0:
        rmax = jax.ops.segment_max(absv, rows, num_segments=n,
                                   indices_are_sorted=True)
        keep &= absv >= factor * rmax[rows]
    if max_elements > 0:
        # keep only the max_elements largest |entries| per row: rank by
        # (row, -|v|) via two stable device argsorts (the int32 lexsort
        # idiom), then cap the within-row rank
        e = rows.shape[0]
        order1 = jnp.argsort(-absv, stable=True)
        order2 = jnp.argsort(rows[order1], stable=True)
        ordn = order1[order2]                    # grouped by row, desc |v|
        pos = jnp.arange(e, dtype=jnp.int32)
        first = jax.ops.segment_min(pos, rows[ordn], num_segments=n)
        within = pos - first[rows[ordn]]
        keep = keep.at[ordn].set(keep[ordn] & (within < max_elements))
    # rescale kept entries to preserve row sums
    rowsum = jax.ops.segment_sum(vals, rows, num_segments=n,
                                 indices_are_sorted=True)
    keptsum = jax.ops.segment_sum(jnp.where(keep, vals, 0.0), rows,
                                  num_segments=n, indices_are_sorted=True)
    scale = rowsum / jnp.where(keptsum == 0, 1.0, keptsum)
    scale = jnp.where(keptsum == 0, 1.0, scale)
    lost = jax.ops.segment_sum((~keep).astype(jnp.int32), rows,
                               num_segments=n, indices_are_sorted=True)
    _tm.inc("amg.interp.truncated_rows", int(jnp.sum(lost > 0)))
    return _compact_coo(rows, cols, vals * scale[rows], keep, P.num_rows,
                        num_cols=P.num_cols)


def _truncate_host(P: CsrMatrix, factor: float, max_elements: int
                   ) -> CsrMatrix:
    """Numpy form of _truncate for the host-setup path (same semantics;
    keeps the hierarchy numpy-backed — the truncated P feeds straight
    into the native RAP/SWELL components)."""
    n = P.num_rows
    ro = np.asarray(P.row_offsets)
    cols = np.asarray(P.col_indices)
    vals = np.asarray(P.values)
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(ro))
    absv = np.abs(vals)
    keep = np.ones(vals.shape[0], bool)
    from ...matrix import _np_row_reduce
    if factor <= 1.0:
        rmax = _np_row_reduce(np.maximum, absv, ro, n, 0.0)
        keep &= absv >= factor * rmax[rows]
    if max_elements > 0:
        # rank entries within each row by descending |v| (stable), cap
        order1 = np.argsort(-absv, kind="stable")
        order2 = np.argsort(rows[order1], kind="stable")
        ordn = order1[order2]
        pos = np.arange(vals.shape[0], dtype=np.int64)
        first = np.full(n, vals.shape[0], np.int64)
        np.minimum.at(first, rows[ordn], pos)
        within = pos - first[rows[ordn]]
        keep[ordn] &= within < max_elements
    rowsum = np.bincount(rows, weights=vals, minlength=n)
    keptsum = np.bincount(rows, weights=np.where(keep, vals, 0.0),
                          minlength=n)
    scale = np.where(keptsum == 0, 1.0,
                     rowsum / np.where(keptsum == 0, 1.0, keptsum))
    new_vals = (vals * scale[rows])[keep]
    new_cols = cols[keep]
    counts = np.bincount(rows[keep], minlength=n)
    _tm.inc("amg.interp.truncated_rows",
            int(np.count_nonzero(counts < np.diff(ro))))
    new_ro = np.zeros(n + 1, np.int32)
    np.cumsum(counts, out=new_ro[1:])
    return CsrMatrix(row_offsets=new_ro, col_indices=new_cols,
                     values=new_vals.astype(vals.dtype, copy=False),
                     num_rows=n, num_cols=P.num_cols)
