"""Coarse-operator generation for aggregation AMG.

Analog of src/aggregation/coarseAgenerators/ (low_deg 1427 LoC, thrust,
hybrid). With piecewise-constant P (aggregates map), the Galerkin triple
product R A P collapses to relabeling A's COO entries by aggregate id and
coalescing duplicates — a sort + segmented-sum, the TPU-native analog of
the reference's hash-table kernels.

The whole product is ONE compiled program with static shapes: instead of
compacting duplicates (data-dependent size), the coarse CSR keeps every
relabeled entry, with the coalesced sum stored on the first occurrence of
each (I, J) pair and zeros on the rest. Zero-valued duplicate entries are
inert in every consumer (SpMV adds 0; diag extraction is
first-occurrence; edge weights ignore w == 0).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ...matrix import CsrMatrix, lexsort_rc


@jax.jit
def _coarse_entries(A, agg):
    """Relabel + sort + coalesce: returns sorted COO with the summed
    value on each (I, J) pair's first occurrence (zeros on duplicates)
    and the traced unique-entry count."""
    rows, cols, vals = A.coo()
    r2 = agg[rows].astype(jnp.int32)
    c2 = agg[cols].astype(jnp.int32)
    if A.has_external_diag:
        # fold external diagonal contributions in: they land on
        # (agg[i], agg[i])
        da = agg.astype(jnp.int32)
        r2 = jnp.concatenate([r2, da])
        c2 = jnp.concatenate([c2, da])
        vals = jnp.concatenate([vals, A.diag])
    e = r2.shape[0]
    order = lexsort_rc(r2, c2)
    r_s = r2[order]
    c_s = c2[order]
    v_s = vals[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (r_s[1:] != r_s[:-1]) | (c_s[1:] != c_s[:-1])])
    seg = jnp.cumsum(first) - 1
    vsum = jax.ops.segment_sum(v_s, seg, num_segments=e,
                               indices_are_sorted=True)
    fexp = first if v_s.ndim == 1 else first[:, None, None]
    v_out = jnp.where(fexp, vsum[seg], 0.0)
    return r_s, c_s, v_out, first, seg[-1] + 1


@functools.partial(jax.jit, static_argnames=("bdims", "nc", "u"))
def _compact_coarse(r_s, c_s, v_out, first, bdims, nc: int, u: int):
    """Gather the u unique entries into an exact-size CSR (restores the
    geometric nnz decay of the hierarchy: each coarse level stores and
    sweeps only its real entries)."""
    e = r_s.shape[0]
    idx = jnp.nonzero(first, size=u, fill_value=e - 1)[0]
    r = r_s[idx]
    c = c_s[idx]
    v = v_out[idx]
    counts = jnp.bincount(r, length=nc)
    row_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts).astype(jnp.int32)])
    is_diag = c == r
    cand = jnp.where(is_diag, jnp.arange(u, dtype=jnp.int32), u)
    dmin = jax.ops.segment_min(cand, r, num_segments=nc,
                               indices_are_sorted=True)
    diag_idx = jnp.where(dmin >= u, -1, dmin).astype(jnp.int32)
    bx, by = bdims
    return CsrMatrix(
        row_offsets=row_offsets, col_indices=c, values=v,
        diag=None, row_ids=r, diag_idx=diag_idx,
        ell_cols=None, ell_vals=None, dia_offsets=None, dia_vals=None,
        num_rows=nc, num_cols=nc, block_dimx=bx, block_dimy=by,
        initialized=True)


def coarse_a_from_aggregates(A: CsrMatrix, agg, nc: int) -> CsrMatrix:
    """A_c[I,J] = sum_{agg[i]==I, agg[j]==J} A[i,j] — two jitted
    sort/segmented-sum programs with static shapes. The per-level host
    materializations are exactly two scalars: `nc` (from the selector)
    and the unique-entry count `u`."""
    r_s, c_s, v_out, first, u = _coarse_entries(A, agg)
    return _compact_coarse(r_s, c_s, v_out, first,
                           (A.block_dimx, A.block_dimy), int(nc), int(u))


# ---------------------------------------------------------------------------
# structured (GEO) Galerkin fast path
# ---------------------------------------------------------------------------

def _decompose(d: int, nx: int, ny: int, nz: int):
    """Split a linear DIA offset into (dx, dy, dz) grid shifts; returns
    None when the offset is not a small stencil shift."""
    for dz in (0, -1, 1, -2, 2):
        if abs(dz) > min(2, nz - 1):
            continue
        for dy in (0, -1, 1, -2, 2):
            if abs(dy) > min(2, ny - 1):
                continue
            dx = d - dz * nx * ny - dy * nx
            if abs(dx) <= min(3, nx - 1):
                return dx, dy, dz
    return None


def pair_sum_axis(v3, e, axis):
    """Pair-sum a (nz, ny, nx) array along ONE grid axis of extent `e`
    (odd extents keep a singleton tail) — the single source of truth for
    the structured aggregation map agg(x,y,z) = (x//2, y//2, z//2),
    shared by the GEO transfer operators and the structured Galerkin.

    Implemented as two strided slices + add: a `(..., e//2, 2)` reshape
    would put the pair in the minor dimension, which TPU tiling pads
    128x (a 4 GB temp at 256^3)."""
    dims = 2 - axis

    def sl(start, stop):
        s = [slice(None)] * 3
        s[dims] = slice(start, stop, 2)
        return v3[tuple(s)]

    out = sl(0, e - 1) + sl(1, e)
    if e % 2:
        s = [slice(None)] * 3
        s[dims] = slice(e - 1, e)
        out = jnp.concatenate([out, v3[tuple(s)]], axis=dims)
    return out


def geo_shapes(fine_shape, axes):
    """Intermediate grid shapes of the per-axis pairing sequence."""
    shapes = [tuple(fine_shape)]
    for a in axes:
        s = list(shapes[-1])
        s[a] = (s[a] + 1) // 2
        shapes.append(tuple(s))
    return shapes


def _pair_sum3(v3, axes, shapes):
    out = v3
    for k, a in enumerate(axes):
        out = pair_sum_axis(out, shapes[k][a], a)
    return out


class _DeferredChecks(threading.local):
    """Per-thread accumulator for the wrap checks of a whole hierarchy
    build: each level appends its device flag; the owner fetches them
    in ONE device->host sync at the end (a per-level bool() would
    block the build once per level). `disable_fast`
    turns the DIA fast path off during the rare rebuild after a failed
    deferred check."""

    def __init__(self):
        self.items = None
        self.disable_fast = False


_deferred = _DeferredChecks()


@contextlib.contextmanager
def deferred_wrap_checks():
    """Collect wrap-check flags instead of blocking per level. Yields a
    `flush()` callable returning True when ANY collected check failed
    (single device fetch)."""
    prev = _deferred.items
    _deferred.items = []

    def flush() -> bool:
        flags = _deferred.items
        _deferred.items = []
        if not flags:
            return False
        return bool(jnp.any(jnp.stack(flags)))

    try:
        yield flush
    finally:
        _deferred.items = prev


@contextlib.contextmanager
def geo_dia_disabled():
    """Force the generic relabel Galerkin (rebuild path after a failed
    deferred wrap check)."""
    prev = _deferred.disable_fast
    _deferred.disable_fast = True
    try:
        yield
    finally:
        _deferred.disable_fast = prev


@functools.partial(jax.jit, static_argnames=("shifts", "shape"))
def _any_wrapped(vals, shifts, shape):
    """True when any nonzero lies where its geometric shift exits the
    grid (the classification would be wrong). `shifts`/`shape` are
    hashable statics so this caches across setups and levels."""
    nx, ny, nz = shape
    n = nx * ny * nz
    sh = jnp.asarray(shifts, jnp.int32)
    ix = jnp.arange(n, dtype=jnp.int32)
    gx = ix % nx
    gy = (ix // nx) % ny
    gz = ix // (nx * ny)
    dx = sh[:, 0][:, None]
    dy = sh[:, 1][:, None]
    dz = sh[:, 2][:, None]
    ok = ((gx + dx >= 0) & (gx + dx < nx) & (gy + dy >= 0)
          & (gy + dy < ny) & (gz + dz >= 0) & (gz + dz < nz))
    return jnp.any(jnp.where(ok, 0.0, vals) != 0)


@functools.lru_cache(maxsize=256)
def _geo_contrib_table(dia_offsets, shifts, axes, coarse_shape):
    """Static contribution table: which fine diagonals (with which
    parity masks) land on which coarse diagonals."""
    cnx, cny, cnz = coarse_shape
    paired = set(axes)

    def splits(delta, axis):
        if axis not in paired:
            return [(delta, None)]
        lo = delta // 2                      # x even: (x+d)//2 - x//2
        hi = (delta + 1) // 2                # x odd
        if lo == hi:
            return [(lo, None)]
        return [(lo, 0), (hi, 1)]            # (coarse shift, fine parity)

    table = {}
    for t in range(len(dia_offsets)):
        dx, dy, dz = shifts[t]
        for cdx, px in splits(dx, 0):
            for cdy, py in splits(dy, 1):
                for cdz, pz in splits(dz, 2):
                    cd = (cdz * cny + cdy) * cnx + cdx
                    table.setdefault((cd, cdx, cdy, cdz), []).append(
                        (t, px, py, pz))
    coffsets = tuple(sorted(table, key=lambda k: k[0]))
    contribs = tuple(tuple(table[k]) for k in coffsets)
    return coffsets, contribs


@functools.partial(jax.jit, static_argnames=("coffsets", "contribs",
                                             "fine_shape", "axes"))
def _geo_compute(vals, coffsets, contribs, fine_shape, axes):
    """The whole structured Galerkin numeric phase as one cached jitted
    program: parity-masked accumulation + reshape pair-sums."""
    nx, ny, nz = fine_shape
    shapes = geo_shapes(fine_shape, axes)
    v3 = vals.reshape(len(vals), nz, ny, nx)
    xpar = jnp.arange(nx, dtype=jnp.int32) % 2
    ypar = jnp.arange(ny, dtype=jnp.int32) % 2
    zpar = jnp.arange(nz, dtype=jnp.int32) % 2
    outs = []
    for entries in contribs:
        acc = jnp.zeros((nz, ny, nx), vals.dtype)
        for (t, px, py, pz) in entries:
            m = v3[t]
            if px is not None:
                m = m * (xpar == px)[None, None, :]
            if py is not None:
                m = m * (ypar == py)[None, :, None]
            if pz is not None:
                m = m * (zpar == pz)[:, None, None]
            acc = acc + m
        outs.append(_pair_sum3(acc, axes, shapes).reshape(-1))
    return jnp.stack(outs)               # (kc, nc)


@functools.lru_cache(maxsize=256)
def _geo_csr_structure(coffsets, coarse_shape):
    """CSR structure of the coarse stencil (host numpy, vectorized;
    cached so resetup rebuilds only the numeric phase)."""
    cnx, cny, cnz = coarse_shape
    nc = cnx * cny * cnz
    ci = np.arange(nc, dtype=np.int32)
    cx = ci % cnx
    cy = (ci // cnx) % cny
    cz = ci // (cnx * cny)
    valid = np.stack([
        (cx + cdx >= 0) & (cx + cdx < cnx) & (cy + cdy >= 0)
        & (cy + cdy < cny) & (cz + cdz >= 0) & (cz + cdz < cnz)
        for (_, cdx, cdy, cdz) in coffsets])          # (kc, nc)
    counts = valid.sum(axis=0).astype(np.int32)
    row_offsets = np.zeros(nc + 1, np.int32)
    np.cumsum(counts, out=row_offsets[1:])
    # entries ordered (row, offset-rank) = (row, ascending column)
    off_idx, rows = np.nonzero(valid)
    order = np.lexsort((off_idx, rows))
    off_e = off_idx[order].astype(np.int32)
    row_e = rows[order].astype(np.int32)
    col_e = row_e + np.asarray([k[0] for k in coffsets], np.int32)[off_e]
    # diagonal position within each row (-1 when offset 0 is not stored)
    zero_rank = next((i for i, k in enumerate(coffsets) if k[0] == 0),
                     None)
    diag_idx = np.full(nc, -1, np.int32)
    if zero_rank is not None:
        is_diag = off_e == zero_rank
        diag_idx[row_e[is_diag]] = np.nonzero(is_diag)[0].astype(np.int32)
    return row_offsets, off_e, row_e, col_e, diag_idx


def geo_coarse_values(A: CsrMatrix, fine_shape, axes, coarse_shape):
    """Numeric phase of the structured (GEO) Galerkin product: the
    coarse diagonal slab (kc, nc) computed WITHOUT sorts or scatters.

    For a fine entry A[i, i+d] with grid shift (dx, dy, dz), the coarse
    offset along each paired axis is floor((x+dx)/2) - floor(x/2) — a
    parity-dependent split into at most two coarse shifts per axis. Each
    fine diagonal therefore scatters into a statically-known set of
    coarse diagonals with parity masks, and the aggregate summation is
    the same reshape pair-sum as the restriction operator. One jitted
    program; numerically identical to the generic COO relabel+sum (both
    compute sum over fine pairs), so iteration counts are unchanged.

    Returns (cvals, coffsets) or None when the fast path does not apply
    (non-stencil offsets, or entries that wrap grid rows).
    """
    nx, ny, nz = fine_shape
    cnx, cny, cnz = coarse_shape
    if A.dia_offsets is None or A.grid_shape != tuple(fine_shape) \
            or A.is_block:
        return None
    decomp = {}
    for d in A.dia_offsets:
        g = _decompose(int(d), nx, ny, nz)
        if g is None:
            return None
        decomp[int(d)] = g

    if _deferred.disable_fast:
        return None
    n = A.num_rows
    vals = A.dia_vals.reshape(len(A.dia_offsets), -1)[:, :n]
    # wrap check: a geometric shift must keep every nonzero inside the
    # grid — entries crossing a grid row boundary would be
    # misclassified. Inside a hierarchy build the flag is DEFERRED
    # (batched single fetch, deferred_wrap_checks); standalone calls
    # block here as before.
    shifts = tuple(decomp[int(d)] for d in A.dia_offsets)
    wrapped = _any_wrapped(vals, shifts, tuple(fine_shape))
    if _deferred.items is not None:
        _deferred.items.append(wrapped)
    elif bool(wrapped):
        return None

    coffsets, contribs = _geo_contrib_table(
        tuple(int(d) for d in A.dia_offsets), shifts, tuple(axes),
        (cnx, cny, cnz))
    cvals = _geo_compute(vals, coffsets, contribs, tuple(fine_shape),
                         tuple(axes))
    return cvals, coffsets


# Device-resident twin of _geo_csr_structure, keyed additionally by the
# ambient device. The structure arrays are pure functions of the offset
# pattern — identical across every warm setup, resetup, and bench
# iteration of the same hierarchy — yet each jnp.asarray used to
# re-cross the host->device wire: at 256^3 the per-setup re-upload of
# the O(nnz) off_e/row_e/col_e/row_ids arrays is ~1 GB of
# host->device copies per warm setup. Bounded
# explicit cache (the arrays are live in the hierarchy anyway, so a
# cache hit adds no HBM beyond one generation).
_GEO_STRUCT_DEV = {}          # insertion-ordered: oldest evicts first
_GEO_STRUCT_DEV_MAX_BYTES = 2 << 30


def _geo_csr_structure_device(coffsets, coarse_shape):
    import jax as _jax
    from ...telemetry import metrics as _tm
    dev = _jax.config.jax_default_device or _jax.devices()[0]
    key = (coffsets, coarse_shape, dev)
    hit = _GEO_STRUCT_DEV.get(key)
    if hit is not None:
        _GEO_STRUCT_DEV[key] = _GEO_STRUCT_DEV.pop(key)   # LRU bump
        _tm.inc("amg.geo_struct_cache.hit")
        return hit
    _tm.inc("amg.geo_struct_cache.miss")
    out = tuple(jnp.asarray(a) for a in _geo_csr_structure(
        coffsets, coarse_shape))
    _GEO_STRUCT_DEV[key] = out
    # bound by BYTES, not entry count: one 256^3-grade entry is
    # hundreds of MB, so a count bound could pin many GB of HBM for
    # hierarchies no longer alive. Entries still referenced by a live
    # hierarchy survive eviction as arrays (only the cache slot goes).
    total = 0
    for k in reversed(list(_GEO_STRUCT_DEV)):
        total += sum(int(a.nbytes) for a in _GEO_STRUCT_DEV[k])
        if total > _GEO_STRUCT_DEV_MAX_BYTES and k != key:
            del _GEO_STRUCT_DEV[k]
    return out


def geo_assemble_dia(cvals, coffsets, coarse_shape) -> CsrMatrix:
    """Layout phase of the structured Galerkin: pack the coarse slab
    into the exact-size CSR + tile-aligned DIA storage (the coarse
    operator's solve layout, built straight from device arrays — this
    is the packing the amg.L*.layout timer wraps). The CSR structure
    arrays come from the device-resident cache above: only the NUMERIC
    slab is new work per setup."""
    cnx, cny, cnz = coarse_shape
    nc = cnx * cny * cnz
    (row_offsets, off_e, row_e, col_e, diag_idx) = \
        _geo_csr_structure_device(coffsets, (cnx, cny, cnz))
    values = cvals[off_e, row_e]
    from ...ops.pallas_spmv import LANES, dia_padded_rows
    kc = len(coffsets)
    rows_pad = dia_padded_rows(kc, nc)
    dia_vals = jnp.zeros((kc, rows_pad * LANES), cvals.dtype
                         ).at[:, :nc].set(cvals).reshape(kc, rows_pad,
                                                         LANES)
    return CsrMatrix(
        row_offsets=row_offsets,
        col_indices=col_e, values=values, diag=None,
        row_ids=row_e, diag_idx=diag_idx,
        ell_cols=None, ell_vals=None,
        dia_offsets=tuple(int(k[0]) for k in coffsets),
        dia_vals=dia_vals, num_rows=nc, num_cols=nc,
        block_dimx=1, block_dimy=1, initialized=True,
        grid_shape=tuple(coarse_shape))




# ---------------------------------------------------------------------------
# planned GEO route (the structured fast path's RapPlan analog)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("coffsets", "contribs",
                                             "fine_shape", "axes",
                                             "nc"))
def _geo_value_phase(vals, off_e, row_e, coffsets, contribs,
                     fine_shape, axes, nc: int):
    """The WHOLE structured-Galerkin numeric phase as one jitted
    program: parity-masked accumulation + pair-sums (_geo_compute's
    math), the CSR entry gather, and the tile-aligned DIA pack —
    `geo_assemble_dia` feeds straight from this output. Shared by the
    planned setup route AND the value-resetup plan (value_resetup.py),
    so the first resetup hits the setup's own compile cache."""
    from ...ops.pallas_spmv import LANES, dia_padded_rows
    cvals = _geo_compute(vals, coffsets, contribs, fine_shape, axes)
    values_c = cvals[off_e, row_e]
    kc = len(coffsets)
    rows_pad = dia_padded_rows(kc, nc)
    dia_c = jnp.zeros((kc, rows_pad * LANES), cvals.dtype
                      ).at[:, :nc].set(cvals).reshape(kc, rows_pad,
                                                      LANES)
    return values_c, dia_c


class GeoRapPlan:
    """Static recipe of one structured (GEO) Galerkin product: the
    offset decomposition, contribution table and coarse CSR/DIA
    structure, memoized once per (offsets, shapes, axes) pattern so a
    warm setup or value resetup re-derives NOTHING — the numeric phase
    is the one jitted `_geo_value_phase` program feeding the assembled
    coarse operator next to the existing device-structure cache
    (`_geo_csr_structure_device`). The plan object itself is
    device-free; the structure arrays resolve through the bounded
    device cache at use, so device changes can never serve stale
    uploads."""

    def __init__(self, dia_offsets, shifts, fine_shape, axes,
                 coarse_shape):
        self.dia_offsets = dia_offsets
        self.shifts = shifts
        self.fine_shape = fine_shape
        self.axes = axes
        self.coarse_shape = coarse_shape
        self.coffsets, self.contribs = _geo_contrib_table(
            dia_offsets, shifts, axes, coarse_shape)
        self.kc = len(self.coffsets)
        self.nc = int(np.prod(coarse_shape))

    def structure(self):
        """(row_offsets, off_e, row_e, col_e, diag_idx) device arrays
        through the bounded GEO structure cache."""
        return _geo_csr_structure_device(self.coffsets,
                                         self.coarse_shape)

    def values(self, vals2d):
        """(values_c, dia_c) from the current fine DIA slab — one
        jitted dispatch, zero symbolic work."""
        (_ro, off_e, row_e, _col_e, _diag) = self.structure()
        return _geo_value_phase(vals2d, off_e, row_e, self.coffsets,
                                self.contribs, self.fine_shape,
                                self.axes, self.nc)

    def assemble(self, values_c, dia_c) -> CsrMatrix:
        (row_offsets, _off_e, row_e, col_e, diag_idx) = self.structure()
        return CsrMatrix(
            row_offsets=row_offsets, col_indices=col_e,
            values=values_c, diag=None, row_ids=row_e,
            diag_idx=diag_idx, ell_cols=None, ell_vals=None,
            dia_offsets=tuple(int(k[0]) for k in self.coffsets),
            dia_vals=dia_c, num_rows=self.nc, num_cols=self.nc,
            block_dimx=1, block_dimy=1, initialized=True,
            grid_shape=tuple(self.coarse_shape))

    def coarse_coeffs(self, coeffs):
        """Coarse constant-stencil coefficients (kc,) straight from the
        fine ones (k,) — the matrix-free twin of `values`: when the fine
        level is a constant-coefficient stencil (ops/stencil.py), every
        in-grid coarse entry is the same static contraction of the fine
        coefficients, so the whole Galerkin numeric phase collapses to a
        (kc, k) matmul on O(k) numbers. Per contribution the weight is
        the number of fine cells in a coarse aggregate that carry it: 2
        for each paired axis whose parity mask is None (both parities
        contribute), 1 otherwise. None when a paired axis has an odd
        fine extent — the last aggregate is then a singleton along that
        axis and the coarse operator is no longer constant."""
        for a in self.axes:
            if self.fine_shape[a] % 2:
                return None
        M = getattr(self, "_coeff_mat", None)
        if M is None:
            M = np.zeros((self.kc, len(self.dia_offsets)))
            for ci, entries in enumerate(self.contribs):
                for (t, px, py, pz) in entries:
                    w = 1
                    for a, p in zip((0, 1, 2), (px, py, pz)):
                        if a in self.axes and p is None:
                            w *= 2
                    M[ci, t] += w
            self._coeff_mat = M
        return jnp.asarray(M, coeffs.dtype) @ coeffs

    def coarse_matrix(self, A: CsrMatrix):
        """Planned numeric phase with the same wrap-check discipline
        as `geo_coarse_values`: deferred inside a hierarchy build
        (batched single fetch), blocking standalone. None when the
        values violate the geometric invariant (standalone mode) —
        the caller falls back to the relabel Galerkin."""
        n = A.num_rows
        vals = A.dia_vals.reshape(len(A.dia_offsets), -1)[:, :n]
        wrapped = _any_wrapped(vals, self.shifts, self.fine_shape)
        if _deferred.items is not None:
            _deferred.items.append(wrapped)
        elif bool(wrapped):
            return None
        values_c, dia_c = self.values(vals)
        return self.assemble(values_c, dia_c)


_GEO_PLAN_CACHE = {}
_GEO_PLAN_CACHE_MAX = 256


def get_geo_plan(A: CsrMatrix, fine_shape, axes, coarse_shape):
    """Memoized GeoRapPlan for A's offset pattern, or None when the
    structured fast path does not apply (non-stencil offsets, blocks,
    a disabled fast path after a failed wrap check). Eligibility
    mirrors `geo_coarse_values`; the wrap check — which depends on the
    VALUES — stays in `GeoRapPlan.coarse_matrix`."""
    from ...telemetry import metrics as _tm
    nx, ny, nz = fine_shape
    if A.dia_offsets is None or A.grid_shape != tuple(fine_shape) \
            or A.is_block or _deferred.disable_fast:
        return None
    shifts = []
    for d in A.dia_offsets:
        g = _decompose(int(d), nx, ny, nz)
        if g is None:
            return None
        shifts.append(g)
    key = (tuple(int(d) for d in A.dia_offsets), tuple(fine_shape),
           tuple(axes), tuple(coarse_shape))
    plan = _GEO_PLAN_CACHE.get(key)
    if plan is not None:
        _tm.inc("amg.spgemm.plan_hit")
        return plan
    _tm.inc("amg.spgemm.plan_build")
    plan = GeoRapPlan(key[0], tuple(shifts), key[1], tuple(axes),
                      key[3])
    _GEO_PLAN_CACHE[key] = plan
    while len(_GEO_PLAN_CACHE) > _GEO_PLAN_CACHE_MAX:
        del _GEO_PLAN_CACHE[next(iter(_GEO_PLAN_CACHE))]
    return plan


def restrict_vector(agg, nc: int, r, block_dim: int = 1):
    """b_c = R r with piecewise-constant restriction = segment-sum over
    aggregates (restrictResidualKernel analog,
    src/aggregation/aggregation_amg_level.cu:93)."""
    if block_dim > 1:
        rb = r.reshape(-1, block_dim)
        out = jax.ops.segment_sum(rb, agg, num_segments=nc)
        return out.reshape(-1)
    return jax.ops.segment_sum(r, agg, num_segments=nc)


def prolongate_corr(agg, xc, block_dim: int = 1):
    """x += P x_c = gather by aggregate id (prolongateAndApplyCorrection
    kernel analog, aggregation_amg_level.cu:158)."""
    if block_dim > 1:
        return xc.reshape(-1, block_dim)[agg].reshape(-1)
    return xc[agg]
