"""Aggregation selectors: parallel-matching aggregation.

Analogs of src/aggregation/selectors/ (size2_selector.cu 920 LoC,
size4/size8, dummy). The reference's handshaking matching is re-expressed
as fixed-point iterations of segmented gather/argmax ops (TPU-friendly:
no atomics, deterministic by construction via smallest-index
tie-breaking):

  repeat:
    every unaggregated vertex proposes its strongest unaggregated
    neighbor (segment-max of edge weights + segment-min index tiebreak);
    mutual proposals (handshakes) become aggregates of two.

SIZE_4 / SIZE_8 run 2 / 3 matching passes, pairing *aggregates* in later
passes through the coarse graph (same machinery as the Galerkin product).
All of this is setup-time eager device code with concrete shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import registry
from ...config import Config
from ...matrix import CsrMatrix, lexsort_rc


def _edge_weights(A: CsrMatrix, formula: int = 0):
    """Symmetrized edge weights (reference weight_formula 0:
    w_ij = 0.5(|a_ij|+|a_ji|)/max(|a_ii|,|a_jj|))."""
    rows, cols, vals = A.coo()
    if A.is_block:
        # reference uses one block component (aggregation_edge_weight_
        # component); the (0,0) entry
        v = vals[:, 0, 0]
        d = A.diagonal()[:, 0, 0]
    else:
        v = vals
        d = A.diagonal()
    absd = jnp.abs(d)
    n = A.num_rows
    # canonicalize to (row, col)-lexicographic order first — uploaded
    # CSR may have unsorted columns within a row, and the positional
    # alignment below requires the canonical order on both sides
    canon = lexsort_rc(rows, cols)
    rows, cols, v = rows[canon], cols[canon], v[canon]
    # |a_ji| via the positional transpose alignment: sorting the entries
    # by (col, row) puts the k-th entry's transpose partner at position
    # k of the canonical order whenever the sparsity pattern is
    # symmetric (two int32 sorts — no emulated 64-bit keys on TPU).
    # Where the pattern is one-sided the pairing check fails and that
    # edge's weight uses the present side only.
    order = lexsort_rc(cols, rows)       # (col, row)-lexicographic
    tr = rows[order]
    tc = cols[order]
    match = (tr == cols) & (tc == rows)
    v_t = jnp.where(match, v[order], 0.0)        # signed a_ji
    if formula == 1:
        # -0.5 (a_ij/a_ii + a_ji/a_jj) — Notay coupling
        # (common_selector.h:113-119, SIGNED values)
        w = -0.5 * (v / jnp.where(d[rows] == 0, 1.0, d[rows])
                    + v_t / jnp.where(d[cols] == 0, 1.0, d[cols]))
    else:
        denom = jnp.maximum(absd[rows], absd[cols])
        w = 0.5 * (jnp.abs(v) + jnp.abs(v_t)) / \
            jnp.where(denom == 0, 1.0, denom)
    w = jnp.where(rows == cols, 0.0, w)
    return rows, cols, w


def _edge_hash(rows, cols):
    """Symmetric per-edge pseudo-random value in [0, 1): hash of the
    unordered pair. Breaks weight ties so handshaking matches a constant
    fraction per round (Luby-style) instead of forming chains; being a
    pure hash it is deterministic across runs (determinism_flag for free)."""
    a = jnp.minimum(rows, cols).astype(jnp.uint32)
    b = jnp.maximum(rows, cols).astype(jnp.uint32)
    h = a * jnp.uint32(73856093) ^ b * jnp.uint32(19349663)
    h = (h ^ (h >> 13)) * jnp.uint32(0x5BD1E995)
    return (h & jnp.uint32(0xFFFFF)).astype(jnp.float64) / float(1 << 20)


def _matching_pass(rows, cols, w, n, max_iters: int, active=None,
                   rows_sorted: bool = True):
    """One size-2 matching: returns aggregate ids (pairs + singletons).
    Unmatched vertices keep their own id; ids are NOT yet renumbered.

    Fully jittable: lax.while_loop fixed point, static shapes. `rows`
    entries equal to n are drop sentinels (padded edges); `active`
    restricts matching to a traced vertex subset (padded coarse passes).
    """
    idx = jnp.arange(n, dtype=jnp.int32)
    if active is None:
        active = jnp.ones((n,), bool)
    # tie-breaking perturbation, small relative to the weight scale
    # (elementwise no-op for zero weights, so no host-side scale check)
    w = w * (1.0 + 1e-3 * _edge_hash(rows, cols).astype(w.dtype))
    INF_NEG = jnp.asarray(-1.0, w.dtype)

    def lookup(mask):
        """Vertex-property gather tolerant of the n sentinel."""
        return jnp.concatenate([mask, jnp.zeros((1,), mask.dtype)])[
            jnp.minimum(rows, n)], \
            jnp.concatenate([mask, jnp.zeros((1,), mask.dtype)])[
            jnp.minimum(cols, n)]

    def cond(state):
        it, agg = state
        return (it < max_iters) & jnp.any((agg < 0) & active)

    def body(state):
        it, agg = state
        un = (agg < 0) & active
        un_r, un_c = lookup(un)
        valid = un_r & un_c & (w > 0)
        we = jnp.where(valid, w, INF_NEG)
        wmax = jax.ops.segment_max(we, rows, num_segments=n,
                                   indices_are_sorted=rows_sorted)
        has = wmax > 0
        is_best = valid & (we == wmax[jnp.clip(rows, 0, n - 1)])
        # smallest-index tiebreak -> determinism
        best = jax.ops.segment_min(jnp.where(is_best, cols, n), rows,
                                   num_segments=n,
                                   indices_are_sorted=rows_sorted)
        best = jnp.where(has, best, n)
        # handshake: best[best[i]] == i
        best_of_best = jnp.where(best < n, best[jnp.clip(best, 0, n - 1)],
                                 n)
        paired = (best < n) & (best_of_best == idx)
        leader = paired & (idx < best)
        agg = jnp.where(leader, idx, agg)
        agg = jnp.where(paired & ~leader, best.astype(jnp.int32), agg)
        return it + 1, agg

    _, agg = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.full((n,), -1, jnp.int32)))
    # leftovers become singletons
    return jnp.where(agg < 0, idx, agg)


def _merge_singletons(rows, cols, w, agg, n, rows_sorted: bool = True):
    """Merge singleton aggregates into their strongest neighbor aggregate
    (merge_singletons=1 semantics). Jittable, sentinel-tolerant."""
    sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), agg,
                                num_segments=n)
    is_singleton = sizes[agg] == 1
    pad = jnp.concatenate([is_singleton, jnp.zeros((1,), bool)])
    s_r = pad[jnp.minimum(rows, n)]
    s_c = pad[jnp.minimum(cols, n)]
    valid = s_r & ~s_c & (w > 0) & (cols < n)
    we = jnp.where(valid, w, -1.0)
    wmax = jax.ops.segment_max(we, rows, num_segments=n,
                               indices_are_sorted=rows_sorted)
    has = wmax > 0
    is_best = valid & (we == wmax[jnp.clip(rows, 0, n - 1)])
    best = jax.ops.segment_min(jnp.where(is_best, cols, n), rows,
                               num_segments=n,
                               indices_are_sorted=rows_sorted)
    target = jnp.where(has & is_singleton,
                       agg[jnp.clip(best, 0, n - 1)], agg)
    return jnp.where(is_singleton, target, agg).astype(jnp.int32)


def _renumber(agg, n, active=None):
    """Compact aggregate ids to 0..nc-1 (order-preserving, deterministic).
    Returns a *traced* nc; the caller materializes it once per level."""
    if active is None:
        present = jnp.zeros((n,), jnp.int32).at[agg].set(1)
    else:
        present = jnp.zeros((n,), jnp.int32).at[
            jnp.where(active, agg, n)].set(1, mode="drop")
    new_id = jnp.cumsum(present) - 1
    nc = new_id[-1] + 1
    return new_id[agg].astype(jnp.int32), nc


def _coarse_graph(rows, cols, w, agg, nc, n):
    """Collapse the weighted graph onto aggregates (for multi-pass
    matching), static-shape: returns (crows, ccols, cw) of the same
    length as the input edge list, duplicates summed onto their first
    occurrence and non-first/invalid entries turned into drop sentinels
    (row == col == n, w == 0)."""
    e = rows.shape[0]
    aggp = jnp.concatenate([agg, jnp.full((1,), n, jnp.int32)])
    cr = aggp[jnp.minimum(rows, n)]
    cc = aggp[jnp.minimum(cols, n)]
    valid = (cr != cc) & (w > 0) & (rows < n)
    # invalid entries sort last: both coordinates forced to n (int32
    # two-pass lexsort — no emulated 64-bit keys)
    cr_k = jnp.where(valid, cr, n).astype(jnp.int32)
    cc_k = jnp.where(valid, cc, n).astype(jnp.int32)
    order = lexsort_rc(cr_k, cc_k)
    cr_s, cc_s, w_s = cr_k[order], cc_k[order], w[order]
    valid_s = cr_s < n
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (cr_s[1:] != cr_s[:-1]) | (cc_s[1:] != cc_s[:-1])]) & valid_s
    seg = jnp.cumsum(first) - 1
    wsum = jax.ops.segment_sum(jnp.where(valid_s, w_s, 0.0), seg,
                               num_segments=e)
    keep = first
    crows = jnp.where(keep, cr_s, n).astype(jnp.int32)
    ccols = jnp.where(keep, cc_s, n).astype(jnp.int32)
    cw = jnp.where(keep, wsum[jnp.clip(seg, 0, e - 1)], 0.0)
    return crows, ccols, cw


class AggregationSelector:
    """Base selector: setAggregates returns (aggregates (n,), num_aggregates)
    (agg_selector.cu analog)."""

    def __init__(self, cfg: Config, scope: str):
        self.cfg = cfg
        self.scope = scope
        self.max_matching_iterations = int(
            cfg.get("max_matching_iterations", scope))
        self.merge_singletons = int(cfg.get("merge_singletons", scope))
        self.weight_formula = int(cfg.get("weight_formula", scope))
        self.deterministic = bool(cfg.get("determinism_flag", scope))

    def set_aggregates(self, A: CsrMatrix):
        raise NotImplementedError


@functools.partial(
    jax.jit,
    static_argnames=("passes", "max_iters", "merge", "formula"))
def _set_aggregates_impl(A, *, passes, max_iters, merge, formula):
    """The whole multi-pass matching as ONE compiled program (static
    shapes throughout; coarse passes run padded to the fine vertex count
    with an `active` mask). Returns (aggregates, traced nc)."""
    n = A.num_rows
    rows, cols, w = _edge_weights(A, formula)
    agg = _matching_pass(rows, cols, w, n, max_iters)
    if merge:
        agg = _merge_singletons(rows, cols, w, agg, n)
    agg, nc = _renumber(agg, n)
    # later passes pair aggregates through the collapsed (padded) graph
    for _ in range(passes - 1):
        crows, ccols, cw = _coarse_graph(rows, cols, w, agg, nc, n)
        active = jnp.arange(n) < nc
        cagg = _matching_pass(crows, ccols, cw, n, max_iters,
                              active=active, rows_sorted=False)
        if merge:
            cagg = _merge_singletons(crows, ccols, cw, cagg, n,
                                     rows_sorted=False)
        cagg, nc = _renumber(cagg, n, active=active)
        agg = cagg[agg]
    return agg, nc


class _SizeNSelector(AggregationSelector):
    passes = 1  # SIZE_2; 2 -> SIZE_4; 3 -> SIZE_8

    def set_aggregates(self, A: CsrMatrix):
        agg, nc = _set_aggregates_impl(
            A, passes=self.passes, max_iters=self.max_matching_iterations,
            merge=bool(self.merge_singletons), formula=self.weight_formula)
        return agg, int(nc)   # one host sync per level


@registry.aggregation_selectors.register("SIZE_2")
class Size2Selector(_SizeNSelector):
    passes = 1


@registry.aggregation_selectors.register("SIZE_4")
class Size4Selector(_SizeNSelector):
    passes = 2


@registry.aggregation_selectors.register("SIZE_8")
class Size8Selector(_SizeNSelector):
    passes = 3


@registry.aggregation_selectors.register("MULTI_PAIRWISE")
class MultiPairwiseSelector(_SizeNSelector):
    """Pairwise aggregation repeated `aggregation_passes` times
    (multi_pairwise.cu analog): each pass matches the weight graph of
    the previous pass's aggregates — the reference's default
    full_ghost_level=0 "weight matrix" scheme. notay_weights=1 switches
    the edge weights to Notay's signed coupling measure
    (multi_pairwise.cu:816, the weight_formula=1 formula); unmatched
    vertices merge into their strongest neighbor aggregate
    (mergeWithExistingAggregates analog = merge_singletons)."""

    def __init__(self, cfg, scope):
        super().__init__(cfg, scope)
        self.passes = int(cfg.get("aggregation_passes", scope))
        if int(cfg.get("notay_weights", scope)):
            self.weight_formula = 1


@registry.aggregation_selectors.register("DUMMY")
class DummySelector(AggregationSelector):
    """Blocks of `aggregate_size` consecutive rows (dummy selector)."""

    def set_aggregates(self, A: CsrMatrix):
        size = int(self.cfg.get("aggregate_size", self.scope))
        n = A.num_rows
        agg = (jnp.arange(n, dtype=jnp.int32) // size)
        nc = int(np.ceil(n / size))
        return agg, nc


@registry.aggregation_selectors.register("PARALLEL_GREEDY")
class ParallelGreedySelector(_SizeNSelector):
    """Greedy matching selector (parallel_greedy_selector.cu analog);
    shares the handshaking fixed-point with SIZE_2."""

    passes = 1


@registry.aggregation_selectors.register("GEO")
class GeoSelector(AggregationSelector):
    """Geometric aggregation (geo_selector.cu analog — the reference
    selector that aggregates by spatial position instead of matrix
    weights). TPU redesign: on a structured grid (CsrMatrix.grid_shape,
    set by the gallery / C-API Poisson generators) each aggregate is the
    2x2x2 block of grid points (every axis with extent >= 2 halved):

      agg(x, y, z) = linear coarse index of (x//2, y//2, z//2).

    The Galerkin product of a separable stencil operator under this
    blocking is again a stencil operator with the same diagonal
    structure, so every level of the hierarchy keeps the DIA roofline
    SpMV layout (no gathers or scatters anywhere in the cycle), and
    restriction/prolongation collapse to per-axis reshape-sums /
    broadcasts (amg/aggregation/__init__.py).
    """

    def set_aggregates(self, A: CsrMatrix):
        shape = A.grid_shape
        n = A.num_rows
        if shape is None or int(np.prod(shape)) != n:
            from ...errors import BadParametersError
            raise BadParametersError(
                "GEO selector requires a structured-grid matrix "
                "(CsrMatrix.grid_shape); use SIZE_2/PARALLEL_GREEDY for "
                "unstructured matrices")
        nx, ny, nz = shape
        axes = tuple(a for a, e in enumerate((nx, ny, nz)) if e >= 2)
        if not axes:
            self.fine_shape = shape
            self.pair_axes = None
            self.coarse_shape = shape
            return jnp.arange(n, dtype=jnp.int32), n
        cnx = (nx + 1) // 2 if 0 in axes else nx
        cny = (ny + 1) // 2 if 1 in axes else ny
        cnz = (nz + 1) // 2 if 2 in axes else nz
        # pure index arithmetic: host numpy (a single device transfer)
        # instead of ~10 eager device ops, each its own compile and
        # dispatch. Per-axis coarse indices broadcast to the grid (z
        # slowest), so the map is the only n-sized array made here:
        # n-sized temporaries in a time step's re-setup are served fast
        # or slow by glibc's mmap threshold (PERF.md, PR 43)
        cx, cy, cz = (np.arange(e, dtype=np.int32) // (2 if a in axes else 1)
                      for a, e in enumerate((nx, ny, nz)))
        agg = ((cz[:, None, None] * cny + cy[None, :, None]) * cnx
               + cx[None, None, :]).reshape(n)
        self.fine_shape = shape
        self.pair_axes = axes
        self.coarse_shape = (cnx, cny, cnz)
        # stays HOST numpy: the structured (paired) levels never touch
        # the aggregates map in the solve phase — restriction/
        # prolongation are reshape pair-sums and the Galerkin product is
        # the parity-mask fast path — so uploading it cost a pointless
        # n*4-byte transfer per level per setup (67 MB for L0 at
        # 256^3). The generic-fallback consumers
        # (coarse_a_from_aggregates, restrict_vector) accept numpy and
        # upload on first use only when that slow path actually runs.
        return agg, int(cnx * cny * cnz)


@registry.aggregation_selectors.register("SERIAL_GREEDY")
@registry.aggregation_selectors.register("SERIAL_GREEDY_BFS")
class SerialGreedySelector(AggregationSelector):
    """Serial greedy BFS aggregation (serial_greedy.cu, 319 LoC). The
    reference runs this selector on the HOST even in device builds
    (serial_greedy.cu:62-80 copies the matrix down); this is the same
    host-serial design: seed at the minimum-degree unaggregated vertex,
    grow the aggregate by the strongest edge until `aggregate_size`,
    repeat. Deterministic by construction."""

    def set_aggregates(self, A: CsrMatrix):
        import numpy as np
        size = max(int(self.cfg.get("aggregate_size", self.scope)), 2)
        n = A.num_rows
        rows_j, cols_j, w_j = _edge_weights(A, self.weight_formula)
        # _edge_weights returns (row, col)-lexicographically sorted edges
        rows = np.asarray(rows_j)
        cols = np.asarray(cols_j)
        w = np.asarray(w_j)
        starts = np.searchsorted(rows, np.arange(n + 1))
        agg = np.full(n, -1, np.int64)
        deg = np.diff(starts)
        for seed in np.argsort(deg, kind="stable"):
            if agg[seed] >= 0:
                continue
            agg[seed] = seed
            members = [seed]
            while len(members) < size:
                best_w, best_v = 0.0, -1
                for m in members:
                    lo, hi = starts[m], starts[m + 1]
                    for e in range(lo, hi):
                        v = cols[e]
                        if agg[v] < 0 and w[e] > best_w:
                            best_w, best_v = w[e], v
                if best_v < 0:
                    break
                agg[best_v] = seed
                members.append(best_v)
        agg_j, nc = _renumber(jnp.asarray(agg, jnp.int32), n)
        return agg_j, int(nc)


@registry.aggregation_selectors.register("ADAPTIVE")
class AdaptiveSelector(AggregationSelector):
    """Adaptive (smoothed-vector binning) aggregation. The reference
    registers this selector but its setAggregates raises
    NOT_IMPLEMENTED with the intended algorithm left in comments
    (adaptive.cu:142-211); this implements that documented algorithm
    for real: relax a random vector on A x = 0 (so x approaches the
    algebraically smooth error), then bin the entries into n/4 linear
    bins — vertices whose smooth-error values agree aggregate
    together."""

    def set_aggregates(self, A: CsrMatrix):
        import numpy as np
        n = A.num_rows
        ns = n * A.block_dimy          # scalar unknowns (block SpMV)
        rng = np.random.default_rng(1234 if self.deterministic else None)
        x = jnp.asarray(rng.uniform(-1.0, 1.0, ns), A.dtype)
        d = A.diagonal()
        if d.ndim == 3:
            d = jnp.diagonal(d, axis1=1, axis2=2).reshape(-1)
        dinv = jnp.where(d == 0, 0.0, 1.0 / jnp.where(d == 0, 1.0, d))

        from ...ops.spmv import spmv

        def sweep(_, x):
            return x - 0.66 * dinv * spmv(A, x)    # 15 Jacobi sweeps
        x = jax.lax.fori_loop(0, 15, sweep, x)
        if A.block_dimy > 1:
            # bin per block row by the mean smooth-error component
            x = x.reshape(n, A.block_dimy).mean(axis=1)
        lo = jnp.min(x)
        rng_w = jnp.maximum(jnp.max(x) - lo, 1e-30)
        n_bins = max(n // 4, 1)
        bins = jnp.clip(((x - lo) / rng_w * n_bins).astype(jnp.int32),
                        0, n_bins - 1)
        # stamp each bin with its first member (root id), then compact
        first = jnp.full((n_bins,), n, jnp.int32).at[bins].min(
            jnp.arange(n, dtype=jnp.int32))
        agg = first[bins]
        agg_j, nc = _renumber(agg, n)
        return agg_j, int(nc)
