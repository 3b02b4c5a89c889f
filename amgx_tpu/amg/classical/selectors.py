"""CF-splitting selectors: PMIS / RS / HMIS / CR and aggressive variants.

Analogs of src/classical/selectors/ (pmis.cu 657 LoC, rs.cu, hmis.cu,
cr.cu 663 LoC, aggressive_*.cu, selector.cu).

- PMIS (parallel modified independent set) is a natural TPU fit — it is
  already a data-parallel fixed point:

    weight w_i = |S^T_i| + hash(i)               (deterministic "random";
                 S^T_i = the points that strongly DEPEND on i)
    start:  a point that depends on nothing, or that nothing depends
            on, is FINE (the first kind keeps an empty row of P: the
            reference's STRONG_FINE)
    repeat: undecided i with w_i greater than every undecided neighbor's
            weight over S | S^T becomes COARSE; an undecided point that
            DEPENDS on a new COARSE point (row i of S) becomes FINE.

  The two directions of a strength edge are kept apart as hypre's
  par_coarsen.c does (the reference's pmis.cu is modelled on it; its
  source is not in this tree): only a point that depends on a C point
  has one to interpolate from. On a symmetric mask (a constant stencil)
  the direction changes nothing; on SPE10's operator the symmetrized
  form left F points with no C point within two steps and FGMRES took
  138 iterations where it takes 9 (PR 47).
- RS is the classical serial first pass. The reference itself refuses to
  run it on the GPU ("it's a sequential algorithm", rs.cu:269-277) and
  runs it on the HOST; here it is a native C++ bucket-queue component
  (amgx_tpu/native/src/rs.cpp) with a Python fallback.
- HMIS = host RS pass, then PMIS initialized from that result — exactly
  the reference composition (hmis.cu:55-82). On one device the PMIS pass
  is a no-op fixup (every point is already assigned); under domain
  decomposition it resolves boundary inconsistencies.
- CR (compatible relaxation): smooth the homogeneous system on the
  current F-set; slow-to-decay points are coarse-grid candidates, and an
  independent subset joins C each round (cr.cu structure: presmooth
  fine-error + update cf_map from smoother colors).
- AGGRESSIVE_* run the PMIS fixed point on the two-hop strength graph
  S@S, giving the reference's aggressive-coarsening grid sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import registry
from ...matrix import CsrMatrix

FINE, COARSE, UNDECIDED = 0, 1, -1


def _hash01(n):
    i = jnp.arange(n, dtype=jnp.uint32)
    h = i * jnp.uint32(2654435761)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    return (h & jnp.uint32(0xFFFFF)).astype(jnp.float64) / float(1 << 20)


def _symmetrize(rows, cols, mask, n):
    """Edges of S | S^T as (rows2, cols2) with duplicates kept (harmless
    for max/any reductions)."""
    r = jnp.concatenate([rows[mask], cols[mask]])
    c = jnp.concatenate([cols[mask], rows[mask]])
    order = jnp.argsort(r, stable=True)
    return r[order], c[order]


def _no_dependency(A: CsrMatrix, strong):
    """(n,) bool: the rows without a strong entry (numpy)."""
    ro = np.asarray(A.row_offsets)
    st = np.asarray(strong, bool)
    has = np.zeros(A.num_rows, bool)
    has[np.repeat(np.arange(A.num_rows), np.diff(ro))[st]] = True
    return ~has


def pmis_split(A: CsrMatrix, strong, max_iters: int = 30, init=None):
    """Returns cf_map (n,) in {FINE, COARSE}. `init` (optional) seeds the
    fixed point with already-decided assignments (cf_map_init=1 analog,
    pmis.cu:508): entries in {FINE, COARSE} are kept, UNDECIDED entries
    are resolved by the PMIS sweeps. Without it, the rows that depend
    on nothing start FINE."""
    n = A.num_rows
    if init is None:
        init = np.where(_no_dependency(A, strong), FINE,
                        UNDECIDED).astype(np.int32)
    from ...ops.spgemm import _on_host
    if _on_host(A):
        # host-setup path: the synchronous fixed point as a native C++
        # sweep (bit-exact: same weights, same round structure)
        from ...native import pmis_native
        cf = pmis_native(
            n, np.asarray(A.row_offsets), np.asarray(A.col_indices),
            np.asarray(strong, np.uint8), np.asarray(init, np.int32),
            max_iters)
        if cf is not None:
            # numpy on purpose: the host hierarchy build stays off jax
            # CPU arrays (jnp consumers accept numpy transparently)
            return cf
    rows, cols, _ = A.coo()
    strong = jnp.asarray(strong, bool) & (cols >= 0) & (cols < n)
    sr, sc = _symmetrize(rows, cols, strong, n)
    dr, dc = rows[strong], cols[strong]      # dr DEPENDS on dc
    indeg = jnp.zeros((n,), jnp.float64).at[dc].add(1.0)
    w = indeg + _hash01(n)
    state = jnp.asarray(init, jnp.int32)
    # nothing depends on it: it is nobody's C point
    state = jnp.where((state == UNDECIDED) & (indeg == 0), FINE, state)

    for _ in range(max_iters):
        und = state == UNDECIDED
        if not bool(jnp.any(und)):
            break
        active_edge = und[sr] & und[sc]
        nbr_max = jax.ops.segment_max(
            jnp.where(active_edge, w[sc], -jnp.inf), sr, num_segments=n,
            indices_are_sorted=True)
        new_c = und & (w > nbr_max)
        state = jnp.where(new_c, COARSE, state)
        # undecided points that depend on any C point become FINE
        c_dep = jnp.zeros((n,), bool).at[dr].max(state[dc] == COARSE)
        state = jnp.where((state == UNDECIDED) & c_dep, FINE, state)
    state = jnp.where(state == UNDECIDED, FINE, state)
    return state.astype(jnp.int32)


def rs_split_python(n, row_offsets, col_indices, strong):
    """Pure-Python RS first pass (fallback when the native lib is
    unavailable). Bit-identical port of native/src/rs.cpp — same bucket
    queue with the same LIFO tie-breaking, so the CF splitting (and
    every hierarchy built on it) is identical with or without the native
    library."""
    ro = np.asarray(row_offsets)
    ci = np.asarray(col_indices)
    st = np.asarray(strong, bool)
    row_ids = np.repeat(np.arange(n), np.diff(ro))
    mask = st & (ci < n) & (ci != row_ids)
    # S (per-row) and S^T (per-col) adjacency, numpy-built
    s_r, s_c = row_ids[mask], ci[mask]
    order = np.argsort(s_c, kind="stable")
    st_c, st_r = s_c[order], s_r[order]
    st_off = np.zeros(n + 1, np.int64)
    np.add.at(st_off, st_c + 1, 1)
    np.cumsum(st_off, out=st_off)
    s_off = np.zeros(n + 1, np.int64)
    np.add.at(s_off, s_r + 1, 1)
    np.cumsum(s_off, out=s_off)

    # bucket queue: head per weight + doubly-linked node lists (rs.cpp);
    # weights are bounded by 2*|S^T_i| (initial in-degree + one bump per
    # in-edge), hence the 2n+2 sizing
    head = np.full(2 * n + 2, -1, np.int64)
    prev = np.full(n, -1, np.int64)
    nxt = np.full(n, -1, np.int64)
    weight = np.zeros(n, np.int64)
    maxw = 0

    def push(i, w):
        nonlocal maxw
        weight[i] = w
        prev[i] = -1
        nxt[i] = head[w]
        if head[w] >= 0:
            prev[head[w]] = i
        head[w] = i
        if w > maxw:
            maxw = w

    def remove(i):
        w = weight[i]
        if prev[i] >= 0:
            nxt[prev[i]] = nxt[i]
        else:
            head[w] = nxt[i]
        if nxt[i] >= 0:
            prev[nxt[i]] = prev[i]
        prev[i] = nxt[i] = -1

    lam = np.diff(st_off).astype(np.int64)
    out_deg = np.diff(s_off)
    state = np.full(n, UNDECIDED, np.int32)
    in_q = lam > 0
    # lam==0: FINE, except fully strong-isolated points (no in- or
    # out-edges) which cannot interpolate -> COARSE (pmis convention)
    state[~in_q] = np.where(out_deg[~in_q] == 0, COARSE, FINE)
    # push in ascending node order, exactly like the C++ loop
    for i in range(n):
        if in_q[i]:
            push(i, lam[i])
    while True:
        while maxw >= 0 and head[maxw] < 0:
            maxw -= 1
        if maxw < 0:
            break
        i = head[maxw]
        remove(i)
        if state[i] != UNDECIDED:
            continue
        state[i] = COARSE
        for t in range(st_off[i], st_off[i + 1]):
            j = st_r[t]
            if state[j] != UNDECIDED:
                continue
            state[j] = FINE
            remove(j)
            for u in range(s_off[j], s_off[j + 1]):
                k = s_c[u]
                if state[k] == UNDECIDED:
                    remove(k)
                    push(k, weight[k] + 1)
    return np.where(state == COARSE, 1, 0).astype(np.int32)


def rs_split(A: CsrMatrix, strong):
    """RS first-pass coarsening: native C++ bucket queue, Python
    fallback."""
    from ...native import rs_coarsen_native, warn_python_fallback
    n = A.num_rows
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    st = np.asarray(strong, np.uint8)
    cf = rs_coarsen_native(n, ro, ci, st)
    if cf is None:
        warn_python_fallback("RS coarsening", n)
        cf = rs_split_python(n, ro, ci, st)
    return jnp.asarray(cf, jnp.int32)


def _hash_key(n):
    """The PMIS integer hash (same mixing as _hash01, kept as int64):
    a deterministic per-vertex tie-break that is bit-identical on
    every backend (pure uint32 arithmetic, no float rounding)."""
    i = jnp.arange(n, dtype=jnp.uint32)
    h = i * jnp.uint32(2654435761)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    return (h & jnp.uint32(0xFFFFF)).astype(jnp.int64)


def rs_sweep(A: CsrMatrix, strong, max_rounds: int = 200):
    """Device-parallel RS first pass: a PMIS-style independent-set
    FIXPOINT with the RS weight as priority (SParSH-AMG's CPU-GPU
    split taken all the way onto the device, arXiv:2007.00056; CLJP
    family). Eager jnp over concrete shapes, so it runs inside the
    setup_backend=device pipeline with zero host-serial work.

    Per round, over the current UNDECIDED set:

      key_i  = lambda_i * 2^20 + hash(i)         (int64, lambda_i =
               the LIVE RS weight: S^T in-degree plus one bump per
               strong neighbor already turned FINE — the bucket
               queue's exact weight function, updated per round
               instead of per pop)
      C:       undecided i whose key beats every undecided neighbor
               in S | S^T (the serial pop's conflict set: a selection
               can only FINE its S^T-dependents, so strict local
               maxima are simultaneously safe)
      F:       undecided j with a new COARSE point in S(j)
      bump:    +1 per (newly FINE j -> undecided k in S(j)) edge

    Initialization matches the queue: lambda=0 vertices start FINE
    (COARSE when fully isolated) and never bump their neighbors.

    NOT bit-equivalent to the serial bucket queue: the queue's
    dynamic LIFO tie-break makes its pop order inherently serial (a
    weight bump re-queues a vertex at its bucket's head), so the host
    path (`selector_device_sweep=0`, or setup_backend=host with
    `auto`) keeps the queue as the reference implementation and
    quality oracle, while this sweep is bit-deterministic ACROSS
    BACKENDS — host-jnp and device runs produce identical splits
    (integer arithmetic only), which is what the device-setup parity
    contract checks. Leftover UNDECIDED vertices after `max_rounds`
    (hash-collision stalemates, < 2^-20 per adjacent pair) turn FINE
    exactly like the PMIS fixpoint's tail."""
    n = A.num_rows
    rows, cols, _ = A.coo()
    rows = jnp.asarray(rows)
    cols = jnp.asarray(cols)
    st = jnp.asarray(strong, bool)
    mask = st & (cols < n) & (cols != rows)
    er = rows[mask]          # directed strength edges: ec in S(er)
    ec = cols[mask]
    one = jnp.ones(er.shape, jnp.int64)
    lam = jnp.zeros((n,), jnp.int64).at[ec].add(one)   # S^T in-degree
    out_deg = jnp.zeros((n,), jnp.int64).at[er].add(one)
    idx = jnp.arange(n, dtype=jnp.int64)
    key_base = _hash_key(n)
    state = jnp.full((n,), UNDECIDED, jnp.int32)
    # lambda == 0: never queued — FINE, except fully isolated points
    # (no edges either way) which cannot interpolate -> COARSE
    no_in = lam == 0
    state = jnp.where(no_in & (out_deg == 0), COARSE,
                      jnp.where(no_in, FINE, state))
    for _ in range(max_rounds):
        und = state == UNDECIDED
        if not bool(jnp.any(und)):
            break
        key = lam * jnp.int64(1 << 20) + key_base
        live = und[er] & und[ec]
        km = jnp.where(live, key[ec], jnp.int64(-1))
        nbr = jnp.full((n,), jnp.int64(-1)).at[er].max(km)
        nbr = nbr.at[ec].max(jnp.where(live, key[er], jnp.int64(-1)))
        new_c = und & (key > nbr)
        state = jnp.where(new_c, COARSE, state)
        # undecided j strongly depending on a new C point -> FINE
        f_hit = jnp.zeros((n,), bool).at[er].max(new_c[ec])
        newly_f = und & ~new_c & f_hit
        state = jnp.where(newly_f, FINE, state)
        # RS weight update: each newly-FINE j bumps its still-
        # undecided strong neighbors k in S(j) by one per edge
        und2 = state == UNDECIDED
        lam = lam.at[ec].add(jnp.where(newly_f[er] & und2[ec],
                                       jnp.int64(1), jnp.int64(0)))
    return jnp.where(state == COARSE, 1, 0).astype(jnp.int32)


def _rs_first_pass(cfg, scope, A: CsrMatrix, strong):
    """RS/HMIS first-pass dispatch: the host bucket queue (the
    reference), or the device-parallel sweep. `selector_device_sweep`
    auto = sweep exactly when the setup pipeline is device-forced
    (setup_backend=device, PR-3 threadlocal), 1 = always sweep (the
    cross-backend parity shape), 0 = always the bucket queue (the
    escape hatch that restores bit-identical splits vs host builds)."""
    mode = str(cfg.get("selector_device_sweep", scope))
    from ...matrix import device_setup_forced
    if mode == "1" or (mode == "auto" and device_setup_forced()):
        from ...profiling import trace_region
        from ...telemetry import metrics as _tm
        _tm.inc("amg.selector.device_sweep")
        with trace_region("selector.device_sweep"):
            return rs_sweep(A, strong)
    return rs_split(A, strong)


def _two_hop_strength(A: CsrMatrix, strong):
    """(S2, mask): the graph of two-step dependence, a CsrMatrix whose
    entries under `mask` are the pairs (i, j != i) with a path i -> k
    -> j over `strong`. On the host-setup path the native stamp sweep
    gives the pattern alone; elsewhere S@S through the sort-based
    expand machinery, path counts and all."""
    from ...ops.spgemm import _on_host, csr_multiply
    if _on_host(A):
        from ...native import two_step_pattern_native
        out = two_step_pattern_native(
            A.num_rows, np.asarray(A.row_offsets),
            np.asarray(A.col_indices), np.asarray(strong, np.uint8))
        if out is not None and out[0][-1] < 2 ** 31:
            ptr, col = out
            mask = np.ones(col.shape[0], np.uint8)
            # the mask stands in for values: a pattern has none, and
            # numpy values are what keeps the split on the host's road
            S2 = CsrMatrix(row_offsets=ptr.astype(np.int32),
                           col_indices=col, values=mask,
                           num_rows=A.num_rows, num_cols=A.num_cols)
            return S2, mask
    sv = jnp.where(strong, 1.0, 0.0)
    S = CsrMatrix(row_offsets=A.row_offsets, col_indices=A.col_indices,
                  values=sv, num_rows=A.num_rows, num_cols=A.num_cols)
    S2 = csr_multiply(S, S)
    r2, c2, v2 = S2.coo()
    return S2, (v2 > 0) & (r2 != c2)


class ClassicalSelector:
    def __init__(self, cfg, scope):
        self.cfg = cfg
        self.scope = scope

    def mark_coarse_fine_points(self, A: CsrMatrix, strong):
        raise NotImplementedError


@registry.classical_selectors.register("PMIS")
class PMISSelector(ClassicalSelector):
    def mark_coarse_fine_points(self, A, strong):
        return pmis_split(A, strong)


@registry.classical_selectors.register("RS")
class RSSelector(ClassicalSelector):
    """Ruge-Stueben first pass: the serial bucket queue (rs.cu host
    path) or, under the device setup pipeline, the device-parallel
    independent-set sweep (`selector_device_sweep`)."""

    def mark_coarse_fine_points(self, A, strong):
        return _rs_first_pass(self.cfg, self.scope, A, strong)


@registry.classical_selectors.register("HMIS")
class HMISSelector(ClassicalSelector):
    """RS first pass, then PMIS seeded with the RS result
    (hmis.cu:55-82). Single-device the PMIS pass keeps the first
    pass's assignment; it exists to resolve partition-boundary
    points. The first pass routes like RSSelector: the host bucket
    queue by default, the device-parallel sweep under the device
    setup pipeline (`selector_device_sweep`)."""

    def mark_coarse_fine_points(self, A, strong):
        cf = _rs_first_pass(self.cfg, self.scope, A, strong)
        return pmis_split(A, strong, init=cf)


@registry.classical_selectors.register("AGGRESSIVE_PMIS")
@registry.classical_selectors.register("AGGRESSIVE_HMIS")
class AggressivePMISSelector(ClassicalSelector):
    """PMIS on the two-hop strength graph -> much smaller coarse grids
    (aggressive_pmis.cu behavior)."""

    def mark_coarse_fine_points(self, A, strong):
        S2, strong2 = _two_hop_strength(A, strong)
        # FINE from the start is a row that depends on nothing in ONE
        # step; a row whose neighbours depend on nothing has an empty
        # row of S@S and still has to find a C point or become one
        init = np.where(_no_dependency(A, strong), FINE,
                        UNDECIDED).astype(np.int32)
        return pmis_split(S2, strong2, init=init)


@registry.classical_selectors.register("CR")
class CRSelector(ClassicalSelector):
    """Compatible-relaxation selector (cr.cu). Starting from an empty
    (or tiny) C-set, repeatedly:

      1. relax the homogeneous system A e = 0 on the F-points (weighted
         Jacobi sweeps with e zeroed at C — the reference presmooths with
         MULTICOLOR_GS, cr.cu:366-435; Jacobi keeps it one XLA program);
      2. the normalized surviving error mu_i = |e_i| / max|e| measures
         how badly relaxation alone handles point i;
      3. slow points (mu_i >= theta) above the global convergence target
         join C as an independent set weighted by mu (the reference uses
         smoother colors for independence, cr.cu:123-144).

    Stops when the CR convergence factor is below 0.7 or the candidate
    set is empty.
    """

    NU = 4              # relaxation sweeps per round
    THETA = 0.5         # candidate threshold on normalized error
    MAX_ROUNDS = 10
    TARGET_RATE = 0.7

    def mark_coarse_fine_points(self, A, strong):
        n = A.num_rows
        rows, cols, _ = A.coo()
        sr, sc = _symmetrize(rows, cols, strong, n)
        diag = A.diagonal()
        dinv = jnp.where(diag != 0, 1.0 / jnp.where(diag == 0, 1.0, diag),
                         0.0)
        from ...ops.spmv import spmv
        state = jnp.full((n,), UNDECIDED, jnp.int32)
        has_nbr = jnp.zeros((n,), bool).at[sr].set(True)
        state = jnp.where(~has_nbr, COARSE, state)  # isolated rows
        rng = np.random.default_rng(5)
        e0 = jnp.asarray(rng.standard_normal(n), A.dtype)

        for _ in range(self.MAX_ROUNDS):
            is_c = state == COARSE
            e = jnp.where(is_c, 0.0, e0)
            e = e / jnp.maximum(jnp.linalg.norm(e), 1e-30)
            norm_prev = jnp.linalg.norm(e)
            for _ in range(self.NU):
                norm_prev = jnp.linalg.norm(e)
                e = e - 0.666 * dinv * spmv(A, e)
                e = jnp.where(is_c, 0.0, e)
            # asymptotic measure: ratio of the LAST sweep (early sweeps
            # only show the fast high-frequency decay)
            rate = jnp.linalg.norm(e) / jnp.maximum(norm_prev, 1e-30)
            if float(rate) < self.TARGET_RATE:
                break
            mu = jnp.abs(e) / jnp.maximum(jnp.max(jnp.abs(e)), 1e-30)
            cand = (state == UNDECIDED) & (mu >= self.THETA)
            if not bool(jnp.any(cand)):
                break
            # independent set among candidates, weighted by mu
            w = mu + _hash01(n) * 1e-6
            active = cand[sr] & cand[sc]
            nbr_max = jax.ops.segment_max(
                jnp.where(active, w[sc], -jnp.inf), sr, num_segments=n,
                indices_are_sorted=True)
            new_c = cand & (w > nbr_max)
            state = jnp.where(new_c, COARSE, state)
        # coverage completion: every F point needs at least one strong C
        # neighbor or classical interpolation has nothing to work with —
        # promote independent sets of uncovered points until covered
        deg = jnp.zeros((n,), jnp.float64).at[sr].add(1.0)
        wfix = deg + _hash01(n)
        for _ in range(30):
            is_c = state == COARSE
            covered = jnp.zeros((n,), bool).at[sr].max(is_c[sc])
            unc = ~is_c & has_nbr & ~covered
            if not bool(jnp.any(unc)):
                break
            active = unc[sr] & unc[sc]
            nbr_max = jax.ops.segment_max(
                jnp.where(active, wfix[sc], -jnp.inf), sr, num_segments=n,
                indices_are_sorted=True)
            state = jnp.where(unc & (wfix > nbr_max), COARSE, state)
        # everything not selected is FINE
        return jnp.where(state == COARSE, COARSE, FINE).astype(jnp.int32)


@registry.classical_selectors.register("DUMMY_CLASSICAL")
class DummyClassicalSelector(ClassicalSelector):
    """Every other point coarse (dummy_selector.cu analog)."""

    def mark_coarse_fine_points(self, A, strong):
        n = A.num_rows
        return (jnp.arange(n, dtype=jnp.int32) % 2 == 0).astype(jnp.int32)
