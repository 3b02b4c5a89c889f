"""Windowed-ELL (SWELL) layout + SpMV tests.

The Pallas kernel itself (ops/pallas_swell.py) only runs on a real TPU;
these tests exercise the layout construction, the XLA gather form (the
semantics the kernel reproduces), the init()-time layout choice, the
interpreter form of the kernel, and coefficient replacement.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import scipy.sparse as sp

from amgx_tpu.matrix import CsrMatrix
from amgx_tpu.ops.pallas_swell import (build_swell_host, swell_spmv,
                                       swell_spmv_xla, swell_vals_host)
from amgx_tpu.ops.spmv import spmv


def _random_local(rng, n, m, width, kmax=12):
    rows = np.repeat(np.arange(n), rng.integers(1, kmax, n))
    center = (rows * m) // max(n, 1)
    cols = np.clip(center + rng.integers(-width, width, rows.shape[0]),
                   0, m - 1)
    vals = rng.standard_normal(rows.shape[0])
    S = sp.csr_matrix((vals, (rows, cols)), shape=(n, m))
    S.sum_duplicates()
    return S


def _swell_matrix(S, dtype=np.float64):
    sw = build_swell_host(S.indptr, S.indices, S.data.astype(dtype),
                          S.shape[0], S.shape[1])
    assert sw is not None
    cols4, vals4, c0row, nchunk, w128 = sw
    return CsrMatrix(
        row_offsets=jnp.asarray(S.indptr, jnp.int32),
        col_indices=jnp.asarray(S.indices, jnp.int32),
        values=jnp.asarray(S.data.astype(dtype)),
        num_rows=S.shape[0], num_cols=S.shape[1], initialized=True,
        swell_cols=jnp.asarray(cols4), swell_vals=jnp.asarray(vals4),
        swell_c0row=jnp.asarray(c0row), swell_nchunk=jnp.asarray(nchunk),
        swell_w128=w128)


@pytest.mark.parametrize("shape", [(3000, 3000), (4000, 900), (900, 4000)])
def test_swell_xla_matches_scipy(shape):
    rng = np.random.default_rng(3)
    S = _random_local(rng, *shape, width=300)
    A = _swell_matrix(S)
    x = jnp.asarray(rng.standard_normal(shape[1]))
    y = np.asarray(swell_spmv_xla(A, x))
    y_ref = S @ np.asarray(x)
    assert np.allclose(y, y_ref, atol=1e-10)


def test_swell_kernel_interpret_matches_scipy():
    rng = np.random.default_rng(5)
    S = _random_local(rng, 2100, 2100, width=200)
    A = _swell_matrix(S, np.float32)
    x = jnp.asarray(rng.standard_normal(2100), jnp.float32)
    y = np.asarray(swell_spmv(A, x, interpret=True))
    y_ref = (S @ np.asarray(x, np.float64)).astype(np.float32)
    assert np.allclose(y, y_ref, rtol=2e-5, atol=2e-5)


def test_init_host_builds_swell_for_unstructured():
    rng = np.random.default_rng(11)
    S = _random_local(rng, 3000, 3000, width=400, kmax=30)
    A = CsrMatrix.from_scipy_like(S.indptr, S.indices, S.data, 3000, 3000)
    Ai = A.init()
    # banded-but-not-DIA local matrix: the host layout choice lands on
    # SWELL (irregular offsets exceed the DIA budget)
    assert Ai.dia_offsets is None
    assert Ai.swell_cols is not None
    x = jnp.asarray(rng.standard_normal(3000))
    assert np.allclose(np.asarray(spmv(Ai, x)), S @ np.asarray(x),
                       atol=1e-10)
    # slim view keeps the layout and still SpMVs
    sl = Ai.slim_for_spmv()
    assert sl.swell_cols is not None
    assert np.allclose(np.asarray(spmv(sl, x)), S @ np.asarray(x),
                       atol=1e-10)


def test_swell_with_values_rescatter():
    rng = np.random.default_rng(13)
    S = _random_local(rng, 1500, 1500, width=150)
    A = CsrMatrix.from_scipy_like(S.indptr, S.indices, S.data,
                                  1500, 1500).init()
    assert A.swell_cols is not None
    new_vals = jnp.asarray(rng.standard_normal(S.nnz))
    A2 = A.with_values(new_vals)
    S2 = sp.csr_matrix((np.asarray(new_vals), S.indices, S.indptr),
                       shape=S.shape)
    x = jnp.asarray(rng.standard_normal(1500))
    assert np.allclose(np.asarray(spmv(A2, x)), S2 @ np.asarray(x),
                       atol=1e-10)


def test_swell_bails_on_wide_rows():
    # one dense row exceeds the slot budget -> layout not built
    n = 600
    rows = np.concatenate([np.arange(n), np.zeros(520, np.int64)])
    cols = np.concatenate([np.arange(n), np.arange(520) * 1])
    vals = np.ones(rows.shape[0])
    S = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    S.sum_duplicates()
    out = build_swell_host(S.indptr, S.indices, S.data, n, n)
    assert out is None


def test_swell_empty_rows_and_tail():
    # rows with no entries + n not a multiple of 1024
    rng = np.random.default_rng(17)
    n = 1500
    rows = np.repeat(np.arange(0, n, 3), 2)
    cols = np.clip(rows + rng.integers(-40, 40, rows.shape[0]), 0, n - 1)
    S = sp.csr_matrix((np.ones(rows.shape[0]), (rows, cols)), shape=(n, n))
    S.sum_duplicates()
    A = _swell_matrix(S)
    x = jnp.asarray(rng.standard_normal(n))
    assert np.allclose(np.asarray(swell_spmv_xla(A, x)), S @ np.asarray(x),
                       atol=1e-12)


def _banded(rng, n, bands, half, per_band=3):
    """Rows that reach a few narrow bands of columns far apart: a coarse
    operator on a 3-D grid (its own z-plane's band, its neighbours')."""
    rows = np.repeat(np.arange(n), per_band * len(bands))
    centre = np.tile(np.repeat(np.asarray(bands), per_band), n) + rows
    cols = np.clip(centre + rng.integers(-half, half, rows.shape[0]),
                   0, n - 1)
    S = sp.csr_matrix((rng.standard_normal(rows.shape[0]), (rows, cols)),
                      shape=(n, n))
    S.sum_duplicates()
    return S


def _far_bands(rng, n, k, bands=(-1500, 0, 1500), half=100):
    """A square matrix whose longest row has exactly `k` entries, the
    diagonal among them, spread over three far bands: distinct offsets
    a band, a tenth of the off-diagonals dropped at random."""
    per = -(-(k - 1) // len(bands))
    offs = np.concatenate([
        c + rng.choice(np.setdiff1d(np.arange(-half, half), [-c]), per,
                       replace=False) for c in bands])[:k - 1]
    rows = np.repeat(np.arange(n), k - 1)
    cols = rows + np.tile(offs, n)
    keep = (cols >= 0) & (cols < n) & ((rng.random(cols.shape[0]) < 0.9)
                                       | (rows == n // 2))
    S = sp.csr_matrix((rng.standard_normal(int(keep.sum())),
                       (rows[keep], cols[keep])), shape=(n, n))
    return (S + sp.diags(np.full(n, 50.0))).tocsr()


def _chunks_of_groups(S, c0row):
    """{row group: the distinct window chunks its entries fall in},
    straight from the CSR arrays."""
    from amgx_tpu.ops import pallas_swell as psw
    rows = np.repeat(np.arange(S.shape[0]), np.diff(S.indptr))
    c0 = c0row.astype(np.int64)[rows // psw.BLOCK_ROWS] * psw.LANES
    chunk = (S.indices - c0) // psw.LANES
    out = {}
    for g, c in zip((rows // psw.LANES).tolist(), chunk.tolist()):
        out.setdefault(g, set()).add(c)
    return out


def test_group_chunk_lists_are_the_chunks_each_group_touches():
    """PR 48: beside each block the layout keeps, for each of its 8 row
    groups, a count and the ascending list of the window chunks the
    group has a column in, padded to whole loop iterations by repeats
    of its last chunk; the native sweep and the numpy form agree."""
    from amgx_tpu.ops import pallas_swell as psw
    rng = np.random.default_rng(17)
    n = 40 * psw.BLOCK_ROWS + 77
    S = _banded(rng, n, bands=(-20000, 0, 20000), half=300)
    _c, _v, c0row, lists, w128 = build_swell_host(
        S.indptr, S.indices, S.data.astype(np.float32), n, n)
    nb = -(-n // psw.BLOCK_ROWS)
    L = lists.shape[2] - 1
    assert lists.shape == (nb, psw.SUBS, 1 + L) and L % psw.UNROLL == 0
    assert lists.dtype == np.int32
    rows = np.repeat(np.arange(n), np.diff(S.indptr))
    again = psw.group_chunk_lists(
        S.indices, rows, c0row.astype(np.int64) * psw.LANES, nb, w128)
    assert np.array_equal(again, lists)
    want = _chunks_of_groups(S, c0row)
    flat = lists.reshape(nb * psw.SUBS, 1 + L)
    for g in range(nb * psw.SUBS):
        count, chunks = int(flat[g, 0]), flat[g, 1:]
        assert chunks[:count].tolist() == sorted(want.get(g, ()))
        assert (chunks[count:] == (chunks[count - 1] if count else 0)).all()
    assert flat[:, 0].max() > L - psw.UNROLL      # L is the longest's
    assert psw.vreg_steps(lists, _c.shape[2]) \
        == sum(map(len, want.values())) * -(-_c.shape[2] // 8)
    # the point of it: a group touches a fraction of its block's span
    span = (_c.reshape(nb, -1).max(axis=1) // psw.LANES + 1).sum()
    assert flat[:, 0].sum() < 0.1 * psw.SUBS * span


@pytest.mark.parametrize("kpad", [4, 21, 32, 112])
@pytest.mark.parametrize("kernel", ["spmv", "smooth"])
def test_swell_kernels_follow_the_group_lists(kernel, kpad):
    """The SpMV and the fused sweep, group by group over the lists, on
    a matrix whose blocks touch three far bands: scipy's numbers, at a
    slot count under a tile, off the tiling, on it, and 14 vregs deep."""
    from amgx_tpu.ops import pallas_swell as psw
    rng = np.random.default_rng(19 + kpad)
    n = 3 * psw.BLOCK_ROWS + 300
    S = _far_bands(rng, n, kpad if kpad <= 24 else kpad - 3)
    A = _swell_matrix(S, np.float32)
    assert A.swell_vals.shape[2] == kpad
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y_ref = S @ np.asarray(x, np.float64)
    if kernel == "spmv":
        y = np.asarray(swell_spmv(A, x, interpret=True))
        assert np.allclose(y, y_ref, rtol=2e-5, atol=2e-4)
        return
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dinv = jnp.asarray(1.0 / S.diagonal(), jnp.float32)
    out = np.asarray(psw.swell_smooth_step(A, b, x, jnp.float32(0.8),
                                           dinv, interpret=True))
    want = np.asarray(x, np.float64) + 0.8 * np.asarray(dinv, np.float64) \
        * (np.asarray(b, np.float64) - y_ref)
    assert np.allclose(out, want, rtol=2e-5, atol=2e-4)


def test_swell_kernel_with_empty_groups_an_empty_block_and_a_tail():
    """Groups without an entry (count 0: the loop runs no iteration and
    the rows read 0), a block without one, and a last block of 200
    rows."""
    from amgx_tpu.ops import pallas_swell as psw
    rng = np.random.default_rng(23)
    n = 3 * psw.BLOCK_ROWS + 200
    S = _far_bands(rng, n, 9, bands=(-700, 0, 700), half=60).tolil()
    S[psw.BLOCK_ROWS:2 * psw.BLOCK_ROWS] = 0           # block 1
    for g in (1, 2, 4, 5, 6, 7):                       # of block 2
        r0 = 2 * psw.BLOCK_ROWS + g * psw.LANES
        S[r0:r0 + psw.LANES] = 0
    S = S.tocsr()
    S.eliminate_zeros()
    A = _swell_matrix(S, np.float32)
    counts = np.asarray(A.swell_nchunk)[:, :, 0]
    assert (counts[1] == 0).all() and (counts[2, [1, 2, 4, 5, 6, 7]] == 0).all()
    assert (counts[2, [0, 3]] > 0).all() and (counts[3, 2:] == 0).all()
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y = np.asarray(swell_spmv(A, x, interpret=True))
    assert y.shape == (n,)
    assert np.allclose(y, S @ np.asarray(x, np.float64),
                       rtol=2e-5, atol=2e-4)
    assert (y[psw.BLOCK_ROWS:2 * psw.BLOCK_ROWS] == 0).all()


def test_with_values_keeps_the_group_lists():
    """New coefficients on the pattern: the lists are the pattern's, so
    `with_values` hands on the same array and the kernel reads the new
    values through it."""
    rng = np.random.default_rng(29)
    n = 2 * 1024 + 100
    S = _random_local(rng, n, n, width=150)
    A = CsrMatrix.from_scipy_like(S.indptr, S.indices,
                                  S.data.astype(np.float32), n, n).init()
    assert A.swell_nchunk is not None and A.swell_nchunk.ndim == 3
    new_vals = rng.standard_normal(S.nnz).astype(np.float32)
    A2 = A.with_values(jnp.asarray(new_vals))
    assert A2.swell_nchunk is A.swell_nchunk
    assert A2.swell_vals is not A.swell_vals
    S2 = sp.csr_matrix((new_vals.astype(np.float64), S.indices, S.indptr),
                       shape=S.shape)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y = np.asarray(swell_spmv(A2, x, interpret=True))
    assert np.allclose(y, S2 @ np.asarray(x, np.float64),
                       rtol=2e-5, atol=2e-4)


def test_hierarchy_counts_the_vreg_steps_a_cycle_is_made_of():
    """Counter `swell.vreg_steps` (PR 48): kept by the hierarchy as its
    set-up ends, from the layouts' host copies, it is the brute-force
    count over the CSR arrays (over an operator's groups of 128 rows,
    the distinct 128-column chunks x the vregs of the group's slots;
    A's x its sweeps and the residual, P's and R's once), a solve
    raises it by the cycles that ran, and the benchmark's reader of it
    reads a counter the program declares."""
    import json
    import os
    import amgx_tpu as amgx
    from amgx_tpu.telemetry import metrics

    A = amgx.gallery.poisson("7pt", 20, 20, 20, dtype=np.float32).init()
    cfg = amgx.Config.from_file(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "configs", "PCG_CLASSICAL_V_JACOBI.json"))
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    amg = slv.preconditioner.amg

    def brute(M):
        if M.swell_cols is None:
            return 0
        ro, ci = np.asarray(M.row_offsets), np.asarray(M.col_indices)
        rows = np.repeat(np.arange(M.num_rows), np.diff(ro))
        pairs = {(r // 128, c // 128) for r, c in zip(rows.tolist(),
                                                      ci.tolist())}
        kmax = int(np.diff(ro).max())
        kpad = kmax if kmax <= 24 else -(-kmax // 8) * 8
        assert kpad == M.swell_cols.shape[2]
        return len(pairs) * -(-kpad // 8)

    want, layouts = 0, 0
    for k, lv in enumerate(amg.levels):
        sweeps = amg._sweeps(k, True) + amg._sweeps(k, False)
        want += brute(lv.A) * (sweeps + 1) + brute(lv.P) + brute(lv.R)
        layouts += sum(M.swell_cols is not None
                       for M in (lv.A, lv.P, lv.R))
    assert layouts >= 5 and want > 0
    assert amg.swell_vreg_steps_per_cycle() == want \
        == slv.swell_vreg_steps_per_iteration()
    before = metrics.get("swell.vreg_steps")
    res = slv.solve(jnp.ones(A.num_rows, jnp.float32))
    assert res.converged and res.iterations > 3
    assert metrics.get("swell.vreg_steps") - before \
        == res.iterations * want
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "layer_metrics",
            "kernels.swell_vreg_steps_per_solve.json")) as f:
        reader = json.load(f)
    assert reader["reduction"] == "delta_per_op" \
        and reader["counters"] == ["swell.vreg_steps"] \
        and reader["moves"] == "solve_s"
    assert set(reader["counters"]) <= set(metrics.COUNTERS)


# ---------------------------------------------------------------------------
# The row-split form as a choice (PR 51): `split_pays` reads a pattern's
# row lengths and chunk lists, and the kernels' clock `model_seconds`.
# ---------------------------------------------------------------------------


def _tailed(rng, n=4096, short=6, long=60, share=0.05, width=3000):
    """(ro, ci) of a local pattern with rows of `short` entries under a
    `share` of rows of `long`: a coarse operator's long tail."""
    lengths = np.full(n, short)
    lengths[rng.choice(n, int(share * n), replace=False)] = long
    ci = np.concatenate([
        np.sort(rng.choice(np.arange(max(0, i - width), min(n, i + width)),
                           k, replace=False))
        for i, k in enumerate(lengths)]).astype(np.int32)
    ro = np.zeros(n + 1, np.int32)
    np.cumsum(lengths, out=ro[1:])
    return ro, ci


def _even(rng, n=4096, k=9, width=3000):
    return _tailed(rng, n, short=k, long=k, share=0.0, width=width)


@pytest.mark.parametrize("listed,kpad,blocks,ns", [
    (1000, 4, 0, 9200.0), (1000, 8, 3, 9200.0 + 3 * 510.0),  # one vreg
    (1000, 16, 0, 1000 * (6.0 + 2 * 4.65)),
    (1000, 21, 1, 1000 * (6.0 + 3 * 4.65) + 510.0),
    (1000, 32, 10, 1000 * (6.0 + 4 * 4.65) + 5100.0),
    (7, 112, 2, 7 * (6.0 + 14 * 4.65) + 1020.0)])
def test_model_seconds_is_the_clock(listed, kpad, blocks, ns):
    """`a` a listed chunk + `b` a vreg-step from two vregs a tile up,
    one constant a listed chunk of a one-vreg tile, `c` a block; the
    constants are the module's (a re-fit moves them, and this test
    with them)."""
    from amgx_tpu.ops import pallas_swell as ps
    assert (ps.SWELL_ENTRY_NS, ps.SWELL_VREG_NS, ps.SWELL_ONE_VREG_NS,
            ps.SWELL_BLOCK_NS) == (6.0, 4.65, 9.2, 510.0)
    assert ps.model_seconds(listed, kpad, blocks) == pytest.approx(
        1e-9 * ns)


@pytest.mark.parametrize("native_on", [True, False])
def test_count_listed_is_the_built_layouts_count(native_on, monkeypatch):
    """The count the choice is made from is the count of the layout
    that would be built, by the native sweeps and by their numpy
    forms."""
    from amgx_tpu import native
    from amgx_tpu.ops import pallas_swell as ps
    if not native_on:
        monkeypatch.setattr(native, "swell_count_native",
                            lambda *a: None)
    ro, ci = _tailed(np.random.default_rng(3))
    n = ro.shape[0] - 1
    vals = np.ones(ci.shape[0], np.float32)
    cols4, _v, _c0, lists, w128 = build_swell_host(ro, ci, vals, n, n)
    kmax, w128_raw, listed = ps.count_listed(ro, ci, n)
    assert kmax == 60 and -(-w128_raw // 8) * 8 == w128
    assert listed == int(lists[:, :, 0].sum())


@pytest.mark.parametrize("case", ["even", "small", "tailed", "tie"])
def test_split_pays_reads_the_pattern(case, monkeypatch):
    """An even operator, and one under SPLIT_MIN_NNZ non-zeros, keeps
    the one layout WITHOUT a count; a long-tailed one (7 padded slots
    a non-zero) takes the row-split form at the K the model puts
    lowest; a saving inside the margin is not taken."""
    from amgx_tpu.ops import pallas_swell as ps
    rng = np.random.default_rng(5)
    counted = []
    real = ps.count_listed
    monkeypatch.setattr(ps, "count_listed",
                        lambda *a: counted.append(a[2]) or real(*a))
    if case == "even":
        ro, ci = _even(rng)
        assert ps.split_pays(ro, ci, ro.shape[0] - 1) is None
        assert counted == []
        return
    if case == "small":
        ro, ci = _tailed(rng, n=1024)
        assert ci.shape[0] < ps.SPLIT_MIN_NNZ
        assert ps.split_pays(ro, ci, ro.shape[0] - 1) is None
        assert counted == []
        return
    ro, ci = _tailed(rng)
    n = ro.shape[0] - 1
    if case == "tie":
        # a candidate inside the margin is left, one past it is taken
        plain = ps.model_seconds(real(ro, ci, n)[2], 64, 4)
        monkeypatch.setattr(ps, "_split_candidates",
                            lambda *a: [(0.9 * plain, 16)])
        assert ps.split_pays(ro, ci, n) is None
        assert counted == [n]
        monkeypatch.setattr(ps, "_split_candidates",
                            lambda *a: [(0.5 * plain, 16)])
        assert 0.5 * plain > ps.SPLIT_MIN_SAVING_S
        assert ps.split_pays(ro, ci, n)[0] == 16
        # and a saving past the margin but under the least one is left
        monkeypatch.setattr(ps, "SPLIT_MIN_SAVING_S", plain)
        assert ps.split_pays(ro, ci, n) is None
        return
    K, words = ps.split_pays(ro, ci, n)
    cands = ps._split_candidates(ro.astype(np.int64), ci,
                                 np.diff(ro.astype(np.int64)))
    # 8 ... 32 under the longest row of 60; 4 is left to 8 (both one
    # vreg, the smaller lists more)
    assert [k for _c, k in cands] == [8, 16, 32]
    cost = min(c for c, _k in cands)
    assert [k for c, k in cands if c == cost] == [K]
    kmax, w, listed = real(ro, ci, n)
    plain = ps.model_seconds(listed, 64, 4)
    assert cost < (1 - ps.SPLIT_MARGIN) * plain
    assert words == f"K={K} model {1e3 * cost:.3f} of {1e3 * plain:.3f} ms"


@pytest.mark.parametrize("K", [4, 8, 16, 32])
def test_split_counts_native_and_numpy_agree(K, monkeypatch):
    """One native sweep of the pattern counts what the row-split form
    at K would list; its numpy form goes through the row offsets of A'
    and `count_listed`; both are the counts of the form as built."""
    from amgx_tpu import native
    from amgx_tpu.ops import pallas_swell as ps
    ro, ci = _tailed(np.random.default_rng(17), n=3000)
    ro[5:8] = ro[5]                       # empty rows
    ro64 = ro.astype(np.int64)
    lengths = np.diff(ro64)
    ci = ci[: ro[-1]]
    n = ro.shape[0] - 1
    fast = ps._split_counts(ro64, ci, lengths, K)
    monkeypatch.setattr(native, "swell_split_count_native",
                        lambda *a: None)
    assert ps._split_counts(ro64, ci, lengths, K) == fast
    (ro_p, lay_a), (_ro_s, lay_s) = ps.split_rows_host(
        ro, ci, np.ones(ci.shape[0], np.float32), n, n, K)
    n_p, kmax_a, w128_raw, listed_a, kmax_s, listed_s = fast
    assert n_p == ro_p.shape[0] - 1 and kmax_a == np.diff(ro_p).max() == K
    assert -(-w128_raw // 8) * 8 == lay_a[4]
    assert listed_a == int(lay_a[3][:, :, 0].sum())
    assert kmax_s == lay_s[0].shape[2] == -(-60 // K)
    assert listed_s == int(lay_s[3][:, :, 0].sum())


def test_cheapest_candidate_ties_go_to_the_larger_k():
    from amgx_tpu.ops import pallas_swell as ps
    assert ps._cheapest([(2.0, 8), (1.0, 16), (1.0, 32), (3.0, 64)]) \
        == (1.0, 32)
    assert ps._cheapest([]) is None


@pytest.mark.parametrize("declined", [False, True])
def test_split_k_is_the_models_for_declined_operators_too(declined):
    """`split_rows_host` without a choice handed in (an operator the
    budget declines) takes the K the model puts lowest among the
    candidates that fit, where it took the one that pads fewest
    slots."""
    from amgx_tpu.ops import pallas_swell as ps
    rng = np.random.default_rng(7)
    ro, ci = _tailed(rng, long=400 if declined else 60, share=0.01,
                     width=600)
    n = ro.shape[0] - 1
    vals = rng.standard_normal(ci.shape[0]).astype(np.float32)
    if declined:
        assert build_swell_host(ro, ci, vals, n, n) is None
    (ro_p, lay_a), (ro_s, lay_s) = ps.split_rows_host(ro, ci, vals, n, n)
    cands = ps._split_candidates(ro.astype(np.int64), ci,
                                 np.diff(ro.astype(np.int64)))
    cost, K = ps._cheapest(cands)
    assert [k for _c, k in cands] == ([32, 64, 128] if declined
                                      else [8, 16, 32])
    assert np.diff(ro_p).max() == K and lay_a[0].shape[2] == ps._kpad(K)
    # the model's cost of the built form is the candidate's
    got = sum(ps.model_seconds(int(lay[3][:, :, 0].sum()), lay[0].shape[2],
                               lay[0].shape[0]) for lay in (lay_a, lay_s))
    assert got == pytest.approx(cost)


@pytest.mark.parametrize("interpret", [False, True])
def test_chosen_split_is_the_operator_and_the_same_on_new_values(interpret):
    """`init()` of a long-tailed operator the budget ADMITS takes the
    row-split form (counted, and said in the collected notes), its
    product is the CSR product to float32 rounding, and other values on
    the same pattern come out the same form (cell 8's road: the choice
    is the pattern's)."""
    import contextlib
    from amgx_tpu.amg.hierarchy import AMG
    from amgx_tpu.ops import pallas_spmv
    from amgx_tpu.ops import pallas_swell as ps
    from amgx_tpu.telemetry import metrics
    rng = np.random.default_rng(11)
    ro, ci = _tailed(rng)
    n = ro.shape[0] - 1
    x = rng.standard_normal(n).astype(np.float32)
    ctx = pallas_spmv.force_pallas_interpret() if interpret \
        else contextlib.nullcontext()
    before = {k: metrics.get(k) for k in (
        "amg.layout.split.chosen", "amg.layout.declined.kmax",
        "amg.layout.declined.fill", "amg.layout.declined.window")}
    forms = []
    with ctx:
        for scale in (1.0, -3.0):
            vals = (scale * rng.standard_normal(ci.shape[0])).astype(
                np.float32)
            M = sp.csr_matrix((vals.astype(np.float64), ci, ro),
                              shape=(n, n))
            with ps.collect_layout_notes() as said:
                A = CsrMatrix(row_offsets=ro, col_indices=ci, values=vals,
                              num_rows=n, num_cols=n).init()
            assert AMG._layout_of(A) == "split"
            assert said["declined"] == [] and len(said["chosen"]) == 1
            Ap, S = A.split
            forms.append((Ap.swell_cols.shape, S.swell_cols.shape,
                          np.asarray(Ap.row_offsets).tobytes(),
                          said["chosen"][0]))
            want = M @ x.astype(np.float64)
            bound = np.abs(M) @ np.abs(x)
            for view in (A, A.slim_for_spmv(),
                         A.with_values(2.0 * vals)):
                got = np.asarray(spmv(view, x), np.float64)
                k = 2.0 if view.values is not A.values \
                    and view.values.shape == vals.shape else 1.0
                assert np.max(np.abs(got - k * want) / bound) < 2e-6
    assert forms[0] == forms[1]
    grown = {k: metrics.get(k) - v for k, v in before.items()}
    assert grown == {"amg.layout.split.chosen": 2,
                     "amg.layout.declined.kmax": 0,
                     "amg.layout.declined.fill": 0,
                     "amg.layout.declined.window": 0}
    # the account the hierarchy keeps reads both parts
    acct = AMG.swell_account(A)
    assert len(acct) == 2 and acct[0][1] == Ap.swell_cols.shape[2]


def test_small_operators_keep_the_one_layout():
    """Tier-1's operators: under SPLIT_MIN_NNZ non-zeros nothing
    changes, however the rows pad."""
    rng = np.random.default_rng(13)
    ro, ci = _tailed(rng, n=1500)
    n = ro.shape[0] - 1
    A = CsrMatrix(row_offsets=ro, col_indices=ci,
                  values=np.ones(ci.shape[0], np.float32),
                  num_rows=n, num_cols=n).init()
    assert A.swell_cols is not None and A.split is None


def test_model_seconds_counter_grows_by_cycles_times_the_static_figure():
    """Counter `swell.model_s`: the hierarchy keeps the model's seconds
    a cycle as its set-up ends (over the SWELL applications of a cycle,
    both parts of a row-split operator), a solve raises the counter by
    the cycles that ran x that figure, and the benchmark's reader reads
    a counter the program declares."""
    import json
    import os
    import amgx_tpu as amgx
    from amgx_tpu.amg.hierarchy import AMG
    from amgx_tpu.ops import pallas_swell as ps
    from amgx_tpu.telemetry import metrics

    A = amgx.gallery.poisson("7pt", 20, 20, 20, dtype=np.float32).init()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = amgx.Config.from_file(os.path.join(
        root, "configs", "PCG_CLASSICAL_V_JACOBI.json"))
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    amg = slv.preconditioner.amg
    want = 0.0
    for k, lv in enumerate(amg.levels):
        apps = amg._sweeps(k, True) + amg._sweeps(k, False) + 1
        for M, times in ((lv.A, apps), (lv.P, 1), (lv.R, 1)):
            want += times * sum(ps.model_seconds(*part)
                                for part in AMG.swell_account(M))
    assert want > 0
    assert amg.swell_model_s_per_cycle() == pytest.approx(want) \
        == pytest.approx(slv.swell_model_s_per_iteration())
    before = metrics.get("swell.model_s")
    res = slv.solve(jnp.ones(A.num_rows, jnp.float32))
    assert res.converged and res.iterations > 3
    assert metrics.get("swell.model_s") - before == pytest.approx(
        res.iterations * want)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "kernels.swell_model_s_per_solve.json")) as f:
        reader = json.load(f)
    assert reader["reduction"] == "delta_per_op" \
        and reader["counters"] == ["swell.model_s"] \
        and "swell.model_s" in metrics.COUNTERS
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "kernels.swell_model_s_per_solve"]
    assert entry and entry[0]["moves"] == "solve_s" \
        and entry[0]["unit"] == "s" and len(entry[0]["workloads"]) == 3
