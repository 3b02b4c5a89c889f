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
