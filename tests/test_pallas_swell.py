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


def test_slab_mask_marks_the_slabs_a_block_touches():
    """PR 47: beside each block's chunk count the layout keeps a bit an
    8-chunk slab of its window, set where the block has a column; the
    native sweep and the numpy form agree, and the mask is exactly the
    set of slabs the block's entries fall in."""
    from amgx_tpu.ops import pallas_swell as psw
    rng = np.random.default_rng(17)
    n = 90 * psw.BLOCK_ROWS + 77
    S = _banded(rng, n, bands=(-40000, 0, 40000), half=300)
    sw = build_swell_host(S.indptr, S.indices, S.data.astype(np.float32),
                          n, n)
    _c, _v, c0row, nchunk, w128 = sw
    nb = -(-n // psw.BLOCK_ROWS)
    assert nchunk.shape == (nb, 1 + psw.mask_words(w128))
    rows = np.repeat(np.arange(n), np.diff(S.indptr))
    block = rows // psw.BLOCK_ROWS
    c0 = c0row.astype(np.int64) * psw.LANES
    again = psw.with_slab_mask(nchunk[:, 0], S.indices, block, c0, w128)
    assert np.array_equal(again, nchunk)
    slab = (S.indices - c0[block]) // (8 * psw.LANES)
    mask = nchunk[:, 1:].view(np.uint32)
    touched = 0
    for b in range(nb):
        want = set(np.unique(slab[block == b]).tolist())
        got = {s for s in range(32 * mask.shape[1])
               if (mask[b, s // 32] >> np.uint32(s % 32)) & np.uint32(1)}
        assert got == want
        touched += len(got)
    # the point of it: most of the spans' slabs are empty
    assert touched < 0.3 * int((-(-nchunk[:, 0] // 8)).sum())


@pytest.mark.parametrize("masked", [True, False])
def test_swell_kernels_skip_empty_slabs_and_agree(masked):
    """The SpMV and the fused sweep over a layout whose blocks touch
    three far bands, with the slab mask and with the chunk count alone
    (a layout from before the mask): the same numbers as scipy."""
    from amgx_tpu.ops import pallas_swell as psw
    rng = np.random.default_rng(19)
    n = 24 * psw.BLOCK_ROWS + 300
    S = _banded(rng, n, bands=(-9000, 0, 9000), half=200)
    S = (S + sp.diags(np.full(n, 50.0))).tocsr()
    A = _swell_matrix(S, np.float32)
    if not masked:
        import dataclasses
        A = dataclasses.replace(A, swell_nchunk=A.swell_nchunk[:, 0])
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y = np.asarray(swell_spmv(A, x, interpret=True))
    y_ref = S @ np.asarray(x, np.float64)
    assert np.allclose(y, y_ref, rtol=2e-5, atol=2e-4)
    dinv = jnp.asarray(1.0 / S.diagonal(), jnp.float32)
    out = np.asarray(psw.swell_smooth_step(A, b, x, jnp.float32(0.8),
                                           dinv, interpret=True))
    want = np.asarray(x, np.float64) + 0.8 * np.asarray(dinv, np.float64) \
        * (np.asarray(b, np.float64) - y_ref)
    assert np.allclose(out, want, rtol=2e-5, atol=2e-4)
