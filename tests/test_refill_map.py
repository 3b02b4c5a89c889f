"""The DIA refill map (ISSUE 31): `CsrMatrix.with_values` keeps, beside
the structure arrays and outside the pytree, where every CSR entry sits
in the DIA slab, and a coefficient replacement applies it in one pass.

- the refilled slab is, bit for bit, what `init()` from scratch builds
  on the new values, for every pattern, dtype and kind of values;
- the map is built once per pattern, reused by every later refill, not
  found by another pattern of the same size, and dies with the
  structure arrays it was built from;
- traced and complex values, and structure the host cannot serve, keep
  the `_build_dia_vals` route."""
import dataclasses
import gc
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import matrix
from amgx_tpu.matrix import CsrMatrix, forced_device_setup
from amgx_tpu.telemetry import metrics

from benchmark.operator_host import poisson_csr

amgx.initialize()

MAP_COUNTERS = ("matrix.refill_map.build", "matrix.refill_map.reuse")


def banded_csr(n=70, offsets=(-3, 0, 2), no_diag_every=4):
    """A banded pattern whose every `no_diag_every`-th row lacks its
    diagonal entry."""
    rows, cols = [], []
    for i in range(n):
        for o in offsets:
            if 0 <= i + o < n and not (o == 0 and i % no_diag_every == 0):
                rows.append(i)
                cols.append(i + o)
    rows, cols = np.asarray(rows), np.asarray(cols, np.int32)
    ro = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=n), out=ro[1:])
    return ro, cols, np.random.default_rng(5).standard_normal(cols.shape[0])


def duplicates_csr():
    """7-pt Poisson whose every row repeats its first entry (the padded-
    duplicate CSR init() speaks of, here with values of its own)."""
    ro, ci, vals = poisson_csr("7pt", (6, 5, 4))
    n = ro.shape[0] - 1
    first = ro[:-1]
    ci2 = np.insert(ci, first, ci[first])
    vals2 = np.insert(vals, first, 0.25 * vals[first])
    return (ro + np.arange(n + 1)).astype(np.int32), ci2, vals2


PATTERNS = {
    "poisson7": lambda: poisson_csr("7pt", (9, 7, 5)),
    "poisson27": lambda: poisson_csr("27pt", (6, 5, 4)),
    "banded_no_diag": banded_csr,
    "duplicates": duplicates_csr,
}


def build(pattern, dtype=np.float64):
    ro, ci, vals = PATTERNS[pattern]()
    n = ro.shape[0] - 1
    A = CsrMatrix.from_scipy_like(ro, ci, vals.astype(dtype), n, n).init()
    assert A.dia_offsets is not None, "the pattern must take the DIA layout"
    return A, (ro, ci, vals.astype(dtype), n)


def new_values(vals, seed=31):
    rng = np.random.default_rng(seed)
    return (vals * (1.0 + rng.random(vals.shape[0]))).astype(vals.dtype)


def from_scratch(csr, vals):
    ro, ci, _, n = csr
    return CsrMatrix.from_scipy_like(ro, ci, vals, n, n).init()


def assert_same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def growth(before, names=MAP_COUNTERS):
    after = metrics.snapshot()
    return {n: after.get(n, 0) - before.get(n, 0) for n in names}


@pytest.mark.parametrize("kind", ["numpy", "jax"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_refill_equals_init_from_scratch_bit_for_bit(pattern, dtype, kind):
    A, csr = build(pattern, dtype)
    vals = new_values(csr[2])
    B = A.with_values(vals if kind == "numpy" else jnp.asarray(vals))
    want = from_scratch(csr, vals)
    assert_same_bits(B.dia_vals, want.dia_vals)
    # values and slab are both on the device, in the values' dtype
    assert isinstance(B.values, jax.Array) and isinstance(B.dia_vals,
                                                          jax.Array)
    assert_same_bits(B.values, vals)
    assert B.dia_offsets == want.dia_offsets
    # and the operator is the new one
    x = np.random.default_rng(7).standard_normal(A.num_rows).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(amgx.ops.spmv(B, jnp.asarray(x))),
        np.asarray(B.to_dense()) @ x, rtol=2e-5 if dtype == np.float32
        else 1e-12, atol=1e-5 if dtype == np.float32 else 1e-12)


@pytest.mark.parametrize("pieces", [2, 3, 8])
@pytest.mark.parametrize("pattern", ["poisson7", "poisson27",
                                     "banded_no_diag"])
def test_take_cut_into_pieces_is_the_same_slab(pattern, pieces, monkeypatch):
    """A large slab is taken in pieces by several threads (at 256^3 the
    page faults of the fresh slab are most of the pass): same bits."""
    A, csr = build(pattern)
    size = int(np.prod(A.dia_vals.shape))
    monkeypatch.setattr(matrix._RefillMap, "PIECE", size // pieces)
    monkeypatch.setattr(matrix.os, "cpu_count", lambda: 8)
    calls = []
    real_take = np.take

    def counted_take(*a, **k):
        if k.get("out") is not None:
            calls.append(k["out"].shape[0])
        return real_take(*a, **k)

    vals = new_values(csr[2])
    with monkeypatch.context() as m:
        m.setattr(np, "take", counted_take)
        B = A.with_values(vals)
    assert len(calls) == pieces and sum(calls) == size
    assert_same_bits(B.dia_vals, from_scratch(csr, vals).dia_vals)


def test_duplicate_entries_are_summed():
    A, csr = build("duplicates")
    vals = new_values(csr[2])
    B = A.with_values(vals)
    key = (id(A.col_indices), id(A.row_offsets), A.dia_offsets)
    assert matrix._REFILL_MAPS[key].duplicates
    # row 0 holds its first entry twice: the slot carries the sum
    off = int(csr[1][0]) - 0
    slot = np.asarray(B.dia_vals)[B.dia_offsets.index(off)].reshape(-1)[0]
    assert slot == vals[0] + vals[1]


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_structure_the_host_cannot_serve_takes_build_dia_vals(pattern):
    """Without host mirrors of the structure (here: the forced device
    setup) no map is built: the slab is rebuilt on the device, as it
    was, with the same result."""
    A, csr = build(pattern)
    vals = new_values(csr[2])
    before = metrics.snapshot()
    with forced_device_setup():
        B = A.with_values(vals)
    assert growth(before) == {"matrix.refill_map.build": 0,
                              "matrix.refill_map.reuse": 0}
    want = from_scratch(csr, vals).dia_vals
    if pattern == "duplicates":      # XLA's order of the sum, not numpy's
        np.testing.assert_allclose(np.asarray(B.dia_vals),
                                   np.asarray(want), rtol=1e-15)
    else:
        assert_same_bits(B.dia_vals, want)
    # the kept map of the same pattern: its inverse is exact
    C = A.with_values(vals)
    kept = matrix._REFILL_MAPS[
        (id(A.col_indices), id(A.row_offsets), A.dia_offsets)]
    assert kept.duplicates == (pattern == "duplicates")
    assert kept.index.dtype == np.intp
    if not kept.duplicates:
        taken = np.setdiff1d(np.arange(kept.size), kept.pad)
        assert np.array_equal(np.sort(kept.index[taken]),
                              np.arange(A.nnz))
    assert_same_bits(C.dia_vals, want)


def test_second_refill_reuses_the_map():
    A, csr = build("poisson7")
    before = metrics.snapshot()
    B = A.with_values(new_values(csr[2], 1))
    assert growth(before) == {"matrix.refill_map.build": 1,
                              "matrix.refill_map.reuse": 0}
    before = metrics.snapshot()
    # the new matrix keeps the structure arrays' identity: same map,
    # whichever matrix of the loop is refilled, host or device values
    vals = new_values(csr[2], 2)
    C = B.with_values(vals)
    assert growth(before) == {"matrix.refill_map.build": 0,
                              "matrix.refill_map.reuse": 1}
    before = metrics.snapshot()
    D = A.with_values(jnp.asarray(vals))
    assert growth(before) == {"matrix.refill_map.build": 0,
                              "matrix.refill_map.reuse": 1}
    want = from_scratch(csr, vals).dia_vals
    assert_same_bits(C.dia_vals, want)
    assert_same_bits(D.dia_vals, want)


def test_another_pattern_of_equal_size_builds_its_own_map():
    ro, ci, vals = banded_csr(offsets=(-3, 0, 2), no_diag_every=10 ** 6)
    ro2, ci2, _ = banded_csr(offsets=(-2, 0, 3), no_diag_every=10 ** 6)
    n = ro.shape[0] - 1
    assert ci.shape == ci2.shape and ro.shape == ro2.shape
    A1 = CsrMatrix.from_scipy_like(ro, ci, vals, n, n).init()
    A2 = CsrMatrix.from_scipy_like(ro2, ci2, vals, n, n).init()
    assert A1.nnz == A2.nnz and A1.num_rows == A2.num_rows
    new = new_values(vals)
    before = metrics.snapshot()
    B1, B2 = A1.with_values(new), A2.with_values(new)
    assert growth(before) == {"matrix.refill_map.build": 2,
                              "matrix.refill_map.reuse": 0}
    assert_same_bits(B1.dia_vals,
                     from_scratch((ro, ci, vals, n), new).dia_vals)
    assert_same_bits(B2.dia_vals,
                     from_scratch((ro2, ci2, vals, n), new).dia_vals)
    assert not np.array_equal(np.asarray(B1.to_dense()),
                              np.asarray(B2.to_dense()))


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_map_dies_with_its_structure_arrays(pattern):
    gc.collect()
    held = len(matrix._REFILL_MAPS)
    A, csr = build(pattern)
    B = A.with_values(new_values(csr[2]))
    assert len(matrix._REFILL_MAPS) == held + 1
    del A
    gc.collect()
    # B holds the same structure arrays: the map stays
    assert len(matrix._REFILL_MAPS) == held + 1
    del B
    gc.collect()
    # nothing holds the pattern: the side table is as it was (empty,
    # where no other test of the process keeps a refilled matrix)
    assert len(matrix._REFILL_MAPS) == held


def test_pytree_has_the_leaves_it_had():
    A, csr = build("poisson7")
    leaves = len(jax.tree_util.tree_leaves(A))
    treedef = jax.tree_util.tree_structure(A)
    B = A.with_values(jnp.asarray(new_values(csr[2])))
    assert len(jax.tree_util.tree_leaves(B)) == leaves
    assert jax.tree_util.tree_structure(B) == treedef
    assert [f.name for f in dataclasses.fields(CsrMatrix)] \
        == ["row_offsets", "col_indices", "values", "diag", "row_ids",
            "diag_idx", "ell_cols", "ell_vals", "dia_offsets", "dia_vals",
            "swell_cols", "swell_vals", "swell_c0row", "swell_nchunk",
            "swell_w128", "split", "num_rows", "num_cols", "block_dimx",
            "block_dimy", "initialized", "grid_shape", "user_colors",
            "user_num_colors"]


@pytest.mark.parametrize("how", ["jit_values", "jit_matrix", "vmap"])
def test_traced_values_take_build_dia_vals(how):
    A, csr = build("banded_no_diag")
    vals = new_values(csr[2])
    want = from_scratch(csr, vals).dia_vals
    before = metrics.snapshot()
    if how == "jit_values":
        got = jax.jit(lambda v: A.with_values(v).dia_vals)(jnp.asarray(vals))
    elif how == "jit_matrix":
        got = jax.jit(lambda M, v: M.with_values(v).dia_vals)(
            A, jnp.asarray(vals))
    else:
        got = jax.vmap(lambda v: A.with_values(v).dia_vals)(
            jnp.stack([jnp.asarray(vals), 2.0 * jnp.asarray(vals)]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      2.0 * np.asarray(want))
        got = got[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # no map is built or looked up inside a trace
    assert growth(before) == {"matrix.refill_map.build": 0,
                              "matrix.refill_map.reuse": 0}


def test_complex_values_take_build_dia_vals():
    A, csr = build("poisson7")
    vals = new_values(csr[2]) * (1.0 + 0.5j)
    before = metrics.snapshot()
    B = A.with_values(jnp.asarray(vals))
    assert growth(before)["matrix.refill_map.build"] == 0
    want = from_scratch(csr, new_values(csr[2])).dia_vals
    np.testing.assert_allclose(np.asarray(B.dia_vals),
                               np.asarray(want) * (1.0 + 0.5j))


def test_first_refill_from_many_threads_builds_one_map():
    A, csr = build("poisson27")
    vals = [new_values(csr[2], s) for s in range(8)]
    out, errors = [None] * 8, []
    start = threading.Barrier(8)

    def work(i):
        try:
            start.wait(timeout=30)
            out[i] = A.with_values(vals[i])
        except Exception as e:       # read below: a thread's error fails
            errors.append(e)

    before = metrics.snapshot()
    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors
    assert growth(before) == {"matrix.refill_map.build": 1,
                              "matrix.refill_map.reuse": 7}
    for i in range(8):
        assert_same_bits(out[i].dia_vals,
                         from_scratch(csr, vals[i]).dia_vals)
