"""Pallas DIA SpMV kernel tests (interpreter mode — the compiled path
runs on the chip through chip_smoke.py and the benchmark's cells).
Mirrors the role of the reference's csrmv fast-path coverage
(src/multiply.cu:74-121)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.ops.pallas_spmv import (dia_padded_rows, dia_spmv,
                                      pick_block_rows)
from amgx_tpu.ops.spmv import spmv_csr_segsum

amgx.initialize()


@pytest.mark.parametrize("stencil,dims", [
    ("5pt", (16, 16)),          # 2D, single block
    ("7pt", (12, 12, 12)),      # odd n (padding tail exercised)
    ("9pt", (20, 20)),          # lane-crossing offsets (+-1, +-21...)
    ("27pt", (8, 8, 8)),        # many diagonals
])
def test_dia_kernel_matches_segsum(stencil, dims):
    A = gallery.poisson(stencil, *dims, dtype=jnp.float32).init()
    assert A.dia_offsets is not None
    n = A.num_rows
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(n), jnp.float32)
    y_ref = spmv_csr_segsum(A, x)
    y = dia_spmv(A, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_dia_kernel_multiblock():
    """Problem large enough for several grid blocks + halo DMA reuse."""
    A = gallery.poisson("7pt", 48, 48, 48, dtype=jnp.float32).init()
    n = A.num_rows
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal(n), jnp.float32)
    y_ref = spmv_csr_segsum(A, x)
    y = dia_spmv(A, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_tiled_layout_consistency():
    """matrix init and the kernel wrapper agree on the tile padding."""
    for stencil, dims in [("5pt", (10, 10)), ("7pt", (32, 32, 32))]:
        A = gallery.poisson(stencil, *dims, dtype=jnp.float32).init()
        k, rows_pad, lanes = A.dia_vals.shape
        assert lanes == 128
        assert rows_pad == dia_padded_rows(k, A.num_rows)
        br = pick_block_rows(k, -(-A.num_rows // 128))
        assert rows_pad % br == 0


def test_vmap_diverts_to_xla():
    """vmap over the Pallas dispatch must take the XLA form (pallas_call
    has no batching rule for ANY-space operands)."""
    from amgx_tpu.ops.spmv import _spmv_dia_pallas, _spmv_dia_xla
    A = gallery.poisson("5pt", 12, 12, dtype=jnp.float32).init()
    n = A.num_rows
    Z = jnp.asarray(
        np.random.default_rng(2).standard_normal((4, n)), jnp.float32)
    Y = jax.vmap(lambda z: _spmv_dia_pallas(A, z))(Z)
    Y_ref = jax.vmap(lambda z: _spmv_dia_xla(A, z))(Z)
    np.testing.assert_allclose(np.asarray(Y), np.asarray(Y_ref),
                               rtol=1e-6)


def test_with_values_keeps_tiled_layout():
    A = gallery.poisson("5pt", 8, 8, dtype=jnp.float32).init()
    A2 = A.with_values(A.values * 2.0)
    assert A2.dia_vals.shape == A.dia_vals.shape
    x = jnp.ones(A.num_rows, jnp.float32)
    np.testing.assert_allclose(np.asarray(amgx.ops.spmv(A2, x)),
                               2 * np.asarray(amgx.ops.spmv(A, x)),
                               rtol=1e-6)
