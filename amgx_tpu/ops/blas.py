"""BLAS-1 vector ops and norms.

Analog of src/blas.cu + src/norm.cu (include/blas.h:17-85). On TPU these
are trivially fused by XLA, so they are plain jnp expressions; the value
of this module is the distributed contract: every reduction takes an
optional `axis_name` and finishes with a `psum`/`pmax` so the same code
runs inside shard_map over a device mesh (the reference finishes its
device reductions with MPI allreduce, src/distributed/).

Block norms: for block matrices the reference computes one norm per block
component unless `use_scalar_norm` (src/core.cu:520-524); `norm` mirrors
that via the `block_size` / `use_scalar_norm` arguments.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def axpy(x, y, a):
    return a * x + y


def axpby(x, y, a, b):
    return a * x + b * y


def axpbypcz(x, y, z, a, b, c):
    return a * x + b * y + c * z


def scal(x, a):
    return a * x


def fill(x, value):
    return jnp.full_like(x, value)


def _axis(axis_name):
    if axis_name is not None:
        return axis_name
    from ..distributed import comms
    return comms.active_axis()


def _psum(v, axis_name):
    axis_name = _axis(axis_name)
    return jax.lax.psum(v, axis_name) if axis_name else v


def _pmax(v, axis_name):
    axis_name = _axis(axis_name)
    return jax.lax.pmax(v, axis_name) if axis_name else v


def dot(x, y, axis_name: Optional[str] = None, num_owned: Optional[int] = None):
    """<x, y> (conjugating x for complex); distributed-safe via psum over
    owned entries only."""
    if num_owned is not None:
        x, y = x[:num_owned], y[:num_owned]
    return _psum(jnp.vdot(x, y), axis_name)


def nrm1(x, axis_name: Optional[str] = None, num_owned: Optional[int] = None):
    if num_owned is not None:
        x = x[:num_owned]
    return _psum(jnp.sum(jnp.abs(x)), axis_name)


def nrm2(x, axis_name: Optional[str] = None, num_owned: Optional[int] = None):
    if num_owned is not None:
        x = x[:num_owned]
    return jnp.sqrt(_psum(jnp.sum(jnp.abs(x) ** 2), axis_name))


def nrmmax(x, axis_name: Optional[str] = None, num_owned: Optional[int] = None):
    if num_owned is not None:
        x = x[:num_owned]
    return _pmax(jnp.max(jnp.abs(x)), axis_name)


_NORMS = {"L1": nrm1, "L2": nrm2, "LMAX": nrmmax}


def norm(x, norm_type: str = "L2", block_size: int = 1,
         use_scalar_norm: bool = True, axis_name: Optional[str] = None,
         num_owned: Optional[int] = None):
    """Norm of a (flat) vector. With block_size>1 and use_scalar_norm=False
    returns a (block_size,) per-component norm vector."""
    fn = _NORMS[norm_type.upper()]
    if block_size <= 1 or use_scalar_norm:
        return fn(x, axis_name, num_owned)
    xb = x.reshape(-1, block_size)
    if num_owned is not None:
        xb = xb[:num_owned]
    if norm_type.upper() == "L1":
        return _psum(jnp.sum(jnp.abs(xb), axis=0), axis_name)
    if norm_type.upper() == "L2":
        return jnp.sqrt(_psum(jnp.sum(jnp.abs(xb) ** 2, axis=0), axis_name))
    return _pmax(jnp.max(jnp.abs(xb), axis=0), axis_name)


def get_norm(norm_type: str):
    return _NORMS[norm_type.upper()]


# ---------------------------------------------------------------------------
# Krylov shell fusion: the single-pass CG update and the packed scalar
# collective
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cg_update_fn():
    """custom_vmap-wrapped single-pass CG update kernel: every vmap
    batch (there is no matrix operand) takes the multi-RHS slab form
    in ops/batched.py, so solve_many's update stays one slab pass."""

    @jax.custom_batching.custom_vmap
    def call(x, p, r, ap, alpha):
        from . import pallas_spmv as _ps
        return _ps._cg_update_call(x, p, r, ap, alpha,
                                   interpret=_ps._FORCE_INTERPRET)

    @call.def_vmap
    def _rule(axis_size, in_batched, x, p, r, ap, alpha):
        from .batched import cg_update_multi

        def bc(v, b):
            return v if b else jnp.broadcast_to(
                v, (axis_size,) + jnp.shape(v))

        return (cg_update_multi(
            bc(x, in_batched[0]), bc(p, in_batched[1]),
            bc(r, in_batched[2]), bc(ap, in_batched[3]),
            bc(alpha, in_batched[4])), (True, True, True))

    return call


def cg_update(x, p, r, ap, alpha):
    """Single-pass CG state update: (x + alpha p, r - alpha Ap, r'.r')
    — the Pallas kernel streams the four vectors once and emits the
    residual dot as a free epilogue (the monitor's norm pass); the
    plain XLA compose (identical unfused expressions) covers f64 / CPU.
    The rr scalar is LOCAL — distributed callers psum it (packed)."""
    from . import pallas_spmv as _ps
    from ..telemetry import metrics as _tm
    if _ps.cg_update_supported(x.dtype):
        _tm.inc("krylov.fused_dispatch")
        return _cg_update_fn()(x, p, r, ap, alpha)
    _tm.inc("krylov.fused_declined")
    a = jnp.asarray(alpha).astype(x.dtype)
    xn = x + a * p
    rn = r - a * ap
    # f32+ accumulation like the kernel's epilogue (rr keeps ONE dtype
    # across the kernel/fallback routes, so loop state stays stable)
    rc = rn.astype(jnp.promote_types(x.dtype, jnp.float32))
    return xn, rn, jnp.vdot(rc, rc)


def psum_bundle(scalars, axis_name: Optional[str] = None):
    """Sum a tuple of LOCAL scalars across the mesh with ONE packed
    collective (stack + psum — the per-iteration collective count
    stays independent of how many dots the iteration needs); the
    identity when no mesh axis is active. Returns the tuple back."""
    axis_name = _axis(axis_name)
    if not axis_name:
        return tuple(scalars)
    packed = jax.lax.psum(jnp.stack([jnp.asarray(s) for s in scalars]),
                          axis_name)
    return tuple(packed[i] for i in range(len(scalars)))


# ---------------------------------------------------------------------------
# The Krylov basis of GMRES / FGMRES: row-bounded passes over a slab
#
# A basis is an (m + 1, R, 128) array: row k is one n-vector laid out
# as R rows of 128 lanes, so that a row is dense and contiguous on the
# chip (an (m + 1, n) array tiles its rows by eight: reading one row
# reads eight) and the leading index is a plain address. Every reader
# takes the number of LIVE rows, a traced value, and touches no row
# beyond it: a stale row may hold anything, finite or not.
# ---------------------------------------------------------------------------

# readings of the basis one CGS2 step makes (cgs2_step below)
CGS2_BASIS_READS = 3


def basis_rows128(n_rows: int, n: int) -> int:
    """R of an (n_rows, R, 128) basis for n-vectors: whole (8, 128)
    tiles, and whole column blocks of the chip's kernel."""
    from . import pallas_spmv as _ps
    return _ps.basis_padded_rows(n_rows, n)


def to_slab(v, rows128: int):
    """(n,) -> (rows128, 128), zero-filled behind n."""
    size = rows128 * 128
    if v.shape[0] != size:
        v = jnp.pad(v, (0, size - v.shape[0]))
    return v.reshape(rows128, 128)


def from_slab(s, n: int):
    """(rows128, 128) -> (n,)."""
    v = s.reshape(-1)
    return v if v.shape[0] == n else v[:n]


def _basis_pass_xla(V, w, coef, nlive):
    """The plain twin of the chip's kernel (pallas_spmv._basis_pass_call),
    pass for pass; `where` and not a product with 0 bounds the rows."""
    live = (jnp.arange(V.shape[0]) < nlive)[:, None, None]
    Vl = jnp.where(live, V, jnp.zeros((), V.dtype))
    if coef is not None:
        w = w - jnp.sum(coef[:, None, None] * Vl, axis=0)
    return w, jnp.sum(Vl * w, axis=(1, 2)), jnp.sum(w * w)


@functools.lru_cache(maxsize=None)
def _basis_pass_fn(project: bool):
    """custom_vmap-wrapped kernel pass: a vmap batch (BatchedSolver)
    takes the plain twin, batched by XLA."""

    def twin(V, w, coef, nlive):
        return _basis_pass_xla(V, w, coef if project else None, nlive)

    @jax.custom_batching.custom_vmap
    def call(V, w, coef, nlive):
        from . import pallas_spmv as _ps
        return _ps._basis_pass_call(V, w, coef, nlive, project=project,
                                    interpret=_ps._FORCE_INTERPRET)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(
            a, (axis_size,) + jnp.shape(a))
            for a, b in zip(args, in_batched)]
        return jax.vmap(twin)(*args), (True, True, True)

    return call


def basis_pass(V, w, coef, nlive):
    """One reading of the live rows of a basis:

        w'      = w - sum_{k < nlive} coef[k] V[k]    (coef None: w' = w)
        dots[k] = <V[k], w'> for k < nlive, else 0
        nrm     = <w', w'>

    V (rows, R, 128), w (R, 128), coef (rows,), nlive a traced count.
    dots and nrm are LOCAL sums: a distributed caller psums what it
    uses. On the chip in f32 this is one Pallas kernel that fetches
    the live rows alone; everywhere else the plain twin."""
    from . import pallas_spmv as _ps
    if _ps.basis_pass_supported(V, w):
        c = jnp.zeros((V.shape[0],), w.dtype) if coef is None else coef
        return _basis_pass_fn(coef is not None)(
            V, w, c, jnp.asarray(nlive, jnp.int32))
    return _basis_pass_xla(V, w, coef, nlive)


def cgs2_step(V, w, nlive, axis_name: Optional[str] = None):
    """Classical Gram-Schmidt with reorthogonalisation of the slab w
    against rows 0..nlive-1 of the basis V, in CGS2_BASIS_READS
    readings of those rows: h = V w; then w' = w - V^T h together
    with h2 = V w' (w' is elementwise in the column, so both come from
    one reading of a column block); then w'' = w' - V^T h2 together
    with its norm. Returns (h + h2, w'', ||w''||); h is zero from
    nlive on. Distributed-safe: each reading's sums finish with one
    psum, as the dots of `dot` and `nrm2` do."""
    _, h, _ = basis_pass(V, w, None, nlive)
    h = _psum(h, axis_name)
    w, h2, _ = basis_pass(V, w, h, nlive)
    h2 = _psum(h2, axis_name)
    w, _, nrm = basis_pass(V, w, h2, nlive)
    return h + h2, w, jnp.sqrt(_psum(nrm, axis_name))


def basis_combine(V, y, nlive, x):
    """x + sum_{k < nlive} y[k] V[k] as a slab: the way back from the
    Krylov coordinates, in one reading of the live rows."""
    return basis_pass(V, x, -y, nlive)[0]
