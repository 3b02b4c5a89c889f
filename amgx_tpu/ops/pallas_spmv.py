"""Pallas TPU SpMV kernel for the DIA (banded stencil) layout.

The reference's SpMV fast path is a hand-tuned CUDA csrmv
(src/multiply.cu:74-121 and the CHANGELOG "fast path" entry). The TPU
equivalent is not a translation of that kernel: on TPU the roofline
layout for stencil matrices is DIA — y = sum_d vals_d * shift(x, d) —
because every stream is a dense sequential read (no gather hardware).
XLA alone materializes each partial sum in HBM, so a 7-diagonal SpMV
pays ~4x the minimum traffic. This kernel performs the whole reduction
in one fused pass:

- grid over row blocks of BLOCK_ROWS*128 elements, sequential on core;
- diagonal values arrive via an auto-pipelined (k, BR, 128) block;
- the x window (block + halo rows for every diagonal offset) is DMA'd
  from HBM into a manually double-buffered VMEM scratch, so the next
  block's halo loads while the current block computes;
- lane-crossing shifts (offset % 128 != 0) use the two-row roll+select
  trick: W[p, q] = a[p, q+r] for q < 128-r else b[p, q+r-128], where
  a/b are consecutive row views of the window — pure VPU work.

Traffic per output element for a k-diagonal matrix: k value floats +
~1 x float + 1 y float, i.e. the HBM minimum (plus a halo sliver).

The matrix stores dia_vals tile-aligned as (k, rows_pad, 128) — see
CsrMatrix._build_dia_vals — so the kernel reads values with zero
re-layout cost. float32 only (TPU has no native f64; the XLA spmv_dia
path covers f64/CPU).
"""
from __future__ import annotations

import contextlib
import functools
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# The ONE VMEM number: the scoped-VMEM limit every kernel hands the
# compiler (kernel_call) — half of a v5e core's 128 MiB, four times
# Mosaic's 16 MiB default. The plans below hold what they can count —
# DMA windows, pipelined blocks, the matrix-free mask set — to
# fractions of it (the budgets, which choose the block sizes), and the
# fused-smoother plan holds windows PLUS the kernel body's live values
# to the limit itself (dia_smooth_plan, smooth_body_planes), so a plan
# that is returned is a kernel the compiler was given room for.
# tests/test_chip_compile.py compiles the flagship's shapes, and a
# 27-point matrix-free level, for a v5e under this limit.
VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET = VMEM_LIMIT * 5 // 32     # 10 MiB of windows/blocks

# Testing hook: the CPU tests run the Pallas kernels through the
# interpreter; flipping this (via force_pallas_interpret) makes the
# trace-time gates report "supported" off-TPU and routes every kernel
# call through interpret mode, so kernel-consuming code paths (spmv
# dispatch, fused smoothers, the cycle) are exercised end to end.
_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_pallas_interpret():
    """Route the Pallas kernels through the interpreter and make their
    support gates ignore the backend check (CPU test path)."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


def pallas_backend():
    """The one capability question every Pallas support gate in ops/
    asks. "interpret": forced through the Pallas interpreter (CPU
    tests). "mosaic": the default backend is a TPU, kernels are
    compiled by Mosaic — the branch where a kernel family the chip's
    compiler refuses declines. None: no Pallas path, XLA forms only."""
    if _FORCE_INTERPRET:
        return "interpret"
    return "mosaic" if jax.default_backend() == "tpu" else None


def kernel_call(kernel, **kw):
    """`pl.pallas_call` for every kernel in ops/, with the two things
    the chip's compiler needs done in ONE place.

    - The package turns x64 on at import, so a Python int inside a
      kernel body, an index map or a DMA slice traces as int64, which
      Mosaic has no type for ("'tpu.memref_slice' op operand #2 must be
      variadic of 32-bit signless integer, but got 'i64'"). The call —
      and with it the kernel body — is traced with x64 off.
    - The compiler is told the VMEM limit the plans were held to
      (VMEM_LIMIT), so plan and compiler never hold two different
      numbers."""
    call = pl.pallas_call(
        kernel, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT), **kw)

    def run(*operands):
        with jax.enable_x64(False):
            return call(*operands)
    return run


# Per-block partial sums (dot epilogues): one (PART_ROWS, 128) output
# block per grid step, the lane sums in row 0 and zeros below, summed
# by the caller's cheap XLA combine. A (1, 128) block per step is what
# the arithmetic needs, but Mosaic refuses it as soon as there is more
# than one step ("the last two dimensions of your block shape are
# divisible by 8 and 128 respectively, or be equal to the respective
# dimensions of the overall array").
PART_ROWS = 8


def _part_spec():
    return pl.BlockSpec((PART_ROWS, LANES), lambda i: (i, jnp.int32(0)),
                        memory_space=pltpu.VMEM)


def _part_shape(n_blocks: int):
    return jax.ShapeDtypeStruct((n_blocks * PART_ROWS, LANES),
                                jnp.float32)


def _part_store(ref, prod):
    """Store the row-sum of `prod` (rows, 128) as this block's partial."""
    s = jnp.sum(prod, axis=0, keepdims=True).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (PART_ROWS, LANES), 0)
    ref[...] = jnp.where(row == 0, s, jnp.zeros((), jnp.float32))


def pick_block_rows(k: int, rows128: int) -> int:
    """Rows (of 128 lanes) per grid block. Shared by matrix init (which
    pads dia_vals to a multiple of this) and the kernel wrapper, so the
    two always agree. Sized so the double-buffered values block fits
    VMEM comfortably."""
    budget_rows = _VMEM_BUDGET // (max(k, 1) * LANES * 4 * 2)
    br = 512
    while br > 8 and br > budget_rows:
        br //= 2
    if rows128 <= br:
        # single block: round the whole matrix up to a tile of 8 rows
        return max(8, -(-rows128 // 8) * 8)
    return br


def dia_padded_rows(k: int, n: int) -> int:
    """Padded row count (of 128 lanes) for the tiled dia_vals store."""
    rows128 = max(1, -(-n // LANES))
    br = pick_block_rows(k, rows128)
    return -(-rows128 // br) * br


def _dia_kernel(offsets, left, block_rows, halo_rows, n_blocks, dtype):
    """Build the kernel body. All layout numbers are static."""
    ro = [(left + o) // LANES for o in offsets]   # window row offset
    rl = [(left + o) % LANES for o in offsets]    # lane shift
    win_rows = block_rows + halo_rows

    def kernel(xp_ref, vals_ref, y_ref, xbuf, sems):
        i = pl.program_id(0)
        slot = jax.lax.rem(i, jnp.int32(2))

        def dma(s, blk):
            return pltpu.make_async_copy(
                xp_ref.at[pl.ds(jnp.int32(blk) * jnp.int32(block_rows),
                                win_rows)],
                xbuf.at[jnp.int32(s)], sems.at[jnp.int32(s)])

        @pl.when(i == 0)
        def _():
            dma(0, 0).start()

        @pl.when(i + 1 < n_blocks)
        def _():
            dma(jax.lax.rem(i + 1, jnp.int32(2)), i + 1).start()

        dma(slot, i).wait()

        col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
        acc = jnp.zeros((block_rows, LANES), dtype)
        xv = xbuf[slot]          # (win_rows, 128) view of this block's x
        for k, _ in enumerate(offsets):
            vk = vals_ref[k]
            if rl[k] == 0:
                w = jax.lax.slice_in_dim(xv, ro[k], ro[k] + block_rows, 1, 0)
            else:
                a = jax.lax.slice_in_dim(xv, ro[k], ro[k] + block_rows, 1, 0)
                b = jax.lax.slice_in_dim(xv, ro[k] + 1,
                                         ro[k] + 1 + block_rows, 1, 0)
                shift = LANES - rl[k]
                wa = pltpu.roll(a, jnp.int32(shift), 1)
                wb = pltpu.roll(b, jnp.int32(shift), 1)
                w = jnp.where(col < shift, wa, wb)
            acc = acc + vk * w
        y_ref[...] = acc

    return kernel


def _layout(offsets, k: int, num_rows: int):
    """Shared layout math: (left pad, halo rows, block rows). The gate
    and the kernel wrapper both call this so they can never diverge."""
    left = -(-max(0, -min(offsets)) // LANES) * LANES
    halo_rows = (left + max(max(offsets), 0)) // LANES + 1
    br = pick_block_rows(k, max(1, -(-num_rows // LANES)))
    return left, halo_rows, br


def dia_spmv_supported(A, x_dtype) -> bool:
    """Trace-time gate for the Pallas path."""
    if pallas_backend() is None:
        return False
    if A.dia_vals is None or A.dia_vals.dtype != jnp.float32 \
            or x_dtype != jnp.float32:
        return False
    if A.num_rows != A.num_cols:
        return False
    k, rows_pad, _ = A.dia_vals.shape
    left, halo_rows, br = _layout(A.dia_offsets, k, A.num_rows)
    if rows_pad % br != 0:
        return False
    # window scratch must fit alongside the values pipeline
    win_bytes = 2 * (br + halo_rows) * LANES * 4
    vals_bytes = 2 * k * br * LANES * 4
    return win_bytes + vals_bytes + 2 * br * LANES * 4 <= \
        _VMEM_BUDGET + 4 * 1024 * 1024


@functools.partial(jax.jit,
                   static_argnames=("offsets", "num_rows", "interpret"))
def _dia_spmv_call(dia_vals, x, offsets, num_rows, interpret=False):
    k, rows_pad, _ = dia_vals.shape
    dtype = dia_vals.dtype
    n = num_rows
    left, halo_rows, br = _layout(offsets, k, n)
    n_blocks = rows_pad // br
    xp_rows = rows_pad + halo_rows
    xp = jnp.zeros((xp_rows * LANES,), dtype)
    xp = jax.lax.dynamic_update_slice(xp, x.astype(dtype), (left,))
    xp = xp.reshape(xp_rows, LANES)

    kernel = _dia_kernel(offsets, left, br, halo_rows, n_blocks, dtype)
    y2 = kernel_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(
                (k, br, LANES),
                lambda i: (jnp.int32(0), i, jnp.int32(0)),
                memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, LANES),
                               lambda i: (i, jnp.int32(0)),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), dtype),
        scratch_shapes=[
            pltpu.VMEM((2, br + halo_rows, LANES), dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * k * rows_pad * LANES,
            bytes_accessed=(k + 2) * rows_pad * LANES * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(xp, dia_vals)
    y = y2.reshape(-1)
    if y.shape[0] != n:
        y = y[:n]
    return y


def dia_spmv(A, x, interpret=False):
    """Fused DIA SpMV; caller must have checked dia_spmv_supported
    (`interpret=True` runs the Pallas interpreter — CPU test path)."""
    return _dia_spmv_call(A.dia_vals, x, A.dia_offsets, A.num_rows,
                          interpret=interpret or _FORCE_INTERPRET)


# ---------------------------------------------------------------------------
# Fused multi-sweep smoother (+ residual epilogue)
#
# The V-cycle's hot pair is presmooth -> residual: S damped sweeps
#   x_{s+1} = x_s + tau_s * dinv . (b - A x_s)
# (Jacobi/Jacobi-L1: tau_s = relaxation_factor, dinv = D^{-1};
#  CHEBYSHEV_POLY: tau_s = magic-damping taus, dinv absent) followed by
# r = b - A x_S. Unfused, that is S+1 HBM passes over A's diagonal slab
# plus an elementwise pass per sweep. One pallas_call runs all S sweeps
# AND the residual epilogue, in one of two forms (dia_smooth_plan):
#
# ONE BLOCK (the level fits a block: rows <= _BR_CAP). The block loads
# a row window wide enough to compute all applications locally. Per
# application the data dependence grows mr0 rows downward and Mr0 rows
# upward (mr0 = ceil(max(0,-min d) / 128), Mr0 = max(0, max d)//128 + 1).
# With n_app applications (n_app = sweeps + 1 when the residual rides):
#   win_v = br + (n_app-1)*(mr0+Mr0)    # vals/b/dinv window (compute rows)
#   win_x = win_v + mr0 + Mr0           # x window (read halo on top)
# The x state lives in "window coordinates" (row j = x row
# -n_app*mr0 + j); each application computes rows [mr0, mr0+win_v)
# of the next state and zero-fills the shrinking edges — the zeros land
# exactly on rows already invalidated by the dependence cone, so the
# final rows [n_app*mr0, n_app*mr0+br) are exact. b and x are padded
# in-trace to that window (a level of at most _BR_CAP rows: 1 MiB).
#
# CARRY (more than one block). The grid runs its row blocks in order on
# one core, so nothing is recomputed: level t (x after t sweeps; the
# residual is level n_app) trails level t-1 by a fixed SKEW of rows, at
# least the operator's forward reach. At grid step i level t computes
# rows [i*br - t*skew, +br) from the newest br + skew + back-reach rows
# of level t-1, which stay in VMEM: every level keeps a ring of its
# newest rows that persists across grid steps and moves down by br rows
# a step (static slices only). x, b and the value/dinv slabs are
# streams: block i+1 is DMA'd behind the ring while step i computes,
# straight from the caller's arrays (no padded copy: rows outside
# [0, n) are zero-filled in VMEM, and the masks / the quota slab's zero
# rows keep every level zero there). Outputs leave block-aligned with a
# LAG of ceil(n_app*skew / br) steps, which are also the extra grid
# steps that drain the pipeline. Every row of every level is computed
# once: x and b are read once and x' (and r) written once a stage.
#
# The values/dinv operands arrive as QUOTA-PADDED slabs (built once per
# setup/resetup by ops.smooth and carried in the smoother's solve_data)
# so no per-cycle re-layout of A happens.
# ---------------------------------------------------------------------------

_SMOOTH_VMEM_BUDGET = VMEM_LIMIT * 11 // 64   # 11 MiB, as above
# the carry form counts everything it holds — rings, output blocks and
# the body's planes — and keeps a quarter of the limit for what the
# compiler adds (tests/test_chip_compile.py asks Mosaic)
_CARRY_VMEM_BUDGET = VMEM_LIMIT * 3 // 4
# a grid step's fixed cost in the carry plan's work model, in
# row-applications (0.35 us a step against 1 ns a row-application)
_CARRY_STEP_ROWS = 512
SMOOTH_MAX_APPS = 8          # sweeps + residual cap for one fused call
_BR_CAP = 2048               # largest candidate block size

# Fused-kernel operand-dtype whitelist. bf16 slabs stream at half the
# HBM bytes of f32 (the kernels are bandwidth-bound, so ~2x per sweep)
# and halve the VMEM the DMA windows occupy (bigger blocks fit under
# the budget — a second, compounding win); the kernels upcast each
# block in VMEM and accumulate every sweep + the trailing residual in
# f32, so only the OPERAND stream is narrow, never the arithmetic.
SMOOTH_DTYPES = ("float32", "bfloat16")


def compute_dtype(dtype):
    """In-kernel accumulation dtype for an operand stream: sub-f32
    operands (bf16) upcast per block and accumulate in f32; f32/f64
    pass through unchanged (identity casts fold away, keeping the f32
    jaxprs bit-identical to the pre-mixed-precision build)."""
    return jnp.float32 if jnp.dtype(dtype).itemsize < 4 else \
        jnp.dtype(dtype)


def smooth_dtype_ok(A, x_dtype) -> bool:
    """Operand-dtype gate shared by every fused-smoother-suite entry:
    the matrix slab dtype and the vector dtype must agree and sit on
    the kernel whitelist. Callers that find a fused payload but fail
    THIS gate count `fusion.declined_dtype` (ops/smooth.py) so a
    config that falls off the fused path is visible, not silent."""
    if getattr(A, "dia_vals", None) is None:
        return False
    dt = jnp.dtype(A.dia_vals.dtype)
    return dt == jnp.dtype(x_dtype) and kernel_dtype_ok(dt)


def kernel_dtype_ok(dtype) -> bool:
    """Operand dtypes of the windowed DIA kernels (dia_smooth,
    dia_spmv_dot, both twins). bf16 is on the whitelist, but compiled
    for the chip its windows are refused: the plans size the DMA
    windows in 8-row units (f32 tiling), bf16 tiles are 16 rows deep,
    and Mosaic (jax 0.9.0, v5e; 7-pt 32^3..128^3, slab and stencil
    twins) says

        Mosaic failed to compile TPU kernel: Slice shape along
        dimension 0 must be aligned to tiling (8), but is 307

    for the (win_x, 128) bf16 window slices (cg_update and the SWELL
    pair, which have no windows, compile in bf16 and stay). Until the
    plans round windows to the operand's tiling (ROADMAP queue A),
    bf16 declines here on the compiled-for-chip branch (callers count
    fusion.declined_dtype and take the XLA forms); interpret mode
    keeps it."""
    name = jnp.dtype(dtype).name
    if pallas_backend() == "mosaic":
        return name == "float32"
    return name in SMOOTH_DTYPES


def smooth_halo_rows(offsets):
    """(mr0, Mr0): per-application dependence growth in 128-lane rows."""
    m = max(0, -min(offsets))
    M = max(0, max(offsets))
    return -(-m // LANES), M // LANES + 1


def smooth_br_candidates(num_rows: int):
    """Candidate block sizes of the smoother's plan, largest first."""
    rows128 = max(1, -(-num_rows // LANES))
    single = max(8, -(-rows128 // 8) * 8)
    cands = [c for c in (_BR_CAP, 1536, 1024, 768, 512, 384, 256, 192,
                         128, 96, 64, 32, 16, 8) if c < single]
    return ([single] if single <= _BR_CAP else []) + cands


def smooth_quota_rows(offsets, num_rows: int):
    """(front, content, back) rows of the quota-padded operand slabs
    (values / dinv) the fused kernel DMAs row blocks from. ONE padded
    slab per matrix (built at setup by ops.smooth) serves every sweep
    count the cycle asks for — the sweep count is only known at trace
    time, after the solve-data pytree is already fixed. The front quota
    is sized for SMOOTH_MAX_APPS applications of the one-block form's
    window; the carry form reads no row before the content, but the
    layout is shared with distributed/fused.py, which FILLS both quotas
    with the neighbour shards' rows and reads them in its boundary
    strips."""
    mr0, Mr0 = smooth_halo_rows(offsets)
    rows128 = max(1, -(-num_rows // LANES))
    content = max(8, -(-rows128 // 8) * 8)
    front = (SMOOTH_MAX_APPS - 1) * mr0
    # block rounding never exceeds one block (every candidate block
    # size is <= min(content, _BR_CAP)), so the back quota stays
    # proportional to the matrix instead of a fixed _BR_CAP slab that
    # would double tiny coarse levels
    back = (SMOOTH_MAX_APPS - 1) * Mr0 + min(content, _BR_CAP)
    return front, content, back


# ---------------------------------------------------------------------------
# Matrix-free (coeffs) mode: constant-coefficient stencil levels pass a
# static `mf` spec (a namedtuple with fields offsets/shifts/shape/n/
# dinv/diag_rank — ops.stencil.StencilSpec) instead of the quota-padded
# vals/dinv slabs. The kernels synthesize each diagonal's masked value
# rows in-register from k SMEM scalars: a row's entry for grid shift
# (dx,dy,dz) is coeffs[t] where the shifted point stays inside the
# (nx,ny,nz) grid and the row itself is a real matrix row, else 0 —
# exactly the slab the matrix build would have materialized, so the
# compute below the value fetch is shared, unchanged, and bit-equal.
# ---------------------------------------------------------------------------

# rows-of-f32 working-set charge per win_v row the plans budget for the
# coeffs mode's in-register coordinates and masks (idx + 3 grid coords
# + mask temporaries, ~6 int32/bool planes)
_MF_WORK_ROWS = 6


def smooth_body_planes(k: int, coeffs: bool) -> int:
    """(compute rows, 128) f32 planes the fused smoother's BODY keeps
    live in VMEM on top of its DMA windows and rings (compute rows:
    win_v in the one-block form, br in the carry form, whose levels run
    one after another over the same planes): state, accumulator and
    shifted views, and in the matrix-free form the k masked value
    planes. An upper bound from compiling for v5e and searching the
    smallest vmem_limit_bytes that is accepted: the slab form needed
    up to 8 planes (7-pt, 32^3 to 128^3), the matrix-free form up to
    21 at k = 7 and 66 at k = 27 (19.5 MiB where its windows took
    4.9 MiB at 7-pt 64^3, 5 sweeps + residual — what XLA refused under
    the 16 MiB default with "Scoped allocation with size 17.23M and
    limit 16.00M exceeded scoped vmem limit" — and 48 MiB at 27-pt
    128^3)."""
    return 3 * k if coeffs else 10


def _mf_coords(shape, idx):
    """(gx, gy, gz) grid coordinates of linear element indices
    (x fastest). Truncating div/rem: negative indices (front-halo pad
    rows) produce garbage coordinates that the caller's row-valid mask
    kills."""
    nx, ny, _nz = shape
    gx = jax.lax.rem(idx, jnp.int32(nx))
    t1 = jax.lax.div(idx, jnp.int32(nx))
    gy = jax.lax.rem(t1, jnp.int32(ny))
    gz = jax.lax.div(t1, jnp.int32(ny))
    return gx, gy, gz


def _mf_ok(shape, coords, shift, base):
    """`base` AND the in-grid mask of one stencil shift — static
    bounds, so axes the shift does not cross cost nothing."""
    nx, ny, nz = shape
    dx, dy, dz = shift
    gx, gy, gz = coords
    ok = base
    if dx < 0:
        ok = ok & (gx >= -dx)
    if dx > 0:
        ok = ok & (gx < nx - dx)
    if dy < 0:
        ok = ok & (gy >= -dy)
    if dy > 0:
        ok = ok & (gy < ny - dy)
    if dz < 0:
        ok = ok & (gz >= -dz)
    if dz > 0:
        ok = ok & (gz < nz - dz)
    return ok


def _mf_dinv(mf, cget, ok, valid, cdt):
    """The dinv rows (or None) the smoother would have shipped,
    synthesized from coefficient scalars: safe_recip of the plain
    ("jacobi") or L1-strengthened ("l1") diagonal. `ok(t)` is diagonal
    t's mask (its shift stays in the grid, on a real row), `valid` the
    row-valid mask."""
    if mf.dinv is None:
        return None
    c0 = cget(mf.diag_rank)
    if mf.dinv == "jacobi":
        den = jnp.where(valid, c0, jnp.zeros((), cdt))
    else:                           # "l1": diag + sign(diag)*sum|off|
        l1 = jnp.zeros(valid.shape, cdt)
        for t in range(len(mf.shifts)):
            if t == mf.diag_rank:
                continue
            l1 = l1 + jnp.where(ok(t), jnp.abs(cget(t)),
                                jnp.zeros((), cdt))
        den = jnp.where(valid, c0 + jnp.sign(c0) * l1,
                        jnp.zeros((), cdt))
    return jnp.where(den == 0, jnp.zeros((), cdt),
                     1 / jnp.where(den == 0, jnp.ones((), cdt), den))


def _mf_vals_dinv(mf, cget, coords, valid, cdt):
    """(val(t), dinv rows | None) synthesized from coefficient scalars.
    `cget(t)` reads diagonal t's scalar at `cdt` (SMEM ref or array);
    `valid` is the row-valid mask of the window. val(t) reproduces the
    slab row (coefficient on in-grid rows, 0 on halo/off-grid rows);
    the dinv rows reproduce what the smoother would have shipped
    (_mf_dinv)."""

    def ok(t):
        return _mf_ok(mf.shape, coords, mf.shifts[t], valid)

    def val(t):
        return jnp.where(ok(t), cget(t), jnp.zeros((), cdt))

    return val, _mf_dinv(mf, cget, ok, valid, cdt)


def _mf_block_vals(mf, coeffs_ref, row0, win_v, col, cdt):
    """Coeffs-mode replacement for a block kernel's vals/dinv VMEM
    windows: masked value rows + dinv rows for the compute region whose
    first row is x row `row0` (traced). Coordinates are computed once
    per block; each diagonal's mask is a handful of VPU compares."""
    row = jax.lax.broadcasted_iota(jnp.int32, (win_v, LANES), 0)
    idx = (row0 + row) * jnp.int32(LANES) + col
    coords = _mf_coords(mf.shape, idx)
    valid = (idx >= 0) & (idx < jnp.int32(mf.n))
    return _mf_vals_dinv(mf, lambda t: coeffs_ref[t].astype(cdt),
                         coords, valid, cdt)


def _r8(rows: int) -> int:
    return -(-rows // 8) * 8


class SmoothPlan(typing.NamedTuple):
    """Block plan of one fused smoother call (dia_smooth_plan).
    `lag` == 0 is the one-block form (win_x / win_v are its windows);
    `lag` > 0 the carry form: `n_blocks` row blocks of `br` rows, level
    t trailing level t-1 by `skew` rows, `lag` drain steps, win_v = br
    rows computed a level a step and win_x the rows one level reads."""
    br: int
    n_app: int
    mr0: int
    Mr0: int
    win_x: int
    win_v: int
    n_blocks: int
    skew: int = 0
    lag: int = 0

    @property
    def steps(self) -> int:
        return self.n_blocks + self.lag

    @property
    def row_apps(self) -> int:
        """Lane-rows x applications the call COMPUTES (halo rows of
        the one-block window and the carry form's drain included)."""
        return self.n_app * self.steps * self.win_v


def _carry_rings(br, skew, lag, back, n_steps, with_residual,
                 with_dot=True):
    """Ring lengths (rows) of the carry form, shared by the plan's VMEM
    arithmetic and the kernel so the two cannot diverge. After step i
    a stream's ring ends at row (i+1)*br and level t's at
    (i+1)*br - t*skew; a ring is as long as its farthest reader needs:
    the next level's window (br + skew + back rows), the levels'
    b / value rows (down to level n_app), the lagged output block."""
    n_app = n_steps + (1 if with_residual else 0)
    read = br + skew + back
    out = (lag + 1) * br
    return {
        "x": read,
        "mid": read,                          # levels 1 .. n_steps-1
        "last": max(out - n_steps * skew,     # level n_steps: x'
                    read if with_residual else br),
        "res": out - n_app * skew,            # the residual's level
        "b": out if with_dot else br + n_app * skew,
        "vals": br + n_app * skew,
    }


def dia_smooth_plan(offsets, k: int, num_rows: int, n_steps: int,
                    with_residual: bool, itemsize: int = 4,
                    coeffs: bool = False):
    """Block plan (SmoothPlan) for the fused smoother, or None when no
    form fits VMEM or the schedule is longer than SMOOTH_MAX_APPS.

    A level of at most _BR_CAP rows is ONE block with the halo window
    of all its applications. Anything larger takes the carry form:
    the skew is the operator's forward reach rounded to the 8-row
    tiling (for a 7-point stencil whose z-plane is whole 8-row tiles,
    exactly one plane, so every level sees the same x/y coordinates),
    and the block size is the candidate with the least modelled work —
    rows computed over all steps, the drain's included, plus the rows
    the rings move a step and a fixed charge a step — among those whose
    rings, output blocks and body fit VMEM_LIMIT. `itemsize` is the
    operand-slab byte width (bf16 streams narrow; levels stay f32).
    `coeffs` plans the matrix-free form: no value/dinv streams, the
    kernel synthesizes masked value rows from k SMEM scalars."""
    if not offsets:
        return None
    n_app = int(n_steps) + (1 if with_residual else 0)
    if n_app < 1 or n_app > SMOOTH_MAX_APPS:
        return None
    ib = int(itemsize)
    mr0, Mr0 = smooth_halo_rows(offsets)
    H = mr0 + Mr0
    rows128 = max(1, -(-num_rows // LANES))
    n_out = 2 if with_residual else 1
    planes = smooth_body_planes(k, coeffs)
    cands = smooth_br_candidates(num_rows)
    if cands[0] >= rows128:         # the level is one block
        br = cands.pop(0)
        win_v = br + (n_app - 1) * H
        win_x = win_v + H
        if coeffs:
            vmem = (2 * (win_v + win_x)  # b/x windows, 2 slots
                    + 2 * n_out * br     # pipelined output blocks
                    ) * LANES * ib \
                + _MF_WORK_ROWS * win_v * LANES * 4   # coord/mask set
        else:
            vmem = (2 * k * win_v        # values, double-buffered
                    + 2 * (2 * win_v + win_x)  # b/dinv/x windows
                    + 2 * n_out * br     # pipelined output blocks
                    ) * LANES * ib
        if ib < 4:
            # sub-f32 operands: the f32 state + per-application upcast
            # temporaries ride on top of the narrow DMA buffers
            vmem += (win_x + 3 * win_v) * LANES * 4
        body = planes * win_v * LANES * 4
        if vmem <= _SMOOTH_VMEM_BUDGET and vmem + body <= VMEM_LIMIT:
            return SmoothPlan(br, n_app, mr0, Mr0, win_x, win_v, 1)
    back = _r8(mr0)
    fwd = max(0, max(o // LANES + (1 if o % LANES else 0)
                     for o in offsets))
    skew = max(8, _r8(fwd))
    best = None
    for br in cands:
        lag = -(-n_app * skew // br)
        ring = _carry_rings(br, skew, lag, back, n_steps, with_residual)
        streams = ring["x"] + ring["b"] + 2 * br
        if not coeffs:
            streams += (k + 1) * (ring["vals"] + br)
        levels = (n_steps - 1) * ring["mid"] + ring["last"] \
            + (ring["res"] if with_residual else 0)
        vmem = (streams + 2 * n_out * br) * LANES * ib \
            + (levels + planes * br) * LANES * 4
        if vmem > _CARRY_VMEM_BUDGET:
            continue
        nb = -(-rows128 // br)
        work = (nb + lag) * (n_app * br + (streams + levels) // 4
                             + _CARRY_STEP_ROWS)
        if best is None or work < best[0]:
            best = (work, SmoothPlan(br, n_app, mr0, Mr0,
                                     back + br + _r8(fwd), br, nb,
                                     skew, lag))
    return None if best is None else best[1]


def dia_smooth_supported(A, x_dtype, n_steps: int,
                         with_residual: bool) -> bool:
    """Trace-time gate for the fused smoother Pallas path."""
    if pallas_backend() is None:
        return False
    if not smooth_dtype_ok(A, x_dtype):
        return False
    if A.num_rows != A.num_cols or A.has_external_diag:
        return False
    k = A.dia_vals.shape[0]
    return dia_smooth_plan(A.dia_offsets, k, A.num_rows, n_steps,
                           with_residual,
                           itemsize=jnp.dtype(x_dtype).itemsize) \
        is not None


def _smooth_refs(refs, mf, has_dinv, with_residual, with_dot):
    """The carry kernel's operand and output refs in call order — x,
    [vals_q], b, [dinv_q] | [coeffs], taus, out_x, [out_r], [out_dot]
    (None where absent) — and an iterator over the scratch refs behind
    them."""
    it = iter(refs)
    x_ref = next(it)
    vals_ref = next(it) if mf is None else None
    b_ref = next(it)
    dinv_ref = next(it) if has_dinv else None
    coeffs_ref = next(it) if mf is not None else None
    taus_ref = next(it)
    y_ref = next(it)
    r_ref = next(it) if with_residual else None
    d_ref = next(it) if with_dot else None
    return (x_ref, vals_ref, b_ref, dinv_ref, coeffs_ref, taus_ref,
            y_ref, r_ref, d_ref), it


def _dia_smooth_kernel(offsets, br, n_app, mr0, Mr0, win_x, win_v,
                       n_steps, with_residual, has_dinv, n_blocks,
                       slab_shift, dtype, mf=None, with_dot=False):
    """Kernel body factory. Buffer coordinates: state row j = x row
    i*br - n_app*mr0 + j; vals/b/dinv compute-region row j' = x row
    i*br - (n_app-1)*mr0 + j' (so an application's output row j'
    aligns with operand-window row j' directly). `slab_shift` is the
    static extra front padding of the quota-padded vals/dinv slabs
    beyond this plan's (n_app-1)*mr0 need. Sub-f32 operand dtypes
    (bf16) stream/DMA narrow and upcast per block in VMEM; the state
    and every accumulation run in `cdt` (f32+), and only the final
    stores round back to the operand dtype. `mf` (matrix-free): no
    vals/dinv operands or windows — value and dinv rows synthesize
    in-register from k SMEM coefficient scalars (_mf_block_vals);
    `has_dinv` must be False (the dinv, if any, comes from mf.dinv)."""
    ro = [mr0 + (o - (o % LANES)) // LANES for o in offsets]
    rl = [o % LANES for o in offsets]
    cdt = compute_dtype(dtype)

    def kernel(*refs):
        # refs: xp, vals_q, bp, [dinv_q], taus, out_x, [out_r],
        #       xbuf, vbuf, bbuf, [dbuf], sems
        # mf:   xp, bp, coeffs, taus, out_x, [out_r], xbuf, bbuf, sems
        if mf is None:
            xp_ref, vals_ref, bp_ref = refs[0], refs[1], refs[2]
            dinv_ref = refs[3] if has_dinv else None
            coeffs_ref = None
            taus_ref = refs[3 + (1 if has_dinv else 0)]
            off = 4 + (1 if has_dinv else 0)
            y_ref = refs[off]
            r_ref = refs[off + 1] if with_residual else None
            off += 2 if with_residual else 1
            d_ref = refs[off] if with_dot else None
            off += 1 if with_dot else 0
            xbuf, vbuf, bbuf = refs[off], refs[off + 1], refs[off + 2]
            dbuf = refs[off + 3] if has_dinv else None
            sems = refs[off + 3 + (1 if has_dinv else 0)]
        else:
            xp_ref, bp_ref = refs[0], refs[1]
            vals_ref = dinv_ref = None
            coeffs_ref, taus_ref = refs[2], refs[3]
            y_ref = refs[4]
            r_ref = refs[5] if with_residual else None
            off = 6 if with_residual else 5
            d_ref = refs[off] if with_dot else None
            off += 1 if with_dot else 0
            xbuf, bbuf = refs[off], refs[off + 1]
            vbuf = dbuf = None
            sems = refs[off + 2]

        i = pl.program_id(0)
        slot = jax.lax.rem(i, jnp.int32(2))

        def dmas(s, blk):
            base = jnp.int32(blk) * jnp.int32(br)
            qbase = base + jnp.int32(slab_shift)
            ops = [
                pltpu.make_async_copy(xp_ref.at[pl.ds(base, win_x)],
                                      xbuf.at[jnp.int32(s)],
                                      sems.at[jnp.int32(s), 0]),
            ]
            if mf is None:
                ops.append(pltpu.make_async_copy(
                    vals_ref.at[:, pl.ds(qbase, win_v)],
                    vbuf.at[jnp.int32(s)], sems.at[jnp.int32(s), 1]))
            ops.append(pltpu.make_async_copy(
                bp_ref.at[pl.ds(base, win_v)], bbuf.at[jnp.int32(s)],
                sems.at[jnp.int32(s), 1 if mf is not None else 2]))
            if has_dinv:
                ops.append(pltpu.make_async_copy(
                    dinv_ref.at[pl.ds(qbase, win_v)],
                    dbuf.at[jnp.int32(s)], sems.at[jnp.int32(s), 3]))
            return ops

        @pl.when(i == 0)
        def _():
            for d in dmas(0, 0):
                d.start()

        @pl.when(i + 1 < n_blocks)
        def _():
            for d in dmas(jax.lax.rem(i + 1, jnp.int32(2)), i + 1):
                d.start()

        for d in dmas(slot, i):
            d.wait()

        col = jax.lax.broadcasted_iota(jnp.int32, (win_v, LANES), 1)
        bw = bbuf[slot].astype(cdt)     # (win_v, 128)
        if mf is None:
            vals = vbuf[slot]           # (k, win_v, 128) operand dtype
            def val(t):
                return vals[t].astype(cdt)
            dw = dbuf[slot].astype(cdt) if has_dinv else None
        else:
            row0 = i * jnp.int32(br) - jnp.int32((n_app - 1) * mr0)
            val, dw = _mf_block_vals(mf, coeffs_ref, row0, win_v, col,
                                     cdt)

        def apply_A(s):
            """A @ state on the compute region (win_v rows)."""
            acc = jnp.zeros((win_v, LANES), cdt)
            for t, _ in enumerate(offsets):
                a = jax.lax.slice_in_dim(s, ro[t], ro[t] + win_v, 1, 0)
                if rl[t] == 0:
                    w = a
                else:
                    b2 = jax.lax.slice_in_dim(s, ro[t] + 1,
                                              ro[t] + 1 + win_v, 1, 0)
                    shift = LANES - rl[t]
                    wa = pltpu.roll(a, jnp.int32(shift), 1)
                    wb = pltpu.roll(b2, jnp.int32(shift), 1)
                    w = jnp.where(col < shift, wa, wb)
                acc = acc + val(t) * w
            return acc

        s = xbuf[slot].astype(cdt)      # (win_x, 128) state, f32+
        for t in range(n_steps):
            tau = taus_ref[t]
            mid = jax.lax.slice_in_dim(s, mr0, mr0 + win_v, 1, 0)
            corr = tau * (bw - apply_A(s))
            if dw is not None:
                corr = corr * dw
            pieces = [mid + corr, jnp.zeros((Mr0, LANES), cdt)]
            if mr0:
                pieces.insert(0, jnp.zeros((mr0, LANES), cdt))
            s = jnp.concatenate(pieces, axis=0)
        y_ref[...] = jax.lax.slice_in_dim(
            s, n_app * mr0, n_app * mr0 + br, 1, 0).astype(dtype)
        if with_residual:
            r = bw - apply_A(s)
            r_ref[...] = jax.lax.slice_in_dim(
                r, (n_app - 1) * mr0, (n_app - 1) * mr0 + br, 1, 0
            ).astype(dtype)
        if with_dot:
            # dot epilogue: the block's final-x rows against the
            # aligned b rows (x row i*br+t <-> b-window row
            # (n_app-1)*mr0+t) — lanes stay unreduced; the caller's
            # cheap XLA combine sums the (nb, 128) partials
            xb = jax.lax.slice_in_dim(
                s, n_app * mr0, n_app * mr0 + br, 1, 0)
            bb = jax.lax.slice_in_dim(
                bw, (n_app - 1) * mr0, (n_app - 1) * mr0 + br, 1, 0)
            _part_store(d_ref, xb * bb)

    return kernel


def _shifted_sum(s, val, ro, rl, rows, col, cdt):
    """A @ state on `rows` compute rows: sum over the diagonals, in
    their order, of val(t) * (the state window `s` shifted by diagonal
    t: ro[t] window rows, rl[t] lanes). Both forms of the fused
    smoother call this, so a row's update is the same f32 sum in both."""
    acc = jnp.zeros((rows, LANES), cdt)
    for t in range(len(ro)):
        a = jax.lax.slice_in_dim(s, ro[t], ro[t] + rows, 1, 0)
        if rl[t] == 0:
            w = a
        else:
            b2 = jax.lax.slice_in_dim(s, ro[t] + 1, ro[t] + 1 + rows,
                                      1, 0)
            shift = LANES - rl[t]
            wa = pltpu.roll(a, jnp.int32(shift), 1)
            wb = pltpu.roll(b2, jnp.int32(shift), 1)
            w = jnp.where(col < shift, wa, wb)
        acc = acc + val(t) * w
    return acc


def _dia_carry_kernel(offsets, plan, n_steps, with_residual, has_dinv,
                      rows, slab_front, dtype, mf=None, with_dot=False):
    """Kernel body factory of the carry form (see the header above).
    `rows` is the row count of the flat (rows, 128) x / b operands, a
    multiple of 8; the last block may be short, and blocks past it are
    zero-filled in VMEM. `slab_front` is the quota slab's front padding
    (slab row slab_front == x row 0). Streams (x, b, values, dinv) keep
    a ring of their newest rows with one more block behind it, where
    the next block's DMA lands while this step computes; every step
    starts by moving all rings down by br rows."""
    br, n_app, nb = plan.br, plan.n_app, plan.n_blocks
    skew, lag = plan.skew, plan.lag
    back = _r8(plan.mr0)
    win = plan.win_x
    ring = _carry_rings(br, skew, lag, back, n_steps, with_residual,
                        with_dot)
    ro = [back + o // LANES for o in offsets]   # window row offset
    rl = [o % LANES for o in offsets]           # lane shift
    cdt = compute_dtype(dtype)
    rem = rows - (nb - 1) * br      # rows of the last block of x and b
    n_full = nb if rem == br else nb - 1
    k = len(offsets)
    # matrix-free: where the skew is a whole number of z-planes every
    # level sees the x / y coordinates of the step's newest rows and a
    # z coordinate a constant below them: one div/rem set a step
    per = mf.shape[0] * mf.shape[1] if mf is not None else None
    aligned = mf is not None and (skew * LANES) % per == 0

    def kernel(*refs):
        # scratch: x ring, b ring, [vals ring], [dinv ring],
        #          level rings 1..n_steps, [residual ring], sems
        (x_ref, vals_ref, b_ref, dinv_ref, coeffs_ref, taus_ref, y_ref,
         r_ref, d_ref), it = _smooth_refs(refs, mf, has_dinv,
                                          with_residual, with_dot)
        xr, brg = next(it), next(it)
        vr = next(it) if mf is None else None
        dr = next(it) if has_dinv else None
        lv = [next(it) for _ in range(n_steps)]
        rr = next(it) if with_residual else None
        sems = next(it)

        # (source, ring, ring rows, first source row, leading axis)
        streams = [(x_ref, xr, ring["x"], 0, False),
                   (b_ref, brg, ring["b"], 0, False)]
        if mf is None:
            streams.append((vals_ref, vr, ring["vals"], slab_front,
                            True))
        if has_dinv:
            streams.append((dinv_ref, dr, ring["vals"], slab_front,
                            False))
        level_rows = [ring["mid"]] * (n_steps - 1) + [ring["last"]]
        # (ring, its rows, rows behind it where a DMA lands, leading axis)
        carried = [(r_, ln, br, lead)
                   for _, r_, ln, _, lead in streams] \
            + [(r_, ln, 0, False) for r_, ln in zip(lv, level_rows)]
        if with_residual:
            carried.append((rr, ring["res"], 0, False))

        i = pl.program_id(0)

        def at(ref, lo, n, lead):
            return ref.at[:, pl.ds(lo, n)] if lead \
                else ref.at[pl.ds(lo, n)]

        def copy(s_, blk, n):
            src, dst, ln, base, lead = streams[s_]
            lo = blk * jnp.int32(br) + jnp.int32(base)
            return pltpu.make_async_copy(
                at(src, lo, n, lead), at(dst, ln, n, lead),
                sems.at[jnp.int32(s_)])

        def fetch(blk, start):
            """Start (or wait for) block `blk` of every stream landing
            behind its ring; zero-fill what no DMA writes."""
            def go(c):
                c.start() if start else c.wait()

            @pl.when(blk < n_full)
            def _():
                for s_ in range(len(streams)):
                    go(copy(s_, blk, br))

            if rem != br:
                @pl.when(blk == nb - 1)
                def _():
                    for s_, (_, dst, ln, base, lead) in \
                            enumerate(streams):
                        if dst is not xr and dst is not brg:
                            # a quota slab has the whole block
                            go(copy(s_, blk, br))
                            continue
                        if start:
                            dst[ln + rem:ln + br] = jnp.zeros(
                                (br - rem, LANES), dst.dtype)
                        go(copy(s_, blk, rem))

            if start:
                @pl.when(blk == nb)
                def _():
                    for _, dst, ln, _, lead in streams:
                        if lead:
                            dst[:, ln:ln + br] = jnp.zeros(
                                (k, br, LANES), dst.dtype)
                        else:
                            dst[ln:ln + br] = jnp.zeros(
                                (br, LANES), dst.dtype)

        @pl.when(i == 0)
        def _():
            # rows before row 0 are zero on every level; the block
            # behind a stream's ring is left to its DMA
            for r_, ln, _, lead in carried:
                if lead:
                    r_[:, 0:ln] = jnp.zeros((k, ln, LANES), r_.dtype)
                else:
                    r_[0:ln] = jnp.zeros((ln, LANES), r_.dtype)
            fetch(i, True)

        fetch(i, False)
        for r_, ln, landing, lead in carried:
            for c in range(0, ln + landing - br, br):
                m = min(br, ln + landing - br - c)
                if lead:
                    r_[:, c:c + m] = r_[:, c + br:c + br + m]
                else:
                    r_[c:c + m] = r_[c + br:c + br + m]
        fetch(i + 1, True)

        col = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
        zero = jnp.zeros((), cdt)
        if aligned:
            # the step's newest rows give every level its x / y masks:
            # one masked value plane a diagonal a STEP; a level adds
            # its z range, two compares of its own linear index
            row = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0)
            idx0 = (i * jnp.int32(br) + row) * jnp.int32(LANES) + col
            gx0, gy0, _ = _mf_coords(mf.shape, idx0)

            def cget(d):
                return coeffs_ref[d].astype(cdt)

            xy_ok = [_mf_ok(mf.shape, (gx0, gy0, None), (dx, dy, 0), True)
                     if dx or dy else None for dx, dy, _ in mf.shifts]
            vxy = [cget(d) if m is None else jnp.where(m, cget(d), zero)
                   for d, m in enumerate(xy_ok)]

        def level_vals(t):
            """(val, dinv rows | None, row-valid mask | None) on level
            t's rows, which start at x row i*br - t*skew. Where the
            mask is given, val(d) is exact on valid rows only and the
            caller zeroes the others (as a zero slab row would)."""
            if mf is None:
                lo = ring["vals"] - br - t * skew

                def val(d):
                    return vr[d, lo:lo + br].astype(cdt)
                dw = dr[lo:lo + br].astype(cdt) \
                    if has_dinv and t <= n_steps else None
                return val, dw, None
            if not aligned:
                row0 = i * jnp.int32(br) - jnp.int32(t * skew)
                return _mf_block_vals(mf, coeffs_ref, row0, br, col,
                                      cdt) + (None,)
            idx = idx0 - jnp.int32(t * skew * LANES)
            z_ok = {}
            for dz in sorted({sh[2] for sh in mf.shifts} | {0}):
                z_ok[dz] = (idx >= jnp.int32(max(0, -dz * per))) \
                    & (idx < jnp.int32(min(mf.n, mf.n - dz * per)))

            def val(d):
                dz = mf.shifts[d][2]
                return jnp.where(z_ok[dz], vxy[d], zero) if dz \
                    else vxy[d]

            def ok(d):
                dz = mf.shifts[d][2]
                return z_ok[dz] if xy_ok[d] is None \
                    else xy_ok[d] & z_ok[dz]
            dw = _mf_dinv(mf, cget, ok, z_ok[0], cdt) \
                if t <= n_steps else None
            return val, dw, z_ok[0]

        def level(t, below, below_rows):
            """b - A @ (level t-1) on level t's rows (zero on rows
            outside [0, n)), level t-1 on the same rows, the dinv
            rows."""
            lo = below_rows - (br + skew + back)
            s = below[lo:lo + win].astype(cdt)
            val, dw, valid = level_vals(t)
            lo_b = ring["b"] - br - t * skew
            res = brg[lo_b:lo_b + br].astype(cdt) \
                - _shifted_sum(s, val, ro, rl, br, col, cdt)
            if valid is not None:
                res = jnp.where(valid, res, zero)
            return res, jax.lax.slice_in_dim(s, back, back + br, 1, 0), dw

        below, below_rows = xr, ring["x"]
        for t in range(1, n_steps + 1):
            res, mid, dw = level(t, below, below_rows)
            corr = taus_ref[t - 1] * res
            if dw is not None:
                corr = corr * dw
            below, below_rows = lv[t - 1], level_rows[t - 1]
            below[below_rows - br:below_rows] = mid + corr
        lo_y = below_rows - (lag + 1) * br + n_steps * skew
        y_ref[...] = below[lo_y:lo_y + br].astype(dtype)
        if with_residual:
            res, _, _ = level(n_app, below, below_rows)
            rr[ring["res"] - br:ring["res"]] = res
            r_ref[...] = rr[0:br].astype(dtype)
        if with_dot:
            lo_b = ring["b"] - (lag + 1) * br
            _part_store(d_ref, below[lo_y:lo_y + br]
                        * brg[lo_b:lo_b + br].astype(cdt))

    return kernel


def _smooth_operands(xv, bv, vals_q, dinv_q, taus, mf, coeffs, dtype):
    """(operands, in_specs, streams) of the carry call in the order
    _smooth_refs reads them: the `streams` operands the kernel
    DMAs itself (x, [vals_q], b, [dinv_q]) stay in HBM; the k
    coefficients of the matrix-free form and the taus ride SMEM at the
    ACCUMULATION dtype — a bf16-rounded damping factor would throw
    away Chebyshev coefficient precision the f32 arithmetic can keep
    (identity for f32/f64 operands)."""
    cdt = compute_dtype(dtype)
    operands = [v for v in (xv, vals_q, bv, dinv_q) if v is not None]
    streams = len(operands)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * streams
    for v in ([coeffs] if mf is not None else []) + [taus]:
        operands.append(v.astype(cdt))
        in_specs.append(pl.BlockSpec(v.shape, lambda i: (jnp.int32(0),),
                                     memory_space=pltpu.SMEM))
    return operands, in_specs, streams


def _smooth_results(outs, n_out, n, with_dot):
    """x' (, r) as n-vectors (, the summed dot partials) of a call's
    (rows, 128) outputs."""
    vecs = [o.reshape(-1) for o in outs[:n_out]]
    vecs = [v if v.shape[0] == n else v[:n] for v in vecs]
    if with_dot:
        vecs.append(jnp.sum(outs[-1]))
    return tuple(vecs) if len(vecs) > 1 else vecs[0]


def _carry_call(plan, vals_q, dinv_q, taus, b, x, offsets, n,
                with_residual, mf, coeffs, with_dot, dtype, interpret):
    """The carry form's pallas_call: x and b go in as flat (rows, 128)
    views of the caller's vectors and x' (and r) come out the same way
    (a vector of no whole number of 8-row tiles is padded to one
    first; the grids of the flagship cells are whole)."""
    br, nb, lag = plan.br, plan.n_blocks, plan.lag
    n_steps = taus.shape[0]
    has_dinv = dinv_q is not None
    k = len(offsets)
    cdt = compute_dtype(dtype)
    rows = _r8(max(1, -(-n // LANES)))

    def flat(v):
        v = v.astype(dtype)
        if rows * LANES != n:
            v = jnp.pad(v, (0, rows * LANES - n))
        return v.reshape(rows, LANES)

    qf = smooth_quota_rows(offsets, n)[0] if mf is None else 0
    ring = _carry_rings(br, plan.skew, lag, _r8(plan.mr0), n_steps,
                        with_residual, with_dot)
    kernel = _dia_carry_kernel(offsets, plan, n_steps, with_residual,
                               has_dinv, rows, qf, dtype, mf=mf,
                               with_dot=with_dot)
    operands, in_specs, streams = _smooth_operands(
        flat(x), flat(b), vals_q, dinv_q, taus, mf, coeffs, dtype)
    scratch = [pltpu.VMEM((ring["x"] + br, LANES), dtype),
               pltpu.VMEM((ring["b"] + br, LANES), dtype)]
    if mf is None:
        scratch.append(pltpu.VMEM((k, ring["vals"] + br, LANES), dtype))
    if has_dinv:
        scratch.append(pltpu.VMEM((ring["vals"] + br, LANES), dtype))
    scratch += [pltpu.VMEM((ring["mid"], LANES), cdt)] * (n_steps - 1)
    scratch.append(pltpu.VMEM((ring["last"], LANES), cdt))
    if with_residual:
        scratch.append(pltpu.VMEM((ring["res"], LANES), cdt))
    scratch.append(pltpu.SemaphoreType.DMA((streams,)))

    def lagged(i):      # the output block that step i completes
        return (jnp.maximum(i - jnp.int32(lag), jnp.int32(0)),
                jnp.int32(0))

    n_out = 2 if with_residual else 1
    out_specs = [pl.BlockSpec((br, LANES), lagged,
                              memory_space=pltpu.VMEM)] * n_out
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), dtype)] * n_out
    if with_dot:
        out_specs.append(pl.BlockSpec((PART_ROWS, LANES), lagged,
                                      memory_space=pltpu.VMEM))
        out_shape.append(_part_shape(nb))
    outs = kernel_call(
        kernel,
        grid=(plan.steps,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        cost_estimate=pl.CostEstimate(
            flops=2 * k * plan.row_apps * LANES,
            # every stream read once, every output written once
            bytes_accessed=(streams + (k - 1 if mf is None else 0)
                            + n_out)
            * rows * LANES * jnp.dtype(dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)
    return _smooth_results(outs, n_out, n, with_dot)


def _one_block_call(plan, vals_q, dinv_q, taus, b, x, offsets,
                    num_rows, with_residual, mf, coeffs, with_dot, dtype,
                    interpret):
    """The one-block form's pallas_call, as it ran before the carry
    form came (PR 52 leaves it as it was): x and b are padded in-trace
    to the halo window of all the applications (a level of at most
    _BR_CAP rows)."""
    br, n_app, mr0, Mr0, win_x, win_v, nb = plan[:7]
    n_steps = taus.shape[0]
    has_dinv = dinv_q is not None
    k = len(offsets)
    ib = jnp.dtype(dtype).itemsize
    # quota slab row qf == x row 0; the window base is x row
    # -(n_app-1)*mr0, i.e. slab row slab_shift
    slab_shift = smooth_quota_rows(offsets, num_rows)[0] \
        - (n_app - 1) * mr0 if mf is None else 0
    n = num_rows
    # x window coordinates: front pad n_app*mr0 rows
    xp_rows = n_app * mr0 + nb * br + n_app * Mr0
    xp = jnp.zeros((xp_rows * LANES,), dtype)
    xp = jax.lax.dynamic_update_slice(xp, x.astype(dtype),
                                      (n_app * mr0 * LANES,))
    xp = xp.reshape(xp_rows, LANES)
    front_v = (n_app - 1) * mr0
    rows_v = front_v + nb * br + (n_app - 1) * Mr0
    bp = jnp.zeros((rows_v * LANES,), dtype)
    bp = jax.lax.dynamic_update_slice(bp, b.astype(dtype),
                                      (front_v * LANES,))
    bp = bp.reshape(rows_v, LANES)

    kernel = _dia_smooth_kernel(offsets, br, n_app, mr0, Mr0, win_x,
                                win_v, n_steps, with_residual, has_dinv,
                                nb, slab_shift, dtype, mf=mf,
                                with_dot=with_dot)
    if mf is None:
        n_sem = 4 if has_dinv else 3
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),          # xp
            pl.BlockSpec(memory_space=pl.ANY),          # vals_q
            pl.BlockSpec(memory_space=pl.ANY),          # bp
        ]
        operands = [xp, vals_q, bp]
        if has_dinv:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            operands.append(dinv_q)
    else:
        n_sem = 2
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),          # xp
            pl.BlockSpec(memory_space=pl.ANY),          # bp
            pl.BlockSpec((k,), lambda i: (jnp.int32(0),),
                         memory_space=pltpu.SMEM),      # coeffs
        ]
        # coefficients ride SMEM at the accumulation dtype (like taus)
        operands = [xp, bp, coeffs.astype(compute_dtype(dtype))]
    in_specs.append(pl.BlockSpec((n_steps,), lambda i: (jnp.int32(0),),
                                 memory_space=pltpu.SMEM))
    # taus stay at the ACCUMULATION dtype: a bf16-rounded damping
    # factor would throw away Chebyshev coefficient precision the f32
    # arithmetic can keep (identity for f32/f64 operands)
    operands.append(taus.astype(compute_dtype(dtype)))
    out_block = pl.BlockSpec((br, LANES), lambda i: (i, jnp.int32(0)),
                             memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((nb * br, LANES), dtype)
    scratch = [pltpu.VMEM((2, win_x, LANES), dtype)]
    if mf is None:
        scratch.append(pltpu.VMEM((2, k, win_v, LANES), dtype))
    scratch.append(pltpu.VMEM((2, win_v, LANES), dtype))
    if has_dinv:
        scratch.append(pltpu.VMEM((2, win_v, LANES), dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2, n_sem)))
    n_out = 2 if with_residual else 1
    nbytes = ((k + 2) * win_v + win_x + n_out * br) if mf is None \
        else (2 * win_v + win_x + n_out * br)
    out_specs_t = tuple([out_block] * n_out)
    out_shape_t = tuple([out_shape] * n_out)
    if with_dot:
        out_specs_t = out_specs_t + (_part_spec(),)
        out_shape_t = out_shape_t + (_part_shape(nb),)
    multi_out = with_residual or with_dot
    out = kernel_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs_t if multi_out else out_block,
        out_shape=out_shape_t if multi_out else out_shape,
        scratch_shapes=scratch,
        cost_estimate=pl.CostEstimate(
            flops=2 * n_app * k * nb * br * LANES,
            bytes_accessed=nbytes * nb * LANES * ib,
            transcendentals=0,
        ),
        # NOTE: `interpret` must be resolved by the (un-jitted) caller —
        # reading the _FORCE_INTERPRET global here would bake it into a
        # trace whose jit cache key does not carry it, so an interpret-
        # mode trace could outlive the forcing context
        interpret=interpret,
    )(*operands)
    outs = out if multi_out else (out,)
    vec_outs = outs[:-1] if with_dot else outs
    trimmed = []
    for o in vec_outs:
        v = o.reshape(-1)
        trimmed.append(v[:n] if v.shape[0] != n else v)
    if with_dot:
        trimmed.append(jnp.sum(outs[-1]))
    return tuple(trimmed) if multi_out else trimmed[0]


# Tallies [launches, row-applications] open while a cycle is traced
# (count_smooth_launches): every _dia_smooth_call a trace makes adds
# itself to each, so the hierarchy knows what ONE cycle launches
# (amg/hierarchy.AMG.cycle -> the smoother.dia_calls /
# smoother.dia_row_apps counters, raised after each solve).
_LAUNCH_TALLIES = []


@contextlib.contextmanager
def count_smooth_launches():
    tally = [0, 0]
    _LAUNCH_TALLIES.append(tally)
    try:
        yield tally
    finally:
        _LAUNCH_TALLIES.remove(tally)


def _dia_smooth_call(vals_q, dinv_q, taus, b, x, offsets, num_rows,
                     with_residual, mf=None, coeffs=None,
                     with_dot=False, interpret=False):
    """The fused smoother's one entry (the jitted program below, whose
    name the device trace shows), noted on the open launch tallies."""
    if _LAUNCH_TALLIES:
        plan = dia_smooth_plan(
            offsets, len(offsets), num_rows, taus.shape[0],
            with_residual, itemsize=jnp.dtype(
                x.dtype if mf is not None else vals_q.dtype).itemsize,
            coeffs=mf is not None)
        for tally in _LAUNCH_TALLIES:
            tally[0] += 1
            tally[1] += plan.row_apps
    return _dia_smooth_jit(vals_q, dinv_q, taus, b, x, offsets, num_rows,
                           with_residual, mf=mf, coeffs=coeffs,
                           with_dot=with_dot, interpret=interpret)


def _dia_smooth_program(vals_q, dinv_q, taus, b, x, offsets, num_rows,
                        with_residual, mf=None, coeffs=None,
                        with_dot=False, interpret=False):
    """Run the fused smoother kernel: x' (and r) after len(taus) damped
    sweeps, x and b read as zero outside [0, num_rows). `vals_q`
    (k, Q, 128) and `dinv_q` ((Q, 128) or None) are the QUOTA-PADDED
    operand slabs from ops.smooth (built once per setup,
    smooth_quota_rows layout). Caller must have checked
    dia_smooth_supported. Matrix-free form (`mf` spec + `coeffs` (k,)):
    vals_q/dinv_q are None — the A-operand stream vanishes and the k
    coefficients ride SMEM next to the taus. `with_dot`
    (postsmoother-only, exclusive with with_residual) appends the x'.b
    dot epilogue and returns (x', dot) — the Krylov shell's cycle-borne
    r.z reduction. A level of more than one block takes the carry form
    (no copy of x or b, every row computed once); a one-block level
    pads its two vectors to the halo window in-trace."""
    # NOTE: `interpret` must be resolved by the (un-jitted) caller —
    # reading the _FORCE_INTERPRET global here would bake it into a
    # trace whose jit cache key does not carry it, so an interpret-mode
    # trace could outlive the forcing context
    assert not (with_dot and with_residual)
    n_steps = taus.shape[0]
    if mf is None:
        k = vals_q.shape[0]
        dtype = vals_q.dtype
    else:
        k = len(offsets)
        dtype = x.dtype
    ib = jnp.dtype(dtype).itemsize
    plan = dia_smooth_plan(offsets, k, num_rows, n_steps, with_residual,
                           itemsize=ib, coeffs=mf is not None)
    if mf is None:
        qf, qc, qb = smooth_quota_rows(offsets, num_rows)
        assert vals_q.shape[1] == qf + qc + qb, \
            f"fused slab rows {vals_q.shape[1]} != quota {qf + qc + qb}"
    if plan.lag:
        return _carry_call(plan, vals_q, dinv_q, taus, b, x, offsets,
                           num_rows, with_residual, mf, coeffs,
                           with_dot, dtype, interpret)
    return _one_block_call(plan, vals_q, dinv_q, taus, b, x, offsets,
                           num_rows, with_residual, mf, coeffs,
                           with_dot, dtype, interpret)


# the device trace names a jitted program after its function: the
# readers (kernels.pallas_busy_share, cycle.glue_busy_share, the scope
# table) match `_dia_*_call*`
_dia_smooth_program.__name__ = "_dia_smooth_call"
_dia_smooth_jit = jax.jit(_dia_smooth_program, static_argnames=(
    "offsets", "num_rows", "with_residual", "mf", "with_dot",
    "interpret"))


def _dia_stencil_smooth_call(coeffs, taus, b, x, spec, with_residual,
                             with_dot=False, interpret=False):
    """Matrix-free fused smoother: the dia_smooth kernel with the
    quota-padded vals/dinv slabs replaced by k SMEM scalars. `spec` is
    the level's StencilSpec (ops.stencil); caller must have checked
    stencil_smooth_supported."""
    return _dia_smooth_call(None, None, taus, b, x, spec.offsets,
                            spec.n, with_residual, mf=spec,
                            coeffs=coeffs, with_dot=with_dot,
                            interpret=interpret)


def declined_families():
    """Kernel families that decline on the compiled-for-chip branch,
    with the compiler's refusal: what chip_smoke.py prints beside the
    kernel census. Empty off the chip and in interpret mode."""
    if pallas_backend() != "mosaic":
        return {}
    return {
        "bf16 operand windows of dia_smooth / dia_spmv_dot (slab and "
        "stencil twins)":
        "Mosaic failed to compile TPU kernel: Slice shape along "
        "dimension 0 must be aligned to tiling (8), but is 307",
    }


# ---------------------------------------------------------------------------
# Krylov shell fusion: SpMV + dot epilogues and the single-pass CG
# update
#
# The fused smoother stops at the preconditioner boundary: a
# CG/PCG iteration still runs a standalone SpMV, three separate dot
# reductions, and bare axpy updates — each a full n-vector HBM pass
# outside the cycle. Two kernels close the shell:
#
# - SPMV + DOT (`_dia_spmv_dot_call`): A.p with a per-block d.Ap
#   partial-sum epilogue ((nb, 128) partials, rows reduced in-kernel,
#   lanes combined by a cheap XLA sum), an optional PROLOGUE folding
#   the direction update p = z + beta*p_prev (beta a scalar in SMEM; the halo rows
#   recompute the update redundantly so the window stays exact), and
#   an optional second Ap.Ap self-dot (BiCGStab's t.t). The x-window
#   layout/DMA pipeline is the plain dia_spmv kernel's; operands
#   follow the fused-suite dtype rules (f32/bf16 streams, f32
#   accumulation, f32 partials).
#
# - CG UPDATE (`_cg_update_call`): x += alpha p and r -= alpha Ap in
#   one auto-pipelined elementwise pass with an r'.r' dot epilogue, so
#   the monitor's residual norm is a free by-product.
#
# Padding rows/lanes carry zero vectors (and zero matrix values), so
# the partial dots are exact without masking.
# ---------------------------------------------------------------------------


def dia_spmv_dot_supported(A, x_dtype) -> bool:
    """Trace-time gate for the SpMV+dot (Krylov shell) Pallas path.
    Wider than dia_spmv_supported: bf16 operands are admitted under
    the fused-suite rules (f32 accumulation)."""
    if pallas_backend() is None:
        return False
    if not smooth_dtype_ok(A, x_dtype):
        return False
    if A.num_rows != A.num_cols:
        return False
    k, rows_pad, _ = A.dia_vals.shape
    left, halo_rows, br = _layout(A.dia_offsets, k, A.num_rows)
    if rows_pad % br != 0:
        return False
    ib = jnp.dtype(x_dtype).itemsize
    win = br + halo_rows
    # worst-case variant: beta prologue (2 windows + p output) plus a
    # streamed dot operand and both partial outputs
    vmem = 2 * k * br * LANES * ib \
        + 2 * 2 * win * LANES * ib \
        + 2 * 3 * br * LANES * ib
    if ib < 4:
        vmem += (2 * win + 2 * br) * LANES * 4
    return vmem <= _VMEM_BUDGET + 4 * 1024 * 1024


def _dia_spmv_dot_kernel(offsets, left, br, halo_rows, n_blocks, dtype,
                         with_beta, with_d, self_dot, mf=None):
    """Kernel body factory. Window coordinates are the plain dia_spmv
    kernel's (x row r lives at window row left//128 + r); the dot
    epilogue reduces rows in-kernel and leaves the 128 lanes to the
    caller's XLA combine. `with_d` streams a separate dot operand
    (auto-pipelined block, no halo) in place of p itself."""
    ro = [(left + o) // LANES for o in offsets]
    rl = [(left + o) % LANES for o in offsets]
    win_rows = br + halo_rows
    prow = left // LANES
    cdt = compute_dtype(dtype)

    def kernel(*refs):
        # refs: pp, [zp], vals|coeffs, [d], [beta], [p_out], ap,
        #       dot, [sdot], pbuf, [zbuf], sems
        off = 0
        pp_ref = refs[off]
        off += 1
        zp_ref = refs[off] if with_beta else None
        off += 1 if with_beta else 0
        if mf is None:
            vals_ref, coeffs_ref = refs[off], None
        else:
            vals_ref, coeffs_ref = None, refs[off]
        off += 1
        d_ref = refs[off] if with_d else None
        off += 1 if with_d else 0
        beta_ref = refs[off] if with_beta else None
        off += 1 if with_beta else 0
        pout_ref = refs[off] if with_beta else None
        off += 1 if with_beta else 0
        ap_ref, dot_ref = refs[off], refs[off + 1]
        off += 2
        sdot_ref = refs[off] if self_dot else None
        off += 1 if self_dot else 0
        pbuf = refs[off]
        off += 1
        zbuf = refs[off] if with_beta else None
        off += 1 if with_beta else 0
        sems = refs[off]

        i = pl.program_id(0)
        slot = jax.lax.rem(i, jnp.int32(2))

        def dmas(s, blk):
            base = jnp.int32(blk) * jnp.int32(br)
            ops = [pltpu.make_async_copy(
                pp_ref.at[pl.ds(base, win_rows)],
                pbuf.at[jnp.int32(s)], sems.at[jnp.int32(s), 0])]
            if with_beta:
                ops.append(pltpu.make_async_copy(
                    zp_ref.at[pl.ds(base, win_rows)],
                    zbuf.at[jnp.int32(s)], sems.at[jnp.int32(s), 1]))
            return ops

        @pl.when(i == 0)
        def _():
            for d in dmas(0, 0):
                d.start()

        @pl.when(i + 1 < n_blocks)
        def _():
            for d in dmas(jax.lax.rem(i + 1, jnp.int32(2)), i + 1):
                d.start()

        for d in dmas(slot, i):
            d.wait()

        if with_beta:
            # direction-update prologue over the WHOLE window: the
            # halo rows feed the shifts, so they need the updated p
            # too (redundant recompute, zero extra HBM)
            s = zbuf[slot].astype(cdt) \
                + beta_ref[0] * pbuf[slot].astype(cdt)
        else:
            s = pbuf[slot].astype(cdt)

        col = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
        if mf is None:
            def val(t):
                return vals_ref[t].astype(cdt)
        else:
            row0 = i * jnp.int32(br)
            val, _dw = _mf_block_vals(mf, coeffs_ref, row0, br, col,
                                      cdt)

        acc = jnp.zeros((br, LANES), cdt)
        for t, _o in enumerate(offsets):
            a = jax.lax.slice_in_dim(s, ro[t], ro[t] + br, 1, 0)
            if rl[t] == 0:
                w = a
            else:
                b2 = jax.lax.slice_in_dim(s, ro[t] + 1, ro[t] + 1 + br,
                                          1, 0)
                shift = LANES - rl[t]
                wa = pltpu.roll(a, jnp.int32(shift), 1)
                wb = pltpu.roll(b2, jnp.int32(shift), 1)
                w = jnp.where(col < shift, wa, wb)
            acc = acc + val(t) * w

        p_blk = jax.lax.slice_in_dim(s, prow, prow + br, 1, 0)
        if with_beta:
            pout_ref[...] = p_blk.astype(dtype)
        ap_ref[...] = acc.astype(dtype)
        dvec = d_ref[...].astype(cdt) if with_d else p_blk
        _part_store(dot_ref, dvec * acc)
        if self_dot:
            _part_store(sdot_ref, acc * acc)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "offsets", "num_rows", "self_dot", "mf", "interpret"))
def _dia_spmv_dot_call(dia_vals, p, z, beta, d, offsets, num_rows,
                       self_dot=False, mf=None, coeffs=None,
                       interpret=False):
    """Fused SpMV + dot epilogue. Returns (Ap, d.Ap[, Ap.Ap]) with
    d = p when no separate dot operand is streamed; with the beta
    prologue (z is not None), p' = z + beta*p is computed in-window
    and the returns become (p', Ap', p'.Ap'[, ...]). The dot scalars
    are LOCAL f32 sums — distributed callers psum them (packed).
    Caller must have checked dia_spmv_dot_supported (slab mode) or
    the stencil twin's gate (mf mode)."""
    with_beta = z is not None
    with_d = d is not None
    if mf is None:
        k, rows_pad, _ = dia_vals.shape
        dtype = dia_vals.dtype
    else:
        k = len(offsets)
        dtype = p.dtype
    left, halo_rows, br = _layout(offsets, k, num_rows)
    if mf is None:
        nb = rows_pad // br
    else:
        rows128 = max(1, -(-num_rows // LANES))
        nb = -(-rows128 // br)
        rows_pad = nb * br
    n = num_rows
    win_rows = br + halo_rows
    xp_rows = rows_pad + halo_rows
    cdt = compute_dtype(dtype)

    def _pad_win(v):
        vp = jnp.zeros((xp_rows * LANES,), dtype)
        vp = jax.lax.dynamic_update_slice(vp, v.astype(dtype), (left,))
        return vp.reshape(xp_rows, LANES)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]          # pp
    operands = [_pad_win(p)]
    if with_beta:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # zp
        operands.append(_pad_win(z))
    if mf is None:
        in_specs.append(pl.BlockSpec(
            (k, br, LANES), lambda i: (jnp.int32(0), i, jnp.int32(0)),
            memory_space=pltpu.VMEM))
        operands.append(dia_vals)
    else:
        in_specs.append(pl.BlockSpec((k,), lambda i: (jnp.int32(0),),
                                     memory_space=pltpu.SMEM))
        operands.append(coeffs.astype(cdt))
    if with_d:
        dp = jnp.zeros((rows_pad * LANES,), dtype)
        dp = jax.lax.dynamic_update_slice(dp, d.astype(dtype), (0,))
        in_specs.append(pl.BlockSpec((br, LANES),
                                     lambda i: (i, jnp.int32(0)),
                                     memory_space=pltpu.VMEM))
        operands.append(dp.reshape(rows_pad, LANES))
    if with_beta:
        in_specs.append(pl.BlockSpec((1,), lambda i: (jnp.int32(0),),
                                     memory_space=pltpu.SMEM))
        operands.append(jnp.reshape(beta, (1,)).astype(cdt))

    blk = pl.BlockSpec((br, LANES), lambda i: (i, jnp.int32(0)),
                       memory_space=pltpu.VMEM)
    part = _part_spec()
    vec_shape = jax.ShapeDtypeStruct((rows_pad, LANES), dtype)
    part_shape = _part_shape(nb)
    out_specs = ([blk] if with_beta else []) + [blk, part] \
        + ([part] if self_dot else [])
    out_shape = ([vec_shape] if with_beta else []) \
        + [vec_shape, part_shape] + ([part_shape] if self_dot else [])

    scratch = [pltpu.VMEM((2, win_rows, LANES), dtype)]
    if with_beta:
        scratch.append(pltpu.VMEM((2, win_rows, LANES), dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2, 2 if with_beta else 1)))

    kernel = _dia_spmv_dot_kernel(offsets, left, br, halo_rows, nb,
                                  dtype, with_beta, with_d, self_dot,
                                  mf=mf)
    ib = jnp.dtype(dtype).itemsize
    streams = (0 if mf is not None else k) + 2 * (2 if with_beta else 1) \
        + (1 if with_d else 0)
    outs = kernel_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        cost_estimate=pl.CostEstimate(
            flops=2 * (k + 2) * nb * br * LANES,
            bytes_accessed=streams * nb * br * LANES * ib,
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)
    idx = 0
    res = []
    for _ in range(2 if with_beta else 1):
        v = outs[idx].reshape(-1)
        res.append(v[:n] if v.shape[0] != n else v)
        idx += 1
    res.append(jnp.sum(outs[idx]))
    idx += 1
    if self_dot:
        res.append(jnp.sum(outs[idx]))
    return tuple(res)


def dia_spmv_dot(A, p, z=None, beta=None, d=None, self_dot=False,
                 interpret=False):
    """Fused DIA SpMV + dot epilogue(s); caller must have checked
    dia_spmv_dot_supported. See _dia_spmv_dot_call for the return
    shapes."""
    return _dia_spmv_dot_call(A.dia_vals, p, z, beta, d,
                              A.dia_offsets, A.num_rows,
                              self_dot=self_dot,
                              interpret=interpret or _FORCE_INTERPRET)


def cg_update_supported(x_dtype) -> bool:
    """Trace-time gate for the single-pass CG update kernel."""
    if pallas_backend() is None:
        return False
    return jnp.dtype(x_dtype).name in SMOOTH_DTYPES


def _cg_update_kernel(dtype):
    cdt = compute_dtype(dtype)

    def kernel(x_ref, p_ref, r_ref, ap_ref, alpha_ref, xo_ref, ro_ref,
               rr_ref):
        a = alpha_ref[0]
        xn = x_ref[...].astype(cdt) + a * p_ref[...].astype(cdt)
        rn = r_ref[...].astype(cdt) - a * ap_ref[...].astype(cdt)
        xo_ref[...] = xn.astype(dtype)
        ro_ref[...] = rn.astype(dtype)
        _part_store(rr_ref, rn * rn)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cg_update_call(x, p, r, ap, alpha, interpret=False):
    """Single-pass CG state update: (x + alpha p, r - alpha Ap,
    r'.r') in one auto-pipelined elementwise kernel — the residual
    norm the monitor wants becomes a free epilogue instead of a
    standalone blas.norm stream. The rr scalar is the LOCAL f32 sum.
    Caller must have checked cg_update_supported."""
    dtype = x.dtype
    n = x.shape[0]
    cdt = compute_dtype(dtype)
    rows128 = max(1, -(-n // LANES))
    br = pick_block_rows(6, rows128)
    nb = -(-rows128 // br)
    rows_pad = nb * br

    def padv(v):
        vp = jnp.zeros((rows_pad * LANES,), dtype)
        vp = jax.lax.dynamic_update_slice(vp, v.astype(dtype), (0,))
        return vp.reshape(rows_pad, LANES)

    blk = pl.BlockSpec((br, LANES), lambda i: (i, jnp.int32(0)),
                       memory_space=pltpu.VMEM)
    part = _part_spec()
    xo, ro, rr = kernel_call(
        _cg_update_kernel(dtype),
        grid=(nb,),
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((1,), lambda i: (jnp.int32(0),),
                               memory_space=pltpu.SMEM)],
        out_specs=(blk, blk, part),
        out_shape=(jax.ShapeDtypeStruct((rows_pad, LANES), dtype),
                   jax.ShapeDtypeStruct((rows_pad, LANES), dtype),
                   _part_shape(nb)),
        cost_estimate=pl.CostEstimate(
            flops=5 * nb * br * LANES,
            bytes_accessed=6 * nb * br * LANES
            * jnp.dtype(dtype).itemsize,
            transcendentals=0),
        interpret=interpret,
    )(padv(x), padv(p), padv(r), padv(ap),
      jnp.reshape(alpha, (1,)).astype(cdt))
    xv = xo.reshape(-1)
    rv = ro.reshape(-1)
    if xv.shape[0] != n:
        xv = xv[:n]
        rv = rv[:n]
    return xv, rv, jnp.sum(rr)


# ---------------------------------------------------------------------------
# Row-bounded pass over a Krylov basis (ops/blas.py basis_pass: GMRES /
# FGMRES's CGS2 step and the way back from the Krylov coordinates)
#
# The basis is (rows, R, 128), `nlive` of its rows live, and nlive is a
# traced value: a grid over the rows would pay a grid step for every
# dead row, and a block over all rows would fetch them. So the grid is
# the column blocks alone, the basis stays in HBM (pl.ANY), and each
# step DMAs the nlive live (br, 128) row blocks of its column block
# into one of two VMEM slots, the next block's while this one
# computes. With every live row of a column block resident, the
# projection w' = w - sum coef[k] V[k] and the dots <V[k], w'> of the
# NEXT Gram-Schmidt pass come from the same reading.
# ---------------------------------------------------------------------------

_BASIS_CHUNK = 64            # rows of 128 lanes the arithmetic holds


def basis_block_rows(n_rows: int, rows128: int) -> int:
    """Rows of 128 lanes in a column block of an n_rows-row basis, as
    pick_block_rows sizes a block: the two slots of n_rows row blocks
    fit the budget; a basis of fewer rows128 is one block of whole
    tiles."""
    budget_rows = _VMEM_BUDGET // (2 * n_rows * LANES * 4)
    br = 512
    while br > 8 and br > budget_rows:
        br //= 2
    return min(br, -(-rows128 // 8) * 8)


def basis_padded_rows(n_rows: int, n: int) -> int:
    """R of an (n_rows, R, 128) basis of n-vectors: whole column blocks."""
    rows128 = max(1, -(-n // LANES))
    br = basis_block_rows(n_rows, rows128)
    return -(-rows128 // br) * br


def basis_pass_supported(V, w) -> bool:
    """Trace-time gate of the kernel pass: f32 through Mosaic or the
    interpreter, on a slab cut into whole column blocks. Everything
    else (f64, the CPU rig) takes the plain twin in ops/blas.py."""
    if pallas_backend() is None:
        return False
    if V.dtype != jnp.float32 or w.dtype != jnp.float32:
        return False
    n_rows, rows128, _ = V.shape
    return rows128 % basis_block_rows(n_rows, rows128) == 0


def _basis_pass_kernel(n_rows, br, n_blocks, project):
    ch = min(_BASIS_CHUNK, br)

    def fold(p):
        # (ch, 128) -> (8, 128): whole-vreg adds, no cross-lane work
        return jnp.sum(p.reshape(ch // 8, 8, LANES), axis=0)

    def kernel(nlive_ref, coef_ref, v_ref, w_ref, *rest):
        if project:
            wo_ref, dots_ref, vbuf, sems = rest
        else:
            dots_ref, vbuf, sems = rest
        c = pl.program_id(0)
        slot = jax.lax.rem(c, jnp.int32(2))
        nlive = nlive_ref[0]

        def rows_dma(s, blk, wait):
            def one(k, carry):
                cp = pltpu.make_async_copy(
                    v_ref.at[k, pl.ds(blk * jnp.int32(br), br)],
                    vbuf.at[s, k], sems.at[s])
                cp.wait() if wait else cp.start()
                return carry
            jax.lax.fori_loop(jnp.int32(0), nlive, one, jnp.int32(0))

        @pl.when(c == 0)
        def _():
            dots_ref[...] = jnp.zeros(dots_ref.shape, jnp.float32)
            rows_dma(jnp.int32(0), jnp.int32(0), False)

        @pl.when(c + 1 < n_blocks)
        def _():
            rows_dma(jax.lax.rem(c + 1, jnp.int32(2)), c + 1, False)

        rows_dma(slot, c, True)

        def chunk(t, carry):
            rows = pl.ds(pl.multiple_of(t * jnp.int32(ch), ch), ch)
            acc = w_ref[rows, :]
            if project:
                acc = jax.lax.fori_loop(
                    jnp.int32(0), nlive,
                    lambda k, a: a - coef_ref[k] * vbuf[slot, k, rows, :],
                    acc)
                wo_ref[rows, :] = acc

            def dot_row(k, carry):
                dots_ref[k] += fold(vbuf[slot, k, rows, :] * acc)
                return carry
            jax.lax.fori_loop(jnp.int32(0), nlive, dot_row, jnp.int32(0))
            dots_ref[n_rows] += fold(acc * acc)
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(br // ch), chunk,
                          jnp.int32(0))

    return kernel


@functools.partial(jax.jit, static_argnames=("project", "interpret"))
def _basis_pass_call(V, w, coef, nlive, project, interpret=False):
    """(w', dots, nrm) of blas.basis_pass in one reading of the live
    rows; `project=False` leaves w as it is (coef is not read) and
    writes no vector. dots and nrm are the LOCAL f32 sums. Caller must
    have checked basis_pass_supported."""
    n_rows, rows128, _ = V.shape
    br = basis_block_rows(n_rows, rows128)
    n_blocks = rows128 // br
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((br, LANES), lambda c: (c, jnp.int32(0)),
                       memory_space=pltpu.VMEM)
    # row k < n_rows: the (8, 128) partial sums of <V[k], w'>; row
    # n_rows: those of <w', w'>. One block, resident over the grid.
    parts = jax.ShapeDtypeStruct((n_rows + 1, PART_ROWS, LANES),
                                 jnp.float32)
    parts_spec = pl.BlockSpec(
        parts.shape, lambda c: (jnp.int32(0),) * 3,
        memory_space=pltpu.VMEM)
    vec = jax.ShapeDtypeStruct((rows128, LANES), w.dtype)
    live = n_rows // 2 + 1      # the estimate's mean of nlive
    out = kernel_call(
        _basis_pass_kernel(n_rows, br, n_blocks, project),
        grid=(n_blocks,),
        in_specs=[smem((1,), lambda c: (jnp.int32(0),)),
                  smem((n_rows,), lambda c: (jnp.int32(0),)),
                  pl.BlockSpec(memory_space=pl.ANY), blk],
        out_specs=(blk, parts_spec) if project else parts_spec,
        out_shape=(vec, parts) if project else parts,
        scratch_shapes=[
            pltpu.VMEM((2, n_rows, br, LANES), V.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=(4 if project else 2) * live * rows128 * LANES,
            bytes_accessed=(live + (2 if project else 1))
            * rows128 * LANES * 4,
            transcendentals=0),
        interpret=interpret,
    )(jnp.reshape(nlive, (1,)).astype(jnp.int32), coef, V, w)
    w_out, sums = out if project else (w, out)
    sums = jnp.sum(sums, axis=(1, 2))
    return w_out, sums[:n_rows], sums[n_rows]
