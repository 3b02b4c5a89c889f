"""Pallas TPU SpMV kernel for the windowed-ELL (SWELL) layout —
unstructured matrices.

The reference's workhorse is a CUDA csrmv over arbitrary CSR
(src/multiply.cu:74-121); AMG coarse operators and the P/R transfer
operators are exactly such matrices. On TPU the XLA lowering of the
gather `x[col_indices]` is catastrophically slow (tens of ms per call at
level sizes) and Mosaic has no arbitrary-gather primitive — but it DOES
support `take_along_axis` within a (rows, 128) tile along lanes. This
kernel builds an SpMV out of that primitive:

- rows are tiled into super-blocks of 1024 (8 sublane groups x 128
  lanes); each super-block's columns all fall inside a window
  [c0_b, c0_b + W) of x, where W is the static max block span (AMG and
  interpolation matrices inherit the fine grid's locality, so
  W ~ bandwidth << num_cols);
- per block, the x window is DMA'd HBM->VMEM (double-buffered, like the
  DIA kernel) as (W/128, 128) chunks;
- entry slots are stored slot-major as (8, kpad, 128): sublane group =
  row-group, sublane = ELL slot, lane = row-in-group. On a group's
  (kpad, 128) tile the gather decomposes per 128-wide window chunk c:
  take_along_axis(chunk broadcast, lo, axis=1) selected where the local
  column's hi bits == c;
- the gather runs ROW GROUP by row group, over that group's own list
  of window chunks: beside each block the layout keeps, for each of
  its 8 row groups, a count and the ascending list of the chunks
  (local to the block's c0) in which the group has a column
  (`swell_nchunk`, (nb, 8, 1 + L); `group_chunk_lists`). A block of a
  coarse operator on a 3-D grid touches a few bands of its span, its
  own z-plane's and its neighbours', and a group of 128 rows a sixth to
  a quarter of the chunks its block does, so the lists hold under a
  fifth of what a loop over the block's chunks against the whole
  (8*kpad, 128) tile visited (PR 48; the forms before it, a loop over
  the span and a bit an 8-chunk slab, are PR 47's). A block's lists
  ride the pipeline into SMEM with its entry slabs; the loop reads a
  chunk index from there, UNROLL list entries an iteration and no
  branch (a list is padded to L by repeats of its last chunk: the
  select is idempotent), then y[g] = sum over slots of acc * vals.
  GROUPS row groups share a loop, as long as the longest of their
  lists: one group's chain of scalar read, row load, gather and select
  left some 80 ns of latency an iteration bare, two fill each other's
  (on the chip 6.2-6.6 ns a vreg-step for 7.5-8.1 at kpad 32: PR 48).

Traffic per block: 8*kpad*128 values + cols (the ELL-padded minimum)
plus a W-element window of x and the block's lists. Compute is ~3 VPU
ops per (kpad, 128) group tile per listed chunk: ceil(kpad / 8) vregs,
a "vreg-step" each (`vreg_steps` counts them: the kernel's clock) —
compute-bound relative to HBM, but 50-500x faster than the XLA gather
form it replaces. float32 only (like the DIA kernel);
the XLA gather form below covers f64/CPU/batched callers.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_spmv import _VMEM_BUDGET, kernel_call

LANES = 128
SUBS = 8                      # sublane groups per super-block
BLOCK_ROWS = SUBS * LANES     # rows per super-block
SWELL_MAX_W = 512 * 1024      # max window elements (2 MB f32 a buffer)
SWELL_MAX_K = 256             # max padded slots per row


UNROLL = 8                    # list entries of a group a loop iteration
GROUPS = 2                    # row groups that share a loop


def pad_chunk_lists(counts, flat, nb):
    """(nb, 8, 1 + L) int32 from the groups' distinct-chunk counts
    (nb * 8,) and their chunks back to back, ascending a group: column
    0 the count, then the list, padded to L (the longest, rounded up to
    whole iterations of UNROLL) by repeats of the group's last chunk;
    an empty group's row is zeros."""
    counts = counts.astype(np.int64)
    L = max(UNROLL, -(-int(counts.max(initial=0)) // UNROLL) * UNROLL)
    start = np.cumsum(counts) - counts
    gid = np.repeat(np.arange(nb * SUBS), counts)
    out = np.zeros((nb * SUBS, 1 + L), np.int32)
    out[gid, 1 + np.arange(flat.shape[0]) - start[gid]] = flat
    # a list ascends from >= 0, so its running maximum repeats its last
    # chunk through the padding
    np.maximum.accumulate(out[:, 1:], axis=1, out=out[:, 1:])
    out[:, 0] = counts
    return out.reshape(nb, SUBS, 1 + L)


def group_chunk_lists(ci, row_ids, c0, nb, w128):
    """`swell_nchunk` from the pattern (numpy form of native
    amgx_swell_chunklists): for each row group of 128 rows the distinct
    128-column chunks of its block's window its entries fall in.
    `row_ids` is each entry's row, `c0` each block's first window
    column."""
    gid = row_ids // LANES
    chunk = (ci.astype(np.int64) - c0[row_ids // BLOCK_ROWS]) // LANES
    key = np.unique(gid * np.int64(w128) + chunk)
    return pad_chunk_lists(
        np.bincount(key // w128, minlength=nb * SUBS),
        (key % w128).astype(np.int32), nb)


def listed_chunks(nchunk) -> int:
    """The distinct chunks a layout's row groups list, all together.
    The padding repeats are not counted: the number is the pattern's,
    not the unroll's."""
    return int(np.asarray(nchunk)[:, :, 0].sum(dtype=np.int64))


def tile_vregs(kpad) -> int:
    """The (8, 128) vregs of a row group's (kpad, 128) tile."""
    return -(-int(kpad) // SUBS)


def vreg_steps(nchunk, kpad) -> int:
    """Vreg-steps one application of a layout costs: over its row
    groups, the distinct chunks listed x the vregs of a group's tile."""
    return listed_chunks(nchunk) * tile_vregs(kpad)


# The SWELL kernels' clock, as the layout choice reads it (PR 51): one
# application costs, per chunk a row group lists, a fixed part (the
# scalar read of the list, the row load, the loop's share) and a part
# per (8, 128) vreg of the group's (kpad, 128) tile that goes through
# gather-select, and per 1,024-row block a grid step with its window's
# DMA; a tile of ONE vreg reads under that line. Fitted to 53 timed
# forms of cells 2, 9 and 10's own operators on the chip (one layout
# and row-split at K 4 ... 64; rms error 3.9%, PERF.md section 3).
SWELL_ENTRY_NS = 6.0          # a listed chunk, tiles of two vregs up
SWELL_VREG_NS = 4.65          # a vreg-step of such a tile
SWELL_ONE_VREG_NS = 9.2       # a listed chunk of a one-vreg tile
SWELL_BLOCK_NS = 510.0        # a block

# The row-split form as a CHOICE (`split_pays`): counted only for an
# operator of SPLIT_MIN_NNZ non-zeros whose one layout would pad
# SPLIT_SCREEN slots a non-zero (even rows never pay for the count),
# taken only where the model puts it SPLIT_MARGIN under the one layout
# (2.5 times the fit's rms error) and SPLIT_MIN_SAVING_S an application
# (on a small operator a second launch is more than any share).
SPLIT_PIECES = (4, 8, 16, 32, 64, 128)   # entries a piece, candidates
SPLIT_MIN_NNZ = 20_000
SPLIT_SCREEN = 2.25
SPLIT_MARGIN = 0.10
SPLIT_MIN_SAVING_S = 20e-6


def model_seconds(listed, kpad, blocks) -> float:
    """What the clock above predicts for one application of a layout
    of `blocks` blocks whose row groups list `listed` chunks over tiles
    of `kpad` slots."""
    v = tile_vregs(kpad)
    chunk = SWELL_ONE_VREG_NS if v == 1 \
        else SWELL_ENTRY_NS + SWELL_VREG_NS * v
    return 1e-9 * (int(listed) * chunk + int(blocks) * SWELL_BLOCK_NS)


_notes = threading.local()


@contextlib.contextmanager
def collect_layout_notes():
    """What the layout choice said while the block ran, as a dict of
    lists in order: `declined`, the reasons `swell_budget` said no, and
    `chosen`, the row-split forms `split_pays` took over a layout the
    budget admits (a set-up's layout spans carry both as args)."""
    outer = getattr(_notes, "log", None)
    log = _notes.log = {"declined": [], "chosen": []}
    try:
        yield log
    finally:
        _notes.log = outer


def _note(kind: str, what: str):
    log = getattr(_notes, "log", None)
    if log is not None:
        log[kind].append(what)


def note_chosen(words: str):
    """Counts a row-split form taken by `split_pays` and built
    (`amg.layout.split.chosen`) and hands the choice in words to
    whoever collects them."""
    from ..telemetry import metrics as _tm
    _tm.inc("amg.layout.split.chosen")
    _note("chosen", words)


def _declined(reason: str):
    """Counts a decline (`amg.layout.declined.<reason>`) and hands the
    reason to whoever collects them."""
    from ..telemetry import metrics as _tm
    _tm.inc(f"amg.layout.declined.{reason}")
    _note("declined", reason)


def _budget(kmax, w128_raw, nb, nnz):
    """(kpad, w128) of a layout that pays, the reason (a str) why it
    does not, or None for no entries at all; `swell_budget`'s rules."""
    if kmax == 0:
        return None                        # nothing to lay out
    if kmax > SWELL_MAX_K:
        return "kmax"
    w128 = -(-int(w128_raw) // 8) * 8
    if w128 * LANES > SWELL_MAX_W:
        return "window"
    kpad = _kpad(kmax)
    if not _fill_ok(nb * SUBS * kpad * LANES, nnz):
        return "fill"
    return kpad, w128


def swell_budget(kmax, w128_raw, nb, nnz):
    """Single source of the SWELL layout-budget decisions, shared by the
    numpy builder below and the native-wrapper path
    (native/__init__.py swell_build_native) — the two drifted once.
    Returns (kpad, w128) or None when the layout does not pay (each
    None is counted and named: `kmax`, `window`, `fill`):
    - kmax: a row longer than SWELL_MAX_K slots (all or nothing: one
      long row declines the operator);
    - kpad: exact for short rows (interpolation operators, kmax 4-5,
      where round-to-8 inflated HBM and wire bytes ~2x), 8-aligned
      above (Mosaic relayouts large unaligned slot dims through
      scoped-VMEM copies);
    - w128: rounded to 8 chunks (the window's VMEM scratch and its
      DMA stay on whole (8, 128) tiles); window: a block's column span
      over SWELL_MAX_W elements;
    - fill guard: one long row would otherwise inflate the padded
      layout to n*kpad slots; small layouts are exempt (round-to-8
      alone inflates tiny matrices past any ratio, and a <1M-slot
      layout cannot blow memory)."""
    said = _budget(kmax, w128_raw, nb, nnz)
    return _declined(said) if isinstance(said, str) else said


def _kpad(kmax: int) -> int:
    return kmax if kmax <= 24 else -(-kmax // 8) * 8


def _fill_ok(slots: int, nnz: int) -> bool:
    return slots <= 6 * max(nnz, 1) or slots <= (1 << 20)


def _windows_host(ro, ci, n):
    """(kmax, c0, w128_raw): the longest row, each 1,024-row block's
    first window column and the widest window in 128-column chunks
    (numpy form of native amgx_swell_windows)."""
    nb = -(-n // BLOCK_ROWS)
    starts = ro[:-1].astype(np.int64)
    nonempty = ro[1:] > ro[:-1]
    idx = np.clip(starts, 0, ci.shape[0] - 1)
    big = np.iinfo(np.int32).max
    rmin = np.where(nonempty, np.minimum.reduceat(ci, idx), big)
    rmax = np.where(nonempty, np.maximum.reduceat(ci, idx), -1)
    pad = nb * BLOCK_ROWS - n
    if pad:
        rmin = np.concatenate([rmin, np.full(pad, big)])
        rmax = np.concatenate([rmax, np.full(pad, -1)])
    bmin = rmin.reshape(nb, BLOCK_ROWS).min(axis=1)
    bmax = rmax.reshape(nb, BLOCK_ROWS).max(axis=1)
    empty_b = bmax < 0
    bmin = np.where(empty_b, 0, bmin)
    bmax = np.where(empty_b, 0, bmax)
    c0 = (bmin // LANES) * LANES
    span = bmax - c0 + 1
    return int(np.diff(ro).max()), c0, -(-int(span.max()) // LANES)


def build_swell_host(ro, ci, vals, num_rows, num_cols):
    """Numpy construction of the SWELL layout for a host-resident CSR.

    Returns (cols4, vals4, c0row, nchunk, w128) or None when the layout
    does not pay (window or slot budget exceeded). cols4/vals4 are
    (nb, 8, kpad, 128) slot-major super-blocks; c0row is each block's
    window start in 128-rows of the padded x; nchunk (nb, 8, 1 + L)
    each row group's count and list of window chunks
    (`group_chunk_lists`).
    """
    n = int(num_rows)
    if n == 0 or ci.shape[0] == 0:
        return None
    from .. import native
    out = native.swell_build_native(ro, ci, vals, n)
    if out is not False:                  # None = layout doesn't pay
        return out
    nb = -(-n // BLOCK_ROWS)
    row_nnz = np.diff(ro)
    kmax = int(row_nnz.max())
    if kmax == 0 or kmax > SWELL_MAX_K:
        # cheap reject before the scan
        return None if kmax == 0 else _declined("kmax")
    kmax, c0, w128_raw = _windows_host(ro, ci, n)
    budget = swell_budget(kmax, w128_raw, nb, ci.shape[0])
    if budget is None:
        return None
    kpad, _w128 = budget
    # scatter entries into (nb, 8, kpad, 128) slot-major blocks
    row_ids = np.repeat(np.arange(n, dtype=np.int64), row_nnz)
    slot = np.arange(ci.shape[0], dtype=np.int64) - \
        ro[row_ids].astype(np.int64)
    b = row_ids // BLOCK_ROWS
    sub = (row_ids % BLOCK_ROWS) // LANES
    lane = row_ids & (LANES - 1)
    flat = (((b * SUBS + sub) * kpad) + slot) * LANES + lane
    cols4 = np.zeros(nb * SUBS * kpad * LANES, np.int32)
    cols4[flat] = ci - c0[b]
    vals4 = np.zeros(nb * SUBS * kpad * LANES, vals.dtype)
    vals4[flat] = vals
    return (cols4.reshape(nb, SUBS, kpad, LANES),
            vals4.reshape(nb, SUBS, kpad, LANES),
            (c0 // LANES).astype(np.int32),
            group_chunk_lists(ci, row_ids, c0, nb, _w128), _w128)


def count_listed(ro, ci, num_rows):
    """(kmax, w128_raw, listed) of the SWELL layout these row offsets
    would give over this column array: the longest row, the widest
    block window in chunks and the chunks its row groups would list,
    from the pattern alone (the native window and chunk-list sweeps, or
    their numpy forms); nothing is scattered, nothing counted as a
    decline."""
    n = int(num_rows)
    from .. import native
    out = native.swell_count_native(ro, ci, n)
    if out is not None:
        return out
    kmax, c0, w128_raw = _windows_host(ro, ci, n)
    row_ids = np.repeat(np.arange(n, dtype=np.int64), np.diff(ro))
    nb = -(-n // BLOCK_ROWS)
    lists = group_chunk_lists(ci, row_ids, c0, nb,
                              -(-int(w128_raw) // 8) * 8)
    return kmax, w128_raw, listed_chunks(lists)


def _piece_offsets(ro, pieces, K):
    """Row offsets of A': piece j of row i holds the entries
    [ro_i + j K, ro_i + (j + 1) K) of the operator's arrays."""
    n_p = int(pieces.sum())
    first = np.cumsum(pieces) - pieces           # row i's first piece
    row_of = np.repeat(np.arange(pieces.shape[0], dtype=np.int64), pieces)
    j = np.arange(n_p, dtype=np.int64) - first[row_of]
    ro_p = np.empty(n_p + 1, np.int32)
    ro_p[:-1] = ro[row_of] + j * K
    ro_p[-1] = ro[-1]
    return ro_p


def _split_counts(ro, ci, lengths, K):
    """(rows of A', its longest row, its widest block window in chunks,
    its listed chunks, S's longest row, S's listed chunks) of the
    row-split form at piece length K, from the pattern alone: one
    native sweep (amgx_swell_split_count), or `count_listed` over the
    row offsets of A' and, for S, whose row's pieces are adjacent
    columns, the chunks from a row group's first piece to its last."""
    n = lengths.shape[0]
    from .. import native
    out = native.swell_split_count_native(ro, ci, n, K)
    if out is not None:
        return out
    pieces = -(-lengths // K)
    ends = np.cumsum(pieces)
    n_p = int(ends[-1])
    kmax_a, w128_raw, listed_a = count_listed(
        _piece_offsets(ro, pieces, K), ci, n_p)
    g0 = np.arange(0, n, LANES)              # a row group's first row
    last = ends[np.minimum(g0 + LANES, n) - 1] - 1
    first = ends[g0] - pieces[g0]
    listed_s = int(np.where(last >= first, last // LANES
                            - first // LANES + 1, 0).sum())
    return n_p, kmax_a, w128_raw, listed_a, int(pieces.max()), listed_s


def _split_candidates(ro, ci, lengths):
    """[(model seconds, K)] of the row-split forms that fit: the K of
    SPLIT_PIECES under the longest row (from there on A' is the
    operator itself; the smallest K stays, the last resort of an
    operator the budget declines, and goes where the next one fits:
    both tiles are one vreg and the smaller lists more chunks over
    more blocks), whose S keeps
    to exact short rows and that pass the budget, each with the
    model's cost of A' plus S by `_split_counts`."""
    n = lengths.shape[0]
    nnz = int(ro[-1])
    longest = int(lengths.max())
    nb = -(-n // BLOCK_ROWS)
    fits = [K for K in SPLIT_PIECES[1:] if K < longest] \
        or list(SPLIT_PIECES[:1])
    out = []
    for K in fits:
        if -(-longest // K) > 24:
            continue                 # S stays on exact short rows
        n_p, kmax_a, w128_raw, listed_a, kmax_s, listed_s = \
            _split_counts(ro, ci, lengths, K)
        said = _budget(kmax_a, w128_raw, -(-n_p // BLOCK_ROWS), nnz)
        if not isinstance(said, tuple) \
                or not _fill_ok(nb * BLOCK_ROWS * kmax_s, n_p):
            continue                 # a part the budget would decline
        out.append((model_seconds(listed_a, said[0], -(-n_p // BLOCK_ROWS))
                    + model_seconds(listed_s, kmax_s, nb), K))
    return out


def _cheapest(candidates):
    """The candidate the model puts lowest, ties to the larger K."""
    return min(candidates, key=lambda c: (c[0], -c[1]), default=None)


def split_pays(ro, ci, num_rows):
    """(K, the choice in words) of the row-split form where the model
    puts it clearly under the one SWELL layout `swell_budget` admits
    (the caller builds it and says so: `note_chosen`), else None: a
    function of the pattern alone (row lengths and chunk lists), so a
    pattern takes the same form at every re-setup. Nothing is counted
    for an operator under SPLIT_MIN_NNZ non-zeros, for one whose tile
    is one vreg already (every P of a truncated interpolation), or for
    one whose one layout pads under SPLIT_SCREEN slots a non-zero. The
    margin is 2.5 times the rms error of the clock against the timed
    operators; what the count does not see of a split (its second
    launch; the Jacobi update, which leaves the fused sweep for XLA
    ops and read the same to the third digit on the chip) matters on
    a small operator alone: hence the least saving."""
    n = int(num_rows)
    nnz = int(ci.shape[0])
    if n == 0 or nnz < SPLIT_MIN_NNZ:
        return None
    ro = np.asarray(ro).astype(np.int64)
    lengths = np.diff(ro)
    nb = -(-n // BLOCK_ROWS)
    kpad = _kpad(int(lengths.max()))
    if kpad <= SUBS or nb * BLOCK_ROWS * kpad < SPLIT_SCREEN * nnz:
        return None
    kmax, w128_raw, listed = count_listed(ro, ci, n)
    if not isinstance(_budget(kmax, w128_raw, nb, nnz), tuple):
        return None                  # declined: the caller's road
    plain = model_seconds(listed, kpad, nb)
    best = _cheapest(_split_candidates(ro, ci, lengths))
    if best is None or plain - best[0] < max(SPLIT_MARGIN * plain,
                                             SPLIT_MIN_SAVING_S):
        return None
    cost, K = best
    return K, f"K={K} model {1e3 * cost:.3f} of {1e3 * plain:.3f} ms"


def split_rows_host(ro, ci, vals, num_rows, num_cols, K=None):
    """The row-split form of a CSR operator whose rows are too uneven
    for one SWELL layout (`swell_budget` said `kmax` or `fill`: a mean
    of 43 entries under a longest row of 295 pads five slots an entry,
    or has no slot count at all; or `split_pays` said the one layout
    costs clearly more): every row is cut into pieces of at most K
    consecutive entries, each piece a row of A' (n' rows, the SAME
    column and value arrays under other row offsets), and the pieces of
    a row are summed by S (n x n', ones, a row's pieces adjacent):
    A = S A'. Both are ordinary SWELL operators, A' with rows of at
    most K entries and the window of the rows it came from, S with
    perfectly local columns, so the product runs through the SWELL
    kernels that are there. `K` is `split_pays`'s where it chose;
    without it, the candidate the model puts lowest
    (`_split_candidates`).

    Returns ((ro', layout of A'), (ro_S, layout of S)) with each
    layout as `build_swell_host` gives it, or None where no candidate
    fits (counted by the declines that said so)."""
    n = int(num_rows)
    ro = np.asarray(ro).astype(np.int64)
    nnz = int(ci.shape[0])
    if n == 0 or nnz == 0:
        return None
    if K is None:
        best = _cheapest(_split_candidates(ro, ci, np.diff(ro)))
        if best is None:
            return None
        K = best[1]
    per_row = -(-np.diff(ro) // K)
    ro_p = _piece_offsets(ro, per_row, K)
    n_p = int(ro_p.shape[0]) - 1
    lay_a = build_swell_host(ro_p, ci, vals, n_p, num_cols)
    if lay_a is None:
        return None
    ro_s = np.zeros(n + 1, np.int32)
    np.cumsum(per_row, out=ro_s[1:])
    lay_s = build_swell_host(ro_s, np.arange(n_p, dtype=np.int32),
                             np.ones(n_p, vals.dtype), n, n_p)
    if lay_s is None:
        return None
    return (ro_p, lay_a), (ro_s, lay_s)


def swell_vals_host(ro, vals, num_rows, kpad):
    """Re-scatter new coefficients into an existing SWELL layout
    (replace_coefficients with structure reuse)."""
    n = int(num_rows)
    from .. import native
    out = native.swell_refill_native(ro, vals, n, int(kpad))
    if out is not None:
        return out
    nb = -(-n // BLOCK_ROWS)
    row_nnz = np.diff(ro)
    row_ids = np.repeat(np.arange(n, dtype=np.int64), row_nnz)
    slot = np.arange(vals.shape[0], dtype=np.int64) - \
        ro[row_ids].astype(np.int64)
    b = row_ids // BLOCK_ROWS
    sub = (row_ids % BLOCK_ROWS) // LANES
    flat = (((b * SUBS + sub) * kpad) + slot) * LANES + \
        (row_ids & (LANES - 1))
    vals4 = np.zeros(nb * SUBS * kpad * LANES, vals.dtype)
    vals4[flat] = vals
    return vals4.reshape(nb, SUBS, kpad, LANES)


def _swell_runtime_payload_ok(A) -> bool:
    """Backend + payload-presence checks shared by the SWELL gates."""
    from .pallas_spmv import pallas_backend
    if pallas_backend() is None:
        return False
    return A.swell_cols is not None and A.swell_vals is not None


def _swell_budget_ok(A, val_itemsize: int, out_blocks: int) -> bool:
    """One VMEM budget formula for both SWELL gates: the x window,
    the double-buffered cols(int32)+vals entry slabs (`val_itemsize`
    narrows for bf16 values), and `out_blocks` double-buffered
    (SUBS, 128) pipeline blocks (1 = SpMV's y; 4 = the fused sweep's
    x/b/dinv/out). The row groups' chunk lists are SMEM, not VMEM: a
    list is at most the window, so a block's are 8 x (1 + w128) words,
    double-buffered 256 KB at SWELL_MAX_W, which the chip's compiler
    takes (tests/test_chip_compile.py) and nothing here counts."""
    w128 = A.swell_w128
    kpad = A.swell_vals.shape[2]
    win_bytes = 2 * w128 * LANES * 4
    ent_bytes = 2 * SUBS * kpad * LANES * (4 + val_itemsize)
    out_bytes = 2 * out_blocks * SUBS * LANES * 4
    return win_bytes + ent_bytes + out_bytes <= _VMEM_BUDGET


def swell_spmv_supported(A, x_dtype) -> bool:
    """Trace-time gate for the Pallas path (f32 only: the plain SpMV's
    output dtype is the caller's vector-dtype contract)."""
    if not _swell_runtime_payload_ok(A):
        return False
    if A.swell_vals.dtype != jnp.float32 or x_dtype != jnp.float32:
        return False
    return _swell_budget_ok(A, 4, 1)


def _window(c0_ref, xp_ref, xbuf, sems, w128, n_blocks):
    """This block's x window, double-buffered: start the next block's
    DMA, wait for this one's; returns the buffer slot it landed in."""
    b = pl.program_id(0)
    slot = jax.lax.rem(b, jnp.int32(2))

    def dma(s, blk):
        return pltpu.make_async_copy(
            xp_ref.at[pl.ds(c0_ref[blk], w128)],
            xbuf.at[jnp.int32(s)], sems.at[jnp.int32(s)])

    @pl.when(b == 0)
    def _():
        dma(0, 0).start()

    @pl.when(b + 1 < n_blocks)
    def _():
        dma(jax.lax.rem(b + 1, jnp.int32(2)), b + 1).start()

    dma(slot, b).wait()
    return slot


def _gather_window(lst_ref, groups, xbuf, slot, cols):
    """acc[k, l] = the window's value at the local column cols[k, l],
    for the (kpad, 128) tile of each row group of `groups`: the loop
    over THOSE groups' chunk lists (`lst_ref[0, g]`: a count, then the
    chunks), shared by the SpMV and the fused sweep. The groups share
    one loop, as long as the longest of their lists, so that one's
    chain of scalar read, row load, gather and select fills the waits
    of the other's; UNROLL list entries of each an iteration. The
    builders pad every list to L by repeats of its last chunk, and a
    chunk selected twice selects the same values, so the shorter list
    and the last iteration need no test."""
    hi = [jax.lax.shift_right_logical(c, jnp.int32(7)) for c in cols]
    lo = [jax.lax.bitwise_and(c, jnp.int32(LANES - 1)) for c in cols]

    def step(i, accs):
        accs = list(accs)
        base = i * jnp.int32(UNROLL) + jnp.int32(1)
        for j in range(UNROLL):
            for k, g in enumerate(groups):
                c = lst_ref[0, g, base + jnp.int32(j)]
                chunk = xbuf[slot, pl.ds(c, 1)]   # (1, 128)
                src = jnp.broadcast_to(chunk, cols[k].shape)
                accs[k] = jnp.where(
                    hi[k] == c, jnp.take_along_axis(src, lo[k], axis=1),
                    accs[k])
        return tuple(accs)

    longest = functools.reduce(
        jnp.maximum, [lst_ref[0, g, 0] for g in groups])
    steps = jax.lax.div(longest + jnp.int32(UNROLL - 1),
                        jnp.int32(UNROLL))
    return jax.lax.fori_loop(
        jnp.int32(0), steps, step,
        tuple(jnp.zeros(c.shape, jnp.float32) for c in cols))


def _block_product(lst_ref, xbuf, slot, cols_ref, vals_ref):
    """The block's (8, 128) product, GROUPS row groups at a time. The
    groups are walked by a loop and not unrolled: the body is traced
    and lowered once (unrolled over all 8 it cost every process 0.2 s
    a kernel of Python lowering, 11 s of a classical cell's set-up on
    the chip's host: PR 48)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBS, LANES), 0)

    def some(p, y):
        groups = [p * jnp.int32(GROUPS) + jnp.int32(k)
                  for k in range(GROUPS)]
        accs = _gather_window(lst_ref, groups, xbuf, slot,
                              [cols_ref[0, g] for g in groups])
        for g, acc in zip(groups, accs):
            y = jnp.where(row == g, jnp.sum(acc * vals_ref[0, g], axis=0,
                                            keepdims=True), y)
        return y

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(SUBS // GROUPS), some,
                             jnp.zeros((SUBS, LANES), jnp.float32))


def _entry_specs(kpad, lists_shape):
    """in_specs of a block's chunk lists (SMEM) and its two entry slabs
    (VMEM), all three riding the pipeline block by block."""
    def blocked(shape, space):
        return pl.BlockSpec(
            (1,) + tuple(shape[1:]),
            lambda b: (b,) + (jnp.int32(0),) * (len(shape) - 1),
            memory_space=space)
    slab = blocked((1, SUBS, kpad, LANES), pltpu.VMEM)
    return [blocked(lists_shape, pltpu.SMEM), slab, slab]


def _swell_kernel(w128, n_blocks):
    def kernel(c0_ref, xp_ref, lst_ref, cols_ref, vals_ref, y_ref,
               xbuf, sems):
        slot = _window(c0_ref, xp_ref, xbuf, sems, w128, n_blocks)
        y_ref[...] = _block_product(lst_ref, xbuf, slot, cols_ref,
                                    vals_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("w128", "num_rows",
                                             "interpret"))
def _swell_spmv_call(cols4, vals4, c0row, nchunk, x, w128, num_rows,
                     interpret=False):
    nb, _, kpad, _ = vals4.shape
    n = num_rows
    ncols = x.shape[0]
    # pad x to whole 128-rows plus the window overhang past the end
    xp_rows = -(-ncols // LANES) + w128
    xp = jnp.zeros((xp_rows * LANES,), jnp.float32)
    xp = jax.lax.dynamic_update_slice(xp, x.astype(jnp.float32), (0,))
    xp = xp.reshape(xp_rows, LANES)

    kernel = _swell_kernel(w128, nb)
    y2 = kernel_call(
        kernel,
        grid=(nb,),
        in_specs=[
            # explicit shapes + int32 index maps: the default full-array
            # spec's index map emits i64 constants under the package's
            # x64 default, which Mosaic cannot legalize
            pl.BlockSpec((nb,), lambda b: (jnp.int32(0),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + _entry_specs(kpad, nchunk.shape),
        out_specs=pl.BlockSpec((SUBS, LANES),
                               lambda b: (b, jnp.int32(0)),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb * SUBS, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, w128, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * nb * SUBS * kpad * LANES,
            bytes_accessed=(2 * kpad + 1) * nb * SUBS * LANES * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(c0row, xp, nchunk, cols4, vals4)
    y = y2.reshape(-1)
    if y.shape[0] != n:
        y = y[:n]
    return y


def swell_spmv(A, x, interpret=False):
    """Fused SWELL SpMV; caller must have checked swell_spmv_supported
    (`interpret=True` runs the Pallas interpreter — CPU test path)."""
    from .pallas_spmv import _FORCE_INTERPRET
    return _swell_spmv_call(A.swell_cols, A.swell_vals, A.swell_c0row,
                            A.swell_nchunk, x, A.swell_w128, A.num_rows,
                            interpret=interpret or _FORCE_INTERPRET)


# ---------------------------------------------------------------------------
# Fused smoother sweep: SpMV + damped-Jacobi update in one pass
#
# x' = x + tau * dinv . (b - A x) for the windowed-ELL layout. The
# lane-gather layout cannot temporally block like the DIA kernel (a
# block's x window reaches arbitrarily far, so a second in-kernel sweep
# would need other blocks' updated values), but fusing the elementwise
# update into the kernel epilogue removes the separate XLA pass and its
# 4 HBM streams (read y/x/b/dinv, write x') per sweep — the unfused
# shape materializes y to HBM because XLA cannot fuse into pallas_call
# outputs. x/b/dinv arrive as exact row blocks via auto-pipelined
# BlockSpecs (no halo needed: the update is pointwise in the row).
# ---------------------------------------------------------------------------


def swell_smooth_supported(A, x_dtype) -> bool:
    """Trace-time gate for the fused-sweep SWELL path. Unlike the
    plain-SpMV gate (f32-only: its output dtype is the caller's vector
    dtype contract), the fused sweep also accepts bf16 value slabs —
    the kernel already upcasts the gathered x window to f32 and the
    value multiply promotes, so only the value stream narrows; the
    wrapper rounds x' back to the vector dtype."""
    from .pallas_spmv import SMOOTH_DTYPES
    if not _swell_runtime_payload_ok(A):
        return False
    dt = jnp.dtype(A.swell_vals.dtype)
    if dt != jnp.dtype(x_dtype) or dt.name not in SMOOTH_DTYPES:
        return False
    if A.has_external_diag or A.num_rows != A.num_cols:
        return False
    # three extra (SUBS, 128) double-buffered blocks ride the pipeline
    return _swell_budget_ok(A, dt.itemsize, 4)


def _swell_smooth_kernel(w128, n_blocks, has_dinv):
    def kernel(*refs):
        # refs: c0, tau, xp, lists, cols, vals, xblk, bblk, [dinvblk],
        #       out, xbuf, sems
        (c0_ref, tau_ref, xp_ref, lst_ref, cols_ref, vals_ref,
         xb_ref, bb_ref) = refs[:8]
        db_ref = refs[8] if has_dinv else None
        out_ref, xbuf, sems = refs[8 + (1 if has_dinv else 0):]

        slot = _window(c0_ref, xp_ref, xbuf, sems, w128, n_blocks)
        y = _block_product(lst_ref, xbuf, slot, cols_ref, vals_ref)
        corr = tau_ref[0] * (bb_ref[...] - y)
        if has_dinv:
            corr = corr * db_ref[...]
        out_ref[...] = xb_ref[...] + corr

    return kernel


@functools.partial(jax.jit, static_argnames=("w128", "num_rows",
                                             "has_dinv", "interpret"))
def _swell_smooth_call(cols4, vals4, c0row, nchunk, x, b, dinv, tau,
                       w128, num_rows, has_dinv, interpret=False):
    nb, _, kpad, _ = vals4.shape
    n = num_rows
    ncols = x.shape[0]
    xp_rows = -(-ncols // LANES) + w128
    xp = jnp.zeros((xp_rows * LANES,), jnp.float32)
    xp = jax.lax.dynamic_update_slice(xp, x.astype(jnp.float32), (0,))
    xp = xp.reshape(xp_rows, LANES)

    def rowpad(v):
        out = jnp.zeros((nb * BLOCK_ROWS,), jnp.float32)
        out = jax.lax.dynamic_update_slice(out, v.astype(jnp.float32),
                                           (0,))
        return out.reshape(nb * SUBS, LANES)

    blk = pl.BlockSpec((SUBS, LANES), lambda i: (i, jnp.int32(0)),
                       memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((nb,), lambda i: (jnp.int32(0),),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1,), lambda i: (jnp.int32(0),),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),
    ] + _entry_specs(kpad, nchunk.shape) + [
        blk,            # x block
        blk,            # b block
    ]
    operands = [c0row, jnp.reshape(tau, (1,)).astype(jnp.float32), xp,
                nchunk, cols4, vals4, rowpad(x), rowpad(b)]
    if has_dinv:
        in_specs.append(blk)
        operands.append(rowpad(dinv))
    kernel = _swell_smooth_kernel(w128, nb, has_dinv)
    y2 = kernel_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((nb * SUBS, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, w128, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * nb * SUBS * kpad * LANES,
            bytes_accessed=(2 * kpad + 5) * nb * SUBS * LANES * 4,
            transcendentals=0,
        ),
        # `interpret` resolved by the un-jitted wrapper below so the
        # flag rides the jit cache key (see _dia_smooth_call)
        interpret=interpret,
    )(*operands)
    y = y2.reshape(-1)
    if y.shape[0] != n:
        y = y[:n]
    return y


def swell_smooth_step(A, b, x, tau, dinv=None, interpret=False):
    """One fused damped sweep x' = x + tau * dinv . (b - A x); caller
    must have checked swell_smooth_supported. The kernel computes in
    f32 (bf16 value slabs promote at the multiply); the result rounds
    back to the vector dtype so the cycle's state dtype is stable."""
    from .pallas_spmv import _FORCE_INTERPRET
    y = _swell_smooth_call(
        A.swell_cols, A.swell_vals, A.swell_c0row, A.swell_nchunk,
        x, b, dinv, tau, A.swell_w128, A.num_rows,
        dinv is not None, interpret=interpret or _FORCE_INTERPRET)
    return y.astype(x.dtype)


def swell_spmv_xla(A, x):
    """XLA gather form of the same layout (f64/CPU/batched fallback).
    Semantically identical to the kernel: absolute column = block window
    start + stored local column."""
    nb, _, kpad, _ = A.swell_vals.shape
    dtype = jnp.promote_types(A.swell_vals.dtype, x.dtype)
    ncols = A.num_cols
    xp_len = (-(-ncols // LANES) + A.swell_w128) * LANES
    xp = jnp.zeros((xp_len,), dtype)
    xp = jax.lax.dynamic_update_slice(xp, x.astype(dtype), (0,))
    abscol = (A.swell_c0row.astype(jnp.int32) * LANES)[:, None, None, None] \
        + A.swell_cols
    y = (A.swell_vals.astype(dtype) * xp[abscol]).sum(axis=2).reshape(-1)
    if y.shape[0] != A.num_rows:
        y = y[: A.num_rows]
    return y
