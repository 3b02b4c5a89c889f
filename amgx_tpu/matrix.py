"""Core sparse-matrix container.

TPU-native analog of the reference Matrix/MatrixBase (include/matrix.h:65,
src/matrix.cu): a block-CSR container held as a JAX pytree so it can flow
through jit/shard_map. Differences from the reference, by design:

- no explicit memory spaces (XLA owns placement);
- "initialization" precomputes static gather/scatter auxiliaries
  (per-nnz row ids, diagonal indices, padded-ELL layout) instead of
  launching setup kernels — these are what make SpMV / smoothers map onto
  the TPU vector units as dense gathers + segmented reductions;
- the DIAG property (externally stored diagonal, include/matrix.h:24-26)
  is the `diag` field being non-None.

Shapes are static: one compiled program per (num_rows, nnz, block) bucket,
matching XLA's compilation model.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .errors import BadParametersError
from .telemetry import metrics as _tm
from .telemetry.spans import span


class _DeviceSetupState(threading.local):
    """Per-thread flag for the device-resident setup pipeline
    (setup_backend=device): while set, every host-numpy fast path that
    gates on host residency reports 'not host' so the jnp/device
    implementations run instead — the same code a real accelerator
    build takes, selectable (and testable) on any backend."""

    forced = False


_device_setup = _DeviceSetupState()


@contextlib.contextmanager
def forced_device_setup(on: bool = True):
    """Force (or explicitly lift, on=False) the device-resident setup
    implementations for the enclosed block on this thread."""
    prev = _device_setup.forced
    _device_setup.forced = bool(on)
    try:
        yield
    finally:
        _device_setup.forced = prev


def device_setup_forced() -> bool:
    return _device_setup.forced

# id(device array) -> the host numpy original it was created from. Real
# AmgX matrices always originate on the host (uploads, readers, gallery);
# the host-CPU setup path (amg_host_setup) reads them back — retaining
# the upload-side original makes that read a lookup instead of a
# device->host copy of the whole matrix. jax ArrayImpl is weakref-able but
# NOT hashable, so the mirror is keyed by id() with weakref.finalize
# eviction (the entry dies with the device array, and the finalizer
# guards against id reuse).
_HOST_MIRROR: dict = {}


def _register_host_mirror(dev_arr, np_arr):
    try:
        key = id(dev_arr)
        weakref.finalize(dev_arr, _HOST_MIRROR.pop, key, None)
    except TypeError:  # pragma: no cover - non-weakrefable array type
        return
    _HOST_MIRROR[key] = np_arr


def host_mirror_asarray(x):
    """np.asarray(x), served from the retained host original when x was
    uploaded from host data (no accelerator->host transfer)."""
    if isinstance(x, np.ndarray):
        return x
    m = _HOST_MIRROR.get(id(x))
    return m if m is not None else np.asarray(x)


def host_arrays(*arrays):
    """numpy views of the given arrays with NO accelerator->host
    transfer: numpy / CPU-resident arrays pass through, accelerator
    arrays resolve via the retained host mirror. Returns None when any
    array cannot be served host-side (callers fall back to the device
    path). This is what lets setup-phase index math run in synchronous
    numpy even when the user's matrix lives on the TPU."""
    if _device_setup.forced:
        return None
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
            continue
        if isinstance(a, np.ndarray):
            out.append(a)
            continue
        m = _HOST_MIRROR.get(id(a))
        if m is not None:
            out.append(m)
            continue
        try:
            if next(iter(a.devices())).platform == "cpu":
                out.append(np.asarray(a))
                continue
        except Exception:
            pass
        return None
    return out


# The DIA refill map of a sparsity pattern: where every CSR entry sits
# in the tiled (k, rows_pad, LANES) slab. It is structure, so it is
# built by the first with_values on a pattern and kept here, NOT in the
# CsrMatrix pytree (a data field would become an argument of every
# solve program). Keyed like _HOST_MIRROR by the identity of the arrays
# it was built from, (id(col_indices), id(row_offsets), dia_offsets),
# and evicted by weakref.finalize when either array dies:
# dataclasses.replace keeps both across with_values, so every matrix of
# a time loop finds the same map, and another pattern of the same size
# does not.
_REFILL_MAPS: dict = {}
_REFILL_MAPS_LOCK = threading.Lock()


class _RefillMap:
    """Host numpy, applied on the host: at 7-pt 256^3 the chip's gather
    through such a map reads 5.0 s a call, this one 0.4 s (PERF.md,
    PR 31). Without duplicate (row, column) entries `index` is the
    inverse map, for every slab slot the index of its entry in
    `values`, and a refill is one np.take; the padding slots (`pad`)
    hold nnz, which the take clips, and are zeroed after it. With
    duplicates `index` is the slot of every entry and a refill is a
    bincount, which sums them as init() does."""

    # the take is cut into pieces of at least this many slots, one
    # thread each: most of its time is the page faults of the fresh
    # slab, which threads take in parallel (numpy drops the GIL)
    PIECE = 1 << 22
    MAX_THREADS = 8

    def __init__(self, ci, row_ids, offsets, shape):
        nnz = ci.shape[0]
        self.shape = shape
        self.size = size = shape[0] * shape[1] * shape[2]
        d_idx = np.searchsorted(np.asarray(offsets, ci.dtype), ci - row_ids)
        slots = d_idx * (shape[1] * shape[2]) + row_ids
        entry = np.full(size, nnz, np.intp)
        entry[slots] = np.arange(nnz, dtype=np.intp)
        self.pad = np.flatnonzero(entry == nnz)
        # two entries in one slot leave fewer slots taken than entries
        self.duplicates = size - self.pad.shape[0] < nnz
        self.index = slots if self.duplicates else entry

    def slab(self, values: np.ndarray) -> np.ndarray:
        """The DIA slab of these coefficients: the same numbers in the
        same places as init() gives."""
        if self.duplicates:
            return np.bincount(
                self.index, weights=values, minlength=self.size
            ).astype(values.dtype).reshape(self.shape)
        flat = np.empty(self.size, values.dtype)
        pieces = min(self.MAX_THREADS, os.cpu_count() or 1,
                     max(self.size // self.PIECE, 1))
        cuts = np.linspace(0, self.size, pieces + 1).astype(np.intp)

        def take(i):
            a, b = cuts[i], cuts[i + 1]
            np.take(values, self.index[a:b], mode="clip", out=flat[a:b])

        if pieces == 1:
            take(0)
        else:
            with ThreadPoolExecutor(pieces) as pool:
                list(pool.map(take, range(pieces)))   # raises a piece's error
        flat[self.pad] = 0
        return flat.reshape(self.shape)


def lexsort_rc(rows, cols):
    """Stable (rows, cols)-lexicographic order via two int32 argsorts.

    TPU-first replacement for the single int64 `row * ncols + col` key:
    the TPU has no native 64-bit integers, so an int64 sort compiles to
    (and executes as) a slow emulated form — two stable 32-bit sorts
    are strictly cheaper at every problem size."""
    order1 = jnp.argsort(cols, stable=True)
    order2 = jnp.argsort(rows[order1], stable=True)
    return order1[order2]

Array = jax.Array


def _seg_sum(data, seg_ids, num_segments):
    return jax.ops.segment_sum(data, seg_ids, num_segments=num_segments,
                               indices_are_sorted=True)


@functools.lru_cache(maxsize=None)
def _warn_swell_dropped():
    """Once a process: with_values dropped a SWELL layout."""
    from .output import amgx_output
    amgx_output(
        "amgx_tpu warning: replace_coefficients with values that live "
        "on the device dropped the matrix's SWELL layout (only host "
        "values re-pack); its SpMV leaves the SWELL kernels until the "
        "next init() (matrix.swell_layout_dropped)\n")


def host_resident(*arrays) -> bool:
    """True when every given array is concrete host-CPU data (numpy or a
    CPU-backend jax array). Tracers and accelerator arrays return False.
    Gates the numpy fast paths of the setup-phase index math: on the
    host-CPU setup path (amg_host_setup) the same math as the jnp form,
    run synchronously in numpy, avoids hundreds of eager XLA:CPU
    dispatches per hierarchy build. Under a forced device-resident
    setup (setup_backend=device) every array reports non-host so the
    jnp implementations run."""
    if _device_setup.forced:
        return False
    for a in arrays:
        if a is None or isinstance(a, np.ndarray):
            continue
        try:
            if next(iter(a.devices())).platform != "cpu":
                return False
        except Exception:
            return False
    return True


_PLACEHOLDERS: dict = {}


def _placeholder(dtype):
    """The one-element array a slim view (`CsrMatrix.slim_for_spmv`)
    carries where a CSR payload was that nothing reads: one a dtype for
    the process (and a default device, and an x64 mode: it is made
    where, and as, a fresh `jnp.zeros` would have been), so that a slim
    view is host work alone and its placeholders are the same leaves in
    every solve-data tree."""
    key = (jnp.dtype(dtype).name, jax.config.jax_default_device,
           jax.config.jax_enable_x64)
    out = _PLACEHOLDERS.get(key)
    if out is None:
        # concrete even where a trace asks for the view
        with jax.ensure_compile_time_eval():
            out = _PLACEHOLDERS.setdefault(key, jnp.zeros((1,), dtype))
    return out


def _np_row_reduce(op, data, ro, n, empty_val):
    """Per-row reduce over CSR-ordered data via ufunc.reduceat, with
    empty rows patched to `empty_val` (reduceat's equal-index semantics
    would otherwise leak the next row's first element)."""
    if data.shape[0] == 0:
        return np.full(n, empty_val, data.dtype)
    starts = ro[:-1].astype(np.int64)
    nonempty = ro[1:] > ro[:-1]
    out = op.reduceat(data, np.clip(starts, 0, data.shape[0] - 1))
    return np.where(nonempty, out, empty_val)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["row_offsets", "col_indices", "values", "diag",
                 "row_ids", "diag_idx", "ell_cols", "ell_vals", "dia_vals",
                 "swell_cols", "swell_vals", "swell_c0row", "swell_nchunk",
                 "split", "user_colors"],
    meta_fields=["num_rows", "num_cols", "block_dimx", "block_dimy",
                 "initialized", "dia_offsets", "swell_w128", "grid_shape",
                 "user_num_colors"],
)
@dataclasses.dataclass(frozen=True)
class CsrMatrix:
    """Block-CSR matrix. `values` is (nnz,) for scalar matrices or
    (nnz, block_dimx, block_dimy) for block matrices. When `diag` is not
    None the diagonal blocks are stored externally (DIAG property) and
    `values` holds only off-diagonal entries."""

    row_offsets: Array                 # (n+1,) int32
    col_indices: Array                 # (nnz,) int32
    values: Array                      # (nnz,) | (nnz, bx, by)
    diag: Optional[Array] = None       # (n,) | (n, bx, by) external diagonal
    # auxiliaries built by .init() (None until then)
    row_ids: Optional[Array] = None    # (nnz,) row of each entry
    diag_idx: Optional[Array] = None   # (n,) values-index of diagonal entry
    ell_cols: Optional[Array] = None   # (n, k) padded column ids
    ell_vals: Optional[Array] = None   # (n, k) | (n, k, bx, by)
    dia_offsets: Optional[tuple] = None  # static tuple of diagonal offsets
    dia_vals: Optional[Array] = None   # (k, rows_pad, 128) tiled diagonals
    # windowed-ELL (SWELL) layout for unstructured matrices (the Pallas
    # gather kernel's storage, ops/pallas_swell.py): slot-major
    # (nb, 8, kpad, 128) blocks + per-block x-window starts and each
    # row group's list of the window chunks it has a column in
    swell_cols: Optional[Array] = None   # (nb, 8, kpad, 128) local columns
    swell_vals: Optional[Array] = None   # (nb, 8, kpad, 128)
    swell_c0row: Optional[Array] = None  # (nb,) window start, 128-rows
    swell_nchunk: Optional[Array] = None  # (nb, 8, 1 + L): a group's count, its chunks
    swell_w128: int = 0                  # static window width, 128-chunks
    # row-split SWELL form of an operator whose rows are too uneven for
    # one SWELL layout (ops/pallas_swell.split_rows_host): (A', S) with
    # A = S A', each an spmv-only CsrMatrix in the SWELL layout; A'
    # keeps its row offsets (a refill's map), S has no values to refill
    split: Optional[tuple] = None
    num_rows: int = 0
    num_cols: int = 0
    block_dimx: int = 1
    block_dimy: int = 1
    initialized: bool = False
    # structured-grid annotation (nx, ny, nz), x fastest — set by the
    # gallery generators and propagated by the GEO aggregation path so
    # every coarse level keeps the banded/DIA roofline layout
    grid_shape: Optional[tuple] = None
    # user-supplied row coloring (AMGX_matrix_attach_coloring): consumed
    # by color_matrix ahead of any computed scheme
    user_colors: Optional[Array] = None
    user_num_colors: int = 0

    # ------------------------------------------------------------------
    @property
    def nnz(self) -> int:
        return self.values.shape[0]

    @property
    def shape(self):
        return (self.num_rows, self.num_cols)

    @property
    def block_size(self) -> int:
        return self.block_dimx * self.block_dimy

    @property
    def is_block(self) -> bool:
        return self.block_size > 1

    @property
    def has_external_diag(self) -> bool:
        return self.diag is not None

    @property
    def dtype(self):
        return self.values.dtype

    # ------------------------------------------------------------------
    def init(self, ell: str = "auto", ell_max_ratio: float = 3.0) -> "CsrMatrix":
        """`set_initialized` analog: precompute SpMV auxiliaries.

        - `row_ids`: per-nnz row index (drives segmented reductions);
        - `diag_idx`: index of each row's diagonal entry in `values`
          (or -1) — used by Jacobi/GS/DILU smoothers;
        - with `ell='auto'` (default): a banded DIA layout when the
          sparsity has few distinct diagonals (stencils; SpMV becomes
          shifted dense multiply-adds — the TPU roofline path), else a
          padded ELL layout when the row-length distribution is tight
          (dense gather+reduce); `ell='always'` forces ELL, `ell='never'`
          keeps plain CSR+segsum.
        """
        n = self.num_rows
        if not self.is_block and host_resident(
                self.row_offsets, self.col_indices, self.values):
            return self._init_host(ell, ell_max_ratio)
        if not self.is_block:
            out = self._init_from_mirrors(ell, ell_max_ratio)
            if out is not None:
                return out
        row_nnz = jnp.diff(self.row_offsets)
        row_ids = jnp.repeat(
            jnp.arange(n, dtype=jnp.int32), row_nnz,
            total_repeat_length=self.nnz)
        if self.has_external_diag:
            diag_idx = None
        else:
            # first-occurrence diagonal (rows without one keep -1) —
            # first matters for padded-duplicate CSR, where coalesced
            # duplicates trail the summed entry with zero values
            is_diag = (self.col_indices == row_ids)
            cand = jnp.where(is_diag, jnp.arange(self.nnz, dtype=jnp.int32),
                             self.nnz)
            dmin = jax.ops.segment_min(cand, row_ids, num_segments=n,
                                       indices_are_sorted=True)
            diag_idx = jnp.where(dmin >= self.nnz, -1, dmin).astype(
                jnp.int32)
        ell_cols, ell_vals, dia_offsets, dia_vals = self._choose_layout(
            row_ids, row_nnz, ell, ell_max_ratio)
        return dataclasses.replace(
            self, row_ids=row_ids, diag_idx=diag_idx,
            ell_cols=ell_cols, ell_vals=ell_vals,
            dia_offsets=dia_offsets, dia_vals=dia_vals, initialized=True)

    def _init_from_mirrors(self, ell: str,
                           ell_max_ratio: float) -> "Optional[CsrMatrix]":
        """init() for an accelerator matrix whose base arrays retain
        host mirrors (every host-originated upload does): build the
        SpMV auxiliaries host-side in numpy and ship the finished
        layout in a few large contiguous puts. The alternative — eager
        per-op init on the accelerator — costs one compile and one
        dispatch per op and leaves eager temporaries in HBM."""
        import jax as _jax
        if _device_setup.forced:
            return None          # setup_backend=device: build on device
        m_ro = _HOST_MIRROR.get(id(self.row_offsets))
        m_ci = _HOST_MIRROR.get(id(self.col_indices))
        m_va = _HOST_MIRROR.get(id(self.values))
        m_dg = (None if self.diag is None
                else _HOST_MIRROR.get(id(self.diag)))
        if m_ro is None or m_ci is None or m_va is None or \
                (self.diag is not None and m_dg is None):
            return None
        try:
            dev = next(iter(self.values.devices()))
        except Exception:
            return None
        host = dataclasses.replace(
            self, row_offsets=m_ro, col_indices=m_ci, values=m_va,
            diag=m_dg)._init_host(ell, ell_max_ratio)

        def up(x):
            if x is None or not hasattr(x, "dtype"):
                return x
            x = np.ascontiguousarray(x)
            d = _jax.device_put(x, dev)
            _register_host_mirror(d, x)
            return d

        def swell_up(m):
            return dict(swell_cols=up(m.swell_cols),
                        swell_vals=up(m.swell_vals),
                        swell_c0row=up(m.swell_c0row),
                        swell_nchunk=up(m.swell_nchunk))

        return dataclasses.replace(
            self, row_ids=up(host.row_ids), diag_idx=up(host.diag_idx),
            ell_cols=up(host.ell_cols), ell_vals=up(host.ell_vals),
            dia_offsets=host.dia_offsets, dia_vals=up(host.dia_vals),
            swell_w128=host.swell_w128, **swell_up(host),
            split=None if host.split is None else tuple(
                dataclasses.replace(part, **swell_up(part),
                                    row_offsets=up(part.row_offsets))
                for part in host.split),
            initialized=True)

    def _init_host(self, ell: str, ell_max_ratio: float) -> "CsrMatrix":
        """Numpy form of init() for host-resident scalar matrices — same
        auxiliaries, synchronous vectorized C instead of eager XLA:CPU
        dispatches (the host-setup path builds every hierarchy level
        through here)."""
        n = self.num_rows
        ro = np.asarray(self.row_offsets)
        ci = np.asarray(self.col_indices)
        vals = np.asarray(self.values)
        row_nnz = np.diff(ro)
        row_ids = np.repeat(np.arange(n, dtype=np.int32), row_nnz)
        if self.has_external_diag:
            diag_idx = None
        else:
            cand = np.where(ci == row_ids,
                            np.arange(self.nnz, dtype=np.int64), self.nnz)
            dmin = _np_row_reduce(np.minimum, cand, ro, n, self.nnz)
            diag_idx = np.where(dmin >= self.nnz, -1, dmin).astype(np.int32)
        layout = self._choose_layout_host(
            ro, ci, vals, row_ids, row_nnz, ell, ell_max_ratio)
        return dataclasses.replace(
            self, row_ids=row_ids, diag_idx=diag_idx, initialized=True,
            **layout)

    def _choose_layout_host(self, ro, ci, vals, row_ids, row_nnz, ell: str,
                            ell_max_ratio: float) -> dict:
        """Host layout choice: DIA if banded, else the windowed-ELL
        (SWELL) Pallas layout if the block windows fit, as one layout
        or in the row-split form (ops/pallas_swell.split_pays: whichever
        the kernels' clock puts lower, from the pattern alone), else
        padded ELL if the row lengths are tight. Returns the layout
        fields as a dict for dataclasses.replace."""
        n = self.num_rows
        out = dict(ell_cols=None, ell_vals=None, dia_offsets=None,
                   dia_vals=None, swell_cols=None, swell_vals=None,
                   swell_c0row=None, swell_nchunk=None, swell_w128=0,
                   split=None)
        if n > 0 and self.nnz > 0 and not self.has_external_diag \
                and ell == "auto":
            diffs = ci.astype(np.int64) - row_ids
            # cheap rejection before the full O(nnz log nnz) unique:
            # distinct offsets in any subset lower-bound the full count,
            # so a >32-offset sample proves the matrix is not banded
            # (coarse AMG operators hit this every level)
            if diffs.shape[0] > (1 << 17) and \
                    np.unique(diffs[: 1 << 17]).shape[0] > \
                    self.DIA_MAX_OFFSETS:
                offs = None
            else:
                offs = np.unique(diffs)
            k = 0 if offs is None else int(offs.shape[0])
            if offs is not None and k <= self.DIA_MAX_OFFSETS and \
                    k * n <= self.DIA_FILL_RATIO * max(self.nnz, 1):
                from .ops.pallas_spmv import LANES, dia_padded_rows
                out["dia_offsets"] = tuple(int(o) for o in offs)
                d_idx = np.searchsorted(offs, diffs)
                rows_pad = dia_padded_rows(k, n)
                slots = d_idx * (rows_pad * LANES) + row_ids
                size = k * rows_pad * LANES
                if np.iscomplexobj(vals):
                    flat = (np.bincount(slots, weights=vals.real,
                                        minlength=size)
                            + 1j * np.bincount(slots, weights=vals.imag,
                                               minlength=size))
                else:
                    flat = np.bincount(slots, weights=vals,
                                       minlength=size)
                out["dia_vals"] = flat.astype(vals.dtype).reshape(
                    k, rows_pad, LANES)
                return out
        if n > 0 and self.nnz > 0 and ell == "auto":
            from .ops.pallas_swell import (build_swell_host, note_chosen,
                                           split_pays)
            # the row-split form where the pattern's count says it is
            # clearly the cheaper, the one SWELL layout where the budget
            # admits it, the row-split form again where it does not
            choice = None if self.has_external_diag \
                else split_pays(ro, ci, n)
            if choice is not None:
                out["split"] = self._split_host(ro, ci, vals, choice[0])
                if out["split"] is not None:
                    note_chosen(choice[1])
                    return out
            sw = build_swell_host(ro, ci, vals, n, self.num_cols)
            if sw is not None:
                (out["swell_cols"], out["swell_vals"], out["swell_c0row"],
                 out["swell_nchunk"], out["swell_w128"]) = sw
                return out
            if choice is None and not self.has_external_diag:
                out["split"] = self._split_host(ro, ci, vals)
                if out["split"] is not None:
                    return out
        if n > 0 and ell != "never" and self.nnz > 0:
            max_k = int(row_nnz.max()) if row_nnz.size else 0
            mean = max(float(self.nnz) / max(n, 1), 1e-30)
            want_ell = (ell == "always") or (
                ell == "auto" and max_k > 0 and max_k / mean <= ell_max_ratio)
            if want_ell and max_k > 0:
                flat = row_ids.astype(np.int64) * max_k + (
                    np.arange(self.nnz, dtype=np.int64) -
                    ro[row_ids].astype(np.int64))
                ec = np.zeros(n * max_k, np.int32)
                ec[flat] = ci
                ev = np.zeros(n * max_k, vals.dtype)
                ev[flat] = vals
                out["ell_cols"], out["ell_vals"] = \
                    ec.reshape(n, max_k), ev.reshape(n, max_k)
        return out

    def _split_host(self, ro, ci, vals, K=None) -> "Optional[tuple]":
        """(A', S) of the row-split SWELL form, as spmv-only matrices
        (placeholders where the CSR payloads were: A' shares this
        matrix's columns and values), or None where it does not fit;
        `K` is `split_pays`'s where it chose."""
        from .ops.pallas_swell import split_rows_host
        parts = split_rows_host(ro, ci, vals, self.num_rows, self.num_cols,
                                K)
        if parts is None:
            return None
        (ro_p, lay_a), (ro_s, lay_s) = parts
        dummy_i, dummy_v = _placeholder(jnp.int32), _placeholder(vals.dtype)

        def part(row_offsets, lay, rows, cols):
            return CsrMatrix(
                row_offsets=row_offsets, col_indices=dummy_i,
                values=dummy_v, swell_cols=lay[0], swell_vals=lay[1],
                swell_c0row=lay[2], swell_nchunk=lay[3], swell_w128=lay[4],
                num_rows=rows, num_cols=cols, initialized=True)
        n_p = int(ro_p.shape[0]) - 1
        return (part(ro_p, lay_a, n_p, self.num_cols),
                part(ro_s, lay_s, self.num_rows, n_p))

    def _choose_layout(self, row_ids, row_nnz, ell: str,
                       ell_max_ratio: float):
        """DIA-if-banded else ELL-if-tight layout choice (shared by init
        and build_spmv_layout)."""
        n = self.num_rows
        ell_cols = ell_vals = None
        dia_offsets = dia_vals = None
        if n > 0 and self.nnz > 0 and not self.is_block \
                and not self.has_external_diag and ell == "auto":
            dia_offsets, dia_vals = self._try_build_dia(row_ids)
        if dia_offsets is None and n > 0 and ell != "never" and self.nnz > 0:
            max_k = int(jnp.max(row_nnz))
            mean = max(float(self.nnz) / max(n, 1), 1e-30)
            want_ell = (ell == "always") or (
                ell == "auto" and max_k > 0 and max_k / mean <= ell_max_ratio)
            if want_ell and max_k > 0:
                ell_cols, ell_vals = self._build_ell(row_ids, row_nnz, max_k)
        return ell_cols, ell_vals, dia_offsets, dia_vals

    def build_spmv_layout(self, ell: str = "auto",
                          ell_max_ratio: float = 3.0) -> "CsrMatrix":
        """Add a DIA/ELL fast-path layout to an already-initialized
        matrix (the AMG setup produces initialized exact-size CSR coarse
        operators; without this they would SpMV through the scatter-based
        segment-sum path, which is the slow shape on TPU)."""
        if not self.initialized:
            return self.init(ell=ell, ell_max_ratio=ell_max_ratio)
        if self.dia_vals is not None or self.ell_cols is not None \
                or self.swell_cols is not None or self.split is not None:
            return self
        if not self.is_block and host_resident(
                self.row_offsets, self.col_indices, self.values,
                self.row_ids):
            ro = np.asarray(self.row_offsets)
            vals = np.asarray(self.values)
            layout = self._choose_layout_host(
                ro, np.asarray(self.col_indices), vals,
                np.asarray(self.row_ids), np.diff(ro), ell,
                ell_max_ratio)
            return dataclasses.replace(self, **layout)
        row_nnz = jnp.diff(self.row_offsets)
        ell_cols, ell_vals, dia_offsets, dia_vals = self._choose_layout(
            self.row_ids, row_nnz, ell, ell_max_ratio)
        return dataclasses.replace(
            self, ell_cols=ell_cols, ell_vals=ell_vals,
            dia_offsets=dia_offsets, dia_vals=dia_vals)

    # ------------------------------------------------------------------
    DIA_MAX_OFFSETS = 32
    DIA_FILL_RATIO = 3.0

    def _try_build_dia(self, row_ids):
        """Diagonal (DIA) storage when the sparsity is banded with few
        distinct offsets (stencil matrices). On TPU this is the fast SpMV
        layout: shifted dense multiply-adds, no gather at all."""
        offs = jnp.unique(self.col_indices.astype(jnp.int32)
                          - row_ids.astype(jnp.int32))
        k = int(offs.shape[0])
        n = self.num_rows
        if k > self.DIA_MAX_OFFSETS or k * n > self.DIA_FILL_RATIO * \
                max(self.nnz, 1):
            return None, None
        offsets = tuple(int(o) for o in offs)
        return offsets, self._build_dia_vals(offsets, row_ids)

    def _build_dia_vals(self, offsets, row_ids):
        """Scatter-add CSR values onto per-diagonal rows (duplicates sum,
        matching the segsum/ELL paths), stored tile-aligned as
        (k, rows_pad, 128) so the Pallas SpMV kernel streams them with
        zero re-layout (see ops/pallas_spmv.py). Serves init() on the
        device, and with_values where the values are traced or complex
        (every other refill goes through the kept map, _refill_dia)."""
        from .ops.pallas_spmv import LANES, dia_padded_rows
        offs = jnp.asarray(offsets, jnp.int32)
        d_idx = jnp.searchsorted(offs, self.col_indices.astype(jnp.int32)
                                 - row_ids.astype(jnp.int32))
        k = len(offsets)
        rows_pad = dia_padded_rows(k, self.num_rows)
        flat = jnp.zeros((k, rows_pad * LANES), self.dtype).at[
            d_idx, row_ids].add(self.values)
        return flat.reshape(k, rows_pad, LANES)

    def _ell_slots(self, row_ids, max_k: int):
        """Flat scatter targets mapping each CSR entry into (n, max_k)."""
        pos_in_row = jnp.arange(self.nnz, dtype=jnp.int32) - \
            self.row_offsets[row_ids]
        return row_ids * max_k + pos_in_row

    def _scatter_ell_vals(self, flat, max_k: int):
        n = self.num_rows
        if self.is_block:
            bx, by = self.block_dimx, self.block_dimy
            ev = jnp.zeros((n * max_k, bx, by), self.dtype).at[flat].set(
                self.values)
            return ev.reshape(n, max_k, bx, by)
        ev = jnp.zeros((n * max_k,), self.dtype).at[flat].set(self.values)
        return ev.reshape(n, max_k)

    def _build_ell(self, row_ids, row_nnz, max_k: int):
        """Scatter CSR entries into an (n, max_k) padded layout. Padding
        slots point at column 0 with zero values so gathers stay in-bounds."""
        n = self.num_rows
        flat = self._ell_slots(row_ids, max_k)
        ell_cols = jnp.zeros((n * max_k,), jnp.int32).at[flat].set(
            self.col_indices)
        return ell_cols.reshape(n, max_k), self._scatter_ell_vals(flat, max_k)

    # ------------------------------------------------------------------
    def diagonal(self) -> Array:
        """Return the diagonal, (n,) scalar or (n, bx, by) block
        (computeDiagonal analog, src/matrix.cu)."""
        if self.has_external_diag:
            return self.diag
        if self.dia_offsets is not None and 0 in self.dia_offsets:
            # O(1) from the DIA layout: row-major slice of the main
            # diagonal (avoids the values gather entirely)
            idx0 = self.dia_offsets.index(0)
            return self.dia_vals[idx0].reshape(-1)[: self.num_rows]
        A = self if self.initialized else self.init(ell="never")
        safe = jnp.maximum(A.diag_idx, 0)
        d = A.values[safe]
        missing = (A.diag_idx < 0)
        if self.is_block:
            d = jnp.where(missing[:, None, None], 0.0, d)
        else:
            d = jnp.where(missing, 0.0, d)
        return d

    def to_dense(self) -> Array:
        """Dense (n*bx, m*by) expansion — test/debug utility."""
        n, m = self.num_rows, self.num_cols
        bx, by = self.block_dimx, self.block_dimy
        row_ids = self.row_ids
        if row_ids is None:
            row_nnz = jnp.diff(self.row_offsets)
            row_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), row_nnz,
                                 total_repeat_length=self.nnz)
        if self.is_block:
            dense = jnp.zeros((n, m, bx, by), self.dtype)
            dense = dense.at[row_ids, self.col_indices].add(self.values)
            if self.has_external_diag:
                dense = dense.at[jnp.arange(n), jnp.arange(n)].add(self.diag)
            return dense.transpose(0, 2, 1, 3).reshape(n * bx, m * by)
        dense = jnp.zeros((n, m), self.dtype)
        dense = dense.at[row_ids, self.col_indices].add(self.values)
        if self.has_external_diag:
            dense = dense + jnp.diag(self.diag)
        return dense

    def with_values(self, values: Array, diag: Optional[Array] = None
                    ) -> "CsrMatrix":
        """Replace coefficients keeping structure
        (AMGX_matrix_replace_coefficients analog). The value layouts
        follow: ELL re-scatters on the device; DIA goes through the
        pattern's kept refill map on the host (_refill_dia; traced or
        complex values and structure without host mirrors rebuild the
        slab on the device with _build_dia_vals); SWELL re-packs host
        values natively and drops its layout for values that live on
        the device."""
        if values.shape != self.values.shape:
            raise BadParametersError(
                f"replace_coefficients: value shape {values.shape} != "
                f"{self.values.shape}")
        new_diag = diag if diag is not None else self.diag
        out = dataclasses.replace(self, values=values, diag=new_diag)
        if self.initialized and self.ell_cols is not None:
            # structure auxiliaries (row_ids, diag_idx, ell_cols) survive;
            # only the padded ELL values depend on the coefficients
            with span("matrix.refill_host", counter="matrix.refill_host_s"):
                max_k = self.ell_cols.shape[1]
                flat = out._ell_slots(self.row_ids, max_k)
                out = dataclasses.replace(
                    out, ell_vals=out._scatter_ell_vals(flat, max_k))
        if self.initialized and self.dia_offsets is not None:
            out = out._refill_dia(values)
        if self.initialized and self.swell_cols is not None:
            if host_resident(self.row_offsets, values):
                out = dataclasses.replace(
                    out, swell_vals=self._swell_vals_of(values))
            else:
                # structure kept but values not re-scatterable off-host;
                # drop the fast-path layout rather than serve stale data
                _tm.inc("matrix.swell_layout_dropped")
                _warn_swell_dropped()
                out = dataclasses.replace(
                    out, swell_cols=None, swell_vals=None,
                    swell_c0row=None, swell_nchunk=None, swell_w128=0)
        if self.initialized and self.split is not None:
            Ap, S = self.split
            if host_resident(Ap.row_offsets, values):
                # A' holds the operator's values under its own offsets
                out = dataclasses.replace(out, split=(dataclasses.replace(
                    Ap, swell_vals=Ap._swell_vals_of(values)), S))
            else:
                _tm.inc("matrix.swell_layout_dropped")
                _warn_swell_dropped()
                out = dataclasses.replace(out, split=None)
        return out

    def _swell_vals_of(self, values):
        """New coefficients (host) packed into this SWELL layout's
        slots, by this matrix's row offsets."""
        from .ops.pallas_swell import swell_vals_host
        with span("matrix.refill_host", counter="matrix.refill_host_s"):
            return swell_vals_host(
                np.asarray(self.row_offsets), np.asarray(values),
                self.num_rows, self.swell_cols.shape[2])

    def _dia_shape(self):
        from .ops.pallas_spmv import LANES, dia_padded_rows
        k = len(self.dia_offsets)
        return (k, dia_padded_rows(k, self.num_rows), LANES)

    def _refill_map(self) -> "Optional[_RefillMap]":
        """The pattern's refill map from the side table; the first call
        on a pattern builds it from the host mirrors of col_indices and
        row_ids. None where the structure cannot be served on the host
        (built on an accelerator, or under a forced device setup)."""
        key = (id(self.col_indices), id(self.row_offsets),
               self.dia_offsets)
        with _REFILL_MAPS_LOCK:
            kept = _REFILL_MAPS.get(key)
            if kept is not None:
                _tm.inc("matrix.refill_map.reuse")
                return kept
            host = host_arrays(self.col_indices, self.row_ids)
            if host is None:
                return None
            kept = _RefillMap(host[0], host[1], self.dia_offsets,
                              self._dia_shape())
            for a in (self.col_indices, self.row_offsets):
                weakref.finalize(a, _REFILL_MAPS.pop, key, None)
            _REFILL_MAPS[key] = kept
            _tm.inc("matrix.refill_map.build")
            return kept

    def _refill_dia(self, values) -> "CsrMatrix":
        """Values-only DIA refill for replace_coefficients. Concrete
        real values on a pattern whose structure has host mirrors go
        through the pattern's kept refill map (_refill_map): one numpy
        pass on the host (values that live on the device come to the
        host for it, through their mirror where they have one), then
        the slab is put, beside host values. Traced values (with_values
        under jit or vmap), complex ones and structure without host
        mirrors rebuild the slab with _build_dia_vals, as init() does
        on the device."""
        traced = any(isinstance(a, jax.core.Tracer)
                     for a in (values, self.col_indices, self.row_ids))
        kept = None
        if not traced and not jnp.iscomplexobj(values):
            with span("matrix.refill_host", counter="matrix.refill_host_s"):
                kept = self._refill_map()
                if kept is not None:
                    host_vals = np.ascontiguousarray(
                        host_mirror_asarray(values))
                    dia_np = kept.slab(host_vals)
        if kept is None:
            return dataclasses.replace(
                self, dia_vals=self._build_dia_vals(self.dia_offsets,
                                                    self.row_ids))
        # to the device of the (unchanged) structure arrays (numpy ones:
        # the default device); no wait on what is put: the call never
        # had one
        ci = self.col_indices
        dev = None if isinstance(ci, np.ndarray) else next(iter(ci.devices()))
        from_host = isinstance(values, np.ndarray)
        with span("matrix.upload", counter="matrix.upload_s"):
            put = (dia_np, host_vals) if from_host else (dia_np,)
            on_dev = [jax.device_put(x, dev) for x in put]
            if next(iter(on_dev[0].devices())).platform != "cpu":
                for d, x in zip(on_dev, put):
                    _register_host_mirror(d, x)
            _tm.inc("matrix.upload_bytes", sum(x.nbytes for x in put))
        return dataclasses.replace(
            self, values=on_dev[1] if from_host else values,
            dia_vals=on_dev[0])

    def interior_exterior_split(self, num_owned_cols: int):
        """INTERIOR/BOUNDARY view split (include/matrix.h:82-88 views):
        returns (A_interior, A_boundary) where A_interior keeps the
        entries whose column is owned (< num_owned_cols) and A_boundary
        the rest — y = A x == A_int x + A_bnd x. Both views share this
        matrix's shape; the split is by entry, matching the
        latency-hiding decomposition the distributed SpMV uses
        (multiply.cu:95-110, distributed/dist_matrix.py)."""
        if self.is_block:
            raise BadParametersError(
                "interior_exterior_split: scalar matrices only")
        src = self if self.initialized else self.init(ell="never")
        rows, cols, vals = src.coo()
        interior = cols < num_owned_cols
        vi = jnp.where(interior, vals, 0.0)
        vb = jnp.where(interior, 0.0, vals)
        base = dict(row_offsets=src.row_offsets,
                    col_indices=src.col_indices,
                    row_ids=rows, num_rows=src.num_rows,
                    num_cols=src.num_cols, initialized=True)
        A_int = CsrMatrix(values=vi, diag=src.diag, diag_idx=src.diag_idx,
                          ell_cols=None, ell_vals=None, dia_offsets=None,
                          dia_vals=None, **base)
        A_bnd = CsrMatrix(values=vb, diag=None,
                          diag_idx=jnp.full((src.num_rows,), -1,
                                            jnp.int32),
                          ell_cols=None, ell_vals=None, dia_offsets=None,
                          dia_vals=None, **base)
        return A_int, A_bnd

    # ------------------------------------------------------------------
    @staticmethod
    def from_coo(rows, cols, vals, num_rows: int, num_cols: int,
                 block_dims=(1, 1), coalesce: bool = True,
                 diag: Optional[Array] = None) -> "CsrMatrix":
        """Build CSR from (unsorted) COO triplets; duplicates are summed
        when `coalesce` (matches the upload semantics of
        AMGX_matrix_upload_all, src/amgx_c.cu:3039)."""
        rows = jnp.asarray(rows, jnp.int32)
        cols = jnp.asarray(cols, jnp.int32)
        vals = jnp.asarray(vals)
        bx, by = block_dims
        order = lexsort_rc(rows, cols)
        rows, cols, vals = rows[order], cols[order], vals[order]
        if coalesce and rows.shape[0] > 0:
            newseg = jnp.concatenate(
                [jnp.ones((1,), bool),
                 (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])])
            seg = jnp.cumsum(newseg) - 1
            nuniq = int(seg[-1]) + 1
            first = jnp.nonzero(newseg, size=nuniq)[0]
            vals = _seg_sum(vals, seg, nuniq)
            rows, cols = rows[first], cols[first]
        counts = jnp.bincount(rows, length=num_rows)
        row_offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts).astype(jnp.int32)])
        return CsrMatrix(row_offsets=row_offsets, col_indices=cols,
                         values=vals, diag=diag, num_rows=num_rows,
                         num_cols=num_cols, block_dimx=bx, block_dimy=by)

    @staticmethod
    def from_dense(dense, tol: float = 0.0) -> "CsrMatrix":
        dense = np.asarray(dense)
        rows, cols = np.nonzero(np.abs(dense) > tol)
        return CsrMatrix.from_coo(rows, cols, jnp.asarray(dense[rows, cols]),
                                  dense.shape[0], dense.shape[1])

    @staticmethod
    def from_scipy_like(row_offsets, col_indices, values, num_rows, num_cols,
                        block_dims=(1, 1), diag=None) -> "CsrMatrix":
        def put(x, dtype=None):
            if x is None:
                return None
            dev = jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)
            if isinstance(x, np.ndarray) and not isinstance(dev, np.ndarray):
                try:
                    on_accel = next(iter(dev.devices())).platform != "cpu"
                except Exception:
                    on_accel = False
                if on_accel:
                    # mirror a COPY: x may view caller-owned memory
                    # (e.g. an upload buffer) that the caller reuses
                    # after upload — the mirror must stay equal to the
                    # immutable device array. CPU-resident arrays skip
                    # the mirror (its only consumer is the host-setup
                    # pull, which is free on CPU).
                    _register_host_mirror(dev, np.array(x, dev.dtype))
            return dev

        return CsrMatrix(
            row_offsets=put(row_offsets, jnp.int32),
            col_indices=put(col_indices, jnp.int32),
            values=put(values), diag=put(diag),
            num_rows=int(num_rows), num_cols=int(num_cols),
            block_dimx=block_dims[0], block_dimy=block_dims[1])

    def slim_for_spmv(self) -> "CsrMatrix":
        """Drop every array the SpMV dispatch path does not read, given
        the built layout (DIA keeps only dia_vals; ELL keeps the padded
        arrays). Solve-phase data pytrees use this so multi-GB unused
        CSR payloads don't occupy HBM as program arguments (at 256^3 the
        fine matrix's unused values/col_indices/row_ids cost ~2 GB).
        The result supports spmv()/residual() ONLY — setup-phase
        consumers (diagonal, coo, Galerkin) need the full matrix."""
        if not self.initialized:
            return self
        dummy_i, dummy_v = _placeholder(jnp.int32), _placeholder(self.dtype)
        if self.dia_vals is not None:
            return dataclasses.replace(
                self, values=dummy_v,
                col_indices=dummy_i, row_ids=None, diag_idx=None,
                row_offsets=dummy_i, ell_cols=None, ell_vals=None,
                swell_cols=None, swell_vals=None, swell_c0row=None,
                swell_nchunk=None, swell_w128=0)
        if self.swell_cols is not None or self.split is not None:
            return dataclasses.replace(
                self, values=dummy_v,
                col_indices=dummy_i, row_ids=None, diag_idx=None,
                row_offsets=dummy_i, ell_cols=None, ell_vals=None,
                split=None if self.split is None else tuple(
                    dataclasses.replace(part, row_offsets=dummy_i)
                    for part in self.split))
        if self.ell_cols is not None:
            return dataclasses.replace(
                self, values=dummy_v,
                col_indices=dummy_i, row_ids=None, diag_idx=None,
                row_offsets=dummy_i)
        return self

    def astype(self, dtype) -> "CsrMatrix":
        """Cast all floating-point payloads (values/diag + any built
        ELL/DIA layouts) to `dtype`, keeping structure arrays intact.
        Used by the mixed-precision execution paths (amg_precision,
        REFINEMENT) to derive the reduced-precision operator."""
        def cast(a):
            if a is not None and jnp.issubdtype(a.dtype, jnp.inexact):
                return a.astype(dtype)
            return a
        return dataclasses.replace(
            self, values=cast(self.values), diag=cast(self.diag),
            ell_vals=cast(self.ell_vals), dia_vals=cast(self.dia_vals),
            swell_vals=cast(self.swell_vals),
            split=None if self.split is None else tuple(
                part.astype(dtype) for part in self.split))

    def coo(self):
        """Return (row_ids, col_indices, values) COO triplets. Computes
        row_ids standalone when uninitialized (no need for the full init)."""
        if self.row_ids is not None:
            return self.row_ids, self.col_indices, self.values
        row_nnz = jnp.diff(self.row_offsets)
        row_ids = jnp.repeat(jnp.arange(self.num_rows, dtype=jnp.int32),
                             row_nnz, total_repeat_length=self.nnz)
        return row_ids, self.col_indices, self.values
