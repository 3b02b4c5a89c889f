"""Native (C++) runtime components.

The reference keeps inherently serial setup algorithms on the host in
C++ (e.g. Ruge-Stueben coarsening, src/classical/selectors/rs.cu:269
refuses the GPU path outright). This package holds the analogous native
pieces: small C++ translation units compiled once into a shared library
with the system toolchain and bound via ctypes — no Python stand-ins for
the serial hot paths.

`lib()` compiles on first use and returns the loaded ctypes library, or
None when it cannot be built — callers that can do without fall back to
their pure-Python equivalents (with one RuntimeWarning); callers that
asked for the native form pass `required=True` and get the compiler's
message as a RuntimeError instead. Build artifacts live in _build/
(gitignored), keyed by a content hash of the sources so stale binaries
are never loaded; the .so is written atomically so concurrent processes
cannot load a half-written file.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_BUILD = os.path.join(_DIR, "_build")

_lock = threading.Lock()
_lib = None
_attempted_hash = None    # content hash of the last build attempt
_build_error = None       # why the last attempt left no library


def _src_files():
    return sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC)
        if f.endswith(".cpp"))


def source_hash() -> str:
    """Content hash of native/src/*.cpp — the library's build key."""
    h = hashlib.sha256()
    for path in _src_files():
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _lib_path(src_hash: str) -> str:
    return os.path.join(_BUILD, f"libamgx_native-{src_hash}.so")


def _build(target: str) -> bool:
    global _build_error
    os.makedirs(_BUILD, exist_ok=True)
    tmp = target + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
           "-o", tmp] + _src_files()
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, target)          # atomic publish
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        stderr = getattr(e, "stderr", None)
        _build_error = f"{type(e).__name__}: {e}" + (
            "\n" + stderr.decode("utf-8", "replace")[-2000:]
            if stderr else "")
        warnings.warn(
            "native library build failed; native fast paths disabled, "
            "pure-Python fallbacks in use: " + _build_error[-300:],
            RuntimeWarning)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    # prune superseded builds; best-effort, must not fail the build
    try:
        for f in os.listdir(_BUILD):
            p = os.path.join(_BUILD, f)
            if f.endswith(".so") and p != target:
                try:
                    os.unlink(p)
                except OSError:
                    pass
    except OSError:
        pass
    return True


def lib(required: bool = False):
    """The loaded native library, or None if unavailable. A failed build
    is cached per source hash — no repeated compiler spawns. With
    `required=True` an unavailable library raises RuntimeError carrying
    the compiler's output instead of returning None."""
    global _lib, _attempted_hash, _build_error
    with _lock:
        h = source_hash()
        if _attempted_hash != h:
            _attempted_hash = h
            _lib = None
            _build_error = None
            target = _lib_path(h)
            if os.path.exists(target) or _build(target):
                try:
                    _lib = ctypes.CDLL(target)
                except OSError as e:
                    _build_error = f"cannot load {target}: {e}"
        if _lib is None and required:
            raise RuntimeError(
                "native library required but unavailable: "
                + (_build_error or "unknown reason"))
    return _lib


_warned_fallback = False


def warn_python_fallback(component: str, n: int):
    """One-shot warning when a serial native component falls back to
    Python on a large problem."""
    global _warned_fallback
    if not _warned_fallback and n > 100_000:
        _warned_fallback = True
        warnings.warn(
            f"native library unavailable (no C++ toolchain?); {component} "
            f"is running its pure-Python fallback on n={n} rows — setup "
            "will be slow", RuntimeWarning)


def rs_coarsen_native(n, row_offsets, col_indices, strong):
    """Native RS first-pass coarsening; returns cf_map (n,) int32 or
    None when the native library is unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    fn = L.amgx_rs_coarsen
    fn.restype = ctypes.c_int
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    st = np.ascontiguousarray(strong, np.uint8)
    cf = np.empty(n, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    rc = fn(ctypes.c_int32(n),
            ro.ctypes.data_as(i32p), ci.ctypes.data_as(i32p),
            st.ctypes.data_as(u8p), cf.ctypes.data_as(i32p))
    if rc != 0:
        return None
    return cf


def pmis_native(n, row_offsets, col_indices, strong, init=None,
                max_iters=30):
    """Native PMIS CF-splitting (bit-exact replica of the jnp fixed
    point in amg/classical/selectors.py::pmis_split); returns cf (n,)
    int32 or None when the native library is unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    fn = L.amgx_pmis
    fn.restype = ctypes.c_int
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    st = np.ascontiguousarray(strong, np.uint8)
    cf = np.empty(n, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    if init is not None:
        init = np.ascontiguousarray(init, np.int32)
        init_p = init.ctypes.data_as(i32p)
    else:
        init_p = None
    rc = fn(ctypes.c_int32(int(n)),
            ro.ctypes.data_as(i32p), ci.ctypes.data_as(i32p),
            st.ctypes.data_as(u8p), init_p,
            ctypes.c_int32(int(max_iters)), cf.ctypes.data_as(i32p))
    if rc != 0:
        return None
    return cf


def strength_ahat_native(n, row_offsets, col_indices, values, theta,
                         max_row_sum):
    """Native AHAT strength mask; returns (strong (nnz,) bool, rows the
    row-sum rule weakened) or None when the native library is
    unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    fn = L.amgx_strength_ahat
    fn.restype = ctypes.c_int64
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    va = np.ascontiguousarray(values, np.float64)
    strong = np.empty(ci.shape[0], np.uint8)
    weakened = fn(
        ctypes.c_int32(int(n)), ro.ctypes.data_as(i32p),
        ci.ctypes.data_as(i32p), va.ctypes.data_as(f64p),
        ctypes.c_double(float(theta)), ctypes.c_double(float(max_row_sum)),
        strong.ctypes.data_as(u8p))
    return strong.view(np.bool_), int(weakened)


def l1_diag_native(n, row_offsets, col_indices, values):
    """Native L1-strengthened Jacobi diagonal; returns (n,) float64 or
    None when the native library is unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    fn = L.amgx_l1_diag
    fn.restype = None
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    va = np.ascontiguousarray(values, np.float64)
    out = np.empty(int(n), np.float64)
    fn(ctypes.c_int32(int(n)), ro.ctypes.data_as(i32p),
       ci.ctypes.data_as(i32p), va.ctypes.data_as(f64p),
       out.ctypes.data_as(f64p))
    return out


def d2_interp_native(n, row_offsets, col_indices, values, strong, cf,
                     trunc_factor=1.1, max_elements=-1):
    """Native distance-two ext+i interpolation (the host analog of
    src/classical/interpolators/distance2.cu) with fused truncation.
    Returns (p_ptr int64 (n+1,), p_col int32, p_val float64, rows that
    lost an entry to the truncation) or None."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    build = L.amgx_d2_build
    build.restype = ctypes.c_longlong
    fetch = L.amgx_d2_fetch
    fetch.restype = None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    va = np.ascontiguousarray(values, np.float64)
    st = np.ascontiguousarray(strong, np.uint8)
    cfm = np.ascontiguousarray(cf, np.int32)
    handle = ctypes.c_void_p()
    lost = ctypes.c_int64(0)
    nnz = build(ctypes.c_int32(int(n)),
                ro.ctypes.data_as(i32p), ci.ctypes.data_as(i32p),
                va.ctypes.data_as(f64p), st.ctypes.data_as(u8p),
                cfm.ctypes.data_as(i32p),
                ctypes.c_double(float(trunc_factor)),
                ctypes.c_int32(int(max_elements)), ctypes.byref(lost),
                ctypes.byref(handle))
    if nnz < 0 or not handle:
        return None
    p_ptr = np.empty(int(n) + 1, np.int64)
    p_col = np.empty(int(nnz), np.int32)
    p_val = np.empty(int(nnz), np.float64)
    fetch(handle, p_ptr.ctypes.data_as(i64p),
          p_col.ctypes.data_as(i32p), p_val.ctypes.data_as(f64p))
    return p_ptr, p_col, p_val, int(lost.value)


def two_step_pattern_native(n, row_offsets, col_indices, strong):
    """Pattern of S@S less its diagonal (the aggressive selector's
    graph) as (ptr int64 (n+1,), col int32), or None."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    build, fetch = L.amgx_two_step_pattern, L.amgx_d2_fetch
    build.restype = ctypes.c_longlong
    fetch.restype = None
    i32p = ctypes.POINTER(ctypes.c_int32)
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    st = np.ascontiguousarray(strong, np.uint8)
    handle = ctypes.c_void_p()
    nnz = build(ctypes.c_int32(int(n)), ro.ctypes.data_as(i32p),
                ci.ctypes.data_as(i32p),
                st.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.byref(handle))
    ptr = np.empty(int(n) + 1, np.int64)
    col = np.empty(int(nnz), np.int32)
    fetch(handle, ptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
          col.ctypes.data_as(i32p), None)
    return ptr, col


def multipass_native(n, row_offsets, col_indices, values, strong, cf):
    """Native multipass interpolation, rows whole (the host analog of
    src/classical/interpolators/multipass.cu). Returns (p_ptr int64
    (n+1,), p_col int32, p_val float64) or None."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    build, fetch = L.amgx_multipass_build, L.amgx_d2_fetch
    build.restype = ctypes.c_longlong
    fetch.restype = None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    ro = np.ascontiguousarray(row_offsets, np.int32)
    ci = np.ascontiguousarray(col_indices, np.int32)
    va = np.ascontiguousarray(values, np.float64)
    st = np.ascontiguousarray(strong, np.uint8)
    cfm = np.ascontiguousarray(cf, np.int32)
    handle = ctypes.c_void_p()
    nnz = build(ctypes.c_int32(int(n)), ro.ctypes.data_as(i32p),
                ci.ctypes.data_as(i32p), va.ctypes.data_as(f64p),
                st.ctypes.data_as(u8p), cfm.ctypes.data_as(i32p),
                ctypes.byref(handle))
    if nnz < 0 or not handle:
        return None
    p_ptr = np.empty(int(n) + 1, np.int64)
    p_col = np.empty(int(nnz), np.int32)
    p_val = np.empty(int(nnz), np.float64)
    fetch(handle, p_ptr.ctypes.data_as(i64p),
          p_col.ctypes.data_as(i32p), p_val.ctypes.data_as(f64p))
    return p_ptr, p_col, p_val


def rap_native(nc, n, ncp, r_ptr, r_col, r_val, a_ptr, a_col, a_val,
               p_ptr, p_col, p_val):
    """Fused native Galerkin triple product C = R@A@P (scalar CSR; the
    csr_galerkin_product analog). Returns (c_ptr int64 (nc+1,), c_col
    int32, c_val float64) with sorted columns per row, or None when the
    native library is unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    build = L.amgx_rap_build
    build.restype = ctypes.c_longlong
    fetch = L.amgx_rap_fetch
    fetch.restype = None
    rp = np.ascontiguousarray(r_ptr, np.int32)
    rc = np.ascontiguousarray(r_col, np.int32)
    rv = np.ascontiguousarray(r_val, np.float64)
    ap = np.ascontiguousarray(a_ptr, np.int32)
    ac = np.ascontiguousarray(a_col, np.int32)
    av = np.ascontiguousarray(a_val, np.float64)
    pp = np.ascontiguousarray(p_ptr, np.int32)
    pc = np.ascontiguousarray(p_col, np.int32)
    pv = np.ascontiguousarray(p_val, np.float64)
    handle = ctypes.c_void_p()
    nnz = build(ctypes.c_int32(int(nc)), ctypes.c_int32(int(n)),
                ctypes.c_int32(int(ncp)),
                rp.ctypes.data_as(i32p), rc.ctypes.data_as(i32p),
                rv.ctypes.data_as(f64p),
                ap.ctypes.data_as(i32p), ac.ctypes.data_as(i32p),
                av.ctypes.data_as(f64p),
                pp.ctypes.data_as(i32p), pc.ctypes.data_as(i32p),
                pv.ctypes.data_as(f64p), ctypes.byref(handle))
    if nnz < 0 or not handle:
        return None
    c_ptr = np.empty(int(nc) + 1, np.int64)
    c_col = np.empty(int(nnz), np.int32)
    c_val = np.empty(int(nnz), np.float64)
    fetch(handle, c_ptr.ctypes.data_as(i64p),
          c_col.ctypes.data_as(i32p), c_val.ctypes.data_as(f64p))
    return c_ptr, c_col, c_val


def rap_plan_values_native(stage1, sr, st, starts2, n_u, a_val, p_val,
                           r_val):
    """Values-only Galerkin RAP sweep through a RapPlan's precomputed
    indices (src/rap_values.cpp): two flat FMA passes, no structure
    discovery, in the operands' own precision (float64, or float32
    where every operand is). `stage1` is the plan's stage-1 dict or
    None (the aggregation relabel form); `sr`/`r_val` / `p_val` may be
    None. Returns the (n_u,) value vector or None when the native
    library is unavailable (callers fall back to the numpy reduceat
    route — same sums, same order)."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    dt = np.result_type(*[x.dtype for x in (a_val, p_val, r_val)
                          if x is not None])
    if dt == np.float32:
        fn, ctype = L.amgx_rap_plan_values_f32, ctypes.c_float
    else:
        dt = np.dtype(np.float64)
        fn, ctype = L.amgx_rap_plan_values, ctypes.c_double
    i32p = ctypes.POINTER(ctypes.c_int32)
    fp = ctypes.POINTER(ctype)
    fn.restype = ctypes.c_int32
    keep = []        # retain converted temporaries across the call

    def ip32(x):
        x = np.ascontiguousarray(x, np.int32)
        keep.append(x)
        return x.ctypes.data_as(i32p)

    def vals(x):
        if x is None:
            return ctypes.cast(None, fp)
        x = np.ascontiguousarray(x, dt)
        keep.append(x)
        return x.ctypes.data_as(fp)

    null32 = ctypes.cast(None, i32p)
    if stage1 is not None:
        args1 = (ctypes.c_int64(int(stage1["nT"])), ip32(stage1["sa"]),
                 ip32(stage1["sp"]), ip32(stage1["starts1"]),)
    else:
        args1 = (ctypes.c_int64(0), null32, null32, null32)
        p_val = None
    if sr is None:
        r_val = None
    out = np.empty(int(n_u), dt)
    rc = fn(*args1, ctypes.c_int64(int(n_u)),
            null32 if sr is None else ip32(sr), ip32(st),
            ip32(starts2), vals(a_val), vals(p_val), vals(r_val),
            ctypes.c_int32(1 if stage1 is not None else 0),
            ctypes.c_int32(1 if sr is not None else 0),
            out.ctypes.data_as(fp))
    if rc != 0:
        return None
    return out


def rap_plan_stage_native(a_ro, a_ci, b_ro, b_ci, limit):
    """One stage of a RapPlan's structure phase (src/rap_plan.cpp): the
    candidates of A @ B from the two patterns, coalesced in the stable
    (row, column) order, row by row. Returns (sa, sb, seg, urow):
    int32 arrays of the A and B entry and the segment of every
    candidate in coalesce order, and the output entries of each row;
    None when the native library is unavailable, False when the
    candidates number `limit` or more (the plan's int32 guard)."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    count = L.amgx_rap_plan_stage_count
    count.restype = ctypes.c_longlong
    fill = L.amgx_rap_plan_stage_fill
    fill.restype = ctypes.c_longlong
    a_ro = np.ascontiguousarray(a_ro, np.int64)
    a_ci = np.ascontiguousarray(a_ci, np.int32)
    b_ro = np.ascontiguousarray(b_ro, np.int64)
    b_ci = np.ascontiguousarray(b_ci, np.int32)
    n = int(a_ro.shape[0]) - 1
    cum = np.empty(n + 1, np.int64)
    head = (ctypes.c_int32(n), a_ro.ctypes.data_as(i64p),
            a_ci.ctypes.data_as(i32p), b_ro.ctypes.data_as(i64p))
    total = int(count(*head, cum.ctypes.data_as(i64p)))
    if total >= limit:
        return False
    sa, sb, seg = (np.empty(total, np.int32) for _ in range(3))
    urow = np.empty(n, np.int32)
    fill(*head, b_ci.ctypes.data_as(i32p), cum.ctypes.data_as(i64p),
         sa.ctypes.data_as(i32p), sb.ctypes.data_as(i32p),
         seg.ctypes.data_as(i32p), urow.ctypes.data_as(i32p))
    return sa, sb, seg, urow


def _swell_windows(L, ro, ci, n):
    """amgx_swell_windows over contiguous int32 arrays: (c0row, kmax,
    w128_raw)."""
    import numpy as np
    from ..ops.pallas_swell import BLOCK_ROWS
    i32p = ctypes.POINTER(ctypes.c_int32)
    L.amgx_swell_windows.restype = ctypes.c_int32
    c0row = np.empty(-(-n // BLOCK_ROWS), np.int32)
    kmax = ctypes.c_int32()
    w128_raw = L.amgx_swell_windows(
        ctypes.c_int32(n), ro.ctypes.data_as(i32p),
        ci.ctypes.data_as(i32p), c0row.ctypes.data_as(i32p),
        ctypes.byref(kmax))
    return c0row, int(kmax.value), int(w128_raw)


def _swell_chunklists(L, ro, ci, n, c0row, w128):
    """amgx_swell_chunklists: (counts (nb * 8,), the lists back to
    back)."""
    import numpy as np
    from ..ops.pallas_swell import SUBS
    i32p = ctypes.POINTER(ctypes.c_int32)
    groups = SUBS * c0row.shape[0]
    counts = np.zeros(groups, np.int32)
    flat = np.empty(min(ci.shape[0], groups * w128), np.int32)
    L.amgx_swell_chunklists.restype = ctypes.c_int64
    listed = L.amgx_swell_chunklists(
        ctypes.c_int32(n), ro.ctypes.data_as(i32p), ci.ctypes.data_as(i32p),
        c0row.ctypes.data_as(i32p), ctypes.c_int32(w128),
        counts.ctypes.data_as(i32p), flat.ctypes.data_as(i32p))
    return counts, flat[:listed]


def swell_count_native(ro, ci, num_rows):
    """(kmax, w128_raw, listed chunks) of the SWELL layout a pattern
    would take (ops/pallas_swell.count_listed): the window and
    chunk-list sweeps alone, nothing scattered; None when the native
    library is unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    n = int(num_rows)
    ro = np.ascontiguousarray(ro, np.int32)
    ci = np.ascontiguousarray(ci, np.int32)
    c0row, kmax, w128_raw = _swell_windows(L, ro, ci, n)
    if kmax == 0:
        return 0, w128_raw, 0
    _counts, flat = _swell_chunklists(L, ro, ci, n, c0row,
                                      -(-w128_raw // 8) * 8)
    return kmax, w128_raw, int(flat.shape[0])


def swell_split_count_native(ro, ci, num_rows, K):
    """(rows of A', its longest row, its widest window in chunks, its
    listed chunks, S's longest row, S's listed chunks) of the row-split
    form at piece length K (ops/pallas_swell._split_counts), in one
    sweep of the pattern; None when the native library is
    unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    ro = np.ascontiguousarray(ro, np.int32)
    ci = np.ascontiguousarray(ci, np.int32)
    out = np.zeros(6, np.int64)
    L.amgx_swell_split_count.restype = None
    L.amgx_swell_split_count(
        ctypes.c_int32(int(num_rows)), ro.ctypes.data_as(i32p),
        ci.ctypes.data_as(i32p), ctypes.c_int32(int(K)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return tuple(int(v) for v in out)


def swell_build_native(ro, ci, vals, num_rows):
    """Native SWELL layout build (ops/pallas_swell.py layout contract).
    Returns (cols4, vals4, c0row, nchunk, w128) with cols4/vals4 shaped
    (nb, 8, kpad, 128) and nchunk (nb, 8, 1 + L) as
    `pallas_swell.pad_chunk_lists` lays it out, None when the layout
    does not pay (budget
    decisions delegated to ops/pallas_swell.swell_budget), or False
    when the native library is unavailable."""
    import numpy as np
    from ..ops.pallas_swell import (BLOCK_ROWS, LANES, SUBS,
                                    pad_chunk_lists, swell_budget)
    L = lib()
    vals = np.asarray(vals)
    if L is None or vals.dtype not in (np.float32, np.float64):
        return False
    n = int(num_rows)
    nb = -(-n // BLOCK_ROWS)
    i32p = ctypes.POINTER(ctypes.c_int32)
    ro = np.ascontiguousarray(ro, np.int32)
    ci = np.ascontiguousarray(ci, np.int32)
    c0row, kmax, w128_raw = _swell_windows(L, ro, ci, n)
    # budget decisions live in ONE place (ops/pallas_swell.swell_budget)
    budget = swell_budget(kmax, w128_raw, nb, ci.shape[0])
    if budget is None:
        return None
    kpad, w128 = budget
    slots = nb * SUBS * kpad * LANES
    vals = np.ascontiguousarray(vals)
    if vals.dtype == np.float32:
        fill, fp = L.amgx_swell_fill_f32, ctypes.POINTER(ctypes.c_float)
    else:
        vals = np.ascontiguousarray(vals, np.float64)
        fill, fp = L.amgx_swell_fill_f64, ctypes.POINTER(ctypes.c_double)
    fill.restype = None
    cols4 = np.zeros(slots, np.int32)
    vals4 = np.zeros(slots, vals.dtype)
    fill(ctypes.c_int32(n), ctypes.c_int32(kpad),
         ro.ctypes.data_as(i32p), ci.ctypes.data_as(i32p),
         vals.ctypes.data_as(fp), c0row.ctypes.data_as(i32p),
         cols4.ctypes.data_as(i32p), vals4.ctypes.data_as(fp))
    counts, flat = _swell_chunklists(L, ro, ci, n, c0row, w128)
    return (cols4.reshape(nb, SUBS, kpad, LANES),
            vals4.reshape(nb, SUBS, kpad, LANES), c0row,
            pad_chunk_lists(counts, flat, nb), w128)


def swell_refill_native(ro, vals, num_rows, kpad):
    """Values-only SWELL re-scatter; returns (nb, 8, kpad, 128) vals4 or
    None when the native library is unavailable."""
    import numpy as np
    from ..ops.pallas_swell import BLOCK_ROWS, LANES, SUBS
    L = lib()
    vals = np.asarray(vals)
    if L is None or vals.dtype not in (np.float32, np.float64):
        return None
    n = int(num_rows)
    nb = -(-n // BLOCK_ROWS)
    i32p = ctypes.POINTER(ctypes.c_int32)
    ro = np.ascontiguousarray(ro, np.int32)
    vals = np.ascontiguousarray(vals)
    if vals.dtype == np.float32:
        fn, fp = L.amgx_swell_refill_f32, ctypes.POINTER(ctypes.c_float)
    else:
        vals = np.ascontiguousarray(vals, np.float64)
        fn, fp = L.amgx_swell_refill_f64, ctypes.POINTER(ctypes.c_double)
    fn.restype = None
    vals4 = np.zeros(nb * SUBS * kpad * LANES, vals.dtype)
    fn(ctypes.c_int32(n), ctypes.c_int32(kpad),
       ro.ctypes.data_as(i32p), vals.ctypes.data_as(fp),
       vals4.ctypes.data_as(fp))
    return vals4.reshape(nb, SUBS, kpad, LANES)


def spgemm_native(n_a, n_b, a_ptr, a_col, a_val, b_ptr, b_col, b_val):
    """Native Gustavson CSR SpGEMM (csr_multiply.h analog). Returns
    (c_ptr int64 (n_a+1,), c_col int32, c_val float64) with sorted
    columns per row, or None when the native library is unavailable."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    count = L.amgx_spgemm_count
    count.restype = ctypes.c_longlong
    fill = L.amgx_spgemm_fill
    fill.restype = None
    ap = np.ascontiguousarray(a_ptr, np.int32)
    ac = np.ascontiguousarray(a_col, np.int32)
    av = np.ascontiguousarray(a_val, np.float64)
    bp = np.ascontiguousarray(b_ptr, np.int32)
    bc = np.ascontiguousarray(b_col, np.int32)
    bv = np.ascontiguousarray(b_val, np.float64)
    cp = np.empty(int(n_a) + 1, np.int64)
    nnz = count(ctypes.c_int32(int(n_a)), ctypes.c_int32(int(n_b)),
                ap.ctypes.data_as(i32p), ac.ctypes.data_as(i32p),
                bp.ctypes.data_as(i32p), bc.ctypes.data_as(i32p),
                cp.ctypes.data_as(i64p))
    cc = np.empty(int(nnz), np.int32)
    cv = np.empty(int(nnz), np.float64)
    fill(ctypes.c_int32(int(n_a)), ctypes.c_int32(int(n_b)),
         ap.ctypes.data_as(i32p), ac.ctypes.data_as(i32p),
         av.ctypes.data_as(f64p),
         bp.ctypes.data_as(i32p), bc.ctypes.data_as(i32p),
         bv.ctypes.data_as(f64p),
         cp.ctypes.data_as(i64p), cc.ctypes.data_as(i32p),
         cv.ctypes.data_as(f64p))
    return cp, cc, cv
