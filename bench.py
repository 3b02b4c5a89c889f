"""Benchmark entry point (run on the real TPU chip by the driver).

Writes the FULL results payload to the `BENCH.json` artifact file and
prints ONE COMPACT JSON line to stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "artifact": "BENCH.json", "extra": {scalar headline keys only}}

The stdout line carries only scalar keys (no nested breakdowns): the
driver captures a bounded stdout tail, and round 5 lost its entire
parse (`BENCH_r05.json parsed: null`) because the one-line JSON with
every per-level breakdown outgrew that capture. Breakdowns, spreads
and per-phase dictionaries live in BENCH.json, which loads with a
plain `json.load`.

The optional 256^3 north-star phase runs only when the headline phase
left wall-clock budget, and under a SIGALRM guard, so the line always
prints.

Headline: 7-pt Poisson 128^3 (2.1M rows) solved to a TRUE 1e-8 relative
residual in full f64 accuracy — BASELINE.md milestone 3 scaled to one
chip — using the TPU-native flagship configuration: REFINEMENT (f64
defect correction) around FGMRES + GEO-aggregation AMG running f32
(every level banded/DIA via the Pallas SpMV kernel, reshape transfer
operators, dense-QR coarse solve).

`vs_baseline` is measured against the reference's roofline on its own
hardware: AmgX SpMV is HBM-bandwidth-bound, so we report our achieved
SpMV bandwidth as a fraction of A100 peak (1555 GB/s) — the honest
single-chip proxy until a side-by-side A100 run exists (the reference
repo publishes no benchmark tables, BASELINE.md).
"""
from __future__ import annotations

import json
import time

import jax

from amgx_tpu import compile_cache

compile_cache.enable()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import amgx_tpu as amgx  # noqa: E402
from amgx_tpu.config import Config  # noqa: E402

A100_HBM_GBPS = 1555.0  # A2 SXM A100-40GB peak memory bandwidth

from amgx_tpu.presets import FLAGSHIP  # noqa: E402


def bench_spmv_vs_ceiling(n: int = 128, reps: int = 50, samples: int = 9):
    """SpMV GB/s on 7-pt Poisson n^3 (DIA layout, float32: the
    bandwidth-bound regime the reference's csrmv lives in), measured
    against a plain-XLA streaming loop on the same device in the SAME
    pass: the two loops are timed interleaved, best-of-N each, and the
    per-pair ratio is reported beside the absolute numbers."""
    A = amgx.gallery.poisson("7pt", n, n, n, dtype=np.float32).init()
    x = jnp.ones(A.num_rows, jnp.float32)

    @jax.jit
    def spmv_loop(x):
        def body(_, x):
            return amgx.ops.spmv(A, x) * (1.0 / 6.0)
        return jax.lax.fori_loop(0, reps, body, x)

    rows = 256 * 1024 * 1024 // (128 * 4)
    v = jnp.ones((rows, 128), jnp.float32)

    @jax.jit
    def stream_loop(v):
        return jax.lax.fori_loop(0, 10, lambda _, x: x * 1.000001, v)

    spmv_loop(x).block_until_ready()         # compile
    stream_loop(v).block_until_ready()
    # honest bytes model: each value read once, x read once, y written
    # once (the Pallas DIA kernel achieves exactly this traffic)
    n_rows = A.num_rows
    if A.dia_vals is not None:
        k = len(A.dia_offsets)
        bytes_moved = (k * n_rows + 2 * n_rows) * 4
    else:
        bytes_moved = A.ell_cols.size * (4 + 4) + A.num_rows * 4 * 2
    stream_bytes = 2 * rows * 128 * 4
    # Pair each spmv sample with an adjacent stream sample and report
    # the MEDIAN per-pair ratio with its spread — the paired quotient
    # cancels the host noise the two mins do not share.
    ratios = []
    spmv_dt, stream_dt = float("inf"), float("inf")
    for _ in range(samples):
        t0 = time.perf_counter()
        spmv_loop(x).block_until_ready()
        s_i = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        stream_loop(v).block_until_ready()
        c_i = (time.perf_counter() - t0) / 10
        spmv_dt = min(spmv_dt, s_i)
        stream_dt = min(stream_dt, c_i)
        ratios.append((bytes_moved / s_i) / (stream_bytes / c_i))
    ratios.sort()
    return {
        "gbps": bytes_moved / spmv_dt / 1e9,
        "ms": spmv_dt * 1e3,
        "ceiling_gbps": stream_bytes / stream_dt / 1e9,
        "ratio_median": ratios[len(ratios) // 2],
        "ratio_min": ratios[0],
        "ratio_max": ratios[-1],
    }


def bench_spmv_layouts(n: int = 128, reps: int = 30, swell_n: int = 192):
    """SpMV efficiency phase (`python bench.py spmv`): achieved GB/s
    against the rig's plain-XLA streaming ceiling per layout
    (DIA/ELL/SWELL), plus fused-vs-unfused for the new smoother
    kernels — the tentpole's one-pass claim as a recorded number.

    Bytes models are the honest per-layout minimums: each stored value
    read once, the vectors read/written once. The fused rows time the
    whole presmooth(2 sweeps)+residual pair; `fused_speedup` is the
    wall-clock ratio against the unfused sweep-by-sweep compose of the
    SAME math on the same layout (both jitted, best-of-N), so rig noise
    cancels in the quotient like the spmv/stream pairing above."""
    import dataclasses

    from amgx_tpu.ops import smooth as fused_ops
    from amgx_tpu.ops.batched import smooth_dia_multi  # noqa: F401
    from amgx_tpu.ops.spmv import spmv as _spmv

    rng = np.random.default_rng(11)
    out = {}

    # shared streaming ceiling (one measurement; the per-layout ratios
    # below each pair against an adjacent sample of it)
    rows = 256 * 1024 * 1024 // (128 * 4)
    v = jnp.ones((rows, 128), jnp.float32)

    @jax.jit
    def stream_loop(v):
        return jax.lax.fori_loop(0, 10, lambda _, x: x * 1.000001, v)

    stream_loop(v).block_until_ready()
    stream_bytes = 2 * rows * 128 * 4

    def _time(fn, *args):
        jax.block_until_ready(fn(*args))          # compile
        best, ceil_dt = float("inf"), float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
            t0 = time.perf_counter()
            stream_loop(v).block_until_ready()
            ceil_dt = min(ceil_dt, time.perf_counter() - t0)
        return best, stream_bytes / ceil_dt / 1e9

    def _loop(op):
        @jax.jit
        def run(x, b):
            def body(_, x):
                return op(x, b)
            return jax.lax.fori_loop(0, reps, body, x)
        return run

    # ---- DIA ----------------------------------------------------------
    A = amgx.gallery.poisson("7pt", n, n, n, dtype=np.float32).init()
    k = len(A.dia_offsets)
    nr = A.num_rows
    x = jnp.ones(nr, jnp.float32)
    b = jnp.ones(nr, jnp.float32)
    dinv = jnp.full((nr,), 1.0 / 6.0, jnp.float32)
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)

    spmv_dt, ceil = _time(_loop(lambda x, b: _spmv(A, x) * (1 / 6.0)),
                          x, b)
    spmv_bytes = (k + 2) * nr * 4
    out["dia"] = {
        "gbps": round(spmv_bytes * reps / spmv_dt / 1e9, 2),
        "vs_ceiling": round((spmv_bytes * reps / spmv_dt / 1e9) / ceil,
                            3),
    }

    slabs = fused_ops.build_fused_slabs(A, dinv) \
        if fused_ops.fused_runtime_on() else None

    def unfused_pair(x, b):
        xx = x
        for t in range(2):
            xx = xx + (taus[t] * (b - _spmv(A, xx))) * dinv
        return xx, b - _spmv(A, xx)

    if slabs is not None:
        def fused_pair(x, b):
            return fused_ops.dia_fused_smooth(A, slabs, b, x, taus,
                                              dinv=dinv,
                                              with_residual=True)
    else:
        fused_pair = None

    # both loops carry (x, r) through the fori state so XLA cannot
    # dead-code-eliminate the residual half of the pair being measured
    @jax.jit
    def unf_loop(x, b):
        def body(_, st):
            return unfused_pair(st[0], b)
        return jax.lax.fori_loop(0, reps, body, (x, b))

    t_unf, _ = _time(lambda x, b: unf_loop(x, b), x, b)
    # fused ideal bytes: values once + x/b/dinv in + x'/r out
    fused_bytes = (k + 5) * nr * 4
    row = {"unfused_s": round(t_unf / reps, 6)}
    if fused_pair is not None:
        @jax.jit
        def fus_loop(x, b):
            def body(_, st):
                return fused_pair(st[0], b)
            return jax.lax.fori_loop(0, reps, body, (x, b))

        t_fus, ceil2 = _time(lambda x, b: fus_loop(x, b), x, b)
        row.update({
            "fused_s": round(t_fus / reps, 6),
            "fused_speedup": round(t_unf / t_fus, 3),
            "fused_gbps": round(fused_bytes * reps / t_fus / 1e9, 2),
            "fused_vs_ceiling": round(
                (fused_bytes * reps / t_fus / 1e9) / ceil2, 3),
        })
    else:
        row["fused"] = "unavailable (non-TPU rig)"
    out["dia_smooth2_residual"] = row

    # ---- ELL ----------------------------------------------------------
    try:
        A_ell = dataclasses.replace(
            A, dia_offsets=None, dia_vals=None, row_ids=None,
            diag_idx=None, initialized=False).init(ell="always")
        assert A_ell.ell_cols is not None
        t_ell, ceil3 = _time(
            _loop(lambda x, b: _spmv(A_ell, x) * (1 / 6.0)), x, b)
        ell_bytes = (A_ell.ell_cols.size * (4 + 4) + 2 * nr * 4)
        out["ell"] = {
            "gbps": round(ell_bytes * reps / t_ell / 1e9, 2),
            "vs_ceiling": round(
                (ell_bytes * reps / t_ell / 1e9) / ceil3, 3),
        }
    except Exception as e:  # pragma: no cover - bench robustness
        out["ell_error"] = str(e)[:120]

    # ---- SWELL (unstructured path; 2D so the window fits) -------------
    try:
        from amgx_tpu.ops.pallas_swell import build_swell_host
        A2 = amgx.gallery.poisson("9pt", swell_n, swell_n,
                                  dtype=np.float32).init()
        sw = build_swell_host(np.asarray(A2.row_offsets),
                              np.asarray(A2.col_indices),
                              np.asarray(A2.values, np.float32),
                              A2.num_rows, A2.num_cols)
        assert sw is not None
        c4, v4, c0r, nch, w128 = sw
        A_sw = dataclasses.replace(
            A2, dia_offsets=None, dia_vals=None, ell_cols=None,
            ell_vals=None, swell_cols=jnp.asarray(c4),
            swell_vals=jnp.asarray(v4), swell_c0row=jnp.asarray(c0r),
            swell_nchunk=jnp.asarray(nch), swell_w128=int(w128))
        n2 = A_sw.num_rows
        x2 = jnp.ones(n2, jnp.float32)
        b2 = jnp.ones(n2, jnp.float32)
        d2 = jnp.full((n2,), 1.0 / 8.0, jnp.float32)
        t_sw, ceil4 = _time(
            _loop(lambda x, b: _spmv(A_sw, x) * 0.1), x2, b2)
        sw_bytes = v4.size * (4 + 4) + 2 * n2 * 4
        out["swell"] = {
            "gbps": round(sw_bytes * reps / t_sw / 1e9, 2),
            "vs_ceiling": round(
                (sw_bytes * reps / t_sw / 1e9) / ceil4, 3),
        }
        tau1 = jnp.asarray(np.full(1, 0.8), jnp.float32)

        @jax.jit
        def sw_unf(x, b):
            def body(_, x):
                return x + (tau1[0] * (b - _spmv(A_sw, x))) * d2
            return jax.lax.fori_loop(0, reps, body, x)

        t_swu, _ = _time(lambda x, b: sw_unf(x, b), x2, b2)
        row = {"unfused_sweep_s": round(t_swu / reps, 6)}
        if fused_ops.fused_runtime_on():
            @jax.jit
            def sw_fus(x, b):
                def body(_, x):
                    return fused_ops.swell_fused_smooth(
                        A_sw, b, x, tau1, dinv=d2, with_residual=False)
                return jax.lax.fori_loop(0, reps, body, x)

            t_swf, _ = _time(lambda x, b: sw_fus(x, b), x2, b2)
            row.update({
                "fused_sweep_s": round(t_swf / reps, 6),
                "fused_speedup": round(t_swu / t_swf, 3),
            })
        out["swell_smooth_step"] = row
    except Exception as e:  # pragma: no cover - bench robustness
        out["swell_error"] = str(e)[:120]

    # ---- fused-vs-unfused CYCLE (grid transfers + coarse tail) --------
    # One GEO/DIA V-cycle at 64^3 f32: the cycle_fusion knob only
    # changes the trace, so both timings run against one hierarchy
    try:
        cfg = Config.from_string(
            "solver(s)=PCG, s:max_iters=1, s:monitor_residual=1,"
            " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
            " amg:selector=GEO, amg:smoother=CHEBYSHEV_POLY,"
            " amg:chebyshev_polynomial_order=2, amg:presweeps=1,"
            " amg:postsweeps=1, amg:max_iters=1,"
            " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=32")
        Ac = amgx.gallery.poisson("7pt", 64, 64, 64,
                                  dtype=np.float32).init()
        slv = amgx.create_solver(cfg)
        slv.setup(Ac)
        sp = cycle_fused_speedup(slv, jnp.ones(Ac.num_rows, jnp.float32),
                                 reps=9)
        if sp is not None:
            out["geo_cycle_64^3"] = sp
    except Exception as e:  # pragma: no cover - bench robustness
        out["cycle_error"] = str(e)[:120]

    return out


def _amg_of(slv):
    """Walk the preconditioner chain to the AMG hierarchy owner."""
    s = slv
    for _ in range(4):
        if hasattr(s, "amg"):
            return s.amg
        s = getattr(s, "preconditioner", None)
        if s is None:
            break
    return None


def _cycle_kernel_counts(amg, data, b):
    """Per-cycle kernel counts from the traced cycle's jaxpr — the
    HBM-pass regression number the artifact tracks round over round
    (each dia_* site is one single-pass kernel; dia_spmv sites are the
    unfused passes cycle fusion is meant to remove)."""
    import re
    jaxpr = str(jax.make_jaxpr(
        lambda bb, xx: amg.cycle(data, bb, xx))(b, jnp.zeros_like(b)))
    names = re.findall(r"name=\"?([A-Za-z_0-9]+)\"?", jaxpr)
    counts = {}
    for nm in names:
        if "dia" in nm or "swell" in nm:
            counts[nm] = counts.get(nm, 0) + 1
    return counts


def _time_median(fn, args, reps):
    jax.block_until_ready(fn(*args))         # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def cycle_attribution(slv, b, reps: int = 10):
    """Solve-phase attribution (the solve-side mirror of the setup
    breakdown): per-level rows / stored diagonals / fusion kind /
    measured per-level transfer+smooth pair time, the fused-tail
    boundary, and the traced cycle's per-cycle kernel counts."""
    from amgx_tpu.amg import cycles as _cyc
    from amgx_tpu.ops import smooth as _sm
    amg = _amg_of(slv)
    if amg is None:
        return {"error": "no AMG preconditioner"}
    data = amg.solve_data()
    dt = amg._PRECISIONS[amg.precision]
    bb = b.astype(dt) if dt is not None else b
    out = {"kernels_per_cycle": _cycle_kernel_counts(amg, data, bb)}
    shape = amg.cycle_name if amg.cycle_name in ("V", "W", "F") else "V"
    tail_start = None
    if amg.cycle_fusion:
        for k in range(len(amg.levels)):
            bk = jnp.ones(amg.levels[k].A.num_rows, bb.dtype)
            if _sm.coarse_tail_cycle(amg, shape, data, k, bk,
                                     jnp.zeros_like(bk)) is not None:
                tail_start = k
                break
    out["tail_start_level"] = tail_start
    levels = []
    for lvl, level in enumerate(amg.levels):
        A = level.A
        row = {"level": lvl, "rows": int(A.num_rows),
               "diags": (len(A.dia_offsets) if A.dia_offsets is not None
                         else None)}
        nxt = (amg.levels[lvl + 1].A if lvl + 1 < len(amg.levels)
               else amg.coarsest_A)
        if tail_start is not None and lvl >= tail_start:
            row["kind"] = "vmem_tail"
            if lvl == tail_start:
                bk = jnp.ones(A.num_rows, bb.dtype)
                fn = jax.jit(lambda bb_, xx_: _sm.coarse_tail_cycle(
                    amg, shape, data, tail_start, bb_, xx_))
                row["tail_s"] = round(_time_median(
                    fn, (bk, jnp.zeros_like(bk)), reps), 6)
            levels.append(row)
            continue
        ld = data["levels"][lvl]
        has_xfer = "xfer" in ld
        row["kind"] = ("fused_transfers" if amg.cycle_fusion and has_xfer
                       else "unfused_transfers")
        bk = jnp.ones(A.num_rows, bb.dtype)
        xck = jnp.ones(nxt.num_rows, bb.dtype)
        swp, swq = amg._sweeps(lvl, pre=True), amg._sweeps(lvl, pre=False)

        def pair(bb_, xx_, xc_, level=level, ld=ld, swp=swp, swq=swq):
            x2, bc = _cyc._smooth_restrict(amg, level, ld, bb_, xx_, swp)
            return _cyc._prolongate_smooth(amg, level, ld, bb_, x2, xc_,
                                           swq), bc
        row["pair_s"] = round(_time_median(
            jax.jit(pair), (bk, jnp.zeros_like(bk), xck), reps), 6)
        levels.append(row)
    out["levels"] = levels
    return out


def cycle_fused_speedup(slv, b, reps: int = 10):
    """Fused-vs-unfused cycle wall clock on the SAME hierarchy: the
    cycle_fusion knob only changes the trace, so flipping it re-traces
    the cycle against identical solve data — no second setup."""
    amg = _amg_of(slv)
    if amg is None:
        return None
    data = amg.solve_data()
    dt = amg._PRECISIONS[amg.precision]
    bb = b.astype(dt) if dt is not None else b
    x0 = jnp.zeros_like(bb)

    def timed():
        f = jax.jit(lambda bb_, xx_: amg.cycle(data, bb_, xx_))
        return _time_median(f, (bb, x0), reps)

    t_fused = timed()
    old = amg.cycle_fusion
    amg.cycle_fusion = False
    try:
        t_unf = timed()
    finally:
        amg.cycle_fusion = old
    return {"fused_s": round(t_fused, 6), "unfused_s": round(t_unf, 6),
            "speedup": round(t_unf / max(t_fused, 1e-12), 3)}


def bench_flagship(n: int = 128, tolerance: str = "1e-8", reps: int = 3,
                   light: bool = False):
    """REFINEMENT(FGMRES + GEO-aggregation AMG, f32 inner) on 7-pt
    Poisson n^3, f64 system, true relative residual <= tolerance. Setup
    AND solve run entirely on the TPU (jitted static-shape setup)."""
    from amgx_tpu import profiling
    A = amgx.gallery.poisson("7pt", n, n, n).init()
    b = jnp.ones(A.num_rows)
    flagship = FLAGSHIP.replace("tolerance=1e-8", f"tolerance={tolerance}")
    assert tolerance == "1e-8" or flagship != FLAGSHIP, \
        "FLAGSHIP tolerance literal drifted; fix the replace target"
    def _settle(s):
        # setup dispatches asynchronously (the blocking per-level syncs
        # were deliberately removed); bound the timer by the device
        # completing all setup products, or the number under-reports
        jax.block_until_ready(s.solve_data())

    slv = amgx.create_solver(Config.from_string(flagship))
    t0 = time.perf_counter()
    slv.setup(A)
    _settle(slv)
    setup_cold_s = time.perf_counter() - t0
    # warm setup: what resetup/compile-cached production runs see.
    # setup_breakdown records the per-level per-stage wall clock
    # (selector / galerkin / layout / smoother_setup / ship) so setup
    # regressions are attributable; the amg.* regions are disjoint leaf
    # spans, so their sum over the warm wall is the accounted fraction
    # (contract: >= 0.9 — the device-sync tail is timed too).
    slv2 = amgx.create_solver(Config.from_string(
        (flagship + ", amg:structure_reuse_levels=-1") if light
        else flagship))
    profiling.reset_timers()
    t0 = time.perf_counter()
    slv2.setup(A)
    with profiling.trace_region("amg.device_sync"):
        _settle(slv2)
    setup_s = time.perf_counter() - t0
    breakdown = {k: round(v[1], 4) for k, v in profiling.timers().items()
                 if k.startswith(("amg.", "ship."))}
    accounted = min(1.0, profiling.timers_total("amg.") /
                    max(setup_s, 1e-9))
    # resetup with the structure-reuse path ON (what production
    # coefficient-replace cycles use; hierarchy structure kept, only
    # values recomputed). light mode (256^3): the warm solver serves
    # the resetup too — one fewer full setup inside the alarm window.
    if light:
        slv3 = slv2
    else:
        slv3 = amgx.create_solver(Config.from_string(
            flagship + ", amg:structure_reuse_levels=-1"))
        slv3.setup(A)
        _settle(slv3)
    t0 = time.perf_counter()
    slv3.resetup(A)
    _settle(slv3)
    resetup_first_s = time.perf_counter() - t0   # traces the fused plan
    resetup_s = float("inf")                     # steady-state cycles
    for _ in range(2):
        t0 = time.perf_counter()
        slv3.resetup(A)
        _settle(slv3)
        resetup_s = min(resetup_s, time.perf_counter() - t0)
    res = slv2.solve(b)                       # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res = slv2.solve(b)
        times.append(time.perf_counter() - t0)
    solve_s = sorted(times)[len(times) // 2]
    # solve-phase attribution (the solve-side mirror of the setup
    # breakdown): per-level cycle pair timings + per-cycle kernel
    # counts + fused-vs-unfused cycle wall clock on the same hierarchy
    try:
        cyc_attr = cycle_attribution(slv2, b, reps=max(reps, 5))
        cyc_speed = cycle_fused_speedup(slv2, b, reps=max(reps, 5))
    except Exception as e:  # pragma: no cover - bench robustness
        cyc_attr = {"error": str(e)[:200]}
        cyc_speed = None
    rel = float(
        np.linalg.norm(np.asarray(amgx.ops.residual(A, res.x, b)))
        / np.linalg.norm(np.asarray(b)))
    rap_s, rap_share = _rap_attr(breakdown, setup_s)
    return {
        "setup_cold_s": setup_cold_s,
        "setup_warm_s": setup_s,
        "setup_rows_per_s": A.num_rows / max(setup_s, 1e-9),
        "setup_accounted_fraction": accounted,
        "rap_s": rap_s,
        "rap_share": rap_share,
        "resetup_s": resetup_s,
        "resetup_first_s": resetup_first_s,
        "breakdown": breakdown,
        "solve_s": solve_s,
        "iters": int(res.iterations),
        "converged": bool(res.converged),
        "rel": rel,
        "cycle_breakdown": cyc_attr,
        "cycle_speedup": cyc_speed,
    }


def bench_precision(n: int = 128, reps: int = 3):
    """Mixed-precision phase (`python bench.py precision`): the
    flagship replayed PAIRED at solve_precision=float vs bfloat16 on
    the same system — same REFINEMENT(f64) outer shell, same FGMRES
    inner, only the AMG cycle's operand-slab precision differs (bf16
    slabs stream half the HBM bytes through the fused kernels with
    f32 in-kernel accumulation). Records the per-precision walls, the
    `mixed_precision_speedup` ratio, per-precision iteration counts
    (SolveReport.precision: f64 outer + f32-Krylov inner), and the
    matched-final-residual gate — the bf16 run must still reach the
    f64 relative tolerance, or the speedup is not comparable."""
    # the gate below must track the preset's tolerance (same drift
    # guard as bench_flagship's replace-target assert)
    assert "tolerance=1e-8" in FLAGSHIP, \
        "FLAGSHIP tolerance literal drifted; update bench_precision's " \
        "matched-residual gate"
    tol = 1e-8
    A = amgx.gallery.poisson("7pt", n, n, n).init()
    b = jnp.ones(A.num_rows)
    out = {}
    walls = {}
    for prec in ("float", "bfloat16"):
        slv = amgx.create_solver(Config.from_string(
            FLAGSHIP + f", solve_precision={prec}"))
        slv.setup(A)
        res = slv.solve(b)                     # compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            res = slv.solve(b)
            times.append(time.perf_counter() - t0)
        wall = sorted(times)[len(times) // 2]
        walls[prec] = wall
        rel = float(np.max(np.asarray(res.res_norm))
                    / max(np.max(np.asarray(res.norm0)), 1e-300))
        pb = (res.report.precision if res.report is not None
              else None) or {}
        tag = "bf16" if prec == "bfloat16" else prec
        out[f"solve_{tag}_s"] = round(wall, 4)
        out[f"outer_iters_{tag}"] = int(res.iterations)
        out[f"inner_iters_{tag}"] = pb.get("inner_iterations")
        out[f"true_rel_residual_{tag}"] = rel
        out[f"converged_{tag}"] = bool(res.converged)
        out[f"precision_report_{tag}"] = pb
        del slv
    out["mixed_precision_speedup"] = round(
        walls["float"] / max(walls["bfloat16"], 1e-9), 3)
    # matched-residual gate: both precisions reach the flagship's
    # relative tolerance, so the speedup compares equal-quality answers
    out["matched_residuals_ok"] = bool(
        out["converged_float"] and out["converged_bf16"]
        and out["true_rel_residual_bf16"] <= tol)
    return out


def bench_setup(grids=(64, 128)):
    """Setup-only CI phase (`python bench.py setup`): warm hierarchy
    build of the flagship configuration per grid, reporting throughput
    (rows/s) and the attribution contract — the disjoint amg.* region
    sum must account for >= 90% of the warm setup wall so setup
    regressions land in a named bucket, not in invisible residue.
    Emitted into BENCH_*.json so the trajectory catches setup
    regressions, not just solve regressions."""
    from amgx_tpu import profiling
    out = {}
    for n in grids:
        A = amgx.gallery.poisson("7pt", n, n, n).init()
        cold = amgx.create_solver(Config.from_string(FLAGSHIP))
        cold.setup(A)                      # compile + trace warm-up
        jax.block_until_ready(cold.solve_data())
        slv = amgx.create_solver(Config.from_string(FLAGSHIP))
        profiling.reset_timers()
        t0 = time.perf_counter()
        slv.setup(A)
        with profiling.trace_region("amg.device_sync"):
            jax.block_until_ready(slv.solve_data())
        dt = time.perf_counter() - t0
        accounted = min(1.0, profiling.timers_total("amg.")
                        / max(dt, 1e-9))
        breakdown = {k: round(v[1], 4)
                     for k, v in profiling.timers().items()
                     if k.startswith(("amg.", "ship."))}
        rap_s, rap_share = _rap_attr(breakdown, dt)
        out[f"{n}^3"] = {
            "setup_warm_s": round(dt, 3),
            "setup_rows_per_s": round(A.num_rows / max(dt, 1e-9)),
            "setup_accounted_fraction": round(accounted, 3),
            "setup_attribution_ok": bool(accounted >= 0.9),
            "rap_s": rap_s,
            "rap_share": rap_share,
            "breakdown": breakdown,
        }
    return out


import re as _re  # noqa: E402

_RAP_SPAN_RE = _re.compile(r"amg\.L\d+\.(?:rap|rap_plan|rap_values"
                           r"|galerkin)$")


def _rap_attr(breakdown: dict, wall: float):
    """(rap_s, rap_share) of a warm-setup breakdown: the summed
    per-level Galerkin RAP spans — the eager routes (amg.L*.rap /
    amg.L*.galerkin) plus the plan split's structure/value spans
    (amg.L*.rap_plan / amg.L*.rap_values) — over the setup wall. This
    is the attribution field ROADMAP 2(b) asks for: when classical
    setup is still the wall, this number says whether RAP is the
    dominant span or the residue lives elsewhere."""
    rap = sum(v for k, v in breakdown.items() if _RAP_SPAN_RE.match(k))
    return round(rap, 4), round(rap / max(wall, 1e-9), 3)


def bench_spgemm_plan(flagship_n: int = 128, classical_n: int = 64,
                      reps: int = 2):
    """Plan-split RAP phase (`python bench.py spgemm [--smoke]`):
    paired plan-vs-eager WARM-setup replay on the flagship GEO shape
    and the benched classical shape. Both twins run the identical
    config except `spgemm_plan` (1 = structure phase memoized +
    fused/sort-free value phase; 0 = today's eager expand/sort/segment
    composition); each mode pays one cold setup first (compiles +
    plan-cache prime), then the best-of-`reps` warm wall is the
    headline — exactly what a production coefficient-replace cycle
    sees. `spgemm_plan_speedup` (flagship) and
    `spgemm_plan_speedup_classical` are sentinel-tracked."""
    from amgx_tpu.telemetry import metrics as _tm

    def _warm_setup(cfg, A):
        cold = amgx.create_solver(cfg)
        cold.setup(A)
        jax.block_until_ready(cold.solve_data())
        del cold
        best = float("inf")
        for _ in range(reps):
            slv = amgx.create_solver(cfg)
            t0 = time.perf_counter()
            slv.setup(A)
            jax.block_until_ready(slv.solve_data())
            best = min(best, time.perf_counter() - t0)
            del slv
        return best

    out = {}
    cases = (
        (f"flagship_{flagship_n}^3",
         lambda m: Config.from_string(
             FLAGSHIP + f", amg:spgemm_plan={m}"),
         flagship_n),
        (f"classical_{classical_n}^3",
         lambda m: _classical_cfg(extra=f", amg:spgemm_plan={m}"),
         classical_n),
    )
    for label, mk, n in cases:
        A = amgx.gallery.poisson("7pt", n, n, n).init()
        cfg1 = mk("1")
        cold = amgx.create_solver(cfg1)
        cold.setup(A)                  # builds + primes the plan cache
        jax.block_until_ready(cold.solve_data())
        del cold
        # hits counted over the WARM window only (the cold setup
        # builds; it can also hit patterns planned by earlier phases)
        hits0 = int(_tm.get("amg.spgemm.plan_hit"))
        best = float("inf")
        for _ in range(reps):
            slv = amgx.create_solver(cfg1)
            t0 = time.perf_counter()
            slv.setup(A)
            jax.block_until_ready(slv.solve_data())
            best = min(best, time.perf_counter() - t0)
            del slv
        plan_s = best
        hits = int(_tm.get("amg.spgemm.plan_hit")) - hits0
        eager_s = _warm_setup(mk("0"), A)
        out[label] = {
            "plan_warm_setup_s": round(plan_s, 3),
            "eager_warm_setup_s": round(eager_s, 3),
            "plan_hits_per_warm_setup": hits / max(reps, 1),
            "speedup": round(eager_s / max(plan_s, 1e-9), 3),
        }
        del A
    out["spgemm_plan_speedup"] = \
        out[f"flagship_{flagship_n}^3"]["speedup"]
    out["spgemm_plan_speedup_classical"] = \
        out[f"classical_{classical_n}^3"]["speedup"]
    return out


def bench_matfree(n: int = 128, reps: int = 3, smoke: bool = False):
    """Matrix-free GEO phase (`python bench.py matfree [--smoke]`):
    paired replay of the SAME solve with `matrix_free=1` (constant-
    coefficient levels route through ops/stencil.py — SMEM-coefficient
    Pallas kernels on TPU, the XLA masked-coefficient compose on this
    rig) against the `matrix_free=0` slab build. Two sentinel-tracked
    numbers: `matrix_free_cycle_speedup` (warm per-cycle wall, slab
    over matrix-free — higher is better) and
    `matrix_free_level_bytes_ratio` (summed per-level operator
    solve-data bytes, matrix-free over slab — lower is better; the
    fine slab alone is ~7/8 of a 7-pt level's operator stream). Both
    twins must converge in the SAME iteration count — the routing is a
    numerics-preserving form change, so any drift fails the phase."""
    from amgx_tpu.serving.cache import solve_data_bytes
    cfg_s = (
        "solver=FGMRES, max_iters=30, monitor_residual=1,"
        " tolerance=1e-8, gmres_n_restart=20,"
        " convergence=RELATIVE_INI, norm=L2,"
        " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
        " amg:selector=GEO, amg:smoother=JACOBI_L1,"
        " amg:relaxation_factor=0.75, amg:presweeps=1,"
        " amg:postsweeps=2, amg:max_iters=1, amg:cycle=V,"
        " amg:max_levels=10, amg:min_coarse_rows=32,"
        " amg:matrix_free=")
    A = amgx.gallery.poisson("7pt", n, n, n, dtype=np.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    out = {"grid": f"{n}^3 poisson7pt", "smoke": bool(smoke)}
    walls, iters, lv_bytes = {}, {}, {}
    for mf in ("0", "1"):
        slv = amgx.create_solver(Config.from_string(cfg_s + mf))
        slv.setup(A)
        res = slv.solve(b)                  # compile + warm caches
        iters[mf] = max(int(res.iterations), 1)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(slv.solve(b).x)
            best = min(best, time.perf_counter() - t0)
        walls[mf] = best
        eng = slv
        while not hasattr(eng, "amg"):
            eng = eng.preconditioner
        per = []
        for ld in eng.amg.solve_data()["levels"]:
            smd = ld.get("smoother") or {}
            per.append({
                "rows": int(ld["A"].num_rows),
                "form": "matrix-free" if "stencil" in ld else "slab",
                "operator_bytes": solve_data_bytes(
                    {"A": ld["A"], "stencil": ld.get("stencil"),
                     "dinv": smd.get("dinv")
                     if isinstance(smd, dict) else None}),
            })
        lv_bytes[mf] = per
        out[f"mf{mf}"] = {
            "solve_warm_s": round(best, 4),
            "iters": iters[mf],
            "cycle_warm_s": round(best / iters[mf], 5),
            "levels": per,
        }
        del slv
    assert iters["0"] == iters["1"], (
        f"matrix-free changed convergence: {iters}")
    tot0 = sum(p["operator_bytes"] for p in lv_bytes["0"])
    tot1 = sum(p["operator_bytes"] for p in lv_bytes["1"])
    out["matrix_free_cycle_speedup"] = round(
        walls["0"] / max(walls["1"], 1e-9), 3)
    # 6 decimals: a fully matrix-free hierarchy sits at ~2e-6, which
    # must stay a nonzero "best" for the regression sentinel's
    # relative-tolerance compare
    out["matrix_free_level_bytes_ratio"] = round(
        tot1 / max(tot0, 1), 6)
    out["slab_operator_bytes"] = int(tot0)
    out["matrix_free_operator_bytes"] = int(tot1)
    return out


def _krylov_pass_census(slv, b):
    """Per-iteration HBM-pass census of ONE traced solve_iteration
    (trace-only, kernels routed through the interpreter gate so the
    TPU dispatch decisions are visible on any rig): Pallas kernels by
    name, standalone full-vector reductions outside kernel bodies, and
    the count of full-n-vector operands/results touched by
    compute-bearing leaf eqns (arithmetic/reduction XLA ops plus
    kernel I/O; call wrappers and layout-only plumbing excluded — see
    the walk below) — the n-vector HBM-pass proxy the shell fusion
    cuts."""
    from amgx_tpu.telemetry import census as _census
    from amgx_tpu.ops import pallas_spmv as _ps
    with _ps.force_pallas_interpret():
        d = slv.solve_data()
        st = {"x": jnp.zeros_like(b), "r": b}
        st.update(slv.solve_init(d, b, jnp.zeros_like(b), b))
        jaxpr = jax.make_jaxpr(
            lambda dd, ss: slv.solve_iteration(dd, b, ss))(d, st)
    nvec = b.size
    kernels = {}
    for nm in _re.findall(r'name="?([A-Za-z_0-9]+)"?', str(jaxpr)):
        if nm.startswith(("_dia", "_cg")):
            kernels[nm] = kernels.get(nm, 0) + 1

    subs = _census.subjaxprs

    counts = {"reductions": 0, "passes": 0}
    # call-like wrappers re-bind their operands to an inner jaxpr whose
    # leaf eqns are counted anyway — counting the wrapper boundary too
    # would double-bill every vector that crosses a pjit/scan/custom
    # wrapper (and the fused helpers carry more wrapper layers than the
    # plain composition, so the double-billing is knob-asymmetric)
    wrappers = ("pjit", "closed_call", "custom_jvp_call",
                "custom_vjp_call", "custom_vmap_call", "scan", "while",
                "cond", "remat", "checkpoint")
    # pure layout plumbing is also excluded from the pass count: on
    # XLA:TPU reshape/transpose/broadcast are metadata and the lane-pad
    # dynamic_update_slice copies fuse into their producer, so none of
    # them is an HBM round trip — and the kernel route necessarily
    # carries more of this plumbing (every pallas operand is padded to
    # lane multiples), which would bill the fused knob for free ops
    layout = ("reshape", "transpose", "broadcast_in_dim", "slice",
              "dynamic_slice", "dynamic_update_slice", "pad",
              "squeeze", "concatenate", "convert_element_type",
              "copy")

    def walk(jx):
        for eq in jx.eqns:
            if eq.primitive.name not in wrappers \
                    and eq.primitive.name not in layout:
                counts["passes"] += sum(
                    1 for v in list(eq.invars) + list(eq.outvars)
                    if getattr(v, "aval", None) is not None
                    and v.aval.size >= nvec)
            if eq.primitive.name == "pallas_call":
                continue
            if eq.primitive.name in ("reduce_sum", "reduce_max",
                                     "reduce_min", "dot_general") \
                    and any(getattr(v, "aval", None) is not None
                            and v.aval.size >= nvec
                            for v in eq.invars):
                counts["reductions"] += 1
            for sub in subs(eq):
                walk(sub)

    walk(jaxpr.jaxpr)
    return {"kernels": kernels,
            "standalone_reductions": counts["reductions"],
            "n_vector_passes": counts["passes"]}


def bench_krylov(n: int = 128, reps: int = 3, smoke: bool = False,
                 northstar: bool = True):
    """Krylov-shell phase (`python bench.py krylov [--smoke]`): paired
    replay of the SAME PCG + GEO-aggregation AMG solve with
    `krylov_fusion=1` (the spmv+p.Ap and cg_update+r.r single-pass
    shell kernels plus the cycle-borne r.z epilogue) against `=0` (the
    unfused SpMV + BLAS-1 composition). Sentinel-tracked number:
    `krylov_fused_speedup` (warm solve wall, unfused over fused —
    higher is better). Both twins must converge in the SAME iteration
    count — the shell fusion is a numerics-preserving form change, so
    any drift fails the phase. The artifact also records the
    per-iteration HBM pass census of one traced iteration per knob
    (kernel inventory, standalone full-vector reductions, n-vector
    operand touches). Full mode adds the northstar 256^3 shape on TPU
    (the shape the ROADMAP's 512^3/1024^3 target sits behind); off-TPU
    the kernels decline to the identical-expression XLA fallback, so
    the rig records ~1.0x with the census still proving the TPU
    dispatch."""
    cfg_s = (
        "solver=PCG, max_iters=80, monitor_residual=1,"
        " tolerance=1e-8, convergence=RELATIVE_INI, norm=L2,"
        " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
        " amg:selector=GEO, amg:smoother=JACOBI_L1,"
        " amg:relaxation_factor=0.75, amg:presweeps=1,"
        " amg:postsweeps=2, amg:max_iters=1, amg:cycle=V,"
        " amg:max_levels=10, amg:min_coarse_rows=32,"
        " krylov_fusion=")
    shapes = [n]
    if northstar and not smoke and jax.default_backend() == "tpu":
        shapes.append(256)
    out = {"smoke": bool(smoke)}
    for nn in shapes:
        A = amgx.gallery.poisson("7pt", nn, nn, nn,
                                 dtype=np.float32).init()
        b = jnp.ones(A.num_rows, jnp.float32)
        row = {}
        iters = {}
        walls = {}
        for kf in ("0", "1"):
            slv = amgx.create_solver(Config.from_string(cfg_s + kf))
            slv.setup(A)
            # census BEFORE the first solve: the aggregation level
            # memoizes its fused transfer slabs on first level_data()
            # use, keyed to whether the fused runtime was on at that
            # moment. Tracing under the interpreter gate first memoizes
            # the TPU-shaped structure (coarse tail eligible) — the
            # same structure a real TPU solve would freeze. An off-TPU
            # solve first would memoize slabs=None and the census would
            # report the rig's fallback cycle instead of the dispatch.
            census = _krylov_pass_census(slv, b)
            res = slv.solve(b)              # compile + warm caches
            iters[kf] = max(int(res.iterations), 1)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(slv.solve(b).x)
                best = min(best, time.perf_counter() - t0)
            walls[kf] = best
            row[f"fusion{kf}"] = {
                "solve_warm_s": round(best, 4),
                "iters": iters[kf],
                "iter_warm_s": round(best / iters[kf], 6),
                "census": census,
            }
            del slv
        assert iters["0"] == iters["1"], (
            f"krylov_fusion changed convergence at {nn}^3: {iters}")
        row["speedup"] = round(walls["0"] / max(walls["1"], 1e-9), 3)
        out[f"{nn}^3"] = row
    head = out[f"{n}^3"]
    out["grid"] = f"{n}^3 poisson7pt"
    out["krylov_fused_speedup"] = head["speedup"]
    out["krylov_fused_passes"] = \
        head["fusion1"]["census"]["n_vector_passes"]
    out["krylov_unfused_passes"] = \
        head["fusion0"]["census"]["n_vector_passes"]
    out["krylov_fused_standalone_reductions"] = \
        head["fusion1"]["census"]["standalone_reductions"]
    return out


def bench_classical(n: int = 64):
    """PCG[f64] + classical PMIS/D2 AMG[f32] (JACOBI_L1) — the
    unstructured-path number the structured flagship does not cover.
    Setup runs through the native host path (amg_host_setup auto: C++
    PMIS / D2 / fused RAP / SWELL builders on numpy-backed levels,
    prefetched to the TPU as they finish); the solve runs the
    windowed-ELL Pallas gather kernel on every unstructured level
    operator and transfer operator (ops/pallas_swell.py).
    amg_precision=float is the reference's dDDI->dDFI mixed-mode
    economics (include/amgx_config.h:102-131): the f64 outer PCG holds
    the true residual. interp_max_elements=4 + max_row_sum=0.9 are the
    reference's own D2 production settings (its flagship classical
    preset, src/configs/FGMRES_CLASSICAL_AGGRESSIVE_PMIS.json).
    Setup is best-of-2: the host path is sensitive to single-core
    scheduler noise on shared rigs."""
    # the literal lives in _classical_cfg so the obs phase replays the
    # SAME config. At 128^3 on TPU the smoother request is
    # MULTICOLOR_DILU: the PR-11 known-fault guard reroutes it to
    # JACOBI_L1 (warned + counted) and the fallback takes the fused
    # classical path — resilience.config_fallback below records the
    # reroute in the bench line. Off-TPU the guard is inert (DILU
    # would actually run), so the CPU rig keeps the JACOBI_L1 literal
    # and its cross-round comparability.
    want_dilu = n >= 128 and jax.default_backend() == "tpu"
    cfg = _classical_cfg("MULTICOLOR_DILU" if want_dilu else
                         "JACOBI_L1")
    from amgx_tpu import profiling
    from amgx_tpu.telemetry import metrics as _tm
    fallback0 = int(_tm.get("resilience.config_fallback"))
    A = amgx.gallery.poisson("7pt", n, n, n).init()
    b = jnp.ones(A.num_rows)
    slv = amgx.create_solver(cfg)
    slv.setup(A)                      # cold (host CPU + compiles)
    jax.block_until_ready(slv.solve_data())
    setup_s = float("inf")
    breakdown = {}
    accounted = 0.0
    for _ in range(2):
        slv2 = amgx.create_solver(cfg)
        profiling.reset_timers()
        t0 = time.perf_counter()
        slv2.setup(A)
        with profiling.trace_region("amg.device_sync"):
            jax.block_until_ready(slv2.solve_data())
        dt = time.perf_counter() - t0
        if dt < setup_s:
            setup_s = dt
            # per-stage attribution of the BEST warm pass (strength /
            # cfsplit / interp / transposeR / rap / layout / ship);
            # amg.* spans are disjoint, so their sum over the wall is
            # the accounted fraction of the warm setup
            breakdown = {
                k: round(v[1], 3) for k, v in profiling.timers().items()
                if k.startswith(("amg.", "ship."))}
            accounted = min(1.0, profiling.timers_total("amg.")
                            / max(dt, 1e-9))
    res = slv2.solve(b)               # compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        res = slv2.solve(b)
        times.append(time.perf_counter() - t0)
    solve_s = sorted(times)[len(times) // 2]
    rel = float(
        np.linalg.norm(np.asarray(amgx.ops.residual(A, res.x, b)))
        / np.linalg.norm(np.asarray(b)))
    amg = slv2.preconditioner.amg
    effective = amg.levels[0].smoother.name if amg.levels else "?"
    rap_s, rap_share = _rap_attr(breakdown, setup_s)
    return {
        "setup_warm_s": setup_s,
        "setup_rows_per_s": A.num_rows / max(setup_s, 1e-9),
        "setup_accounted_fraction": accounted,
        "rap_s": rap_s,
        "rap_share": rap_share,
        "breakdown": breakdown,
        "solve_s": solve_s,
        "iters": int(res.iterations),
        "rel": rel,
        # fallback visibility (PR-11 DILU guard): how many hierarchy
        # builds rerouted their smoother, what was asked, what ran
        "config_fallback": int(_tm.get("resilience.config_fallback"))
        - fallback0,
        "smoother_requested": "MULTICOLOR_DILU" if want_dilu
        else "JACOBI_L1",
        "smoother_effective": effective,
    }


def bench_batched(n: int = 32, batch_sizes=(1, 8, 32), reps: int = 3):
    """Batched-serving phase (amgx_tpu/batch/): per-system throughput of
    the vmapped multi-RHS solve at several batch sizes on the n^3 7-pt
    Poisson gallery. The figure of merit is solves/s per batch size —
    the curve shows how much of a single solve's cost the batch
    amortizes (one trace, one dispatch, shared matrix data). Returns
    {batch: {"solves_per_s": ..., "solve_s": ..., "iters": ...}}."""
    from amgx_tpu.batch import BatchedSolver
    from amgx_tpu.presets import BATCHED_CG
    A = amgx.gallery.poisson("7pt", n, n, n).init()
    rng = np.random.default_rng(7)
    out = {}
    bs = BatchedSolver(Config.from_string(BATCHED_CG))
    bs.setup(A)
    for nb in batch_sizes:
        B = jnp.asarray(rng.standard_normal((nb, A.num_rows)))
        res = bs.solve_many(B)                    # compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            res = bs.solve_many(B)
            times.append(time.perf_counter() - t0)
        dt = sorted(times)[len(times) // 2]
        out[str(nb)] = {
            "solves_per_s": round(nb / dt, 2),
            "solve_s": round(dt, 4),
            "iters_max": int(np.max(res.iterations)),
            "all_converged": bool(res.all_converged),
        }
    return out


def bench_serving(n: int = 32, smoke: bool = False,
                  aot_dir: str = None):
    """Serving phase (amgx_tpu/serving/): a synthetic OPEN-LOOP load —
    arrivals follow a fixed schedule, independent of completions —
    against the continuous-batching solve service. Traffic shape: a
    hot tenant streaming same-pattern systems with per-request value
    perturbations (the hierarchy-cache + value-resetup steady state), a
    cold tenant submitting a second pattern, and a slice of
    impossible-deadline requests that must complete with
    DEADLINE_EXCEEDED rather than stall their bucket.

    Two service processes are simulated: a WARMUP service traces the
    buckets and exports them to the AOT store, then a fresh MEASURED
    service starts from that store — so `retraces_after_warmup` counts
    the python traces a restarted production service would pay (the
    acceptance gate is ZERO). Figures of merit: sustained solves/sec
    over the measured window, p50/p99 submit-to-complete latency, the
    cache-hit rate and the setup-routing proof (value-resetups vs full
    setups during the window)."""
    import tempfile
    from amgx_tpu.presets import SERVING_CG
    from amgx_tpu.serving import SolveService
    from amgx_tpu.telemetry import metrics as _tm
    from amgx_tpu.resilience.status import SolveStatus

    if smoke:
        n, n_requests, arrival_dt = 10, 14, 0.0
    else:
        n_requests, arrival_dt = 60, 0.002
    if aot_dir is None:
        aot_dir = tempfile.mkdtemp(prefix="amgx_serving_aot_")
    cfg = Config.from_string(
        SERVING_CG + f", serving_bucket_slots=4, serving_chunk_iters=4,"
        f" serving_aot_dir={aot_dir}")

    hot = amgx.gallery.poisson("7pt", n, n, n).init()
    cold = amgx.gallery.poisson("7pt", n + 2, n + 2, n + 2).init()
    rng = np.random.default_rng(11)

    def shifted(A, c):
        vals = np.asarray(A.values).copy()
        vals[np.asarray(A.diag_idx)] += c
        return A.with_values(vals)

    # request schedule: (matrix, rhs, tenant, deadline). ~1/5 of the
    # traffic is the cold pattern, every 7th hot request carries an
    # already-expired deadline
    sched = []
    for i in range(n_requests):
        if i % 5 == 4:
            sched.append((cold, rng.standard_normal(cold.num_rows),
                          "cold", None))
        else:
            A_i = shifted(hot, 0.1 * (i % 3))
            dl = 0.0 if i % 7 == 3 else None
            sched.append((A_i, rng.standard_normal(hot.num_rows),
                          "hot", dl))

    # warmup service: builds both buckets, traces, exports to the store
    warm = SolveService(cfg)
    for A_i, b_i, tn, _dl in (sched[0], sched[4]):  # one per pattern
        warm.submit(A_i, b_i, tenant=tn)
    warm.drain(timeout_s=600)

    # measured service: a "restarted process" starting from the store
    base = _tm.snapshot()
    svc = SolveService(cfg)
    tickets = []
    t_start = time.perf_counter()
    next_i = 0
    while next_i < len(sched) or not svc.idle:
        now = time.perf_counter() - t_start
        while next_i < len(sched) and now >= next_i * arrival_dt:
            A_i, b_i, tn, dl = sched[next_i]
            tickets.append(svc.submit(A_i, b_i, tenant=tn,
                                      deadline_s=dl))
            next_i += 1
        svc.step()
        if time.perf_counter() - t_start > 600:   # pragma: no cover
            break
    window_s = time.perf_counter() - t_start

    cur = _tm.snapshot()

    def delta(name):
        return int(cur.get(name, 0) - base.get(name, 0))

    lat_ms = sorted(1e3 * t.latency_s for t in tickets if t.done
                    and t.deadline_t is None)
    n_solved = len(lat_ms)
    dl_tickets = [t for t in tickets if t.deadline_t is not None]
    dl_ok = all(
        t.done and t.result.status_code
        == int(SolveStatus.DEADLINE_EXCEEDED) for t in dl_tickets)
    hits, misses = delta("serving.cache.hit"), delta("serving.cache.miss")
    out = {
        "grid": f"{n}^3 poisson7pt (+ {n + 2}^3 cold pattern)",
        "requests": len(tickets),
        "window_s": round(window_s, 3),
        "solves_per_s": round(n_solved / max(window_s, 1e-9), 2),
        "p50_ms": round(lat_ms[len(lat_ms) // 2], 2) if lat_ms else -1,
        "p99_ms": round(lat_ms[min(len(lat_ms) - 1,
                                   int(0.99 * len(lat_ms)))], 2)
        if lat_ms else -1,
        "cache_hit_rate": round(hits / max(hits + misses, 1), 3),
        "value_resetups_routed": delta("amg.resetup.value"),
        "full_setups": delta("amg.setup.full"),
        "retraces_after_warmup": delta("serving.retrace"),
        "aot_loads": delta("serving.aot.load"),
        "deadline_requests": len(dl_tickets),
        "deadline_miss": delta("serving.deadline_miss"),
        "deadline_statuses_ok": bool(dl_ok),
        "all_completed": bool(all(t.done for t in tickets)),
        "smoke": bool(smoke),
    }
    return out


def bench_autotune(n: int = 16, smoke: bool = False):
    """Autotune phase (amgx_tpu/serving/autotune.py): the online
    per-fingerprint config tuner, measured on both sides of its
    contract.

    A: the WIN — a deliberately mistuned hot fingerprint (an
    overdamped BLOCK_JACOBI, the convergence-doctor classic) is served
    until hot, the tuner shadow-solves the diagnostics-derived
    candidates on idle cycles and promotes the winner; the SAME
    request set is then re-served under the promoted overlay. Figures
    of merit: median iterations and in-bucket wall before vs after
    (`autotune_speedup` = the smaller of the two ratios — the
    conservative claim; the gate is >= 2x on BOTH).

    B: the COST — the identical saturated burst runs against
    autotune=0 and autotune=1 services stepped in LOCKSTEP (one
    shared loop, so box noise lands on both arms' in-flight tickets
    identically; tuner eager: hot thresholds at the floor). Shadow
    solves only ever use idle capacity, so under saturation the tuner
    must be structurally inert: `autotune_shadow_p99_impact_pct` is
    the paired p99 delta (gate: within noise),
    `search_deadline_misses` the deadline misses the search added
    (gate: zero)."""
    import tempfile
    from amgx_tpu.presets import BATCHED_CG
    from amgx_tpu.serving import SolveService
    from amgx_tpu.telemetry import metrics as _tm

    if smoke:
        n, k_serve, k_pair = 8, 6, 10
    else:
        k_serve, k_pair = 12, 16
    root = tempfile.mkdtemp(prefix="amgx_autotune_")
    mistuned = (
        BATCHED_CG + ", amg:smoother(sm2)=BLOCK_JACOBI,"
        " sm2:max_iters=1, sm2:relaxation_factor=0.02,"
        " serving_bucket_slots=2, serving_chunk_iters=2")
    tuned_cfg = Config.from_string(
        mistuned + ", autotune=1, autotune_hot_requests=4,"
        " autotune_hot_exec_share=0.0,"
        f" serving_hierarchy_dir={root}/hier,"
        f" serving_journal_dir={root}/journal")

    A = amgx.gallery.poisson("7pt", n, n, n).init()
    rng = np.random.default_rng(7)
    rhs = [rng.standard_normal(A.num_rows) for _ in range(k_serve)]

    def exec_wall(t):
        return t.complete_t - t.admit_t

    def serve(svc, excl_first=1):
        tix = [svc.submit(A, b) for b in rhs]
        svc.drain(timeout_s=600)
        meas = tix[excl_first:]     # first request pays build+trace
        iters = sorted(t.result.iterations for t in meas)
        # iterations: median (exact, noise-free). wall: min — the
        # deterministic-cost estimator (OS scheduler jitter only ever
        # inflates a request's wall, identically on both sides)
        walls = sorted(exec_wall(t) for t in meas)
        return (tix, iters[len(iters) // 2], walls[0])

    # -- A: the win -------------------------------------------------------
    base = _tm.snapshot()
    svc = SolveService(tuned_cfg)
    tix, pre_iters, pre_wall = serve(svc)
    assert all(t.result.converged for t in tix)
    # idle cycles: baseline probe + candidate shadows + the verdict
    for _ in range(20):
        svc.step()
        if svc.stats()["autotune"]["promoted"]:
            break
    snap = svc.stats()["autotune"]
    tix2, post_iters, post_wall = serve(svc)
    cur = _tm.snapshot()

    def delta(name):
        return int(cur.get(name, 0) - base.get(name, 0))

    sp_iters = pre_iters / max(post_iters, 1)
    sp_wall = pre_wall / max(post_wall, 1e-9)
    rec = (next(iter(snap["fingerprints"].values()))
           if snap["fingerprints"] else {})

    # -- B: the cost (lockstep paired saturated open loop) ----------------
    # Both arms step in ONE shared loop: every scheduler stall,
    # neighbor steal, and allocator hiccup lands on BOTH arms'
    # in-flight tickets, so the paired p99 delta isolates what the
    # tuner itself adds (back-to-back arm runs drown a percent-level
    # delta in several percent of box noise). A service is stepped
    # only while it has traffic, so the on-arm's post-burst idle-time
    # shadows never spend the shared loop's clock inside the measured
    # window — and mid-burst shadows are exactly what the capacity
    # gate forbids (counted below, must be zero).
    off_cfg = mistuned + ", autotune=0"
    # warm-up stays below the hot threshold (4), so the on-arm tuner
    # goes hot on its FIRST burst finish: hot-path bookkeeping and
    # shadow gating are live for the whole measured burst
    on_cfg = (mistuned + ", autotune=1, autotune_hot_requests=4,"
              " autotune_hot_exec_share=0.0")
    svcs = [SolveService(Config.from_string(c))
            for c in (off_cfg, on_cfg)]
    prng = np.random.default_rng(13)
    warm = [prng.standard_normal(A.num_rows) for _ in range(3)]
    for svc in svcs:
        for b in warm:
            svc.submit(A, b)
        svc.drain(timeout_s=600)
    d0 = _tm.get("serving.deadline_miss")
    r0 = _tm.get("autotune.shadow.runs")
    sched = [prng.standard_normal(A.num_rows) for _ in range(k_pair)]
    c0 = time.process_time()
    pair_tix = [[svc.submit(A, b) for b in sched] for svc in svcs]
    t0 = time.perf_counter()
    runs_during = 0
    while any(not svc.idle for svc in svcs):
        for svc in svcs:
            if not svc.idle:
                svc.step()
        if any(not t.done for tt in pair_tix for t in tt):
            # traffic still in flight: any shadow counted so far ran
            # CONCURRENTLY with production — the structural violation
            # the capacity gate exists to prevent. (Shadows in the
            # drained tail are the tuner doing its job.)
            runs_during = _tm.get("autotune.shadow.runs") - r0
        if time.perf_counter() - t0 > 600:  # pragma: no cover
            break

    def p99_ms(tickets, stamp):
        lat = sorted(stamp(t) for t in tickets if t.done)
        return lat[min(len(lat) - 1, int(0.99 * len(lat)))]

    def wall(t):
        return 1e3 * t.latency_s

    def cpu(t):
        # the process-CPU completion stamp: the ruler neighbor steal
        # cannot touch (a mid-burst shadow would burn process CPU and
        # shift every later completion)
        return 1e3 * (t.complete_cpu_t - c0)

    p99_off = p99_ms(pair_tix[0], wall)
    p99_on = p99_ms(pair_tix[1], wall)
    impact_cpu_pct = 100.0 * (
        p99_ms(pair_tix[1], cpu) - p99_ms(pair_tix[0], cpu)) \
        / max(p99_ms(pair_tix[0], cpu), 1e-9)
    miss_on = _tm.get("serving.deadline_miss") - d0
    miss_off = 0
    runs_on = int(runs_during)
    impact_pct = 100.0 * (p99_on - p99_off) / max(p99_off, 1e-9)

    return {
        "grid": f"{n}^3 poisson7pt",
        "mistuning": "BLOCK_JACOBI relaxation_factor=0.02",
        "promoted_knob": rec.get("knob"),
        "promoted_overlay": rec.get("overlay"),
        "shadow_runs": delta("autotune.shadow.runs"),
        "shadow_errors": delta("autotune.shadow.errors"),
        "promotions": delta("autotune.promotions"),
        "pre_iters_median": int(pre_iters),
        "post_iters_median": int(post_iters),
        "pre_exec_wall_ms": round(1e3 * pre_wall, 2),
        "post_exec_wall_ms": round(1e3 * post_wall, 2),
        "autotune_speedup_iters": round(sp_iters, 3),
        "autotune_speedup_wall": round(sp_wall, 3),
        "autotune_speedup": round(min(sp_iters, sp_wall), 3),
        "search_deadline_misses": delta("serving.deadline_miss"),
        "paired_requests": k_pair,
        "paired_design": "lockstep",
        "paired_p99_off_ms": round(p99_off, 2),
        "paired_p99_on_ms": round(p99_on, 2),
        "autotune_shadow_p99_cpu_impact_pct": round(impact_cpu_pct, 2),
        "autotune_shadow_p99_impact_pct": round(impact_pct, 2),
        "paired_deadline_misses": int(miss_on - miss_off),
        "saturated_shadow_runs": int(runs_on),
        "all_completed": bool(all(t.done for t in tix + tix2)),
        "smoke": bool(smoke),
    }


def bench_fleet(n: int = 16, smoke: bool = False):
    """Fleet phase (amgx_tpu/serving/fleet.py): the fingerprint-affine
    replica router vs ONE replica of the identical per-replica config,
    under a load built to expose the placement lever the router
    actually owns — which hierarchy stays warm where. Three sections:

    1. SCALING — a wave-interleaved load alternates two hot sparsity
       patterns, each wave value-perturbed same-pattern systems, with a
       drain boundary between waves. Per-replica
       `serving_cache_entries=1`: the single replica evicts the idle
       bucket at every pattern switch and pays a full hierarchy setup
       per wave, while the 2-replica fleet's rendezvous affinity pins
       each pattern to its home replica so every wave after the first
       sighting rides the value-resetup path. Both runs see the
       IDENTICAL schedule (waves 0+1 land together so the router's
       least-loaded cold placement observes real queue imbalance —
       and the single service gets the same burst). The headline is
       sustained solves/sec fleet vs single and the per-replica route
       counters proving >= 90% affine service.

       HONEST FRAMING: on this rig every replica shares one CPU core
       and one jax device, so the fleet CANNOT win on parallel
       compute — the measured scaling is the aggregate-cache-capacity
       + affinity effect (the fleet's combined cache holds the whole
       working set; the single replica's cannot), which is exactly the
       lever the router exists to exercise. It can exceed 2x for the
       same reason a working set crossing a cache boundary does.
       Compute scaling needs multi-host replicas.

    2. AFFINITY under saturation rides section 1's route counters:
       spills require a strictly-less-loaded candidate, so uniform
       overload keeps traffic home instead of ping-ponging cold
       builds.

    3. SHED AT 2x SATURATION — the bench_chaos section-3 pattern
       against the fleet: train both replicas' latency estimators,
       measure the fleet's closed-loop service rate, then drive
       open-loop arrivals at 2x that rate (on this one-core rig the
       fleet's closed-loop rate on warm alternating traffic is at
       least the single replica's, so this overdrives 2x
       single-replica saturation) with a deadline a few multiples of
       the per-request service time. Gates: every shed classified
       OVERLOADED (the fleet-wide feasibility consult routes the
       request home for an honest per-replica shed, never a silent
       drop), ZERO admitted request finishing DEADLINE_EXCEEDED, and
       admitted p99 within the deadline budget."""
    from amgx_tpu.presets import SERVING_CG
    from amgx_tpu.serving import FleetRouter, SolveService
    from amgx_tpu.telemetry import metrics as _tm
    from amgx_tpu.resilience.status import SolveStatus

    if smoke:
        n, waves, per_wave, slots = 10, 4, 2, 2
    else:
        waves, per_wave, slots = 8, 4, 4
    base_cfg = (SERVING_CG + f", serving_bucket_slots={slots},"
                f" serving_chunk_iters=4, serving_cache_entries=1")
    cfg = Config.from_string(base_cfg)

    pat_a = amgx.gallery.poisson("7pt", n, n, n).init()
    pat_b = amgx.gallery.poisson("7pt", n + 1, n + 1, n + 1).init()
    rng = np.random.default_rng(23)

    def shifted(A, c):
        vals = np.asarray(A.values).copy()
        vals[np.asarray(A.diag_idx)] += c
        return A.with_values(vals)

    # one schedule, built once, replayed verbatim against both systems
    sched, ctr = [], 0
    for w in range(waves):
        A = pat_a if w % 2 == 0 else pat_b
        wave = []
        for _j in range(per_wave):
            wave.append((shifted(A, 0.1 * (ctr % 3)),
                         rng.standard_normal(A.num_rows)))
            ctr += 1
        sched.append(wave)

    # pre-warm a throwaway service on both patterns so process-global
    # compile caches are equally hot for both measured runs (the later
    # run must not inherit a warmup the earlier one paid for)
    warm = SolveService(Config.from_string(
        base_cfg.replace("serving_cache_entries=1",
                         "serving_cache_entries=2")))
    warm.submit(*sched[0][0])
    warm.submit(*sched[1][0])
    warm.drain(timeout_s=600)
    del warm

    def run_sched(submit, drain):
        """Replay the wave schedule closed-loop: waves 0+1 land
        together (cold placement sees real load), then a drain
        boundary per wave — the boundary idles every bucket, which is
        what lets the one-entry cache evict on the next pattern's
        build."""
        tickets = []
        t0 = time.perf_counter()
        for w, wave in enumerate(sched):
            for A_i, b_i in wave:
                tickets.append(submit(A_i, b_i))
            if w != 0:
                drain()
        return tickets, time.perf_counter() - t0

    def delta(cur, base, name):
        return int(cur.get(name, 0) - base.get(name, 0))

    # -- 1a. single-replica baseline (identical per-replica config) ------
    base = _tm.snapshot()
    svc = SolveService(cfg)
    ts_single, wall_single = run_sched(
        svc.submit, lambda: svc.drain(timeout_s=600))
    cur = _tm.snapshot()
    single_setups = delta(cur, base, "amg.setup.full")
    single_evicts = delta(cur, base, "serving.cache.evictions")
    single_ok = all(t.done and t.result.converged for t in ts_single)

    # -- 1b. the 2-replica fleet, same schedule --------------------------
    base = _tm.snapshot()
    fleet = FleetRouter.build(cfg, n_replicas=2)
    ts_fleet, wall_fleet = run_sched(
        fleet.submit, lambda: fleet.drain(timeout_s=600))
    cur = _tm.snapshot()
    fleet_setups = delta(cur, base, "amg.setup.full")
    fleet_resetups = delta(cur, base, "amg.resetup.value")
    fleet_done_ok = all(t.done and t.result.converged for t in ts_fleet)

    routes = fleet.stats()["routes"]
    n_warm = sum(c["warm"] for c in routes.values())
    n_cold = sum(c["cold"] for c in routes.values())
    n_spill = sum(c["spill"] for c in routes.values())
    # affinity: of every request with an established home (all but the
    # cold first-sightings), the fraction its affine replica served
    affinity_rate = n_warm / max(n_warm + n_spill, 1)

    n_req = len(ts_single)
    single_sps = n_req / max(wall_single, 1e-9)
    fleet_sps = n_req / max(wall_fleet, 1e-9)
    scaling_x = fleet_sps / max(single_sps, 1e-9)

    # -- 3. shed accuracy at 2x saturation -------------------------------
    fleet2 = FleetRouter.build(
        Config.from_string(base_cfg + ", serving_shed_policy=deadline"),
        n_replicas=2)
    pats = (pat_a, pat_b)

    def sat_req(i):
        A = pats[i % 2]
        return shifted(A, 0.1 * (i % 3)), rng.standard_normal(A.num_rows)

    for i in range(8):                    # train both estimators
        fleet2.submit(*sat_req(i))
    fleet2.drain(timeout_s=600)
    k = 8 if smoke else 24
    t0 = time.perf_counter()
    closed = [fleet2.submit(*sat_req(i)) for i in range(k)]
    fleet2.drain(timeout_s=600)
    assert all(t.done for t in closed)
    per_req = (time.perf_counter() - t0) / k
    # deadline budget in the admission estimator's own unit: 4x the
    # worst idle-replica feasibility estimate (single-request
    # residence + safety margins), floored by the chaos-phase rule of
    # a few multiples of the closed-loop per-request rate — so an
    # idle fleet ADMITS, a 2x-overdriven backlog turns infeasible and
    # SHEDS, and the gap between the shed threshold (estimate crosses
    # the deadline) and the deadline itself absorbs the estimator's
    # contention error on admitted work near the threshold
    est_idle = max((fleet2.replicas[r]._estimate_latency_s() or 0.0)
                   for r in fleet2.replicas)
    deadline_s = max(4 * est_idle, 8 * per_req, 0.05)
    arrival_dt = per_req / 2.0            # 2x the fleet's service rate
    n_sat = 24 if smoke else 48
    import gc
    gc.collect()          # no mid-burst GC pause from prior sections
    base = _tm.snapshot()
    tickets = []
    t0 = time.perf_counter()
    next_i = 0
    while next_i < n_sat or not fleet2.idle:
        now = time.perf_counter() - t0
        while next_i < n_sat and now >= next_i * arrival_dt:
            A_i, b_i = sat_req(next_i)
            tickets.append(fleet2.submit(A_i, b_i,
                                         deadline_s=deadline_s))
            next_i += 1
        fleet2.step()
        if time.perf_counter() - t0 > 600:   # pragma: no cover
            break
    fleet2.drain(timeout_s=600)
    cur = _tm.snapshot()
    shed = [t for t in tickets if t.done and t.result.status_code
            == int(SolveStatus.OVERLOADED)]
    shed_ids = {id(t) for t in shed}
    admitted = [t for t in tickets if id(t) not in shed_ids]
    adm_miss = [t for t in admitted if t.done and t.result.status_code
                == int(SolveStatus.DEADLINE_EXCEEDED)]
    lat = sorted(1e3 * t.latency_s for t in admitted if t.done)
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else -1.0
    sat_ok = bool(all(t.done for t in tickets) and not adm_miss
                  and all(t.result.status == "overloaded" for t in shed)
                  and (p99 < 0 or p99 <= 1e3 * deadline_s))

    # -- 4. failover: kill 1 of 2 mid-load ------------------------------
    # The fleet-level kill-and-recover drill (bench_chaos section 1
    # raised to the router): a journaled 2-replica fleet takes a mixed
    # two-pattern load, steps until the first ticket's home replica
    # holds admitted + checkpointed work, then that replica is killed
    # (chaos replica_kill). Gates: ZERO lost tickets (every submit
    # terminal), the moved solves finish BIT-IDENTICAL to an
    # uninterrupted twin fleet, the victim reads DOWN, and at least
    # one ticket actually changed replicas. fleet_failover_wall_s is
    # kill -> last victim-homed ticket terminal.
    import shutil
    import tempfile
    from amgx_tpu.resilience import faultinject
    k_fo = 6 if smoke else 12
    reqs = [sat_req(1000 + i) for i in range(k_fo)]
    fo_dirs = [tempfile.mkdtemp(prefix="amgx_fleet_fo_")
               for _ in range(2)]
    fo_base = (base_cfg + ", serving_chunk_iters=1,"
               " serving_checkpoint_cycles=1")
    ref_fleet = FleetRouter.build(Config.from_string(
        fo_base + f", serving_journal_dir={fo_dirs[0]}"), n_replicas=2)
    ref_ts = [ref_fleet.submit(A_i, b_i) for A_i, b_i in reqs]
    ref_fleet.drain(timeout_s=600)
    xrefs = [np.asarray(t.result.x) for t in ref_ts]
    flt = FleetRouter.build(Config.from_string(
        fo_base + f", serving_journal_dir={fo_dirs[1]}"), n_replicas=2)
    fo_ts = [flt.submit(A_i, b_i) for A_i, b_i in reqs]
    victim = fo_ts[0].replica
    orig_replica = [t.replica for t in fo_ts]
    for _ in range(3):     # admit + checkpoint work on the victim
        flt.step()
    t0 = time.monotonic()
    with faultinject.inject("replica_kill", fires=1, target=victim):
        flt.drain(timeout_s=600)
    fo_lost = sum(0 if t.done else 1 for t in fo_ts)
    vt = [t for t, r0 in zip(fo_ts, orig_replica)
          if r0 == victim and t.done]
    failover_wall = (max(t.complete_t for t in vt) - t0) if vt else -1.0
    fo_bit_same = bool(all(
        t.done and np.array_equal(np.asarray(t.result.x), xr)
        for t, xr in zip(fo_ts, xrefs)))
    fo_moved = sum(1 for t, r0 in zip(fo_ts, orig_replica)
                   if t.replica != r0)
    fo_down = bool(flt.health_snapshot()[victim]["down"])
    failover_ok = bool(fo_lost == 0 and fo_bit_same and fo_moved > 0
                       and fo_down
                       and all(t.done and t.result.converged
                               for t in fo_ts))
    for d in fo_dirs:
        shutil.rmtree(d, ignore_errors=True)

    scaling_ok = bool(scaling_x >= 1.7)
    affinity_ok = bool(affinity_rate >= 0.90)
    out = {
        "grid": f"{n}^3 + {n + 1}^3 poisson7pt, {waves} waves x "
                f"{per_wave}, bucket_slots={slots}, cache_entries=1",
        "requests_per_run": n_req,
        "single_solves_per_s": round(single_sps, 2),
        "fleet_solves_per_s": round(fleet_sps, 2),
        "fleet_scaling_x": round(scaling_x, 3),
        "fleet_scaling_efficiency": round(scaling_x / 2.0, 3),
        "fleet_n_replicas": 2,
        "single_full_setups": single_setups,
        "single_cache_evictions": single_evicts,
        "fleet_full_setups": fleet_setups,
        "fleet_value_resetups": fleet_resetups,
        "fleet_affinity_rate": round(affinity_rate, 4),
        "routes": {rid: dict(c) for rid, c in routes.items()},
        "route_warm": n_warm, "route_cold": n_cold,
        "route_spill": n_spill,
        "all_completed": bool(single_ok and fleet_done_ok),
        "sat_deadline_ms": round(1e3 * deadline_s, 2),
        "sat_requests": len(tickets),
        "sat_shed_rate": round(len(shed) / max(len(tickets), 1), 3),
        "sat_admitted_deadline_misses": len(adm_miss),
        "fleet_p99_at_2x_ms": round(p99, 2),
        "fleet_shed_consults": delta(cur, base, "fleet.shed.infeasible"),
        "sat_ok": sat_ok,
        "failover_requests": k_fo,
        "failover_victim": victim,
        "failover_moved_tickets": fo_moved,
        "failover_bit_identical": fo_bit_same,
        "fleet_failover_wall_s": round(failover_wall, 4),
        "fleet_failover_lost_requests": int(fo_lost),
        "failover_ok": failover_ok,
        "scaling_ok": scaling_ok,
        "affinity_ok": affinity_ok,
        "fleet_ok": bool(scaling_ok and affinity_ok and sat_ok
                         and failover_ok
                         and single_ok and fleet_done_ok),
        "smoke": bool(smoke),
    }
    return out


def bench_chaos(n: int = 16, smoke: bool = False):
    """Chaos phase (serving fault tolerance, amgx_tpu/serving/ +
    resilience/faultinject.py service kinds). Three measurements:

    1. KILL-AND-RECOVER — a journaled + hierarchy-persisted + AOT'd
       service is killed mid-flight; its successor replays the journal
       and must (a) resume the checkpointed solves to final iterates
       BIT-IDENTICAL to an uninterrupted run, (b) pay ZERO full AMG
       setups (persisted structures) and ZERO engine retraces (AOT) —
       `chaos_recover_wall_s` is the successor's construct-to-drained
       wall, the restart-story headline.
    2. SCRIPTED FAULT SCENARIOS — builder crash (with retry_backoff
       recovery), device-step exception (quarantine + requeue), wedged
       bucket (heartbeat supervisor), journal corruption (torn write
       dropped at replay), AOT-store corruption (degrades to
       retracing), clock-skewed deadlines. Gate: every scenario ends
       with 100% of tickets terminal — no hangs, no lost requests.
    3. SHED ACCURACY AT 2x SATURATION — open-loop arrivals at twice
       the measured closed-loop service rate with per-request
       deadlines and `serving_shed_policy=deadline`. Gates: sheds are
       classified OVERLOADED, no ADMITTED request ends
       DEADLINE_EXCEEDED, and the accepted p99 stays within the
       deadline budget (`chaos_accepted_p99_ms`)."""
    import shutil
    import tempfile
    from amgx_tpu.presets import SERVING_CG
    from amgx_tpu.resilience import faultinject as fi
    from amgx_tpu.resilience.status import SolveStatus
    from amgx_tpu.serving import SolveService
    from amgx_tpu.telemetry import flightrec as _frec
    from amgx_tpu.telemetry import metrics as _tm

    if smoke:
        n = 10
    root = tempfile.mkdtemp(prefix="amgx_chaos_")
    dirs = (f"serving_aot_dir={root}/aot,"
            f" serving_hierarchy_dir={root}/hier,"
            f" serving_journal_dir={root}/journal")
    base_cfg = (SERVING_CG + ", serving_bucket_slots=4,"
                " serving_chunk_iters=2")
    A = amgx.gallery.poisson("7pt", n, n, n).init()
    rng = np.random.default_rng(7)
    bs = [rng.standard_normal(A.num_rows) for _ in range(6)]
    out = {"grid": f"{n}^3 poisson7pt", "smoke": bool(smoke)}

    def svc_new(extra=""):
        return SolveService(Config.from_string(
            base_cfg + (", " + extra if extra else "")))

    # -- 1. kill-and-recover ---------------------------------------------
    # tight tolerance + 1-iteration chunks so the kill lands
    # mid-flight (the tiny grid would otherwise finish before it)
    kr = "s:tolerance=1e-12, serving_chunk_iters=1"
    ref = svc_new(kr)
    refs = [ref.submit(A, b) for b in bs[:3]]
    ref.drain(timeout_s=600)
    jcfg = dirs + ", serving_checkpoint_cycles=1, " + kr
    victim = svc_new(jcfg)
    vt = [victim.submit(A, b, request_key=f"kr-{i}")
          for i, b in enumerate(bs[:3])]
    for _ in range(3):          # build + a couple of cycles, then die
        victim.step()
    out["killed_inflight"] = sum(not t.done for t in vt)
    del victim
    base = _tm.snapshot()
    t0 = time.perf_counter()
    succ = svc_new(jcfg)        # journal replays at construction
    done = succ.drain(timeout_s=600)
    recover_wall = time.perf_counter() - t0
    cur = _tm.snapshot()

    def delta(name):
        return int(cur.get(name, 0) - base.get(name, 0))

    by_key = {t.request_key: t for t in done if t.request_key}
    bitwise = bool(by_key) \
        and delta("serving.recovery.resumed") > 0 and all(
        t.done and np.array_equal(np.asarray(t.result.x),
                                  np.asarray(refs[int(k.split("-")[1])]
                                             .result.x))
        for k, t in by_key.items())
    out.update({
        "chaos_recover_wall_s": round(recover_wall, 3),
        "recover_replayed": delta("serving.recovery.replayed"),
        "recover_resumed": delta("serving.recovery.resumed"),
        "recover_bitwise_ok": bitwise,
        "restart_full_setups": delta("amg.setup.full"),
        "restart_hier_restored": delta("amg.setup.restored"),
        "restart_retraces": delta("serving.retrace"),
        "recover_all_terminal": bool(all(t.done for t in done)
                                     and succ.idle),
    })

    # -- 2. scripted fault scenarios -------------------------------------
    scen_ok = {}

    def terminal(tickets, svc):
        return bool(all(t.done for t in tickets) and svc.idle)

    def fr_cause(kind, since):
        """The flight-recorder postmortem contract per scenario: the
        LAST chaos event recorded since the scenario started names
        the injected fault — the event trail explains what hit the
        service, not merely that something did."""
        chaos = _frec.events(kind="chaos", since_seq=since)
        return bool(chaos) and chaos[-1].get("fault") == kind

    # builder crash -> bounded backoff retry -> converges
    seq0 = _frec.last_seq()
    svc = svc_new("serving_fault_policy=BUILD_FAILED>retry_backoff,"
                  " serving_retry_backoff_s=0.01")
    with fi.inject("build_crash", fires=1):
        ts = [svc.submit(A, bs[0])]
        svc.drain(timeout_s=600)
    scen_ok["builder_crash"] = terminal(ts, svc) and \
        ts[0].result.converged and fr_cause("build_crash", seq0) and \
        bool(_frec.events(kind="bucket.build_failed", since_seq=seq0))
    # device-step exception -> quarantine -> requeue -> rebuilt bucket
    seq0 = _frec.last_seq()
    svc = svc_new()
    ts = [svc.submit(A, b) for b in bs[:2]]
    svc.step()
    with fi.inject("step_crash", fires=1):
        svc.step()
    svc.drain(timeout_s=600)
    scen_ok["step_crash"] = terminal(ts, svc) and \
        all(t.result.converged for t in ts) and \
        fr_cause("step_crash", seq0) and \
        bool(_frec.events(kind="bucket.quarantine", since_seq=seq0))
    # wedged bucket -> heartbeat supervisor quarantine
    seq0 = _frec.last_seq()
    svc = svc_new("serving_supervisor_cycles=2")
    ts = [svc.submit(A, bs[0])]
    svc.step()
    with fi.inject("step_wedge", fires=6):
        for _ in range(6):
            svc.step()
    svc.drain(timeout_s=600)
    scen_ok["wedged_bucket"] = terminal(ts, svc) and \
        fr_cause("step_wedge", seq0)
    # journal torn write -> dropped at replay, successor keeps serving
    seq0 = _frec.last_seq()
    jd2 = tempfile.mkdtemp(prefix="amgx_chaos_j2_")
    svc = svc_new(f"serving_journal_dir={jd2}")
    with fi.inject("journal_corrupt", fires=1):
        svc.submit(A, bs[0])
    del svc
    svc = svc_new(f"serving_journal_dir={jd2}")
    ts = [svc.submit(A, bs[1])]
    svc.drain(timeout_s=600)
    scen_ok["journal_corrupt"] = terminal(ts, svc) and \
        ts[0].result.converged and fr_cause("journal_corrupt", seq0)
    # AOT-store torn write -> load fails -> degrades to retracing
    seq0 = _frec.last_seq()
    ad2 = tempfile.mkdtemp(prefix="amgx_chaos_a2_")
    with fi.inject("aot_corrupt", fires=None):
        svc = svc_new(f"serving_aot_dir={ad2}")
        svc.submit(A, bs[0])
        svc.drain(timeout_s=600)
    scen_aot_cause = fr_cause("aot_corrupt", seq0)
    svc = svc_new(f"serving_aot_dir={ad2}")
    ts = [svc.submit(A, bs[1])]
    svc.drain(timeout_s=600)
    scen_ok["aot_corrupt"] = terminal(ts, svc) and \
        ts[0].result.converged and scen_aot_cause
    # clock skew: deadline bookkeeping under a shifted clock
    seq0 = _frec.last_seq()
    with fi.inject("clock_skew", value=300.0, fires=None):
        svc = svc_new()
        ts = [svc.submit(A, bs[0], deadline_s=1e9),
              svc.submit(A, bs[1])]
        svc.drain(timeout_s=600)
    scen_ok["clock_skew"] = terminal(ts, svc) and \
        fr_cause("clock_skew", seq0)
    out["chaos_scenarios"] = scen_ok
    out["chaos_all_terminal"] = bool(all(scen_ok.values()))

    # -- 3. shedding at 2x saturation ------------------------------------
    svc = svc_new("serving_shed_policy=deadline")
    warm = [svc.submit(A, b) for b in bs[:4]]
    svc.drain(timeout_s=600)          # warm + train the exec histogram
    k = 8 if smoke else 24
    t0 = time.perf_counter()
    closed = [svc.submit(A, bs[i % len(bs)]) for i in range(k)]
    svc.drain(timeout_s=600)
    assert all(t.done for t in closed)
    per_req = (time.perf_counter() - t0) / k   # closed-loop service rate
    # deadline budget: a few multiples of the measured closed-loop
    # per-request service time (about 2 execution waves at this bucket
    # width), floored for rig noise — tight enough that a 2x-overdriven
    # queue makes tail requests genuinely unmeetable, so the shed
    # policy has real work to do
    deadline_s = max(8 * per_req, 0.05)
    arrival_dt = per_req / 2.0                 # 2x saturation arrivals
    n_req = 24 if smoke else 48
    tickets = []
    t0 = time.perf_counter()
    next_i = 0
    while next_i < n_req or not svc.idle:
        now = time.perf_counter() - t0
        while next_i < n_req and now >= next_i * arrival_dt:
            tickets.append(svc.submit(A, bs[next_i % len(bs)],
                                      deadline_s=deadline_s))
            next_i += 1
        svc.step()
        if time.perf_counter() - t0 > 600:   # pragma: no cover
            break
    svc.drain(timeout_s=600)
    shed = [t for t in tickets if t.done and t.result.status_code
            == int(SolveStatus.OVERLOADED)]
    shed_ids = {id(t) for t in shed}
    admitted = [t for t in tickets if id(t) not in shed_ids]
    adm_miss = [t for t in admitted if t.done and t.result.status_code
                == int(SolveStatus.DEADLINE_EXCEEDED)]
    lat = sorted(1e3 * t.latency_s for t in admitted if t.done)
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else -1.0
    out.update({
        "shed_deadline_ms": round(1e3 * deadline_s, 2),
        "shed_rate": round(len(shed) / max(len(tickets), 1), 3),
        "chaos_accepted_p99_ms": round(p99, 2),
        "shed_admitted_deadline_misses": len(adm_miss),
        "shed_all_overloaded": bool(all(
            t.result.status == "overloaded" for t in shed)),
        "shed_ok": bool(all(t.done for t in tickets)
                        and not adm_miss
                        and (p99 < 0 or p99 <= 1e3 * deadline_s)),
    })
    shutil.rmtree(root, ignore_errors=True)
    return out


def bench_resilience(n: int = 32, iters: int = 300, reps: int = 9):
    """Resilience smoke phase: per-iteration cost of the guarded solve
    loop (health_guards=1, the default: NaN/breakdown/divergence
    classification riding the residual check) vs the unguarded loop
    (health_guards=0, the pre-resilience monitor). Both run CG to a
    full `iters` iterations (unreachable tolerance) on the n^3 7-pt
    Poisson so the quotient isolates the in-loop guard cost; the
    acceptance gate is overhead_pct <= 2. (The opt-in stall window is
    excluded: CG's early L2 residual is non-monotone, so a window
    would legitimately end the guarded run early and skew the
    per-iteration quotient.)"""
    from amgx_tpu.resilience.status import SolveStatus
    A = amgx.gallery.poisson("7pt", n, n, n).init()
    b = jnp.ones(A.num_rows)
    solvers = {}
    for tag, extra in (
            ("guarded", "health_guards=1"),
            ("unguarded", "health_guards=0")):
        cfg = Config.from_string(
            f"solver=CG, max_iters={iters}, monitor_residual=1,"
            f" tolerance=1e-30, convergence=RELATIVE_INI, {extra}")
        slv = amgx.create_solver(cfg)
        slv.setup(A)
        slv.solve(b)                           # compile
        solvers[tag] = slv
    # rig noise swings single measurements several percent either way;
    # pair each guarded sample with an adjacent unguarded one and take
    # the MEDIAN per-pair ratio (the bench_spmv_vs_ceiling technique)
    out = {}
    ratios, best = [], {"guarded": float("inf"),
                        "unguarded": float("inf")}
    for _ in range(2 * reps + 1):
        pair = {}
        for tag in ("guarded", "unguarded"):
            t0 = time.perf_counter()
            res = solvers[tag].solve(b)
            pair[tag] = time.perf_counter() - t0
            best[tag] = min(best[tag], pair[tag])
            out[tag] = {
                "per_iter_us": round(
                    best[tag] / max(res.iterations, 1) * 1e6, 2),
                "iters": int(res.iterations),
                "status": res.status,
            }
        ratios.append(pair["guarded"] / pair["unguarded"])
    ratios.sort()
    # headline: MEDIAN per-pair ratio (paired quotients cancel the
    # scheduler noise both sides share; the min-of-N ratio proved
    # jumpier on shared rigs); best-of mins and the pair spread are
    # kept to show the noise floor the headline was pulled from
    out["overhead_pct"] = round(
        100.0 * (ratios[len(ratios) // 2] - 1.0), 2)
    out["overhead_pct_bestof"] = round(
        100.0 * (best["guarded"] / best["unguarded"] - 1.0), 2)
    out["overhead_pct_pair_spread"] = [
        round(100.0 * (ratios[0] - 1.0), 2),
        round(100.0 * (ratios[-1] - 1.0), 2)]
    # prove the guards actually fire on this rig, not just cost little:
    # one NaN-injected solve must exit early with NAN_DETECTED
    from amgx_tpu.resilience import faultinject as _fi
    slv = amgx.create_solver(Config.from_string(
        f"solver=CG, max_iters={iters}, monitor_residual=1,"
        f" tolerance=1e-30, convergence=RELATIVE_INI"))
    slv.setup(A)
    with _fi.inject("spmv_nan", iteration=3):
        res = slv.solve(b)
    out["nan_inject_status"] = res.status
    out["nan_inject_detected_at"] = int(res.iterations)
    out["guards_fire"] = bool(
        res.status_code == SolveStatus.NAN_DETECTED)
    return out


def _classical_cfg(smoother: str = "JACOBI_L1", extra: str = ""):
    """The benched classical configuration (bench_classical's literal),
    shared with the obs phase so both replay the SAME config. The
    128^3 TPU line requests MULTICOLOR_DILU (the reference's classical
    smoother) and rides the PR-11 known-fault guard: above 96^3 on a
    single TPU chip it falls back to JACOBI_L1 with a warning and a
    `resilience.config_fallback` count — recorded in the bench line so
    the fallback is visible, not silent — and the fallback smoother
    takes the fused classical path (weighted transfer slabs +
    single-pass smoother kernels on the DIA fine level)."""
    return Config.from_string(
        "config_version=2, solver(s)=PCG, s:max_iters=100,"
        " s:tolerance=1e-8, s:convergence=RELATIVE_INI,"
        " s:monitor_residual=1, s:preconditioner(amg)=AMG,"
        " amg:algorithm=CLASSICAL, amg:selector=PMIS,"
        f" amg:interpolator=D2, amg:smoother={smoother},"
        " amg:presweeps=1,"
        " amg:postsweeps=1, amg:max_iters=1,"
        " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=32,"
        " amg:max_levels=20, amg:strength_threshold=0.25,"
        " amg:interp_max_elements=4, amg:max_row_sum=0.9,"
        " amg:amg_precision=float" + extra)


def bench_obs(n_flagship: int = 128, n_classical: int = 64,
              reps: int = 7):
    """Observability phase (`python bench.py obs`): replay the flagship
    and classical configs INSTRUMENTED and record what the telemetry
    subsystem says about them — the full structured SolveReport per
    config, the process-wide counter/gauge dump (structure-cache
    hit/miss, setup routing, retrace counts, memory watermarks), and a
    Perfetto trace-event export of the recorded host spans.

    Acceptance gates carried in the payload:
    - `overhead_pct`: paired-median per-iteration cost of the
      instrumented (telemetry=1) flagship solve vs telemetry=0 — must
      be within rig noise (the report is built host-side from the
      stats array the solve already returns; the traced program is
      identical by construction, so this measures ~0 plus noise);
    - `*_report_valid`: each emitted report validates against the
      checked-in schema (telemetry/report_schema.json);
    - `perfetto_valid`: the exported trace file loads as JSON.
    """
    import os

    from amgx_tpu.telemetry import metrics, spans, validate_report

    out = {}
    metrics.reset()

    # ---- flagship, instrumented vs uninstrumented ---------------------
    A = amgx.gallery.poisson("7pt", n_flagship, n_flagship,
                             n_flagship).init()
    b = jnp.ones(A.num_rows)
    slv_on = amgx.create_solver(Config.from_string(FLAGSHIP))
    slv_off = amgx.create_solver(Config.from_string(
        FLAGSHIP + ", telemetry=0"))
    slv_on.setup(A)
    slv_off.setup(A)
    res_on = slv_on.solve(b)          # compile
    res_off = slv_off.solve(b)
    assert res_off.report is None and res_on.report is not None
    # paired per-iteration quotients (the bench_resilience technique):
    # rig noise cancels in each pair, the median is the headline
    ratios = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res_on = slv_on.solve(b)
        dt_on = time.perf_counter() - t0
        t0 = time.perf_counter()
        res_off = slv_off.solve(b)
        dt_off = time.perf_counter() - t0
        ratios.append((dt_on / max(res_on.iterations, 1))
                      / (dt_off / max(res_off.iterations, 1)))
    ratios.sort()
    out["overhead_pct"] = round(
        100.0 * (ratios[len(ratios) // 2] - 1.0), 2)
    out["overhead_pct_pair_spread"] = [
        round(100.0 * (ratios[0] - 1.0), 2),
        round(100.0 * (ratios[-1] - 1.0), 2)]
    out["overhead_ok"] = bool(abs(out["overhead_pct"]) <= 2.0)
    rep = res_on.report.to_dict()
    errs = validate_report(rep)
    out[f"flagship_{n_flagship}^3_report"] = rep
    out["flagship_report_valid"] = not errs
    if errs:
        out["flagship_report_schema_errors"] = errs[:10]
    # the warm-setup headline is now IN the standard report (the 256^3
    # warm-setup footnote check reads report.setup_time_s instead of
    # only the BENCH breakdown)
    out[f"flagship_{n_flagship}^3_report_setup_s"] = round(
        rep["setup_time_s"], 3)

    # ---- convergence diagnostics (diagnostics=1 probe) ----------------
    # the flagship replayed with the diagnostics knob: the report must
    # name a bottleneck level with per-level reduction factors — the
    # per-round proof that the probe works at the flagship's
    # REFINEMENT -> FGMRES -> AMG nesting depth on the real chip
    try:
        slv_d = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", diagnostics=1"))
        slv_d.setup(A)
        res_d = slv_d.solve(b)
        dg = (res_d.report.diagnostics
              if res_d.report is not None else None)
        out["diagnostics"] = dg
        out["diagnostics_bottleneck_level"] = (
            None if dg is None else dg.get("bottleneck_level"))
        out["diagnostics_acf"] = (
            None if dg is None
            else dg.get("asymptotic_convergence_factor"))
        out["diagnostics_ok"] = bool(
            dg is not None and dg.get("bottleneck_level") is not None
            and all(r.get("level_reduction") is not None
                    for r in dg.get("levels", [])))
    except Exception as e:  # pragma: no cover - bench robustness
        out["diagnostics_error"] = str(e)[:200]
        out["diagnostics_ok"] = False

    # ---- classical replay ---------------------------------------------
    try:
        Ac = amgx.gallery.poisson("7pt", n_classical, n_classical,
                                  n_classical).init()
        bc = jnp.ones(Ac.num_rows)
        slc = amgx.create_solver(_classical_cfg())
        slc.setup(Ac)
        resc = slc.solve(bc)
        repc = resc.report.to_dict()
        errsc = validate_report(repc)
        out[f"classical_{n_classical}^3_report"] = repc
        out["classical_report_valid"] = not errsc
        if errsc:
            out["classical_report_schema_errors"] = errsc[:10]
    except Exception as e:  # pragma: no cover - bench robustness
        out["classical_error"] = str(e)[:200]

    # ---- counter dump + Perfetto span export --------------------------
    out["counters"] = metrics.snapshot()
    trace_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_obs_trace.json")
    out["perfetto_events"] = spans.export_chrome_trace(trace_path)
    out["perfetto_trace"] = os.path.basename(trace_path)
    try:
        with open(trace_path) as f:
            doc = json.load(f)
        out["perfetto_valid"] = bool(
            isinstance(doc.get("traceEvents"), list)
            and len(doc["traceEvents"]) == out["perfetto_events"])
    except Exception as e:  # pragma: no cover - bench robustness
        out["perfetto_valid"] = False
        out["perfetto_error"] = str(e)[:120]

    # ---- serving tracing replay ---------------------------------------
    # request-path tracing (serving_tracing) on vs off over the SAME
    # serving load: the per-ticket lifecycle spans + flow tagging are
    # host-side dict appends, so the paired-median per-request cost
    # must stay within 2%. Runs AFTER the full-timeline export above,
    # and resets the span buffer post-warmup, so BENCH_obs_requests
    # carries ONLY the burst's request chains — a per-request
    # artifact, not a second copy of the whole solver timeline.
    try:
        from amgx_tpu.presets import SERVING_CG
        from amgx_tpu.serving import SolveService

        ns = 20
        As = amgx.gallery.poisson("7pt", ns, ns, ns).init()
        rng = np.random.default_rng(11)
        bsrv = [rng.standard_normal(As.num_rows) for _ in range(6)]

        def _svc(tracing):
            return SolveService(Config.from_string(
                SERVING_CG + ", serving_bucket_slots=4,"
                f" serving_chunk_iters=8, serving_tracing={tracing}"))

        svc_on, svc_off = _svc(1), _svc(0)
        for svc in (svc_on, svc_off):     # build bucket + warm traces
            for b_ in bsrv[:4]:
                svc.submit(As, b_)
            svc.drain(timeout_s=300)

        def _burst(svc):
            t0 = time.perf_counter()
            ts = [svc.submit(As, b_) for b_ in bsrv]
            svc.drain(timeout_s=300)
            assert all(t.done and t.result.converged for t in ts)
            return (time.perf_counter() - t0) / len(bsrv)

        spans.reset()       # requests-only artifact from here on
        tr_ratios = []
        for _ in range(reps):
            tr_ratios.append(_burst(svc_on) / _burst(svc_off))
        tr_ratios.sort()
        out["serving_trace_overhead_pct"] = round(
            100.0 * (tr_ratios[len(tr_ratios) // 2] - 1.0), 2)
        out["serving_trace_overhead_pair_spread"] = [
            round(100.0 * (tr_ratios[0] - 1.0), 2),
            round(100.0 * (tr_ratios[-1] - 1.0), 2)]
        out["serving_trace_ok"] = bool(
            abs(out["serving_trace_overhead_pct"]) <= 2.0)
        req_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_obs_requests.json")
        out["serving_trace_events"] = spans.export_chrome_trace(
            req_path)
        with open(req_path) as f:
            reqdoc = json.load(f)
        flows = [e for e in reqdoc["traceEvents"]
                 if e.get("cat") == "trace.flow"]
        starts = sum(1 for e in flows if e["ph"] == "s")
        out["serving_trace_flow_events"] = len(flows)
        out["serving_trace_flow_chains"] = starts
        out["serving_trace_artifact"] = os.path.basename(req_path)
        # every traced burst request must have minted a flow chain
        out["serving_trace_flows_ok"] = bool(
            starts >= len(bsrv) and len(flows) > 2 * starts)
    except Exception as e:  # pragma: no cover - bench robustness
        out["serving_trace_error"] = str(e)[:200]
        out["serving_trace_ok"] = False
    return out


# artifact schema: version 2 adds the `round`/`schema_version` stamps
# (tools/bench_history.py keys rounds on them instead of parsing
# filenames) and the incremental checkpoint writes below
BENCH_SCHEMA_VERSION = 2


def _round_stamp():
    """Stable round id for the artifact: the driver exports
    AMGX_BENCH_ROUND when it knows the round number; None otherwise
    (bench_history falls back to the wrapper's `n`, then filename)."""
    import os
    r = os.environ.get("AMGX_BENCH_ROUND", "").strip()
    if not r:
        return None
    return int(r) if r.isdigit() else r


def _write_artifact(payload):
    """(Re)write BENCH.json. Called after EVERY phase, not only at the
    end of main(): a round whose process dies mid-run (driver timeout,
    OOM) still leaves the completed phases' numbers on disk instead of
    an unrecorded round — the regression sentinel then sees a partial
    round, not a hole."""
    import os
    art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH.json")
    with open(art, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def main():
    t_start = time.perf_counter()
    amgx.initialize()
    extra = {}
    spmv_gbps, spmv_s = 0.0, 1.0
    _round = _round_stamp()

    def _checkpoint(metric="bench_incomplete", value=-1.0, unit="none",
                    error=None):
        payload = {
            "schema_version": BENCH_SCHEMA_VERSION,
            "round": _round,
            "metric": metric,
            "value": value,
            "unit": unit,
            "vs_baseline": round(spmv_gbps / A100_HBM_GBPS, 4),
            "extra": extra,
        }
        if error is not None:
            payload["error"] = str(error)[:300]
        elif metric == "bench_incomplete":
            payload["error"] = "incomplete: process ended mid-run " \
                               "(checkpoint write)"
        try:
            _write_artifact(payload)
        except Exception as e:  # pragma: no cover - bench robustness
            extra["artifact_error"] = str(e)[:120]
        return payload
    try:
        sp = bench_spmv_vs_ceiling()
        spmv_gbps, spmv_s = sp["gbps"], sp["ms"] / 1e3
        extra["spmv_7pt_128^3_f32_gbps"] = round(sp["gbps"], 2)
        extra["spmv_7pt_128^3_f32_ms"] = round(sp["ms"], 4)
        extra["stream_ceiling_gbps"] = round(sp["ceiling_gbps"], 2)
        extra["spmv_vs_ceiling"] = round(sp["ratio_median"], 3)
        extra["spmv_vs_ceiling_spread"] = [round(sp["ratio_min"], 3),
                                           round(sp["ratio_max"], 3)]
    except Exception as e:  # pragma: no cover - bench robustness
        extra["spmv_error"] = str(e)[:120]
    _checkpoint()
    # every optional phase runs under a SIGALRM guard so the single
    # JSON line always prints
    import signal

    class _Budget(Exception):
        pass

    def _on_alarm(*_a):  # pragma: no cover - timing dependent
        raise _Budget()

    import gc

    # classical lines first (cheap since the host-path rework: ~3 s at
    # 64^3, ~20 s warm at 128^3); the 256^3 north star runs LAST with
    # the largest alarm — an aborted 256^3 phase must never poison the
    # other measurements (eager leftovers degrade later transfers).
    for cn in (64, 128):
        if time.perf_counter() - t_start > 900:   # alarm-abort pile-up
            extra[f"classical_{cn}_error"] = "skipped: out of budget"
            continue
        try:
            old = signal.signal(signal.SIGALRM, _on_alarm)
            signal.alarm(300)
            try:
                cr = bench_classical(cn)
                extra.update({
                    f"classical_pmis_d2_{cn}^3_setup_warm_s":
                        round(cr["setup_warm_s"], 2),
                    f"classical_pmis_d2_{cn}^3_setup_rows_per_s":
                        round(cr["setup_rows_per_s"]),
                    f"classical_pmis_d2_{cn}^3_setup_accounted_fraction":
                        round(cr["setup_accounted_fraction"], 3),
                    f"classical_pmis_d2_{cn}^3_solve_s":
                        round(cr["solve_s"], 3),
                    f"classical_pmis_d2_{cn}^3_iters": cr["iters"],
                    f"classical_pmis_d2_{cn}^3_true_rel_residual":
                        cr["rel"],
                })
                extra[f"classical_{cn}^3_config_fallback"] = \
                    cr["config_fallback"]
                extra[f"classical_{cn}^3_smoother"] = \
                    cr["smoother_effective"]
                if cn == 128:
                    extra["classical_128^3_setup_breakdown"] = \
                        cr["breakdown"]
                    # sentinel-tracked aliases (tools/bench_history.py
                    # SERIES): the 24x classical-vs-flagship gap's two
                    # headline walls, declared from this round forward
                    extra["classical_128^3_setup_s"] = \
                        round(cr["setup_warm_s"], 2)
                    extra["classical_128^3_solve_s"] = \
                        round(cr["solve_s"], 3)
                    # plan-split RAP attribution (sentinel-tracked):
                    # the summed per-level RAP spans of the warm setup
                    extra["classical_128^3_rap_s"] = cr["rap_s"]
                    extra["classical_128^3_rap_share"] = \
                        cr["rap_share"]
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        except _Budget:  # pragma: no cover - timing dependent
            extra[f"classical_{cn}_error"] = "wall-clock budget exceeded"
            break
        except Exception as e:  # pragma: no cover - bench robustness
            extra[f"classical_{cn}_error"] = str(e)[:200]
            break
    _checkpoint()
    gc.collect()

    # spmv layout-efficiency phase (DIA/ELL/SWELL, fused vs unfused):
    # the tentpole's one-pass win as a recorded number per round
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(240)
        try:
            extra["spmv_layouts_128^3"] = bench_spmv_layouts()
            fl_row = extra["spmv_layouts_128^3"].get(
                "dia_smooth2_residual", {})
            if "fused_speedup" in fl_row:
                extra["fused_smooth_residual_speedup"] = \
                    fl_row["fused_speedup"]
            cy_row = extra["spmv_layouts_128^3"].get(
                "geo_cycle_64^3", {})
            if "speedup" in cy_row:
                extra["fused_cycle_speedup_64^3"] = cy_row["speedup"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["spmv_layouts_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["spmv_layouts_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # Krylov-shell phase: paired krylov_fusion=1 vs 0 replay (PCG +
    # GEO AMG) — the fused SpMV+dot / cg_update shell's warm-solve
    # speedup plus the per-iteration HBM pass census
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(300)
        try:
            kr = bench_krylov()
            extra["krylov_shell"] = kr
            extra["krylov_fused_speedup"] = \
                kr["krylov_fused_speedup"]
            extra["krylov_fused_passes"] = kr["krylov_fused_passes"]
            extra["krylov_unfused_passes"] = \
                kr["krylov_unfused_passes"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["krylov_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["krylov_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # batched-serving phase: cheap (32^3, f64 CG+AggAMG), guarded like
    # the other optional phases so the JSON line always prints
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(240)
        try:
            extra["batched_32^3_per_system"] = bench_batched()
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["batched_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["batched_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # serving phase: open-loop load against the continuous-batching
    # solve service — sustained solves/sec, p50/p99 latency, cache-hit
    # rate, zero-retrace-after-AOT and deadline-miss proof (nested
    # payload -> artifact; scalar headlines -> compact line)
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(240)
        try:
            sv = bench_serving()
            extra["serving"] = sv
            extra["serving_solves_per_s"] = sv["solves_per_s"]
            extra["serving_p50_ms"] = sv["p50_ms"]
            extra["serving_p99_ms"] = sv["p99_ms"]
            extra["serving_cache_hit_rate"] = sv["cache_hit_rate"]
            extra["serving_retraces_after_warmup"] = \
                sv["retraces_after_warmup"]
            extra["serving_deadline_ok"] = sv["deadline_statuses_ok"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["serving_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["serving_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # fleet phase: 2-replica fingerprint-affine router vs one replica
    # of the identical config under the cache-capacity wave load —
    # scaling ratio, route-counter affinity proof, shed accuracy at 2x
    # saturation (nested payload -> artifact; gates -> compact line)
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(300)
        try:
            fl = bench_fleet()
            extra["fleet"] = fl
            extra["fleet_scaling_x"] = fl["fleet_scaling_x"]
            extra["fleet_scaling_efficiency"] = \
                fl["fleet_scaling_efficiency"]
            extra["fleet_p99_at_2x_ms"] = fl["fleet_p99_at_2x_ms"]
            extra["fleet_affinity_rate"] = fl["fleet_affinity_rate"]
            extra["fleet_failover_wall_s"] = \
                fl["fleet_failover_wall_s"]
            extra["fleet_failover_lost_requests"] = \
                fl["fleet_failover_lost_requests"]
            extra["fleet_ok"] = fl["fleet_ok"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["fleet_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["fleet_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # chaos phase: serving fault tolerance — kill-and-recover wall
    # (journal replay + persisted hierarchies + AOT: zero full setups,
    # zero retraces, bit-identical resume), scripted fault scenarios
    # all-terminal, shed accuracy at 2x saturation
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(300)
        try:
            ch = bench_chaos()
            extra["chaos"] = ch
            extra["chaos_recover_wall_s"] = ch["chaos_recover_wall_s"]
            extra["chaos_accepted_p99_ms"] = \
                ch["chaos_accepted_p99_ms"]
            extra["chaos_all_terminal"] = ch["chaos_all_terminal"]
            extra["chaos_recover_bitwise_ok"] = \
                ch["recover_bitwise_ok"]
            extra["chaos_shed_ok"] = ch["shed_ok"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["chaos_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["chaos_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # resilience smoke phase: guarded vs unguarded iteration-loop cost
    # (BENCH_* tracks that the health guards stay within 2% of baseline)
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(180)
        try:
            extra["resilience_32^3"] = bench_resilience()
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["resilience_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["resilience_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # observability phase: instrumented flagship+classical replays with
    # the full SolveReport + counter dump recorded in the artifact, the
    # telemetry-on-vs-off paired overhead gate, and the Perfetto span
    # export (nested payload -> artifact; scalar gates -> compact line)
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(300)
        try:
            obs = bench_obs(reps=5)
            extra["obs"] = obs
            extra["obs_overhead_pct"] = obs.get("overhead_pct")
            extra["obs_overhead_ok"] = obs.get("overhead_ok")
            extra["obs_report_valid"] = bool(
                obs.get("flagship_report_valid")
                and obs.get("classical_report_valid", True))
            extra["obs_perfetto_valid"] = obs.get("perfetto_valid")
            extra["obs_perfetto_events"] = obs.get("perfetto_events")
            extra["obs_diagnostics_ok"] = obs.get("diagnostics_ok")
            extra["obs_diagnostics_bottleneck_level"] = \
                obs.get("diagnostics_bottleneck_level")
            extra["serving_trace_overhead_pct"] = \
                obs.get("serving_trace_overhead_pct")
            extra["serving_trace_ok"] = obs.get("serving_trace_ok")
            extra["serving_trace_flow_chains"] = \
                obs.get("serving_trace_flow_chains")
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["obs_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["obs_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    try:
        fl = bench_flagship()
        solve_s = fl["solve_s"]
        extra.update({
            "flagship_128^3_setup_cold_s": round(fl["setup_cold_s"], 2),
            "flagship_128^3_setup_warm_s": round(fl["setup_warm_s"], 3),
            "flagship_128^3_setup_rows_per_s":
                round(fl["setup_rows_per_s"]),
            "flagship_128^3_setup_accounted_fraction":
                round(fl["setup_accounted_fraction"], 3),
            "flagship_128^3_setup_attribution_ok":
                bool(fl["setup_accounted_fraction"] >= 0.9),
            "flagship_128^3_resetup_s": round(fl["resetup_s"], 3),
            "flagship_128^3_resetup_first_s":
                round(fl["resetup_first_s"], 3),
            # trajectory guard for the trace-reuse fix: the FIRST
            # resetup now replays the setup's compiled pieces, so this
            # ratio stays O(1) instead of the old fused-jit retrace blowup
            "flagship_128^3_resetup_first_over_steady": round(
                fl["resetup_first_s"] / max(fl["resetup_s"], 1e-9), 1),
            "flagship_128^3_setup_breakdown": fl["breakdown"],
            "flagship_128^3_solve_s": round(solve_s, 4),
            "flagship_128^3_outer_iters": fl["iters"],
            "flagship_128^3_converged": fl["converged"],
            "flagship_128^3_true_rel_residual": fl["rel"],
            # solve-phase attribution: per-level cycle breakdown +
            # per-cycle kernel counts (nested -> artifact only) and the
            # fused-vs-unfused cycle speedup scalar (compact line too)
            "flagship_128^3_cycle_breakdown": fl["cycle_breakdown"],
            "flagship_128^3_cycle_speedup": fl["cycle_speedup"],
            "flagship_128^3_cycle_fused_speedup":
                (fl["cycle_speedup"] or {}).get("speedup"),
            "flagship_config":
                "REFINEMENT[f64] -> FGMRES+GEO-AggAMG[f32]+Cheb2",
        })
        value = solve_s
        metric = "poisson7pt_128^3 refined FGMRES+AggAMG solve to 1e-8 (f64)"
        unit = "s"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["flagship_error"] = str(e)[:200]
        if "spmv_error" in extra:
            # neither phase produced a real measurement — say so rather
            # than reporting the spmv placeholder as a timing
            value, metric, unit = -1.0, "bench_failed", "none"
        else:
            value = spmv_s * 1e3
            metric = "poisson7pt_128^3 SpMV"
            unit = "ms"
    _checkpoint(metric=metric, value=value, unit=unit,
                error="incomplete: north-star phase still pending")

    # plan-split RAP phase: paired plan-vs-eager warm-setup replay
    # (flagship GEO + classical) — the spgemm_plan knob's measured win;
    # sentinel-tracked via spgemm_plan_speedup
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(420)
        try:
            sg = bench_spgemm_plan()
            extra["spgemm"] = sg
            extra["spgemm_plan_speedup"] = sg["spgemm_plan_speedup"]
            extra["spgemm_plan_speedup_classical"] = \
                sg["spgemm_plan_speedup_classical"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["spgemm_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["spgemm_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # mixed-precision phase: the flagship paired-replayed at
    # solve_precision=float vs bfloat16 (ROADMAP item 5: bf16 operand
    # slabs through the fused kernels inside the f64 refinement
    # shell); sentinel-tracked via flagship_128^3_solve_bf16_s +
    # mixed_precision_speedup
    try:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(420)
        try:
            mp = bench_precision(reps=3)
            extra["precision"] = mp
            extra["flagship_128^3_solve_bf16_s"] = mp["solve_bf16_s"]
            extra["mixed_precision_speedup"] = \
                mp["mixed_precision_speedup"]
            extra["mixed_precision_matched_residuals"] = \
                mp["matched_residuals_ok"]
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    except _Budget:  # pragma: no cover - timing dependent
        extra["precision_error"] = "wall-clock budget exceeded"
    except Exception as e:  # pragma: no cover - bench robustness
        extra["precision_error"] = str(e)[:200]
    _checkpoint()
    gc.collect()

    # the 256^3 north star (BASELINE.md headline). Solo phase cost with
    # a cold compile cache is ~500 s (gallery + one cold setup + the
    # fused-resetup trace); warm-cache runs are far cheaper. light mode
    # folds the resetup into the warm solver.
    if time.perf_counter() - t_start < 1100:
        try:
            old = signal.signal(signal.SIGALRM, _on_alarm)
            signal.alarm(720)
            try:
                ns = bench_flagship(256, tolerance="1e-10", reps=1,
                                    light=True)
                extra.update({
                    "northstar_256^3_setup_cold_s":
                        round(ns["setup_cold_s"], 2),
                    "northstar_256^3_setup_warm_s":
                        round(ns["setup_warm_s"], 2),
                    "northstar_256^3_setup_rows_per_s":
                        round(ns["setup_rows_per_s"]),
                    "northstar_256^3_setup_accounted_fraction":
                        round(ns["setup_accounted_fraction"], 3),
                    # per-stage attribution of the 256^3 warm setup:
                    # round 5's 17.37 s regression was unattributable
                    # because only the 128^3 breakdown was recorded
                    "northstar_256^3_setup_breakdown": ns["breakdown"],
                    "northstar_256^3_resetup_s": round(ns["resetup_s"], 3),
                    "northstar_256^3_resetup_first_s":
                        round(ns["resetup_first_s"], 3),
                    "northstar_256^3_solve_s": round(ns["solve_s"], 3),
                    "northstar_256^3_outer_iters": ns["iters"],
                    "northstar_256^3_converged": ns["converged"],
                    "northstar_256^3_true_rel_residual": ns["rel"],
                    "northstar_256^3_cycle_breakdown":
                        ns["cycle_breakdown"],
                    "northstar_256^3_cycle_speedup": ns["cycle_speedup"],
                    "northstar_256^3_cycle_fused_speedup":
                        (ns["cycle_speedup"] or {}).get("speedup"),
                })
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        except _Budget:  # pragma: no cover - timing dependent
            extra["northstar_error"] = "wall-clock budget exceeded"
        except Exception as e:  # pragma: no cover - bench robustness
            extra["northstar_error"] = str(e)[:200]

    # full payload -> BENCH.json artifact (machine-readable by contract:
    # json.load must work; already checkpoint-written after every phase
    # above — this is the final, complete, error-free write); stdout
    # gets ONE COMPACT line — scalars only, no nested breakdowns —
    # because the driver's stdout-tail capture is bounded and round 5's
    # full-fat line outgrew it (parsed: null, the SpMV-efficiency /
    # 64^3 / classical headline numbers lost).
    _checkpoint(metric=metric, value=value, unit=unit)
    compact = {k: v for k, v in extra.items()
               if not isinstance(v, (dict, list))}
    print(json.dumps({
        "schema_version": BENCH_SCHEMA_VERSION,
        "round": _round,
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": round(spmv_gbps / A100_HBM_GBPS, 4),
        "artifact": "BENCH.json",
        "extra": compact,
    }), flush=True)


if __name__ == "__main__":
    import sys

    if sys.argv[1:] == ["setup"]:
        # standalone setup-attribution phase: `python bench.py setup`
        amgx.initialize()
        res = bench_setup()
        worst = min(v["setup_accounted_fraction"] for v in res.values())
        print(json.dumps({
            "metric": "flagship warm setup attribution "
                      "(accounted fraction, worst grid)",
            "value": worst,
            "unit": "fraction",
            "vs_baseline": 0.0,
            "extra": res,
        }), flush=True)
    elif sys.argv[1:] == ["spmv"]:
        # standalone layout-efficiency phase: `python bench.py spmv`
        amgx.initialize()
        res = bench_spmv_layouts()
        headline = res.get("dia_smooth2_residual", {}).get(
            "fused_speedup", 0.0)
        print(json.dumps({
            "metric": "fused smooth(2)+residual speedup vs unfused "
                      "(poisson7pt 128^3 DIA)",
            "value": headline,
            "unit": "x",
            "vs_baseline": res.get("dia", {}).get("vs_ceiling", 0.0),
            "extra": res,
        }), flush=True)
    elif sys.argv[1:2] == ["precision"]:
        # standalone mixed-precision phase: `python bench.py precision`
        # (optionally `--smoke` at 32^3 for a fast functional check) —
        # flagship paired replay at solve_precision=float vs bfloat16
        amgx.initialize()
        smoke = "--smoke" in sys.argv[2:]
        res = bench_precision(n=32 if smoke else 128,
                              reps=3 if smoke else 5)
        print(json.dumps({
            "metric": "flagship solve_precision float/bfloat16 "
                      "paired-replay speedup",
            "value": res.get("mixed_precision_speedup", -1.0),
            "unit": "x",
            "vs_baseline": 0.0,
            "extra": res,
        }), flush=True)
    elif sys.argv[1:] == ["obs"]:
        # standalone observability phase: `python bench.py obs` —
        # instrumented replays, full reports + counter dump into the
        # BENCH_obs.json artifact, Perfetto span export, overhead gate
        amgx.initialize()
        res = bench_obs()
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_obs.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        compact = {k: v for k, v in res.items()
                   if not isinstance(v, (dict, list))}
        print(json.dumps({
            "metric": "telemetry-instrumented flagship per-iteration "
                      "overhead vs telemetry=0 (paired median)",
            "value": res.get("overhead_pct", -1.0),
            "unit": "pct",
            "vs_baseline": 0.0,
            "artifact": "BENCH_obs.json",
            "extra": compact,
        }), flush=True)
    elif sys.argv[1:2] == ["serving"]:
        # standalone serving phase: `python bench.py serving` (full) or
        # `python bench.py serving --smoke` (the tier-1 fast path:
        # tiny grids, arrival schedule collapsed)
        amgx.initialize()
        res = bench_serving(smoke="--smoke" in sys.argv[2:])
        # round stamp + series-named scalars: tools/bench_history.py
        # reads phase artifacts directly, so a standalone run recorded
        # under AMGX_BENCH_ROUND populates the serving_* series even
        # when no BENCH_r<NN>.json wrapper carried them
        res["round"] = _round_stamp()
        res["extra"] = {
            "serving_solves_per_s": res["solves_per_s"],
            "serving_p50_ms": res["p50_ms"],
            "serving_p99_ms": res["p99_ms"],
            "serving_cache_hit_rate": res["cache_hit_rate"],
        }
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_serving.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "serving sustained throughput under open-loop "
                      "load (continuous batching)",
            "value": res["solves_per_s"],
            "unit": "solves/s",
            "vs_baseline": 0.0,
            "artifact": "BENCH_serving.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:2] == ["fleet"]:
        # standalone fleet phase: `python bench.py fleet` (full) or
        # `python bench.py fleet --smoke` (tier-1 fast path: tiny
        # grids, short waves) — 2-replica scaling, affinity, 2x shed
        amgx.initialize()
        res = bench_fleet(smoke="--smoke" in sys.argv[2:])
        res["round"] = _round_stamp()
        res["extra"] = {
            "fleet_scaling_x": res["fleet_scaling_x"],
            "fleet_scaling_efficiency":
                res["fleet_scaling_efficiency"],
            "fleet_p99_at_2x_ms": res["fleet_p99_at_2x_ms"],
            "fleet_affinity_rate": res["fleet_affinity_rate"],
            "fleet_solves_per_s": res["fleet_solves_per_s"],
            "fleet_failover_wall_s": res["fleet_failover_wall_s"],
            "fleet_failover_lost_requests":
                res["fleet_failover_lost_requests"],
        }
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_fleet.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "fleet 2-replica vs single-replica sustained "
                      "throughput (fingerprint-affine router, "
                      "cache-capacity wave load)",
            "value": res["fleet_scaling_x"],
            "unit": "x",
            "vs_baseline": 0.0,
            "artifact": "BENCH_fleet.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:2] == ["autotune"]:
        # standalone autotune phase: `python bench.py autotune` (full)
        # or `python bench.py autotune --smoke` (tier-1 fast path:
        # tiny grid, short paired loop) — the online tuner's win
        # (mistuned hot fingerprint re-served >=2x faster after
        # promotion) and its cost (paired saturated p99 within noise,
        # zero deadline misses added by the search)
        amgx.initialize()
        res = bench_autotune(smoke="--smoke" in sys.argv[2:])
        res["round"] = _round_stamp()
        res["extra"] = {
            "autotune_speedup": res["autotune_speedup"],
            "autotune_shadow_p99_impact_pct":
                res["autotune_shadow_p99_impact_pct"],
        }
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_autotune.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "autotuner speedup on a mistuned hot "
                      "fingerprint (min of iteration and wall "
                      "ratios, measured post-promotion)",
            "value": res["autotune_speedup"],
            "unit": "x",
            "vs_baseline": 0.0,
            "artifact": "BENCH_autotune.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:2] == ["chaos"]:
        # standalone chaos phase: `python bench.py chaos` (full) or
        # `python bench.py chaos --smoke` (tier-1 fast path)
        amgx.initialize()
        res = bench_chaos(smoke="--smoke" in sys.argv[2:])
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_chaos.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "serving kill-and-recover wall (journal replay "
                      "+ persisted hierarchies + AOT warm start)",
            "value": res["chaos_recover_wall_s"],
            "unit": "s",
            "vs_baseline": 0.0,
            "artifact": "BENCH_chaos.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:2] == ["spgemm"]:
        # standalone plan-split RAP phase: `python bench.py spgemm`
        # (full: flagship 128^3 + classical 64^3 paired warm-setup
        # replay) or `--smoke` (tiny grids, tier-1 functional check)
        amgx.initialize()
        smoke = "--smoke" in sys.argv[2:]
        res = bench_spgemm_plan(
            flagship_n=32 if smoke else 128,
            classical_n=16 if smoke else 64,
            reps=1 if smoke else 2)
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_spgemm.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "plan-split vs eager Galerkin RAP warm-setup "
                      "speedup (paired replay, flagship)",
            "value": res.get("spgemm_plan_speedup", -1.0),
            "unit": "x",
            "vs_baseline": 0.0,
            "artifact": "BENCH_spgemm.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:2] == ["matfree"]:
        # standalone matrix-free phase: `python bench.py matfree`
        # (full: 128^3 paired replay) or `--smoke` (16^3, the tier-1
        # functional check — must exit 0)
        amgx.initialize()
        smoke = "--smoke" in sys.argv[2:]
        res = bench_matfree(n=16 if smoke else 128,
                            reps=1 if smoke else 3, smoke=smoke)
        res["round"] = _round_stamp()
        res["extra"] = {
            "matrix_free_cycle_speedup":
                res["matrix_free_cycle_speedup"],
            "matrix_free_level_bytes_ratio":
                res["matrix_free_level_bytes_ratio"],
        }
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_matfree.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "matrix-free vs slab warm cycle speedup "
                      "(paired replay, GEO)",
            "value": res["matrix_free_cycle_speedup"],
            "unit": "x",
            "vs_baseline": 0.0,
            "artifact": "BENCH_matfree.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:2] == ["krylov"]:
        # standalone Krylov-shell phase: `python bench.py krylov`
        # (full: 128^3 paired replay, + northstar 256^3 on TPU) or
        # `--smoke` (16^3, the tier-1 functional check — must exit 0)
        amgx.initialize()
        smoke = "--smoke" in sys.argv[2:]
        res = bench_krylov(n=16 if smoke else 128,
                           reps=1 if smoke else 3, smoke=smoke)
        res["round"] = _round_stamp()
        res["extra"] = {
            "krylov_fused_speedup": res["krylov_fused_speedup"],
            "krylov_fused_passes": res["krylov_fused_passes"],
            "krylov_unfused_passes": res["krylov_unfused_passes"],
        }
        try:
            import os
            art = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_krylov.json")
            with open(art, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except Exception as e:  # pragma: no cover - bench robustness
            res["artifact_error"] = str(e)[:120]
        print(json.dumps({
            "metric": "fused vs unfused Krylov-shell warm solve "
                      "speedup (paired replay, PCG+AMG)",
            "value": res["krylov_fused_speedup"],
            "unit": "x",
            "vs_baseline": 0.0,
            "artifact": "BENCH_krylov.json",
            "extra": {k: v for k, v in res.items()
                      if not isinstance(v, (dict, list))},
        }), flush=True)
    elif sys.argv[1:] == ["resilience"]:
        # standalone smoke phase: `python bench.py resilience`
        amgx.initialize()
        res = bench_resilience()
        print(json.dumps({
            "metric": "resilience guarded-vs-unguarded CG iteration "
                      "overhead (poisson7pt 32^3)",
            "value": res["overhead_pct"],
            "unit": "pct",
            "vs_baseline": 0.0,
            "extra": res,
        }), flush=True)
    else:
        main()
