#!/usr/bin/env python
"""Bare device time of one fused DIA smoother stage, form by form.

One `_dia_smooth_call` alone, f32, on the 7-point operator of the
grids given (default 256^3, 128^3, 64^3), for each schedule (default
5 sweeps + residual and 5 sweeps: the flagship's Chebyshev pre- and
post-smoother) in both modes:

    mf      matrix-free: k coefficients in SMEM, masked value rows
            made in the kernel (`_dia_stencil_smooth_call`)
    slab    the quota-padded value slab streams from HBM, no dinv
            (the classical cells' Jacobi fine level adds a dinv slab:
            `--dinv`)

Every stage runs `--reps` times under one profiler trace; the device
seconds are the trace's busy time between the call's host annotations
(benchmark/trace_reduce.reduce: the reduction the benchmark uses), so
host dispatch is not in them. Beside the time: the plan, GB/s by the
bytes the shapes say a stage has to move (x and b read once, x' and r
written once, the slab's k (+1) streams read once), the calls and
row-applications the plan counts, and the largest difference from the
XLA compose of the same sweeps (`ops.stencil._xla_smooth`) relative to
the largest entry. One JSON line a (grid, mode, schedule), all of them
also in `chiprun_out/dia_smooth_timing.json`.

A CPU run (`--interpret`, tiny grids) is the rehearsal of the control
flow: it has no device plane, and reports `device_ms` null.

Usage (on the chip):  python3 tools/dia_smooth_timing.py
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import amgx_tpu  # noqa: E402,F401  (x64 on, as the program runs)
from amgx_tpu.ops import pallas_spmv as ps  # noqa: E402
from amgx_tpu.ops import stencil  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

F32 = jnp.float32


def spec7(fs, dinv=None):
    """StencilSpec of the constant-coefficient 7-point operator."""
    nx, ny, nz = fs
    shifts = ((0, 0, -1), (0, -1, 0), (-1, 0, 0), (0, 0, 0),
              (1, 0, 0), (0, 1, 0), (0, 0, 1))
    offs = tuple(dx + nx * dy + nx * ny * dz for dx, dy, dz in shifts)
    return stencil.StencilSpec(offs, shifts, fs, nx * ny * nz, dinv, 3)


def quota_slabs(spec, coeffs, with_dinv):
    """The quota-padded (vals_q, dinv_q) of ops.smooth.build_fused_slabs
    made from the stencil, without a host matrix."""
    qf, qc, qb = ps.smooth_quota_rows(spec.offsets, spec.n)
    idx = jnp.arange(qc * ps.LANES, dtype=jnp.int32)
    coords = ps._mf_coords(spec.shape, idx)
    valid = idx < spec.n
    rows = [jnp.where(ps._mf_ok(spec.shape, coords, sh, valid),
                      coeffs[t], jnp.zeros((), F32))
            for t, sh in enumerate(spec.shifts)]
    vals = jnp.stack(rows).reshape(len(rows), qc, ps.LANES)
    vals_q = jnp.pad(vals, ((0, 0), (qf, qb), (0, 0)))
    dinv_q = None
    if with_dinv:
        d = jnp.where(valid, 1 / coeffs[spec.diag_rank],
                      jnp.zeros((), F32)).reshape(qc, ps.LANES)
        dinv_q = jnp.pad(d, ((qf, qb), (0, 0)))
    return vals_q, dinv_q


def stage_bytes(spec, mode, with_residual, with_dinv):
    """Bytes a stage has to move, from shapes: every stream once."""
    streams = 2 + (2 if with_residual else 1)
    if mode == "slab":
        streams += len(spec.offsets) + (1 if with_dinv else 0)
    return streams * spec.n * 4


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--grids", default="256,128,64",
                    help="n of each n^3 grid, or nx:ny:nz")
    ap.add_argument("--schedules", default="5r,5",
                    help="sweeps, with r where the residual rides")
    ap.add_argument("--modes", default="mf,slab")
    ap.add_argument("--dinv", action="store_true",
                    help="Jacobi: a dinv slab / the mf jacobi diagonal")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--interpret", action="store_true")
    a = ap.parse_args(argv)
    grids = [tuple(int(e) for e in g.split(":")) if ":" in g
             else (int(g),) * 3 for g in a.grids.split(",")]
    schedules = [(int(s.rstrip("r")), s.endswith("r"))
                 for s in a.schedules.split(",")]
    coeffs = jnp.asarray([-1, -1, -1, 6, -1, -1, -1], F32)

    runs, static = [], {}
    for fs in grids:
        n = fs[0] * fs[1] * fs[2]
        rng = np.random.default_rng(n)
        b = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        spec = spec7(fs, "jacobi" if a.dinv else None)
        for mode in a.modes.split(","):
            slabs = quota_slabs(spec, coeffs, a.dinv) \
                if mode == "slab" else None
            for ns, wr in schedules:
                taus = jnp.asarray(
                    rng.uniform(0.05, 0.15, ns).astype(np.float32))
                plan = ps.dia_smooth_plan(
                    spec.offsets, len(spec.offsets), n, ns, wr,
                    coeffs=mode == "mf")
                key = ("x".join(map(str, fs)), mode,
                       f"{ns}{'r' if wr else ''}")
                if plan is None:
                    print(json.dumps({"key": key, "plan": None}),
                          flush=True)
                    continue
                if mode == "mf":
                    fn = jax.jit(lambda c, t, b_, x_, wr=wr, spec=spec:
                                 ps._dia_stencil_smooth_call(
                                     c, t, b_, x_, spec, wr,
                                     interpret=a.interpret))
                    args = (coeffs, taus, b, x)
                else:
                    fn = jax.jit(lambda v, d, t, b_, x_, wr=wr, spec=spec:
                                 ps._dia_smooth_call(
                                     v, d, t, b_, x_, spec.offsets,
                                     spec.n, wr, interpret=a.interpret))
                    args = (slabs[0], slabs[1], taus, b, x)
                got = jax.block_until_ready(fn(*args))      # compiles
                want = jax.jit(lambda c, t, b_, x_, wr=wr, spec=spec:
                               stencil._xla_smooth(spec, c, t, b_, x_, wr)
                               )(coeffs, taus, b, x)
                got, want = (got, want) if wr else ((got,), (want,))
                static[key] = {
                    "plan": plan._asdict(), "steps": plan.steps,
                    "row_apps": plan.row_apps,
                    "bytes": stage_bytes(spec, mode, wr, a.dinv),
                    "rel_diff_xla": [
                        float(jnp.max(jnp.abs(g - w))
                              / jnp.max(jnp.abs(w)))
                        for g, w in zip(got, want)]}
                runs.append((key, fn, args))

    tracedir = tempfile.mkdtemp(prefix="dia_smooth_timing_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for key, fn, args in runs:
                for _ in range(a.reps):
                    with jax.profiler.TraceAnnotation("/".join(key)):
                        jax.block_until_ready(fn(*args))
    finally:
        jax.profiler.stop_trace()
    pb = next((os.path.join(base, f) for base, _d, files in os.walk(tracedir)
               for f in files if f.endswith(".xplane.pb")), None)
    lines = []
    for key, _fn, _args in runs:
        red = trace_reduce.reduce(pb, "/".join(key)) if pb else {}
        busy = red.get("busy_s")
        ops = sorted(red.get("op_time", {}).items(), key=lambda kv: -kv[1])
        ms = None if busy is None else 1e3 * busy / a.reps
        line = {"grid": key[0], "mode": key[1], "schedule": key[2],
                "device_ms": ms,
                "gb_per_s": None if not ms
                else static[key]["bytes"] / ms / 1e6,
                "instructions": len(ops),
                "longest": [[nm, 1e3 * t / a.reps] for nm, t in ops[:4]],
                **static[key],
                "device": jax.devices()[0].device_kind}
        lines.append(line)
        print(json.dumps(line), flush=True)
    shutil.rmtree(tracedir, ignore_errors=True)
    out = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "dia_smooth_timing.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
