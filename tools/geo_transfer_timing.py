#!/usr/bin/env python
"""Bare device times of a GEO level's two transfers, form by form.

`restrict(r)` and `x + P xc` of the all-axes 2x2x2 pairing, alone, f32,
at the grids given (default 256^3, 128^3, 192^3), each in four forms:

    parent    the XLA form the cycle ran before PR 38: per axis two
              strided slices and an add (x, y, z: still the XLA road's
              restriction) / two interior pads and an add (z, y, x),
              then the correction's add
    reordered XLA, z first as a major-axis reshape, then y, the lane
              axis x last on the quarter-size array; the reverse for
              prolongation (x first on the coarse array, as pads)
    matmul    XLA, the lane axis as a 0/1 matrix at HIGHEST precision;
              its prolongation is amg/aggregation/transfer.py's XLA
              road as it stands (x through the matrix on the coarse
              array, y and z as broadcasts)
    onepass   ops/pallas_geo's kernels (declined grids are skipped)

Every form runs `--reps` times under one profiler trace; the device
seconds are the trace's busy time between the call's host annotations
(benchmark/trace_reduce.reduce: the reduction the benchmark uses), so
host dispatch is not in them. Beside the time: whether the f32 result
is bit-equal to the parent's, and its largest error against the f64 sum in
f32 ulps of the sum of the terms' magnitudes. One JSON line a (grid, op, form), all of
them also in `chiprun_out/geo_transfer_timing.json`.

A CPU run (`--interpret`, tiny grids) is the rehearsal of the control
flow: it has no device plane, and reports `device_ms` null.

Usage (on the chip):  python3 tools/geo_transfer_timing.py
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
from amgx_tpu.amg.aggregation import transfer as xfer  # noqa: E402
from amgx_tpu.ops import pallas_geo as pg  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

AXES = (0, 1, 2)
HIGHEST = jax.lax.Precision.HIGHEST


def _pair_matrix(e, dtype):
    """(e, e // 2) 0/1: column c sums rows 2c and 2c + 1."""
    return jnp.asarray(np.arange(e)[:, None] // 2
                       == np.arange(e // 2)[None, :], dtype)


def restrict_reordered(r, fs):
    nx, ny, nz = fs
    v = r.reshape(nz // 2, 2, ny, nx)
    v = v[:, 0] + v[:, 1]
    v = v[:, 0::2, :] + v[:, 1::2, :]
    return (v[:, :, 0::2] + v[:, :, 1::2]).reshape(-1)


def prolong_reordered(x, xc, fs):
    nx, ny, nz = fs
    v = xc.reshape(nz // 2, ny // 2, nx // 2)
    zero = jnp.zeros((), v.dtype)
    for dim, e in ((2, nx), (1, ny)):
        lo = [(0, 0, 0)] * 3
        hi = [(0, 0, 0)] * 3
        lo[dim], hi[dim] = (0, 1, 1), (1, 0, 1)
        v = jax.lax.pad(v, zero, lo) + jax.lax.pad(v, zero, hi)
    v = jnp.broadcast_to(v[:, None], (nz // 2, 2, ny, nx))
    return x + v.reshape(-1)


def restrict_matmul(r, fs):
    nx, ny, nz = fs
    v = jnp.dot(r.reshape(nz * ny, nx), _pair_matrix(nx, r.dtype),
                precision=HIGHEST).reshape(nz, ny, nx // 2)
    v = v[:, 0::2, :] + v[:, 1::2, :]
    return (v[0::2] + v[1::2]).reshape(-1)


def prolong_parent(x, xc, fs):
    shapes = [fs, (fs[0] // 2, fs[1], fs[2]),
              (fs[0] // 2, fs[1] // 2, fs[2]),
              (fs[0] // 2, fs[1] // 2, fs[2] // 2)]
    zero = jnp.zeros((), xc.dtype)
    for axis in (2, 1, 0):
        nx, ny, nz = shapes[axis + 1]
        lo = [(0, 0, 0)] * 3
        hi = [(0, 0, 0)] * 3
        lo[2 - axis], hi[2 - axis] = (0, 1, 1), (1, 0, 1)
        v = xc.reshape(nz, ny, nx)
        xc = (jax.lax.pad(v, zero, lo) + jax.lax.pad(v, zero, hi)).reshape(-1)
    return x + xc


def forms(fs, interpret):
    out = {
        "parent": (lambda r: xfer.restrict_xla(r, fs, AXES),
                   lambda x, xc: prolong_parent(x, xc, fs)),
        "reordered": (lambda r: restrict_reordered(r, fs),
                      lambda x, xc: prolong_reordered(x, xc, fs)),
        "matmul": (lambda r: restrict_matmul(r, fs),
                   lambda x, xc: x + xfer.prolongate_xla(xc, fs, AXES)),
    }
    if pg.geo_onepass_plan(fs, AXES) is not None:
        out["onepass"] = (
            lambda r: pg._dia_geo_restrict_call(r, fs, interpret=interpret),
            lambda x, xc: pg._dia_geo_prolong_call(x, xc, fs,
                                                   interpret=interpret))
    return out


def _ulps(got, want64, size64):
    """Largest error against the f64 result, in f32 ulps of the sum of
    the terms' magnitudes (a sum that cancels has no ulp of its own)."""
    ulp = np.spacing(size64.astype(np.float32)).astype(np.float64)
    return float(np.max(
        np.abs(np.asarray(got).astype(np.float64) - want64) / ulp))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--grids", default="256,128,192",
                    help="n of each n^3 grid, or nx:ny:nz")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--forms", default="parent,reordered,matmul,onepass")
    ap.add_argument("--interpret", action="store_true")
    a = ap.parse_args(argv)
    grids = [tuple(int(e) for e in g.split(":")) if ":" in g
             else (int(g),) * 3 for g in a.grids.split(",")]

    runs, checks = [], {}
    for fs in grids:
        n = fs[0] * fs[1] * fs[2]
        rng = np.random.default_rng(n)
        r = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        xc = jnp.asarray(rng.standard_normal(n // 8).astype(np.float32))
        r64, x64, xc64 = (np.asarray(v, np.float64) for v in (r, x, xc))

        def blocks(v):      # (coarse z, 2, coarse y, 2, coarse x, 2)
            return v.reshape(fs[2] // 2, 2, fs[1] // 2, 2, fs[0] // 2, 2)

        def spread(vc):
            return np.broadcast_to(
                vc.reshape(fs[2] // 2, 1, fs[1] // 2, 1, fs[0] // 2, 1),
                blocks(x64).shape).reshape(-1)

        want = {"restrict": blocks(r64).sum(axis=(1, 3, 5)).reshape(-1),
                "prolong": x64 + spread(xc64)}
        size = {"restrict": blocks(np.abs(r64)).sum(axis=(1, 3, 5))
                .reshape(-1),
                "prolong": np.abs(x64) + spread(np.abs(xc64))}
        parent = {}
        for form, (f_r, f_p) in forms(fs, a.interpret).items():
            if form not in a.forms.split(","):
                continue
            # x is donated, as the cycle's x is dead after the
            # correction: each call's result is the next call's x
            for op, fn, args in (
                    ("restrict", jax.jit(f_r), (r,)),
                    ("prolong", jax.jit(f_p, donate_argnums=0), (x, xc))):
                if op == "prolong":
                    args = (x + 0, xc)
                got = jax.block_until_ready(fn(*args))      # compiles
                if op == "prolong":
                    args = (x + 0, xc)
                if form == "parent":
                    parent[op] = got
                key = ("x".join(map(str, fs)), op, form)
                checks[key] = {
                    "bit_equal_parent": bool(jnp.array_equal(
                        got, parent[op])) if op in parent else None,
                    "ulps_of_f64_sum": _ulps(got, want[op], size[op])}
                runs.append((key, fn, args))

    tracedir = tempfile.mkdtemp(prefix="geo_timing_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for key, fn, args in runs:
                for _ in range(a.reps):
                    with jax.profiler.TraceAnnotation("/".join(key)):
                        out = jax.block_until_ready(fn(*args))
                    if key[1] == "prolong":
                        args = (out, args[1])
    finally:
        jax.profiler.stop_trace()
    pb = next((os.path.join(base, f) for base, _d, files in os.walk(tracedir)
               for f in files if f.endswith(".xplane.pb")), None)
    lines = []
    for key, _fn, _args in runs:
        red = trace_reduce.reduce(pb, "/".join(key)) if pb else {}
        busy = red.get("busy_s")
        ops = sorted(red.get("op_time", {}).items(), key=lambda kv: -kv[1])
        line = {"grid": key[0], "op": key[1], "form": key[2],
                "device_ms": None if busy is None
                else 1e3 * busy / a.reps,
                "instructions": len(ops),
                "longest": [[nm, 1e3 * t / a.reps] for nm, t in ops[:4]],
                **checks[key],
                "device": jax.devices()[0].device_kind}
        lines.append(line)
        print(json.dumps(line), flush=True)
    shutil.rmtree(tracedir, ignore_errors=True)
    out = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "geo_transfer_timing.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
