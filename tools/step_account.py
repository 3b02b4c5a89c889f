#!/usr/bin/env python
"""A time-step cell's re-setups step by step, and what carries a mode.

Runs the benchmark's own run of a time-step cell (`benchmark.run.run`,
nothing of it changed, untraced, the whole window) once per `--seed`,
all in this process, and reads beside it the program's account of each
re-setup (`amgx_tpu.telemetry.spans.resetup_rows()`). Per run it prints

    step <k> factor=<f> replace=<s> resetup=<s> call=<s> drain=<s>
         solve=<s> wait=<s> unnamed=<s> route=<r>
         top=<leaf>:<s>,<leaf>:<s>,<leaf>:<s>

one line a step (`resetup` is the benchmark's `bench.resetup`, `call` the
program's `Solver.resetup` inside it, `drain` what the caller waits for
the device after the call: the one less the other),
then `leaf <name> median=<s>` for every leaf and `under <span>` for the
spans whose self time is the unnamed part; and, where the sorted
re-setup walls have a gap wider than 10% of their median, the steps on
each side of the widest gap: how many, which, and per part (each leaf,
`unnamed`, `drain`) the median on each side and the difference, largest
first: the line that names what carries the mode.

With `--trace 1` the run is the benchmark's short traced window, and
the tool reads the trace itself before the benchmark reduces it:
device seconds by PROGRAM, from the `XLA Modules` line (one event a run
of a program, `jit_<name>(<fingerprint>)`), each with its three longest
instructions by containment in time.

Everything printed is also written, whole, to
`chiprun_out/step_account/<workload>.<seed>.<pid>.json`.

Usage (on the chip, through the chip tool):
    python3 tools/step_account.py --workload flagship-p7-128.time-step \
        --seconds 40 --seed 3800000021 3900000031
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from collections import defaultdict
from contextlib import contextmanager

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

GAP = 0.10          # of the median re-setup wall: what makes two modes


def step_table(bench, rows):
    """One dict a whole step, in order: the benchmark's walls (`bench`:
    (name, start, wall) of its spans, on the program's clock, in the
    order they closed) joined to the program's re-setup row that
    started inside the step's `bench.resetup`."""
    steps, cur = [], {}
    for name, t0, wall in bench:
        if name == "bench.step":        # closes after its three parts
            if {"replace", "resetup", "solve"} <= set(cur):
                steps.append(cur)
            cur = {}
            continue
        cur[name.split(".", 1)[1]] = wall
        if name == "bench.resetup":
            cur["row"] = next((r for r in rows
                               if t0 <= r["start"] <= t0 + wall), None)
    out = []
    for k, s in enumerate(steps):
        row = s["row"] or {"wall": float("nan"), "wait": float("nan"),
                           "unnamed": float("nan"), "route": None,
                           "leaves": {}, "under": {}}
        out.append({"step": k, "factor": None, "replace": s["replace"],
                    "resetup": s["resetup"], "call": row["wall"],
                    "drain": s["resetup"] - row["wall"],
                    "solve": s["solve"], "wait": row["wait"],
                    "unnamed": row["unnamed"], "route": row["route"],
                    "leaves": row["leaves"], "under": row["under"]})
    return out


def parts(step):
    """The parts a re-setup's wall is the sum of."""
    return dict(step["leaves"], unnamed=step["unnamed"],
                drain=step["drain"])


def modes(steps, gap=GAP):
    """Split the steps at the widest gap of their sorted re-setup
    walls, where that gap is wider than `gap` of the median; None where
    there is one mode. Returns {"gap", "fast", "slow" (step indices),
    "parts": [[name, median fast, median slow, difference], ...]
    largest difference first}."""
    if len(steps) < 2:
        return None
    by_wall = sorted(steps, key=lambda s: s["resetup"])
    walls = [s["resetup"] for s in by_wall]
    jumps = [b - a for a, b in zip(walls, walls[1:])]
    cut = max(range(len(jumps)), key=jumps.__getitem__)
    if jumps[cut] <= gap * statistics.median(walls):
        return None
    fast, slow = by_wall[:cut + 1], by_wall[cut + 1:]
    names = sorted({n for s in steps for n in parts(s)})

    def med(side, name):
        return statistics.median(parts(s).get(name, 0.0) for s in side)

    table = [[n, med(fast, n), med(slow, n)] for n in names]
    table = [row + [row[2] - row[1]] for row in table]
    table.sort(key=lambda row: -abs(row[3]))
    return {"gap": jumps[cut],
            "fast": sorted(s["step"] for s in fast),
            "slow": sorted(s["step"] for s in slow),
            "walls": [statistics.median(s["resetup"] for s in fast),
                      statistics.median(s["resetup"] for s in slow)],
            "parts": table}


def programs_table(path, window="bench.window", top=3):
    """Device seconds by program of one trace: {program: {"runs": n,
    "seconds": s, "ops": [[instruction, seconds], ...] its `top`
    longest}}, from the `XLA Modules` line (one event a run of a
    program) over the ops of `XLA Ops` that start inside each run; an
    op's seconds are its own (less what it holds). Inside the host's
    `window` annotation where the trace has one, else the whole trace."""
    import numpy as np
    from jax.profiler import ProfileData
    from benchmark import trace_reduce as tr
    data = ProfileData.from_file(path)
    w0, w1 = -float("inf"), float("inf")
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == window:
                    w0, w1 = e.start_ns, e.start_ns + e.duration_ns
    table = defaultdict(lambda: {"runs": 0, "seconds": 0.0,
                                 "ops": defaultdict(float)})
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        ms, md, mn = tr._line_arrays(lines["XLA Modules"])
        os_, od, on = tr._line_arrays(lines["XLA Ops"])
        order = np.argsort(ms)
        ms, md, mn = ms[order], md[order], [mn[i] for i in order]
        own = tr._self_time(os_, od)
        run = np.searchsorted(ms, os_, side="right") - 1
        for j in range(ms.size):
            if w0 <= ms[j] < w1:
                row = table[mn[j]]
                row["runs"] += 1
                row["seconds"] += md[j] * 1e-9
        for i, j in enumerate(run):
            if j >= 0 and os_[i] < ms[j] + md[j] and w0 <= ms[j] < w1:
                table[mn[j]]["ops"][tr.op_name(on[i])] += own[i] * 1e-9
    return {prog: {"runs": row["runs"], "seconds": row["seconds"],
                   "ops": sorted(row["ops"].items(),
                                 key=lambda kv: -kv[1])[:top]}
            for prog, row in table.items()}


def report(steps, out=print):
    for s in steps:
        top = sorted(s["leaves"].items(), key=lambda kv: -kv[1])[:3]
        out(f"step {s['step']} factor={s['factor']:.6f} "
            f"replace={s['replace']:.6f} resetup={s['resetup']:.6f} "
            f"call={s['call']:.6f} "
            f"drain={s['drain']:.6f} solve={s['solve']:.6f} "
            f"wait={s['wait']:.6f} unnamed={s['unnamed']:.6f} "
            f"route={s['route']} top="
            + ",".join(f"{n}:{v:.6f}" for n, v in top))
    if not steps:
        return None
    for label, key in (("leaf", "leaves"), ("under", "under")):
        names = {n for s in steps for n in s[key]}
        med = {n: statistics.median(s[key].get(n, 0.0) for s in steps)
               for n in names}
        for n in sorted(names, key=lambda n: -med[n]):
            out(f"{label} {n} median={med[n]:.6f}")
    def med(key):
        return statistics.median(s[key] for s in steps)

    share = sum(s["unnamed"] for s in steps) / sum(s["call"] for s in steps)
    out(f"account call={med('call'):.6f} drain={med('drain'):.6f} "
        f"wait={med('wait'):.6f} unnamed={med('unnamed'):.6f} "
        f"unnamed_share={share:.4f}")
    m = modes(steps)
    if m is None:
        out(f"modes: one (no gap wider than {GAP:.0%} of the median "
            f"among {len(steps)} re-setup walls)")
        return None
    out(f"modes: two, gap {m['gap']:.6f} s; fast n={len(m['fast'])} "
        f"median={m['walls'][0]:.6f} steps={m['fast']}; slow "
        f"n={len(m['slow'])} median={m['walls'][1]:.6f} steps={m['slow']}")
    for name, fast, slow, diff in m["parts"]:
        if abs(diff) >= 0.0005:
            out(f"mode part {name} fast={fast:.6f} slow={slow:.6f} "
                f"difference={diff:+.6f}")
    return m


def run_one(workload, seed, seconds, trace, out=print, **run_kw):
    """One run of the benchmark with the account read beside it;
    `run_kw` goes to `benchmark.run.run` (the tests' `devs`)."""
    from amgx_tpu.telemetry import spans as prog_spans
    from benchmark import run as bench_run
    from benchmark import trace_reduce, traffic

    bench, factors, programs = [], [], {}

    clock = getattr(prog_spans, "clock", time.perf_counter)

    class Spans(traffic.Spans):
        @contextmanager
        def span(self, name):
            t0 = clock()
            with super().span(name):
                yield
            bench.append((name, t0, self.walls[name][-1]))

    note = traffic._note

    def noted(entry, log, sample, op, rhs_i, factor):
        factors.append(factor)
        return note(entry, log, sample, op, rhs_i, factor)

    reduce = trace_reduce.reduce

    def reduced(path, *a, **kw):
        programs.update(programs_table(path))
        return reduce(path, *a, **kw)

    kept = traffic.Spans
    traffic.Spans, traffic._note, trace_reduce.reduce = Spans, noted, reduced
    try:
        since = clock()
        result = bench_run.run(workload, seed, seconds, bool(trace),
                               out=out, **run_kw)
    finally:
        traffic.Spans, traffic._note, trace_reduce.reduce = \
            kept, note, reduce
    # a tree from before the account has the benchmark's walls alone
    rows, wrapped = getattr(prog_spans, "resetup_rows",
                            lambda since: ([], False))(since)
    if wrapped:
        out("step_account: the span buffer wrapped inside this run: "
            "re-setups of its window are missing below")
    # the window's steps are the run's last: warm-up's come before
    n = result["attempted"]
    steps = step_table(bench, rows)[-n:]
    for k, (s, f) in enumerate(zip(steps, factors[-n:])):
        s["step"], s["factor"] = k, f
    mode = report(steps, out)
    ranked = sorted(programs.items(), key=lambda kv: -kv[1]["seconds"])
    for prog, row in ranked[:12]:
        out(f"program {prog} runs={row['runs']} seconds={row['seconds']:.6f} "
            + " ".join(f"{n}:{t:.6f}" for n, t in row["ops"]))
    return {"workload": workload, "seed": seed, "result": result,
            "wrapped": wrapped, "steps": steps, "modes": mode,
            "programs": dict(ranked)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    outdir = os.path.join(_ROOT, "chiprun_out", "step_account")
    os.makedirs(outdir, exist_ok=True)
    for seed in a.seed:
        print(f"step_account: {a.workload} seed {seed} pid {os.getpid()}",
              flush=True)
        doc = run_one(a.workload, seed, a.seconds, a.trace)
        with open(os.path.join(
                outdir, f"{a.workload}.{seed}.{os.getpid()}.json"),
                "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps(doc["result"]), flush=True)


if __name__ == "__main__":
    main()
