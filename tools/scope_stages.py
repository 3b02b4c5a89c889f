#!/usr/bin/env python
"""Device seconds of one traced benchmark run by STAGE of the cycle.

The benchmark's `scope` lines stop at the level (`amg.L0 kernels= glue=
ops=`). This runs the same traced run (`python3 -m benchmark.run ...
--trace 1`, nothing of it changed) and prints, beside them, one line
per scope of the program's table,

    stage amg.L0.prolong kernels=<s> glue=<s> ops=<n>

and writes `chiprun_out/scope_stages/<workload>.json`: per scope, each
instruction that ran in the window with its device seconds and its
op_name (the road from `solve_fn` down to the primitive), which is what
"what are the 73 instructions of L0" asks for.

Usage (on the chip, through the chip tool):
    python3 tools/scope_stages.py --workload flagship-p7-256.solve-stream \
        --seed 7 --seconds 40
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def stage_table(op_time: dict, scopes: dict, op_names: dict) -> dict:
    """{scope: {"kernels": s, "glue": s, "ops": {instruction:
    [seconds, op_name]}}} of one window's op times."""
    from benchmark import scope_metrics
    table = defaultdict(lambda: {"kernels": 0.0, "glue": 0.0, "ops": {}})
    for name, seconds in op_time.items():
        row = table[scopes.get(name) or "unscoped"]
        kind = "kernels" if scope_metrics._any(
            name, scope_metrics.KERNELS) else "glue"
        row[kind] += seconds
        row["ops"][name] = [seconds, op_names.get(name, "")]
    return dict(table)


def main(argv=None):
    from benchmark import run as bench_run
    from benchmark import scope_metrics
    argv = list(sys.argv[1:] if argv is None else argv)
    workload = argv[argv.index("--workload") + 1]
    inner = scope_metrics.split

    def split(op_time, scopes):
        from amgx_tpu.telemetry import programs
        table = stage_table(op_time, scopes, programs.op_names() or {})
        for scope in sorted(table):
            row = table[scope]
            print(f"stage {scope} kernels={row['kernels']:.6f} "
                  f"glue={row['glue']:.6f} ops={len(row['ops'])}",
                  flush=True)
        out = os.path.join(_ROOT, "chiprun_out", "scope_stages")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, workload + ".json"), "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        return inner(op_time, scopes)

    scope_metrics.split = split
    bench_run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    main()
