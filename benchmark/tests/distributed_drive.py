"""`python3 -m benchmark.tests.distributed_drive`, in a process that
has four devices (test_by_module.py starts it with the CPU backend's
`--xla_force_host_platform_device_count=4`): the distributed C API
entry through the harness at 8x8x16, and its x beside the `capi`
entry's. Prints one JSON line.
"""
from __future__ import annotations

import json

import numpy as np

GRID = [8, 8, 16]
CONFIG = {
    "operator": {"stencil": "7pt", "grid": GRID, "dtype": "float64"},
    "entry": "CApiDistributedEntry",
    "entry_module": "benchmark.entry_capi_distributed",
    "solver": {
        "mode": "dDDI", "ranks": 4,
        "json": {"config_version": 2, "solver": {
            "scope": "s", "solver": "FGMRES", "max_iters": 200,
            "tolerance": 1e-13, "convergence": "RELATIVE_INI",
            "gmres_n_restart": 20, "monitor_residual": 1,
            "preconditioner": {
                "scope": "amg", "solver": "AMG",
                "algorithm": "AGGREGATION", "selector": "SIZE_2",
                "smoother": "JACOBI_L1", "max_iters": 1,
                "coarse_solver": "DENSE_LU_SOLVER",
                "min_coarse_rows": 16}}}},
    "guarantees": {"status": "success", "true_relative_residual": 1e-8},
}
CELL = {"name": "dist.solve-stream", "config": "dist",
        "traffic": "solve-stream", "chips": 4}


def main():
    import jax
    from benchmark import entries, run
    from benchmark.entry_capi_distributed import CApiDistributedEntry
    from benchmark.probe import fine_spmv_probe
    devs = jax.devices()
    out = {"devices": len(devs), "ranks": CONFIG["solver"]["ranks"]}
    find = run.find_cell
    spec = dict(run.load_json("traffic", "solve-stream.json"), rhs=2)
    bench = find("flagship-p7-128.solve-stream")[3]
    run.find_cell = lambda workload: (CELL, CONFIG, spec, bench)
    run._peaks = lambda kind: {}
    lines = []
    result = run.run(CELL["name"], seed=2147483901, seconds=0.3,
                     trace=False, devs=devs[:4], out=lines.append)
    out.update(correct=result["correct"], failed=result["failed"],
               attempted=result["attempted"],
               device_count=result["device"]["count"])

    host_op = run.generator_of(CONFIG["operator"])(CONFIG["operator"], 0)
    b = np.random.default_rng(3).standard_normal(host_op[0].shape[0] - 1)
    xs = []
    for cls in (CApiDistributedEntry, entries.CApiEntry):
        entry = cls(CONFIG["solver"], CONFIG["operator"])
        try:
            entry.upload(*host_op, [b])
            entry.setup()
            entry.solve(0)
            xs.append(np.asarray(entry.last().x, np.float64))
            if cls is CApiDistributedEntry:
                tree = entry.solver_tree()
                out["solver"] = type(tree).__name__
                out["probe"] = fine_spmv_probe(tree)
                try:
                    entry.resetup()
                except NotImplementedError as e:
                    out["replace"] = str(e)
        finally:
            entry.close()
        out["handles_left"] = len(entry.handles)
    out["x_rel_diff"] = float(np.linalg.norm(xs[0] - xs[1])
                              / np.linalg.norm(xs[1]))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
