"""`python3 -m benchmark.selfcheck`: the yardstick checks itself, on a
CPU, with no chip.

- BENCHMARK.json and the data files agree: every configuration,
  traffic mix and metric it names has its file, every reader names a
  reduction that exists and moves what BENCHMARK.json says, every
  per-layer metric moves an end-to-end metric that each of its cells
  reports.
- Every name and module that every file under `configs/` and
  `traffic/` gives resolves (entry, operator generator, traffic kind,
  control), each cell's chips are its entry's, and no module that makes
  an operator or stands in as a control holds `jax` or `amgx_tpu` once
  a fresh interpreter has imported it: the reference imports nothing of
  the program.
- The trace reduction gives known numbers on a small recorded trace
  (data/fine_spmv_probe.xplane.pb: 20 calls of the 128^3 fine-level
  DIA SpMV on a TPU v5e, recorded by PR 28's exploration run).
- The host operator is the 7-point Poisson matrix it says it is.
"""
from __future__ import annotations

import glob
import importlib
import json
import os
import subprocess
import sys

import numpy as np

from . import layer_metrics, probe, run, trace_reduce
from .operator_host import poisson_csr


def check_files():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in bench["configs"]:
        cfg = run.load_json("configs", c["name"] + ".json")
        assert c["file"] == f"benchmark/configs/{c['name']}.json", c
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert cfg["guarantees"]["true_relative_residual"] > 0
    for w in cells.values():
        run.kind_of(run.load_json("traffic", w["traffic"] + ".json"))
        run.entry_of(run.load_json("configs", w["config"] + ".json"), w)
    for m in e2e.values():
        spec = run.load_json("end_to_end", m["name"] + ".json")
        assert run.statistic(spec["statistic"], [1.0, 2.0], 3.0) > 0
    for m in bench["per_layer"]:
        spec = layer_metrics.load(m["name"])
        module = importlib.import_module(
            spec.get("module", "benchmark.layer_metrics"))
        assert callable(getattr(module, spec["reduction"])), m["name"]
        assert spec["moves"] == m["moves"], m["name"]
        for cell in m.get("workloads", cells):
            assert run.reported_here(e2e[m["moves"]], cell), (
                f"{m['name']} moves {m['moves']}, which {cell} does not "
                f"report")
    for cell in cells:
        here = [m["name"] for m in e2e.values()
                if run.reported_here(m, cell)]
        assert "setup_s" in here and len(here) >= 2, cell
        assert any(run.reported_here(m, cell, here)
                   for m in bench["per_layer"]), cell
    return len(cells), len(e2e), len(bench["per_layer"])


def imports_of(module: str) -> list:
    """Which of jax, jaxlib and amgx_tpu a fresh interpreter holds
    after importing `module`."""
    code = ("import importlib, sys; importlib.import_module(sys.argv[1]); "
            "print(*sorted({m.split('.')[0] for m in sys.modules} "
            "& {'jax', 'jaxlib', 'amgx_tpu'}))")
    done = subprocess.run([sys.executable, "-c", code, module],
                          cwd=run.ROOT, capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout.split()


def check_names():
    """Resolves what every configuration and traffic file names, held
    by a cell or not (`check_files` holds each cell's chips to its
    entry's), and holds the modules of operators and controls to the
    reference's rule."""
    from . import control
    clean = {"benchmark.operator_host", "benchmark.reference"}
    configs = sorted(glob.glob(os.path.join(run.HERE, "configs", "*.json")))
    for path in configs:
        config = run.load_json(path)
        run.entry_of(config)
        run.generator_of(config["operator"])
        control.control_class(config)
        for block in (config["operator"], config["control"]):
            clean.add(block.get("module", "benchmark.reference"))
    specs = sorted(glob.glob(os.path.join(run.HERE, "traffic", "*.json")))
    for path in specs:
        run.kind_of(run.load_json(path))
    for module in sorted(clean):
        held = imports_of(module)
        assert not held, f"{module} imports {held}"
    return {"configs": len(configs), "traffic": len(specs),
            "clean": sorted(clean)}


def check_trace():
    path = os.path.join(run.HERE, "data", "fine_spmv_probe.xplane.pb")
    # the recording predates bench.window: its 20 bench.probe spans hold
    # one jitted SpMV each, so the first stands in as the window and the
    # PjitFunction(spmv) call inside it as the operation
    r = trace_reduce.reduce(path, "PjitFunction(spmv)",
                            window="bench.probe", probe="bench.probe")
    assert r["devices"] == 1 and r["traced_ops"] == 2, r
    assert r["n_ops"] == 2, r["n_ops"]
    assert sorted(r["op_time"]) == ["_dia_spmv_call.1",
                                    "pad_bitcast_fusion"], r["op_time"]
    assert abs(r["op_time"]["_dia_spmv_call.1"] - 93.910e-6) < 1e-9
    assert abs(r["probe"]["device_s_per_call"] - 105.890e-6) < 1e-9
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_gaps"][0][0] == "PjitFunction(spmv)"
    # the probe's bytes for that call, from shapes: 7 diagonals of
    # 16384x128 f32, x and y of 128^3 f32
    n = 128 ** 3
    nbytes = probe.dia_spmv_bytes(np.empty((7, 16384, 128), np.float32),
                                  np.empty(n, np.float32),
                                  np.empty(n, np.float32))
    obs = layer_metrics.Observed(
        probe=dict(bytes=nbytes, **r["probe"]),
        peaks=run._peaks("TPU v5 lite"))
    share = layer_metrics.probe_hbm_share(obs)
    assert 86.0 < share < 88.0, share
    return r["n_ops"], share


def check_operator():
    ro, ci, vals = poisson_csr("7pt", (4, 3, 2))
    n = 24
    dense = np.zeros((n, n))
    for i in range(n):
        dense[i, ci[ro[i]:ro[i + 1]]] = vals[ro[i]:ro[i + 1]]
    want = np.zeros((n, n))
    for z in range(2):
        for y in range(3):
            for x in range(4):
                i = (z * 3 + y) * 4 + x
                want[i, i] = 6.0
                for dx, dy, dz in ((1, 0, 0), (-1, 0, 0), (0, 1, 0),
                                   (0, -1, 0), (0, 0, 1), (0, 0, -1)):
                    if 0 <= x + dx < 4 and 0 <= y + dy < 3 \
                            and 0 <= z + dz < 2:
                        want[i, ((z + dz) * 3 + y + dy) * 4 + x + dx] = -1.0
    assert np.array_equal(dense, want)
    assert all(np.all(np.diff(ci[ro[i]:ro[i + 1]]) > 0) for i in range(n))
    return n


def main():
    cells, e2e, layer = check_files()
    print(f"files: {cells} cells, {e2e} end-to-end and {layer} per-layer "
          f"metrics agree with their data files")
    named = check_names()
    print(f"names: {named['configs']} configurations and "
          f"{named['traffic']} traffic files name parts that resolve; "
          f"{len(named['clean'])} reference modules import neither jax "
          f"nor the program")
    n_ops, share = check_trace()
    print(f"trace: recorded probe reduces to {n_ops} device ops and "
          f"{share:.2f}% of HBM peak")
    print(f"operator: 7pt on 4x3x2 matches the definition "
          f"({check_operator()} rows)")
    print("selfcheck ok")


if __name__ == "__main__":
    sys.exit(main())
