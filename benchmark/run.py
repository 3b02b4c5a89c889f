"""One cell, once: `python3 -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`, from the root of a checkout.

One process. Refuses anything but a TPU (and fewer chips than the cell
asks for) before anything is built: exit code 3 and no result line.
Set-up (everything up to the opening of the window) is reported as
`setup_s`; the window then drives the cell's traffic for `--seconds`;
the check runs after the window has closed. The last line of standard
output is the result object; the lines before it say where the set-up
time went, each number the check compared beside its limit, and in a
traced run the probe. What the check compared, in sum, is also the
result's last key, `compared`, and the last lines of standard error.

Everything that belongs to one cell is data found by name from
BENCHMARK.json: `configs/<config>.json`, `traffic/<traffic>.json`,
`end_to_end/<metric>.json`, `layer_metrics/<metric>.json`. The code a
configuration or a traffic file names (its entry, its operator's
generator, its traffic kind, its control) is found by `named`: in the
table that is there, or in the module of the benchmark package that the
file gives beside the name.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, as near as Python gets

import argparse                 # noqa: E402
import gc                       # noqa: E402
import importlib                # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import statistics               # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP = 3


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_cell(workload: str):
    """(cell, configuration, traffic, BENCHMARK.json) by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    return (cell, load_json("configs", cell["config"] + ".json"),
            load_json("traffic", cell["traffic"] + ".json"), bench)


def named(what: str, name: str, module, table: dict):
    """What a data file names as its `what`: `table[name]`, or, where
    the file gives `module` beside the name, that attribute of the
    module. The module lies in the benchmark package, so that the
    yardstick stays under BENCHMARK.json's `paths`. Anything else is a
    SystemExit that says what was looked for and what is known."""
    if module is None:
        if name not in table:
            raise SystemExit(
                f"benchmark: no {what} {name!r}; known: {sorted(table)} "
                f"(one of its own comes with its module named beside it)")
        return table[name]
    if not str(module).startswith("benchmark."):
        raise SystemExit(
            f"benchmark: {what} {name!r} names module {module!r}, which "
            f"is outside the benchmark package (benchmark.<module>)")
    try:
        mod = importlib.import_module(module)
    except ImportError as e:
        raise SystemExit(f"benchmark: {what} {name!r} names module "
                         f"{module!r}, which does not import: {e!r}")
    if not hasattr(mod, name):
        known = sorted(k for k in vars(mod) if not k.startswith("_"))
        raise SystemExit(f"benchmark: module {module!r} has no {what} "
                         f"{name!r}; it has: {known}")
    return getattr(mod, name)


def entry_of(config: dict, cell: dict = None):
    """The class a configuration names as its entry. One that runs on
    as many chips as its own configuration says names that key of
    `solver` as `chips_key`, and a cell has to ask for as many."""
    from .entries import ENTRIES
    entry = named("entry", config["entry"], config.get("entry_module"),
                  ENTRIES)
    key = getattr(entry, "chips_key", None)
    if cell is not None and key is not None \
            and int(config["solver"][key]) != int(cell["chips"]):
        raise SystemExit(
            f"benchmark: cell {cell['name']!r} asks for {cell['chips']} "
            f"chip(s), entry {config['entry']!r} runs on solver.{key} = "
            f"{config['solver'][key]} of configuration {cell['config']!r}")
    return entry


def generator_of(operator: dict):
    """`fn(operator, seed) -> CSR arrays` of a configuration's operator
    block: `operator_host.stencil` where it gives a `stencil`, else the
    `generator` of the `module` it names."""
    from . import operator_host
    if "generator" in operator:
        return named("operator generator", operator["generator"],
                     operator.get("module"), {})
    named("stencil", operator["stencil"], None, operator_host.STENCILS)
    return operator_host.stencil


def kind_of(traffic_spec: dict):
    """(loop, the span that is one operation of it) of a traffic file."""
    from . import traffic
    return named("traffic kind", traffic_spec["kind"],
                 traffic_spec.get("module"), traffic.KINDS)


def reported_here(metric: dict, cell: str, e2e_here=None):
    """Does this cell report the metric? By its `workloads` key, or,
    for a per-layer metric without one, wherever the end-to-end metric
    it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_here is None:
        return True
    return metric["moves"] in e2e_here


def require_chips(chips: int):
    """The devices as JAX reports them, or exit: this benchmark does
    not run on a CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); jax reports "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return devs


def compile_cache():
    """JAX's persistent cache at a fixed place, every program in it
    (the eager set-up programs compile in well under a second each and
    JAX's defaults would leave them out): where
    JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache."""
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


STATISTICS = {
    "first": lambda walls: walls[0],
    "median": statistics.median,
    "mean": statistics.fmean,
    # nearest rank
    "p95": lambda walls: sorted(walls)[math.ceil(0.95 * len(walls)) - 1],
}


def statistic(kind: str, walls, setup_s: float):
    """An end-to-end metric's value from its span's walls; `setup` is
    the set-up time itself. None where the span never ran."""
    if kind == "setup":
        return setup_s
    return STATISTICS[kind](walls) if walls else None


def memory_peak_bytes(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(workload: str, seed: int, seconds: float, trace: bool,
        make_entry=None, devs=None, out=print) -> dict:
    """Set-up, window, check; returns the result object. `make_entry`
    puts another entry in the program's place (control.py, the tests)
    and `devs` skips the look for a chip (the tests)."""
    cell, config, traffic_spec, bench = find_cell(workload)
    # what the files name, or exit: before any device call
    entry_class = entry_of(config, cell)
    generator = generator_of(config["operator"])
    loop, op_span = kind_of(traffic_spec)
    if devs is None:
        devs = require_chips(int(cell["chips"]))
    import jax
    import numpy as np
    out(f"compile cache: {compile_cache()}")
    phases, last = [], [T0]

    def mark(what):
        now = time.perf_counter()
        phases.append((what, now - last[0]))
        last[0] = now

    mark("start to devices")

    from . import layer_metrics, reference, trace_reduce, traffic
    from .probe import fine_spmv_probe

    op = config["operator"]
    host_op = generator(op, seed)
    mark("host operator")
    inputs = traffic.Inputs(seed, traffic_spec, host_op[0].shape[0] - 1)
    mark("right-hand sides")
    if make_entry is None:
        entry = entry_class(config["solver"], op)
    else:
        entry = make_entry(config)

    # the program's own instruments, where the entry is the program
    try:
        from amgx_tpu.telemetry import metrics as prog_counters
        from amgx_tpu.telemetry import spans as prog_spans
    except ImportError:
        if make_entry is None:
            raise
        prog_counters = prog_spans = None

    def counters():
        if prog_counters is None:
            return {}
        return {k: v for k, v in prog_counters.snapshot().items()
                if isinstance(v, (int, float))}

    def timers():
        if prog_spans is None:
            return {}
        return {k: tot for k, (_c, tot) in prog_spans.flat_timers().items()}

    obs = layer_metrics.Observed(
        peaks=_peaks(devs[0].device_kind))
    spans = traffic.Spans()
    try:
        entry.upload(*host_op, inputs.rhs)
        mark("operator upload")
        before = timers()
        with spans.span("bench.amg_setup"):
            entry.setup()
        after = timers()
        obs.setup_timers = {k: v - before.get(k, 0.0)
                            for k, v in after.items()}
        mark("Solver.setup")
        warm = int(traffic_spec["warm_ops"])
        warm_spans = traffic.Spans()
        loop(entry, traffic_spec, inputs, warm_spans,
             traffic.Window(0.0, warm), base_vals=host_op[2])
        op_s = warm_spans.walls[op_span][-1]     # the last: compiled
        gc.collect()
        gc.freeze()
        mark("warm-up")
        setup_s = time.perf_counter() - T0
        for what, took in phases:
            out(f"set-up: {what} {took:.2f} s")

        if trace:
            tracedir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tracedir, profiler_options=opts)
            window = traffic.Window(float(traffic_spec["traced_seconds"]),
                                    int(traffic_spec["traced_min_ops"]),
                                    op_s)
        else:
            window = traffic.Window(seconds, op_s=op_s)
        try:
            before = counters()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                log, sample = loop(entry, traffic_spec, inputs, spans,
                                   window, base_vals=host_op[2],
                                   first_op=warm)
            obs.counter_growth = {k: v - before.get(k, 0)
                                  for k, v in counters().items()}
            if trace:
                obs.probe = fine_spmv_probe(entry.solver_tree()) or {}
        finally:
            if trace:
                # the trace is reduced and thrown away here, whatever
                # the check does afterwards
                try:
                    jax.profiler.stop_trace()
                    pb = _find_xplane(tracedir)
                    obs.trace = trace_reduce.reduce(pb, op_span) if pb else {}
                finally:
                    shutil.rmtree(tracedir, ignore_errors=True)
        peak = memory_peak_bytes(devs)
        obs.ops = len(log)
        obs.spans = dict(spans.walls)
        obs.iterations = [r["iterations"] for r in log]
        for name, walls in sorted(spans.walls.items()):
            if name != "bench.amg_setup":
                out(f"window: {name} n={len(walls)} "
                    f"median={statistics.median(walls):.6f} "
                    f"mean={statistics.fmean(walls):.6f} "
                    f"min={min(walls):.6f} max={max(walls):.6f} s")

        checked, failed, compared = reference.decide(
            sample.records(), log, host_op, inputs, np.dtype(op["dtype"]),
            entry.vector_dtype, config["guarantees"], out=out)
    finally:
        entry.close()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(checked and not failed),
              "attempted": len(log), "failed": failed}
    metrics = {}
    e2e_here = [m for m in bench["end_to_end"]
                if reported_here(m, cell["name"])]
    if not trace:
        for m in e2e_here:
            spec = load_json("end_to_end", m["name"] + ".json")
            value = statistic(spec["statistic"],
                              spans.walls.get(spec.get("span")), setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        if obs.probe and obs.trace.get("probe"):
            obs.probe.update(obs.trace["probe"])
            rate = obs.probe["bytes"] / obs.probe["device_s_per_call"]
            out("probe fine_spmv " + " ".join(
                f"{k}={v}" for k, v in obs.probe.items())
                + f" bytes_per_s={rate:.4e}")
        names = [m["name"] for m in e2e_here]
        for m in bench["per_layer"]:
            if not reported_here(m, cell["name"], names):
                continue
            value = layer_metrics.read(m["name"], obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if obs.trace.get("busy_s"):
            device["busy_s"] = obs.trace["busy_s"]
            device["window_s"] = obs.trace["window_s"]
            result["breakdown"] = trace_reduce.breakdown(obs.trace)
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = compared       # last, as the contract has it
    return result


def say(result: dict):
    """The result line, last on standard output; each number the check
    compared beside its limit, last on standard error."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']:.6e} limit {c['limit']:.1e}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def _peaks(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} "
                         f"in benchmark/peaks.json")
    return table[kind]


def _find_xplane(tracedir):
    for base, _dirs, files in os.walk(tracedir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    say(run(a.workload, a.seed, a.seconds, bool(a.trace)))


if __name__ == "__main__":
    main()
