"""The step account (PR 43): a re-setup closes its own books.

- the counters `resetup.call_s`, `resetup.device_wait_s`,
  `resetup.unnamed_s`, `amg.resetup.selector_s` grow by one call's worth
  a `resetup` and by nothing a `solve`, and the leaves and the unnamed
  part add to the call, on the flagship and on the classical preset;
- `spans.resetup_rows()` returns one row a re-setup, with the route the
  flight recorder names, and says when the buffer wrapped;
- a steady `solve` touches the span names and counters it touched at
  the parent commit, and no other: the solve path gained nothing;
- the reader files return None on an empty `Observed`, and the
  drain is the benchmark's span less the program's call;
- `tools/step_account.py`: the join of the benchmark's walls with the
  program's rows, the split into modes, the programs of a recorded
  trace, and one run of a time-step cell at 16^3.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import threading

import jax
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu.config import Config
from amgx_tpu.telemetry import flightrec, metrics, spans
from benchmark import layer_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ACCOUNT = ("resetup.call_s", "resetup.device_wait_s", "resetup.unnamed_s",
           "amg.resetup.selector_s")
READERS = ("step.resetup_call_s", "step.resetup_drain_s",
           "step.resetup_wait_s", "step.resetup_unnamed_s",
           "step.selector_s", "step.slab_row_sums")


def _config(which):
    if which == "classical":
        return Config.from_file(os.path.join(
            REPO, "configs", "PCG_CLASSICAL_V_JACOBI.json"))
    with open(os.path.join(REPO, "benchmark", "configs",
                           which + ".json")) as f:
        return Config.from_string(json.load(f)["solver"]["options"])


# preset -> (the route its resetup takes, does that route run a selector)
PRESETS = {"flagship-p7-128": ("full", True),
           "flagship-reuse-p7-256": ("value", False),
           "classical": ("full", False)}


class Loop:
    """A set-up solver at 12^3 that has solved, re-set-up and solved
    once, so that what follows is steady."""

    def __init__(self, which):
        self.A = amgx.gallery.poisson("7pt", 12, 12, 12).init()
        self.slv = amgx.create_solver(_config(which))
        self.slv.setup(self.A)
        self.b = jax.numpy.ones(self.A.num_rows)
        self.slv.solve(self.b)
        self.resetup()
        self.slv.solve(self.b)

    def resetup(self):
        self.A = self.A.with_values(np.asarray(self.A.values) * 1.25)
        if not self.A.initialized:
            self.A = self.A.init()
        self.slv.resetup(self.A)


@pytest.fixture(scope="module", params=sorted(PRESETS))
def loop(request):
    return request.param, Loop(request.param)


def _counters():
    return {k: v for k, v in metrics.snapshot().items()
            if isinstance(v, (int, float))}


def _grown(before):
    after = _counters()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def test_counters_grow_by_one_call_a_resetup(loop):
    which, lp = loop
    since, before = spans.clock(), _counters()
    lp.resetup()
    grew = _grown(before)
    (row,), wrapped = spans.resetup_rows(since)
    assert not wrapped
    assert grew["resetup.call_s"] == pytest.approx(row["wall"], rel=1e-9)
    assert grew.get("resetup.device_wait_s", 0.0) == \
        pytest.approx(row["wait"], abs=1e-12)
    assert grew["resetup.unnamed_s"] == \
        pytest.approx(row["unnamed"], rel=1e-9)
    selector = sum(s for n, s in row["leaves"].items()
                   if n.endswith(".selector"))
    assert grew.get("amg.resetup.selector_s", 0.0) == \
        pytest.approx(selector, abs=1e-12)
    assert (selector > 0.0) == PRESETS[which][1]
    assert 0.0 <= row["wait"] <= row["wall"]


def test_leaves_and_unnamed_add_to_the_call(loop):
    _which, lp = loop
    since = spans.clock()
    lp.resetup()
    (row,), _ = spans.resetup_rows(since)
    assert row["leaves"]["solver.release_operator"] > 0.0
    assert set(row["under"]) <= {"REFINEMENT.resetup", "FGMRES.resetup",
                                 "AMG.resetup", "PCG.resetup"}
    assert sum(row["leaves"].values()) + row["unnamed"] == \
        pytest.approx(row["wall"], rel=0.01)
    assert sum(row["under"].values()) == pytest.approx(row["unnamed"])
    assert row["wait"] == pytest.approx(sum(
        s for n, s in row["leaves"].items() if n in spans.WAIT_SPANS))
    # the same division from the records themselves: the self times of
    # everything that closed inside the outermost re-setup's span
    recs = [r for r in spans.records() if r["ts"] >= since
            and r.get("ph") != "i"]
    root = max(recs, key=lambda r: r["dur"])
    assert root["name"].endswith(".resetup") and "account" in root
    inside = [r for r in recs if r["tid"] == root["tid"]
              and root["ts"] <= r["ts"] <= root["ts"] + root["dur"]]
    assert sum(r["self"] for r in inside) == \
        pytest.approx(root["dur"], rel=1e-6)
    nested = [r for r in inside if r is not root
              and r["name"].endswith(".resetup")]
    assert all("account" not in r for r in nested)


def test_a_solve_adds_nothing_to_the_account(loop):
    _which, lp = loop
    since, before = spans.clock(), _counters()
    lp.slv.solve(lp.b)
    grew = _grown(before)
    assert not set(grew) & set(ACCOUNT)
    assert spans.resetup_rows(since) == ([], False)


# what a steady solve touched at the parent commit (d031932), read there
# with the same calls
PARENT_SOLVE_SPANS = {"solve.prepare", "solve.run", "solve.readback",
                      "solve.report"}
PARENT_SOLVE_COUNTERS = {
    "flagship-p7-128": {
        "amg.geo_transfer.xla", "krylov.arnoldi_steps", "krylov.basis_rows",
        "solve.stage_s.prepare", "solve.stage_s.readback",
        "solve.stage_s.report", "solve.stage_s.run", "solve_data.reuse"},
    # PR 48: a cycle over SWELL operators counts its vreg-steps, a
    # product of two numbers the hierarchy and the solve already hold;
    # PR 51: and the seconds the layout choice's model puts on them
    "classical": {
        "solve.stage_s.prepare", "solve.stage_s.readback",
        "solve.stage_s.report", "solve.stage_s.run", "solve_data.reuse",
        "swell.vreg_steps", "swell.model_s"},
}
PARENT_SOLVE_COUNTERS["flagship-reuse-p7-256"] = \
    PARENT_SOLVE_COUNTERS["flagship-p7-128"]


def test_steady_solve_touches_what_it_touched_at_the_parent(loop):
    which, lp = loop
    since, before = spans.clock(), _counters()
    lp.slv.solve(lp.b)
    names = {r["name"] for r in spans.records() if r["ts"] >= since}
    assert names == PARENT_SOLVE_SPANS | {lp.slv.name + ".solve"}
    assert set(_grown(before)) == PARENT_SOLVE_COUNTERS[which]


def test_rows_one_a_resetup_with_the_flight_recorders_route(loop):
    which, lp = loop
    since = spans.clock()
    seq = len(flightrec.events())
    for _ in range(3):
        lp.resetup()
        lp.slv.solve(lp.b)
    rows, wrapped = spans.resetup_rows(since)
    assert not wrapped and len(rows) == 3
    assert [r["start"] for r in rows] == sorted(r["start"] for r in rows)
    routes = [e["route"] for e in flightrec.events()[seq:]
              if e.get("kind") == "resetup.route"]
    assert [r["route"] for r in rows] == routes[-3:]
    assert {r["route"] for r in rows} == {PRESETS[which][0]}
    assert spans.resetup_rows(spans.clock()) == ([], False)


def test_rows_say_when_the_buffer_wrapped(monkeypatch):
    lp = Loop("flagship-reuse-p7-256")
    spans.reset()
    monkeypatch.setattr(spans, "_MAX_RECORDS", 64)
    since = spans.clock()
    for _ in range(12):         # more records than the buffer now holds
        lp.resetup()
        lp.slv.solve(lp.b)
    rows, wrapped = spans.resetup_rows(since)
    assert wrapped and 0 < len(rows) < 12
    later = spans.clock()
    lp.resetup()
    rows, wrapped = spans.resetup_rows(later)
    assert not wrapped and len(rows) == 1
    spans.reset()
    assert spans.resetup_rows(since) == ([], False)


def test_self_time_and_account_are_per_thread():
    with spans.span("A.resetup", annotate=False, account=True):
        t = threading.Thread(target=lambda: spans.span(
            "amg.host_pull", annotate=False).__enter__())
        t.start()
        t.join(10)
        assert not t.is_alive()
        with spans.span("amg.L0.selector", annotate=False):
            pass
        with spans.span("B.resetup", annotate=False, account=True):
            with spans.span("amg.wrap_check", annotate=False):
                pass
    recs = {r["name"]: r for r in spans.records()[-4:]}
    acct = recs["A.resetup"]["account"]
    assert set(acct["leaves"]) == {"amg.L0.selector", "amg.wrap_check"}
    assert set(acct["under"]) == {"A.resetup", "B.resetup"}
    assert acct["wait"] == acct["leaves"]["amg.wrap_check"]
    assert "account" not in recs["B.resetup"]
    assert recs["B.resetup"]["self"] == pytest.approx(
        recs["B.resetup"]["dur"] - recs["amg.wrap_check"]["dur"])
    assert recs["amg.wrap_check"]["self"] == recs["amg.wrap_check"]["dur"]


def test_account_names_are_declared():
    for name in spans.WAIT_SPANS + ("resetup.route", "amg.L3.selector"):
        assert spans.is_declared(name), name
    for name in ACCOUNT:
        assert name in metrics.COUNTERS, name
    import fnmatch
    assert fnmatch.fnmatchcase("amg.L3.selector", spans.SELECTOR_SPANS)


# -- the benchmark's readers ------------------------------------------------

@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_on_empty_observations(name):
    assert layer_metrics.read(name, layer_metrics.Observed()) is None
    # ... and where the program has no such counter (the parent commit)
    obs = layer_metrics.Observed(
        ops=4, counter_growth={"amg.setup.full": 4},
        spans={"bench.resetup": [0.3, 0.2, 0.4, 0.3]})
    assert layer_metrics.read(name, obs) is None


def test_call_and_drain_add_to_the_resetup_span():
    obs = layer_metrics.Observed(
        ops=4, spans={"bench.resetup": [0.30, 0.20, 0.40, 0.32]},
        counter_growth={"resetup.call_s": 0.40,
                        "resetup.device_wait_s": 0.04,
                        "resetup.unnamed_s": 0.004,
                        "amg.resetup.selector_s": 0.0})
    call = layer_metrics.read("step.resetup_call_s", obs)
    drain = layer_metrics.read("step.resetup_drain_s", obs)
    assert call == pytest.approx(0.10)
    assert call + drain == pytest.approx(
        layer_metrics.read("step.resetup_s", obs))
    assert layer_metrics.read("step.resetup_wait_s", obs) == \
        pytest.approx(0.01)
    assert layer_metrics.read("step.resetup_unnamed_s", obs) == \
        pytest.approx(0.001)
    assert layer_metrics.read("step.selector_s", obs) == 0.0


def test_benchmark_json_lists_the_readers_in_the_time_step_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    steps = [w["name"] for w in bench["workloads"]
             if w["traffic"] == "time-step"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == steps
        assert entries[name]["moves"] == "step_s"
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + len(READERS)] == list(READERS)


# -- tools/step_account.py --------------------------------------------------

def _load_tool():
    path = os.path.join(REPO, "tools", "step_account.py")
    spec = importlib.util.spec_from_file_location("step_account_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tool = _load_tool()


def _step(k, resetup, call, leaves):
    return {"step": k, "factor": 1.0, "replace": 0.05, "resetup": resetup,
            "call": call, "drain": resetup - call, "solve": 0.05,
            "wait": 0.0, "unnamed": call - sum(leaves.values()),
            "route": "full", "leaves": leaves, "under": {}}


def test_modes_name_the_part_that_differs():
    fast = [_step(k, 0.240 + 0.001 * k, 0.10, {"amg.L0.selector": 0.02,
                                               "amg.wrap_check": 0.07})
            for k in range(6)]
    slow = [_step(6 + k, 0.310 + 0.001 * k, 0.17,
                  {"amg.L0.selector": 0.02, "amg.wrap_check": 0.14})
            for k in range(3)]
    m = tool.modes(fast + slow)
    assert m["fast"] == list(range(6)) and m["slow"] == [6, 7, 8]
    name, f, s, diff = m["parts"][0]
    assert name == "amg.wrap_check" and diff == pytest.approx(0.07)
    # the device's side of it reads as the drain
    slow = [_step(6 + k, 0.310 + 0.001 * k, 0.10,
                  {"amg.L0.selector": 0.02, "amg.wrap_check": 0.07})
            for k in range(3)]
    assert tool.modes(fast + slow)["parts"][0][0] == "drain"
    assert tool.modes(fast) is None
    assert tool.modes(fast[:1]) is None


def test_step_table_joins_walls_and_rows_by_time():
    bench = [("bench.amg_setup", 0.0, 1.0)]
    rows = []
    for k in range(3):
        t = 10.0 + k
        bench += [("bench.replace", t, 0.05), ("bench.resetup", t + 0.05, 0.3),
                  ("bench.solve", t + 0.35, 0.05), ("bench.step", t, 0.4)]
        rows.append({"start": t + 0.0501, "wall": 0.1 + 0.01 * k,
                     "route": "full", "leaves": {"x": 0.09}, "wait": 0.0,
                     "unnamed": 0.01 + 0.01 * k, "under": {}})
    steps = tool.step_table(bench, rows)
    assert [s["step"] for s in steps] == [0, 1, 2]
    assert [s["call"] for s in steps] == pytest.approx([0.1, 0.11, 0.12])
    assert [s["drain"] for s in steps] == pytest.approx([0.2, 0.19, 0.18])
    # a tree without the account: the benchmark's walls alone
    bare = tool.step_table(bench, [])
    assert len(bare) == 3 and all(np.isnan(s["call"]) for s in bare)


def test_programs_of_the_recorded_trace():
    path = os.path.join(REPO, "benchmark", "data",
                        "fine_spmv_probe.xplane.pb")
    table = tool.programs_table(path)
    (name, row), = table.items()
    assert name.startswith("jit_spmv(") and row["runs"] == 20
    assert [n for n, _t in row["ops"]] == ["_dia_spmv_call.1",
                                           "pad_bitcast_fusion"]
    assert sum(t for _n, t in row["ops"]) <= row["seconds"]


def test_tool_runs_a_time_step_cell(monkeypatch):
    from amgx_tpu.ops import pallas_spmv
    from benchmark import run
    find = run.find_cell

    def find_small(workload):
        cell, config, spec, bench = find(workload)
        config = copy.deepcopy(config)
        config["operator"]["grid"] = [16, 16, 16]
        return cell, config, dict(spec, rhs=2), bench

    monkeypatch.setattr(run, "find_cell", find_small)
    monkeypatch.setattr(run, "_peaks", lambda kind: {})
    lines = []
    with pallas_spmv.force_pallas_interpret():
        doc = tool.run_one("flagship-reuse-p7-256.time-step", 2147483700,
                           0.5, 0, out=lines.append, devs=jax.devices())
    assert doc["result"]["correct"] and not doc["wrapped"]
    steps = doc["steps"]
    assert len(steps) == doc["result"]["attempted"] >= 1
    for s in steps:
        assert s["route"] == "value" and 1.0 <= s["factor"] < 2.0
        assert 0.0 < s["call"] <= s["resetup"]
        assert sum(s["leaves"].values()) + s["unnamed"] == \
            pytest.approx(s["call"], rel=0.01)
    assert sum(ln.startswith("step ") for ln in lines) == len(steps)
    assert any(ln.startswith("account call=") for ln in lines)
    assert any(ln.startswith("leaf value_resetup.dispatch") for ln in lines)
