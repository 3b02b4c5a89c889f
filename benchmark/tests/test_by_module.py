"""A configuration names its entry, its operator, its traffic code and
its control by module.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q` from
the root of a checkout:

- a cell whose four parts are `local_parts.py`'s, named by
  `local/config.json` and `local/traffic.json`, runs through
  `benchmark.run.run` at 8^3 with no edit to any file of the harness,
  and its control through `benchmark.control`'s look-up comes out NOT
  correct;
- each thing a file can name wrongly (a module outside the benchmark
  package, one that does not import, a name the module lacks, an entry
  whose ranks are not the cell's chips) is a SystemExit that names it,
  before the look for a chip;
- the names the six configurations and two traffic files give resolve
  to the classes, the loops and, byte for byte, the operator arrays the
  tables gave before there was a look-up;
- cell 8's configuration is cell 2's but for the reuse key and its own
  control block;
- the distributed C API entry (`entry_capi_distributed.py`), over four
  forced host devices in a process of its own, comes out correct and
  gives the `capi` entry's x.
"""
from __future__ import annotations

import copy
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import control, entries, run, selfcheck, traffic
from benchmark.operator_host import poisson_csr
from benchmark.tests import local_parts

HERE = os.path.dirname(os.path.abspath(__file__))
LOCAL_CELL = {"name": "local.solve-twice", "config": "local",
              "traffic": "solve-twice", "chips": 1}


def _local(*parts):
    with open(os.path.join(HERE, "local", *parts)) as f:
        return json.load(f)


@pytest.fixture
def local_cell(monkeypatch):
    """`find_cell` answers with the test-local cell; the tests change
    the dictionaries it hands out."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = (dict(LOCAL_CELL), _local("config.json"),
             _local("traffic.json"), bench)
    monkeypatch.setattr(run, "find_cell", lambda workload: found)
    monkeypatch.setattr(run, "_peaks", lambda kind: {})
    del local_parts.USED[:]
    return found


def drive(make_entry=None):
    lines = []
    result = run.run(LOCAL_CELL["name"], seed=2147483900, seconds=0.3,
                     trace=False, make_entry=make_entry,
                     devs=jax.devices(), out=lines.append)
    return result, lines


def test_a_cell_whose_parts_arrive_as_files_runs(local_cell):
    result, lines = drive()
    assert result["correct"] and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert local_parts.USED == ["generator", "entry", "traffic", "traffic"]
    assert any(ln.startswith("window: bench.pair n=") for ln in lines)
    assert "setup_s" in result["metrics"]


def test_its_control_is_found_by_module_and_fails(local_cell):
    assert control.control_class(local_cell[1]) is local_parts.LocalControl
    result, lines = drive(make_entry=control.control_entry)
    assert "control" in local_parts.USED and "entry" not in local_parts.USED
    assert not result["correct"] and result["failed"] >= 1, lines


def test_the_seed_reaches_a_generator():
    op = _local("config.json")["operator"]
    a, b = (local_parts.scaled_poisson(op, seed)[2] for seed in (1, 2))
    assert a.shape == b.shape and not np.array_equal(a, b)
    again = local_parts.scaled_poisson(op, 1)[2]
    assert a.tobytes() == again.tobytes()


def _outside(cell, config, spec):
    config["entry_module"] = "amgx_tpu.capi"
    return "outside the benchmark package", "'amgx_tpu.capi'"


def _missing_module(cell, config, spec):
    config["operator"]["module"] = "benchmark.tests.no_such_module"
    return "does not import", "'benchmark.tests.no_such_module'"


def _missing_attribute(cell, config, spec):
    spec["kind"] = "solve_thrice"
    return "has no traffic kind 'solve_thrice'", "solve_twice"


def _unknown_name(cell, config, spec):
    del spec["module"]
    return "no traffic kind 'solve_twice'", "solve_stream"


def _unknown_stencil(cell, config, spec):
    config["operator"] = {"stencil": "5pt", "grid": [8, 8, 8],
                          "dtype": "float64"}
    return "no stencil '5pt'", "27pt"


def _chips_mismatch(cell, config, spec):
    config.update(entry="CApiDistributedEntry",
                  entry_module="benchmark.entry_capi_distributed")
    config["solver"]["ranks"] = 4
    return "asks for 1 chip(s)", "solver.ranks = 4"


@pytest.mark.parametrize("spoil", [
    _outside, _missing_module, _missing_attribute, _unknown_name,
    _unknown_stencil, _chips_mismatch], ids=lambda f: f.__name__[1:])
def test_a_wrong_name_exits_before_the_look_for_a_chip(
        local_cell, monkeypatch, spoil):
    def reached(chips):
        raise AssertionError("the look for a chip was reached")
    monkeypatch.setattr(run, "require_chips", reached)
    words = spoil(*local_cell[:3])
    with pytest.raises(SystemExit) as e:
        run.run(LOCAL_CELL["name"], seed=1, seconds=0.1, trace=False)
    assert isinstance(e.value.code, str), e.value.code
    for word in words:
        assert word in e.value.code, e.value.code


def test_a_control_that_cannot_be_found_exits_before_the_run(
        local_cell, monkeypatch):
    def reached(*a, **kw):
        raise AssertionError("the run was reached")
    monkeypatch.setattr(run, "run", reached)
    local_cell[1]["control"]["entry"] = "NoSuchControl"
    with pytest.raises(SystemExit) as e:
        control.main(["--workload", LOCAL_CELL["name"], "--seed", "1",
                      "--seconds", "0.1"])
    assert "has no control entry 'NoSuchControl'" in e.value.code
    assert "LocalControl" in e.value.code


CONFIGS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(run.HERE, "configs", "*.json")))
TRAFFICS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(run.HERE, "traffic", "*.json")))


@pytest.mark.parametrize("name", CONFIGS)
def test_built_in_names_give_what_the_tables_gave(name):
    config = run.load_json("configs", name + ".json")
    entry = run.entry_of(config, {"name": name, "config": name, "chips": 1})
    generator = run.generator_of(config["operator"])
    assert entry is entries.ENTRIES[config["entry"]]
    assert entry in (entries.PythonEntry, entries.CApiEntry)
    op = dict(config["operator"], grid=[6, 5, 4])
    want = poisson_csr(op["stencil"], op["grid"], np.dtype(op["dtype"]))
    for seed in (0, 2147483900):
        got = generator(op, seed)
        assert [a.dtype for a in got] == [a.dtype for a in want]
        assert [a.tobytes() for a in got] == [a.tobytes() for a in want]
    ctl = control.control_class(config)
    assert ctl is control.CONTROL_ENTRIES.get(
        config["control"]["entry"], ctl)


@pytest.mark.parametrize("name", TRAFFICS)
def test_built_in_traffic_kinds_are_the_table_s(name):
    spec = run.load_json("traffic", name + ".json")
    assert run.kind_of(spec) is traffic.KINDS[spec["kind"]]
    assert "module" not in spec


def test_cell_8_is_cell_2_but_for_the_key_and_its_control():
    """No key of classical-reuse-p7-128.json moved when its control
    block came to name its module."""
    one = run.load_json("configs", "classical-p7-128.json")
    other = run.load_json("configs", "classical-reuse-p7-128.json")
    for key in ("operator", "entry", "precision", "reduced"):
        assert one[key] == other[key], key
    solver = copy.deepcopy(one["solver"])
    solver["add"] += ", amg:structure_reuse_levels=-1"
    assert other["solver"] == solver
    assert other["guarantees"]["true_relative_residual"] == \
        one["guarantees"]["true_relative_residual"]
    ctl = other["control"]
    assert (ctl["entry"], ctl["module"]) == (
        "ReferenceCGSteps", "benchmark.reference_classical_reuse")
    assert ctl["solver"] == one["control"]["solver"]
    assert "python3 -m" not in ctl["what"]


def test_selfcheck_resolves_every_name_every_file_gives():
    named = selfcheck.check_names()
    assert named["configs"] == len(CONFIGS)
    assert named["traffic"] == len(TRAFFICS)
    assert "benchmark.reference_classical_reuse" in named["clean"]
    assert "benchmark.operator_host" in named["clean"]


def test_selfcheck_sees_a_module_that_imports_jax():
    assert selfcheck.imports_of("benchmark.operator_host") == []
    assert "jax" in selfcheck.imports_of("benchmark.tests.test_correct")


def test_distributed_entry_over_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                          "--xla_force_host_platform_device_count=4"))
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.tests.distributed_drive"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["ranks"] == 4
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["device_count"] == 4
    assert out["solver"] == "DistributedSolver" and out["probe"] is None
    assert out["x_rel_diff"] <= 1e-10, out
    assert out["replace"].startswith("CApiDistributedEntry serves a solve")
    assert out["handles_left"] == 0
