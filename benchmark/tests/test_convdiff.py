"""The recirculating convection-diffusion cell and its control.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q` from
the root of a checkout:

- `convdiff-pbicgstab-classical.solve-stream` through the harness at
  n = 16 comes out correct, reports its three end-to-end metrics, and
  its control (the plain reference's BiCGStab in bfloat16, named by
  module) does not;
- the control's class is found by `control.control_class`, and its
  module holds neither `jax` nor `amgx_tpu` once imported.

The operator and the reference themselves are held in tier-1
(tests/test_convdiff_reference.py).
"""
from __future__ import annotations

import copy

import jax
import pytest

from benchmark import control, reference_convdiff, run, selfcheck

CELL = "convdiff-pbicgstab-classical.solve-stream"


@pytest.fixture
def small(monkeypatch):
    """The cell at n = 16, two right-hand sides, on whatever JAX has."""
    find = run.find_cell

    def find_small(workload):
        cell, config, spec, bench = find(workload)
        config = copy.deepcopy(config)
        config["operator"].update(n=16, rows=16 ** 3)
        return cell, config, dict(spec, rhs=2), bench

    monkeypatch.setattr(run, "find_cell", find_small)
    monkeypatch.setattr(run, "_peaks", lambda kind: {})
    from amgx_tpu.ops import pallas_spmv
    with pallas_spmv.force_pallas_interpret():
        yield


def drive(make_entry=None):
    lines = []
    result = run.run(CELL, seed=2147483749, seconds=0.5, trace=False,
                     make_entry=make_entry, devs=jax.devices(),
                     out=lines.append)
    return result, lines


def test_cell_is_correct(small):
    result, lines = drive()
    assert result["correct"] and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert {"setup_s", "amg_setup_s", "solve_s"} == set(result["metrics"])
    assert any(ln.startswith("check op=") and ln.endswith(" ok")
               for ln in lines)


def test_control_is_not_correct(small):
    result, lines = drive(make_entry=control.control_entry)
    assert not result["correct"] and result["failed"] >= 1, lines
    assert any(ln.endswith(" FAILED") for ln in lines)


def test_control_is_named_by_module_and_its_module_is_clean():
    config = run.load_json("configs", "convdiff-pbicgstab-classical.json")
    assert control.control_class(config) \
        is reference_convdiff.ReferenceBiCGStab
    assert selfcheck.imports_of(config["control"]["module"]) == []
    assert selfcheck.imports_of(config["operator"]["module"]) == []
