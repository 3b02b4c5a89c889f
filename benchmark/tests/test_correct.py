"""`correct` is a comparison that has been shown to fail.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q` from
the root of a checkout. Every test drives the harness itself
(`benchmark.run.run`) at 16^3 with the Pallas kernels in interpret
mode, skipping only its look for a chip:

- the program as it is comes out correct in each cell;
- each cell's lower-precision control (the same entry
  `benchmark.control` puts in the program's place on the chip) comes
  out NOT correct;
- the plain reference CG, the control of the classical cell, meets the
  limit in float32, so what fails it in bfloat16 is the precision;
- with the timed path broken underneath (an answer altered where it is
  produced; a step whose resetup leaves the solver's state unchanged)
  `correct` comes out false;
- every result carries what the check compared, each number beside its
  limit, as its last key, and `run.say` ends standard error with them.
"""
from __future__ import annotations

import copy
import json

import jax
import pytest

from benchmark import control, entries, reference, run

CELLS = ["flagship-p7-128.solve-stream", "classical-p7-128.solve-stream",
         "flagship-p7-256.time-step"]


@pytest.fixture
def small(monkeypatch):
    """The cells at 16^3, two right-hand sides, on whatever JAX has."""
    find = run.find_cell

    def find_small(workload):
        cell, config, spec, bench = find(workload)
        config = copy.deepcopy(config)
        config["operator"]["grid"] = [16, 16, 16]
        return cell, config, dict(spec, rhs=2), bench

    monkeypatch.setattr(run, "find_cell", find_small)
    monkeypatch.setattr(run, "_peaks", lambda kind: {})
    from amgx_tpu.ops import pallas_spmv
    with pallas_spmv.force_pallas_interpret():
        yield


def drive(workload, make_entry=None, seconds=0.5):
    lines = []
    result = run.run(workload, seed=2147483700, seconds=seconds,
                     trace=False, make_entry=make_entry,
                     devs=jax.devices(), out=lines.append)
    return result, lines


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(small, workload):
    result, lines = drive(workload)
    assert result["correct"] and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert any(ln.startswith("check op=") and ln.endswith(" ok")
               for ln in lines)
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("workload", CELLS)
def test_lower_precision_control_fails(small, workload):
    result, lines = drive(workload, make_entry=control.control_entry)
    assert not result["correct"] and result["failed"] >= 1, lines
    assert any(ln.endswith(" FAILED") for ln in lines)


def test_reference_cg_meets_the_limit_in_float32(small):
    def f32_cg(config):
        ctl = config["control"]
        assert ctl["entry"] == "reference_cg"
        return reference.ReferenceCG(dict(ctl["solver"], dtype="float32"),
                                     config["operator"])
    result, lines = drive("classical-p7-128.solve-stream", f32_cg)
    assert result["correct"], lines


class AlteredAnswer(entries.PythonEntry):
    """The answer changed in its sixth digit where it is produced."""

    def last(self):
        s = super().last()
        return entries.Solved(s.x * (1.0 + 1e-5), s.iterations, s.ok)


class StaleStep(entries.PythonEntry):
    """A step that leaves the solver's state unchanged: the new
    coefficients never reach it."""

    def resetup(self):
        pass


@pytest.mark.parametrize("workload,broken", [
    ("flagship-p7-128.solve-stream", AlteredAnswer),
    ("flagship-p7-256.time-step", StaleStep)])
def test_broken_timed_path_is_not_correct(small, workload, broken):
    result, lines = drive(
        workload, lambda cfg: broken(cfg["solver"], cfg["operator"]))
    assert not result["correct"] and result["failed"] >= 1, lines


def test_a_failed_status_counts_even_unsampled(small):
    class NeverConverges(entries.PythonEntry):
        def last(self):
            s = super().last()
            return entries.Solved(s.x, s.iterations, False)
    result, lines = drive(
        "flagship-p7-128.solve-stream",
        lambda cfg: NeverConverges(cfg["solver"], cfg["operator"]))
    assert not result["correct"]
    assert result["failed"] == result["attempted"], lines
    status = result["compared"]["status_not_success"]
    assert status == {"value": result["attempted"], "limit": 0}


@pytest.mark.parametrize("broken,holds", [(None, True),
                                          (AlteredAnswer, False)])
def test_the_numbers_compared_end_the_result_and_standard_error(
        small, capsys, broken, holds):
    result, lines = drive(
        "flagship-p7-128.solve-stream", broken and (
            lambda cfg: broken(cfg["solver"], cfg["operator"])))
    assert list(result)[-1] == "compared"
    worst = result["compared"]["true_relres_max"]
    assert worst["limit"] == 1e-8
    assert (worst["value"] <= worst["limit"]) is holds is result["correct"]
    assert any(f"largest true_relres {worst['value']:.6e}" in ln
               for ln in lines)
    run.say(result)
    said = capsys.readouterr()
    assert json.loads(said.out.splitlines()[-1]) == result
    assert said.err.splitlines()[-2:] == [
        f"compared true_relres_max {worst['value']:.6e} limit 1.0e-08",
        "compared status_not_success 0.000000e+00 limit 0.0e+00"]
