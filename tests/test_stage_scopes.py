"""Where a solve's and a time step's seconds go (ISSUE 30).

- host stages: the four `solve.*` spans under `<NAME>.solve` and their
  counters of seconds; `matrix.refill_host` / `matrix.upload` in
  `CsrMatrix.with_values` with the bytes it put; JAX's compile events
  as counters and spans with `fun_name`; which solver made a resetup
  drop the cached programs;
- device scopes: the scope table `telemetry.programs` reads from the
  executable a solve runs names every level of the hierarchy, holds no
  solver and outlives `AMGX_solver_destroy`;
- the benchmark's reader of both (`benchmark/scope_metrics.py`, and
  `benchmark.selfcheck` over the new reader files and the new cell).

The `_dia_*_call*` instructions exist only in a program compiled for
the chip: that half of the scope table is tested in
tests/test_chip_compile.py, the one file that describes a chip."""
import gc
import json
import os
import threading
import time
import weakref

import numpy as np
import pytest
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import capi, gallery
from amgx_tpu.config import Config
from amgx_tpu.matrix import CsrMatrix
from amgx_tpu.telemetry import metrics, programs, spans

from benchmark import layer_metrics, scope_metrics, selfcheck
from benchmark.operator_host import poisson_csr

amgx.initialize()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("prepare", "run", "readback", "report")


def flagship_options():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "flagship-p7-128.json")) as f:
        return json.load(f)["solver"]["options"]


@pytest.fixture(scope="module")
def flagship16():
    """The benchmark's flagship configuration set up at 16^3."""
    programs._reset()        # the worker's earlier test files' programs
    A = gallery.poisson("7pt", 16, 16, 16).init()
    slv = amgx.create_solver(Config.from_string(flagship_options()))
    slv.setup(A)
    b = np.random.default_rng(30).standard_normal(A.num_rows)
    slv.solve(b)                        # compiles; registers the program
    return slv, b


def growth(before, names):
    after = metrics.snapshot()
    return {n: after[n] - before.get(n, 0) for n in names}


def test_solve_stages_nest_once_and_account_for_the_solve(flagship16):
    slv, b = flagship16
    spans.reset()
    names = [f"solve.stage_s.{s}" for s in STAGES]
    before = metrics.snapshot()
    res = slv.solve(b)
    assert res.converged
    grown = growth(before, names)
    recs = spans.records()
    whole = [r for r in recs if r["name"] == "REFINEMENT.solve"]
    assert len(whole) == 1
    for stage in STAGES:
        mine = [r for r in recs if r["name"] == f"solve.{stage}"]
        assert len(mine) == 1, stage
        assert mine[0]["parent"] == "REFINEMENT.solve"
        assert mine[0]["depth"] == 1
        # the counter is the span's own wall
        assert grown[f"solve.stage_s.{stage}"] == pytest.approx(
            mine[0]["dur"])
    wall = whole[0]["dur"]
    assert 0.9 * wall <= sum(grown.values()) <= wall


def test_with_values_records_refill_upload_and_bytes():
    ro, ci, host_vals = poisson_csr("7pt", (12, 12, 12))
    n = ro.shape[0] - 1
    A = CsrMatrix.from_scipy_like(ro, ci, host_vals, n, n).init()
    assert A.dia_vals is not None
    names = ("matrix.refill_host_s", "matrix.upload_s",
             "matrix.upload_bytes", "matrix.refill_map.build",
             "matrix.refill_map.reuse")
    # the pattern's first replacement builds the refill map
    before = metrics.snapshot()
    B = A.with_values(1.25 * host_vals)
    first = growth(before, names)
    assert (first["matrix.refill_map.build"],
            first["matrix.refill_map.reuse"]) == (1, 0)
    # every later one finds it; both times the new values and the slab
    # made from them on the host are put, whatever the platform
    vals = 1.5 * host_vals
    spans.reset()
    before = metrics.snapshot()
    B = B.with_values(vals)
    grown = growth(before, names)
    by_name = {r["name"]: r for r in spans.records()}
    assert by_name["matrix.refill_host"]["dur"] == pytest.approx(
        grown["matrix.refill_host_s"])
    assert by_name["matrix.upload"]["dur"] == pytest.approx(
        grown["matrix.upload_s"])
    assert grown["matrix.upload_bytes"] == vals.nbytes + B.dia_vals.nbytes \
        == first["matrix.upload_bytes"]
    assert (grown["matrix.refill_map.build"],
            grown["matrix.refill_map.reuse"]) == (0, 1)
    np.testing.assert_array_equal(np.asarray(B.dia_vals),
                                  1.5 * np.asarray(A.dia_vals))
    # values that live on the device already are not put again
    before = metrics.snapshot()
    C = B.with_values(jnp.asarray(vals))
    assert growth(before, names)["matrix.upload_bytes"] == C.dia_vals.nbytes


def test_retrace_raises_compile_counters_and_names_the_program():
    import jax
    spans.reset()
    names = ("compile.programs", "compile.backend_s", "compile.lower_s",
             "compile.trace_s")
    before = metrics.snapshot()

    @jax.jit
    def issue30_probe(x):
        return jnp.sin(x) * 2.0

    issue30_probe(jnp.ones(31))
    first = growth(before, names)
    assert first["compile.programs"] >= 1
    assert first["compile.backend_s"] > 0 and first["compile.trace_s"] > 0
    issue30_probe(jnp.ones(31))                 # cached: nothing compiles
    assert growth(before, names) == first
    issue30_probe(jnp.ones(37))                 # a new shape retraces
    second = growth(before, names)
    assert second["compile.programs"] > first["compile.programs"]
    assert second["compile.backend_s"] > first["compile.backend_s"]
    backend = [r for r in spans.records() if r["name"] == "compile.backend"
               and r["args"]["fun_name"] == "jit(issue30_probe)"]
    assert len(backend) == 2
    traced = [r for r in spans.records() if r["name"] == "compile.trace"
              and r["args"]["fun_name"] == "issue30_probe"]
    assert len(traced) == 2


def test_nested_trace_events_count_each_second_once(monkeypatch):
    """A trace inside a trace: the counter takes each event's own
    time, so the sum is the outer event's duration."""
    monkeypatch.setattr(programs, "_traces", threading.local())
    own = programs._own_trace_time
    # events of one thread end in order: inner 2..3, inner 4..6, then
    # the outer 1..8 that holds both; a later, disjoint 9..10
    assert own(2.0, 1.0) == 1.0
    assert own(4.0, 2.0) == 2.0
    assert own(1.0, 7.0) == pytest.approx(4.0)
    assert own(9.0, 1.0) == 1.0
    # one holding everything so far
    assert own(0.5, 10.0) == pytest.approx(2.0)


def test_seconds_counter_accumulates_and_exports():
    before = metrics.get("solve.stage_s.report")
    metrics.add("solve.stage_s.report", 0.25)
    metrics.add("solve.stage_s.report", 0.5)
    assert metrics.get("solve.stage_s.report") == pytest.approx(
        before + 0.75)
    text = metrics.to_openmetrics()
    assert "# TYPE amgx_solve_stage_s_report counter" in text
    assert "amgx_compile_backend_s_total" in text
    with pytest.raises(KeyError):
        metrics.add("solve.stage_s.nosuch", 1.0)


def test_resetup_names_the_solver_that_forces_the_retrace():
    A = gallery.poisson("5pt", 12, 12).init()
    slv = amgx.create_solver(Config.from_string(
        "solver=PCG, max_iters=20, monitor_residual=1, tolerance=1e-6, "
        "preconditioner(c)=CHEBYSHEV, c:max_iters=2, "
        "c:chebyshev_lambda_estimate_mode=2, c:preconditioner=NOSOLVER"))
    slv.setup(A)
    b = np.ones(A.num_rows)
    slv.solve(b)
    spans.reset()
    before = metrics.snapshot()
    slv.resetup(A.with_values(2.0 * np.asarray(A.values)))
    grown = growth(before, ("resetup.retrace_cause.CHEBYSHEV",
                            "resetup.retrace_cause.AMG",
                            "resetup.retrace_cause.other",
                            "solver.retrace.solve"))
    # counted once, where programs were dropped, and not as a retrace
    assert grown == {"resetup.retrace_cause.CHEBYSHEV": 1,
                     "resetup.retrace_cause.AMG": 0,
                     "resetup.retrace_cause.other": 0,
                     "solver.retrace.solve": 0}
    by_name = {r["name"]: r for r in spans.records()}
    assert by_name["PCG.resetup"]["args"] == {
        "retrace_cause": "CHEBYSHEV"}
    assert len(slv._jit_cache) == 0
    # a resetup that keeps the programs names no cause
    plain = amgx.create_solver(Config.from_string(
        "solver=PCG, max_iters=50, monitor_residual=1, tolerance=1e-6, "
        "preconditioner(j)=BLOCK_JACOBI, j:max_iters=2"))
    plain.setup(A)
    plain.solve(b)
    spans.reset()
    plain.resetup(A.with_values(2.0 * np.asarray(A.values)))
    kept = {r["name"]: r for r in spans.records()}["PCG.resetup"]
    assert "args" not in kept and len(plain._jit_cache) == 1


def test_solve_holds_one_program_per_placement():
    """The call is jax.jit's own: a right-hand side committed to
    another device compiles once more and both programs stay, so
    alternating between the two compiles nothing."""
    import jax
    A = gallery.poisson("5pt", 10, 10).init()
    slv = amgx.create_solver(Config.from_string(
        "solver=CG, max_iters=30, monitor_residual=1, tolerance=1e-6"))
    slv.setup(A)
    here = jnp.ones(A.num_rows)
    assert slv.solve(here).converged
    elsewhere = jax.device_put(here, jax.devices()[1])
    before = metrics.get("compile.programs")
    res = slv.solve(elsewhere)
    assert res.converged and res.x.devices() == {jax.devices()[1]}
    assert metrics.get("compile.programs") == before + 1
    for rhs in (here, elsewhere, here, elsewhere):
        assert slv.solve(rhs).converged
    assert metrics.get("compile.programs") == before + 1
    assert len(slv._jit_cache) == 1


def test_registering_the_program_that_ran_compiles_nothing():
    """`Solver._first_solve` asks `lower().compile()` for the program
    its jitted call has just compiled: JAX answers from its caches
    with the executable that runs. No lowering, no compile event, and
    a few milliseconds."""
    A = gallery.poisson("5pt", 11, 11).init()
    slv = amgx.create_solver(Config.from_string(
        "solver=CG, max_iters=30, monitor_residual=1, tolerance=1e-6"))
    slv.setup(A)
    b = jnp.ones(A.num_rows)
    slv.solve(b)
    ((_key, solve_fn),) = slv._jit_cache.items()
    args = (slv.solve_data(), b, jnp.zeros_like(b))
    names = ("compile.programs", "compile.trace_s", "compile.lower_s",
             "compile.backend_s", "compile.cache_hits",
             "compile.cache_misses")
    before = metrics.snapshot()
    t0 = time.perf_counter()
    compiled = solve_fn.lower(*args).compile()
    wall = time.perf_counter() - t0
    grown = growth(before, names)
    # JAX reports the trace's cache lookup as a trace event of its own
    assert grown.pop("compile.trace_s") < 0.01
    assert grown == dict.fromkeys(grown, 0)
    assert wall < 0.25
    assert "krylov.CG.iter" in compiled.as_text()


def test_the_kept_solve_data_tree_is_the_argument_list_it_was():
    """The solve program takes `solve_data()` as its first argument, and
    a resetup that keeps the program (PR 33) replays it on the new
    tree. Keeping the tree between solves (ISSUE 41) changes neither
    the program nor its argument list: the tree a solver has kept over
    its solves and the first assembly of a fresh solver have one
    treedef, the same leaf shapes and dtypes and one jaxpr, and neither
    a steady solve nor the solve after a resetup traces or compiles."""
    import jax
    from amgx_tpu import presets

    def described(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef, [(l.shape, str(l.dtype)) for l in leaves]

    def jaxpr(slv, tree):
        return str(jax.make_jaxpr(slv._build_solve_fn())(tree, b, x0).jaxpr)

    A = gallery.poisson("7pt", 16, 16, 16).init()
    A = A.with_values(np.asarray(A.values))
    b = jnp.ones(A.num_rows)
    x0 = jnp.zeros_like(b)
    slv = amgx.create_solver(Config.from_string(presets.FLAGSHIP))
    slv.setup(A)
    for _ in range(3):
        assert slv.solve(b).converged
    kept = slv.solve_data()
    fresh_slv = amgx.create_solver(Config.from_string(presets.FLAGSHIP))
    fresh_slv.setup(A)
    fresh = fresh_slv.solve_data()
    assert described(fresh) == described(kept)
    assert jaxpr(fresh_slv, fresh) == jaxpr(slv, kept)
    names = ("solver.retrace.solve", "compile.programs",
             "resetup.program_kept")
    before = metrics.snapshot()
    assert slv.solve(b).converged                   # steady
    slv.resetup(A.with_values(1.5 * np.asarray(A.values)))
    assert described(slv.solve_data()) == described(kept)
    assert slv.solve(b).converged                   # the kept program
    assert growth(before, names) == {
        "solver.retrace.solve": 0, "compile.programs": 0,
        "resetup.program_kept": 1}


def lowered_elsewhere(solve_fn, args):
    """The same program lowered from another call site."""
    return solve_fn.lower(*args).compile()


def test_solve_program_cache_key_holds_op_names_not_call_sites():
    """The solve program is cached under its op names (an executable
    cached under other scopes would name nothing) and under no Python
    frame: the same program lowered from another call site is a hit,
    not a cold compile inside somebody's timed window."""
    import jax
    from amgx_tpu.compile_cache import op_names_in_key
    A = gallery.poisson("5pt", 9, 9).init()
    slv = amgx.create_solver(Config.from_string(
        "solver=CG, max_iters=30, monitor_residual=1, tolerance=1e-6"))
    slv.setup(A)
    b = jnp.ones(A.num_rows)
    slv.solve(b)                    # compiled from Solver._first_solve
    args = (slv.solve_data(), b, jnp.zeros_like(b))
    again = jax.jit(slv._build_solve_fn())      # nothing of it cached
    before = metrics.snapshot()
    with op_names_in_key():
        compiled = lowered_elsewhere(again, args)
    grown = growth(before, ("compile.cache_hits", "compile.cache_misses",
                            "compile.programs"))
    assert grown == {"compile.cache_hits": 1, "compile.cache_misses": 0,
                     "compile.programs": 1}
    assert "krylov.CG.iter" in compiled.as_text()


def test_op_names_in_key_is_this_threads_alone():
    """The two settings reach no other thread and are gone afterwards,
    whatever order two compiling threads enter and leave in."""
    import jax
    from amgx_tpu.compile_cache import op_names_in_key
    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")

    def read():
        return tuple(getattr(jax.config, f) for f in flags)

    defaults = read()
    inside, outside = threading.Event(), threading.Event()
    seen = {}

    def first():
        with op_names_in_key():
            seen["first"] = read()
            inside.set()
            outside.wait(10)        # leaves after the second has left

    def second():
        inside.wait(10)
        seen["second, before"] = read()
        with op_names_in_key():
            seen["second"] = read()
        seen["second, after"] = read()
        outside.set()

    threads = [threading.Thread(target=f) for f in (first, second)]
    for t in threads:
        t.start()
    inside.wait(10)
    seen["main, meanwhile"] = read()
    for t in threads:
        t.join(20)
    assert seen["first"] == seen["second"] == (True, 0)
    assert seen["second, before"] == seen["second, after"] == defaults
    assert seen["main, meanwhile"] == defaults
    assert read() == defaults


def test_scope_table_names_every_level_of_the_hierarchy(flagship16):
    slv, b = flagship16
    # alone in the registry: the programs of this worker's other tests
    # name their instructions alike, and tables that disagree join
    # nothing
    programs._reset()
    slv._jit_cache.clear()
    slv.solve(b)                        # traced and registered anew
    # (label, (shape, dtype, fault-injection epoch))
    assert ("REFINEMENT.solve", ((16 ** 3,), "float64")) in [
        (label, sig[:2]) for label, sig in programs._registered()]
    table = programs.scopes()
    found = {s for s in table.values() if s}
    amg = slv.preconditioner.preconditioner.amg
    assert len(amg.levels) >= 2
    for k in range(len(amg.levels)):
        for stage in ("presmooth", "restrict", "prolong", "postsmooth"):
            assert f"amg.L{k}.{stage}" in found, (k, stage)
    assert "amg.coarse" in found
    for scope in ("krylov.REFINEMENT.iter", "krylov.REFINEMENT.monitor",
                  "krylov.FGMRES.iter", "refine.defect", "refine.update"):
        assert scope in found, scope
    # the join key: names as the compiler wrote them, op_names as paths
    names = programs.op_names()
    assert set(table) == set(names)
    some = next(n for n, s in table.items() if s == "amg.L1.presmooth")
    assert "/amg.L0/amg.L1/amg.L1.presmooth/" in names[some]


@pytest.mark.parametrize("name", [
    "geo7-cheb-mf", "geo27-gs", "agg-size2", "classical-pmis-d2",
    "classical-aggr-l1trunc", "classical-nonsym"])
def test_scope_table_names_the_four_stages(name):
    """Under the Pallas interpreter, as on the chip: the registered
    solve program of each of test_cycle_one_path.py's hierarchies puts
    ops under the four stages of every level and under `amg.coarse`
    (where the coarse solver does anything), and under no other `amg.`
    name: the names `benchmark/scope_metrics.py` joins a trace on."""
    import test_cycle_one_path as one_path
    from amgx_tpu.ops import pallas_spmv as ps
    programs._reset()
    with ps.force_pallas_interpret():
        slv, A = one_path.set_up(name)
        slv.solve(jnp.ones(A.num_rows, A.values.dtype))
    found = {s for s in programs.scopes().values()
             if s and s.startswith("amg.")}
    programs._reset()       # an interpreter's program: not the next test's
    amg = one_path.amg_of(slv)
    levels = range(len(amg.levels))
    stages = {f"amg.L{k}.{stage}" for k in levels for stage in
              ("presmooth", "restrict", "prolong", "postsmooth")}
    coarse = {"amg.coarse"}
    wanted = set(stages)
    if amg.coarse_solver.name == "NOSOLVER":
        # no coarse correction: nothing reads the deepest level's
        # coarse right-hand side, and the compiler drops what makes it
        coarse = set()
        wanted.discard(f"amg.L{len(amg.levels) - 1}.restrict")
    assert wanted <= found, wanted - found
    assert found - stages - {f"amg.L{k}" for k in levels} == coarse


def test_scope_of_takes_the_innermost_component():
    assert programs.scope_of(
        "jit(solve_fn)/while/body/krylov.FGMRES.iter/while/body/"
        "closed_call/amg.L0/amg.L1/amg.L1.restrict/gather") == \
        "amg.L1.restrict"
    assert programs.scope_of(
        "jit(solve_fn)/krylov.REFINEMENT.iter/refine.defect/jit(_pad)/pad"
    ) == "refine.defect"
    assert programs.scope_of("jit(solve_fn)/while/cond/lt") is None
    text = ('  %pad.3 = f32[8]{0} pad(%a, %b), padding=0_1, '
            'metadata={op_name="jit(f)/amg.L0.presmooth/jit(_pad)/pad" '
            'source_file="x.py" source_line=3}\n'
            '  ROOT fusion.7 = f32[8]{0} fusion(pad.3), kind=kLoop, '
            'calls=%fused, metadata={op_name="jit(f)/krylov.CG.iter/mul"}\n'
            '  %bare.1 = f32[8]{0} add(%a, %a)\n')
    assert programs.parse_op_names(text) == {
        "pad.3": "jit(f)/amg.L0.presmooth/jit(_pad)/pad",
        "fusion.7": "jit(f)/krylov.CG.iter/mul"}


def test_tables_that_disagree_on_a_name_join_nothing():
    """`fusion.3` is in most programs; a trace gives the name alone."""
    programs._reset()
    one = {"fusion.3": "jit(f)/amg.L0.presmooth/mul",
           "pad.1": "jit(f)/krylov.CG.iter/jit(_pad)/pad"}
    other = {"fusion.3": "jit(g)/amg.L1.presmooth/mul",
             "while.2": "jit(g)/while"}
    with programs._lock:
        programs._programs["A.solve", 1] = {"exe": None, "names": one}
        programs._programs["B.solve", 2] = {"exe": None, "names": other}
    assert programs.op_names() is None
    assert programs.scopes() is None
    # the same stage under another program's name: the scopes agree
    other["fusion.3"] = "jit(g)/amg.L0.presmooth/mul"
    assert programs.op_names() is None
    assert programs.scopes() == {"fusion.3": "amg.L0.presmooth",
                                 "pad.1": "krylov.CG.iter",
                                 "while.2": None}
    programs._reset()
    assert programs.scopes() == {}


def test_registry_is_bounded_and_keeps_no_solver_alive():
    programs._reset()
    A = gallery.poisson("5pt", 10, 10).init()
    slv = amgx.create_solver(Config.from_string(
        "solver=CG, max_iters=30, monitor_residual=1, tolerance=1e-6"))
    slv.setup(A)
    slv.solve(np.ones(A.num_rows))
    b = jnp.ones(A.num_rows)
    (solve_fn,) = slv._jit_cache.values()
    compiled = solve_fn.lower(slv.solve_data(), b,
                              jnp.zeros_like(b)).compile()
    sigs = [((100,), "float64", 1000 + n) for n in range(programs.KEEP + 3)]
    for sig in sigs:
        programs.register("CG.solve", sig, compiled)
    held = programs._registered()
    assert held == [("CG.solve", sig) for sig in sigs[-programs.KEEP:]]
    # a signature registered again takes its predecessor's place
    programs.register("CG.solve", sigs[-1], compiled)
    assert programs._registered() == held
    table = programs.scopes()
    assert "krylov.CG.iter" in set(table.values())
    ref = weakref.ref(slv)
    del slv, compiled, solve_fn
    gc.collect()
    assert ref() is None
    assert programs.scopes() == table


def test_scope_table_outlives_capi_solver_destroy():
    def ok(rc, *out):
        assert rc == capi.RC.OK
        return out[0] if len(out) == 1 else out

    programs._reset()
    ok(capi.AMGX_initialize())
    cfg = ok(*capi.AMGX_config_create(
        "solver=PCG, max_iters=40, monitor_residual=1, tolerance=1e-6, "
        "preconditioner(j)=BLOCK_JACOBI, j:max_iters=2"))
    rsc = ok(*capi.AMGX_resources_create_simple(cfg))
    mtx = ok(*capi.AMGX_matrix_create(rsc, "dDDI"))
    rhs = ok(*capi.AMGX_vector_create(rsc, "dDDI"))
    sol = ok(*capi.AMGX_vector_create(rsc, "dDDI"))
    A = gallery.poisson("5pt", 8, 8)
    n = A.num_rows
    ok(capi.AMGX_matrix_upload_all(
        mtx, n, A.nnz, 1, 1, np.asarray(A.row_offsets),
        np.asarray(A.col_indices), np.asarray(A.values), None))
    ok(capi.AMGX_vector_upload(rhs, n, 1, np.ones(n)))
    slv = ok(*capi.AMGX_solver_create(rsc, "dDDI", cfg))
    ok(capi.AMGX_solver_setup(slv, mtx))
    ok(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    ok(capi.AMGX_solver_destroy(slv))
    gc.collect()
    assert [label for label, _sig in programs._registered()] == [
        "PCG.solve"]
    assert "krylov.PCG.iter" in set(programs.scopes().values())
    for destroy, h in ((capi.AMGX_vector_destroy, sol),
                       (capi.AMGX_vector_destroy, rhs),
                       (capi.AMGX_matrix_destroy, mtx),
                       (capi.AMGX_resources_destroy, rsc),
                       (capi.AMGX_config_destroy, cfg)):
        ok(destroy(h))


# ---------------------------------------------------------------------------
# the benchmark's reader
# ---------------------------------------------------------------------------

MADE_UP_SCOPES = {
    "_dia_smooth_call.80": "amg.L0.presmooth",
    "pad.580": "amg.L0.prolong",
    "gather.3": "amg.L0",
    "_dia_smooth_call.81": "amg.L1.presmooth",
    "fusion.9": "amg.coarse",
    "multiply_reduce_fusion.16": "krylov.FGMRES.iter",
    "fusion.40": "refine.defect",
    "while.5": None,
}
MADE_UP_OP_TIME = {
    "_dia_smooth_call.80": 0.20, "pad.580": 0.10, "gather.3": 0.05,
    "_dia_smooth_call.81": 0.04, "fusion.9": 0.01,
    "multiply_reduce_fusion.16": 0.30, "fusion.40": 0.20,
    "while.5": 0.02,
    "convert_element_type.1": 0.08,        # an eager program's: unknown
}


def made_up_obs():
    return layer_metrics.Observed(
        ops=3, trace={"op_time": dict(MADE_UP_OP_TIME), "devices": 1,
                      "busy_s": sum(MADE_UP_OP_TIME.values())})


def test_scope_metrics_shares_add_to_100(monkeypatch, capsys):
    monkeypatch.setattr(scope_metrics, "program_scopes",
                        lambda: dict(MADE_UP_SCOPES))
    obs = made_up_obs()
    read = {name: layer_metrics.read(name, obs) for name in (
        "cycle.fine_level_busy_share", "cycle.glue_busy_share",
        "krylov.shell_busy_share", "device.unscoped_busy_share")}
    assert read["cycle.fine_level_busy_share"] == pytest.approx(35.0)
    assert read["cycle.glue_busy_share"] == pytest.approx(16.0)
    assert read["krylov.shell_busy_share"] == pytest.approx(50.0)
    assert read["device.unscoped_busy_share"] == pytest.approx(10.0)
    amg_all = scope_metrics.scope_share(obs, ["amg.*"])
    assert amg_all == pytest.approx(40.0)
    assert amg_all + read["krylov.shell_busy_share"] \
        + read["device.unscoped_busy_share"] == pytest.approx(100.0)
    # the per-level lines: printed once a run, whatever is read
    lines = capsys.readouterr().out.splitlines()
    assert lines == [
        "scope amg.L0 kernels=0.200000 glue=0.150000 ops=3",
        "scope amg.L1 kernels=0.040000 glue=0.000000 ops=1",
        "scope amg.coarse kernels=0.000000 glue=0.010000 ops=1",
        "scope krylov kernels=0.000000 glue=0.500000 ops=2",
        "scope unscoped kernels=0.000000 glue=0.100000 ops=2"]


@pytest.mark.parametrize("table", [None, {}])
def test_scope_metrics_without_a_table_leave_the_metric_out(
        monkeypatch, capsys, table):
    """A program that registered nothing (a control's entry) or has no
    `telemetry.programs` (the parent): None, and nothing printed."""
    monkeypatch.setattr(scope_metrics, "program_scopes", lambda: table)
    obs = made_up_obs()
    assert layer_metrics.read("cycle.glue_busy_share", obs) is None
    assert layer_metrics.read("device.unscoped_busy_share", obs) is None
    assert capsys.readouterr().out == ""
    # and a run that traced nothing
    assert layer_metrics.read("krylov.shell_busy_share",
                              layer_metrics.Observed()) is None


def test_host_stage_readers_are_deltas_per_operation():
    obs = layer_metrics.Observed(ops=4, counter_growth={
        "solve.stage_s.prepare": 0.08, "solve.stage_s.run": 4.0,
        "solve.stage_s.readback": 0.012, "solve.stage_s.report": 0.008,
        "matrix.refill_host_s": 36.0, "matrix.upload_s": 6.0,
        "matrix.upload_bytes": 8 * 2 ** 30, "matrix.refill_map.reuse": 4,
        "compile.trace_s": 2.0, "compile.lower_s": 1.0,
        "compile.backend_s": 3.0, "compile.programs": 9})
    assert layer_metrics.read("entry.solve_host_s", obs) == \
        pytest.approx(0.025)
    assert layer_metrics.read("step.refill_host_s", obs) == 9.0
    assert layer_metrics.read("step.upload_s", obs) == 1.5
    assert layer_metrics.read("step.compile_s", obs) == 1.5
    assert layer_metrics.read("step.upload_bytes", obs) == 2 * 2 ** 30
    assert layer_metrics.read("step.compiled_programs", obs) == 2.25
    assert layer_metrics.read("step.refill_map_reuses", obs) == 1.0
    # a program without the counters (the parent): left out, no raise
    bare = layer_metrics.Observed(ops=4, counter_growth={
        "solver.retrace.solve": 4})
    for name in ("entry.solve_host_s", "step.refill_host_s",
                 "step.upload_s", "step.compile_s", "step.upload_bytes",
                 "step.compiled_programs", "step.refill_map_reuses"):
        assert layer_metrics.read(name, bare) is None


def test_benchmark_selfcheck_passes_with_the_new_cell(capsys):
    selfcheck.main()
    out = capsys.readouterr().out
    assert "files: 10 cells, 5 end-to-end and 62 per-layer" in out
    assert out.rstrip().endswith("selfcheck ok")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "flagship-p7-256.solve-stream"
    assert cell in [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert cell in e2e["solve_s"]["workloads"]
    assert cell not in e2e["solve_p95_s"]["workloads"]
