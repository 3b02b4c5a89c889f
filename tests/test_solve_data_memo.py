"""A set-up solver owns its solve-data tree (ISSUE 41, solve_data.py).

- `solve_data()` hands every caller the same object with the same
  leaves until the next (re)setup, and a steady solve assembles and
  dispatches nothing for it;
- it dies with its node's `setup` / `resetup`, not with the compiled
  solve: after a value change every value-carrying leaf is a new array
  on all three routes (full, structure, value-only), and what is solved
  with is what a fresh `setup` gives;
- a parent never serves a stale child: a `resetup` called on an inner
  solver alone turns the outer tree over;
- `solve_data.build` / `.reuse` count it: 1 / n-1 over n solves, 1 a
  step over a time loop, and no node assembles twice a (re)setup;
- nothing of it reaches the answer: `x` is bit-identical to what the
  same calls give with every kept tree dropped before each solve (the
  parent's behaviour).
"""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery, presets
from amgx_tpu.amg.hierarchy import AMG
from amgx_tpu.config import Config
from amgx_tpu.solve_data import SolveDataOwner
from amgx_tpu.telemetry import metrics
from amgx_tpu.telemetry.report import _amg_of

amgx.initialize()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("solve_data.build", "solve_data.reuse",
            "solver.retrace.solve")


def _bench(name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)["solver"]


def _flagship(extra=""):
    return lambda: amgx.create_solver(
        Config.from_string(presets.FLAGSHIP + extra))


def _classical():
    s = _bench("classical-p7-128")
    cfg = Config.from_dict(s["json"])
    cfg.parse_parameter_string(s["add"])
    return amgx.create_solver(cfg)


def _hpcg():
    return amgx.create_solver(
        Config.from_string(_bench("hpcg-p27-192")["options"]))


def _smoother():
    return amgx.create_solver(Config.from_string(
        "solver=JACOBI_L1, max_iters=100, monitor_residual=1,"
        " tolerance=0.3, convergence=RELATIVE_INI, norm=L2"))


# name -> (solver factory, stencil, grid edge, built on the host)
CASES = {
    "flagship": (_flagship(), "7pt", 16, False),
    "classical-host-built": (_classical, "7pt", 12, True),
    "hpcg": (_hpcg, "27pt", 16, False),
    "bare-smoother": (_smoother, "7pt", 8, False),
}


@pytest.fixture(params=sorted(CASES))
def case(request, monkeypatch):
    make, stencil, n, host_built = CASES[request.param]
    if host_built:
        # a CPU rig's stand-in for a hierarchy built on the host and
        # shipped to an accelerator (on the chip: every classical one)
        monkeypatch.setattr(AMG, "_host_setup_device",
                            lambda self, A: jax.devices("cpu")[0])
    A = gallery.poisson(stencil, n, n, n).init()
    # as a time loop holds it: uploaded by with_values
    A = A.with_values(np.asarray(A.values))
    return make, A, host_built


def _rhs(A, seed=41):
    return np.random.default_rng(seed).standard_normal(A.num_rows)


def _scaled(A, f):
    return A.with_values(f * np.asarray(A.values))


def _grew(before):
    after = metrics.snapshot()
    return {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def _owners(slv):
    """Every node of the solver tree that keeps a tree of its own."""
    seen, stack = [], [slv]
    while stack:
        node = stack.pop()
        if isinstance(node, SolveDataOwner) and \
                not any(node is s for s in seen):
            seen.append(node)
            stack.extend(node._solve_data_children())
    return seen


def _drop_all(slv):
    for node in _owners(slv):
        node.drop_solve_data()


def _value_leaves(tree):
    """The leaves a value change must replace: floating-point arrays of
    more than one element, less the transfer operators (`P`, `R`),
    which a structure-reuse rebuild keeps WITH the weights of the
    first setup, on the device too (PR 39)."""
    kept = ("P", "R")
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if any(k in kept for k in keys):
            continue
        if hasattr(leaf, "dtype") and leaf.size > 1 and \
                jnp.issubdtype(leaf.dtype, jnp.inexact):
            out.append(leaf)
    return out


def _same_leaves(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(x is y for x, y in zip(la, lb))


# -- (1) one tree from a setup to the next --------------------------------
def test_the_same_tree_until_the_next_setup(case):
    make, A, _ = case
    slv = make()
    slv.setup(A)
    tree = slv.solve_data()
    assert slv.solve_data() is tree
    leaves = jax.tree.leaves(tree)
    b = _rhs(A)
    x1 = slv.solve(b).x
    assert slv.solve_data() is tree
    x2 = slv.solve(b).x
    assert slv.solve_data() is tree
    assert all(p is q for p, q in zip(leaves, jax.tree.leaves(tree)))
    assert np.array_equal(np.asarray(x1), np.asarray(x2))
    # every node below serves its own the same way
    for node in _owners(slv):
        assert node.solve_data() is node.solve_data()


def test_counters_over_solves_and_steps(case):
    make, A, _ = case
    slv = make()
    slv.setup(A)
    b = _rhs(A)
    before = metrics.snapshot()
    n = 4
    for _ in range(n):
        assert slv.solve(b).converged
    grew = _grew(before)
    assert (grew["solve_data.build"], grew["solve_data.reuse"]) == \
        (1, n - 1)
    # a time loop as the benchmark's entries drive it: the caller's
    # wait for the hierarchy and the solve's prepare share one build
    for step in range(3):
        before = metrics.snapshot()
        slv.resetup(_scaled(A, 1.1 + 0.2 * step))
        jax.block_until_ready(slv.solve_data())
        assert slv.solve(b).converged
        grew = _grew(before)
        assert (grew["solve_data.build"],
                grew["solve_data.reuse"]) == (1, 1), step


def test_no_node_assembles_twice_a_setup(case, monkeypatch):
    """What the counters cannot see (they count the caller's calls): the
    static signature's reading inside a (re)setup, the caller's wait
    and the solve's prepare make each node's tree ONCE between them."""
    make, A, _ = case
    built = []                      # the nodes, kept alive: no id reuse
    plain = SolveDataOwner.solve_data_part

    def counting(self):
        before = self._data_cache
        tree = plain(self)
        if self._data_cache is not before:
            built.append(self)
        return tree

    monkeypatch.setattr(SolveDataOwner, "solve_data_part", counting)
    slv = make()
    b = _rhs(A)
    for step in range(3):
        del built[:]
        if step == 0:
            slv.setup(A)
        else:
            slv.resetup(_scaled(A, 1.0 + 0.3 * step))
        jax.block_until_ready(slv.solve_data())
        assert slv.solve(b).converged
        assert slv.solve(b).converged
        owners = _owners(slv)
        assert all(sum(n is o for n in built) == 1 for o in owners), step
        assert len(built) == len(owners), step


def test_a_steady_solve_puts_nothing_on_the_device(case):
    """A steady solve, `solve.prepare` to the report, makes no
    host-to-device transfer other than what depends on b / x0 (both
    handed over on the device here; `x0=None` is a `jnp.zeros_like(b)`,
    whose fill value is a transfer of one scalar). The CPU backend
    honours the guard: the parent's per-solve placeholders (the
    `DevicePut` of the benchmark's idle gaps) fail it."""
    make, A, _ = case
    slv = make()
    slv.setup(A)
    b = jnp.asarray(_rhs(A))
    x0 = jnp.zeros_like(b)
    want = slv.solve(b, x0)
    on_device = all(isinstance(leaf, jax.Array)
                    for leaf in jax.tree.leaves(slv.solve_data()))
    before = metrics.snapshot()
    with jax.transfer_guard_host_to_device("disallow"):
        if on_device:
            got = slv.solve(b, x0)
        else:
            # a smoother set up on host values keeps numpy leaves (the
            # program's call uploads them, as it did): the tree alone
            slv.solve_data()
    if on_device:
        assert np.array_equal(np.asarray(got.x), np.asarray(want.x))
    grew = _grew(before)
    assert (grew["solve_data.build"], grew["solve_data.reuse"]) == (0, 1)


# -- (2) it dies with the node's (re)setup --------------------------------
ROUTES = {
    # route -> (options added to the flagship, counter that names it)
    "full": ("", "amg.setup.full"),
    "structure": (", amg:structure_reuse_levels=1",
                  "amg.resetup.structure"),
    "value-only": (", amg:structure_reuse_levels=-1",
                   "amg.resetup.value"),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_new_values_give_new_leaves_on_every_route(route):
    extra, counter = ROUTES[route]
    n = 16
    A = gallery.poisson("7pt", n, n, n).init()
    A = A.with_values(np.asarray(A.values))
    b = _rhs(A)
    slv = _flagship(extra)()
    slv.setup(A)
    assert slv.solve(b).converged
    old_tree = slv.solve_data()
    old = _value_leaves(old_tree)
    assert len(old) >= 8
    A2 = _scaled(A, 1.7)
    before = metrics.snapshot()
    slv.resetup(A2)
    assert metrics.snapshot()[counter] - before.get(counter, 0) == 1
    new_tree = slv.solve_data()
    assert new_tree is not old_tree
    new = _value_leaves(new_tree)
    assert len(new) == len(old)
    assert not any(p is q for p in new for q in old)
    got = slv.solve(b)
    # what a fresh setup on the new matrix gives
    fresh = _flagship(extra)()
    fresh.setup(A2)
    want = fresh.solve(b)
    assert got.iterations == want.iterations
    if route == "value-only":
        # the value phase is one fused program, a setup's is eager:
        # the same sums in another order
        np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                                   rtol=1e-9, atol=1e-12)
    else:
        assert np.array_equal(np.asarray(got.x), np.asarray(want.x))


def test_new_values_give_new_leaves_on_a_host_built_hierarchy(
        monkeypatch):
    """The structure route of a host-built hierarchy: every leaf that
    carries the step's values is shipped anew, the kept transfer
    operators stay the device objects they were."""
    monkeypatch.setattr(AMG, "_host_setup_device",
                        lambda self, A: jax.devices("cpu")[0])
    s = _bench("classical-reuse-p7-128")
    cfg = Config.from_dict(s["json"])
    cfg.parse_parameter_string(s["add"])
    slv = amgx.create_solver(cfg)
    A = gallery.poisson("7pt", 12, 12, 12).init()
    A = A.with_values(np.asarray(A.values))
    slv.setup(A)
    amg = _amg_of(slv)
    assert amg._ship_device is not None
    old_tree = slv.solve_data()
    old = _value_leaves(old_tree)
    before = metrics.snapshot()
    slv.resetup(_scaled(A, 1.3))
    assert metrics.snapshot()["amg.resetup.structure"] - before.get(
        "amg.resetup.structure", 0) == 1
    new = _value_leaves(slv.solve_data())
    assert len(new) == len(old) > 0
    assert not any(p is q for p in new for q in old)
    assert slv.solve(_rhs(A)).converged


def test_setup_with_another_matrix_turns_the_tree_over(case):
    make, A, _ = case
    slv = make()
    slv.setup(A)
    tree = slv.solve_data()
    slv.setup(_scaled(A, 2.0))
    again = slv.solve_data()
    assert again is not tree
    assert not any(p is q for p in _value_leaves(again)
                   for q in _value_leaves(tree))


# -- (3) a parent never serves a stale child ------------------------------
def test_an_inner_resetup_alone_reaches_the_top():
    n = 16
    A = gallery.poisson("7pt", n, n, n).init()
    A = A.with_values(np.asarray(A.values))
    b = _rhs(A)
    slv = _flagship()()
    slv.setup(A)
    tree = slv.solve_data()
    inner_tree = tree["inner"]
    # the f32 FGMRES under the shell, re-set-up behind the shell's back
    # on the operator the shell would have handed it
    A2 = _scaled(A, 1.9)
    inner = slv.preconditioner
    inner.resetup(A2.astype(jnp.float32))
    assert inner.solve_data() is not inner_tree
    top = slv.solve_data()
    assert top is not tree
    assert top["inner"] is inner.solve_data()
    # one level further down: a smoother of the hierarchy
    amg = _amg_of(slv)
    sm = amg.levels[0].smoother
    kept = slv.solve_data()
    sm.resetup(sm.A)
    assert slv.solve_data() is not kept
    assert slv.solve_data() is slv.solve_data()
    assert slv.solve(b).x is not None


def test_a_child_that_overrides_solve_data_is_read_through_it():
    """A solver class from outside the package may still say its tree
    by overriding `solve_data()`: as a preconditioner it is read through
    the override, as before, and nothing above it is kept."""
    from amgx_tpu.solvers.relaxation import JacobiL1Solver

    class Overriding(JacobiL1Solver):
        calls = 0

        def solve_data(self):
            type(self).calls += 1
            return self._build_solve_data()

    A = gallery.poisson("7pt", 8, 8, 8).init()
    slv = amgx.create_solver(Config.from_string(
        "solver=PCG, max_iters=50, monitor_residual=1, tolerance=1e-8,"
        " preconditioner=JACOBI_L1"))
    slv.preconditioner = Overriding(slv.cfg, "default")
    slv.preconditioner._owns_scaling = False
    slv.setup(A)
    first = slv.solve_data()
    assert Overriding.calls >= 1
    assert slv.solve_data() is not first
    assert slv.solve(_rhs(A)).converged


# -- (4) nothing of it reaches the answer ---------------------------------
def test_ten_solves_bit_identical_to_the_parents(case):
    """The golden is made in this process by the parent's behaviour:
    every kept tree dropped before each solve, so that each assembles
    its own."""
    make, A, _ = case
    rng = np.random.default_rng(7)
    bs = [rng.standard_normal(A.num_rows) for _ in range(5)]
    factors = (None, None, 1.25, None, None, 1.6, None, None, None, None)

    def run(memo: bool):
        slv = make()
        slv.setup(A)
        xs, iters = [], []
        for k, f in enumerate(factors):
            if f is not None:
                slv.resetup(_scaled(A, f))
            if not memo:
                _drop_all(slv)
            res = slv.solve(bs[k % len(bs)])
            xs.append(np.asarray(res.x))
            iters.append(int(res.iterations))
        return xs, iters

    before = metrics.snapshot()
    golden, golden_iters = run(memo=False)
    assert _grew(before)["solve_data.build"] == len(factors)
    before = metrics.snapshot()
    got, iters = run(memo=True)
    assert _grew(before)["solve_data.build"] == 3   # setup + 2 resetups
    assert iters == golden_iters
    for k, (x, want) in enumerate(zip(got, golden)):
        assert np.array_equal(x, want), k
