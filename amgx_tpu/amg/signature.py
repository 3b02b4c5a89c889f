"""Static signature of a set-up hierarchy: what a traced solve program
read from it that is not a leaf of `solve_data()`.

A cached solve program (Solver._jit_cache) stays right for a rebuilt
hierarchy exactly when everything it was traced from, other than the
arrays it takes as arguments, is what it was. That has two halves:

- the OBSERVABLE half: the treedef of the hierarchy's solve-data tree
  (which carries the meta fields of CsrMatrix, StencilOperator and the
  other registered nodes: dia_offsets, grid_shape, num_rows, shifts ...)
  and every leaf's shape, dtype and weak type. Read from the tree
  BEFORE placement and precision casts (AMG._solve_tree) together with
  the cast policy, which decides the rest: no cast is issued and
  nothing is shipped for it;
- the PYTHON half: what the cycle reads from objects at trace time.
  Of the hierarchy the attributes amg/cycles.py and ops/smooth.py
  read; of each level, its smoother and the coarse solver every
  instance attribute, encoded by `_encode` (numbers, strings and
  tuples by value; arrays by shape and dtype; registered pytree nodes
  by treedef and leaf shapes; nested solvers by class and attributes;
  anything else by its class). A value-derived Python float on a
  solver (POLYNOMIAL's lmax) therefore changes the signature when the
  values do, and the program is dropped as before: the sweep errs to
  the side of a retrace.

The signature is plain tuples, strings, numbers and treedefs: it holds
no array, so keeping the old one across a re-setup pins no device
memory. Equality is the only question asked of it.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np

from ..config import Config
from ..solvers.base import Solver
from .hierarchy import AMG, AMGLevel

# what the cycle and the cast of solve_data read from the hierarchy
# object itself (amg/cycles.py, AMG.solve_data / cycle)
_AMG_ATTRS = ("algorithm", "cycle_name", "cycle_iters", "precision",
              "coarsest_sweeps", "diagnostics")

# instance attributes that are no input of a trace: the matrix and the
# config object (the first is the observable half's, the second is read
# into attributes at construction), clocks, the caches of programs, the
# kept solve-data tree (solve_data.py: the observable half's again), and
# `_reused`, which names the ROUTE a level was built by (reuse_structure
# sets it for create_coarse_matrix to read during setup) and nothing of
# the tree: with it in, a loop's first resetup differed from the setup
# by that flag alone and dropped a program that was still right
_NOT_TRACED = frozenset({
    "A", "cfg", "setup_time", "_jit_cache", "_batched",
    "_batched_wrappers", "_color_steps", "_geo_transfers", "_reused",
    "_data_cache", "_swell_steps", "_dia_smooth"})


def _aval(x):
    return (tuple(np.shape(x)), str(x.dtype),
            bool(getattr(x, "weak_type", False)))


def _tree(tree):
    """(treedef, leaf avals) of a pytree; a leaf that is no array (a
    Python scalar passed as an argument is traced, not baked) counts by
    its type."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        _aval(l) if hasattr(l, "dtype") else type(l).__name__
        for l in leaves)


def _attrs(obj, depth):
    return tuple((k, _encode(v, depth + 1))
                 for k, v in sorted(vars(obj).items())
                 if k not in _NOT_TRACED)


def _encode(v: Any, depth: int = 0):
    """A hashable stand-in for an attribute a trace may have read."""
    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        return v
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, (np.ndarray, jax.Array)):
        return ("array",) + _aval(v)
    if depth > 8 or isinstance(v, Config):
        return type(v).__name__
    if isinstance(v, (tuple, list)):
        return tuple(_encode(x, depth + 1) for x in v)
    if isinstance(v, (set, frozenset)):
        return tuple(sorted(repr(_encode(x, depth + 1)) for x in v))
    if isinstance(v, dict):
        return tuple(sorted((repr(k), _encode(x, depth + 1))
                            for k, x in v.items()))
    if isinstance(v, AMG):
        return static_signature(v)
    if isinstance(v, (Solver, AMGLevel)):
        return (type(v).__qualname__, _attrs(v, depth))
    leaves, treedef = jax.tree_util.tree_flatten(v)
    if not (len(leaves) == 1 and leaves[0] is v):
        return ("tree",) + _tree(v)       # a registered pytree node
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return (type(v).__qualname__, tuple(
            (f.name, _encode(getattr(v, f.name), depth + 1))
            for f in dataclasses.fields(v)))
    return type(v).__qualname__


def static_signature(amg) -> tuple:
    """The static signature of a set-up AMG hierarchy (see the module
    docstring). Host work only; assembles what the next solve_data()
    ships or casts (the smoothers' and the coarse solver's trees, which
    they keep for that call) and nothing else."""
    # the tree first: level_data() memoizes the transfer and smoother
    # slabs the attribute sweep below then finds on both sides
    observable = _tree(amg._solve_tree())
    levels = tuple(
        (_encode(level),
         # the full operator's size: the report's level table reads it,
         # and the slim view in the tree may have dropped `values`
         int(level.A.num_rows), tuple(np.shape(level.A.values)),
         amg._sweeps(k, True), amg._sweeps(k, False))
        for k, level in enumerate(amg.levels))
    return (observable,
            tuple((a, getattr(amg, a)) for a in _AMG_ATTRS),
            str(amg.precision_policy.coarse_dtype),
            amg._ship_device is None,
            levels, _encode(amg.coarse_solver))
