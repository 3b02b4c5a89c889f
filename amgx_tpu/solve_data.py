"""A set-up node of a solver tree owns its solve-data tree.

`solve_data()` is the argument list of every solve program: a pytree of
the device arrays the traced solve reads, assembled from the node's own
leaves and its children's trees (a Krylov solver's preconditioner, the
AMG wrapper's hierarchy, the hierarchy's smoothers and coarse solver).
Assembling it is host work, and where a leaf is derived on the spot
(a precision cast, a scalar turned into an array) an eager device
program as well; a solve needs neither, because nothing in the tree
changes between one (re)setup and the next.

So every node keeps what it assembled (`_data_cache`) and hands the same
object to every caller until it dies:

- with the node's own `setup` / `resetup` (or whatever else replaces one
  of its leaves: `drop_solve_data()`), and NOT with the compiled solve
  programs, which a resetup may keep while every value leaf is new;
- with a child's: a node keeps each child's tree beside its own and
  serves its own only while every child still owns that very object (a
  pointer compare down the tree, no dispatch), so a `resetup` called on
  an inner solver directly still reaches the top.

A subclass says what its tree is made of in `_build_solve_data()` and who
its children are in `_solve_data_children()`; `solve_data()` is not
overridden. (A subclass that does override it is served as before, never
memoized, and so is whatever sits above it.)

`solve_data()` is the caller's entry, and the one place that counts:
`solve_data.build` when the call had to assemble any part of the tree,
`solve_data.reuse` when it was served the kept tree whole. What reads a
node as a part of something else (a parent's assembly, the hierarchy's
static signature, the ship of a finished level) takes
`solve_data_part()`, the same tree from the same memo, uncounted.
"""
from __future__ import annotations

from .telemetry import metrics as _tm
from .telemetry.spans import span


class SolveDataOwner:
    """The keeping and serving of a node's solve-data tree (see the
    module docstring)."""

    _data_cache = None      # (tree, ((child, the child's tree), ...))

    def _build_solve_data(self):
        """Assemble this node's tree; the children's by their
        `solve_data_part()`."""
        raise NotImplementedError

    def _solve_data_children(self) -> tuple:
        """The nodes whose trees `_build_solve_data` reads."""
        return ()

    def drop_solve_data(self):
        """What this node kept is no longer its tree (a leaf was, or is
        about to be, replaced). Called before the new leaves are made,
        so that the old ones do not outlive their use."""
        self._data_cache = None

    def solve_data_kept(self):
        """The kept tree where it is still this node's (every child
        still owns the tree that was kept of it), else None. Host
        pointer compares only."""
        cache = self._data_cache
        if cache is not None and all(
                child.solve_data_kept() is tree for child, tree in cache[1]):
            return cache[0]
        return None

    def solve_data_part(self):
        """This node's tree as a part of another's: kept, else
        assembled and kept."""
        if type(self).solve_data is not SolveDataOwner.solve_data:
            return self.solve_data()    # an override: never kept
        tree = self.solve_data_kept()
        if tree is None:
            tree = self._build_solve_data()
            self._data_cache = (tree, tuple(
                (c, c.solve_data_part())
                for c in self._solve_data_children()))
        return tree

    def solve_data(self):
        """The pytree of device data the jitted solve needs: the same
        object, with the same leaves, from one (re)setup to the next."""
        tree = self.solve_data_kept()
        if tree is not None:
            _tm.inc("solve_data.reuse")
            return tree
        _tm.inc("solve_data.build")
        with span("solve_data.build"):
            return self.solve_data_part()
