"""Unsmoothed-aggregation AMG level.

Analog of src/aggregation/aggregation_amg_level.cu (2654 LoC): the
selector builds an `aggregates` map, restriction/prolongation are
segment-sum / gather with that map (no explicit CSR transfer operators),
and the coarse matrix is the COO-relabel Galerkin product.

GEO (structured pairing) levels additionally know the grid geometry:
restriction/prolongation become axis reshape-sums / broadcasts — pure
dense data movement with no gather/scatter at all (the TPU-optimal
shape) — and the coarse matrix inherits the coarse grid annotation so
the whole hierarchy stays banded/DIA.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ... import registry
from ...config import Config
from ...matrix import CsrMatrix
from ..hierarchy import AMGLevel
from . import selectors  # noqa: F401  (registers selectors)
from . import transfer
from .galerkin import (coarse_a_from_aggregates, prolongate_corr,
                       restrict_vector)


@registry.amg_levels.register("AGGREGATION")
class AggregationAMGLevel(AMGLevel):
    algorithm = "AGGREGATION"

    geo_axes = None          # set when the selector pairs geometrically
    geo_fine_shape = None
    geo_coarse_shape = None

    def create_coarse_vertices(self):
        from ...profiling import trace_region
        sel_name = str(self.cfg.get("selector", self.scope))
        sel = registry.aggregation_selectors.create(
            sel_name, self.cfg, self.scope)
        with trace_region(f"amg.L{self.level_index}.selector"):
            self.aggregates, self.coarse_size = sel.set_aggregates(self.A)
        if getattr(sel, "pair_axes", None) is not None and \
                not self.A.is_block:
            self.geo_axes = sel.pair_axes
            self.geo_fine_shape = sel.fine_shape
            self.geo_coarse_shape = sel.coarse_shape

    def create_coarse_matrix(self) -> CsrMatrix:
        from ...ops import spgemm
        from ...profiling import trace_region
        k = self.level_index
        planned = spgemm.plan_enabled(self.cfg, self.scope)
        if self.geo_axes is not None:
            if planned:
                # planned GEO route: the memoized GeoRapPlan skips
                # every symbolic step; the numeric phase is one jitted
                # program feeding geo_assemble_dia's output shape next
                # to the device-structure cache
                from .galerkin import get_geo_plan
                with trace_region(f"amg.L{k}.rap_plan"):
                    plan = get_geo_plan(self.A, self.geo_fine_shape,
                                        self.geo_axes,
                                        self.geo_coarse_shape)
                if plan is not None:
                    with trace_region(f"amg.L{k}.rap_values"):
                        Ac = plan.coarse_matrix(self.A)
                    if Ac is not None:
                        self._geo_plan_memo = (plan,)
                        return Ac
            else:
                from .galerkin import (geo_assemble_dia,
                                       geo_coarse_values)
                with trace_region(f"amg.L{k}.galerkin"):
                    pre = geo_coarse_values(self.A,
                                            self.geo_fine_shape,
                                            self.geo_axes,
                                            self.geo_coarse_shape)
                if pre is not None:     # structured sort-free Galerkin
                    # the DIA pack is the coarse operator's LAYOUT
                    # build — timed as such, not hidden inside the
                    # galerkin bucket
                    with trace_region(f"amg.L{k}.layout"):
                        return geo_assemble_dia(pre[0], pre[1],
                                                self.geo_coarse_shape)
        if planned and not self.A.is_block \
                and self.aggregates is not None:
            Ac = self._relabel_planned(k)
            if Ac is not None:
                if self.geo_coarse_shape is not None:
                    Ac = dataclasses.replace(
                        Ac, grid_shape=self.geo_coarse_shape)
                return Ac
        with trace_region(f"amg.L{k}.galerkin"):
            Ac = coarse_a_from_aggregates(self.A, self.aggregates,
                                          self.coarse_size)
        if self.geo_coarse_shape is not None:
            Ac = dataclasses.replace(Ac, grid_shape=self.geo_coarse_shape)
        return Ac

    def _relabel_planned(self, k: int):
        """Plan-split relabel Galerkin: structure memoized on the level
        (carried across structure resetups — the aggregates map is the
        pattern) with the digest cache catching warm full setups of
        the same pattern; value phase through ops/spgemm.rap_values."""
        from ...ops import spgemm
        from ...profiling import trace_region
        plan = None
        # pattern proven by IDENTITY of A's structure arrays (retained
        # in the memo) — a same-nnz permuted pattern misses and takes
        # the content-keyed digest cache instead (see the classical
        # twin for the full rationale)
        memo = getattr(self, "_rap_plan_memo", None)
        if memo is not None and memo[0] is self.aggregates \
                and memo[1] is self.A.row_offsets \
                and memo[2] is self.A.col_indices \
                and memo[3] == self.A.has_external_diag:
            plan = memo[4]
        if plan is None:
            with trace_region(f"amg.L{k}.rap_plan"):
                plan = spgemm.get_agg_plan(self.A, self.aggregates,
                                           self.coarse_size)
            if plan is not None:
                self._rap_plan_memo = (
                    self.aggregates, self.A.row_offsets,
                    self.A.col_indices, self.A.has_external_diag,
                    plan)
        if plan is None:
            return None
        with trace_region(f"amg.L{k}.rap_values"):
            return spgemm.plan_coarse_matrix(plan, self.A)

    def reuse_structure(self, old):
        """structure_reuse_levels: keep the aggregates map; the Galerkin
        relabel-sum then runs against the new coefficients. The RAP
        plans ride along (same aggregates object = same pattern), so a
        structure resetup does zero symbolic RAP work."""
        self.aggregates = old.aggregates
        self.coarse_size = old.coarse_size
        self.geo_axes = old.geo_axes
        self.geo_fine_shape = old.geo_fine_shape
        self.geo_coarse_shape = old.geo_coarse_shape
        for attr in ("_rap_plan_memo", "_geo_plan_memo"):
            memo = getattr(old, attr, None)
            if memo is not None:
                setattr(self, attr, memo)

    def structure_snapshot(self):
        if self.coarse_size is None:
            return None
        meta = {"num_rows": int(self.A.num_rows),
                "coarse_size": int(self.coarse_size),
                "geo_axes": None if self.geo_axes is None
                else list(self.geo_axes),
                "geo_fine_shape": None if self.geo_fine_shape is None
                else list(self.geo_fine_shape),
                "geo_coarse_shape": None if self.geo_coarse_shape is None
                else list(self.geo_coarse_shape)}
        arrays = {}
        if self.aggregates is not None:
            arrays["aggregates"] = np.asarray(self.aggregates)
        return meta, arrays

    @classmethod
    def structure_restore(cls, meta, arrays):
        g = cls._ghost(meta["num_rows"])
        g.coarse_size = int(meta["coarse_size"])
        g.aggregates = arrays.get("aggregates")
        g.geo_axes = None if meta["geo_axes"] is None \
            else tuple(meta["geo_axes"])
        g.geo_fine_shape = None if meta["geo_fine_shape"] is None \
            else tuple(meta["geo_fine_shape"])
        g.geo_coarse_shape = None if meta["geo_coarse_shape"] is None \
            else tuple(meta["geo_coarse_shape"])
        return g

    def level_data(self):
        d = super().level_data()
        if self.geo_axes is None:
            # structured (paired) levels restrict/prolongate by reshape
            # pair-sums — the aggregates map is setup-only state there,
            # and carrying it in the solve pytree would re-upload an
            # n-sized host array per jitted call (the GEO selector keeps
            # it host-resident on purpose)
            d["aggregates"] = self.aggregates
        return d

    def restrict(self, data, r):
        if "R" in data:       # distributed: explicit sharded R = P^T
            from ...ops.spmv import spmv
            return spmv(data["R"], r)
        if self.geo_axes is not None:
            return transfer.restrict(r, self.geo_fine_shape, self.geo_axes)
        return restrict_vector(data["aggregates"], self.coarse_size, r,
                               self.A.block_dimx)

    def prolongate(self, data, xc):
        if "P" in data:       # distributed: explicit sharded P
            from ...ops.spmv import spmv
            return spmv(data["P"], xc)
        if self.geo_axes is not None:
            return transfer.prolongate_xla(xc, self.geo_fine_shape,
                                           self.geo_axes)
        return prolongate_corr(data["aggregates"], xc, self.A.block_dimx)

    def prolongate_correct(self, data, x, xc):
        """x + P xc (the cycle's coarse-grid correction): a GEO level
        does the add inside the transfer's own pass (transfer.py)."""
        if "P" in data or self.geo_axes is None:
            return x + self.prolongate(data, xc)
        return transfer.prolong_correct(x, xc, self.geo_fine_shape,
                                        self.geo_axes)

    def geo_transfer_road(self, dtype):
        """"onepass" / "xla": the schedule this level's two transfers
        take in an unbatched single-device cycle of `dtype` vectors
        (transfer.road); None for a level that is not GEO."""
        if self.geo_axes is None:
            return None
        return transfer.road(self.geo_fine_shape, self.geo_axes, dtype)
