"""Classical Ruge-Stuben AMG level.

Analog of src/classical/classical_amg_level.cu (987 LoC): strength of
connection -> CF-splitting (selector) -> interpolation P -> R = P^T ->
Galerkin RAP (createCoarseVertices :213, createCoarseMatrices :254-341).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ... import registry
from ...matrix import CsrMatrix
from ...ops.spgemm import galerkin_rap
from ...ops.spmv import spmv
from ...ops.transpose import transpose
from ..hierarchy import AMGLevel
from . import strength as _strength  # noqa: F401
from . import selectors as _selectors  # noqa: F401
from . import interpolators as _interpolators  # noqa: F401


@registry.amg_levels.register("CLASSICAL")
class ClassicalAMGLevel(AMGLevel):
    """Shared strength -> CF-split -> P -> R=P^T -> RAP level flow.
    Subclasses (energymin) retarget the selector/interpolator registries
    via the class attributes below."""

    algorithm = "CLASSICAL"
    selector_param = "selector"
    selector_fallback = "PMIS"
    interpolator_registry = registry.interpolators
    interpolator_param = "interpolator"
    interpolator_fallback = "D1"

    def create_coarse_vertices(self):
        """Strength + CF-split (markCoarseFinePoints analog,
        classical_amg_level.cu:345)."""
        if self.A.is_block:
            from ...errors import BadParametersError
            raise BadParametersError(
                f"{self.algorithm} AMG supports scalar matrices only (the "
                "reference has the same restriction); use "
                "algorithm=AGGREGATION for block matrices")
        from ...profiling import trace_region
        cfg, scope = self.cfg, self.scope
        st = registry.strength.create(str(cfg.get("strength", scope)),
                                      cfg, scope)
        with trace_region(f"amg.L{self.level_index}.strength"):
            self.strong = st.strong_mask(self.A)
        sel_name = str(cfg.get(self.selector_param, scope))
        # aggressive coarsening on the first `aggressive_levels` levels
        aggressive = self.level_index < int(cfg.get("aggressive_levels",
                                                    scope))
        if aggressive:
            agg_sel = str(cfg.get("aggressive_selector", scope))
            if agg_sel == "DEFAULT":
                agg_sel = "AGGRESSIVE_" + sel_name if not \
                    sel_name.startswith("AGGRESSIVE") else sel_name
            sel_name = agg_sel
        if not registry.classical_selectors.has(sel_name):
            sel_name = self.selector_fallback
        sel = registry.classical_selectors.create(sel_name, cfg, scope)
        with trace_region(f"amg.L{self.level_index}.cfsplit"):
            self.cf_map = sel.mark_coarse_fine_points(self.A, self.strong)
            self.coarse_size = int(jnp.sum(self.cf_map == 1))
        self._aggressive = aggressive

    def create_coarse_matrix(self) -> CsrMatrix:
        """P (interpolator), R = P^T, RAP
        (computeProlongationOperator :406, computeRestrictionOperator
        :441, csr_galerkin_product)."""
        from ...profiling import trace_region
        if getattr(self, "_reused", False):
            # structure reuse: transfer operators kept, only the
            # Galerkin product sees the new coefficients (the RAP plan
            # rides the reuse — zero symbolic work, value phase only)
            return self._galerkin_rap()
        cfg, scope = self.cfg, self.scope
        interp_name = str(cfg.get(self.interpolator_param, scope))
        if self._aggressive:
            interp_name = str(cfg.get("aggressive_interpolator", scope))
        if not self.interpolator_registry.has(interp_name):
            interp_name = self.interpolator_fallback
        interp = self.interpolator_registry.create(interp_name, cfg, scope)
        interp.level_index = self.level_index   # names its truncate leaf
        # host path: ell='auto' gives P and R the windowed-ELL (SWELL)
        # layout, the Pallas gather kernel's storage — transfer operators
        # are the other half of the unstructured cycle's SpMV traffic.
        # setup_backend=device also uses ell='auto': the DIA/ELL layouts
        # build from the device CSR directly (_choose_layout's jnp path,
        # no host round trip). Only the legacy in-place accelerator path
        # keeps ell='never' (its layout probe would block per level).
        from ...matrix import device_setup_forced, host_resident
        k = self.level_index
        with trace_region(f"amg.L{k}.interp"):
            P = interp.generate(self.A, self.cf_map, self.strong)
        ell = "auto" if device_setup_forced() or host_resident(
            P.row_offsets, P.col_indices, P.values) else "never"
        from ..hierarchy import laid_out
        with trace_region(f"amg.L{k}.layoutP", args=(why := {})):
            self.P = laid_out(P, why, lambda M: M.init(ell=ell))
        with trace_region(f"amg.L{k}.transposeR", args=(why := {})):
            self.R = laid_out(transpose(self.P), why,
                              lambda M: M.init(ell=ell))
        return self._galerkin_rap()

    def _galerkin_rap(self) -> CsrMatrix:
        """RAP through the plan split (ops/spgemm.py): the structure
        phase is memoized on the level (structure resetups carry it —
        P/R survive with their values) and in the digest-keyed cache
        (warm full setups of the same pattern hit it), so only the
        VALUE phase runs per setup — through the slab or the host
        route regardless of backend forcing. The plan
        lookup precedes the host-native dispatch on purpose: a warm
        host setup used to rebuild the whole product from numpy even
        when the pattern was already planned. spgemm_plan=0 (or
        ineligible operands) short-circuits to the eager
        `galerkin_rap` composition, bit-for-bit."""
        from ...ops import spgemm
        from ...profiling import trace_region
        k = self.level_index
        if spgemm.plan_enabled(self.cfg, self.scope) \
                and not self.A.is_block:
            plan = None
            # the memo shortcut must prove the PATTERN unchanged, not
            # just the sizes: A's structure arrays are compared by
            # identity (retained in the memo — id() alone could alias
            # a freed array). A value-splice resetup keeps the objects
            # (and a planned product's output structure arrays are the
            # plan's own cached uploads, identical across resetups);
            # anything else falls through to the digest cache, which
            # keys on content — a same-nnz permuted pattern can never
            # be served a stale plan.
            memo = getattr(self, "_rap_plan_memo", None)
            if memo is not None and memo[0] is self.P \
                    and memo[1] is self.R \
                    and memo[2] is self.A.row_offsets \
                    and memo[3] is self.A.col_indices \
                    and memo[4] == self.A.has_external_diag:
                plan = memo[5]
            if plan is None:
                with trace_region(f"amg.L{k}.rap_plan"):
                    plan = spgemm.get_rap_plan(self.R, self.A, self.P)
                if plan is not None:
                    self._rap_plan_memo = (
                        self.P, self.R, self.A.row_offsets,
                        self.A.col_indices, self.A.has_external_diag,
                        plan)
            if plan is not None:
                with trace_region(f"amg.L{k}.rap_values"):
                    return spgemm.plan_coarse_matrix(plan, self.A,
                                                     self.R, self.P)
        with trace_region(f"amg.L{k}.rap"):
            return galerkin_rap(self.R, self.A, self.P)

    def reuse_structure(self, old):
        """structure_reuse_levels: keep strength/CF-split and the
        transfer operators from the prior setup."""
        self.strong = old.strong
        self.cf_map = old.cf_map
        self.coarse_size = old.coarse_size
        self._aggressive = old._aggressive
        self.P = old.P
        self.R = old.R
        # the RAP plan is a function of (A pattern, P, R) — all kept by
        # structure reuse — so a resetup's Galerkin is value-phase only
        memo = getattr(old, "_rap_plan_memo", None)
        if memo is not None:
            self._rap_plan_memo = memo
        self._reused = True

    def structure_snapshot(self):
        P = getattr(self, "P", None)
        if P is None or self.coarse_size is None or P.is_block:
            return None
        meta = {"num_rows": int(self.A.num_rows),
                "coarse_size": int(self.coarse_size),
                "aggressive": bool(self._aggressive),
                "p_rows": int(P.num_rows), "p_cols": int(P.num_cols)}
        # R = P^T is recomputed on restore (bit-exact, and exactly how
        # create_coarse_matrix built it); `strong` is only consulted by
        # a FRESH interpolation, which the reuse path never runs
        arrays = {"cf_map": np.asarray(self.cf_map),
                  "p_row_offsets": np.asarray(P.row_offsets),
                  "p_col_indices": np.asarray(P.col_indices),
                  "p_values": np.asarray(P.values)}
        return meta, arrays

    @classmethod
    def structure_restore(cls, meta, arrays):
        from ...matrix import device_setup_forced, host_resident
        g = cls._ghost(meta["num_rows"])
        g.coarse_size = int(meta["coarse_size"])
        g._aggressive = bool(meta["aggressive"])
        g.cf_map = arrays["cf_map"]
        g.strong = None
        P = CsrMatrix(row_offsets=arrays["p_row_offsets"],
                      col_indices=arrays["p_col_indices"],
                      values=arrays["p_values"],
                      num_rows=int(meta["p_rows"]),
                      num_cols=int(meta["p_cols"]))
        ell = "auto" if device_setup_forced() or host_resident(
            P.row_offsets, P.col_indices, P.values) else "never"
        g.P = P.init(ell=ell)
        g.R = transpose(g.P).init(ell=ell)
        return g

    def level_data(self):
        d = super().level_data()
        # the cycle only SpMVs against the transfer operators — layout
        # views keep their CSR payloads out of the solve program's HBM
        d["P"] = self.P.slim_for_spmv()
        d["R"] = self.R.slim_for_spmv()
        return d

    def restrict(self, data, r):
        return spmv(data["R"], r)

    def prolongate(self, data, xc):
        return spmv(data["P"], xc)
