"""Pipelined value-only resetup for GEO/DIA hierarchies.

The reference's structure-reuse resetup (src/amg.cu:232-262) keeps the
coarsening and re-runs only the Galerkin products. The value plan here
chains the SAME jitted building blocks the setup itself dispatches
(`_geo_compute`, `_any_wrapped`, the eager DIA pack and dense-QR ops):
new fine DIA values in, every level's coarse DIA values, the Chebyshev
taus, and the coarse dense QR factor out — all async dispatches with
exactly ONE device sync (the batched GEO wrap-check flag, which must be
re-validated because it depends on the values; matrix-free levels fold
their stencil-constancy re-check into the same fetch and get their
StencilOperator coefficients respliced from it).

Reusing the setup's own jitted pieces is load-bearing for
`resetup_first_s`: an earlier revision fused the whole plan into one
mega-`jax.jit` program, which re-traced and re-compiled a second copy
of every Galerkin product on the FIRST resetup (23 s at 256^3 — worse
than a cold setup). The chained form hits the setup's compile caches,
so the first resetup costs roughly a steady-state resetup plus the tiny
tau/QR glue compiles.

Applies when every level is a GEO-paired DIA level with an in-line
diagonal (the flagship and north-star shape), every smoother is
CHEBYSHEV_POLY or NOSOLVER, and the coarse solver is DENSE_LU.
Anything else falls back to the generic structure-reuse loop, and says
which test failed: every decline raises `Declined(reason)` inside this
module, `try_value_resetup` hands the reason to its caller's span
(`amg.value_resetup`, arg `reason`), and `AMG._resetup_route` counts it
(`amg.resetup.value_declined`) and puts it on the flight recorder's
`resetup.route` event.

Host spans (children of `amg.value_resetup`, named outside the
accounted `amg.` prefix like `selector.device_sweep`, so the accounted
sum never counts them twice): `value_resetup.plan` (first call),
`.dispatch`, `.sync` (the one fetch: where the host waits for the
upload and the whole value phase; its seconds are the counter
`amg.value_resetup.wait_s`), `.splice`. Device scopes:
`amg.value_resetup.L<k>` round a level's value phase, `.coarse` round
the dense scatter + QR, `.cast` round the precast.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..matrix import CsrMatrix
from ..profiling import trace_region
from ..solvers.polynomial import chebyshev_poly_coeffs, dia_abs_row_sums


class Declined(Exception):
    """The value route does not apply; `reason` names the test that
    failed (one word, stable: it is read by tests and dashboards)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _level_plan(level, Ac_structure):
    """Static per-level recompute recipe; raises Declined."""
    from .aggregation import AggregationAMGLevel
    from .aggregation.galerkin import (_decompose, _geo_contrib_table,
                                       _geo_csr_structure)
    if type(level) is not AggregationAMGLevel or level.geo_axes is None:
        raise Declined("level_not_geo")
    A = level.A
    if A.dia_offsets is None or A.is_block or \
            A.grid_shape != tuple(level.geo_fine_shape):
        raise Declined("level_not_dia")
    if A.has_external_diag or Ac_structure.has_external_diag:
        # external diagonals live outside dia_vals — the fused program
        # reads only dia_vals, so such hierarchies must take the
        # generic reuse loop
        raise Declined("external_diag")
    if A.ell_vals is not None or A.swell_vals is not None or \
            Ac_structure.ell_vals is not None or \
            Ac_structure.swell_vals is not None:
        # the splice (try_value_resetup) rewrites values/dia_vals ONLY:
        # an ELL/SWELL cache on either matrix would keep serving the OLD
        # coefficients through spmv's layout dispatch. GEO levels never
        # build these layouts today — this check turns that assumption
        # into an enforced invariant instead of a silent-wrong-answer
        # path (load-bearing for the batched subsystem's per-system
        # value splice, batch/core.py).
        raise Declined("ell_or_swell_layout")
    nx, ny, nz = level.geo_fine_shape
    # a planned setup (spgemm_plan=auto/1) memoized its GeoRapPlan on
    # the level: consume it — the contribution table and the
    # device-resident structure arrays are NEVER rebuilt by a value
    # resetup, and the numeric phase runs the very jitted program
    # (_geo_value_phase) the setup itself dispatched, so the first
    # resetup hits the setup's compile cache
    geo_plan = (getattr(level, "_geo_plan_memo", None) or (None,))[0]
    if geo_plan is not None:
        if tuple(int(k[0]) for k in geo_plan.coffsets) != \
                Ac_structure.dia_offsets:
            raise Declined("signature_drift")
        return dict(
            n=A.num_rows, k=len(A.dia_offsets),
            shifts=geo_plan.shifts,
            fine_shape=tuple(level.geo_fine_shape),
            geo_plan=geo_plan,
            nc=Ac_structure.num_rows,
            kc=len(Ac_structure.dia_offsets))
    decomp = {}
    for d in A.dia_offsets:
        g = _decompose(int(d), nx, ny, nz)
        if g is None:
            raise Declined("offset_not_stencil")
        decomp[int(d)] = g
    shifts = tuple(decomp[int(d)] for d in A.dia_offsets)
    coffsets, contribs = _geo_contrib_table(
        tuple(int(d) for d in A.dia_offsets), shifts,
        tuple(level.geo_axes), tuple(level.geo_coarse_shape))
    if tuple(int(k[0]) for k in coffsets) != Ac_structure.dia_offsets:
        # structure drifted; generic path sorts it out
        raise Declined("signature_drift")
    (_ro, off_e, row_e, _col_e, _diag) = _geo_csr_structure(
        coffsets, tuple(level.geo_coarse_shape))
    return dict(
        n=A.num_rows, k=len(A.dia_offsets), shifts=shifts,
        fine_shape=tuple(level.geo_fine_shape),
        axes=tuple(level.geo_axes),
        coarse_shape=tuple(level.geo_coarse_shape),
        coffsets=coffsets, contribs=contribs, geo_plan=None,
        # device-resident ONCE at plan build: re-uploading these O(nnz)
        # gather indices per resetup call would pay a host->device
        # transfer every time step
        off_e=jnp.asarray(off_e), row_e=jnp.asarray(row_e),
        nc=Ac_structure.num_rows, kc=len(Ac_structure.dia_offsets))


def _smoother_plan(sm):
    name = getattr(sm, "name", "")
    if name == "CHEBYSHEV_POLY":
        return ("cheb", sm.order)
    if name in ("NOSOLVER", "DUMMY"):
        return ("none",)
    raise Declined("smoother_not_cheb")


def _mf_on(amg):
    """Per level: does its smoother hold a matrix-free stencil?"""
    return [getattr(lv.smoother, "_mf_stencil", None) is not None
            for lv in amg.levels]


def _lam_rowmax(dia_vals, num_rows: int):
    # Gershgorin bound of a level from its DIA slab, by the expression a
    # rebuilt level's CHEBYSHEV_POLY.solver_setup takes for a DIA operator
    # (solvers/polynomial.dia_abs_row_sums; a matrix with no slab sums its
    # COO triplets there, and never comes here: the plan declines it).
    # Sound only while the slab's off-grid and pad slots hold zero
    return jnp.max(dia_abs_row_sums(dia_vals, num_rows))


def build_plan(amg):
    """Trace-ready plan for amg's current hierarchy, or None."""
    try:
        return _build_plan(amg)
    except Declined:
        return None


def _build_plan(amg):
    if not amg.levels or getattr(amg, "coarse_solver", None) is None:
        raise Declined("no_levels")
    if getattr(amg.coarse_solver, "name", "") != "DENSE_LU_SOLVER":
        raise Declined("coarse_not_dense_lu")
    lv_plans, sm_plans = [], []
    chain = list(amg.levels)
    for i, lv in enumerate(chain):
        nxt = (chain[i + 1].A if i + 1 < len(chain) else amg.coarsest_A)
        lv_plans.append(_level_plan(lv, nxt))
        sm_plans.append(_smoother_plan(lv.smoother))
    Az = amg.coarsest_A
    if Az.dia_offsets is None or Az.row_ids is None:
        raise Declined("coarsest_not_dia")
    if Az.num_rows > 4096:
        raise Declined("coarsest_too_large")
    # coarsest dense scatter structure + damping tables: device-resident
    # once here, not re-uploaded per resetup call
    cz_rows = jnp.asarray(Az.row_ids)
    cz_cols = jnp.asarray(Az.col_indices)
    nz = Az.num_rows
    dt_cast = amg._PRECISIONS[amg.precision]
    # the coarse-solver payload (QR factors) casts to the policy's
    # f32+ coarse dtype, matching the solve_data split cast
    dt_coarse = amg.precision_policy.coarse_dtype
    l0_dtype = chain[0].A.dtype
    cheb_tabs = {o: jnp.asarray(np.asarray(chebyshev_poly_coeffs(o)),
                                l0_dtype)
                 for _, *rest in sm_plans for o in rest}

    from .aggregation.galerkin import _any_wrapped, _geo_compute
    from ..ops.pallas_spmv import LANES, dia_padded_rows
    from ..ops.stencil import stencil_candidate

    # matrix-free levels (ops/stencil.py): their StencilOperator
    # coefficients must be refreshed from the new values, and the
    # constancy invariant re-validated — new values may no longer be a
    # constant stencil. The flag folds into the same single fetch as
    # the wrap check below.
    mf_on = _mf_on(amg)

    def run(dia_vals0):
        # EAGER on purpose: every heavy piece below (_geo_compute,
        # _any_wrapped) is the very jitted function the setup already
        # compiled for this hierarchy, and the glue (DIA pack, dense
        # scatter, QR, casts) is small eager ops — so the first resetup
        # reuses the setup traces instead of compiling a fused twin.
        outs = {"dia": [], "vals": [], "taus": [], "mf": [],
                "cast": {}}
        dia_vals = dia_vals0
        wrapped = jnp.zeros((), bool)
        for i, p in enumerate(lv_plans):
            with jax.named_scope(f"amg.value_resetup.L{i}"):
                dia_vals, wrapped = level(i, p, dia_vals, wrapped, outs)
        # coarsest dense + QR (DenseLUSolver.solver_setup semantics)
        with jax.named_scope("amg.value_resetup.coarse"):
            dense = jnp.zeros((nz, nz), dia_vals0.dtype).at[
                cz_rows, cz_cols].add(outs["vals"][-1])
            zero_rows = jnp.all(dense == 0, axis=1)
            dense = jnp.where(jnp.diag(zero_rows),
                              jnp.eye(nz, dtype=dense.dtype), dense)
            q, r = jnp.linalg.qr(dense)
            outs["qt"], outs["r"] = q.T, r
        if dt_cast is not None:
            with jax.named_scope("amg.value_resetup.cast"):
                outs["cast"] = {
                    "dia0": dia_vals0.astype(dt_cast),
                    "dia": [d.astype(dt_cast) for d in outs["dia"]],
                    "taus": [None if t is None else t.astype(dt_cast)
                             for t in outs["taus"]],
                    "qt": outs["qt"].astype(dt_coarse),
                    "r": outs["r"].astype(dt_coarse)}
        outs["wrapped"] = wrapped
        return outs

    def level(i, p, dia_vals, wrapped, outs):
        """One level's value phase: wrap / constancy flags, taus, the
        coarse operator's values. Returns the coarse DIA slab (the
        next level's input) and the flag so far."""
        vals2d = dia_vals.reshape(p["k"], -1)[:, : p["n"]]
        wrapped = wrapped | _any_wrapped(vals2d, p["shifts"],
                                         p["fine_shape"])
        c = None
        if mf_on[i]:
            if i > 0 and mf_on[i - 1]:
                gp = lv_plans[i - 1]["geo_plan"]
                if gp is not None:
                    # constancy is inherited: a constant fine
                    # stencil with even paired extents coarsens to
                    # a constant stencil, so the derived coarse
                    # coefficients need no re-compare
                    c = gp.coarse_coeffs(outs["mf"][i - 1])
            if c is None:
                ok_i, c = stencil_candidate(vals2d, p["shifts"],
                                            p["fine_shape"])
                wrapped = wrapped | ~ok_i
        outs["mf"].append(c)
        if sm_plans[i][0] == "cheb":
            lam = _lam_rowmax(dia_vals, p["n"])
            taus = cheb_tabs[sm_plans[i][1]].astype(
                vals2d.dtype) / lam
        else:
            taus = None
        outs["taus"].append(taus)
        if p["geo_plan"] is not None:
            # the planned setup route's own jitted numeric phase
            # (galerkin._geo_value_phase): compute + gather + DIA
            # pack in one dispatch, structure arrays cache-served
            values_c, dia_c = p["geo_plan"].values(vals2d)
        else:
            cvals = _geo_compute(vals2d, p["coffsets"],
                                 p["contribs"], p["fine_shape"],
                                 p["axes"])
            values_c = cvals[p["off_e"], p["row_e"]]
            rows_pad = dia_padded_rows(p["kc"], p["nc"])
            dia_c = jnp.zeros(
                (p["kc"], rows_pad * LANES), cvals.dtype
            ).at[:, : p["nc"]].set(cvals).reshape(
                p["kc"], rows_pad, LANES)
        outs["dia"].append(dia_c)
        outs["vals"].append(values_c)
        return dia_c, wrapped

    return {"fn": run, "lv": lv_plans, "sm": sm_plans, "mf_on": mf_on,
            "l0_sig": (tuple(int(d) for d in chain[0].A.dia_offsets),
                       chain[0].A.num_rows, len(chain))}


def _plan_for(amg, A: CsrMatrix):
    """The hierarchy's kept plan, built on first use; raises Declined
    (an ineligible hierarchy keeps its reason in the plan's place, so
    later calls decline without looking again)."""
    if not A.initialized or A.dia_vals is None:
        raise Declined("fine_not_dia")
    plan = getattr(amg, "_vr_plan", None)
    if isinstance(plan, dict) and _mf_on(amg) != plan["mf_on"]:
        # a generic resetup flipped a level's matrix-free form since
        # this plan was traced — rebuild so the coefficient refresh
        # covers exactly the live stencils (a stale splice would leave
        # old coefficients serving new values)
        plan = None
    if plan is None:
        with trace_region("value_resetup.plan"):
            try:
                plan = _build_plan(amg)
            except Declined as e:
                plan = e.reason
        amg._vr_plan = plan
    if isinstance(plan, str):
        raise Declined(plan)
    sig = (tuple(int(d) for d in A.dia_offsets), A.num_rows,
           len(amg.levels))
    if sig != plan["l0_sig"]:
        raise Declined("signature_drift")
    return plan


def try_value_resetup(amg, A: CsrMatrix, note: dict) -> bool:
    """Apply the one-dispatch value-only resetup. Returns False when
    the hierarchy shape is ineligible or the new values break the GEO
    wrap invariant (caller falls back to the generic reuse loop), with
    the test that failed under `note["reason"]`."""
    try:
        plan = _plan_for(amg, A)
    except Declined as e:
        note["reason"] = e.reason
        return False
    with trace_region("value_resetup.dispatch"):
        outs = plan["fn"](A.dia_vals)
    with trace_region("value_resetup.sync",
                      counter="amg.value_resetup.wait_s"):
        wrapped = bool(outs["wrapped"])   # ONE scalar fetch, the only sync
    if wrapped:
        amg._vr_plan = None       # values violate the GEO invariant
        note["reason"] = "wrapped_or_not_constant"
        return False
    with trace_region("value_resetup.splice"):
        _splice(amg, A, plan, outs)
    return True


def _splice(amg, A: CsrMatrix, plan, outs):
    """Put the value phase's outputs in the hierarchy's place: host-side
    bookkeeping only, no device work."""
    precast = {}
    cast = outs["cast"]
    amg.levels[0].A = A
    if cast:
        precast[id(A.dia_vals)] = cast["dia0"]
    fine = A
    for i, lv in enumerate(amg.levels):
        Ac_old = (amg.levels[i + 1].A if i + 1 < len(amg.levels)
                  else amg.coarsest_A)
        Ac = dataclasses.replace(Ac_old, values=outs["vals"][i],
                                 dia_vals=outs["dia"][i])
        if i + 1 < len(amg.levels):
            amg.levels[i + 1].A = Ac
        else:
            amg.coarsest_A = Ac
        if cast:
            precast[id(Ac.dia_vals)] = cast["dia"][i]
        sm = lv.smoother
        sm.drop_solve_data()     # its leaves are replaced below
        sm.A = fine
        st = getattr(sm, "_mf_stencil", None)
        if st is not None and outs["mf"][i] is not None:
            # fresh leaf on purpose: downstream solve_data caches key
            # on identity, and the stencil's static fields are unchanged
            sm._mf_stencil = dataclasses.replace(
                st, coeffs=outs["mf"][i])
        if plan["sm"][i][0] == "cheb":
            sm._taus = outs["taus"][i]
            if cast:
                precast[id(sm._taus)] = cast["taus"][i]
        fine = Ac
    cs = amg.coarse_solver
    cs.drop_solve_data()
    cs.A = amg.coarsest_A
    cs._qt, cs._r = outs["qt"], outs["r"]
    if cast:
        precast[id(cs._qt)] = cast["qt"]
        precast[id(cs._r)] = cast["r"]
    amg.drop_solve_data()
    amg._resetup_precast = precast
