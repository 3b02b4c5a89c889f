"""AMG hierarchy driver.

Analog of AMG<> + the AMG_Level linked list (src/amg.cu:152-421 setup
loop, include/amg_level.h:51). Redesign for XLA:

- setup is host-orchestrated, device-math (each level's coarsening is
  eager jnp with concrete shapes);
- the finished hierarchy is a *list of level pytrees* with static shapes,
  so one multigrid cycle traces into a single fused XLA program with the
  recursion unrolled over the (static) depth;
- levels own their smoother's solve-data; the coarsest level owns the
  coarse solver's data (DENSE_LU by default).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from .. import registry
from ..config import Config
from ..errors import BadConfigurationError
from ..matrix import CsrMatrix
from ..ops import pallas_spmv as _ps
from ..solve_data import SolveDataOwner


def _record_route(route: str, A, **why):
    """Flight-recorder trail of the setup-routing decision (full build
    vs value/structure resetup vs restored-from-snapshot) — ONE event
    shape for all four routes (telemetry/flightrec.py; lazy import:
    telemetry must stay importable without the amg package). A
    structure resetup that the value route declined carries the test
    that failed as `reason`."""
    from ..telemetry import flightrec, spans
    flightrec.record("resetup.route", route=route,
                     rows=int(A.num_rows), **why)
    # the same name in the span buffer, for spans.resetup_rows()
    spans.mark("resetup.route", args={"route": route})


def laid_out(M, why: dict, build=None):
    """`M` with its SpMV layout (`build(M)`; a coarse operator's
    `build_spmv_layout()` / `init()` by default); where the SWELL
    budget said no on the way (ops/pallas_swell.swell_budget), the
    reason goes into `why["declined"]`, and where the row-split form
    was taken over a layout the budget admits (`split_pays`), its K
    and the model's two costs into `why["chosen"]`: the layout span's
    args."""
    from ..ops.pallas_swell import collect_layout_notes
    if build is None:
        def build(M):
            return M.build_spmv_layout() if M.initialized else M.init()
    with collect_layout_notes() as said:
        out = build(M)
    why.update({k: ",".join(v) for k, v in said.items() if v})
    return out


class AMGLevel:
    """One hierarchy level: fine matrix + transfer operators + smoother.

    Subclasses (aggregation / classical / energymin) implement
    create_coarse_vertices / create_coarse_matrix / restrict / prolongate
    (the pure-virtual interface of include/amg_level.h:51-215).
    """

    algorithm = "?"

    def __init__(self, A: CsrMatrix, cfg: Config, scope: str,
                 level_index: int):
        self.A = A
        self.cfg = cfg
        self.scope = scope
        self.level_index = level_index
        self.smoother = None           # set by AMG.setup
        self.coarse_size: Optional[int] = None

    # -- build interface -------------------------------------------------
    def create_coarse_vertices(self):
        raise NotImplementedError

    def create_coarse_matrix(self) -> CsrMatrix:
        raise NotImplementedError

    def reuse_structure(self, old: "AMGLevel"):
        """Adopt the coarsening structure of a previous setup of this
        level (structure_reuse_levels); create_coarse_matrix then only
        recomputes the Galerkin product against the new coefficients."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support structure reuse")

    # -- persistent structure (serving/hstore.py) ------------------------
    def structure_snapshot(self):
        """(meta, arrays) capturing exactly what `reuse_structure`
        reads — the host-persistable form of this level's coarsening
        structure (deterministic from the sparsity pattern, ROADMAP
        3d). `meta` is JSON-able scalars, `arrays` numpy arrays. None
        when this level class does not support persistence (the store
        then skips the whole hierarchy)."""
        return None

    @classmethod
    def structure_restore(cls, meta, arrays):
        """Rebuild a 'ghost' level from a persisted snapshot: an
        instance carrying ONLY the attributes `reuse_structure` reads
        (plus A.num_rows for the reuse-loop compatibility check) — it
        is never solved with, only adopted from."""
        raise NotImplementedError(
            f"{cls.__name__} does not support structure restore")

    @classmethod
    def _ghost(cls, num_rows: int):
        import types
        g = cls.__new__(cls)
        g.A = types.SimpleNamespace(num_rows=int(num_rows))
        g.smoother = None
        return g

    # -- solve-phase (pure) ----------------------------------------------
    def level_data(self) -> Dict[str, Any]:
        # slim matrices: the cycle only SpMVs against level operators,
        # so layout-only views keep multi-GB unused CSR payloads out of
        # the solve program's HBM arguments
        A = self.A.slim_for_spmv()
        d = {"A": A}
        if self.smoother is not None:
            # the smoother's solve_data already slims its own A when its
            # sweeps only SpMV (Solver.slim_A_ok)
            d["smoother"] = self.smoother.solve_data_part()
            st = d["smoother"].get("stencil") if isinstance(
                d["smoother"], dict) else None
            if st is not None:
                # matrix-free level: the LEVEL operator view drops its
                # value slab too (the stencil payload is the operator;
                # consumers that need a matrix rebuild it in-trace via
                # ops/stencil.level_operator)
                from ..ops.stencil import mf_slim
                d["A"] = mf_slim(A)
                d["stencil"] = st
        return d

    def restrict(self, data, r):
        raise NotImplementedError

    def prolongate(self, data, xc):
        raise NotImplementedError


_PENDING = object()    # _put_cache placeholder: (src, (_PENDING, fut, i))


class AMG(SolveDataOwner):
    """Hierarchy owner + setup loop (AMG<>::setup analog, src/amg.cu)."""

    def __init__(self, cfg: Config, scope: str = "default"):
        self.cfg = cfg
        self.scope = scope
        self.algorithm = str(cfg.get("algorithm", scope)).upper()
        self.max_levels = int(cfg.get("max_levels", scope))
        self.min_coarse_rows = int(cfg.get("min_coarse_rows", scope))
        self.min_fine_rows = int(cfg.get("min_fine_rows", scope))
        self.coarsen_threshold = float(cfg.get("coarsen_threshold", scope))
        self.presweeps = int(cfg.get("presweeps", scope))
        self.postsweeps = int(cfg.get("postsweeps", scope))
        self.finest_sweeps = int(cfg.get("finest_sweeps", scope))
        self.coarsest_sweeps = int(cfg.get("coarsest_sweeps", scope))
        # the reference's constructor (src/amg.cu, AMG::AMG) reads the
        # key only inside `if (solverName.compare("DENSE_LU_SOLVER") ==
        # 0)`, beside dense_lu_max_rows, and leaves m_dense_lu_num_rows
        # 0 otherwise: under any other coarse solver (NOSOLVER, a
        # smoother) nothing stops the coarsening at that size, and
        # min_coarse_rows, max_levels or a stall end the hierarchy (as
        # remembered: the reference's source is not in this tree)
        self.dense_lu_num_rows = (
            int(cfg.get("dense_lu_num_rows", scope))
            if str(cfg.get("coarse_solver", scope)).upper()
            == "DENSE_LU_SOLVER" else 0)
        self.cycle_name = str(cfg.get("cycle", scope)).upper()
        self.cycle_iters = int(cfg.get("cycle_iters", scope))
        # matrix-free GEO levels (ops/stencil.py): auto = only on a
        # real TPU backend (CPU rigs stay bit-identical to the slab
        # build), 1 = force the detector everywhere, 0 = never
        self.matrix_free = str(cfg.get("matrix_free", scope))
        # effective hierarchy/cycle precision: the shared policy
        # resolves amg_precision / solve_precision / tpu_dtype into one
        # answer (precision.py) and rejects contradictory combinations
        from ..precision import resolve_precision
        self.precision_policy = resolve_precision(cfg, scope)
        self.precision = self.precision_policy.name
        self.print_grid_stats = bool(cfg.get("print_grid_stats", scope))
        self.intensive_smoothing = bool(cfg.get("intensive_smoothing", scope))
        self.host_setup = str(cfg.get("amg_host_setup", scope))
        self.setup_backend = str(cfg.get("setup_backend", scope)).lower()
        self.setup_device_min_rows = int(
            cfg.get("setup_device_min_rows", scope))
        self.convergence_analysis = int(cfg.get("convergence_analysis",
                                                scope))
        # convergence diagnostics (telemetry/diagnostics.py): when on,
        # the solve driver appends one instrumented probe cycle whose
        # per-level stage norms ride the packed stats
        self.diagnostics = bool(int(cfg.get("diagnostics", scope)))
        self.levels: List[AMGLevel] = []
        self.coarse_solver = None
        self.setup_time = 0.0
        self._data_cache = None     # solve_data.py
        self._ship_device = None
        # host-setup transfer overlap: id(host leaf) -> (host leaf,
        # device leaf); filled by _prefetch_level as levels finish
        # building so the host->device transfer hides behind the
        # remaining host compute
        self._put_cache: Dict[int, tuple] = {}
        self._ship_pool = None
        # True from a structure-reuse rebuild to the next full setup:
        # what is shipped for that hierarchy goes on amg.resetup.ship_*
        self._ship_counted = False
        # which implementations the last setup used ("host" pull-and-ship,
        # "device" forced pipeline, "auto" residency-driven)
        self._setup_backend_used = None
        # distributed setup builds the replicated tail through
        # _build_levels but owns its smoother assignment
        self._defer_smoothers = False
        # amg/signature.py: what a solve program traced against this
        # hierarchy read besides its arguments, as of the last setup,
        # and whether the last resetup rebuilt the levels to the same
        self._static_sig = None
        self._resetup_same_static = False

    # -- setup -----------------------------------------------------------
    def _host_setup_device(self, A: CsrMatrix):
        """Host-CPU hierarchy construction (the TPU answer to the
        reference's host-level machinery, src/amg.cu:152-421): the
        classical/energymin setup is hundreds of small eager index ops,
        each costing a full device round trip on a remote accelerator —
        built on the host CPU backend the same code runs in milliseconds,
        and the finished hierarchy ships to the accelerator once (cached
        solve-data). mode: auto (host when the default backend is a
        remote accelerator and the algorithm's setup is index-heavy),
        always, never. `setup_backend` outranks `amg_host_setup`:
        device never pulls, host always does (on an accelerator)."""
        import jax
        if self.setup_backend == "device":
            return None          # device-resident pipeline: never pull
        mode = self.host_setup
        if mode == "never" and self.setup_backend != "host":
            return None
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            return None
        ambient = jax.config.jax_default_device or jax.devices()[0]
        if ambient.platform == "cpu":
            return None          # already on host
        if self.setup_backend == "host" or mode == "always" \
                or self.algorithm in ("CLASSICAL", "ENERGYMIN"):
            return cpu
        return None

    def adopt_structure(self, ghost_levels):
        """Install a persisted structure snapshot (serving/hstore.py):
        the NEXT setup() call routes through the structure-reuse
        rebuild (`_resetup_impl` — Galerkin values + smoothers only,
        the cheap path) instead of a full coarsening, counted as
        amg.setup.restored. One-shot: consumed (or discarded on a
        shape mismatch) by that setup."""
        self._ghost_levels = list(ghost_levels)

    def setup(self, A: CsrMatrix):
        self._resetup_same_static = False
        self.drop_solve_data()
        self._setup_route(A)
        self._static_sig = self._signature()
        return self

    def _signature(self):
        from .signature import static_signature
        from ..profiling import trace_region
        with trace_region("amg.static_signature"):
            return static_signature(self)

    def _setup_route(self, A: CsrMatrix):
        import jax
        from ..telemetry import metrics as _tm
        ghosts = getattr(self, "_ghost_levels", None)
        if ghosts is not None:
            self._ghost_levels = None
            if ghosts and ghosts[0].A.num_rows == A.num_rows:
                return self._setup_restored(A, ghosts)
        _tm.inc("amg.setup.full")
        _record_route("full", A)
        t0 = time.perf_counter()
        self.levels = []
        self._put_cache = {}
        self._ship_counted = False
        self._l0_seed = None     # dropped unless this setup re-registers
        self._resetup_precast = None
        self._vr_plan = None     # value-resetup plan re-derives lazily
        self._last_resetup_value_only = False
        self._telemetry_level_cache = None
        host = self._host_setup_device(A)
        if host is not None:
            self._setup_backend_used = "host"
            # decide BEFORE init: the SpMV-layout build is itself eager
            # device work that belongs on the host in this mode; ship to
            # the device the caller's context selected
            self._ship_device = (jax.config.jax_default_device
                                 or jax.devices()[0])
            # cast OUTSIDE the host default-device block: orig's arrays
            # are uncommitted accelerator data, and an astype dispatched
            # under default_device(cpu) would copy them to the host first
            from ..profiling import trace_region
            l0_dev = self._l0_device_cast(A)
            with jax.default_device(host):
                with trace_region("amg.host_pull"):
                    Af = self._pull_host_l0(A)
                self._register_device_l0(A, Af, l0_dev)
                self._build_levels_checked(Af, 0)
                self._finalize_setup(t0)
            return self
        self._ship_device = None
        # "host" here means setup_backend=host on a host-ambient rig
        # (no pull needed — the build IS on the host)
        self._setup_backend_used = self.setup_backend
        from ..matrix import forced_device_setup
        from ..profiling import trace_region
        with forced_device_setup(self._level_device_forced(A.num_rows)):
            with trace_region("amg.l0_layout"):
                Af = A if A.initialized else A.init()
        self._build_levels_checked(Af, 0)
        self._finalize_setup(t0)
        return self

    def _setup_restored(self, A: CsrMatrix, ghosts):
        """setup() against a persisted structure snapshot: install the
        ghost levels as the reuse source and run the structure-reuse
        rebuild — values-only Galerkin + fresh smoothers, no coarsening
        selection. The restart path's answer to the 17 s cold setup."""
        import jax
        from ..profiling import trace_region
        from ..telemetry import metrics as _tm
        _tm.inc("amg.setup.restored")
        _record_route("restored", A)
        self.levels = list(ghosts)
        self._put_cache = {}
        self._l0_seed = None
        self._resetup_precast = None
        self._vr_plan = None
        self._last_resetup_value_only = False
        self._telemetry_level_cache = None
        host = self._host_setup_device(A)
        if host is not None:
            self._setup_backend_used = "host"
            self._ship_device = (jax.config.jax_default_device
                                 or jax.devices()[0])
            l0_dev = self._l0_device_cast(A)
            with jax.default_device(host):
                with trace_region("amg.host_pull"):
                    Af = self._pull_host_l0(A)
                self._register_device_l0(A, Af, l0_dev)
                return self._resetup_impl(Af, -1)
        self._ship_device = None
        self._setup_backend_used = self.setup_backend
        Af = A if A.initialized else A.init()
        return self._resetup_impl(Af, -1)

    def _level_device_forced(self, n: int) -> bool:
        """setup_backend=device forces the jnp/device implementations
        for this level; levels under setup_device_min_rows lift the
        forcing (dispatch overhead loses against tiny host numpy)."""
        return (self.setup_backend == "device"
                and self._ship_device is None
                and n >= self.setup_device_min_rows)

    def _pull_numpy(self, A: CsrMatrix) -> CsrMatrix:
        """Pull a (layout-stripped) matrix's arrays to host numpy. The
        host hierarchy build runs on numpy end to end: every native
        component (PMIS/D2/RAP/SWELL) consumes and produces numpy, so
        staying off jax CPU arrays avoids one full copy of every array
        at every native-call boundary. Arrays uploaded from host data
        resolve through the retained host mirror (matrix.py
        _HOST_MIRROR) — no accelerator->host transfer at all."""
        import dataclasses
        from ..matrix import host_mirror_asarray as pull
        return dataclasses.replace(
            A, row_offsets=pull(A.row_offsets),
            col_indices=pull(A.col_indices),
            values=pull(A.values),
            diag=None if A.diag is None else pull(A.diag))

    # L0 SpMV-layout payload fields and which of them carry float data
    # (the others are structure arrays the amg_precision cast ignores)
    _L0_PAYLOADS = ("dia_vals", "ell_vals", "ell_cols", "swell_vals",
                    "swell_cols", "swell_c0row", "swell_nchunk")

    def _pull_host_l0(self, A: CsrMatrix) -> CsrMatrix:
        """Host-numpy finest-level matrix for the host build. When the
        caller's device matrix already carries its SpMV layout (DIA/
        ELL/SWELL) with retained host mirrors, the layout arrays are
        REUSED instead of rebuilt — the pre-layout strip + numpy
        re-pack only runs when some piece cannot be served host-side."""
        import dataclasses as _dc
        from ..matrix import host_arrays
        if A.initialized:
            fields = ("row_offsets", "col_indices", "values", "diag",
                      "row_ids", "diag_idx") + self._L0_PAYLOADS
            arrs = host_arrays(*[getattr(A, f) for f in fields])
            if arrs is not None:
                return _dc.replace(A, **dict(zip(fields, arrs)))
        Af = self._pull_numpy(self._strip_layouts(A))
        return Af.init()

    def _l0_device_cast(self, orig: CsrMatrix):
        """Device twins of the caller's finest-level SpMV-layout
        payloads: precision casts for the float slabs (dispatched on
        the caller's device — must run OUTSIDE the host default-device
        block, see setup()), the resident arrays themselves for the
        integer structure."""
        if orig is None or not orig.initialized:
            return None
        import jax.numpy as jnp
        out = {}
        for f in self._L0_PAYLOADS:
            v = getattr(orig, f)
            if v is None:
                continue
            out[f] = (self._cast_leaf(v)
                      if jnp.issubdtype(v.dtype, jnp.inexact) else v)
        return out or None

    def _register_device_l0(self, orig: CsrMatrix, Af_host: CsrMatrix,
                            dev):
        """The caller's device matrix already holds the finest level's
        SpMV layout; pre-seeding the transfer cache with its (precision-
        cast, cast ON device) payloads makes the ship skip the arrays
        that are both the largest and already resident — a host-held
        L0 layout never crosses the wire. A payload seeds when the host
        array IS the device array's retained mirror (layout reused by
        _pull_host_l0), or — for DIA — when the host rebuild provably
        produced the same packing (identical offset tuple)."""
        self._l0_seed = None
        if dev is None:
            return
        from ..matrix import _HOST_MIRROR
        seeds = []
        for f, d in dev.items():
            h = getattr(Af_host, f, None)
            if h is None or not isinstance(h, np.ndarray):
                continue
            ok = h is _HOST_MIRROR.get(id(getattr(orig, f)))
            if not ok and f == "dia_vals":
                ok = Af_host.dia_offsets == orig.dia_offsets
            if ok:
                seeds.append((h, d))
        if seeds:
            self._l0_seed = tuple(seeds)
            self._seed_put_cache()

    def _seed_put_cache(self):
        """(Re)apply the L0 device-payload seeds after any _put_cache
        reset (resetup, abandoned GEO builds)."""
        for src, dev in getattr(self, "_l0_seed", None) or ():
            self._put_cache[id(src)] = (src, dev)

    @staticmethod
    def _strip_layouts(A: CsrMatrix) -> CsrMatrix:
        """Drop SpMV auxiliaries before pulling a device matrix to the
        host: the host setup rebuilds them in numpy anyway, so the
        accelerator->host transfer of row_ids/ELL/DIA payloads would be
        bytes moved for nothing."""
        import dataclasses
        return dataclasses.replace(
            A, row_ids=None, diag_idx=None, ell_cols=None, ell_vals=None,
            dia_offsets=None, dia_vals=None, swell_cols=None,
            swell_vals=None, swell_c0row=None, swell_nchunk=None,
            swell_w128=0, initialized=False)

    def _build_levels_checked(self, Af: CsrMatrix, lvl: int):
        """_build_levels with the GEO fast path's wrap checks deferred
        to ONE batched device fetch (each per-level bool() is a
        blocking device->host sync); the rare failure rebuilds without
        the fast path."""
        from .aggregation.galerkin import (deferred_wrap_checks,
                                           geo_dia_disabled)
        from ..profiling import trace_region
        base = list(self.levels)
        with deferred_wrap_checks() as flush:
            self._build_levels(Af, lvl)
            # where the main thread waits for the levels' device work
            with trace_region("amg.wrap_check"):
                wrapped = flush()
            if wrapped:
                self.levels = base
                # drop transfers prefetched for the abandoned build (they
                # pin both host and HBM copies of every shipped level)
                self._put_cache = {}
                self._seed_put_cache()
                with geo_dia_disabled():
                    self._build_levels(Af, lvl)

    def resetup(self, A: CsrMatrix):
        """Coefficient-replace re-setup honoring structure_reuse_levels
        (AMG_Setup structure-reuse path, src/amg.cu:232-262): the first
        `structure_reuse_levels` levels (-1 = all) keep their coarsening
        structure (aggregates / CF-split + transfer operators) and only
        recompute the Galerkin products; deeper levels rebuild fully.

        Whichever route rebuilt levels, the new hierarchy's static
        signature (amg/signature.py) is compared with the one before:
        where they are equal a solve program traced against the old
        levels is the program a trace against the new ones would give,
        `_resetup_same_static` says so, and what that trace left on the
        hierarchy as a side effect is carried over (no trace will
        record it again)."""
        before = self._static_sig
        table = self._telemetry_level_cache
        self._resetup_same_static = False
        # the old values' tree (its cast twins with it) goes before
        # whichever route makes the new leaves
        self.drop_solve_data()
        self._resetup_route(A)
        if self._last_resetup_value_only:
            return self     # the levels, and all of the above, stand
        self._static_sig = self._signature()
        if before is not None and before == self._static_sig:
            self._resetup_same_static = True
            # the report's level table is keyed on the level list:
            # equal signatures make the old one true of the new list
            # (rows, sizes, layouts, fused payloads and dtypes are all
            # in the signature)
            from ..telemetry.report import carry_level_table
            carry_level_table(self, table)
        return self

    def _resetup_route(self, A: CsrMatrix):
        reuse = int(self.cfg.get("structure_reuse_levels", self.scope))
        if reuse == 0 or not self.levels or \
                A.num_rows != self.levels[0].A.num_rows:
            return self._setup_route(A)
        self._last_resetup_value_only = False
        from ..telemetry import metrics as _tm
        why = {}        # the value route's reason, where it declined
        if reuse < 0 or reuse >= len(self.levels):
            from .value_resetup import try_value_resetup
            from ..profiling import trace_region
            with trace_region("amg.value_resetup", args=why):
                if self._ship_device is not None:
                    why["reason"] = "host_built"
                elif try_value_resetup(self, A, why):
                    self._last_resetup_value_only = True
                    _tm.inc("amg.resetup.value")
                    _record_route("value", A)
                    return self
            _tm.inc("amg.resetup.value_declined")
        _tm.inc("amg.resetup.structure")
        _record_route("structure", A, **why)
        # a structure resetup rebuilds levels: the memoized report
        # level table is for the OLD hierarchy (the value-only path
        # above keeps it valid)
        self._telemetry_level_cache = None
        if self._ship_device is not None:
            host = jax.devices("cpu")[0]
            l0_dev = self._l0_device_cast(A)        # see setup()
            with jax.default_device(host):
                from ..profiling import trace_region
                with trace_region("amg.host_pull"):
                    Af = self._pull_host_l0(A)
                # refresh the L0 seeds: a rebuilt host hierarchy has
                # NEW layout arrays (stale seeds would both miss the
                # ship skip and pin the previous payloads for the
                # object's lifetime)
                self._register_device_l0(A, Af, l0_dev)
                return self._resetup_impl(Af, reuse)
        Af = A if A.initialized else A.init()
        return self._resetup_impl(Af, reuse)

    def _resetup_impl(self, Af: CsrMatrix, reuse: int):
        t0 = time.perf_counter()
        k = len(self.levels) if reuse < 0 else min(reuse, len(self.levels))
        old_levels, self.levels = self.levels, []
        self._resetup_precast = None
        self._vr_plan = None
        self._ship_counted = True
        shipped = self._put_cache
        from .aggregation.galerkin import (deferred_wrap_checks,
                                           geo_dia_disabled)

        from ..matrix import forced_device_setup
        from ..profiling import trace_region
        from ..telemetry import metrics as _tm
        from ..telemetry.spans import flat_timers

        def rap_spans(lvl):
            # (seconds of the level's Galerkin value phases, plans
            # built) so far, from the level's own span timers
            t = flat_timers()
            return (t.get(f"amg.L{lvl}.rap_values", (0, 0.0))[1],
                    t.get(f"amg.L{lvl}.rap_plan", (0, 0.0))[0])

        def reuse_loop(Af):
            self._put_cache = {}
            self._seed_put_cache()
            lvl = 0
            while lvl < k:
                old = old_levels[lvl]
                if Af.num_rows != old.A.num_rows:
                    break
                level = type(old)(Af, self.cfg, self.scope, lvl)
                level.reuse_structure(old)
                _tm.inc("amg.resetup.reused_levels")
                # structure kept means kept on the device too: what
                # reuse_structure carried over stays in the put cache,
                # so nothing casts and ships it again; every other
                # entry was of the old values and is gone
                self._put_cache.update(self._carried_puts(shipped, level))
                forced = self._level_device_forced(Af.num_rows)
                from ..matrix import host_resident
                level.built_backend = "device" if forced or \
                    not host_resident(Af.row_offsets, Af.values) else "host"
                with forced_device_setup(forced):
                    rap_s, plans = rap_spans(lvl)
                    Ac = level.create_coarse_matrix()
                    now_s, now_plans = rap_spans(lvl)
                    _tm.add("amg.resetup.rap_values_s", now_s - rap_s)
                    _tm.inc("amg.resetup.rap_plans_built",
                            now_plans - plans)
                    self.levels.append(level)
                    if not self._defer_smoothers:
                        self._attach_level_smoother(level)
                    self._prefetch_level(level)
                    with trace_region(f"amg.L{lvl}.layout",
                                      counter="amg.resetup.layout_s",
                                      args=(why := {})):
                        Af = laid_out(Ac, why)
                lvl += 1
            return Af, lvl

        Af0 = Af
        with deferred_wrap_checks() as flush:
            Af, lvl = reuse_loop(Af0)
            failed = flush()
        if failed:
            # rare: the new coefficients break the GEO fast path's
            # geometric invariant — redo the reuse loop with the generic
            # relabel Galerkin (same reused aggregates, one extra pass)
            self.levels = []
            with geo_dia_disabled():
                Af, lvl = reuse_loop(Af0)
        self._build_levels_checked(Af, lvl)
        self._finalize_setup(t0)
        return self

    @staticmethod
    def _carried_puts(shipped: Dict[int, tuple], level) -> Dict[int, tuple]:
        """The entries of a put cache whose host source is a leaf this
        level took over by `reuse_structure`: the transfer operators (P
        and R hold the coefficients of the first setup, layout slabs
        included). Matched by identity, as the cache is keyed."""
        carried = [getattr(level, "P", None), getattr(level, "R", None)]
        kept = {}
        for leaf in jax.tree.leaves(carried):
            entry = shipped.get(id(leaf))
            if entry is not None and entry[0] is leaf:
                kept[id(leaf)] = entry
        return kept

    def _build_levels(self, Af: CsrMatrix, lvl: int):
        from ..matrix import forced_device_setup, host_resident
        from ..profiling import trace_region
        level_cls = registry.amg_levels.get(self.algorithm)
        while True:
            n = Af.num_rows
            stop = (lvl + 1 >= self.max_levels
                    or n <= max(self.min_coarse_rows, 1)
                    or n < self.min_fine_rows
                    or n <= self.dense_lu_num_rows and lvl > 0)
            if stop:
                break
            level = level_cls(Af, self.cfg, self.scope, lvl)
            forced = self._level_device_forced(n)
            level.built_backend = "device" if forced or not host_resident(
                Af.row_offsets, Af.values) else "host"
            with forced_device_setup(forced):
                # selector/interpolation/Galerkin phase timers live in
                # the level classes (disjoint amg.L*.{selector,strength,
                # cfsplit,interp,transposeR,rap,galerkin,...} leaves)
                level.create_coarse_vertices()
                nc = level.coarse_size
                # stalling coarsening -> stop (coarsen_threshold
                # semantics: the grid must shrink at least that factor)
                if nc <= 0 or nc >= n or \
                        (n / max(nc, 1)) < self.coarsen_threshold:
                    break
                Ac = level.create_coarse_matrix()
                # resilience fault harness: a `galerkin_perturb` spec
                # scales this level's coarse values (host-orchestrated —
                # no cached trace can replay it); inert when unarmed
                from ..resilience import faultinject as _fault
                Ac = _fault.perturb_galerkin(Ac, lvl)
                self.levels.append(level)
                # per-level pipeline: the smoother is set up as soon as
                # its level finishes, so its solve-data (and the level's
                # operators) ship while the NEXT level is coarsening.
                # Trade-off: a build abandoned by a failed deferred GEO
                # wrap check (rare — values violating the geometric
                # invariant) now discards this smoother work too and
                # pays it again on the rebuild.
                if not self._defer_smoothers:
                    self._attach_level_smoother(level)
                self._prefetch_level(level)
                with trace_region(f"amg.L{lvl}.layout",
                                  args=(why := {})):
                    Af = laid_out(Ac, why)
            lvl += 1
        self.coarsest_A = Af

    def _smoother_spec(self, level_index: int):
        """Smoother (name, scope) for one level: with fine_levels >= 0,
        levels < fine_levels use fine_smoother and the rest use
        coarse_smoother (the reference's fine/coarse algorithm split);
        fine_levels=-1 (default) disables the split and every level
        uses `smoother`."""
        fine_levels = int(self.cfg.get("fine_levels", self.scope))
        if fine_levels < 0:
            return self.cfg.get_solver("smoother", self.scope)
        if level_index < fine_levels:
            return self.cfg.get_solver("fine_smoother", self.scope)
        return self.cfg.get_solver("coarse_smoother", self.scope)

    # known TPU-runtime fault (README "Known limitations"): the
    # combined PCG+V-cycle program with MULTICOLOR_DILU smoothing
    # faults on single-chip TPU at 128^3 scale — every level's DILU
    # passes in isolation and the config validates through 96^3, so
    # the guard trips strictly above the validated size. The benched
    # workaround is JACOBI_L1; routing it HERE (config-validation /
    # setup time, before any trace) replaces a solve-time runtime
    # fault with a warned, counted fallback.
    DILU_TPU_FAULT_MIN_ROWS = 96 ** 3 + 1

    def _guard_known_faults(self, name: str) -> str:
        if name != "MULTICOLOR_DILU" or not self.levels:
            return name
        n_fine = self.levels[0].A.num_rows
        if n_fine < self.DILU_TPU_FAULT_MIN_ROWS:
            return name
        import jax
        if jax.default_backend() != "tpu" or jax.device_count() > 1:
            return name          # sharded/CPU DILU paths are unaffected
        if not getattr(self, "_fault_fallback_warned", False):
            # once per hierarchy: the guard fires for every level, but
            # one rerouted CONFIGURATION is one counted event — a
            # per-level count would inflate the series by the depth
            self._fault_fallback_warned = True
            from ..output import amgx_output
            from ..telemetry import metrics as _tm
            _tm.inc("resilience.config_fallback")
            amgx_output(
                f"amgx_tpu warning: MULTICOLOR_DILU at {n_fine} rows "
                f"on a single TPU chip hits a known runtime fault "
                f"(validated clean through 96^3); smoothing falls "
                f"back to JACOBI_L1 (resilience.config_fallback)\n")
        return "JACOBI_L1"

    def _attach_level_smoother(self, level: AMGLevel):
        from ..solvers.base import make_solver
        from ..profiling import trace_region
        name, scope = self._smoother_spec(level.level_index)
        name = self._guard_known_faults(name)
        level.smoother = make_solver(name, self.cfg, scope)
        level.smoother._owns_scaling = False
        # fused operand slabs emit directly in the hierarchy's
        # effective precision (ops/smooth.solver_fused_slabs): the
        # solve-data cast then finds them already narrow — no
        # full-precision twin ever materializes
        level.smoother._slab_dtype = self._PRECISIONS[self.precision]
        if getattr(level.smoother, "needs_cf_map", False) and \
                getattr(level, "cf_map", None) is not None:
            level.smoother.set_cf_map(level.cf_map)
        self._color_ahead(level.smoother, level.A, level.level_index)
        with trace_region(f"amg.L{level.level_index}.smoother_setup"):
            level.smoother.setup(level.A)
        self._maybe_install_stencil(level)

    @staticmethod
    def _color_ahead(solver, A, level_index: int):
        """A colored smoother's coloring, made before its setup and
        under a span of its own (amg.L<k>.coloring, a leaf beside
        smoother_setup): a JPL pass over a fine level's edges is
        seconds of host work that setup_s should be able to name."""
        color = getattr(solver, "color", None)
        if color is not None:
            from ..profiling import trace_region
            with trace_region(f"amg.L{level_index}.coloring"):
                color(A)

    def _maybe_install_stencil(self, level: AMGLevel):
        """Matrix-free install (`matrix_free` knob): when this level's
        operator is a constant-coefficient grid stencil and its
        smoother can run from coefficients alone, attach a
        StencilOperator to the smoother — its solve_data then drops
        the DIA value slab (and dinv vector / fused slabs) and every
        smooth entry routes through ops/stencil.py. `_mf_stencil` is
        ALWAYS (re)assigned so a stale stencil from a previous install
        can never survive a resetup with new (variable) values."""
        sm = level.smoother
        if sm is None:
            return
        mode = getattr(self, "matrix_free", "auto")
        on = mode == "1" or (mode == "auto"
                             and _ps.pallas_backend() == "mosaic")
        if not on or not getattr(type(sm), "supports_matrix_free",
                                 False) \
                or not getattr(sm, "fused_smoother", False):
            sm._mf_stencil = None
            return
        from ..ops.stencil import detect_stencil
        from ..profiling import trace_region
        why = {}
        with trace_region(f"amg.L{level.level_index}.mf_detect", args=why):
            sm._mf_stencil = detect_stencil(
                level.A, dinv_mode=sm.matrix_free_dinv, why=why)
        if sm._mf_stencil is None:
            from ..telemetry import metrics as _tm
            _tm.inc("amg.stencil.declined")

    def _finalize_setup(self, t0: float):
        from ..solvers.base import make_solver
        from ..profiling import trace_region
        # smoothers normally attach per level during the build (the
        # overlapped-shipping pipeline); this catches levels built by
        # paths that defer (distributed tails restore their own)
        for level in self.levels:
            if level.smoother is None:
                self._attach_level_smoother(level)
        cs_name, cs_scope = self.cfg.get_solver("coarse_solver", self.scope)
        self.coarse_solver = make_solver(cs_name, self.cfg, cs_scope)
        self.coarse_solver._owns_scaling = False
        self._color_ahead(self.coarse_solver, self.coarsest_A,
                          len(self.levels))
        with trace_region("amg.coarse_solver_setup"):
            self.coarse_solver.setup(self.coarsest_A)
        if self._ship_device is not None:
            # completion barrier of the per-level ship pipeline: every
            # prefetched transfer resolves before setup returns
            with trace_region("amg.ship_resolve",
                              counter="amg.resetup.ship_s"
                              if self._ship_counted else None):
                self._resolve_put_cache()
        self.num_levels = len(self.levels) + 1
        self._swell_steps, self._swell_model_s = self._count_swell_costs()
        self._csr_road_nnz = self._count_csr_road_nnz()
        self.setup_time = time.perf_counter() - t0
        if self.print_grid_stats:
            from ..output import amgx_printf
            amgx_printf(self.grid_stats())
        if self.convergence_analysis > 0 and self.levels:
            # convergence_analysis.cu: instrumented error-propagation
            # cycle over the first `convergence_analysis` levels
            from ..output import amgx_printf
            from .analysis import convergence_analysis
            amgx_printf(convergence_analysis(self) + "\n")

    # -- solve-phase data -------------------------------------------------
    _PRECISIONS = {"double": None, "float": "float32", "bfloat16": "bfloat16"}

    def _cast_leaf(self, leaf, dt=False):
        """Precision cast of one solve-data leaf (identity for
        structure arrays and full-precision mode). `dt` overrides the
        target dtype name — the coarse-solver subtree casts to the
        policy's f32+ coarse dtype while the levels take the full
        reduced precision."""
        import jax.numpy as jnp
        if dt is False:
            dt = self._PRECISIONS[self.precision]
        if dt is not None and hasattr(leaf, "dtype") and \
                jnp.issubdtype(leaf.dtype, jnp.inexact):
            return leaf.astype(dt)
        return leaf

    def _prefetch_leaves(self, tree):
        """Start host->device transfers of a solve-data subtree's unique
        leaves, keyed by the PRE-cast host leaf identity so solve_data
        can pick them up. The cast + device_put run on a single worker
        thread: a device_put of host memory occupies its caller for
        the copy, while the build thread spends its time inside
        GIL-releasing native sweeps — threading the ship overlaps the
        two (the reference gets the same overlap from CUDA async memcpy,
        e.g. matrix_upload's streamed transfers)."""
        import jax
        todo = []
        for leaf in jax.tree.leaves(tree):
            if hasattr(leaf, "dtype") and id(leaf) not in self._put_cache:
                todo.append(leaf)
        if not todo:
            return
        if self._ship_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._ship_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="amgx-ship")
        dev = self._ship_device
        counted = self._ship_counted

        def _ship(leaves=todo):
            # leaves are numpy on the native host path, so the casts are
            # host-side regardless of this thread's default device; the
            # rare no-toolchain fallback can leave jnp-backed leaves
            # that transfer uncast (full precision) — acceptable for a
            # path that is already warning-slow. The region is
            # deliberately NOT amg.-prefixed: it runs on the ship
            # worker, overlapped with the main-thread build — summing
            # it with the amg.* regions would double-count wall time
            # (the non-overlapped remainder shows up in
            # amg.ship_resolve instead).
            from ..profiling import trace_region
            from ..telemetry import metrics as _tm
            with trace_region("ship.cast_put", counter="amg.resetup.ship_s"
                              if counted else None):
                cast = [self._cast_leaf(x) for x in leaves]
                if counted:
                    _tm.inc("amg.resetup.ship_bytes",
                            sum(int(x.nbytes) for x in cast))
                return jax.device_put(cast, dev)

        fut = self._ship_pool.submit(_ship)
        for i, src in enumerate(todo):
            self._put_cache[id(src)] = (src, (_PENDING, fut, i))

    def _resolve_put_cache(self):
        """Wait for in-flight ship futures and replace placeholders with
        device arrays."""
        for key, (src, dev) in list(self._put_cache.items()):
            if isinstance(dev, tuple) and dev[0] is _PENDING:
                self._put_cache[key] = (src, dev[1].result()[dev[2]])

    def _prefetch_level(self, level: AMGLevel):
        """Ship a finished level's solve data while the rest of the
        hierarchy is still building (device_put is async; the transfer
        overlaps the remaining host compute): the level
        operators, the transfer operators, and — now that smoothers
        attach per level — the smoother's solve-data payloads (layout
        slabs, damping tables, color maps)."""
        if self._ship_device is None:
            return
        from ..profiling import trace_region
        # the build thread's share of the ship: slim views, the
        # smoother's solve-data tree, the hand-over to the ship worker
        with trace_region(f"amg.L{level.level_index}.prefetch"):
            A_slim = level.A.slim_for_spmv()
            if getattr(level.smoother, "_mf_stencil", None) is not None:
                # matrix-free level: never ship the value slab — the
                # solve-data tree carries only the stencil coefficients
                from ..ops.stencil import mf_slim
                A_slim = mf_slim(A_slim)
            pieces = [A_slim]
            for name in ("P", "R"):
                op = getattr(level, name, None)
                if op is not None and op.initialized:
                    pieces.append(op.slim_for_spmv())
            if level.smoother is not None:
                pieces.append(level.smoother.solve_data_part())
            self._prefetch_leaves(pieces)

    def _solve_data_children(self) -> tuple:
        nodes = [lv.smoother for lv in self.levels] + [self.coarse_solver]
        return tuple(s for s in nodes if isinstance(s, SolveDataOwner))

    def _solve_tree(self) -> Dict[str, Any]:
        """The solve-data tree before placement and precision casts:
        what solve_data() ships or casts, and what the static signature
        (amg/signature.py) reads the shapes from. Host work alone once
        the smoothers and the coarse solver keep their trees: the two
        readers each make it, and share what those keep."""
        return {
            "levels": [lv.level_data() for lv in self.levels],
            "coarse": self.coarse_solver.solve_data_part(),
        }

    def _build_solve_data(self) -> Dict[str, Any]:
        """Where the leaves come from is the one difference between a
        host-built and a device-built hierarchy: the ship worker's
        transfers, or the device arrays themselves (cast, under a
        reduced amg_precision). Either way once a (re)setup."""
        data = self._solve_tree()
        if self._ship_device is not None:
            # host-built hierarchy: transfer the UNIQUE arrays (each
            # level's matrix arrays appear twice in the tree by object
            # identity — level data + smoother data; per-leaf transfer
            # would double the bytes shipped and HBM). Leaves prefetched by
            # _prefetch_level during the build are already on (or in
            # flight to) the accelerator; only the stragglers (smoother
            # and coarse-solver payloads) transfer here. amg_precision
            # casting happens host-side before the wire.
            from ..profiling import trace_region
            # ship.-prefixed (NOT amg.): solve_data may run inside a
            # caller's amg.device_sync span — an amg.* region here would
            # double-count against the disjoint-leaf attribution sum.
            # The setup-side barrier (amg.ship_resolve in
            # _finalize_setup) already accounts the level transfers.
            with trace_region("ship.resolve_stragglers"):
                self._prefetch_leaves(data)
                self._resolve_put_cache()
                return jax.tree.map(
                    lambda leaf: self._put_cache[id(leaf)][1]
                    if hasattr(leaf, "dtype") else leaf, data)
        dt = self._PRECISIONS[self.precision]
        if dt is not None:
            # mixed-precision preconditioning (the dDFI-mode analog,
            # include/amgx_config.h:102-131): the whole stored hierarchy
            # and cycle run in reduced precision inside an f64 flexible
            # Krylov outer loop — on TPU this halves (or quarters) HBM
            # traffic and turns on the f32/bf16 Pallas kernel suite.
            # The COARSE-solver subtree casts to the policy's f32+
            # coarse dtype (precision.py): the dense factorization,
            # back-substitution and the K-cycle coarse matvec never
            # run below f32 even when the levels stream bf16
            memo = {}
            pre = getattr(self, "_resetup_precast", None) or {}
            cdt = self.precision_policy.coarse_dtype

            import jax.numpy as jnp

            def mk(target):
                tgt = jnp.dtype(target)

                def cast(leaf):
                    key = (id(leaf), target)
                    if key not in memo:
                        # the one-dispatch value-resetup emits the
                        # reduced-precision twins inside its own
                        # program; reuse a twin only when its dtype
                        # matches THIS subtree's target (the coarse
                        # subtree's f32+ target can differ from the
                        # level target under bf16)
                        tw = pre.get(id(leaf))
                        if tw is not None and tw.dtype == tgt:
                            out = tw
                        else:
                            out = self._cast_leaf(leaf, target)
                        memo[key] = (leaf, out)
                    return memo[key][1]
                return cast
            data = {"levels": jax.tree.map(mk(dt), data["levels"]),
                    "coarse": jax.tree.map(mk(cdt), data["coarse"])}
        return data

    def _sweeps(self, level_index: int, pre: bool) -> int:
        s = self.presweeps if pre else self.postsweeps
        if level_index == 0 and self.finest_sweeps >= 0:
            s = self.finest_sweeps
        if self.intensive_smoothing:
            s = max(4 * s, 4)
        return s

    def color_steps_per_cycle(self) -> int:
        """Ordered color steps one cycle is made of: over the levels,
        sweeps x the smoother's steps a sweep, and the coarsest level's
        where a colored smoother stands in for a solve. (A V cycle's
        count: W and F visit coarse levels more than once.)"""
        def steps(solver):
            fn = getattr(solver, "color_steps_per_iteration", None)
            return fn() if fn is not None else 0
        total = sum(
            (self._sweeps(k, True) + self._sweeps(k, False))
            * steps(lv.smoother) for k, lv in enumerate(self.levels))
        cs = getattr(self, "coarse_solver", None)
        if cs is not None and cs.is_smoother \
                and cs.name != "DENSE_LU_SOLVER":
            total += self.coarsest_sweeps * steps(cs)
        return total

    _swell_steps = 0      # _count_swell_costs() of the last set-up:
    _swell_model_s = 0.0  # vreg-steps and the model's seconds a cycle

    @staticmethod
    def swell_account(M) -> list:
        """(listed chunks, kpad, blocks) of each SWELL layout one
        application of `M` runs through: its own, or the two of its
        row-split form (A' first); none where it has neither. Read from
        the layouts' host copies."""
        from ..matrix import host_mirror_asarray
        from ..ops.pallas_swell import listed_chunks
        if getattr(M, "split", None) is not None:
            return [row for part in M.split
                    for row in AMG.swell_account(part)]
        if getattr(M, "swell_nchunk", None) is None:
            return []
        return [(listed_chunks(host_mirror_asarray(M.swell_nchunk)),
                 int(M.swell_cols.shape[2]), int(M.swell_cols.shape[0]))]

    def _count_swell_costs(self):
        """What the SWELL gather of one cycle costs, by two counts
        (ops/pallas_swell): its vreg-steps (`vreg_steps`: over an
        operator's row groups, the window chunks listed x the vregs of
        a group's tile) and the seconds the layout choice's model puts
        on it (`model_seconds`); over the levels, A's x (sweeps + the
        residual), P's and R's once, and the coarsest operator's where
        a smoother stands in for a solve. Read as a set-up ends, so a
        solve fetches nothing for it. (A V cycle's count, as
        color_steps_per_cycle's.)"""
        from ..ops.pallas_swell import model_seconds, tile_vregs
        return (
            self._sum_over_cycle_operators(lambda M: sum(
                listed * tile_vregs(kpad)
                for listed, kpad, _blocks in self.swell_account(M))),
            self._sum_over_cycle_operators(lambda M: sum(
                model_seconds(*row) for row in self.swell_account(M))))

    def swell_vreg_steps_per_cycle(self) -> int:
        return self._swell_steps

    def swell_model_s_per_cycle(self) -> float:
        return self._swell_model_s

    _csr_road_nnz = 0     # _count_csr_road_nnz() of the last set-up

    def _count_csr_road_nnz(self) -> int:
        """Non-zeros one cycle sends down the XLA gather + segment-sum
        road: over the levels, the non-zeros of every operator
        application (A's x (sweeps + the residual), P's and R's once,
        the coarsest operator's where a smoother stands in for a solve)
        whose operator has no DIA / ELL / SWELL layout (`_layout_of`:
        "csr"). Host metadata alone. (A V cycle's count, as
        color_steps_per_cycle's.)"""
        def nnz(M):
            return int(M.nnz) if M is not None \
                and self._layout_of(M) == "csr" else 0
        return self._sum_over_cycle_operators(nnz)

    def _sum_over_cycle_operators(self, cost) -> int:
        """`cost(M)` summed over the operator applications one V cycle
        makes: a level's A x (sweeps + the residual), its P and R once,
        and the coarsest operator x `coarsest_sweeps` where a smoother
        stands in for a solve."""
        total = 0
        for k, lv in enumerate(self.levels):
            total += cost(lv.A) * (self._sweeps(k, True)
                                   + self._sweeps(k, False) + 1)
            total += cost(getattr(lv, "P", None))
            total += cost(getattr(lv, "R", None))
        cs = getattr(self, "coarse_solver", None)
        if cs is not None and cs.is_smoother \
                and cs.name != "DENSE_LU_SOLVER":
            total += self.coarsest_sweeps * cost(self.coarsest_A)
        return total

    def csr_road_nnz_per_cycle(self) -> int:
        return self._csr_road_nnz

    def geo_transfers_per_cycle(self):
        """(levels on the one-pass road, levels on the XLA road) of the
        GEO levels one single-device cycle runs through, from what the
        road is chosen by (aggregation/transfer.road): static after
        setup. (A V cycle's count, as color_steps_per_cycle's.)"""
        dt = self._PRECISIONS[self.precision]
        roads = [lv.geo_transfer_road(dt if dt is not None else lv.A.dtype)
                 for lv in self.levels if hasattr(lv, "geo_transfer_road")]
        return roads.count("onepass"), roads.count("xla")

    def cycle(self, data, b, x):
        """One multigrid cycle (CycleFactory::generate analog). With
        amg_precision=float/bfloat16 the cycle computes in the reduced
        precision and the correction is returned in the caller's dtype."""
        from .cycles import run_cycle
        dt = self._PRECISIONS[self.precision]
        out_dtype = x.dtype
        if dt is not None:
            b, x = b.astype(dt), x.astype(dt)
        with self._note_dia_smooth():
            x = run_cycle(self, self.cycle_name, data, b, x)
        return x.astype(out_dtype)

    _dia_smooth = (0, 0)    # what the last traced cycle launched

    @contextlib.contextmanager
    def _note_dia_smooth(self):
        """Keep what ONE cycle launches of the fused DIA smoother
        (`_dia_smooth_call`s, and the lane-rows x applications their
        plans compute, a halo's or a drain's included) while it is
        TRACED: static per program, and no set-up clears it, so a
        rebuild that keeps the program keeps its count."""
        with _ps.count_smooth_launches() as tally:
            yield
        self._dia_smooth = tuple(tally)

    def dia_smooth_per_cycle(self):
        return self._dia_smooth

    # -- observability ----------------------------------------------------
    @staticmethod
    def _layout_of(M) -> str:
        if getattr(M, "dia_vals", None) is not None:
            return "dia"
        if getattr(M, "swell_vals", None) is not None:
            return "swell"
        if getattr(M, "split", None) is not None:
            return "split"
        if getattr(M, "ell_vals", None) is not None:
            return "ell"
        return "csr"

    def grid_stats_dict(self) -> Dict[str, Any]:
        """Grid statistics as STRUCTURED data (the single source of
        truth — `grid_stats()` renders its text from this, and it feeds
        `SolveReport.hierarchy` + the C API's
        `AMGX_solver_get_grid_stats`). Everything reads host metadata
        (shapes, layout presence) — building the dict issues no device
        transfers, so the per-solve report path may call it freely."""
        mats = [lv.A for lv in self.levels]
        coarsest = getattr(self, "coarsest_A", None)
        if coarsest is not None:
            mats = mats + [coarsest]
        rows: List[Dict[str, Any]] = []
        total_nnz = 0
        total_rows = 0
        for i, M in enumerate(mats):
            nnz = M.nnz * M.block_size + (
                M.num_rows * M.block_size if M.has_external_diag else 0)
            rows.append({
                "level": i,
                "rows": int(M.num_rows),
                "nnz": int(nnz),
                "sparsity": nnz / max(M.num_rows, 1) ** 2,
                "layout": self._layout_of(M),
            })
            total_nnz += nnz
            total_rows += M.num_rows
        fine_rows = rows[0]["rows"] if rows else 0
        fine_nnz = rows[0]["nnz"] if rows else 0
        return {
            "algorithm": self.algorithm,
            "cycle": self.cycle_name,
            "num_levels": len(mats),
            "levels": rows,
            "total_rows": int(total_rows),
            "total_nnz": int(total_nnz),
            "grid_complexity": total_rows / max(fine_rows, 1),
            "operator_complexity": total_nnz / max(fine_nnz, 1),
        }

    def grid_stats(self) -> str:
        """Grid-statistics report (print_grid_stats analog,
        src/amg.cu:1231-1350). Rendered from `grid_stats_dict()` so the
        text and the structured surface can never drift apart."""
        d = self.grid_stats_dict()
        lines = ["AMG Grid:",
                 f"         Number of Levels: {d['num_levels']}",
                 "            LVL         ROWS               NNZ    SPRSTY",
                 "         " + "-" * 50]
        for row in d["levels"]:
            lines.append(f"           {row['level']:3d}  "
                         f"{row['rows']:11d}  {row['nnz']:16d}  "
                         f"{row['sparsity']:8.3g}")
        lines.append("         " + "-" * 50)
        lines.append(f"         Grid Complexity: "
                     f"{d['grid_complexity']:.5g}")
        lines.append(f"         Operator Complexity: "
                     f"{d['operator_complexity']:.5g}")
        return "\n".join(lines)
