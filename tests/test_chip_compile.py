"""The chip's compiler, asked in the sandbox.

AOT-compiles, from shapes, the kernels of the main path for a DESCRIBED
TPU v5e (no chip attached) at the flagship's real fine-level shape
(7-pt 128^3) and one coarse-level shape, so a kernel Mosaic refuses —
an int64 in a kernel body, a block shape off the (8, 128) tiling, more
VMEM than the limit handed to the compiler — fails tier-1 instead of
the first solve on a chip. A pass here is not a chip run:
`python chip_smoke.py` on the chip is.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when
it cannot be, never at import and never autouse; everything compiles
in this process (the TPU library is held by one process); all such
tests live in this one file.
"""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops import pallas_swell as sw
from amgx_tpu.ops import stencil

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

FINE, COARSE = 128, 32          # 7-pt n^3 grids: flagship L0, and L2
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the one capability function (ops.pallas_spmv.
    pallas_backend) onto its compiled-for-chip branch, from inside the
    test: the program has no option for this."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ps.pallas_backend() == "mosaic"


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it off around
    these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec7(n):
    """StencilSpec of the constant-coefficient 7-pt operator on n^3
    (Chebyshev levels carry no dinv)."""
    offs = (-n * n, -n, -1, 0, 1, n, n * n)
    shifts = ((0, 0, -1), (0, -1, 0), (-1, 0, 0), (0, 0, 0),
              (1, 0, 0), (0, 1, 0), (0, 0, 1))
    return stencil.StencilSpec(offs, shifts, (n, n, n), n ** 3, None, 3)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n", [FINE, COARSE])
def test_dia_spmv_compiles(n, one_chip, on_tpu, no_persistent_cache):
    sp = _spec7(n)
    rows_pad = ps.dia_padded_rows(7, sp.n)
    _compile(lambda v, x: ps._dia_spmv_call(v, x, sp.offsets, sp.n),
             one_chip, ((7, rows_pad, 128), F32), ((sp.n,), F32))


# (sweeps, with_residual): the flagship's Chebyshev pre-smoother is 5
# damped applications + the residual, its post-smoother 5 without.
# 256 and FINE are levels of many blocks (the carry form: its rings,
# the lagged outputs and, at 256, 64 blocks of 2,048 rows), COARSE one
# block
@pytest.mark.parametrize("n", [256, FINE, COARSE])
@pytest.mark.parametrize("ns,wr", [(5, True), (5, False)])
def test_dia_smooth_stencil_twin_compiles(n, ns, wr, one_chip, on_tpu,
                                          no_persistent_cache):
    sp = _spec7(n)
    assert stencil.stencil_smooth_supported(sp, F32, ns, wr)
    plan = ps.dia_smooth_plan(sp.offsets, 7, sp.n, ns, wr, coeffs=True)
    assert (plan.lag > 0) == (n > COARSE)
    _compile(lambda c, t, b, x: ps._dia_stencil_smooth_call(
        c, t, b, x, sp, wr),
        one_chip, ((7,), F32), ((ns,), F32), ((sp.n,), F32),
        ((sp.n,), F32))


def test_dia_smooth_stencil_twin_27pt_compiles(one_chip, on_tpu,
                                               no_persistent_cache):
    """A 27-point matrix-free level keeps 27 masked value planes live:
    the widest body the plan's VMEM arithmetic has to cover
    (ops.pallas_spmv.smooth_body_planes)."""
    n = 64
    shifts = tuple((dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                   for dx in (-1, 0, 1))
    offs = tuple(dx + n * dy + n * n * dz for dx, dy, dz in shifts)
    sp = stencil.StencilSpec(offs, shifts, (n, n, n), n ** 3, "l1", 13)
    assert stencil.stencil_smooth_supported(sp, F32, 2, True)
    _compile(lambda c, t, b, x: ps._dia_stencil_smooth_call(
        c, t, b, x, sp, True),
        one_chip, ((27,), F32), ((2,), F32), ((sp.n,), F32),
        ((sp.n,), F32))


# the slab twin as classical DIA levels run it: Jacobi, dinv slab
@pytest.mark.parametrize("n", [FINE, COARSE])
@pytest.mark.parametrize("ns,wr", [(2, True), (1, False)])
def test_dia_smooth_slab_twin_compiles(n, ns, wr, one_chip, on_tpu,
                                       no_persistent_cache):
    sp = _spec7(n)
    assert ps.dia_smooth_plan(sp.offsets, 7, sp.n, ns, wr) is not None
    q = sum(ps.smooth_quota_rows(sp.offsets, sp.n))
    _compile(lambda v, d, t, b, x: ps._dia_smooth_call(
        v, d, t, b, x, sp.offsets, sp.n, wr),
        one_chip, ((7, q, 128), F32), ((q, 128), F32), ((ns,), F32),
        ((sp.n,), F32), ((sp.n,), F32))


@pytest.mark.parametrize("n", [FINE, COARSE])
@pytest.mark.parametrize("form", ["pdot_beta", "ddot_self"])
def test_dia_spmv_dot_compiles(n, form, one_chip, on_tpu,
                               no_persistent_cache):
    sp = _spec7(n)
    rows_pad = ps.dia_padded_rows(7, sp.n)
    vec = ((sp.n,), F32)
    if form == "pdot_beta":       # CG: p' = z + beta p, Ap', p'.Ap'
        _compile(lambda v, p, z, beta: ps._dia_spmv_dot_call(
            v, p, z, beta, None, sp.offsets, sp.n),
            one_chip, ((7, rows_pad, 128), F32), vec, vec, ((), F32))
    else:                         # BiCGStab: t = A s, t.s and t.t
        _compile(lambda v, p, d: ps._dia_spmv_dot_call(
            v, p, None, None, d, sp.offsets, sp.n, self_dot=True),
            one_chip, ((7, rows_pad, 128), F32), vec, vec)


@pytest.mark.parametrize("n", [FINE, COARSE])
def test_stencil_spmv_dot_compiles(n, one_chip, on_tpu,
                                   no_persistent_cache):
    sp = _spec7(n)
    assert stencil.stencil_spmv_dot_supported(sp, F32)
    vec = ((sp.n,), F32)
    _compile(lambda c, p, z, beta: ps._dia_spmv_dot_call(
        None, p, z, beta, None, sp.offsets, sp.n, mf=sp, coeffs=c),
        one_chip, ((7,), F32), vec, vec, ((), F32))


# the HPCG configuration's fine level and its first coarse level: 27
# diagonals with a halo of nx*ny + nx + 1 rows either way
HPCG_LEVELS = [192, 96]


def _box27(n):
    shifts = tuple((dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                   for dx in (-1, 0, 1))
    return shifts, tuple(dx + n * dy + n * n * dz for dx, dy, dz in shifts)


@pytest.mark.parametrize("n", HPCG_LEVELS)
@pytest.mark.parametrize("kernel", ["spmv", "spmv_dot"])
def test_dia_27pt_kernels_compile(n, kernel, one_chip, on_tpu,
                                  no_persistent_cache):
    """The probe's and the residual's `_dia_spmv_call`, and PCG's fused
    SpMV + dot, on 27 offsets at the configuration's level shapes."""
    _shifts, offs = _box27(n)
    rows_pad = ps.dia_padded_rows(27, n ** 3)
    slab, vec = ((27, rows_pad, 128), F32), ((n ** 3,), F32)
    if kernel == "spmv":
        _compile(lambda v, x: ps._dia_spmv_call(v, x, offs, n ** 3),
                 one_chip, slab, vec)
    else:
        _compile(lambda v, p, z, beta: ps._dia_spmv_dot_call(
            v, p, z, beta, None, offs, n ** 3),
            one_chip, slab, vec, vec, ((), F32))


@pytest.mark.parametrize("n", HPCG_LEVELS)
def test_parity_sweep_compiles(n, one_chip, no_persistent_cache):
    """The color step on its own rows (ops/parity_sweep.py), a whole
    symmetric sweep: plain XLA, no gather and no matrix product for the
    chip's compiler to make of a cut, and one rolled loop whose steps
    are traced once."""
    import itertools
    from amgx_tpu.ops import parity_sweep
    shifts, _offs = _box27(n)
    rows = tuple(itertools.product((0, 1), repeat=2))
    plan = parity_sweep.ParityPlan((n, n, n), shifts, rows, False)
    m = n // 2

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, F32, sharding=one_chip)

    slabs = {"vals": tuple(shape(27, m, m, n) for _ in rows),
             "dinv": tuple(shape(m, m, n) for _ in rows)}
    compiled = jax.jit(lambda sl, b, x: parity_sweep.sweep(
        plan, sl, b, x, 1.0, True)).lower(
        slabs, shape(n ** 3), shape(n ** 3)).compile()
    text = compiled.as_text()
    assert " gather(" not in text and "Gather" not in text
    assert " convolution(" not in text and " dot(" not in text
    assert text.count(" while(") == 1
    # the program holds what it is given and little more
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes / 2


@pytest.mark.parametrize("n", [FINE, COARSE])
def test_cg_update_compiles(n, one_chip, on_tpu, no_persistent_cache):
    vec = ((n ** 3,), F32)
    _compile(lambda x, p, r, ap, a: ps._cg_update_call(x, p, r, ap, a),
             one_chip, vec, vec, vec, vec, ((), F32))


# the flagship's basis at 128^3 and at the 256^3 that fills a chip
# (gmres_n_restart 10: V has 11 rows, Z 10), and a restart of 30: the
# column block shrinks with the rows, the live-row count is an operand
@pytest.mark.parametrize("n_rows,n", [(11, FINE), (10, FINE), (11, 256),
                                      (31, FINE)])
@pytest.mark.parametrize("project", [False, True])
def test_basis_pass_compiles(n_rows, n, project, one_chip, on_tpu,
                             no_persistent_cache):
    rows128 = ps.basis_padded_rows(n_rows, n ** 3)
    shapes = (((n_rows, rows128, 128), F32), ((rows128, 128), F32),
              ((n_rows,), F32), ((), jnp.int32))
    args = [jax.ShapeDtypeStruct(s[0], s[1]) for s in shapes]
    assert ps.basis_pass_supported(*args[:2])
    compiled = _compile(lambda V, w, c, nl: ps._basis_pass_call(
        V, w, c, nl, project=project), one_chip, *shapes)
    # the basis stays where it is: the kernel's own memory is VMEM
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024


def test_cgs2_step_compiles_under_shard_map(topo, on_tpu,
                                            no_persistent_cache):
    """A distributed f32 solve takes the kernel inside shard_map (as
    distributed/solver.py maps it: check_vma off): one program across
    the four chips of the described host, each shard's three readings
    ending in one all-reduce each."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from amgx_tpu.ops import blas
    mesh = Mesh(np.array(topo.devices).reshape(4), ("p",))
    n_rows = 11
    rows128 = ps.basis_padded_rows(n_rows, FINE ** 3 // 4)

    def step(V, w, nlive):
        return blas.cgs2_step(V[0], w[0], nlive, axis_name="p")

    fn = jax.shard_map(step, mesh=mesh, in_specs=(P("p"), P("p"), P()),
                       out_specs=(P(), P("p"), P()), check_vma=False)
    args = [jax.ShapeDtypeStruct((4, n_rows, rows128, 128), F32,
                                 sharding=NamedSharding(mesh, P("p"))),
            jax.ShapeDtypeStruct((4, rows128, 128), F32,
                                 sharding=NamedSharding(mesh, P("p"))),
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 3


# SWELL shapes of a classical coarse level under 7-pt 64^3 (PMIS+D2):
# 32 super-blocks of 1024 rows, 24 slots per row, a 96-row x window
@pytest.mark.parametrize("n", [256, FINE, 192])
@pytest.mark.parametrize("op", ["restrict", "prolong_correct"])
def test_geo_transfer_compiles(n, op, one_chip, on_tpu,
                               no_persistent_cache):
    """A GEO level's two transfers through the road they choose
    (amg/aggregation/transfer.py) at the flagship's 256^3 -> 128^3 and
    128^3 -> 64^3: each is one `_dia_geo_*_call` and nothing of the
    fine vector's size beside it. 192^3 (x rows off the 128-lane rows)
    declines and lowers the XLA form."""
    from amgx_tpu.amg.aggregation import transfer
    fs, axes = (n, n, n), (0, 1, 2)
    fine = jax.ShapeDtypeStruct((n ** 3,), F32, sharding=one_chip)
    coarse = jax.ShapeDtypeStruct((n ** 3 // 8,), F32, sharding=one_chip)
    if op == "restrict":
        lowered = jax.jit(
            lambda r: transfer.restrict(r, fs, axes)).lower(fine)
    else:
        lowered = jax.jit(
            lambda x, xc: transfer.prolong_correct(x, xc, fs, axes),
            donate_argnums=0).lower(fine, coarse)
    compiled = lowered.compile()
    text = compiled.as_text()
    if n == 192:
        assert transfer.road(fs, axes, F32) == "xla"
        assert "tpu_custom_call" not in text
        return
    assert transfer.road(fs, axes, F32) == "onepass"
    kernel = {"restrict": "_dia_geo_restrict_call",
              "prolong_correct": "_dia_geo_prolong_call"}[op]
    assert text.count("tpu_custom_call") == 1 and kernel in text
    # one pass: no temporary, and x's buffer is the result's
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    if op == "prolong_correct":
        assert mem.alias_size_in_bytes == 4 * n ** 3


@pytest.mark.parametrize("n", [256, FINE, COARSE])
def test_slab_row_sums_compile_to_one_pass(n, one_chip,
                                           no_persistent_cache):
    """CHEBYSHEV_POLY's Gershgorin row sums on a DIA level
    (solvers/polynomial.dia_abs_row_sums) at the flagship's 256^3,
    128^3 and a coarse level: one fusion that streams the slab where it
    lies, with no temporary, no relayout copy and no scatter (the COO
    road's scatter-add held the host for half of a 256^3 time step)."""
    from amgx_tpu.solvers.polynomial import dia_abs_row_sums
    rows = n ** 3
    slab = jax.ShapeDtypeStruct(
        (7, ps.dia_padded_rows(7, rows), ps.LANES), F32, sharding=one_chip)
    compiled = dia_abs_row_sums.lower(slab, num_rows=rows).compile()
    text = compiled.as_text()
    assert "scatter" not in text and " copy(" not in text
    assert text.count(" fusion(") == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes == 4 * rows


def _strided_or_padding(closed_jaxpr):
    """Eqns outside the kernels that move a vector between a grid and
    its paired grid the XLA way: interior pads, strided slices (as
    `slice` or as the `gather` jnp's stepped indexing traces to)."""
    from amgx_tpu.telemetry import census as _census
    hits = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                continue
            strides = eqn.params.get("strides") if name == "slice" else None
            if name in ("pad", "gather") or (
                    strides is not None and any(s != 1 for s in strides)):
                hits.append(name)
            for sub in _census.subjaxprs(eqn):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return hits


@pytest.mark.parametrize("grid,onepass", [((128, 8, 8), True),
                                          ((192, 8, 8), False)])
def test_geo_cycle_on_the_chips_branch_has_no_xla_transfer(grid, onepass,
                                                           on_tpu):
    """Jaxpr census of the flagship's cycle steered onto the chip's
    branch, two levels (L0 and the coarse solve): where L0 is on the
    kernels' grid the cycle is the two smoother kernels and the two
    transfer kernels, with no pad and no strided slice outside them;
    at nx = 192 the same census finds the XLA restriction's."""
    import amgx_tpu as amgx
    from amgx_tpu.config import Config
    from amgx_tpu.presets import FLAGSHIP
    from amgx_tpu.telemetry import census as _census

    A = amgx.gallery.poisson("7pt", *grid).init()
    slv = amgx.create_solver(Config.from_string(
        FLAGSHIP + ", amg:max_levels=2"))
    slv.setup(A)
    amg = slv.preconditioner.preconditioner.amg
    assert [lv.geo_fine_shape for lv in amg.levels] == [grid]
    d = amg.solve_data()
    v = jnp.ones(A.num_rows, F32)
    jaxpr = jax.make_jaxpr(lambda b, x: amg.cycle(d, b, x))(v, v)
    assert not any(c["interpret"] for c in _census.pallas_calls(jaxpr))
    counts = _census.kernel_counts(jaxpr)
    assert counts.get("_dia_smooth_call", 0) == 2
    # what the trace left for the smoother.dia_* counters: the two
    # launches, and the row-applications of their two plans
    sp = amg.levels[0].level_data()["smoother"]["stencil"].spec()
    assert amg.dia_smooth_per_cycle() == (2, sum(
        ps.dia_smooth_plan(sp.offsets, 7, sp.n, 5, wr,
                           coeffs=True).row_apps for wr in (True, False)))
    moved = _strided_or_padding(jaxpr)
    if onepass:
        assert amg.geo_transfers_per_cycle() == (1, 0)
        assert counts.get("_dia_geo_restrict_call", 0) == 1
        assert counts.get("_dia_geo_prolong_call", 0) == 1
        assert moved == []
    else:
        assert amg.geo_transfers_per_cycle() == (0, 1)
        assert not any(k.startswith("_dia_geo") for k in counts)
        # the XLA restriction's stepped sums; its f32 prolongation
        # spreads x through a 0/1 matrix and pads nothing
        assert moved and set(moved) == {"gather"}


@pytest.mark.parametrize("w128,kpad,longest", [(96, 21, 40),
                                               (1488, 112, 120),
                                               (4096, 256, 4096)])
@pytest.mark.parametrize("kernel", ["spmv", "smooth"])
def test_swell_kernels_compile(kernel, w128, kpad, longest, one_chip,
                               on_tpu, no_persistent_cache):
    """With the row groups' chunk lists (PR 48: a blocked SMEM operand,
    (nb, 8, 1 + L) int32, a block's 8 lists a grid step) at a slot
    count off the tiling under a narrow window (cell 9's L0.R: `kpad`
    21), 14 vregs deep under a window of 1,488 chunks (its L2.A:
    `kpad` 112, lists up to 114 chunks), and at the largest window,
    slot count and list `swell_budget` lets through (a list is at most
    the window: 2 x 8 x 4,097 words of SMEM, which no gate counts)."""
    nb = 32
    n = nb * sw.BLOCK_ROWS
    ent = ((nb, sw.SUBS, kpad, 128), jnp.int32)
    val = ((nb, sw.SUBS, kpad, 128), F32)
    blk = ((nb,), jnp.int32)
    nch = ((nb, sw.SUBS, 1 + longest), jnp.int32)
    vec = ((n,), F32)
    if kernel == "spmv":
        _compile(lambda c, v, c0, nc, x: sw._swell_spmv_call(
            c, v, c0, nc, x, w128, n),
            one_chip, ent, val, blk, nch, vec)
    else:
        _compile(lambda c, v, c0, nc, x, b, d, t: sw._swell_smooth_call(
            c, v, c0, nc, x, b, d, t, w128, n, True),
            one_chip, ent, val, blk, nch, vec, vec, vec, ((1,), F32))


@pytest.mark.parametrize("rows,cols,w128,kpad,longest", [
    (4, 2, 16, 4, 8),          # A' of a P: pieces of 4 entries
    (2, 4, 8, 5, 8),           # S of it: a row's pieces, adjacent
    (6, 4, 160, 16, 80),       # A' of a long-row level: pieces of 16
    (4, 6, 8, 18, 8),          # S of it
    (5, 1, 112, 64, 112),      # pieces of 64 under a window of the level
    # forms `split_pays` takes over a layout the budget admits (PR 51):
    (64, 57, 1480, 8, 48),     # A' of cell 9's L0.R at K 8: one vreg a tile
    (56, 64, 16, 2, 8),        # S of its L1.A: two pieces a row at most
    (100, 50, 344, 16, 40)])   # A' of cell 10's L1.A at K 16: 798 blocks
def test_row_split_operators_compile(rows, cols, w128, kpad, longest,
                                     one_chip, on_tpu, no_persistent_cache):
    """The two factors of the row-split SWELL form A = S A'
    (ops/pallas_swell.split_rows_host) are NOT square and their slot
    counts are small and off the tiling: A' has the operator's columns
    under more rows, S has A's rows over A''s. Shapes of the
    convection-diffusion cell's hierarchy (PR 49), in 1,024-row blocks."""
    nb = 8 * rows
    n = nb * sw.BLOCK_ROWS
    n_cols = 8 * cols * sw.BLOCK_ROWS
    _compile(lambda c, v, c0, nc, x: sw._swell_spmv_call(
        c, v, c0, nc, x, w128, n),
        one_chip, ((nb, sw.SUBS, kpad, 128), jnp.int32),
        ((nb, sw.SUBS, kpad, 128), F32), ((nb,), jnp.int32),
        ((nb, sw.SUBS, 1 + longest), jnp.int32), ((n_cols,), F32))


def test_declined_families_decline_on_chip_only(on_tpu):
    """The one family Mosaic still refuses (the bf16 operand windows of
    the DIA kernels: a slice off the (8, 128) tiling) says no on the
    compiled-for-chip branch, and yes again under the interpreter, so
    the CPU interpret suites of those kernels keep running."""
    sp = _spec7(16)
    assert list(ps.declined_families()) == [
        "bf16 operand windows of dia_smooth / dia_spmv_dot (slab and "
        "stencil twins)"]
    assert ps.kernel_dtype_ok(F32) and not ps.kernel_dtype_ok(jnp.bfloat16)
    assert not stencil.stencil_smooth_supported(sp, jnp.bfloat16, 2, True)
    with ps.force_pallas_interpret():
        assert ps.declined_families() == {}
        assert ps.kernel_dtype_ok(jnp.bfloat16)
        assert stencil.stencil_smooth_supported(sp, jnp.bfloat16, 2, True)


def test_scope_table_puts_every_kernel_of_the_solve_under_a_stage(
        one_chip, on_tpu, no_persistent_cache):
    """The flagship's whole solve program, compiled for the chip at
    16^3: the instruction names a profiler trace carries
    (`_dia_smooth_call.<n>`, `pad.<n>`) are in the compiled text, and
    `telemetry.programs` maps every Pallas kernel among them to the
    cycle level or Krylov stage that issues it."""
    import fnmatch
    import json
    import re
    import amgx_tpu as amgx
    from amgx_tpu import gallery
    from amgx_tpu.config import Config
    from amgx_tpu.telemetry import programs

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "flagship-p7-128.json")) as f:
        options = json.load(f)["solver"]["options"]
    A = gallery.poisson("7pt", 16, 16, 16).init()
    slv = amgx.create_solver(Config.from_string(options))
    slv.setup(A)

    def shape(x):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    data = jax.tree_util.tree_map(shape, slv.solve_data())
    b = jax.ShapeDtypeStruct((A.num_rows,), jnp.float64, sharding=one_chip)
    text = jax.jit(slv._build_solve_fn()).lower(data, b, b).compile(
        ).as_text()
    names = programs.parse_op_names(text)
    kernels = [n for n in names if fnmatch.fnmatchcase(n, "_dia_*_call*")]
    smooth = [n for n in kernels if n.startswith("_dia_smooth")]
    levels = len(slv.preconditioner.preconditioner.amg.levels)
    assert levels >= 2 and len(smooth) == 2 * levels, kernels
    for n in smooth:
        assert re.fullmatch(r"amg\.L\d+\.(pre|post)smooth",
                            programs.scope_of(names[n])), names[n]
    assert {programs.scope_of(names[n]) for n in smooth} == {
        f"amg.L{k}.{stage}" for k in range(levels)
        for stage in ("presmooth", "postsmooth")}
    # the shell's SpMVs are kernels too: under a krylov.* stage
    assert len(kernels) > len(smooth)
    for n in set(kernels) - set(smooth):
        assert programs.scope_of(names[n]).startswith("krylov."), names[n]


def test_chip_smoke_flagship_phase_rehearsal():
    """Rehearsal 1 of chip_smoke.py kept as a test so the script cannot
    rot between chip runs: the flagship phase (setup, three solves,
    plain path, resetup) at 16^3 under the Pallas interpreter."""
    with ps.force_pallas_interpret():
        chip_smoke.phase_flagship(16, on_chip=False)


def test_chip_smoke_refuses_cpu(capsys):
    """No accelerator: non-zero exit at the device phase, no result
    line on stdout."""
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
