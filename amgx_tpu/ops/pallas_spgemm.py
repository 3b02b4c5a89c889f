"""Fused Galerkin RAP value kernel (the plan split's TPU numeric phase).

The structure phase (ops/spgemm.py `RapPlan`) fixes, once per sparsity
pattern, the (A·P) expansion gather indices, the lexsorted coalesce
order and the output CSR pattern. This module turns the VALUE phase —
today a chain of XLA gather/segment dispatches — into ONE pallas_call:

    cand1[e] = a[sa[e]] * p[sp[e]]            # segment-gather-multiply
    t[k]     = sum_{j<len1[k]} cand1[start1[k]+j]   # sorted-segment sum
    cand2[f] = r[sr[f]] * t[st[f]]
    out[u]   = sum_{j<len2[f]} cand2[start2[u]+j]

All indices are precomputed and window-rebased at plan time (host
numpy), so the kernel is pure VMEM-resident gathers over static index
slabs — no data-dependent addressing, no sort, no scatter. Because the
candidates are stored in lexsorted output order, the contributors of
any contiguous output range are a contiguous candidate range, and the
candidate sources of a contiguous row range are contiguous windows of
the operand value vectors: a chunk of output entries needs only
contiguous slices of a/p/r — the chained-block fallback splits the
output into such chunks when one VMEM-resident call does not fit
(mirroring ops/smooth.py's chained fused sub-calls). A plan that still
does not fit (or exceeds the contributor caps) declines, and the
caller runs the XLA slab program instead — never a wrong answer.

The call is `custom_vmap`-wrapped like `dia_smooth`: vector-only
batches (a batched coefficient stream over one pattern) route to the
multi-RHS slab form in ops/batched.py (`rap_values_multi`), which is
also the f64 parity reference of the kernel tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_spmv as _ps

LANES = _ps.LANES
_RAP_VMEM_BUDGET = _ps._SMOOTH_VMEM_BUDGET
RAP_MAX_CONTRIB = 64        # largest per-entry contributor run the
# kernel's masked j-loop unrolls; longer segments decline to the slab
# route (segment_sum handles any length)
RAP_MAX_CHUNKS = 32         # longest chained-call fallback
_RAP_MIN_CHUNK = 512        # smallest output chunk before declining


def _rows(n: int) -> int:
    """Padded 128-lane row count (f32 tile: multiples of 8 rows)."""
    r = max(1, -(-max(int(n), 1) // LANES))
    return -(-r // 8) * 8


def _pad2(a: np.ndarray, rows: int) -> jnp.ndarray:
    out = np.zeros((rows * LANES,), a.dtype)
    out[: a.shape[0]] = a
    return jnp.asarray(out.reshape(rows, LANES))


class _ChunkSpec:
    """Static window geometry of one chained kernel call."""

    __slots__ = ("a_lo", "a_n", "p_lo", "p_n", "r_lo", "r_n", "m1",
                 "m2", "r_c1", "r_t", "r_c2", "r_u", "n_u", "has1",
                 "has_r")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw[k])

    def key(self):
        return tuple(getattr(self, k) for k in self.__slots__)


def _measure_chunk(plan, lo: int, hi: int):
    """(spec, operand arrays, bytes) for output entries [lo, hi)."""
    starts2 = plan.starts2
    e2lo, e2hi = int(starts2[lo]), int(starts2[hi])
    st = plan.st[e2lo:e2hi].astype(np.int64)
    len2 = (starts2[lo + 1: hi + 1] - starts2[lo: hi]).astype(np.int64)
    m2 = int(len2.max()) if len2.size else 1
    has_r = plan.sr is not None
    has1 = plan.stage1 is not None
    arrs = {}
    if has_r:
        sr = plan.sr[e2lo:e2hi].astype(np.int64)
        r_lo, r_hi = int(sr.min()), int(sr.max()) + 1
        arrs["sr"] = sr - r_lo
    else:
        r_lo, r_hi = 0, 0
    if has1:
        s1 = plan.stage1
        tlo, thi = int(st.min()), int(st.max()) + 1
        e1lo, e1hi = int(s1["starts1"][tlo]), int(s1["starts1"][thi])
        sa = s1["sa"][e1lo:e1hi].astype(np.int64)
        sp = s1["sp"][e1lo:e1hi].astype(np.int64)
        a_lo, a_hi = int(sa.min()), int(sa.max()) + 1
        p_lo, p_hi = int(sp.min()), int(sp.max()) + 1
        len1 = (s1["starts1"][tlo + 1: thi + 1]
                - s1["starts1"][tlo: thi]).astype(np.int64)
        m1 = int(len1.max()) if len1.size else 1
        arrs["sa"] = sa - a_lo
        arrs["sp"] = sp - p_lo
        arrs["s1"] = (s1["starts1"][tlo:thi] - e1lo).astype(np.int64)
        arrs["l1"] = len1
        arrs["st"] = st - tlo
        n_t = thi - tlo
        n_c1 = e1hi - e1lo
    else:
        # relabel form: st indexes the (folded) A value vector directly
        a_lo, a_hi = int(st.min()), int(st.max()) + 1
        p_lo, p_hi = 0, 0
        arrs["st"] = st - a_lo
        m1, n_t, n_c1 = 1, 0, 0
    arrs["s2"] = (starts2[lo:hi] - e2lo).astype(np.int64)
    arrs["l2"] = len2
    n_c2 = e2hi - e2lo
    spec = _ChunkSpec(
        a_lo=a_lo, a_n=a_hi - a_lo, p_lo=p_lo, p_n=p_hi - p_lo,
        r_lo=r_lo, r_n=r_hi - r_lo, m1=m1, m2=m2,
        r_c1=_rows(n_c1) if has1 else 0, r_t=_rows(n_t) if has1 else 0,
        r_c2=_rows(n_c2), r_u=_rows(hi - lo), n_u=hi - lo,
        has1=has1, has_r=has_r)
    # VMEM estimate: f32 value windows + int32 index slabs + the
    # kernel's flat intermediates (cand1/t/cand2/out), x2 headroom for
    # the take temporaries the compiler materializes
    words = (_rows(spec.a_n) + _rows(spec.p_n) + _rows(spec.r_n)
             + 2 * spec.r_c1 + 2 * spec.r_t + 2 * spec.r_c2
             + 2 * spec.r_u) * LANES
    words += (spec.r_c1 + spec.r_t + spec.r_c2 + spec.r_u) * LANES
    return spec, arrs, 2 * 4 * words


def _plan_chunks(plan, lo: int, hi: int, depth: int = 0):
    spec, arrs, nbytes = _measure_chunk(plan, lo, hi)
    if spec.m1 > RAP_MAX_CONTRIB or spec.m2 > RAP_MAX_CONTRIB:
        return None
    if nbytes <= _RAP_VMEM_BUDGET:
        return [(spec, arrs)]
    if hi - lo <= _RAP_MIN_CHUNK or depth > 12:
        return None
    mid = (lo + hi) // 2
    left = _plan_chunks(plan, lo, mid, depth + 1)
    if left is None:
        return None
    right = _plan_chunks(plan, mid, hi, depth + 1)
    if right is None:
        return None
    out = left + right
    return out if len(out) <= RAP_MAX_CHUNKS else None


def build_rap_kernel(plan):
    """Kernel route of a RapPlan: (static spec tuple, per-chunk device
    operand dicts) or None (decline -> slab route). Memoized on the
    plan (`plan._kernel`); the index windows upload once per plan."""
    if plan._kernel is not None:
        return plan._kernel or None
    # cheap upfront bound BEFORE any slicing: _measure_chunk copies
    # window-rebased int64 twins of the index slabs, so a GB-scale
    # plan that could only ever decline (its total operand footprint
    # exceeds every chunk's budget times the chunk cap) must not pay
    # O(plan_bytes x bisection_depth) transient allocations first
    e1 = 0 if plan.stage1 is None else plan.stage1["sa"].shape[0]
    n_t = 0 if plan.stage1 is None else plan.stage1["nT"]
    est = 2 * 4 * (3 * e1 + 2 * n_t + 3 * plan.st.shape[0]
                   + 2 * plan.nU)
    if est > _RAP_VMEM_BUDGET * RAP_MAX_CHUNKS:
        plan._kernel = False
        return None
    chunks = None
    if plan.nU > 0:
        chunks = _plan_chunks(plan, 0, plan.nU)
    if not chunks:
        plan._kernel = False
        return None
    specs = []
    arrs = []
    for spec, a in chunks:
        specs.append(spec.key())
        up = {}
        for k, v in a.items():
            rows = {"sa": spec.r_c1, "sp": spec.r_c1,
                    "s1": spec.r_t, "l1": spec.r_t,
                    "st": spec.r_c2, "sr": spec.r_c2,
                    "s2": spec.r_u, "l2": spec.r_u}[k]
            up[k] = _pad2(v.astype(np.int32), rows)
        arrs.append(up)
    plan._kernel = (tuple(specs), tuple(arrs))
    return plan._kernel


def rap_kernel_ready(plan, dtype) -> bool:
    """Trace-time gate for the fused value-kernel route."""
    if not _ps.flat_gather_ok():    # 1-D jnp.take: refused by Mosaic
        return False
    if jnp.dtype(dtype) != jnp.float32:
        return False
    return build_rap_kernel(plan) is not None


def _rap_kernel(spec_key):
    """Kernel body factory for one chunk's static geometry."""
    spec = _ChunkSpec(**dict(zip(_ChunkSpec.__slots__, spec_key)))

    def kernel(*refs):
        it = iter(refs)
        a_ref = next(it)
        p_ref = next(it) if spec.has1 else None
        r_ref = next(it) if spec.has_r else None
        if spec.has1:
            sa_ref, sp_ref, s1_ref, l1_ref = (next(it), next(it),
                                              next(it), next(it))
        st_ref = next(it)
        sr_ref = next(it) if spec.has_r else None
        s2_ref, l2_ref = next(it), next(it)
        out_ref = next(it)

        aw = a_ref[...].reshape(-1)
        if spec.has1:
            pw = p_ref[...].reshape(-1)
            cand1 = jnp.take(aw, sa_ref[...].reshape(-1)) \
                * jnp.take(pw, sp_ref[...].reshape(-1))
            s1 = s1_ref[...].reshape(-1)
            l1 = l1_ref[...].reshape(-1)
            base = jnp.zeros((spec.r_t * LANES,), jnp.float32)
            for j in range(spec.m1):
                base = base + jnp.where(
                    j < l1, jnp.take(cand1, s1 + j), 0.0)
        else:
            base = aw
        cand2 = jnp.take(base, st_ref[...].reshape(-1))
        if spec.has_r:
            rw = r_ref[...].reshape(-1)
            cand2 = cand2 * jnp.take(rw, sr_ref[...].reshape(-1))
        s2 = s2_ref[...].reshape(-1)
        l2 = l2_ref[...].reshape(-1)
        out = jnp.zeros((spec.r_u * LANES,), jnp.float32)
        for j in range(spec.m2):
            out = out + jnp.where(j < l2, jnp.take(cand2, s2 + j), 0.0)
        out_ref[...] = out.reshape(spec.r_u, LANES)

    return kernel


def _value_window(vec, lo: int, n: int):
    """Zero-padded (rows, 128) window of a flat value vector (static
    slice bounds — plan-time constants)."""
    rows = _rows(n)
    w = jax.lax.slice_in_dim(vec, lo, lo + n, 1, 0)
    buf = jnp.zeros((rows * LANES,), vec.dtype)
    buf = jax.lax.dynamic_update_slice(buf, w, (0,))
    return buf.reshape(rows, LANES)


@functools.partial(jax.jit, static_argnames=("specs", "interpret"))
def _rap_kernel_program(specs, arrs, af, r_vals, p_vals,
                        interpret=False):
    """The whole planned value phase: one pallas_call per chunk (ONE
    for every plan that fits the budget), chained over static output
    ranges. Outer prims are only the window slices/pads and the final
    concat — zero sort/gather/segment-sum outside the kernel."""
    pieces = []
    for key, a in zip(specs, arrs):
        spec = _ChunkSpec(**dict(zip(_ChunkSpec.__slots__, key)))
        operands = [_value_window(af, spec.a_lo, spec.a_n)]
        if spec.has1:
            operands.append(_value_window(p_vals, spec.p_lo, spec.p_n))
        if spec.has_r:
            operands.append(_value_window(r_vals, spec.r_lo, spec.r_n))
        if spec.has1:
            operands += [a["sa"], a["sp"], a["s1"], a["l1"]]
        operands.append(a["st"])
        if spec.has_r:
            operands.append(a["sr"])
        operands += [a["s2"], a["l2"]]
        out = _ps.kernel_call(
            _rap_kernel(key),
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)
                      for _ in operands],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((spec.r_u, LANES),
                                           jnp.float32),
            cost_estimate=pl.CostEstimate(
                flops=2 * (spec.r_c1 + spec.r_c2) * LANES,
                bytes_accessed=4 * (spec.a_n + spec.p_n + spec.r_n
                                    + (2 * spec.r_c1 + 2 * spec.r_t
                                       + 2 * spec.r_c2 + 2 * spec.r_u)
                                    * LANES),
                transcendentals=0),
            interpret=interpret,
        )(*operands)
        pieces.append(out.reshape(-1)[: spec.n_u])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


@functools.lru_cache(maxsize=None)
def _rap_call_fn(specs, has1: bool, has_r: bool, nT: int, nU: int,
                 interpret: bool):
    """custom_vmap-wrapped kernel call: vector-only batches (a batched
    coefficient stream over one pattern) take the multi-RHS slab form
    in ops/batched.py; batched plan operands fall back to vmapped slab
    singles."""
    tu = jax.tree_util

    @jax.custom_batching.custom_vmap
    def call(karrs, sarrs, af, r_vals, p_vals):
        return _rap_kernel_program(specs, karrs, af, r_vals, p_vals,
                                   interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, karrs, sarrs, af, r_vals, p_vals):
        from .batched import rap_values_multi
        plan_b = any(tu.tree_leaves(in_batched[0])) \
            or any(tu.tree_leaves(in_batched[1]))
        if not plan_b:
            AF = af if in_batched[2] else jnp.broadcast_to(
                af, (axis_size,) + af.shape)
            r_b = bool(r_vals is not None
                       and any(tu.tree_leaves(in_batched[3])))
            p_b = bool(p_vals is not None
                       and any(tu.tree_leaves(in_batched[4])))
            y = rap_values_multi(sarrs, AF, r_vals, p_vals, nT, nU,
                                 has1, has_r, r_batched=r_b,
                                 p_batched=p_b)
            return y, True
        axes = tuple(tu.tree_map(lambda bb: 0 if bb else None, ib)
                     for ib in in_batched)
        y = jax.vmap(lambda k_, s_, a_, r_, p_: call(k_, s_, a_, r_,
                                                     p_),
                     in_axes=axes, axis_size=axis_size)(
            karrs, sarrs, af, r_vals, p_vals)
        return y, True

    return call


def rap_value_call(plan, af, r_vals, p_vals):
    """Planned value phase through the fused kernel route. Caller must
    have checked `rap_kernel_ready`."""
    specs, karrs = plan._kernel
    sarrs = plan.dev()
    s1 = plan.stage1
    return _rap_call_fn(
        specs, s1 is not None, plan.sr is not None,
        0 if s1 is None else s1["nT"], plan.nU,
        _ps._FORCE_INTERPRET)(
        karrs, sarrs, af,
        None if r_vals is None else jnp.asarray(r_vals),
        None if p_vals is None else jnp.asarray(p_vals))
