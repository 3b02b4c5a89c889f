"""Sharded (per-rank) distributed AMG setup.

This is the TPU-native analog of the reference's distributed hierarchy
build, where every rank constructs its partition of every AMG level and
no rank ever materializes a global coarse operator
(src/aggregation/aggregation_amg_level.cu ghost-aggregate handling,
src/classical/classical_amg_level.cu:297-315 distributed Galerkin RAP,
src/distributed/distributed_manager.cu `createOneRingHaloRows` /
`renumberMatrixOneRing`). The single-controller `shard_amg` path
(distributed/amg.py) builds the hierarchy globally then shards it; this
module replaces that global phase: the whole level build — edge weights,
handshaking matching, aggregate numbering, Galerkin RAP, coarse halo-map
construction — runs as shard_mapped SPMD programs over the mesh, with
per-shard peak memory O(n/p).

Key design decisions (vs the reference's MPI machinery):

- **Two id spaces.** Decisions (matching tie-break hash, orderings,
  dedup keys) use *semantic* contiguous global ids — identical to the
  ids the single-device setup uses, so the sharded selector makes
  bit-identical aggregation decisions and the hierarchy matches the
  global-setup hierarchy exactly (the reference instead renumbers
  owned-interior/boundary/halo per rank and accepts layout-dependent
  hierarchies). Storage and exchange use *physical* block-aligned ids
  (`rank * NCL + slot`, NCL = max per-shard coarse count), which keep
  the equal-block ShardMatrix machinery (rank = id // NCL) working
  unchanged; `offsets` arrays convert between the two.
- **Routing is all_to_all.** Cross-rank aggregates make RAP
  contributions land on remote coarse rows; the reference exchanges
  halo rows (B2L rings). Here every cross contribution is a (CI, CJ, v)
  triple routed to CI's owner with one `lax.all_to_all` of per-peer
  padded buffers — hop-count-free (an aggregate rooted two ranks away
  is routed identically to a neighbor's).
- **Static shapes via per-level count syncs.** Each level build is
  three jitted phases; between phases the host reads a small packed
  count vector (one device round trip) and re-invokes with exact
  padded sizes. Value buffers keep first-occurrence-summed duplicates
  (zero-valued, inert — the single-device Galerkin uses the same
  trick) until the final compaction.
- **Consolidation boundary.** Once the global coarse size fits a single
  shard's budget, the level is gathered, compacted to the semantic
  (single-device) numbering, and the *existing* global setup builds the
  remaining levels replicated — the `glue_matrices` endpoint
  (include/distributed/glue.h:200) that distributed/amg.py already
  implements for the solve phase.

Scope (v1): aggregation AMG with the matching selectors
(SIZE_2/4/8, PARALLEL_GREEDY, MULTI_PAIRWISE) and row-partitionable
smoothers (JACOBI, BLOCK_JACOBI on scalar systems, JACOBI_L1,
NOSOLVER). Cross-rank edge weights assume a value-symmetric matrix
(|a_ji| = |a_ij|; exact for the SPD systems aggregation targets —
documented deviation: the single-device path handles pattern-symmetric
non-value-symmetric matrices via its positional-transpose alignment).
Everything else falls back to the global-setup + shard_amg path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..errors import BadParametersError
from .dist_matrix import ShardMatrix

_SENT = jnp.int32(2**31 - 1)          # sentinel global id (sorts last)


# ---------------------------------------------------------------------------
# generic SPMD primitives (per-shard bodies; collectives over `axis`)
# ---------------------------------------------------------------------------

def _bucket_by_owner(owner, R: int, maxq: int, valid):
    """Stable-sort positions by owner rank; per-peer contiguous segments.

    Returns (ord_, idx, in_seg, cnt): `ord_[start[p] + k]` is the source
    position of the k-th item for peer p; `idx[p, k]` indexes into the
    sorted order; `in_seg[p, k]` masks real items."""
    Q = owner.shape[0]
    key = jnp.where(valid, owner, R)            # invalid sorts last
    ord_ = jnp.argsort(key, stable=True)
    sorted_owner = key[ord_]
    start = jnp.searchsorted(sorted_owner, jnp.arange(R + 1))
    cnt = start[1:] - start[:-1]
    k = jnp.arange(maxq)
    idx = jnp.clip(start[:-1, None] + k[None, :], 0, Q - 1)
    in_seg = k[None, :] < cnt[:, None]
    return ord_, idx, in_seg, cnt


def _remote_lookup(table, queries, owner, offsets, me, n_owner_local,
                   axis, R: int, maxq: int, fill):
    """values = table[queries] where each query's answer lives on
    `owner`'s shard (request/response over two all_to_alls). `queries`
    are semantic ids; the owner indexes its table at
    `query - offsets[owner]`."""
    Q = queries.shape[0]
    valid = owner < R
    ord_, idx, in_seg, _ = _bucket_by_owner(owner, R, maxq, valid)
    sortedq = queries[ord_]
    req = jnp.where(in_seg, sortedq[idx], _SENT)
    got = jax.lax.all_to_all(req, axis, split_axis=0, concat_axis=0,
                             tiled=True)
    ok = got != _SENT
    loc = jnp.clip(got - offsets[me], 0, table.shape[0] - 1)
    ans = jnp.where(ok, table[loc], fill)
    back = jax.lax.all_to_all(ans, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    out = jnp.full((Q,), fill, back.dtype)
    scatter_pos = jnp.where(in_seg, ord_[idx], Q)
    return out.at[scatter_pos.reshape(-1)].set(
        back.reshape(-1), mode="drop")


def _route(payloads, dest, me, axis, R: int, maxq: int, fills):
    """Route per-item payload tuples to `dest` ranks; returns the
    received (R * maxq,)-flat payloads (fill-padded). The receiving
    order is (source rank, sender's bucketed order) — deterministic."""
    valid = dest < R
    ord_, idx, in_seg, _ = _bucket_by_owner(dest, R, maxq, valid)
    outs = []
    for arr, fill in zip(payloads, fills):
        buf = jnp.where(in_seg, arr[ord_[idx]], fill)
        got = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        outs.append(got.reshape(-1))
    return outs


def _a2a_maps(halo_phys, n_halo, me, NCL: int, n_local_cols: int,
              axis, R: int, maxpair: int):
    """Build all_to_all send/recv maps from a sorted physical halo list
    (device-side DistributedArranger analog, distributed_arranger.h:
    28-117: neighbor detection from global ids + B2L map construction).

    halo_phys: (H,) sorted physical global col ids, _SENT-padded past
    n_halo. Returns (a2a_send (R, maxpair) local col slots,
    a2a_recv (R, maxpair) halo slots) compatible with
    ShardMatrix.exchange_halo's "a2a" mode."""
    H = halo_phys.shape[0]
    valid = jnp.arange(H) < n_halo
    src = jnp.where(valid, halo_phys // NCL, R)
    # per-peer contiguous segments (halo list sorted by physical id)
    start = jnp.searchsorted(src, jnp.arange(R + 1))
    cnt = start[1:] - start[:-1]
    k = jnp.arange(maxpair)
    idx = jnp.clip(start[:-1, None] + k[None, :], 0, H - 1)
    in_seg = k[None, :] < cnt[:, None]
    req = jnp.where(in_seg, halo_phys[idx], _SENT)
    got = jax.lax.all_to_all(req, axis, split_axis=0, concat_axis=0,
                             tiled=True)
    a2a_send = jnp.where(got != _SENT, got - me * NCL,
                         n_local_cols).astype(jnp.int32)
    a2a_recv = jnp.where(in_seg, start[:-1, None] + k[None, :],
                         H).astype(jnp.int32)
    return a2a_send, a2a_recv


# ---------------------------------------------------------------------------
# per-shard edge view of a ShardMatrix level
# ---------------------------------------------------------------------------

class _Edges:
    """Local edge list of one shard: rows (local ids, sentinel n_local),
    semantic global col ids, values, and col-state accessors that read
    either the local state vector or the exchanged halo buffer."""

    def __init__(self, M: ShardMatrix, offsets, me):
        self.M = M
        self.n_local = M.n_local
        self.e_own = M.rid_own.shape[0]
        self.rows = jnp.concatenate([M.rid_own, M.rid_halo])
        self.is_halo = jnp.concatenate([
            jnp.zeros(M.rid_own.shape, bool),
            jnp.ones(M.rid_halo.shape, bool)])
        self.ci = jnp.concatenate([M.ci_own, M.ci_halo])
        self.vals = jnp.concatenate([M.va_own, M.va_halo])
        # sentinel entries: padded slots carry rid == n_local
        self.valid = self.rows < M.n_local
        halo_phys = jnp.where(
            jnp.arange(M.halo_src.shape[0]) < M.n_halo,
            M.halo_src.astype(jnp.int32), _SENT)
        self._halo_phys = halo_phys
        hp = jnp.concatenate([halo_phys, jnp.full((1,), _SENT)])
        cp_own = me * M.n_local_cols + jnp.clip(
            M.ci_own.astype(jnp.int32), 0, M.n_local_cols - 1)
        cp_halo = hp[jnp.clip(M.ci_halo, 0, hp.shape[0] - 1)]
        col_phys = jnp.concatenate([cp_own, cp_halo])
        self.col_phys = jnp.where(self.valid, col_phys, _SENT)
        self.col_sem = _sem_of(self.col_phys, offsets, M.n_local_cols)
        self.row_sem = jnp.where(
            self.valid, offsets[me] + self.rows, _SENT).astype(jnp.int32)

    def exchange(self, vec):
        """Halo-exchange a per-vertex state vector (square level)."""
        return self.M.exchange_halo(vec)

    def col_state(self, local_vec, halo_vec, fill):
        """Per-edge state of the column vertex (local or exchanged)."""
        lv = jnp.concatenate([local_vec,
                              jnp.full((1,), fill, local_vec.dtype)])
        hv = jnp.concatenate([halo_vec,
                              jnp.full((1,), fill, halo_vec.dtype)])
        own = lv[jnp.clip(self.ci[: self.e_own], 0, lv.shape[0] - 1)]
        hal = hv[jnp.clip(self.ci[self.e_own:], 0, hv.shape[0] - 1)]
        out = jnp.concatenate([own, hal])
        return jnp.where(self.valid, out, fill)


def _owner_of_sem(sem, offsets, R: int, valid):
    """Owner rank of a semantic id: the shard whose [offsets[r],
    offsets[r+1]) range contains it (coarse levels are unevenly
    partitioned in semantic space)."""
    own = jnp.searchsorted(offsets, sem, side="right") - 1
    return jnp.where(valid, jnp.clip(own, 0, R - 1), R).astype(jnp.int32)


def _sem_of(phys, offsets, NCL: int):
    """Physical block-aligned id -> semantic contiguous id."""
    rank = jnp.clip(phys // NCL, 0, offsets.shape[0] - 2)
    return jnp.where(phys == _SENT, _SENT,
                     offsets[rank] + (phys - rank * NCL)).astype(jnp.int32)


def _edge_hash_sem(a_sem, b_sem):
    """The selector's symmetric tie-break hash on semantic ids (the
    single implementation — sharded matching must perturb identically
    to the single-device pass for bit-identical decisions)."""
    from ..amg.aggregation.selectors import _edge_hash
    return _edge_hash(a_sem, b_sem)


# ---------------------------------------------------------------------------
# phase A: sharded handshaking matching (+ singleton merge + root counts)
# ---------------------------------------------------------------------------

def _sharded_weights(E: _Edges, diag, halo_diag, formula: int):
    """selectors._edge_weights under the value-symmetry assumption:
    w_ij = |a_ij| / max(|a_ii|, |a_jj|) (formula 0) computed per local
    edge; |a_ji| = |a_ij| so the 0.5(|a_ij|+|a_ji|) average collapses."""
    v = jnp.abs(E.vals)
    dl = jnp.concatenate([diag, jnp.ones((1,), diag.dtype)])
    d_r = dl[jnp.minimum(E.rows, E.n_local)]
    d_c = E.col_state(diag, halo_diag, 0.0)
    if formula == 1:
        # Notay coupling -0.5 (a_ij/a_ii + a_ji/a_jj)
        # (common_selector.h:113-119); a_ji = a_ij under the documented
        # value-symmetry assumption
        w = -0.5 * (E.vals / jnp.where(d_r == 0, 1.0, d_r)
                    + E.vals / jnp.where(d_c == 0, 1.0, d_c))
    else:
        denom = jnp.maximum(jnp.abs(d_r), jnp.abs(d_c))
        w = v / jnp.where(denom == 0, 1.0, denom)
    w = jnp.where(E.row_sem == E.col_sem, 0.0, w)
    return jnp.where(E.valid, w, 0.0)


def _seg_max(vals, rows, n, fill):
    return jax.ops.segment_max(
        jnp.concatenate([vals, jnp.full((1,), fill, vals.dtype)]),
        jnp.concatenate([rows, jnp.full((1,), n - 1, rows.dtype)]),
        num_segments=n)


def _seg_min(vals, rows, n, fill):
    return jax.ops.segment_min(
        jnp.concatenate([vals, jnp.full((1,), fill, vals.dtype)]),
        jnp.concatenate([rows, jnp.full((1,), n - 1, rows.dtype)]),
        num_segments=n)


def _sharded_matching(E: _Edges, w, active, me, offsets, axis,
                      max_iters: int):
    """selectors._matching_pass distributed: the same synchronized
    fixed point, with the column-vertex state (unaggregated flag, best
    proposal) halo-exchanged each sweep. Decisions are bit-identical to
    the single-device pass (same weights, same semantic-id tie-breaks,
    same smallest-index selection)."""
    exchange = E.exchange
    n = E.n_local
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    w = w * (1.0 + 1e-3 * _edge_hash_sem(E.row_sem, E.col_sem).astype(
        w.dtype))

    def cond(state):
        it, agg, paired = state
        un_any = jnp.any((agg < 0) & active)
        return (it < max_iters) & (
            jax.lax.psum(un_any.astype(jnp.int32), axis) > 0)

    def body(state):
        it, agg, paired = state
        un = (agg < 0) & active
        un_h = exchange(un.astype(jnp.int8)) > 0
        un_r = jnp.concatenate(
            [un, jnp.zeros((1,), bool)])[jnp.minimum(E.rows, n)]
        un_c = E.col_state(un, un_h, False)
        valid = un_r & un_c & (w > 0)
        we = jnp.where(valid, w, -1.0)
        wmax = _seg_max(we, E.rows, n, -1.0)
        has = wmax > 0
        is_best = valid & (we == wmax[jnp.clip(E.rows, 0, n - 1)])
        best = _seg_min(jnp.where(is_best, E.col_sem, _SENT), E.rows, n,
                        _SENT)
        best = jnp.where(has, best, _SENT)
        # handshake: the column vertex's own best proposal, per edge
        best_h = exchange(best)
        ebob = E.col_state(best, best_h, _SENT)
        bl = jnp.concatenate([best, jnp.full((1,), _SENT)])
        row_best = bl[jnp.minimum(E.rows, n)]
        hand = (E.col_sem == row_best) & (ebob == jnp.where(
            E.valid, jnp.concatenate(
                [idx_sem, jnp.full((1,), _SENT, jnp.int32)])[
                jnp.minimum(E.rows, n)], _SENT))
        paired_now = _seg_max(hand.astype(jnp.int8), E.rows, n,
                              jnp.int8(0)) > 0
        paired_now = paired_now & (best < _SENT)
        leader = paired_now & (idx_sem < best)
        agg = jnp.where(leader, idx_sem, agg)
        agg = jnp.where(paired_now & ~leader, best, agg)
        return it + 1, agg, paired | paired_now

    _, agg, paired = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.full((n,), -1, jnp.int32),
         jnp.zeros((n,), bool)))
    agg = jnp.where((agg < 0) & active, idx_sem, agg)
    return agg, paired


def _sharded_merge_singletons(E: _Edges, w, agg, paired, active, me,
                              offsets):
    """selectors._merge_singletons distributed: a singleton (never
    paired) vertex joins its strongest non-singleton neighbor's
    aggregate."""
    exchange = E.exchange
    n = E.n_local
    singleton = active & ~paired
    s_h = exchange(singleton.astype(jnp.int8)) > 0
    agg_h = exchange(agg)
    sl = jnp.concatenate([singleton, jnp.zeros((1,), bool)])
    s_r = sl[jnp.minimum(E.rows, n)]
    s_c = E.col_state(singleton, s_h, True)
    valid = s_r & ~s_c & (w > 0) & E.valid
    we = jnp.where(valid, w, -1.0)
    wmax = _seg_max(we, E.rows, n, -1.0)
    has = wmax > 0
    is_best = valid & (we == wmax[jnp.clip(E.rows, 0, n - 1)])
    best = _seg_min(jnp.where(is_best, E.col_sem, _SENT), E.rows, n,
                    _SENT)
    bl = jnp.concatenate([best, jnp.full((1,), _SENT)])
    row_best = bl[jnp.minimum(E.rows, n)]
    agg_c = E.col_state(agg, agg_h, _SENT)
    tgt = _seg_min(jnp.where(is_best & (E.col_sem == row_best), agg_c,
                             _SENT), E.rows, n, _SENT)
    return jnp.where(singleton & has & (tgt < _SENT), tgt, agg)

# ---------------------------------------------------------------------------
# phase B: coarse numbering, cid lookup, routed Galerkin triples
# ---------------------------------------------------------------------------

def _coarse_numbering(agg, active, offsets, me, n_local: int, axis):
    """Global coarse numbering identical to the single-device
    selectors._renumber: aggregates ordered by root semantic id. Returns
    (is_root, slot, nc_local, offsets_c) — offsets_c identical on every
    shard (all_gather of counts)."""
    idx_sem = offsets[me] + jnp.arange(n_local, dtype=jnp.int32)
    is_root = active & (agg == idx_sem)
    nc_local = jnp.sum(is_root.astype(jnp.int32))
    counts = jax.lax.all_gather(nc_local, axis)          # (R,)
    offsets_c = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]).astype(jnp.int32)
    slot = (jnp.cumsum(is_root.astype(jnp.int32)) - 1).astype(jnp.int32)
    return is_root, slot, nc_local, offsets_c


def _assign_cids(agg, active, is_root, slot, offsets, offsets_c, me,
                 n_local: int, NCL_c: int, axis, R: int, maxq: int):
    """Per-vertex coarse ids (semantic + physical). Remote roots are
    resolved with one request/response lookup on the root's owner —
    the renumbering exchange of distributed_manager.cu
    `renumberMatrixOneRing`, minus the renumbering (two id spaces
    instead)."""
    cid_table = jnp.where(is_root, offsets_c[me] + slot, -1)
    owner = _owner_of_sem(agg, offsets, R, active & (agg >= 0))
    local_ans = cid_table[jnp.clip(agg - offsets[me], 0, n_local - 1)]
    remote_owner = jnp.where(owner == me, R, owner)      # self answered
    looked = _remote_lookup(cid_table, agg, remote_owner, offsets, me,
                            n_local, axis, R, maxq, jnp.int32(-1))
    cid_sem = jnp.where(owner == me, local_ans, looked)
    cid_sem = jnp.where(active, cid_sem, -1)
    rank_r = jnp.clip(owner, 0, R - 1)
    cid_phys = jnp.where(
        active & (cid_sem >= 0),
        rank_r * NCL_c + (cid_sem - offsets_c[rank_r]), -1)
    return cid_sem.astype(jnp.int32), cid_phys.astype(jnp.int32)


def _rap_triples(E: _Edges, cid_sem, cid_phys, owner_of_root, me,
                 offsets_c, NCL_c: int, axis, R: int, maxt: int,
                 values=None):
    """Distributed Galerkin triples: every local entry (i, j, v) becomes
    (CI, CJ, v); contributions to remote coarse rows are all_to_all'd
    to the owner (classical_amg_level.cu:297-315's halo-row RAP
    exchange, hop-count-free). Returns the shard's coarse entries
    sorted by (local slot, physical CJ) with duplicate values summed
    onto first occurrences (zeros elsewhere, inert — the single-device
    Galerkin keeps the same representation)."""
    from ..matrix import lexsort_rc  # local import: avoid cycle at init
    n = E.n_local
    halo_cs = E.exchange(cid_sem)
    halo_cp = E.exchange(cid_phys)
    cs_l = jnp.concatenate([cid_sem, jnp.full((1,), -1, jnp.int32)])
    CI = cs_l[jnp.minimum(E.rows, n)]
    CJ_phys = E.col_state(cid_phys, halo_cp, jnp.int32(-1))
    vals = E.vals if values is None else values
    ok = E.valid & (CI >= 0) & (CJ_phys >= 0)
    ol = jnp.concatenate([owner_of_root, jnp.full((1,), R, jnp.int32)])
    dest = jnp.where(ok, ol[jnp.minimum(E.rows, n)], R)
    # remote contributions: routed; local ones kept in place
    rCI, rCJ, rv = _route(
        (CI, CJ_phys, vals), jnp.where(dest == me, R, dest), me, axis,
        R, maxt, (_SENT, _SENT, jnp.zeros((), vals.dtype)))
    keep = ok & (dest == me)
    aCI = jnp.concatenate([jnp.where(keep, CI, _SENT), rCI])
    aCJ = jnp.concatenate([jnp.where(keep, CJ_phys, _SENT), rCJ])
    av = jnp.concatenate([jnp.where(keep, vals, 0.0), rv])
    slot = jnp.where(aCI != _SENT, aCI - offsets_c[me],
                     NCL_c).astype(jnp.int32)
    cj = jnp.where(aCJ != _SENT, aCJ, _SENT).astype(jnp.int32)
    order = lexsort_rc(slot, cj)
    slot_s, cj_s, v_s = slot[order], cj[order], av[order]
    valid_s = slot_s < NCL_c
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (slot_s[1:] != slot_s[:-1]) | (cj_s[1:] != cj_s[:-1])]) & valid_s
    seg = jnp.cumsum(first) - 1
    Etot = slot_s.shape[0]
    vsum = jax.ops.segment_sum(jnp.where(valid_s, v_s, 0.0), seg,
                               num_segments=Etot, indices_are_sorted=True)
    v_out = jnp.where(first, vsum[jnp.clip(seg, 0, Etot - 1)], 0.0)
    n_unique = jnp.sum(first.astype(jnp.int32))
    return slot_s, cj_s, v_out, first, n_unique


def _remote_uniq_flags(vals_phys, mask, me, NCL: int):
    """Shared core of the halo-list builders: sorted remote ids with
    first-occurrence flags."""
    remote = mask & (vals_phys // NCL != me) & (vals_phys != _SENT) & \
        (vals_phys >= 0)
    k = jnp.sort(jnp.where(remote, vals_phys, _SENT))
    uniq = jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]]) & \
        (k != _SENT)
    return k, uniq


def _unique_remote(vals_phys, mask, me, NCL: int, size: int):
    """Sorted unique physical ids with owner != me (halo-list builder).
    Returns (_SENT-padded (size,) list, count)."""
    k, uniq = _remote_uniq_flags(vals_phys, mask, me, NCL)
    cnt = jnp.sum(uniq.astype(jnp.int32))
    idx = jnp.nonzero(uniq, size=size, fill_value=k.shape[0] - 1)[0]
    lst = jnp.where(jnp.arange(size) < cnt, k[idx], _SENT)
    return lst, cnt


def _per_peer_counts(list_phys, cnt, NCL: int, R: int):
    """Per-peer segment sizes of a sorted physical halo list."""
    valid = jnp.arange(list_phys.shape[0]) < cnt
    src = jnp.where(valid, list_phys // NCL, R)
    start = jnp.searchsorted(src, jnp.arange(R + 1))
    return start[1:] - start[:-1]


def _sorted_by_rid(rid, *arrs, n_sent: int):
    """Stable-sort entry arrays by row id (ShardMatrix.spmv declares
    indices_are_sorted)."""
    order = jnp.argsort(jnp.where(rid < n_sent, rid, n_sent),
                        stable=True)
    return (rid[order],) + tuple(a[order] for a in arrs)


def _take(mask, size: int, fill_idx: int):
    """Compact positions where mask holds into a (size,) index buffer."""
    cnt = jnp.sum(mask.astype(jnp.int32))
    idx = jnp.nonzero(mask, size=size, fill_value=fill_idx)[0]
    sel = jnp.arange(size) < cnt
    return idx, sel, cnt


# ---------------------------------------------------------------------------
# the three per-level phases (shard_map bodies)
# ---------------------------------------------------------------------------

def _phase_a_body(M: ShardMatrix, offsets, axis: str, max_iters: int,
                  formula: int, merge: bool, graph_values: bool):
    """Matching + root counts. Returns (agg, paired, countsA) where
    countsA = [nc_local, triples_to_peer*R, members_to_peer*R]."""
    me = jax.lax.axis_index(axis)
    R = offsets.shape[0] - 1
    n = M.n_local
    E = _Edges(M, offsets, me)
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    active = idx_sem < offsets[me + 1]
    if graph_values:
        # coarse matching pass: entry values ARE the summed edge
        # weights (selectors._coarse_graph semantics)
        w = jnp.where(E.valid & (E.row_sem != E.col_sem), E.vals, 0.0)
    else:
        halo_diag = E.exchange(M.diag)
        w = _sharded_weights(E, M.diag, halo_diag, formula)
    agg, paired = _sharded_matching(E, w, active, me, offsets, axis,
                                    max_iters)
    if merge:
        agg = _sharded_merge_singletons(E, w, agg, paired, active, me,
                                        offsets)
    is_root = active & (agg == idx_sem)
    nc_local = jnp.sum(is_root.astype(jnp.int32))
    # routing budgets: triples by dest (owner of the row's root), member
    # records by owner of each vertex's root
    owner_root = _owner_of_sem(agg, offsets, R, active & (agg >= 0))
    ol = jnp.concatenate([owner_root, jnp.full((1,), R, jnp.int32)])
    dest_e = ol[jnp.minimum(E.rows, n)]
    dest_e = jnp.where(E.valid, dest_e, R)
    tri_cnt = jnp.zeros((R,), jnp.int32).at[
        jnp.clip(dest_e, 0, R - 1)].add((dest_e < R).astype(jnp.int32))
    mem_remote = jnp.where(owner_root == me, R, owner_root)
    mem_cnt = jnp.zeros((R,), jnp.int32).at[
        jnp.clip(mem_remote, 0, R - 1)].add(
        (mem_remote < R).astype(jnp.int32))
    counts = jnp.concatenate([nc_local[None], tri_cnt, mem_cnt])
    return agg, paired, w, counts


def _phase_b_body(M: ShardMatrix, offsets, agg, w_vals, axis: str,
                  NCL_c: int, maxq: int, maxt: int, maxm: int,
                  graph_rap: bool):
    """Numbering + cid lookup + routed RAP triples + member routing.

    graph_rap=True builds the next matching pass's weight graph (values
    = summed w) instead of the coarse operator (and skips members)."""
    me = jax.lax.axis_index(axis)
    R = offsets.shape[0] - 1
    n = M.n_local
    E = _Edges(M, offsets, me)
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    active = idx_sem < offsets[me + 1]
    is_root, slot, nc_local, offsets_c = _coarse_numbering(
        agg, active, offsets, me, n, axis)
    cid_sem, cid_phys = _assign_cids(agg, active, is_root, slot,
                                     offsets, offsets_c, me, n, NCL_c,
                                     axis, R, maxq)
    owner_root = _owner_of_sem(agg, offsets, R, active & (agg >= 0))
    slot_s, cj_s, v_s, first, n_unique = _rap_triples(
        E, cid_sem, cid_phys, owner_root, me, offsets_c, NCL_c, axis, R,
        maxt, values=w_vals if graph_rap else None)
    # halo-list / map-size counts for phase C
    hlist_cnt = _count_unique_remote(cj_s, first, me, NCL_c)
    owner_cj = jnp.clip(cj_s // NCL_c, 0, R)
    n_own_u = jnp.sum((first & (owner_cj == me)).astype(jnp.int32))
    n_halo_u = jnp.sum((first & (owner_cj != me)).astype(jnp.int32))
    if graph_rap:
        mcid = jnp.full((R * maxm,), _SENT, jnp.int32)
        mgid = jnp.full((R * maxm,), _SENT, jnp.int32)
        n_p_halo = jnp.zeros((), jnp.int32)
        n_r_halo = jnp.zeros((), jnp.int32)
    else:
        # member records -> root owners (for the explicit R operator)
        gid_phys = me * n + jnp.arange(n, dtype=jnp.int32)
        dest_m = jnp.where(owner_root == me, R, owner_root)
        mcid, mgid = _route((cid_sem, gid_phys), dest_m, me, axis, R,
                            maxm, (_SENT, _SENT))
        n_p_halo = _count_unique_remote(cid_phys,
                                        active & (cid_phys >= 0), me,
                                        NCL_c)
        n_r_halo = _count_unique_remote(mgid, mcid != _SENT, me, n)
    counts = jnp.concatenate([
        nc_local[None], n_unique[None], n_own_u[None], n_halo_u[None],
        hlist_cnt[None], n_p_halo[None], n_r_halo[None]])
    return (slot_s, cj_s, v_s, cid_sem, cid_phys, mcid, mgid, counts)


def _count_unique_remote(vals_phys, mask, me, NCL: int):
    _, uniq = _remote_uniq_flags(vals_phys, mask, me, NCL)
    return jnp.sum(uniq.astype(jnp.int32))


def _phase_c_body(M: ShardMatrix, offsets, triples, cid_sem, cid_phys,
                  mcid, mgid, offsets_c, axis: str, NCL_c: int,
                  E_own: int, E_halo: int, H_c: int, mp_c: int,
                  H_p: int, mp_p: int, H_r: int, mp_r: int,
                  build_transfers: bool):
    """Assemble the coarse ShardMatrix (+ P and R transfer shards) from
    phase B's sorted triples, building the coarse halo maps on device.
    Everything row-placement derives from the per-vertex coarse ids
    (works for both the single-pass and the composed multipass path)."""
    me = jax.lax.axis_index(axis)
    R = offsets.shape[0] - 1
    n = M.n_local
    slot_s, cj_s, v_s = triples
    Etot = slot_s.shape[0]
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    active = idx_sem < offsets[me + 1]
    nc_local = offsets_c[me + 1] - offsets_c[me]
    valid_s = slot_s < NCL_c
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (slot_s[1:] != slot_s[:-1]) | (cj_s[1:] != cj_s[:-1])]) & valid_s
    owner_cj = jnp.clip(cj_s // NCL_c, 0, R)
    # owned-column entries
    oidx, osel, _ = _take(first & (owner_cj == me), E_own, Etot - 1)
    rid_own = jnp.where(osel, slot_s[oidx], NCL_c).astype(jnp.int32)
    ci_own = jnp.where(osel, cj_s[oidx] - me * NCL_c, 0).astype(jnp.int32)
    va_own = jnp.where(osel, v_s[oidx], 0.0)
    # halo-column entries + device-built halo list and maps
    hlist, hcnt = _unique_remote(cj_s, first, me, NCL_c, H_c)
    hidx, hsel, _ = _take(first & (owner_cj != me), E_halo, Etot - 1)
    rid_halo = jnp.where(hsel, slot_s[hidx], NCL_c).astype(jnp.int32)
    ci_halo = jnp.where(
        hsel, jnp.searchsorted(hlist, cj_s[hidx]), 0).astype(jnp.int32)
    va_halo = jnp.where(hsel, v_s[hidx], 0.0)
    send_c, recv_c = _a2a_maps(hlist, hcnt, me, NCL_c, NCL_c, axis, R,
                               mp_c)
    # coarse diagonal (pad slots -> 1.0)
    isd = first & (cj_s == me * NCL_c + slot_s)
    diag = jnp.zeros((NCL_c,), v_s.dtype).at[
        jnp.where(isd, slot_s, NCL_c)].add(
        jnp.where(isd, v_s, 0.0), mode="drop")
    diag = jnp.where(jnp.arange(NCL_c) < nc_local, diag, 1.0)
    A_c = dict(rid_own=rid_own, ci_own=ci_own, va_own=va_own,
               rid_halo=rid_halo, ci_halo=ci_halo, va_halo=va_halo,
               diag=diag, halo_src=hlist, a2a_send=send_c,
               a2a_recv=recv_c)
    if not build_transfers:
        return A_c, None, None
    dt = v_s.dtype
    # P: one entry per active fine row at column cid
    owner_p = jnp.clip(cid_phys // NCL_c, 0, R)
    own_p = active & (cid_phys >= 0) & (owner_p == me)
    halo_p = active & (cid_phys >= 0) & (owner_p != me)
    ar = jnp.arange(n, dtype=jnp.int32)
    plist, pcnt = _unique_remote(cid_phys, active & (cid_phys >= 0),
                                 me, NCL_c, H_p)
    p_own = dict(rid=jnp.where(own_p, ar, n).astype(jnp.int32),
                 ci=jnp.where(own_p, cid_phys - me * NCL_c, 0
                              ).astype(jnp.int32),
                 va=jnp.where(own_p, 1.0, 0.0).astype(dt))
    p_halo = dict(rid=jnp.where(halo_p, ar, n).astype(jnp.int32),
                  ci=jnp.where(halo_p,
                               jnp.searchsorted(plist, cid_phys), 0
                               ).astype(jnp.int32),
                  va=jnp.where(halo_p, 1.0, 0.0).astype(dt))
    send_p, recv_p = _a2a_maps(plist, pcnt, me, NCL_c, NCL_c, axis, R,
                               mp_p)
    P_sh = dict(rid_own=p_own["rid"], ci_own=p_own["ci"],
                va_own=p_own["va"], rid_halo=p_halo["rid"],
                ci_halo=p_halo["ci"], va_halo=p_halo["va"],
                diag=jnp.ones((n,), dt), halo_src=plist,
                a2a_send=send_p, a2a_recv=recv_p)
    # R: rows = my coarse slots; columns = fine member vertices
    owner_f = _owner_of_sem(cid_sem, offsets_c, R,
                            active & (cid_sem >= 0))
    local_m = active & (owner_f == me)
    r_rid_o = jnp.where(local_m, cid_sem - offsets_c[me], NCL_c
                        ).astype(jnp.int32)
    r_rid_o, r_ci_o, r_va_o = _sorted_by_rid(
        r_rid_o, ar, jnp.where(local_m, 1.0, 0.0).astype(dt),
        n_sent=NCL_c)
    mvalid = mcid != _SENT
    rlist, rcnt = _unique_remote(mgid, mvalid, me, n, H_r)
    r_rid_h = jnp.where(mvalid, mcid - offsets_c[me], NCL_c
                        ).astype(jnp.int32)
    r_ci_h = jnp.where(mvalid, jnp.searchsorted(rlist, mgid), 0
                       ).astype(jnp.int32)
    r_rid_h, r_ci_h, r_va_h = _sorted_by_rid(
        r_rid_h, r_ci_h, jnp.where(mvalid, 1.0, 0.0).astype(dt),
        n_sent=NCL_c)
    send_r, recv_r = _a2a_maps(rlist, rcnt, me, n, n, axis, R, mp_r)
    R_sh = dict(rid_own=r_rid_o, ci_own=r_ci_o, va_own=r_va_o,
                rid_halo=r_rid_h, ci_halo=r_ci_h, va_halo=r_va_h,
                diag=jnp.ones((NCL_c,), dt), halo_src=rlist,
                a2a_send=send_r, a2a_recv=recv_r)
    return A_c, P_sh, R_sh


# ---------------------------------------------------------------------------
# level objects + host orchestration
# ---------------------------------------------------------------------------

class DistAMGLevel:
    """A sharded hierarchy level: transfers apply through the explicit
    P/R ShardMatrix shards in the solve-data (the same duck-typed spmv
    dispatch the solve-phase sharding uses)."""

    def __init__(self, A_sh: ShardMatrix, level_index: int,
                 offsets: Optional[np.ndarray] = None):
        self.A = A_sh
        self.level_index = level_index
        self.smoother = None
        # semantic row-offset vector of this level's numbering (used by
        # the sharded coloring to hash semantic ids)
        self.offsets = offsets

    def restrict(self, data, r):
        from ..ops.spmv import spmv
        return spmv(data["R"], r)

    def prolongate(self, data, xc):
        from ..ops.spmv import spmv
        return spmv(data["P"], xc)

    # The halo-folded per-shard smoother kernel (distributed/fused.py,
    # attached as the smoother's "dist_fused" payload) dispatches
    # inside smooth/smooth_residual (ops/smooth.fused_smooth), so the
    # cycle's one composition IS the fused distributed path; the
    # sharded R/P's owned-aggregate segment sums are shard-local by
    # construction of the partition (remote members arrive through R's
    # own halo map).


class ShardedConsolidationLevel:
    """Boundary between the sharded levels and the replicated tail
    (glue_matrices endpoint, include/distributed/glue.h:200): restrict
    gathers the padded block-aligned coarse vector and compacts it to
    the semantic (single-device) numbering the replicated tail was
    built in; prolongate re-expands."""

    def __init__(self, level, axis: str, offsets_c: np.ndarray,
                 NCL_c: int):
        self._level = level
        self._axis = axis
        self._offsets = jnp.asarray(offsets_c, jnp.int32)
        self._NCL = NCL_c
        self._nc_g = int(offsets_c[-1])
        # semantic -> physical gather map (static, tiny)
        ranks = np.searchsorted(offsets_c, np.arange(self._nc_g),
                                side="right") - 1
        self._sem2phys = jnp.asarray(
            ranks * NCL_c + (np.arange(self._nc_g) - offsets_c[ranks]),
            jnp.int32)

    def __getattr__(self, name):
        return getattr(self._level, name)

    def restrict(self, data, r):
        bc_local = self._level.restrict(data, r)          # (NCL_c,)
        bc_phys = jax.lax.all_gather(bc_local, self._axis, tiled=True)
        return bc_phys[self._sem2phys]                    # semantic

    def prolongate(self, data, xc):
        me = jax.lax.axis_index(self._axis)
        k = jnp.arange(self._NCL)
        lo = self._offsets[me]
        cnt = self._offsets[me + 1] - lo
        xp = jnp.concatenate([xc, jnp.zeros((1,), xc.dtype)])
        xc_local = jnp.where(
            k < cnt, xp[jnp.clip(lo + k, 0, self._nc_g)], 0.0)
        return self._level.prolongate(data, xc_local)

    # No `prolongate_correct` here: the wrapped level's would finish
    # with ITS transfer (the shard-local P), skipping this wrapper's
    # gather/compact into the replicated tail's numbering. The cycle
    # resolves it through the CLASS (amg/cycles.py
    # _prolongate_correct), so the delegation is never consulted.


def _mk_shard(fields: dict, n_global: int, n_local: int,
              n_local_cols: int, n_halo: int, R: int, axis: str
              ) -> ShardMatrix:
    return ShardMatrix(
        rid_own=fields["rid_own"], ci_own=fields["ci_own"],
        va_own=fields["va_own"], rid_halo=fields["rid_halo"],
        ci_halo=fields["ci_halo"], va_halo=fields["va_halo"],
        diag=fields["diag"], halo_src=fields["halo_src"],
        send_prev=None, send_next=None, recv_prev=None, recv_next=None,
        a2a_send=fields["a2a_send"], a2a_recv=fields["a2a_recv"],
        n_global=n_global, n_local=n_local, n_local_cols=n_local_cols,
        n_halo=n_halo, n_ranks=R, axis_name=axis, exchange_mode="a2a")


def _smoother_data(name: str, M: ShardMatrix, solver, mesh=None,
                   axis=None, offsets=None):
    """Row-partitioned smoother solve-data from stacked shard fields
    (JACOBI dinv; JACOBI_L1 dinv with halo-inclusive off-diagonal L1
    sums — solver._dinv_l1 semantics; MULTICOLOR_DILU/GS via the
    sharded JPL coloring + per-color halo-exchanging Einv recurrence)."""
    if name in ("NOSOLVER", "DUMMY"):
        return {"A": M}
    d = M.diag

    def dinv_of(dd):
        safe = jnp.where(dd == 0, 1.0, dd)
        return jnp.where(dd == 0, 0.0, 1.0 / safe)

    if name in ("JACOBI", "BLOCK_JACOBI"):
        return {"A": M, "dinv": jax.jit(dinv_of)(d)}
    if name in ("MULTICOLOR_DILU", "MULTICOLOR_GS"):
        colors_s, nc = sharded_coloring(M, mesh, axis, offsets)
        # the solve-phase color sweeps read num_colors off the solver
        # (solver_setup never runs — there is no global matrix)
        solver.num_colors = nc
        solver.row_colors = None
        if name == "MULTICOLOR_GS":
            return {"A": M, "dinv": jax.jit(dinv_of)(d),
                    "colors": colors_s}
        Einv = _sharded_dilu_einv(M, mesh, axis, colors_s, nc)
        return {"A": M, "Einv": Einv, "colors": colors_s}
    if name == "CHEBYSHEV_POLY":
        # taus need only the global Gershgorin bound: per-shard absolute
        # row sums (owned + halo entries are all shard-local), global
        # max across shards (polynomial.py solver_setup semantics)
        n_local = M.n_local

        @jax.jit
        def lam_of(vo, ro, vh, rh):
            def one(vo, ro, vh, rh):
                s = jax.ops.segment_sum(jnp.abs(vo), ro,
                                        num_segments=n_local) + \
                    jax.ops.segment_sum(jnp.abs(vh), rh,
                                        num_segments=n_local)
                return jnp.max(s)
            return jnp.max(jax.vmap(one)(vo, ro, vh, rh))

        from ..solvers.polynomial import chebyshev_poly_coeffs
        lam = lam_of(M.va_own, M.rid_own, M.va_halo, M.rid_halo)
        taus = jnp.asarray(chebyshev_poly_coeffs(solver.order),
                           M.dtype) / lam.astype(M.dtype)
        R = M.rid_own.shape[0]
        return {"A": M,
                "taus": jnp.broadcast_to(taus[None], (R,) + taus.shape)}
    if name == "JACOBI_L1":
        n_local = M.n_local

        @jax.jit
        def l1(vo, ro, co, vh, rh, dd):
            def one(vo, ro, co, vh, rh, dd):
                off = jnp.where((co == ro) & (ro < n_local), 0.0,
                                jnp.abs(vo))
                s = jax.ops.segment_sum(off, ro, num_segments=n_local) \
                    + jax.ops.segment_sum(jnp.abs(vh), rh,
                                          num_segments=n_local)
                return dinv_of(dd + jnp.sign(dd) * s)
            return jax.vmap(one)(vo, ro, co, vh, rh, dd)

        return {"A": M,
                "dinv": l1(M.va_own, M.rid_own, M.ci_own, M.va_halo,
                           M.rid_halo, d)}
    raise BadParametersError(
        f"sharded setup: smoother {name} not row-partitionable")


# ---------------------------------------------------------------------------
# sharded coloring + strong smoothers (MULTICOLOR_DILU / MULTICOLOR_GS)
# ---------------------------------------------------------------------------

def _hash_w_sem(sem_ids, rnd):
    """ops.coloring._hash_w on explicit semantic ids with a traced
    round (identical uint32 math, so the sharded JPL fixed point makes
    the same per-round decisions as the single-device one)."""
    i = sem_ids.astype(jnp.uint32)
    h = (i + rnd.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)) * \
        jnp.uint32(2654435761)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA6B)
    return h ^ (h >> 13)


def sharded_coloring(M: ShardMatrix, mesh, axis: str, offsets_np,
                     max_rounds: int = 64):
    """Per-shard Jones-Plassmann-Luby MIN_MAX coloring with a halo
    color-state exchange each round — the boundary_coloring=SYNC_COLORS
    policy (src/core.cu:353-354; min_max.cu): boundary rows always see
    their cross-rank neighbors' true color state, so the coloring is
    globally proper. Hash weights are keyed on SEMANTIC global ids,
    which makes the result the EXACT coloring ops.coloring._jpl_min_max
    computes on the assembled matrix — bit-identical colors, hence
    bit-identical DILU factors and iteration parity with the
    single-device path. Assumes a pattern-symmetric matrix (the sharded
    envelope's value-symmetry probe already guarantees it).

    Returns (stacked row colors (R, n_local) int32, num_colors)."""
    n_local = M.n_local
    offsets = jnp.asarray(offsets_np, jnp.int32)
    pspec = jax.tree.map(lambda _: P(axis), M)

    def init_body(Ms):
        Mx = jax.tree.map(lambda a: a[0], Ms)
        offd = (Mx.ci_own != Mx.rid_own).astype(jnp.int32)
        has = jax.ops.segment_max(offd, Mx.rid_own,
                                  num_segments=n_local,
                                  indices_are_sorted=True)
        if Mx.rid_halo.shape[0]:
            has = jnp.maximum(has, jax.ops.segment_max(
                jnp.ones_like(Mx.rid_halo), Mx.rid_halo,
                num_segments=n_local, indices_are_sorted=True))
        # rows with no neighbors (and last-rank pad rows, which have no
        # entries at all) take color 0 immediately
        return jnp.where(has > 0, jnp.int32(-1), jnp.int32(0))[None]

    def round_body(Ms, colors_s, rnd, nc0):
        Mx = jax.tree.map(lambda a: a[0], Ms)
        colors = colors_s[0]
        me = jax.lax.axis_index(axis)
        sem = offsets[me] + jnp.arange(n_local, dtype=jnp.int32)
        w = _hash_w_sem(sem, rnd)
        offd = Mx.ci_own != Mx.rid_own

        def extract(colors, ncol, maximize):
            un = colors < 0
            fill = jnp.uint32(0) if maximize else jnp.uint32(0xFFFFFFFF)
            wm = jnp.where(un, w, fill)
            seg = jax.ops.segment_max if maximize else jax.ops.segment_min
            nbest = seg(jnp.where(offd, wm[Mx.ci_own], fill), Mx.rid_own,
                        num_segments=n_local, indices_are_sorted=True)
            if Mx.rid_halo.shape[0]:
                halo_w = Mx.exchange_halo(wm)
                hp = halo_w if Mx.n_halo else jnp.full((1,), fill,
                                                       jnp.uint32)
                nb2 = seg(hp[Mx.ci_halo], Mx.rid_halo,
                          num_segments=n_local, indices_are_sorted=True)
                nbest = jnp.maximum(nbest, nb2) if maximize \
                    else jnp.minimum(nbest, nb2)
            take = un & ((w > nbest) if maximize else (w < nbest))
            return jnp.where(take, ncol, colors)

        colors = extract(colors, nc0, True)
        un1 = jax.lax.psum(jnp.sum((colors < 0).astype(jnp.int32)), axis)
        colors = extract(colors, nc0 + 1, False)
        un2 = jax.lax.psum(jnp.sum((colors < 0).astype(jnp.int32)), axis)
        return colors[None], jnp.stack([un1, un2])

    def fin_body(colors_s, nxt):
        c = jnp.where(colors_s[0] < 0, nxt, colors_s[0])
        num = jax.lax.pmax(jnp.max(c), axis) + 1
        return c[None], num

    init_fn = jax.jit(shard_map(init_body, mesh=mesh, in_specs=(pspec,),
                                out_specs=P(axis), check_vma=False))
    step_fn = jax.jit(shard_map(
        round_body, mesh=mesh, in_specs=(pspec, P(axis), P(), P()),
        out_specs=(P(axis), P()), check_vma=False))
    fin_fn = jax.jit(shard_map(
        fin_body, mesh=mesh, in_specs=(P(axis), P()),
        out_specs=(P(axis), P()), check_vma=False))

    colors_s = init_fn(M)
    next_color = 0
    for rnd in range(max_rounds):
        colors_s, cnt = step_fn(M, colors_s, jnp.uint32(rnd),
                                jnp.int32(next_color))
        after_max, after_min = (int(v) for v in np.asarray(cnt))
        if after_max == 0:
            next_color += 1          # min phase was a no-op
            break
        next_color += 2
        if after_min == 0:
            break
    colors_s, num = fin_fn(colors_s, jnp.int32(next_color))
    return colors_s, int(num)


def _sharded_dilu_einv(M: ShardMatrix, mesh, axis: str, colors_s,
                       num_colors: int):
    """Per-shard DILU E^{-1} recurrence color-by-color with a halo Einv
    exchange per color (multicolor_dilu_solver.cu:650-810 setup). The
    reverse-edge value a_ji equals the stored a_ij because the sharded
    envelope admits only (probe-verified) value-symmetric matrices —
    the transpose lookup the single-device _match_transpose performs
    collapses to the owned value. Einv_j is zero until color_j is
    processed, so the color_j < color_i predicate falls out for free,
    exactly as in the single-device setup."""
    n_local = M.n_local
    pspec = jax.tree.map(lambda _: P(axis), M)

    def body(Ms, cs):
        Mx = jax.tree.map(lambda a: a[0], Ms)
        colors = cs[0]
        d = Mx.diag
        Einv = jnp.zeros((n_local,), Mx.va_own.dtype)
        for c in range(num_colors):
            e = jax.ops.segment_sum(
                Mx.va_own * Einv[Mx.ci_own] * Mx.va_own, Mx.rid_own,
                num_segments=n_local, indices_are_sorted=True)
            if Mx.rid_halo.shape[0]:
                halo_E = Mx.exchange_halo(Einv)
                hp = halo_E if Mx.n_halo else jnp.zeros((1,), Einv.dtype)
                e = e + jax.ops.segment_sum(
                    Mx.va_halo * hp[Mx.ci_halo] * Mx.va_halo,
                    Mx.rid_halo, num_segments=n_local,
                    indices_are_sorted=True)
            blk = d - e
            new = jnp.where(blk == 0, 0.0, 1.0 / jnp.where(blk == 0, 1.0,
                                                           blk))
            Einv = jnp.where(colors == c, new, Einv)
        return Einv[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(pspec, P(axis)),
                           out_specs=P(axis), check_vma=False))
    return fn(M, colors_s)


# MIN_MAX-equivalent schemes the sharded coloring reproduces exactly.
# GREEDY_RECOLOR is deliberately excluded: its single-device form adds
# a recoloring pass on top of MIN_MAX (ops/coloring.py), which the
# sharded JPL does not reproduce — it falls back to the global setup.
_SHARDED_COLORINGS = {"MIN_MAX", "PARALLEL_GREEDY", "LOCALLY_DOWNWIND"}

_SHARDED_SMOOTHERS = {"JACOBI", "BLOCK_JACOBI", "JACOBI_L1", "NOSOLVER",
                      "DUMMY", "CHEBYSHEV_POLY", "MULTICOLOR_DILU",
                      "MULTICOLOR_GS"}
# selector -> matching passes. MULTI_PAIRWISE's entry marks membership
# only; its real pass count comes from cfg aggregation_passes.
_SHARDED_SELECTORS = {"SIZE_2": 1, "PARALLEL_GREEDY": 1, "SIZE_4": 2,
                      "SIZE_8": 3, "MULTI_PAIRWISE": 2}


def sharded_eligible(amg, A) -> Optional[str]:
    """None if the sharded setup supports this AMG config; else the
    reason string (callers fall back to the global-setup path)."""
    if amg.algorithm == "CLASSICAL":
        # sharded classical (setup_classical.py): PMIS + D1 + AHAT only
        sel = str(amg.cfg.get("selector", amg.scope)).upper()
        if sel != "PMIS":
            return f"classical selector {sel} not sharded (PMIS only)"
        interp = str(amg.cfg.get("interpolator", amg.scope)).upper()
        if interp != "D1":
            return (f"classical interpolator {interp} not sharded "
                    "(D1 only)")
        if str(amg.cfg.get("strength", amg.scope)).upper() != "AHAT":
            return "classical strength != AHAT not sharded"
        if int(amg.cfg.get("aggressive_levels", amg.scope)) > 0:
            return "aggressive coarsening uses the global setup"
        # interp_max_elements / interp_truncation_factor are supported:
        # truncation is a per-row top-k on the D1 slot vectors
        # (setup_classical._truncate_slots, src/truncate.cu semantics)
    elif amg.algorithm != "AGGREGATION":
        return "energymin algorithms use the global setup"
    else:
        sel = str(amg.cfg.get("selector", amg.scope)).upper()
        if sel not in _SHARDED_SELECTORS:
            return (f"selector {sel} not sharded (geo/dummy use global "
                    "setup)")
    if A.is_block:
        return "block systems use the global setup"
    if amg.cycle_name in ("CG", "CGF"):
        return "K-cycles use the global setup"
    pairs = [amg.cfg.get_solver("smoother", amg.scope)]
    if int(amg.cfg.get("fine_levels", amg.scope)) >= 0:
        pairs.append(amg.cfg.get_solver("fine_smoother", amg.scope))
        pairs.append(amg.cfg.get_solver("coarse_smoother", amg.scope))
    bad = {n.upper() for n, _ in pairs} - _SHARDED_SMOOTHERS
    if bad:
        return f"smoother(s) {sorted(bad)} not row-partitionable"
    for n, scp in pairs:
        if n.upper() not in ("MULTICOLOR_DILU", "MULTICOLOR_GS"):
            continue
        scheme = str(amg.cfg.get("matrix_coloring_scheme", scp)).upper()
        if scheme not in _SHARDED_COLORINGS:
            return (f"coloring scheme {scheme} has no sharded analog "
                    "(MIN_MAX-family only)")
        if int(amg.cfg.get("coloring_level", scp)) != 1:
            return "sharded coloring supports coloring_level=1 only"
    if float(amg.cfg.get("error_scaling", amg.scope)):
        return "error_scaling uses the global setup"
    return None


def _wrap(mesh, axis, in_tree, fn):
    pspec = jax.tree.map(lambda _: P(axis), in_tree)
    mapped = shard_map(fn, mesh=mesh, in_specs=(pspec,),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(mapped)


def _gather_compact(M: ShardMatrix, offsets: np.ndarray):
    """Gather a (small) stacked shard level to the host and compact it
    to the semantic contiguous numbering — the matrix the single-device
    setup would hold at this level. Runs once per solve setup at the
    consolidation boundary; size is bounded by one shard's budget."""
    from ..matrix import CsrMatrix
    R = offsets.shape[0] - 1
    NCL = M.n_local
    rid_o = np.asarray(M.rid_own)
    ci_o = np.asarray(M.ci_own)
    va_o = np.asarray(M.va_own)
    rid_h = np.asarray(M.rid_halo)
    ci_h = np.asarray(M.ci_halo)
    va_h = np.asarray(M.va_halo)
    hsrc = np.asarray(M.halo_src)
    rows, cols, vals = [], [], []
    for r in range(R):
        vo = rid_o[r] < NCL
        rows.append(offsets[r] + rid_o[r][vo])
        cols.append(offsets[r] + ci_o[r][vo])
        vals.append(va_o[r][vo])
        vh = rid_h[r] < NCL
        rows.append(offsets[r] + rid_h[r][vh])
        ph = hsrc[r][np.clip(ci_h[r][vh], 0, hsrc.shape[1] - 1)]
        cols.append(offsets[np.clip(ph // NCL, 0, R - 1)] + ph % NCL)
        vals.append(va_h[r][vh])
    rows = np.concatenate(rows).astype(np.int64)
    cols = np.concatenate(cols).astype(np.int64)
    vals = np.concatenate(vals)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    first = np.concatenate([[True], (rows[1:] != rows[:-1])
                            | (cols[1:] != cols[:-1])])
    seg = np.cumsum(first) - 1
    vsum = np.zeros(int(seg[-1]) + 1 if seg.size else 0, vals.dtype)
    np.add.at(vsum, seg, vals)
    rows_u, cols_u = rows[first], cols[first]
    n = int(offsets[-1])
    counts = np.bincount(rows_u, minlength=n)
    row_offsets = np.zeros(n + 1, np.int32)
    np.cumsum(counts, out=row_offsets[1:])
    return CsrMatrix.from_scipy_like(
        row_offsets, cols_u.astype(np.int32), jnp.asarray(vsum), n, n)


def _smoother_assignment(amg):
    cfg, scope = amg.cfg, amg.scope
    sm = cfg.get_solver("smoother", scope)
    fine_levels = int(cfg.get("fine_levels", scope))
    fs = cfg.get_solver("fine_smoother", scope)
    cs2 = cfg.get_solver("coarse_smoother", scope)

    def assign(k: int):
        if fine_levels < 0:
            return sm
        return fs if k < fine_levels else cs2
    return assign


def build_sharded_hierarchy(amg, shard_A: ShardMatrix, mesh, axis: str,
                            global_A=None):
    """Build the distributed AMG hierarchy per-shard (no global level is
    ever materialized above the consolidation boundary). Mutates `amg`
    (levels, coarse solver) and returns the stacked solve-data pytree
    {"levels": [...], "coarse": ...}, or None when the problem is too
    small for even one sharded level (caller falls back to the global
    setup path). `global_A`, when the caller holds it (the
    non-pieces upload path), enables the halo-folded fused-smoother
    payload on the finest level (distributed/fused.py) — its DIA slabs
    are the only global view this build ever touches, and coarse
    levels stay strictly per-shard."""
    from ..solvers.base import make_solver
    from .amg import _replicate
    cfg, scope = amg.cfg, amg.scope
    R = int(mesh.devices.size)
    max_it = int(cfg.get("max_matching_iterations", scope))
    merge = bool(int(cfg.get("merge_singletons", scope)))
    formula = int(cfg.get("weight_formula", scope))
    n_local0 = shard_A.n_local
    n_g0 = shard_A.n_global
    # consolidation boundary: by default a coarse level consolidates to
    # the replicated tail when its global size fits one shard's initial
    # budget; matrix_consolidation_lower_threshold (the reference's
    # consolidation knob, an AVERAGE-rows-per-rank threshold) overrides
    # it so deeper levels stay sharded
    thr = int(cfg.get("matrix_consolidation_lower_threshold", scope))
    consolidate_at = thr * R if thr > 0 else n_local0
    offsets = np.minimum(np.arange(R + 1) * n_local0, n_g0
                         ).astype(np.int32)
    M = shard_A
    levels, levels_data, ncl_last = [], [], None
    offsets_last = None
    lvl = 0
    if amg.algorithm == "CLASSICAL":
        from .setup_classical import run_classical_levels
        res = run_classical_levels(amg, mesh, axis, M, offsets, R,
                                   consolidate_at)
        if res is None:
            return None
        (levels, levels_data, M, offsets, lvl, offsets_last,
         ncl_last) = res
        return _finish_sharded(amg, mesh, axis, M, offsets, lvl,
                               levels, levels_data, offsets_last,
                               ncl_last, R, global_A=global_A)
    sel = str(cfg.get("selector", scope)).upper()
    passes = _SHARDED_SELECTORS.get(sel, 1)
    if sel == "MULTI_PAIRWISE":
        passes = max(int(cfg.get("aggregation_passes", scope)), 1)
        if int(cfg.get("notay_weights", scope)):
            formula = 1

    def runA(Ms, offs_np, graph):
        offs = jnp.asarray(offs_np)

        def fa(Mx, _offs=offs, _g=graph):
            out = _phase_a_body(Mx.local(), _offs, axis, max_it,
                                formula, merge, _g)
            return jax.tree.map(lambda a: a[None], out)
        return _wrap(mesh, axis, Ms, fa)(Ms)

    def runB(Ms, offs_np, agg_s, w_s, NCL, mq, mt, graph_rap):
        offs = jnp.asarray(offs_np)

        def fb(args, _offs=offs):
            Mx, a_, w_ = args
            out = _phase_b_body(Mx.local(), _offs, a_[0], w_[0], axis,
                                NCL, mq, mt, mq, graph_rap)
            return jax.tree.map(lambda a: a[None], out)
        return _wrap(mesh, axis, (Ms, agg_s, w_s), fb)((Ms, agg_s, w_s))

    def runC(Ms, offs_np, offsets_c_np, triples, cid_sem_s, cid_phys_s,
             mcid_s, mgid_s, sizes, build_transfers):
        offs = jnp.asarray(offs_np)
        offs_c = jnp.asarray(offsets_c_np)
        E_own, E_halo, H_c, H_p, H_r = sizes

        def fc(args, _offs=offs, _offs_c=offs_c):
            (Mx, s1, c1, v1, cs, cp, mc, mg) = args
            out = _phase_c_body(
                Mx.local(), _offs, (s1[0], c1[0], v1[0]), cs[0], cp[0],
                mc[0], mg[0], _offs_c, axis, _NCL_of(offsets_c_np),
                E_own, E_halo, H_c, max(H_c, 1), H_p, max(H_p, 1),
                H_r, max(H_r, 1), build_transfers)
            return jax.tree.map(
                lambda a: a[None] if a is not None else None, out)
        argsC = (Ms, *triples, cid_sem_s, cid_phys_s, mcid_s, mgid_s)
        return _wrap(mesh, axis, argsC, fc)(argsC)

    def _NCL_of(offsets_c_np):
        return max(int(np.diff(offsets_c_np).max()), 1)

    while True:
        n = int(offsets[-1])
        if (lvl + 1 >= amg.max_levels or n <= max(amg.min_coarse_rows, 1)
                or n < amg.min_fine_rows
                or (n <= amg.dense_lu_num_rows and lvl > 0)):
            break
        if lvl > 0 and n <= consolidate_at:
            break      # tail fits the consolidation budget
        # -- pass 1: matching on this level's matrix --------------------
        agg, paired, w, countsA = runA(M, offsets, False)
        ca = np.asarray(countsA)
        nc_locals = ca[:, 0].astype(np.int64)
        nc_g = int(nc_locals.sum())
        if nc_g <= 0 or nc_g >= n:
            break
        if passes == 1 and (n / max(nc_g, 1)) < amg.coarsen_threshold:
            # multipass selectors apply the threshold to the COMPOSED
            # ratio below (hierarchy._build_levels semantics)
            break
        NCL_c = max(int(nc_locals.max()), 1)
        maxt = max(int(ca[:, 1:1 + R].max()), 1)
        maxm = max(int(ca[:, 1 + R:1 + 2 * R].max()), 1)
        outB = runB(M, offsets, agg, w, NCL_c, maxm, maxt,
                    graph_rap=(passes > 1))
        (slot_s, cj_s, v_s, cid_sem, cid_phys, mcid, mgid,
         countsB) = outB
        cb = np.asarray(countsB)
        sizes = tuple(max(int(cb[:, i].max()), 1) for i in
                      (2, 3, 4, 5, 6))
        offsets_c = np.concatenate(
            [[0], np.cumsum(nc_locals)]).astype(np.int32)
        # -- passes 2..P: matching on the coarse weight graph -----------
        if passes > 1:
            G_f, _, _ = runC(M, offsets, offsets_c,
                             (slot_s, cj_s, v_s), cid_sem, cid_phys,
                             mcid, mgid, sizes, False)
            G = _mk_shard(G_f, R * NCL_c, NCL_c, NCL_c, sizes[2], R,
                          axis)
            offs_g = offsets_c
            cid_fine = cid_sem          # per-FINE-vertex coarse id
            for p in range(2, passes + 1):
                aggp, pairedp, wp, countsAp = runA(G, offs_g, True)
                cap = np.asarray(countsAp)
                ncl_p = cap[:, 0].astype(np.int64)
                if int(ncl_p.sum()) <= 0:
                    break               # pass made no progress
                NCLp = max(int(ncl_p.max()), 1)
                mtp = max(int(cap[:, 1:1 + R].max()), 1)
                mmp = max(int(cap[:, 1 + R:1 + 2 * R].max()), 1)
                outBp = runB(G, offs_g, aggp, wp, NCLp, mmp, mtp,
                             graph_rap=True)
                (gs, gc, gv, Tp, _Tphys, _mc, _mg, countsBp) = outBp
                # compose: fine vertex -> its pass-p coarse id
                offs_gj = jnp.asarray(offs_g)

                def fcnt(args, _o=offs_gj):
                    c_, = args
                    return _compose_counts_body(c_[0], _o, axis)[None]
                qc = np.asarray(_wrap(mesh, axis, (cid_fine,), fcnt)(
                    (cid_fine,)))
                maxq = max(int(qc.max()), 1)

                def fcomp(args, _o=offs_gj, _mq=maxq):
                    c_, t_ = args
                    return _compose_body(c_[0], t_[0], _o, axis,
                                         _mq)[None]
                cid_fine = _wrap(mesh, axis, (cid_fine, Tp), fcomp)(
                    (cid_fine, Tp))
                offsets_c = np.concatenate(
                    [[0], np.cumsum(ncl_p)]).astype(np.int32)
                nc_locals = ncl_p
                if p < passes:
                    cbp = np.asarray(countsBp)
                    sizes_p = tuple(max(int(cbp[:, i].max()), 1)
                                    for i in (2, 3, 4, 5, 6))
                    G_f, _, _ = runC(G, offs_g, offsets_c,
                                     (gs, gc, gv), Tp, _Tphys, _mc,
                                     _mg, sizes_p, False)
                    G = _mk_shard(G_f, R * NCLp, NCLp, NCLp,
                                  sizes_p[2], R, axis)
                offs_g = offsets_c
            nc_g = int(nc_locals.sum())
            if nc_g >= n or (n / max(nc_g, 1)) < amg.coarsen_threshold:
                break
            NCL_c = max(int(np.diff(offsets_c).max()), 1)  # composed
            # -- final RAP on the fine matrix with composed cids --------
            offs_j = jnp.asarray(offsets)
            offs_cj = jnp.asarray(offsets_c)

            # per-dest budgets for the final routing
            def ffin(args, _o=offs_j, _oc=offs_cj):
                Mx, c_ = args
                return _final_route_counts(Mx.local(), _o, c_[0], _oc,
                                           axis)[None]
            fc2 = np.asarray(_wrap(mesh, axis, (M, cid_fine), ffin)(
                (M, cid_fine)))
            maxt2 = max(int(fc2[:, :R].max()), 1)
            maxm2 = max(int(fc2[:, R:].max()), 1)

            def fb2(args, _o=offs_j, _oc=offs_cj, _NCL=NCL_c,
                    _mt=maxt2, _mm=maxm2):
                Mx, c_ = args
                out = _phase_b2_full(Mx.local(), _o, c_[0], _oc, axis,
                                     _NCL, _mt, _mm)
                return jax.tree.map(lambda a: a[None], out)
            outB2 = _wrap(mesh, axis, (M, cid_fine), fb2)((M, cid_fine))
            (slot_s, cj_s, v_s, cid_phys2, mcid, mgid, countsB2) = outB2
            cid_sem = cid_fine
            cid_phys = cid_phys2
            cb2 = np.asarray(countsB2)
            sizes = tuple(max(int(cb2[:, i].max()), 1) for i in
                          (2, 3, 4, 5, 6))
        A_c_f, P_f, R_f = runC(M, offsets, offsets_c,
                               (slot_s, cj_s, v_s), cid_sem, cid_phys,
                               mcid, mgid, sizes, True)
        NCL_c = max(int(np.diff(offsets_c).max()), 1)  # final numbering
        A_c = _mk_shard(A_c_f, R * NCL_c, NCL_c, NCL_c, sizes[2], R,
                        axis)
        P_sh = _mk_shard(P_f, n_g0, M.n_local, NCL_c, sizes[3], R, axis)
        R_sh = _mk_shard(R_f, R * NCL_c, NCL_c, M.n_local, sizes[4], R,
                         axis)
        level = DistAMGLevel(M, lvl, offsets=np.asarray(offsets))
        levels.append(level)
        levels_data.append({"A": M, "P": P_sh, "R": R_sh})
        offsets_last, ncl_last = offsets_c, NCL_c
        M, offsets = A_c, offsets_c
        lvl += 1
    if not levels:
        return None
    return _finish_sharded(amg, mesh, axis, M, offsets, lvl, levels,
                           levels_data, offsets_last, ncl_last, R,
                           global_A=global_A)


def _finish_sharded(amg, mesh, axis, M, offsets, lvl, levels,
                    levels_data, offsets_last, ncl_last, R,
                    global_A=None):
    """Shared tail of the sharded build (aggregation and classical):
    gather + compact the consolidation-boundary level, build the
    replicated tail with the existing global setup, attach smoothers."""
    from ..solvers.base import make_solver
    from .amg import _replicate
    cfg, scope = amg.cfg, amg.scope
    # ---- replicated tail: gather + compact + existing global setup ----
    A_tail = _gather_compact(M, offsets).init()
    amg.levels = list(levels)
    # this function owns the smoother assignment for every level (incl.
    # the replicated tail below) — suppress the hierarchy's per-level
    # inline attach so tail smoothers are not set up twice
    amg._defer_smoothers = True
    try:
        amg._build_levels(A_tail, lvl)
    finally:
        amg._defer_smoothers = False
    assign = _smoother_assignment(amg)
    boundary = len(levels)
    for k, lv in enumerate(levels):
        name, scp = assign(k)
        lv.smoother = make_solver(name, cfg, scp)
        lv.smoother._owns_scaling = False
        # duck-typed operator view: color-sweep smoothers read static
        # metadata (is_block, block_dimx) off self.A at trace time
        lv.smoother.A = levels_data[k]["A"]
        levels_data[k]["smoother"] = _smoother_data(
            name.upper(), levels_data[k]["A"], lv.smoother,
            mesh=mesh, axis=axis, offsets=lv.offsets)
    # halo-folded fused payload for the FINEST level (its global DIA
    # operator is the caller's upload; coarse levels are COO-built
    # per-shard with no DIA view and keep the unfused path)
    if global_A is not None and levels:
        from .fused import attach_shard_fused, fusion_gates
        # cheap gates FIRST: the dinv materialization below is a full
        # device->host pull, wasted on every knob=0 / unfused-runtime
        # setup if done unconditionally
        if fusion_gates(cfg, scope, levels[0].smoother):
            smd0 = levels_data[0]["smoother"]
            dinv_src = smd0.get("dinv")
            dinv_g = None
            if dinv_src is not None:
                # thunk + dinv_key: the flatten is a full device->host
                # pull, deferred past the memo check (keyed on the
                # stacked source array's identity — a slice would be a
                # fresh object every setup) so repeated setups on the
                # same values transfer nothing
                dinv_g = lambda: np.asarray(dinv_src).reshape(-1)[
                    : global_A.num_rows]
            attach_shard_fused(smd0, global_A, levels[0].smoother, R,
                               levels_data[0]["A"].n_local, cfg, scope,
                               dinv_global=dinv_g, dinv_key=dinv_src)
    tail_data = []
    for k in range(boundary, len(amg.levels)):
        lv = amg.levels[k]
        name, scp = assign(k)
        lv.smoother = make_solver(name, cfg, scp)
        lv.smoother._owns_scaling = False
        if getattr(lv.smoother, "needs_cf_map", False) and \
                getattr(lv, "cf_map", None) is not None:
            lv.smoother.set_cf_map(lv.cf_map)
        lv.smoother.setup(lv.A)
        tail_data.append(_replicate(lv.level_data(), R))
    cs_name, cs_scope = cfg.get_solver("coarse_solver", scope)
    amg.coarse_solver = make_solver(cs_name, cfg, cs_scope)
    amg.coarse_solver._owns_scaling = False
    amg.coarse_solver.setup(amg.coarsest_A)
    amg.num_levels = len(amg.levels) + 1
    coarse_data = _replicate(amg.coarse_solver.solve_data_part(), R)
    # wrap the last sharded level: gather/compact into the tail's space
    amg.levels[boundary - 1] = ShardedConsolidationLevel(
        levels[-1], axis, offsets_last, ncl_last)
    return {"levels": levels_data + tail_data, "coarse": coarse_data}


# ---------------------------------------------------------------------------
# multipass (SIZE_4 / SIZE_8 / MULTI_PAIRWISE) support: matching repeats
# on the coarse weight graph, composed cids drive one final RAP
# ---------------------------------------------------------------------------

def _phase_b2_body(M: ShardMatrix, offsets, cid_sem, cid_phys,
                   offsets_c, axis: str, NCL_c: int, maxt: int,
                   maxm: int):
    """RAP + member routing from PRE-COMPOSED per-vertex coarse ids
    (the multipass path: ids come from matching rounds on coarse weight
    graphs, not from this level's own aggregate roots)."""
    me = jax.lax.axis_index(axis)
    R = offsets.shape[0] - 1
    n = M.n_local
    E = _Edges(M, offsets, me)
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    active = idx_sem < offsets[me + 1]
    owner_final = _owner_of_sem(cid_sem, offsets_c, R,
                                active & (cid_sem >= 0))
    slot_s, cj_s, v_s, first, n_unique = _rap_triples(
        E, cid_sem, cid_phys, owner_final, me, offsets_c, NCL_c, axis,
        R, maxt)
    hlist_cnt = _count_unique_remote(cj_s, first, me, NCL_c)
    owner_cj = jnp.clip(cj_s // NCL_c, 0, R)
    n_own_u = jnp.sum((first & (owner_cj == me)).astype(jnp.int32))
    n_halo_u = jnp.sum((first & (owner_cj != me)).astype(jnp.int32))
    gid_phys = me * n + jnp.arange(n, dtype=jnp.int32)
    dest_m = jnp.where(owner_final == me, R, owner_final)
    mcid, mgid = _route((cid_sem, gid_phys), dest_m, me, axis, R, maxm,
                        (_SENT, _SENT))
    n_p_halo = _count_unique_remote(cid_phys, active & (cid_phys >= 0),
                                    me, NCL_c)
    n_r_halo = _count_unique_remote(mgid, mcid != _SENT, me, n)
    counts = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), n_unique[None], n_own_u[None],
        n_halo_u[None], hlist_cnt[None], n_p_halo[None],
        n_r_halo[None]])
    return slot_s, cj_s, v_s, mcid, mgid, counts


def _compose_counts_body(cid_sem, offsets_c, axis: str):
    """Per-peer query counts for the compose lookup (fine vertex ->
    owner of its current coarse id)."""
    R = offsets_c.shape[0] - 1
    me = jax.lax.axis_index(axis)
    valid = cid_sem >= 0
    owner = _owner_of_sem(cid_sem, offsets_c, R, valid)
    remote = jnp.where(owner == me, R, owner)
    cnt = jnp.zeros((R,), jnp.int32).at[
        jnp.clip(remote, 0, R - 1)].add((remote < R).astype(jnp.int32))
    return cnt


def _compose_body(cid_sem, table_sem, offsets_c, axis: str,
                  maxq: int):
    """cid_new[i] = table[cid_sem[i]] — the pass-composition lookup
    (table maps this pass's coarse vertices, shard-local, to the next
    pass's semantic coarse ids)."""
    R = offsets_c.shape[0] - 1
    me = jax.lax.axis_index(axis)
    n_local_c = table_sem.shape[0]
    valid = cid_sem >= 0
    owner = _owner_of_sem(cid_sem, offsets_c, R, valid)
    local_ans = table_sem[jnp.clip(cid_sem - offsets_c[me], 0,
                                   n_local_c - 1)]
    remote_owner = jnp.where(owner == me, R, owner)
    looked = _remote_lookup(table_sem, cid_sem, remote_owner, offsets_c,
                            me, n_local_c, axis, R, maxq,
                            jnp.int32(-1))
    out = jnp.where(owner == me, local_ans, looked)
    return jnp.where(valid, out, -1).astype(jnp.int32)


def _final_route_counts(M: ShardMatrix, offsets, cid_sem, offsets_c,
                        axis: str):
    """Per-dest triple + member counts for the final multipass RAP
    (packed (2R,)): [triples_to_peer*R, members_to_peer*R]."""
    me = jax.lax.axis_index(axis)
    R = offsets.shape[0] - 1
    n = M.n_local
    E = _Edges(M, offsets, me)
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    active = idx_sem < offsets[me + 1]
    owner = _owner_of_sem(cid_sem, offsets_c, R, active & (cid_sem >= 0))
    ol = jnp.concatenate([owner, jnp.full((1,), R, jnp.int32)])
    dest_e = jnp.where(E.valid, ol[jnp.minimum(E.rows, n)], R)
    tri = jnp.zeros((R,), jnp.int32).at[
        jnp.clip(dest_e, 0, R - 1)].add((dest_e < R).astype(jnp.int32))
    mem_r = jnp.where(owner == me, R, owner)
    mem = jnp.zeros((R,), jnp.int32).at[
        jnp.clip(mem_r, 0, R - 1)].add((mem_r < R).astype(jnp.int32))
    return jnp.concatenate([tri, mem])


def _phase_b2_full(M: ShardMatrix, offsets, cid_sem, offsets_c,
                   axis: str, NCL_c: int, maxt: int, maxm: int):
    """Final multipass RAP: derive physical ids from the composed
    semantic cids, route triples and member records, dedup-sum."""
    me = jax.lax.axis_index(axis)
    R = offsets.shape[0] - 1
    n = M.n_local
    idx_sem = offsets[me] + jnp.arange(n, dtype=jnp.int32)
    active = idx_sem < offsets[me + 1]
    valid = active & (cid_sem >= 0)
    rank_c = _owner_of_sem(cid_sem, offsets_c, R, valid)
    rr = jnp.clip(rank_c, 0, R - 1)
    cid_phys = jnp.where(valid, rr * NCL_c + (cid_sem - offsets_c[rr]),
                         -1).astype(jnp.int32)
    (slot_s, cj_s, v_s, mcid, mgid, counts) = _phase_b2_body(
        M, offsets, cid_sem, cid_phys, offsets_c, axis, NCL_c, maxt,
        maxm)
    return slot_s, cj_s, v_s, cid_phys, mcid, mgid, counts
