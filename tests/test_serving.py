"""Serving subsystem tests (amgx_tpu/serving/): chunked-solve parity,
continuous-batching parity vs one-shot solve_many, slot refill without
retrace, per-tenant deadlines (expiry -> DEADLINE_EXCEEDED, never a
hung bucket), hierarchy-cache routing to value-resetup, bytes-budgeted
eviction, AOT round-trip with zero retraces, batcher fairness/LRU
satellites, the capi + bench surfaces — and the fault-tolerance layer:
journaled crash recovery with bit-identical checkpoint resume,
persisted hierarchy structures (restart without a full setup), the
scheduler lock split (submit never waits on device work), OVERLOADED
load shedding, and the service-level chaos scenarios (builder crash,
device-step exception, wedged bucket, store corruption, clock skew —
every one must end all-tickets-terminal). No reference analog — AMGX
is consumed AS a service library; the service loop itself is new."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.batch import BatchedSolver, RequestBatcher
from amgx_tpu.batch.queue import pattern_fingerprint
from amgx_tpu.config import Config
from amgx_tpu.presets import BATCHED_CG, SERVING_CG
from amgx_tpu.resilience import faultinject
from amgx_tpu.resilience.policy import parse_fallback_policy
from amgx_tpu.resilience.status import (SolveStatus, status_string,
                                        to_amgx_status)
from amgx_tpu.serving import (BucketEngine, HierarchyCache,
                              SolveService, solve_data_bytes)
from amgx_tpu.solvers.base import Solver
from amgx_tpu.telemetry import metrics

amgx.initialize()


@pytest.fixture(scope="module")
def poisson16():
    return gallery.poisson("5pt", 16, 16).init()


@pytest.fixture(scope="module")
def geo10():
    return gallery.poisson("7pt", 10, 10, 10).init()


def _shift(A, c):
    vals = np.asarray(A.values).copy()
    vals[np.asarray(A.diag_idx)] += c
    return A.with_values(vals)


def _rhs(A, seed=0):
    return np.random.default_rng(seed).standard_normal(A.num_rows)


def _svc_cfg(base=BATCHED_CG, extra=""):
    return Config.from_string(
        base + ", serving_bucket_slots=2, serving_chunk_iters=4"
        + (", " + extra if extra else ""))


def _key(A, b):
    return f"{pattern_fingerprint(A)}/{np.asarray(b).dtype}"


# ---------------------------------------------------------------------------
# chunked solve entry
# ---------------------------------------------------------------------------


def test_chunk_fns_match_one_shot_solve(poisson16):
    """Stepping the chunked entry to completion reproduces solve()
    exactly: same iterates, same packed stats, bit-identical x."""
    slv = amgx.create_solver(Config.from_string(BATCHED_CG))
    slv.setup(poisson16)
    b = _rhs(poisson16, 1)
    ref = slv.solve(b)
    init, step, fin = slv._build_chunk_fns(3)
    data = slv.solve_data()
    bj = jnp.asarray(b)
    st = jax.jit(init)(data, bj, jnp.zeros_like(bj))
    jstep = jax.jit(step)
    for _ in range(100):
        st = jstep(data, bj, st)
        if bool(st["done"]) or int(st["iters"]) >= slv.max_iters:
            break
    x, stats = jax.jit(fin)(data, bj, st)
    it, cv, sc, n0, rn, hist = Solver.unpack_stats(
        stats, slv.max_iters + 1)
    assert it == ref.iterations and cv == ref.converged
    assert sc == ref.status_code
    np.testing.assert_array_equal(np.asarray(x), np.asarray(ref.x))
    np.testing.assert_allclose(rn, ref.res_norm, rtol=1e-12)


def test_chunk_window_is_per_system_relative(poisson16):
    """A chunk advances at most `chunk` iterations from the ENTRY
    count, whatever iteration the system resumed at."""
    slv = amgx.create_solver(Config.from_string(BATCHED_CG))
    slv.setup(poisson16)
    b = jnp.asarray(_rhs(poisson16, 2))
    init, step, _fin = slv._build_chunk_fns(5)
    st = jax.jit(init)(slv.solve_data(), b, jnp.zeros_like(b))
    st = jax.jit(step)(slv.solve_data(), b, st)
    assert int(st["iters"]) == 5
    st = jax.jit(step)(slv.solve_data(), b, st)
    assert int(st["iters"]) == 10


# ---------------------------------------------------------------------------
# continuous batching parity + refill
# ---------------------------------------------------------------------------


def test_service_parity_vs_one_shot_solve_many(poisson16):
    """Continuous batching delivers the same per-system iterates as a
    one-shot batched solve_many over the same systems (same hierarchy
    structure, same while_loop body — only the chunking differs)."""
    mats = [_shift(poisson16, 0.3 * i) for i in range(4)]
    bs_rhs = np.stack([_rhs(poisson16, i) for i in range(4)])
    svc = SolveService(_svc_cfg())
    tickets = [svc.submit(m, b) for m, b in zip(mats, bs_rhs)]
    svc.drain(timeout_s=300)
    one = BatchedSolver(Config.from_string(BATCHED_CG))
    one.setup(mats[0])
    ref = one.solve_many(bs_rhs, matrices=mats)
    assert ref.all_converged
    for i, t in enumerate(tickets):
        assert t.done and t.result.converged
        assert t.result.iterations == int(ref.iterations[i])
        np.testing.assert_allclose(np.asarray(t.result.x),
                                   np.asarray(ref.x[i]),
                                   rtol=1e-12, atol=1e-12)


def test_slot_refill_without_retrace(poisson16):
    """5 systems through a 2-slot bucket: drained slots are refilled
    mid-flight and the engine's three functions trace exactly once."""
    mats = [_shift(poisson16, 0.2 * i) for i in range(5)]
    base = metrics.get("serving.retrace")
    svc = SolveService(_svc_cfg())
    tickets = [svc.submit(m, _rhs(m, i)) for i, m in enumerate(mats)]
    svc.drain(timeout_s=300)
    assert all(t.result.converged for t in tickets)
    assert len(svc.buckets) == 1
    eng = svc.buckets.peek(tickets[0].fingerprint)
    assert eng.slots == 2 and eng.idle
    assert eng.trace_count == 3          # init1 / step / finish, once
    assert metrics.get("serving.retrace") - base == 3


def test_background_build_failure_rejects_tickets(poisson16):
    """A bucket build that raises on a builder thread rejects the
    queued tickets (BREAKDOWN + .error) instead of retrying forever
    or killing the scheduler."""
    cfg = _svc_cfg(extra="scaling=DIAGONAL_SYMMETRIC")  # engine refuses
    svc = SolveService(cfg)
    svc.start()
    try:
        t = svc.submit(poisson16, _rhs(poisson16, 20))
        assert t.wait(timeout=300)
        assert t.result.status_code == int(SolveStatus.BREAKDOWN)
        assert t.error is not None and "scaling" in str(t.error)
        assert svc.idle
    finally:
        svc.stop()


def test_sync_build_failure_rejects_tickets(poisson16):
    """The inline (no background thread) build-failure path matches
    the threaded one: tickets complete with BREAKDOWN, step() never
    raises, the queue never wedges."""
    svc = SolveService(_svc_cfg(extra="scaling=DIAGONAL_SYMMETRIC"))
    t = svc.submit(poisson16, _rhs(poisson16, 21))
    done = svc.step()                  # build fails inside this cycle
    assert t in done and t.done
    assert t.result.status_code == int(SolveStatus.BREAKDOWN)
    assert t.error is not None
    assert svc.idle and svc.step() == []


def test_submit_validates_rhs_length(poisson16):
    with pytest.raises(Exception, match="rhs length"):
        SolveService(_svc_cfg()).submit(poisson16, np.ones(7))


def test_service_background_thread(poisson16):
    """The async mode: submit from the caller thread, the scheduler
    thread completes the ticket."""
    svc = SolveService(_svc_cfg())
    svc.start()
    try:
        t = svc.submit(poisson16, _rhs(poisson16, 3))
        assert t.wait(timeout=300) and t.result.converged
        assert t.latency_s > 0
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# deadlines + admission control
# ---------------------------------------------------------------------------


def test_deadline_inflight_partial_never_hangs(poisson16):
    """Mid-flight expiry completes the ticket with DEADLINE_EXCEEDED
    and the current (partial) iterate; the bucket keeps serving."""
    cfg = _svc_cfg(extra="serving_chunk_iters=1, s:tolerance=1e-14")
    svc = SolveService(cfg)
    b = _rhs(poisson16, 4)
    miss0 = metrics.get("serving.deadline_miss")
    t = svc.submit(poisson16, b, tenant="late", deadline_s=1e9)
    svc.step()                       # admitted + one cycle
    assert not t.done
    t.deadline_t = 0.0               # force expiry at the next boundary
    svc.step()
    assert t.done
    assert t.result.status_code == int(SolveStatus.DEADLINE_EXCEEDED)
    assert t.result.status == "deadline_exceeded"
    assert not t.result.converged
    assert float(np.linalg.norm(np.asarray(t.result.x))) > 0  # partial
    assert metrics.get("serving.deadline_miss") - miss0 == 1
    assert svc.stats()["tenants"]["late"]["deadline_miss"] == 1
    # the bucket is not hung: the next request completes normally
    t2 = svc.submit(poisson16, b)
    svc.drain(timeout_s=300)
    assert t2.result.converged


def test_deadline_queued_expiry_rejects(poisson16):
    """A request that expires while still queued never touches a slot:
    it completes with DEADLINE_EXCEEDED and the initial iterate."""
    svc = SolveService(_svc_cfg())
    t = svc.submit(poisson16, _rhs(poisson16, 5), deadline_s=0.0)
    svc.step()
    assert t.done and t.result.iterations == 0
    assert t.result.status_code == int(SolveStatus.DEADLINE_EXCEEDED)
    assert float(np.linalg.norm(np.asarray(t.result.x))) == 0


def test_deadline_action_reject_returns_initial_iterate(poisson16):
    """serving_deadline_action=reject: an expired in-flight request
    completes with the initial iterate, not the partial one."""
    cfg = _svc_cfg(extra="serving_deadline_action=reject, "
                         "serving_chunk_iters=1, s:tolerance=1e-14")
    svc = SolveService(cfg)
    t = svc.submit(poisson16, _rhs(poisson16, 6), deadline_s=1e9)
    svc.step()
    t.deadline_t = 0.0
    svc.step()
    assert t.done
    assert t.result.status_code == int(SolveStatus.DEADLINE_EXCEEDED)
    assert float(np.linalg.norm(np.asarray(t.result.x))) == 0


def test_admission_control_queue_bound(poisson16):
    """serving_max_queue: over-budget submits complete immediately
    with OVERLOADED (the honest shed class — DEADLINE_EXCEEDED is
    reserved for admitted work that ran out of time) instead of
    growing the queue."""
    svc = SolveService(_svc_cfg(extra="serving_max_queue=1"))
    rej0 = metrics.get("serving.rejected")
    ovl0 = metrics.get("serving.shed.overload")
    t1 = svc.submit(poisson16, _rhs(poisson16, 7))
    t2 = svc.submit(poisson16, _rhs(poisson16, 8))
    assert not t1.done
    assert t2.done and t2.result.status_code == \
        int(SolveStatus.OVERLOADED)
    assert t2.result.status == "overloaded"
    assert metrics.get("serving.rejected") - rej0 == 1
    assert metrics.get("serving.shed.overload") - ovl0 == 1
    svc.drain(timeout_s=300)
    assert t1.result.converged


def test_deadline_status_in_fallback_grammar():
    """The new status plugs into the existing policy grammar (with the
    DEADLINE alias) and the capi status mapping."""
    pol = parse_fallback_policy("DEADLINE_EXCEEDED>retry")
    assert pol == {int(SolveStatus.DEADLINE_EXCEEDED):
                   [("retry", "")]}
    assert parse_fallback_policy("DEADLINE>retry") == pol
    assert status_string(SolveStatus.DEADLINE_EXCEEDED) == \
        "deadline_exceeded"
    assert to_amgx_status(SolveStatus.DEADLINE_EXCEEDED) == 3


# ---------------------------------------------------------------------------
# hierarchy cache
# ---------------------------------------------------------------------------


def test_cache_hit_routes_to_value_resetup(geo10):
    """The setup-routing proof: after the bucket exists, every
    repeat-pattern admit goes through the fused value-resetup (the
    0.43 s path) — the full-setup counter stays flat."""
    svc = SolveService(_svc_cfg(base=SERVING_CG))
    base = metrics.snapshot()
    t0 = svc.submit(geo10, _rhs(geo10, 0))
    svc.drain(timeout_s=300)
    mid = metrics.snapshot()
    assert mid["amg.setup.full"] - base["amg.setup.full"] == 1
    assert mid["serving.cache.miss"] - base["serving.cache.miss"] == 1
    # repeat-pattern, different-values traffic: hits + value-resetups
    tickets = [svc.submit(_shift(geo10, 0.2 * i), _rhs(geo10, i))
               for i in range(1, 4)]
    svc.drain(timeout_s=300)
    cur = metrics.snapshot()
    assert all(t.result.converged for t in tickets + [t0])
    assert cur["amg.setup.full"] == mid["amg.setup.full"]
    assert cur["amg.resetup.value"] - mid["amg.resetup.value"] >= 3
    assert cur["serving.cache.hit"] > mid["serving.cache.hit"]


def test_cache_eviction_by_bytes(poisson16):
    """A 1-byte budget keeps at most one idle bucket live: the second
    pattern evicts the first, with eviction counters + gauges."""
    ev0 = metrics.get("serving.cache.evictions")
    svc = SolveService(_svc_cfg(extra="serving_cache_bytes=1"))
    other = gallery.poisson("5pt", 12, 12).init()
    svc.submit(poisson16, _rhs(poisson16, 9))
    svc.drain(timeout_s=300)
    svc.submit(other, _rhs(other, 10))
    svc.drain(timeout_s=300)
    assert len(svc.buckets) == 1
    assert svc.buckets.evictions >= 1
    assert metrics.get("serving.cache.evictions") - ev0 >= 1
    assert metrics.get("serving.live_buckets") == 1


def test_cache_never_evicts_busy_or_newest_bucket():
    """Eviction skips buckets with in-flight slots AND the most
    recently used entry (a just-built oversized bucket must survive
    its own insertion); draining the busy one makes it evictable."""
    class E:
        def __init__(self, idle):
            self.idle = idle

    cache = HierarchyCache(budget_bytes=10, counters={},
                           can_evict=lambda e: e.idle)
    busy, idle = E(False), E(True)
    cache.put("busy", busy, nbytes=100)
    assert "busy" in cache            # newest: survives its own insert
    cache.put("idle", idle, nbytes=100)
    assert "busy" in cache and "idle" in cache   # over budget, all held
    busy.idle = True
    cache.evict_to_budget()           # now the oldest is evictable
    assert "busy" not in cache and "idle" in cache
    assert cache.evictions == 1


def test_solve_data_bytes_counts_unique_leaves(poisson16):
    slv = amgx.create_solver(Config.from_string(BATCHED_CG))
    slv.setup(poisson16)
    nb = solve_data_bytes(slv)
    # at least the fine matrix values must be accounted
    assert nb >= np.asarray(poisson16.values).nbytes
    # shared leaves count once
    leaf = jnp.ones(1000)
    assert solve_data_bytes([leaf, leaf]) == leaf.nbytes


# ---------------------------------------------------------------------------
# AOT warm paths
# ---------------------------------------------------------------------------


def test_aot_round_trip_zero_retrace(poisson16, tmp_path):
    """A fresh service against a warmed AOT store solves without a
    single engine trace (the restart story), with identical results."""
    cfg = _svc_cfg(extra=f"serving_aot_dir={tmp_path}")
    b = _rhs(poisson16, 11)
    exp0 = metrics.get("serving.aot.export")
    err0 = metrics.get("serving.aot.error")
    svc1 = SolveService(cfg)
    t1 = svc1.submit(poisson16, b)
    svc1.drain(timeout_s=300)
    assert metrics.get("serving.aot.export") - exp0 == 1
    assert metrics.get("serving.aot.error") - err0 == 0

    retr0 = metrics.get("serving.retrace")
    load0 = metrics.get("serving.aot.load")
    svc2 = SolveService(cfg)           # the "restarted process"
    t2 = svc2.submit(poisson16, b)
    svc2.drain(timeout_s=300)
    assert metrics.get("serving.retrace") - retr0 == 0
    assert metrics.get("serving.aot.load") - load0 == 1
    eng = svc2.buckets.peek(t2.fingerprint)
    assert eng.aot_warm and eng.trace_count == 0
    assert t2.result.iterations == t1.result.iterations
    np.testing.assert_array_equal(np.asarray(t2.result.x),
                                  np.asarray(t1.result.x))


# ---------------------------------------------------------------------------
# batcher satellites
# ---------------------------------------------------------------------------


def test_batcher_dispatches_oldest_first(poisson16):
    """drain() orders buckets by earliest pending submit, not by
    pending-map insertion: the longest-waiting request's bucket goes
    first even when a hot fingerprint entered the map before it."""
    rb = RequestBatcher(Config.from_string(BATCHED_CG), max_buckets=4)
    cold_A = gallery.poisson("5pt", 12, 12).init()
    hot = [rb.submit(poisson16, _rhs(poisson16, i)) for i in range(3)]
    cold = rb.submit(cold_A, _rhs(cold_A, 3))
    # simulate the cold request having waited longest
    cold.submit_t = hot[0].submit_t - 1.0
    rb.drain()
    assert all(r.done for r in hot + [cold])
    assert rb.dispatch_log[0][0] == cold.fingerprint
    assert rb.dispatch_log[1][0] == hot[0].fingerprint


def test_batcher_bytes_lru_bound(poisson16):
    """max_bucket_bytes bounds the solver store; evictions surface
    through the telemetry counter and the live_buckets property."""
    ev0 = metrics.get("batch.bucket_evictions")
    rb = RequestBatcher(Config.from_string(BATCHED_CG),
                        max_buckets=8, max_bucket_bytes=1)
    other = gallery.poisson("5pt", 12, 12).init()
    rb.submit(poisson16, _rhs(poisson16, 0))
    rb.drain()
    assert rb.live_buckets == 1
    rb.submit(other, _rhs(other, 1))
    rb.drain()
    assert rb.live_buckets == 1          # first bucket evicted
    assert rb.bucket_evictions >= 1
    assert metrics.get("batch.bucket_evictions") - ev0 >= 1
    assert metrics.get("batch.live_buckets") == 1


# ---------------------------------------------------------------------------
# capi surface
# ---------------------------------------------------------------------------


def test_capi_service_roundtrip(poisson16):
    from amgx_tpu import capi
    assert capi.AMGX_initialize() == 0
    rc, cfg_h = capi.AMGX_config_create(
        BATCHED_CG + ", serving_bucket_slots=2")
    assert rc == 0
    rc, rsrc_h = capi.AMGX_resources_create_simple(cfg_h)
    assert rc == 0
    rc, svc_h = capi.AMGX_service_create(rsrc_h, "dDDI", cfg_h)
    assert rc == 0
    rc, m_h = capi.AMGX_matrix_create(rsrc_h, "dDDI")
    rc, b_h = capi.AMGX_vector_create(rsrc_h, "dDDI")
    rc, x_h = capi.AMGX_vector_create(rsrc_h, "dDDI")
    ro = np.asarray(poisson16.row_offsets)
    ci = np.asarray(poisson16.col_indices)
    v = np.asarray(poisson16.values)
    assert capi.AMGX_matrix_upload_all(
        m_h, poisson16.num_rows, v.size, 1, 1, ro, ci, v, None) == 0
    b = _rhs(poisson16, 12)
    assert capi.AMGX_vector_upload(b_h, b.size, 1, b) == 0
    rc, tkt = capi.AMGX_service_submit(svc_h, m_h, b_h, "acme", None)
    assert rc == 0
    rc, done, st = capi.AMGX_service_ticket_status(tkt)
    assert rc == 0 and done == 0 and st is None
    rc, n_done = capi.AMGX_service_drain(svc_h, 300)
    assert rc == 0 and n_done == 1
    rc, done, st = capi.AMGX_service_ticket_status(tkt)
    assert rc == 0 and done == 1 and st == 0      # AMGX_SOLVE_SUCCESS
    assert capi.AMGX_service_ticket_download(tkt, x_h) == 0
    rc, x = capi.AMGX_vector_download(x_h)
    assert rc == 0 and x.shape == (poisson16.num_rows,)
    rc, stats = capi.AMGX_service_stats(svc_h)
    assert rc == 0 and stats["tenants"]["acme"]["completed"] == 1
    assert capi.AMGX_service_ticket_destroy(tkt) == 0
    assert capi.AMGX_service_destroy(svc_h) == 0


# ---------------------------------------------------------------------------
# fault tolerance: journal, checkpoints, crash recovery
# ---------------------------------------------------------------------------


def test_checkpoint_restart_resumes_bit_identical(poisson16, tmp_path):
    """THE recovery acceptance: a service killed mid-flight is
    replaced by a successor that replays the journal and resumes the
    checkpointed solve — reaching a final iterate BIT-IDENTICAL to an
    uninterrupted run, at the same iteration count."""
    b = _rhs(poisson16, 30)
    kr = (f"serving_journal_dir={tmp_path}, serving_checkpoint_cycles=1,"
          " serving_chunk_iters=1, s:tolerance=1e-12")
    ref = SolveService(_svc_cfg(
        extra="serving_chunk_iters=1, s:tolerance=1e-12"))
    rt = ref.submit(poisson16, b)
    ref.drain(timeout_s=300)
    victim = SolveService(_svc_cfg(extra=kr))
    vt = victim.submit(poisson16, b, tenant="acme", deadline_s=1e6,
                       request_key="kr-0")
    for _ in range(4):               # build + a few cycles, then die
        victim.step()
    assert not vt.done               # genuinely mid-flight
    del victim
    rep0 = metrics.get("serving.recovery.replayed")
    res0 = metrics.get("serving.recovery.resumed")
    succ = SolveService(_svc_cfg(extra=kr))   # journal replays here
    assert metrics.get("serving.recovery.replayed") - rep0 == 1
    done = succ.drain(timeout_s=300)
    assert len(done) == 1 and done[0].done
    assert metrics.get("serving.recovery.resumed") - res0 == 1
    assert done[0].result.iterations == rt.result.iterations
    np.testing.assert_array_equal(np.asarray(done[0].result.x),
                                  np.asarray(rt.result.x))
    # deadline survived the restart (remaining budget re-anchored)
    assert done[0].result.converged
    assert succ.stats()["journal_pending"] == 0


def test_submit_request_key_idempotent(poisson16, tmp_path):
    """The idempotency satellite: a retried submit with the same
    request_key returns the LIVE ticket while in flight, and after
    completion (even across a restart) a fresh ticket completed from
    the journaled result — never a second enqueue."""
    b = _rhs(poisson16, 31)
    cfg = _svc_cfg(extra=f"serving_journal_dir={tmp_path}")
    svc = SolveService(cfg)
    ded0 = metrics.get("serving.dedupe")
    t1 = svc.submit(poisson16, b, request_key="abc")
    t2 = svc.submit(poisson16, b, request_key="abc")
    assert t2 is t1                  # live dedupe: the same ticket
    assert metrics.get("serving.dedupe") - ded0 == 1
    svc.drain(timeout_s=300)
    assert t1.result.converged
    # across a "restart": the journaled result answers the retry
    svc2 = SolveService(cfg)
    t3 = svc2.submit(poisson16, b, request_key="abc")
    assert t3.done and t3 is not t1
    assert metrics.get("serving.dedupe") - ded0 == 2
    np.testing.assert_array_equal(np.asarray(t3.result.x),
                                  np.asarray(t1.result.x))
    assert svc2.idle                 # nothing was enqueued


def test_journal_corrupt_record_dropped_not_wedged(poisson16, tmp_path):
    """A torn-write-corrupted journal record is dropped (and counted)
    at replay; the records around it still recover — corruption can
    cost one request's durability, never the service."""
    cfg = _svc_cfg(extra=f"serving_journal_dir={tmp_path},"
                         " serving_chunk_iters=1, s:tolerance=1e-12")
    svc = SolveService(cfg)
    svc.submit(poisson16, _rhs(poisson16, 32))        # clean pattern
    with faultinject.inject("journal_corrupt", fires=1):
        svc.submit(poisson16, _rhs(poisson16, 33))    # corrupt record
    svc.submit(poisson16, _rhs(poisson16, 34))        # clean record
    del svc
    jc0 = metrics.get("serving.recovery.journal_corrupt")
    rep0 = metrics.get("serving.recovery.replayed")
    succ = SolveService(cfg)
    assert metrics.get("serving.recovery.journal_corrupt") - jc0 == 1
    assert metrics.get("serving.recovery.replayed") - rep0 == 2
    done = succ.drain(timeout_s=300)
    assert len(done) == 2 and all(t.result.converged for t in done)
    assert succ.idle


def test_hierarchy_store_restart_zero_full_setups(geo10, tmp_path):
    """The persistent-hierarchy acceptance: a restarted service with a
    warm hierarchy store + AOT store services its first request via
    snapshot load + structure-reuse rebuild + AOT executables — ZERO
    full AMG setups, ZERO engine retraces, identical results."""
    cfg = _svc_cfg(base=SERVING_CG,
                   extra=f"serving_hierarchy_dir={tmp_path}/h,"
                         f" serving_aot_dir={tmp_path}/a")
    b = _rhs(geo10, 35)
    hs0 = metrics.get("serving.recovery.hstore_save")
    svc1 = SolveService(cfg)
    t1 = svc1.submit(geo10, b)
    svc1.drain(timeout_s=300)
    assert metrics.get("serving.recovery.hstore_save") - hs0 == 1
    full0 = metrics.get("amg.setup.full")
    rest0 = metrics.get("amg.setup.restored")
    retr0 = metrics.get("serving.retrace")
    svc2 = SolveService(cfg)           # the "restarted process"
    t2 = svc2.submit(geo10, b)
    svc2.drain(timeout_s=300)
    assert metrics.get("amg.setup.full") - full0 == 0
    assert metrics.get("amg.setup.restored") - rest0 == 1
    assert metrics.get("serving.retrace") - retr0 == 0
    eng = svc2.buckets.peek(t2.fingerprint)
    assert eng.hier_restored and eng.aot_warm
    np.testing.assert_array_equal(np.asarray(t2.result.x),
                                  np.asarray(t1.result.x))


# ---------------------------------------------------------------------------
# lock split (ROADMAP 3e)
# ---------------------------------------------------------------------------


def test_submit_never_waits_for_device_cycle(poisson16, monkeypatch):
    """The lock-split contention proof: while a scheduler cycle is
    blocked inside device stepping, submit() still completes — it
    contends only with bookkeeping, never with a cycle of device
    work (ROADMAP 3e)."""
    svc = SolveService(_svc_cfg(
        extra="serving_chunk_iters=1, s:tolerance=1e-14"))
    t1 = svc.submit(poisson16, _rhs(poisson16, 36))
    svc.step()                          # build + admit
    assert not t1.done
    in_step, release = threading.Event(), threading.Event()
    orig_step = BucketEngine.step

    def blocked_step(self):
        in_step.set()
        assert release.wait(30)
        return orig_step(self)

    monkeypatch.setattr(BucketEngine, "step", blocked_step)
    th = threading.Thread(target=svc.step)
    th.start()
    try:
        assert in_step.wait(30)         # cycle is inside device work
        t0 = time.monotonic()
        t2 = svc.submit(poisson16, _rhs(poisson16, 37))
        dt = time.monotonic() - t0
        assert th.is_alive()            # the cycle is STILL blocked
        assert not t2.done and dt < 5.0
    finally:
        release.set()
        th.join()
    monkeypatch.setattr(BucketEngine, "step", orig_step)
    svc.drain(timeout_s=300)
    assert t1.result.converged and t2.result.converged


# ---------------------------------------------------------------------------
# backpressure & load shedding
# ---------------------------------------------------------------------------


def test_shed_deadline_unmeetable_overloaded(poisson16):
    """serving_shed_policy=deadline: once the live estimator is
    trained, a request whose deadline cannot be met at the current
    queue depth is shed OVERLOADED at submit — before it ever queues."""
    svc = SolveService(_svc_cfg(
        extra="serving_shed_policy=deadline"))
    warm = svc.submit(poisson16, _rhs(poisson16, 38))
    svc.drain(timeout_s=300)
    assert warm.result.converged       # estimator now trained
    svc._exec_recent.extend([0.05, 0.05, 0.05])
    shd0 = metrics.get("serving.shed.deadline")
    t = svc.submit(poisson16, _rhs(poisson16, 39), deadline_s=1e-4)
    assert t.done
    assert t.result.status_code == int(SolveStatus.OVERLOADED)
    assert metrics.get("serving.shed.deadline") - shd0 == 1
    # a generous deadline is admitted and served normally
    t2 = svc.submit(poisson16, _rhs(poisson16, 40), deadline_s=1e6)
    svc.drain(timeout_s=300)
    assert t2.result.converged


def test_shed_tenant_quota(poisson16):
    """serving_tenant_quota: a tenant at its live-request quota has
    further submits shed OVERLOADED; other tenants are unaffected."""
    svc = SolveService(_svc_cfg(extra="serving_tenant_quota=1"))
    q0 = metrics.get("serving.shed.quota")
    t1 = svc.submit(poisson16, _rhs(poisson16, 41), tenant="greedy")
    t2 = svc.submit(poisson16, _rhs(poisson16, 42), tenant="greedy")
    t3 = svc.submit(poisson16, _rhs(poisson16, 43), tenant="modest")
    assert not t1.done and not t3.done
    assert t2.done and t2.result.status == "overloaded"
    assert metrics.get("serving.shed.quota") - q0 == 1
    assert svc.stats()["tenants"]["greedy"]["shed"] == 1
    svc.drain(timeout_s=300)
    assert t1.result.converged and t3.result.converged


# ---------------------------------------------------------------------------
# supervision, quarantine & the service-level chaos scenarios
# ---------------------------------------------------------------------------


def test_step_crash_quarantines_and_resumes_bit_identical(poisson16):
    """A device-step exception mid-flight quarantines the bucket: the
    in-flight slot requeues with its LIVE state, the rebuilt bucket
    resumes it, and the final iterate is bit-identical to a run that
    never crashed (default policy: STEP_FAILED>requeue)."""
    extra = "serving_chunk_iters=1, s:tolerance=1e-12"
    ref = SolveService(_svc_cfg(extra=extra))
    b = _rhs(poisson16, 44)
    rt = ref.submit(poisson16, b)
    ref.drain(timeout_s=300)
    svc = SolveService(_svc_cfg(extra=extra))
    q0 = metrics.get("serving.recovery.quarantined")
    rq0 = metrics.get("serving.recovery.requeued")
    t = svc.submit(poisson16, b)
    svc.step()                          # build + admit + first cycle
    with faultinject.inject("step_crash", fires=1):
        svc.step()                      # crashes -> quarantine
    assert metrics.get("serving.recovery.quarantined") - q0 == 1
    assert metrics.get("serving.recovery.requeued") - rq0 == 1
    assert not t.done
    svc.drain(timeout_s=300)
    assert t.result.converged
    assert t.result.iterations == rt.result.iterations
    np.testing.assert_array_equal(np.asarray(t.result.x),
                                  np.asarray(rt.result.x))


def test_wedged_bucket_detected_and_recovered(poisson16):
    """The supervisor satellite: a bucket whose progress heartbeat
    flatlines (scripted step_wedge — cycles run, iteration counters
    frozen) is quarantined after serving_supervisor_cycles and its
    work requeued; the scheduler never hangs."""
    svc = SolveService(_svc_cfg(
        extra="serving_supervisor_cycles=2, serving_chunk_iters=1,"
              " s:tolerance=1e-12"))
    q0 = metrics.get("serving.recovery.quarantined")
    t = svc.submit(poisson16, _rhs(poisson16, 45))
    svc.step()
    with faultinject.inject("step_wedge", fires=4):
        for _ in range(5):
            svc.step()
    assert metrics.get("serving.recovery.quarantined") - q0 >= 1
    svc.drain(timeout_s=300)
    assert t.done and t.result.converged


def test_build_crash_retry_backoff_converges(poisson16):
    """BUILD_FAILED>retry_backoff: a crashed bucket build leaves its
    tickets queued behind a bounded exponential backoff; the retry
    succeeds and the tickets converge (vs the default reject)."""
    svc = SolveService(_svc_cfg(
        extra="serving_fault_policy=BUILD_FAILED>retry_backoff,"
              " serving_retry_backoff_s=0.01"))
    r0 = metrics.get("serving.recovery.build_retries")
    with faultinject.inject("build_crash", fires=1):
        t = svc.submit(poisson16, _rhs(poisson16, 46))
        svc.drain(timeout_s=300)
    assert t.result.converged
    assert metrics.get("serving.recovery.build_retries") - r0 == 1


def test_build_crash_attempts_bounded_then_reject(poisson16):
    """An always-crashing build cannot retry forever: after
    serving_retry_max_attempts the tickets reject with BREAKDOWN and
    the error attached — bounded, terminal, no hang."""
    svc = SolveService(_svc_cfg(
        extra="serving_fault_policy=BUILD_FAILED>retry_backoff,"
              " serving_retry_backoff_s=0.001,"
              " serving_retry_max_attempts=2"))
    with faultinject.inject("build_crash", fires=None):
        t = svc.submit(poisson16, _rhs(poisson16, 47))
        svc.drain(timeout_s=60)
    assert t.done
    assert t.result.status_code == int(SolveStatus.BREAKDOWN)
    assert isinstance(t.error, faultinject.ChaosInjected)
    assert svc.idle


def test_step_crash_attempts_bounded_then_reject(poisson16):
    """A bucket whose device step crashes EVERY cycle cannot loop
    quarantine->rebuild->quarantine forever: a successful rebuild does
    not reset the fault-attempt counter (only a terminal completion
    does), so serving_retry_max_attempts bounds STEP_FAILED too and
    the tickets reject terminally."""
    svc = SolveService(_svc_cfg(
        extra="serving_retry_max_attempts=1, serving_chunk_iters=1"))
    with faultinject.inject("step_crash", fires=None):
        t = svc.submit(poisson16, _rhs(poisson16, 51))
        svc.drain(timeout_s=120)
    assert t.done
    assert t.result.status_code == int(SolveStatus.BREAKDOWN)
    assert svc.idle
    # ...and a healthy completion clears the counter: the same
    # fingerprint serves normally once the fault is gone
    t2 = svc.submit(poisson16, _rhs(poisson16, 52))
    svc.drain(timeout_s=300)
    assert t2.result.converged


def test_journal_corrupt_pattern_self_heals(poisson16, tmp_path):
    """A corrupt PATTERN file (shared across a fingerprint's records)
    is deleted at the failed replay read, so the next submit rewrites
    it — corruption cannot permanently poison a fingerprint's
    durability."""
    cfg = _svc_cfg(extra=f"serving_journal_dir={tmp_path},"
                         " serving_chunk_iters=1, s:tolerance=1e-12")
    svc = SolveService(cfg)
    with faultinject.inject("journal_corrupt", fires=1):
        svc.submit(poisson16, _rhs(poisson16, 53))  # pattern write torn
    del svc
    succ = SolveService(cfg)          # replay drops the corrupt record
    assert succ.stats()["journal_pending"] == 0
    # durability restored: a new journaled request round-trips a crash
    t = succ.submit(poisson16, _rhs(poisson16, 54))
    for _ in range(3):
        succ.step()
    assert not t.done
    del succ
    succ2 = SolveService(cfg)
    done = succ2.drain(timeout_s=300)
    assert len(done) == 1 and done[0].result.converged


def test_engine_admit_occupied_slot_still_raises(poisson16):
    """Direct BucketEngine users keep the strict occupied-slot guard:
    the scheduler's reservation protocol (unique occupant objects)
    must not have weakened the default-occupant path."""
    from amgx_tpu.errors import BadParametersError
    eng = BucketEngine(_svc_cfg(), "default", poisson16, slots=2,
                       chunk=4, dtype=np.float64)
    eng.admit(0, poisson16, _rhs(poisson16, 55))
    with pytest.raises(BadParametersError, match="occupied"):
        eng.admit(0, poisson16, _rhs(poisson16, 56))


def test_bucket_failure_status_does_not_poison_neighbors(poisson16):
    """Status interplay inside a chunked bucket: a slot that hits
    NAN_DETECTED mid-chunk (injected SpMV NaN baked into the bucket's
    traces) finalizes with that status while a neighbor slot in the
    SAME bucket still finalizes CONVERGED — per-slot statuses are
    independent, and the bucket keeps serving afterwards."""
    with faultinject.inject("spmv_nan", iteration=3, fires=None):
        # armed at build: the engine's chunked step trace carries the
        # iteration-3 corruption for the bucket's lifetime
        svc = SolveService(_svc_cfg(extra="serving_chunk_iters=2"))
        bad = svc.submit(poisson16, _rhs(poisson16, 48))
        zero = svc.submit(poisson16, np.zeros(poisson16.num_rows))
        svc.drain(timeout_s=300)
    assert bad.done
    assert bad.result.status_code == int(SolveStatus.NAN_DETECTED)
    assert not bad.result.converged
    # the all-zero rhs converges at iteration 0 — before the fault
    # iteration — in the SAME poisoned bucket
    assert zero.done and zero.result.converged
    assert zero.result.iterations == 0
    # and the bucket is not poisoned for the service: a fresh service
    # (clean trace epoch) serves the same pattern fine
    svc2 = SolveService(_svc_cfg(extra="serving_chunk_iters=2"))
    ok = svc2.submit(poisson16, _rhs(poisson16, 48))
    svc2.drain(timeout_s=300)
    assert ok.result.converged


def test_clock_skew_deadlines_stay_terminal(poisson16):
    """Chaos: with the service clock skewed forward, deadline
    bookkeeping stays consistent (submit and expiry read the same
    skewed clock) and every ticket still terminates."""
    with faultinject.inject("clock_skew", value=600.0, fires=None):
        svc = SolveService(_svc_cfg())
        t1 = svc.submit(poisson16, _rhs(poisson16, 49), deadline_s=1e9)
        t2 = svc.submit(poisson16, _rhs(poisson16, 50), deadline_s=0.0)
        svc.drain(timeout_s=300)
    assert t1.done and t1.result.converged
    assert t2.done and t2.result.status_code == \
        int(SolveStatus.DEADLINE_EXCEEDED)


# ---------------------------------------------------------------------------
# telemetry catalog + bench smoke
# ---------------------------------------------------------------------------


def test_serving_metrics_declared():
    snap = metrics.snapshot()
    for name in ("serving.requests", "serving.completed",
                 "serving.rejected", "serving.deadline_miss",
                 "serving.cache.hit", "serving.cache.miss",
                 "serving.cache.evictions", "serving.retrace",
                 "serving.aot.export", "serving.aot.load",
                 "serving.aot.error", "batch.bucket_evictions",
                 # fault-tolerance layer
                 "serving.recovery.checkpoints",
                 "serving.recovery.replayed",
                 "serving.recovery.resumed",
                 "serving.recovery.restart_fresh",
                 "serving.recovery.journal_corrupt",
                 "serving.recovery.quarantined",
                 "serving.recovery.salvaged",
                 "serving.recovery.requeued",
                 "serving.recovery.build_retries",
                 "serving.recovery.hstore_save",
                 "serving.recovery.hstore_load",
                 "serving.recovery.hstore_skip",
                 "serving.recovery.hstore_error",
                 "serving.dedupe", "serving.shed.overload",
                 "serving.shed.deadline", "serving.shed.quota",
                 "amg.setup.restored", "resilience.config_fallback"):
        assert name in snap
    assert "serving.exec_s" in metrics.HISTOGRAMS
