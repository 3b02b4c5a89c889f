"""Online config autotuner tests (amgx_tpu/serving/autotune.py): the
diagnostics->candidate mapping shared with the convergence doctor, the
per-fingerprint exec-time estimator (mixed-size traffic must not shed
the small tenant on the big tenant's median), the default-off inertness
contract (autotune=0 builds no tuner, applies no overlay, changes no
trace counts), shadow isolation (a saturated service runs ZERO shadow
solves and the search introduces no deadline misses), chaos absorption
(an injected shadow-solve crash is counted + backed off, never a failed
ticket), the promote path end to end (mistuned fingerprint converges
strictly faster after promotion), restart durability (the tuned config
survives via the hstore and serves from the first request with zero
full setups), drain quiescing, and the fleet drain_replica tuned-config
handoff. No reference analog — AMGX has no online tuner; the invariants
are the service's own contracts."""
import os
import time

import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.presets import BATCHED_CG
from amgx_tpu.resilience import faultinject
from amgx_tpu.resilience.status import SolveStatus
from amgx_tpu.serving import FleetRouter, SolveService
from amgx_tpu.telemetry import metrics
from amgx_tpu.telemetry.diagnostics import (HINT_CORRECTION,
                                            HINT_SMOOTHER,
                                            suggest_config_deltas)

amgx.initialize()


@pytest.fixture(scope="module")
def geo10():
    return gallery.poisson("7pt", 10, 10, 10).init()


@pytest.fixture(autouse=True)
def _noise_free_shadow_clock(monkeypatch):
    """Shadow solves here are 10^3 systems whose warm solve wall (3-8
    ms) is fixed overhead, not iterations: 21 against 12 iterations
    measured 4.1 against 4.8 ms as often as the reverse, so the
    promote gate's `tuned wall <= baseline wall` was a coin toss on a
    busy machine (it only looked stable while the native library did
    not build and the Python-fallback hierarchy gave a 37-iteration
    baseline). These tests are about the search and promote LOGIC, so
    the measured wall is replaced by a noise-free clock proportional
    to the iterations the shadow solve really took."""
    from amgx_tpu.serving.autotune import ConfigAutotuner
    real = ConfigAutotuner._shadow_solve

    def shadow(self, fp, rec, deltas, label):
        m = real(self, fp, rec, deltas, label)
        if m is not None:
            m["wall_s"] = 1e-4 * m["iters"]
            m["score"] = m["iters"] * m["wall_s"]
        return m

    monkeypatch.setattr(ConfigAutotuner, "_shadow_solve", shadow)


def _rhs(A, seed=0):
    return np.random.default_rng(seed).standard_normal(A.num_rows)


# a deliberately mistuned config: overdamped BLOCK_JACOBI (the
# convergence-doctor demo's classic) on the aggregation path — the
# diagnostics probe attributes it, the smoother/relaxation candidates
# fix it
MISTUNED = (BATCHED_CG +
            ", amg:smoother(sm2)=BLOCK_JACOBI, sm2:max_iters=1,"
            " sm2:relaxation_factor=0.15,"
            " serving_bucket_slots=2, serving_chunk_iters=8")


def _at_cfg(extra=""):
    return Config.from_string(
        MISTUNED + ", autotune=1, autotune_hot_requests=4,"
        " autotune_hot_exec_share=0.0"
        + (", " + extra if extra else ""))


def _heat(svc, A, n=5, seed0=0):
    """Submit + drain `n` same-fingerprint requests (makes the
    fingerprint hot without letting the tuner act: drain quiesces)."""
    tix = [svc.submit(A, _rhs(A, seed0 + i)) for i in range(n)]
    svc.drain(timeout_s=600)
    assert all(t.done for t in tix)
    return tix


def _search(svc, max_steps=16):
    """Idle scheduler cycles: each may run one shadow solve."""
    for _ in range(max_steps):
        svc.step()
        if svc.stats()["autotune"]["promoted"]:
            break


# ---------------------------------------------------------------------------
# diagnostics -> candidate mapping (shared with the convergence doctor)
# ---------------------------------------------------------------------------


def test_suggest_config_deltas_rules():
    diag = {"levels": [
        {"level": 0, "smoother_effectiveness": 0.95,
         "correction_reduction": 1.5}],
        "bottleneck_level": 0,
        "asymptotic_convergence_factor": 0.9}
    out = suggest_config_deltas(diag)
    knobs = [s["knob"] for s in out]
    assert knobs == ["smoother_swap", "relaxation", "strength",
                     "interp", "cycle"]
    by = {s["knob"]: s for s in out}
    # doctor hints ride the suggestions they came from
    assert by["smoother_swap"]["hint"] == HINT_SMOOTHER
    assert by["relaxation"]["hint"] == HINT_SMOOTHER
    assert by["strength"]["hint"] == HINT_CORRECTION
    assert by["cycle"]["hint"] is None
    assert by["smoother_swap"]["deltas"] == [
        {"param": "smoother", "value": "JACOBI_L1"},
        {"param": "relaxation_factor", "value": 0.9}]
    assert by["cycle"]["deltas"] == [{"param": "cycle", "value": "W"}]
    # comfortable convergence -> the precision wall lever, alone
    fast = {"levels": [{"level": 0, "smoother_effectiveness": 0.2,
                        "correction_reduction": 0.5}],
            "bottleneck_level": 0,
            "asymptotic_convergence_factor": 0.2}
    assert [s["knob"] for s in suggest_config_deltas(fast)] \
        == ["precision"]
    # no diagnostics -> no candidates (the tuner then retires the
    # search instead of guessing)
    assert suggest_config_deltas(None) == []
    assert suggest_config_deltas({}) == []


def test_doctor_output_comes_from_shared_mapping():
    """The doctor's printed sentences are exactly the mapping's hint
    strings, deduplicated in rule order — refactor-proven by deriving
    them the way examples/convergence_doctor.py now does."""
    diag = {"levels": [
        {"level": 1, "smoother_effectiveness": 0.9,
         "correction_reduction": 1.3}],
        "bottleneck_level": 1,
        "asymptotic_convergence_factor": 0.95}
    hints = []
    for s in suggest_config_deltas(diag):
        if s["hint"] and s["hint"] not in hints:
            hints.append(s["hint"])
    assert hints == [HINT_SMOOTHER, HINT_CORRECTION]


# ---------------------------------------------------------------------------
# per-fingerprint exec-time estimator (satellite: mixed-size traffic)
# ---------------------------------------------------------------------------


def test_estimator_prefers_fingerprint_window(geo10):
    svc = SolveService(Config.from_string(
        BATCHED_CG + ", serving_bucket_slots=2,"
        " serving_chunk_iters=8, serving_shed_policy=deadline"))
    t0 = svc.submit(geo10, _rhs(geo10))
    svc.drain(timeout_s=600)
    assert t0.result.converged
    fp = t0.fingerprint
    # a co-resident big tenant polluted the GLOBAL window...
    svc._exec_recent.clear()
    svc._exec_recent.extend([5.0] * 10)
    # ...but this fingerprint's own window is trained and tight
    svc._exec_fp[fp].clear()
    svc._exec_fp[fp].extend([0.01] * 8)
    with svc._lock:
        est_fp = svc._estimate_latency_s(fp)
        est_global = svc._estimate_latency_s()
    assert est_fp < 0.1 < est_global


def test_small_tenant_not_shed_on_big_tenants_median(geo10):
    """The regression the satellite demands: under mixed-size traffic
    the small tenant's tight deadline used to be judged on the global
    median the big tenant dominates — now it is judged on its own
    fingerprint's history and admitted."""
    svc = SolveService(Config.from_string(
        BATCHED_CG + ", serving_bucket_slots=2,"
        " serving_chunk_iters=8, serving_shed_policy=deadline"))
    t0 = svc.submit(geo10, _rhs(geo10))
    svc.drain(timeout_s=600)
    fp = t0.fingerprint
    svc._exec_recent.clear()
    svc._exec_recent.extend([5.0] * 10)   # big tenant's medians
    svc._exec_fp[fp].clear()
    svc._exec_fp[fp].extend([0.01] * 8)   # the small tenant's own
    base_shed = metrics.get("serving.shed.deadline")
    t1 = svc.submit(geo10, _rhs(geo10, 1), deadline_s=1.0)
    assert not (t1.done and t1.result.status_code
                == int(SolveStatus.OVERLOADED))
    svc.drain(timeout_s=600)
    assert t1.result.converged
    assert metrics.get("serving.shed.deadline") == base_shed
    # an untrained fingerprint still falls back to the global window:
    # the same deadline against the polluted median sheds
    other = gallery.poisson("5pt", 12, 12).init()
    t2 = svc.submit(other, _rhs(other), deadline_s=1.0)
    assert t2.done and t2.result.status_code \
        == int(SolveStatus.OVERLOADED)
    assert metrics.get("serving.shed.deadline") == base_shed + 1


# ---------------------------------------------------------------------------
# default-off inertness (autotune=0)
# ---------------------------------------------------------------------------


def test_autotune_off_is_inert(geo10):
    base = {k: metrics.get(k) for k in (
        "autotune.hot", "autotune.shadow.runs",
        "autotune.overlay.applied", "autotune.promotions")}
    svc = SolveService(Config.from_string(MISTUNED))
    assert svc._tuner is None
    tix = _heat(svc, geo10, n=5)
    for _ in range(8):
        svc.step()                       # idle cycles: no tuner tick
    for k, v in base.items():
        assert metrics.get(k) == v, k
    # the engine was built from the SERVICE config object — no clone,
    # no overlay — and a tuner-enabled service that never promoted
    # solves bit-identically
    svc2 = SolveService(_at_cfg("autotune_hot_requests=1000"))
    tix2 = _heat(svc2, geo10, n=5)
    for a, b in zip(tix, tix2):
        assert a.result.iterations == b.result.iterations
        np.testing.assert_array_equal(np.asarray(a.result.x),
                                      np.asarray(b.result.x))
    eng = svc.buckets.peek(tix[0].fingerprint)
    eng2 = svc2.buckets.peek(tix2[0].fingerprint)
    assert eng.trace_count == eng2.trace_count


# ---------------------------------------------------------------------------
# shadow isolation + chaos absorption
# ---------------------------------------------------------------------------


def test_saturated_service_runs_no_shadows(geo10):
    """Shadow solves only ever occupy capacity production is not
    using: while the queue is non-empty not one shadow runs, and the
    search adds zero deadline misses to admitted traffic."""
    svc = SolveService(_at_cfg())
    base_runs = metrics.get("autotune.shadow.runs")
    base_miss = metrics.get("serving.deadline_miss")
    # a burst deeper than one bucket's slots: the queue stays
    # non-empty across many scheduler cycles
    tix = [svc.submit(geo10, _rhs(geo10, i)) for i in range(8)]
    saturated_cycles = 0
    for _ in range(400):
        with svc._lock:
            queued = len(svc._queue)
        svc.step()
        if queued:
            saturated_cycles += 1
            assert metrics.get("autotune.shadow.runs") == base_runs
        if svc.idle:
            break
    assert saturated_cycles >= 1          # the burst did queue
    assert all(t.done and t.result.converged for t in tix)
    assert metrics.get("serving.deadline_miss") == base_miss


def test_shadow_crash_absorbed_and_backed_off(geo10):
    """Chaos drill: an injected shadow-solve crash is counted and
    backs the fingerprint's search off — no ticket fails, the service
    stays serviceable, and the search recovers after the backoff."""
    svc = SolveService(_at_cfg())
    tix = _heat(svc, geo10, n=5)
    assert all(t.result.converged for t in tix)
    base_err = metrics.get("autotune.shadow.errors")
    with faultinject.inject("shadow_crash", fires=1):
        svc.step()                        # the baseline shadow crashes
    assert metrics.get("autotune.shadow.errors") == base_err + 1
    snap = svc.stats()["autotune"]["fingerprints"]
    rec = next(iter(snap.values()))
    assert rec["errors"] == 1 and rec["phase"] in ("hot", "search")
    # production is untouched: every ticket still terminal-converged,
    # and new traffic solves
    assert all(t.done and t.result.converged for t in tix)
    t2 = svc.submit(geo10, _rhs(geo10, 50))
    svc.drain(timeout_s=600)
    assert t2.result.converged
    # backoff elapses -> the search resumes and completes
    time.sleep(0.3)
    _search(svc)
    assert svc.stats()["autotune"]["promoted"] == 1


def test_second_shadow_crash_retires_search(geo10):
    svc = SolveService(_at_cfg())
    _heat(svc, geo10, n=5)
    with faultinject.inject("shadow_crash", fires=None):
        svc.step()
        time.sleep(0.3)
        svc.step()
    snap = svc.stats()["autotune"]["fingerprints"]
    rec = next(iter(snap.values()))
    assert rec["phase"] == "exhausted" and rec["errors"] == 2


# ---------------------------------------------------------------------------
# the promote path + drain quiesce
# ---------------------------------------------------------------------------


def test_promotion_fixes_mistuned_fingerprint(geo10):
    svc = SolveService(_at_cfg())
    base_runs = metrics.get("autotune.shadow.runs")
    tix = _heat(svc, geo10, n=5)
    pre = int(np.median([t.result.iterations for t in tix]))
    # drain() quiesced the tuner: not one shadow ran during it
    assert metrics.get("autotune.shadow.runs") == base_runs
    assert not svc._draining and not svc._tuner._quiesced
    _search(svc)
    snap = svc.stats()["autotune"]
    assert snap["promoted"] == 1
    rec = next(iter(snap["fingerprints"].values()))
    assert rec["phase"] == "promoted" and rec["overlay"]
    base_applied = metrics.get("autotune.overlay.applied")
    t2 = svc.submit(geo10, _rhs(geo10, 90))
    svc.drain(timeout_s=600)
    assert t2.result.converged
    assert metrics.get("autotune.overlay.applied") == base_applied + 1
    assert t2.result.iterations < pre


def test_fleet_drain_hands_off_tuned_config(tmp_path):
    """PR-17's rolling-restart path carries the tuner state: draining
    a replica hands its promoted overlays to the surviving replica
    its fingerprints rehome to, live + persisted in the adopter's
    hstore."""
    cfg = Config.from_string(
        MISTUNED + ", autotune=1, fleet_replicas=2,"
        f" serving_hierarchy_dir={tmp_path}/hier")
    fleet = FleetRouter.build(cfg, 2)
    rids = list(fleet.replicas)
    fp = "handoff-test-fingerprint/float64"
    state = {"deltas": [{"param": "relaxation_factor", "value": 0.9}],
             "knob": "relaxation", "trace": "tr-1"}
    fleet.replicas[rids[0]]._tuner.adopt(fp, state)
    base = metrics.get("autotune.handoffs")
    fleet.drain_replica(rids[0])
    assert metrics.get("autotune.handoffs") == base + 1
    adopted = fleet.replicas[rids[1]]._tuner.overlay_for(fp)
    assert adopted == state["deltas"]
    # ... and the adopter persisted it: ITS hstore resolves the
    # overlay for a fresh service too
    assert fleet.replicas[rids[1]].hstore.load_tuned(fp)["deltas"] \
        == state["deltas"]


# ---------------------------------------------------------------------------
# restart durability (extends the PR-11 recovery-guarantees table)
# ---------------------------------------------------------------------------


def test_tuned_config_survives_restart_zero_full_setups(geo10,
                                                        tmp_path):
    dirs = (f"serving_hierarchy_dir={tmp_path}/hier,"
            f" serving_journal_dir={tmp_path}/journal")
    svc = SolveService(_at_cfg(dirs))
    _heat(svc, geo10, n=5)
    _search(svc)
    assert svc.stats()["autotune"]["promoted"] == 1
    # one tuned build in THIS incarnation persists the tuned
    # hierarchy structure under the tuned config's keys
    t1 = svc.submit(geo10, _rhs(geo10, 91))
    svc.drain(timeout_s=600)
    tuned_iters = t1.result.iterations
    assert svc.hstore.load_tuned(t1.fingerprint) is not None

    # the restarted replica: overlay resolves from the hstore BEFORE
    # the first build — tuned from the first request, zero full
    # setups (hierarchy restored, not re-coarsened)
    base_restored = metrics.get("autotune.overlay.restored")
    base_full = metrics.get("amg.setup.full")
    svc2 = SolveService(_at_cfg(dirs))
    t2 = svc2.submit(geo10, _rhs(geo10, 91))   # t1's system again
    svc2.drain(timeout_s=600)
    assert t2.result.converged
    assert t2.result.iterations == tuned_iters
    assert metrics.get("autotune.overlay.restored") == base_restored + 1
    assert metrics.get("amg.setup.full") == base_full
    snap = svc2.stats()["autotune"]["fingerprints"]
    assert next(iter(snap.values()))["restored"]


def test_demotion_drops_overlay_and_record(geo10, tmp_path):
    """Hysteresis: a live regression past autotune_demote_factor over
    the demote window drops the overlay and deletes the persisted
    record."""
    svc = SolveService(_at_cfg(
        f"serving_hierarchy_dir={tmp_path}/hier,"
        " autotune_demote_window=2"))
    _heat(svc, geo10, n=5)
    _search(svc)
    assert svc.stats()["autotune"]["promoted"] == 1
    fp = next(iter(svc._tuner._fp))
    rec = svc._tuner._fp[fp]
    assert svc.hstore.load_tuned(fp) is not None
    # fake the regression: promoted-era completions far above the
    # pre-promotion median
    rec["pre_exec"] = 0.01
    rec["post"].extend([1.0, 1.0])
    base = metrics.get("autotune.demotions")
    svc.step()
    assert metrics.get("autotune.demotions") == base + 1
    assert rec["phase"] == "demoted" and rec["overlay"] is None
    assert svc.hstore.load_tuned(fp) is None
    assert svc._tuner.overlay_for(fp) is None
