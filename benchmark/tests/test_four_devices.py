"""What the harness does with four devices, pinned.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q`. No
chip and no recording: the devices and the trace are written out here.

- `require_chips` takes the cell's count of TPU devices or exits with
  code 3; more devices than asked for are fine;
- `memory_peak_bytes` is the peak of the fullest device;
- `trace_reduce.reduce` on a trace of two device planes: `busy_s` is
  the mean over the planes, `n_ops` and `op_time` are summed over them,
  the idle gaps are the first device's; the readers that divide by
  `devices` give one device's share.
"""
from __future__ import annotations

from types import SimpleNamespace as NS

import jax
import pytest

from benchmark import layer_metrics, run, trace_reduce


def _devices(platform, count, peaks=None):
    peaks = peaks or [0] * count
    return [NS(platform=platform, device_kind="TPU v5 lite", id=i,
               memory_stats=lambda p=p: {"peak_bytes_in_use": p})
            for i, p in enumerate(peaks)]


@pytest.mark.parametrize("platform,have,asked,code", [
    ("tpu", 4, 4, None), ("tpu", 4, 1, None), ("tpu", 1, 4, run.NO_CHIP),
    ("cpu", 4, 4, run.NO_CHIP), ("cpu", 1, 1, run.NO_CHIP)])
def test_require_chips(monkeypatch, capsys, platform, have, asked, code):
    monkeypatch.setattr(jax, "devices", lambda: _devices(platform, have))
    if code is None:
        assert len(run.require_chips(asked)) == have
        return
    with pytest.raises(SystemExit) as e:
        run.require_chips(asked)
    assert e.value.code == code
    assert f"needs {asked} TPU chip(s)" in capsys.readouterr().err


def test_memory_peak_is_the_fullest_device_s():
    devs = _devices("tpu", 4, peaks=[10, 40, 30, 20])
    assert run.memory_peak_bytes(devs) == 40
    devs[1].memory_stats = lambda: None        # a backend that reports none
    assert run.memory_peak_bytes(devs) == 30


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _two_plane_trace():
    """A window of two operations (1000-4000 and 5000-9000 ns) and two
    devices: device 0 runs a, b | c (busy 5000 ns), device 1 a | c
    (busy 3000 ns); an op outside the operations' spans on each."""
    host = NS(name="/host:CPU", lines=[
        _line("other thread", [("elsewhere", 0, 10000)]),
        _line("main", [("bench.window", 0, 10000),
                       ("bench.solve", 1000, 3000),
                       ("bench.solve", 5000, 4000),
                       ("readback", 4000, 1000),
                       ("host.a", 2000, 600)])])
    dev0 = NS(name="/device:TPU:0", lines=[
        _line("XLA Ops", [("%a = f32[8] fusion(...)", 1000, 1000),
                          ("%b = f32[8] fusion(...)", 2500, 1000),
                          ("%between = f32[8] copy(...)", 4200, 300),
                          ("%c.1 = f32[8] custom-call(...)", 5000, 3000)]),
        _line("XLA Modules", [("jit_solve(1)", 1000, 2500)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        _line("XLA Ops", [("%a = f32[8] fusion(...)", 1000, 2000),
                          ("%between = f32[8] copy(...)", 4200, 300),
                          ("%c.1 = f32[8] custom-call(...)", 6000, 1000)]),
        _line("Steps", [])])
    idle = NS(name="/device:TPU:2", lines=[_line("XLA Ops", [])])
    return NS(planes=[host, dev0, dev1, idle])


def test_a_two_plane_trace_reduces_as_the_readme_says(monkeypatch):
    monkeypatch.setattr(jax.profiler, "ProfileData", NS(
        from_file=lambda path: _two_plane_trace()))
    r = trace_reduce.reduce("unused.xplane.pb", "bench.solve")
    ns = 1e-9
    assert r["traced_ops"] == 2
    assert r["window_s"] == pytest.approx(7000 * ns)
    assert r["devices"] == 2                   # the idle plane is not one
    assert r["busy_s"] == pytest.approx((5000 + 3000) / 2 * ns)
    assert r["n_ops"] == 3 + 2
    assert r["op_time"] == pytest.approx(
        {"a": 3000 * ns, "b": 1000 * ns, "c.1": 4000 * ns})
    # device 0's gaps: 2000-2500 under host.a, 3500-4000 and 8000-9000
    # under the operation's own span
    assert r["idle_gaps"] == [["bench.solve", pytest.approx(1500 * ns)],
                              ["host.a", pytest.approx(500 * ns)]]
    assert "probe" not in r

    obs = layer_metrics.Observed(ops=2, trace=r)
    assert layer_metrics.device_ops_per_op(obs) == pytest.approx(5 / 2 / 2)
    assert layer_metrics.share_of_busy(obs, ["c.*"]) == pytest.approx(
        100.0 * 4000 / 2 / 4000)
    assert layer_metrics.idle_share(obs) == pytest.approx(
        100.0 * (1 - 4000 / 7000))
    assert trace_reduce.breakdown(r)["device_ops"][0] == [
        "c.1", pytest.approx(4000 * ns)]
