"""Fault tolerance: heartbeats, stragglers, elastic mesh planning."""

import pytest
pytest.importorskip("hypothesis")  # optional dep: property tests skip without it
from hypothesis import given, settings, strategies as st

from repro.runtime import fault_tolerance as ft

settings.register_profile("ci", max_examples=30, deadline=None)
settings.load_profile("ci")


def test_heartbeat_timeout_detection():
    mon = ft.HeartbeatMonitor(["a", "b"], timeout_s=5.0)
    mon.beat("a", now=100.0)
    mon.beat("b", now=100.0)
    assert mon.check(now=104.0) == []
    mon.beat("a", now=104.0)
    assert mon.check(now=107.0) == ["b"]
    assert mon.alive_hosts() == ["a"]


def test_mark_failed_out_of_band():
    mon = ft.HeartbeatMonitor(["a", "b", "c"], timeout_s=1e9)
    mon.mark_failed("b")
    assert mon.check() == ["b"]


def test_straggler_detection():
    det = ft.StragglerDetector(threshold=2.0, window=8, min_samples=4)
    for i in range(8):
        for h in ("h0", "h1", "h2", "h3"):
            det.record(h, 1.0 if h != "h3" else 3.5)
    assert det.stragglers() == ["h3"]


def test_straggler_needs_samples():
    det = ft.StragglerDetector(min_samples=4)
    det.record("a", 1.0)
    det.record("b", 99.0)
    assert det.stragglers() == []


@given(alive=st.integers(16, 4096), tp=st.sampled_from([4, 8, 16]),
       pods=st.sampled_from([1, 2]))
def test_elastic_plan_invariants(alive, tp, pods):
    if alive < tp * pods:
        return
    plan = ft.plan_elastic_mesh(alive, model_parallel=tp, pods=pods)
    used = 1
    for s in plan.shape:
        used *= s
    assert used + plan.dropped_chips == alive or used <= alive
    assert plan.dropped_chips >= 0
    # TP degree preserved (param shards stay valid)
    assert plan.shape[-1] == tp
    if pods > 1:
        assert plan.axes == ("pod", "data", "model")
        assert plan.shape[0] == pods
    # DP is a power of two (ring-friendly collectives)
    dp = plan.shape[-2]
    assert dp & (dp - 1) == 0


def test_elastic_plan_fails_below_tp():
    with pytest.raises(ValueError, match="fewer chips"):
        ft.plan_elastic_mesh(8, model_parallel=16)


def test_failure_injector():
    mon = ft.HeartbeatMonitor(["a", "b"], timeout_s=1e9)
    inj = ft.FailureInjector({3: ["b"]})
    assert inj.maybe_fail(2, mon) == []
    assert inj.maybe_fail(3, mon) == ["b"]
    assert mon.check() == ["b"]
