"""The scheduler-cycle metrics on a hand-made capture of three cycles
(``data/cycle.pbtxt``) and the tracer's record of the same cycles plus a
fourth after an idle wait, with every value counted by hand."""

import pathlib

import pytest
from jax.profiler import ProfileData

from bench import run_cell, trace_reduce
from bench.tests.cells import ROOT

DATA = pathlib.Path(__file__).parent / "data"
T0 = 100.0          # the capture's start on the tracer's clock, seconds

# (name, begin us, end us) from the capture's start; the capture holds the
# first three cycles, the tracer all four and the idle wait.
SPANS = [
    ("sched.step", 0.2, 8.0), ("sched.token_sync", 0.3, 5.8),
    ("sched.pool_step", 5.8, 6.3), ("sched.drain", 6.3, 7.9),
    ("frontend.yield", 8.2, 10.8),
    ("sched.step", 10.9, 16.0), ("sched.admit", 10.95, 11.6),
    ("engine.prefill_chunk", 11.0, 11.5), ("sched.token_sync", 11.7, 15.0),
    ("sched.pool_step", 15.0, 15.2), ("sched.drain", 15.2, 15.9),
    ("frontend.yield", 16.1, 18.5),
    ("sched.step", 18.6, 19.8), ("sched.token_sync", 18.7, 19.1),
    ("sched.pool_step", 19.1, 19.4),
    ("frontend.idle", 20.0, 29.9),
    ("sched.step", 30.0, 31.0), ("sched.token_sync", 30.1, 30.6),
    ("sched.pool_step", 30.6, 30.7),
]


class Window:
    t_open = T0
    t_close = T0 + 25e-6       # the fourth cycle begins after the window


def _events(spans):
    ev = []
    for name, b, e in spans:
        ev += [{"ph": "B", "name": name, "tid": 0, "t": T0 + b * 1e-6},
               {"ph": "E", "name": name, "tid": 0, "t": T0 + e * 1e-6}]
    return sorted(ev, key=lambda x: x["t"])


def _ctx(spans):
    r = trace_reduce.reduce_profile(ProfileData.from_text_proto(
        (DATA / "cycle.pbtxt").read_text()))
    return run_cell.TraceContext(r, Window, T0, T0 + 22e-6, _events(spans),
                                 [], {}, 0.0, {}, {}, ROOT)


def _read(metric, ctx):
    return run_cell._load_module(ROOT / "bench" / "metrics"
                                 / f"{metric}.py").read(ctx)


def test_the_capture_names_each_gap():
    # Device busy 0-5, 6-10, 12-15, 19-20, 20.5-21 us: gaps of 1 us (middle
    # 5.5, in token_sync), 2 us (11, in the prefill chunk inside admit),
    # 4 us (17, in the yield) and 0.5 us (20.25, in no span).
    r = _ctx(SPANS).reduced
    assert trace_reduce.idle_by_span(r) == [
        ["frontend.yield", pytest.approx(4e-6)],
        ["engine.prefill_chunk", pytest.approx(2e-6)],
        ["sched.token_sync", pytest.approx(1e-6)],
        ["unannotated host work", pytest.approx(0.5e-6)]]


def test_device_idle_in_step():
    # (1 + 2) us of 22: the yield's 4 us and the unnamed 0.5 us do not count.
    assert _read("device.idle_in_step", _ctx(SPANS)) == pytest.approx(
        100 * 3 / 22)


def test_relaunch_ms():
    # pool_step end - token_sync end in the cycles begun in the window:
    # 6.3 - 5.8, 15.2 - 15.0, 19.4 - 19.1 us -> median 0.3 us (the fourth
    # cycle's 0.1 us lies after the window).
    assert _read("sched.relaunch_ms", _ctx(SPANS)) == pytest.approx(0.3e-3)


def test_host_busy_ms():
    # (10.9 - 0.2) - 5.5 = 5.2 us and (18.6 - 10.9) - 3.3 = 4.4 us; the
    # third cycle holds the idle wait and is left out, the fourth has no
    # successor: median 4.8 us.
    assert _read("sched.host_busy_ms", _ctx(SPANS)) == pytest.approx(4.8e-3)


@pytest.mark.parametrize("metric", ["device.idle_in_step",
                                    "sched.relaunch_ms",
                                    "sched.host_busy_ms"])
def test_a_program_without_cycle_spans_gives_nothing(metric):
    # An earlier program's trace: dispatch annotations only, which name
    # gaps in the capture but leave the tracer without the cycle's spans.
    assert _read(metric, _ctx([s for s in SPANS
                               if s[0] == "engine.prefill_chunk"])) is None
