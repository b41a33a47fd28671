"""The trace reduction, on a hand-made capture whose numbers are counted
by hand, and on 10 ms of a recorded pool step (TPU v5 lite, retnet-1.3b,
8 lanes)."""

import pathlib
import re

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce
from bench.tests.cells import ROOT

DATA = pathlib.Path(__file__).parent / "data"


def _load(name):
    return ProfileData.from_text_proto((DATA / name).read_text())


def test_hand_made_capture():
    r = trace_reduce.reduce_profile(_load("hand.pbtxt"))
    # Device 0 is busy 0-6 us and 8-9 us; device 1 0-4 us: mean 5.5 us.
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx(5.5e-6)
    assert r.programs == {"jit_pool_step": [1, pytest.approx(6e-6)],
                          "jit__prefill_chunk_impl": [1, pytest.approx(1e-6)]}
    assert r.program_seconds("pool_step") == (1, pytest.approx(6e-6))
    # The while holds the three kernel calls: its own time is 6 - 4.6 us.
    assert r.ops["while"] == pytest.approx(1.4e-6)
    assert r.ops["mxint4_matmul_pallas"] == pytest.approx(4e-6)
    assert r.ops["vmap_jit_mxint4_matmul_pallas__"] == pytest.approx(0.6e-6)
    # Per execution: the vmapped wrapper's call counts with the others.
    assert r.kernel_calls("mxint4_matmul", "pool_step") == [
        (3, pytest.approx(4.6e-6))]
    assert r.kernel_calls("w8a8_matmul", "prefill_chunk") == [
        (1, pytest.approx(1e-6))]
    assert r.kernel_calls("w8a8_matmul", "pool_step") == [(0, 0.0)]
    assert r.top_ops(1) == [["mxint4_matmul_pallas", pytest.approx(4e-6)]]
    # The one idle gap of device 0 (6-8 us) falls in the innermost dotted
    # annotation on the host; the runtime's own events do not count.
    assert r.gaps == [(pytest.approx(2e-6), "engine.prefill_chunk")]
    assert trace_reduce.idle_by_span(r) == [["engine.prefill_chunk",
                                             pytest.approx(2e-6)]]


def test_recorded_pool_step():
    text = (DATA / "pool_step_10ms.pbtxt").read_text()
    r = trace_reduce.reduce_profile(_load("pool_step_10ms.pbtxt"))
    names = dict(re.findall(r'key: (\d+) value \{ id: \d+ name: "([^"]*)"',
                            text))
    events = [(names[m], int(o), int(d)) for m, o, d in re.findall(
        r"metadata_id: (\d+) offset_ps: (\d+) duration_ps: (\d+)", text)]
    kernel = [d for n, _, d in events if n.startswith("%mxint4_matmul_pallas")]
    # The slice holds no program event: take one that spans it.
    r.executions = [trace_reduce.Op("jit_pool_step", 0, 1e13)]
    assert r.kernel_calls("mxint4_matmul", "pool_step") == [
        (len(kernel), pytest.approx(sum(kernel) * 1e-12))]
    assert len(kernel) > 20
    assert r.top_ops(1)[0][0] == "mxint4_matmul_pallas"
    assert sum(kernel) * 1e-12 < r.busy_s <= 10e-3
    assert r.busy_s == pytest.approx(sum(r.ops.values()), rel=1e-6)


def test_kernel_time_checks_calls_against_the_jaxpr():
    from bench import run_cell
    ops = [trace_reduce.Op("%mxint4_matmul_pallas.1", 10 * i + j, 10 * i + j + 1)
           for i, n in enumerate((1, 3, 3, 2)) for j in range(n)]
    execs = [trace_reduce.Op("jit_pool_step", 10 * i, 10 * i + 5)
             for i in range(4)]
    r = trace_reduce.Reduced(1, 0.0, {}, {}, [], ops, execs)
    ctx = run_cell.TraceContext(r, None, 0.0, 1.0, [], [], {}, 0.0, {}, {},
                                ROOT)
    # The first and the last execution may be cut by the capture.
    assert ctx.kernel_time("mxint4_matmul", "pool_step", {3}) == (
        2, pytest.approx(6e-9))
    # A step in the middle that disagrees with the jaxpr is an error.
    with pytest.raises(ValueError, match="calls per execution"):
        ctx.kernel_time("mxint4_matmul", "pool_step", {2})


def test_no_device_operations_is_an_error():
    pd = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(pd)
