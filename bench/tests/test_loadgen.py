"""The traffic generator and the closed-loop driver, on virtual time."""

import json

import pytest

from bench import loadgen
from bench.tests.cells import ROOT

MIX = {"loop": "closed", "clients": 3, "slots": 3,
       "prompt": {"min": 33, "max": 36}, "output": {"min": 8, "max": 40}}


def test_every_seed_serves_the_same_sizes_in_another_order():
    t = loadgen.Traffic.from_json("m", MIX)
    a, b = loadgen.Requests(t, 1, 512), loadgen.Requests(t, 2 ** 31 + 5, 512)
    assert a._start != b._start
    n = 3 * loadgen.CYCLE
    sa = [a.take()[1:] for _ in range(n)]
    sb = [b.take()[1:] for _ in range(n)]
    size = lambda s: [(len(p), k) for p, k in s]  # noqa: E731
    # One cycle of sizes, repeated; each seed enters it at its own point.
    cyc = size(sa[:loadgen.CYCLE])
    assert size(sa) == cyc * 3
    shift = (b._start - a._start) % loadgen.CYCLE
    assert size(sb) == (cyc[shift:] + cyc[:shift]) * 3
    assert sa[0][0] != sb[0][0]
    assert {len(p) for p, _ in sa} == set(t.prompt_lengths())
    assert len(t.prompt_lengths()) <= 4
    assert all(8 <= k <= 40 for _, k in sa)
    assert all(1 <= tok < 512 for p, _ in sa for tok in p)


def test_first_requests_take_a_fixed_set_of_budget_scales():
    t = loadgen.Traffic.from_json("m", MIX)
    scales = [loadgen.Requests(t, seed, 512)._first_scale for seed in (0, 5)]
    assert scales[0] == scales[1]
    assert sorted(scales[0]) == pytest.approx([1 / 6, 1 / 2, 5 / 6])


def test_open_loop_arrivals_keep_the_mean_rate():
    import itertools

    import numpy as np
    for kind in ("bursty", "poisson"):
        gaps = loadgen.arrival_gaps({"kind": kind, "rate_rps": 4.0},
                                    np.random.default_rng(0))
        assert np.mean(list(itertools.islice(gaps, 20000))) == pytest.approx(
            0.25, rel=0.05)


def test_bursty_arrivals_come_in_bursts():
    import itertools

    import numpy as np
    gaps = np.array(list(itertools.islice(loadgen.arrival_gaps(
        {"kind": "bursty", "rate_rps": 4.0, "burst_factor": 8.0},
        np.random.default_rng(1)), 20000)))
    # Short gaps follow short gaps: the regime persists across arrivals.
    short = gaps < np.median(gaps)
    assert np.mean(short[1:][short[:-1]]) > np.mean(short) + 0.05


def _serve(seconds: float, seed: int = 3):
    from repro.serving import EngineSpec, InferenceEngine
    from repro.serving.clock import VirtualClock
    from repro.serving.frontend import FrontendConfig, ServingFrontend
    from repro.serving.sampling import GenerationConfig
    from repro.serving.scheduler import RequestScheduler

    t = loadgen.Traffic.from_json("m", MIX)
    clock = VirtualClock()
    eng = InferenceEngine.from_config("retnet-1.3b", EngineSpec(reduced=True))
    sched = RequestScheduler(
        eng, n_slots=t.slots, cache_len=t.cache_len, chunk_size=32,
        gen=GenerationConfig(max_new_tokens=40, stop_tokens=()),
        clock=clock.now)
    fe = ServingFrontend(sched, clock=clock, config=FrontendConfig(
        shed_action="off", step_period_s=0.01))
    opened = []

    async def main():
        async with fe:
            drv = loadgen.Driver(fe, loadgen.Requests(t, seed, 512),
                                 on_open=opened.append)
            await drv.run(seconds)
            return drv

    return clock.run(main()), opened


def test_closed_loop_window_rule():
    drv, opened = _serve(1.0)
    recs = drv.records
    # The window opens when every client holds a first token, once.
    assert opened == [drv.t_open]
    firsts = sorted(r.times[0] for r in recs[:3])
    assert drv.t_open == pytest.approx(firsts[-1])
    # Closed loop: at most `clients` requests in flight at any time.
    for r in recs:
        live = sum(1 for o in recs if o.submitted <= r.submitted
                   and (o.finished is None or o.finished > r.submitted))
        assert live <= 3
    # Budgets are served exactly; the first request of each client was
    # scaled; nothing is submitted after the close.
    done = [r for r in recs if r.complete]
    assert done and all(len(r.tokens) == r.budget for r in done)
    assert all(r.submitted < drv.t_close for r in recs)
    # Requests still running at the close are cut, not failed.
    assert not any(r.failed for r in recs)
    win = loadgen.Window(recs, drv.t_open, drv.t_close)
    inside = sum(1 for r in recs for x in r.times
                 if drv.t_open <= x < drv.t_close)
    assert win.output_tokens_per_s() == pytest.approx(inside / 1.0)
    assert win.attempted() == sum(1 for r in recs
                                  if drv.t_open <= r.submitted < drv.t_close)
    # Every request submitted in the window got its first token.
    assert all(r.times for r in win.submitted())
    assert win.ttft_samples() and win.itl_samples() is not None


def test_itl_samples_are_every_gap_in_the_window():
    # Two requests, gaps of 0.1 s and 0.2 s; the window holds the later
    # token of gaps 3..9 of the first and 1..2 of the second.
    a = loadgen.Record(0, [1], 10, submitted=0.0,
                       times=[0.1 * i for i in range(10)])
    b = loadgen.Record(1, [1], 5, submitted=0.0,
                       times=[0.15 + 0.2 * i for i in range(5)])
    win = loadgen.Window([a, b], 0.25, 0.6)
    assert sorted(win.itl_samples()) == pytest.approx([0.1] * 3 + [0.2] * 2)
    assert loadgen.p95([]) is None


# The fixed cycles of (prompt length p, budget n), read off by hand, with
# the sums of n*p, of n*(n-1)/2 and of n over each: a request puts its n
# tokens at positions p .. p+n-1, which add up to n*p + n*(n-1)/2.
CYCLES = {
    "silo-8": ([(40, 512), (74, 1024), (32, 1024), (45, 569), (117, 1024),
                (32, 512), (91, 512), (35, 512), (128, 759), (56, 922),
                (67, 1024), (61, 628), (50, 691), (102, 835), (81, 512),
                (128, 1024)], 903297, 4930266, 12084),
    "liso-4": ([(482, 17), (885, 45), (384, 64), (543, 25), (1408, 51),
                (384, 23), (1087, 16), (419, 16), (1536, 34), (666, 41),
                (805, 59), (733, 28), (603, 31), (1224, 37), (978, 20),
                (1536, 64)], 520300, 12017, 571),
}


def _mix(name):
    return loadgen.Traffic.from_json(name, json.loads(
        (ROOT / "bench" / "traffic" / f"{name}.json").read_text()))


@pytest.mark.parametrize("mix", sorted(CYCLES))
def test_decode_positions_from_the_cycle(mix):
    t = _mix(mix)
    cycle, np_sum, tri_sum, n_sum = CYCLES[mix]
    assert t.fixed_draws()[0] == cycle
    assert (sum(n * p for p, n in cycle), sum(n * (n - 1) // 2
                                              for _, n in cycle),
            sum(n for _, n in cycle)) == (np_sum, tri_sum, n_sum)
    positions = t.decode_positions()
    assert (sum(positions), len(positions)) == (np_sum + tri_sum, n_sum)


def test_prefill_chunks_tile_each_prompt_of_the_cycle():
    chunks = _mix("liso-4").prefill_chunks(128)
    # The cycle's first prompt, 482 = 3 x 128 + 64 + 32 + 2 tokens.
    assert chunks[:6] == [(0, 128), (128, 128), (256, 128), (384, 64),
                          (448, 32), (480, 2)]
    assert sum(c for _, c in chunks) == sum(p for p, _ in CYCLES["liso-4"][0])
    assert all(start % c == 0 for start, c in chunks)
