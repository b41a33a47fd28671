"""repro.obs: numpy-faithful percentiles, live counter views, span
nesting/ordering in the lifecycle tracer, the scheduler cycle's spans and
their join with a profiler capture, and the zero-overhead contract —
greedy output is token-identical with the full observability stack enabled,
across every serving cache architecture.
"""

import contextlib
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs
from conftest import fp_engine, prompt_list
from repro.obs import (ENGINE_TRACK, SCHED_TRACK, CounterView, Histogram,
                       MetricsRegistry, NullTracer, Observability, Tracer,
                       clock_offset, percentile, request_track)
from repro.serving import (GenerationConfig, Request, RequestScheduler,
                           ServingFrontend, VirtualClock)

# -- percentiles vs numpy -----------------------------------------------------


class TestPercentile:
    def test_matches_numpy_linear(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 7, 50, 501):
            xs = rng.normal(size=n).tolist()
            for q in (0.0, 25.0, 50.0, 90.0, 95.0, 99.0, 100.0):
                assert percentile(xs, q) == pytest.approx(
                    float(np.percentile(xs, q)), rel=1e-12, abs=1e-12)

    def test_empty_and_bad_q_raise(self):
        with pytest.raises(ValueError):
            percentile([], 50.0)
        with pytest.raises(ValueError):
            percentile([1.0], 101.0)

    def test_histogram_summary_matches_numpy(self):
        h = MetricsRegistry().histogram("t")
        xs = np.random.default_rng(1).exponential(size=257)
        for x in xs:
            h.record(float(x))
        s = h.summary()
        assert s["count"] == 257
        assert s["mean"] == pytest.approx(float(xs.mean()))
        for q in (50, 95, 99):
            assert s[f"p{q}"] == pytest.approx(float(np.percentile(xs, q)))

    def test_empty_histogram_summarizes_to_count_only(self):
        assert Histogram("idle").summary() == {"count": 0}

    def test_decimation_bounds_memory_exact_extremes(self):
        h = Histogram("x", max_samples=8)
        for i in range(1000):
            h.record(float(i))
        assert h.count == 1000
        assert (h.min, h.max) == (0.0, 999.0)
        assert len(h.samples) < 8
        # The retained subsample still estimates the median reasonably.
        assert 250.0 < h.percentile(50.0) < 750.0


class TestWindowedPercentile:
    """`Histogram.percentile(window_s=..., now=...)` — the live-SLO read
    the frontend's admission policy is built on."""

    def test_matches_numpy_on_window_slice(self):
        h = Histogram("ttft")
        rng = np.random.default_rng(2)
        vals = rng.exponential(size=64).tolist()
        for i, v in enumerate(vals):
            h.record(v, t=float(i))          # one sample per "second"
        now, window = 63.0, 20.0
        in_window = vals[43:]                # t in [43, 63]
        assert h.window_samples(window, now) == in_window
        for q in (0.0, 50.0, 95.0, 99.0, 100.0):
            assert h.percentile(q, window_s=window, now=now) == pytest.approx(
                float(np.percentile(in_window, q)), rel=1e-12, abs=1e-12)

    def test_full_window_equals_lifetime(self):
        h = Histogram("ttft")
        for i, v in enumerate((3.0, 1.0, 2.0, 5.0)):
            h.record(v, t=float(i))
        assert h.percentile(99.0, window_s=1e9, now=3.0) == h.percentile(99.0)

    def test_empty_window_raises(self):
        h = Histogram("ttft")
        h.record(1.0, t=0.0)
        with pytest.raises(ValueError):
            h.percentile(50.0, window_s=0.5, now=100.0)   # sample aged out

    def test_window_without_now_raises(self):
        h = Histogram("ttft")
        h.record(1.0, t=0.0)
        with pytest.raises(ValueError, match="explicit `now`"):
            h.percentile(50.0, window_s=1.0)

    def test_nonpositive_window_raises(self):
        h = Histogram("ttft")
        h.record(1.0, t=0.0)
        with pytest.raises(ValueError):
            h.window_samples(0.0, now=1.0)

    def test_untimed_records_stamp_monotonic(self):
        import time

        h = Histogram("ttft")
        before = time.monotonic()
        h.record(7.0)                        # no t= — stamps time.monotonic()
        after = time.monotonic()
        assert h.window_samples(1e9, now=after) == [7.0]
        # ...and the stamp really is from the monotonic clock, not zero.
        assert before <= h._times[0] <= after

    def test_decimation_keeps_times_and_values_paired(self):
        h = Histogram("ttft", max_samples=64)
        for i in range(10_000):
            h.record(float(i), t=float(i))   # value == timestamp
        assert len(h._times) == len(h._samples)
        # After heavy decimation a trailing window must return only samples
        # actually recorded inside it — pairing drift would leak old values.
        recent = h.window_samples(1000.0, now=9999.0)
        assert recent and all(v >= 9000.0 - 1e-9 for v in recent)


# -- registry + live counter views --------------------------------------------


class TestRegistry:
    def test_counter_view_is_live_both_ways(self):
        reg = MetricsRegistry()
        v = reg.counter_view("s.", ["a", "b"])
        v["a"] += 2                       # legacy dict spelling
        reg.counter("s.b").inc(3)         # registry-side increment
        assert reg.counter("s.a").value == 2
        assert v["b"] == 3
        assert dict(v) == {"a": 2, "b": 3}
        assert v == {"a": 2, "b": 3}

    def test_counter_view_fixed_keys(self):
        v = MetricsRegistry().counter_view("s.", ["a"])
        with pytest.raises(KeyError):
            v["typo"]
        with pytest.raises(KeyError):
            v["typo"] = 1
        with pytest.raises(TypeError):
            del v["a"]
        assert isinstance(v, CounterView) and len(v) == 1

    def test_type_collision_raises_get_or_create_shares(self):
        reg = MetricsRegistry()
        c = reg.counter("x")
        assert reg.counter("x") is c
        with pytest.raises(ValueError):
            reg.gauge("x")
        with pytest.raises(ValueError):
            reg.histogram("x")

    def test_gauge_watermarks(self):
        g = MetricsRegistry().gauge("g")
        for v in (5, 2, 9):
            g.set(v)
        assert (g.value, g.min, g.max) == (9, 2, 9)

    def test_snapshot_is_json_ready(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.gauge("g").set(1.5)
        reg.histogram("h").record(0.25)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["counters"] == {"c": 1}
        assert snap["gauges"]["g"]["max"] == 1.5
        assert snap["histograms"]["h"]["p50"] == 0.25


# -- tracer: span nesting / ordering / export ---------------------------------


class TestTracer:
    def test_mispaired_end_raises(self):
        tr = Tracer()
        tr.begin("a")
        tr.begin("b")
        with pytest.raises(ValueError):
            tr.end("a")                   # b is innermost
        tr.end("b")
        tr.end("a")
        assert tr.open_spans() == []
        with pytest.raises(ValueError):
            tr.end("a")                   # nothing open

    def test_event_order_and_monotone_timestamps(self):
        tr = Tracer()
        with tr.span("outer"):
            tr.instant("mark")
            with tr.span("inner"):
                pass
        evs = [e for e in tr.events if e["ph"] != "M"]
        assert [(e["ph"], e["name"]) for e in evs] == [
            ("B", "outer"), ("i", "mark"), ("B", "inner"),
            ("E", "inner"), ("E", "outer")]
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts) and all(t >= 0 for t in ts)

    def test_per_track_nesting_is_independent(self):
        tr = Tracer()
        tr.begin("a", "scheduler")
        tr.begin("b", "req 0")
        tr.end("a", "scheduler")          # fine: different track's stack
        tr.end("b", "req 0")

    def test_tracks_declare_thread_names_once(self):
        tr = Tracer()
        for _ in range(3):
            tr.instant("x", "engine")
        tr.counter("depth", 1, "scheduler")
        meta = [e for e in tr.events if e["ph"] == "M"]
        assert [m["args"]["name"] for m in meta] == ["engine", "scheduler"]
        assert len({m["tid"] for m in meta}) == 2

    def test_deferred_device_args_gathered_at_flush(self):
        tr = Tracer()
        tr.instant("done", "engine", lengths=jnp.arange(3), n=7)
        assert tr._pending_args           # recorded, not yet gathered
        d = tr.to_dict()
        ev = [e for e in d["traceEvents"] if e["name"] == "done"][0]
        assert ev["args"]["lengths"] == [0, 1, 2] and ev["args"]["n"] == 7
        assert not tr._pending_args       # one-shot gather

    def test_export_perfetto_shape(self, tmp_path):
        tr = Tracer()
        with tr.span("s", "scheduler", k=1):
            tr.counter("q", 2, "scheduler")
        path = tmp_path / "trace.json"
        tr.export(str(path))
        with open(path) as f:
            doc = json.load(f)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        for ev in doc["traceEvents"]:
            assert {"ph", "pid", "tid", "name"} <= set(ev)

    def test_null_tracer_noops_everything(self):
        nt = NullTracer()
        with nt.span("a"):
            nt.instant("b")
            nt.counter("c", 1)
        nt.end("never-opened")            # no bookkeeping, no raise
        nt.flush()
        assert not nt.enabled


# -- scheduler lifecycle trace ------------------------------------------------


def _req_events(tracer: Tracer, uid: int) -> list[tuple[str, str]]:
    tids = {e["args"]["name"]: e["tid"] for e in tracer.events
            if e["ph"] == "M"}
    tid = tids[request_track(uid)]
    return [(e["ph"], e["name"]) for e in tracer.events
            if e["tid"] == tid and e["ph"] != "M"]


class TestSchedulerTrace:
    def test_request_lifecycle_ordering(self):
        engine = fp_engine("retnet-1.3b")
        obs = Observability(tracer=Tracer())
        sched = RequestScheduler(engine, n_slots=1, cache_len=32,
                                 gen=GenerationConfig(max_new_tokens=4),
                                 chunk_size=8, obs=obs)
        sched.submit(Request(uid=7, prompt=prompt_list(engine, 4)))
        sched.run()
        names = [n for _, n in _req_events(obs.tracer, 7)]
        assert names[0] == "request" and names[-1] == "request"
        order = [names.index(n) for n in
                 ("queued", "admit", "prefill_chunk", "decode",
                  "first_token", "finish")]
        assert order == sorted(order)
        assert obs.tracer.open_spans(request_track(7)) == []
        snap = obs.metrics.snapshot()
        assert snap["histograms"]["sched.ttft_s"]["count"] == 1
        assert snap["counters"]["sched.admitted"] == 1

    def test_preemption_reads_as_preempt_resume_pair(self):
        engine = fp_engine("retnet-1.3b")
        obs = Observability(tracer=Tracer())
        sched = RequestScheduler(engine, n_slots=1, cache_len=32,
                                 gen=GenerationConfig(max_new_tokens=6),
                                 chunk_size=8, host_spill=True, obs=obs)
        sched.submit(Request(uid=0, prompt=[2, 3, 4]))
        while not sched._active:
            sched.step()
        sched.submit(Request(uid=1, prompt=[3, 4, 5]), priority=2)
        res = sched.run()
        assert len(res) == 2 and sched.stats["preempted"] == 1
        names = [n for _, n in _req_events(obs.tracer, 0)]
        order = [names.index(n) for n in
                 ("admit", "preempt", "preempted", "resume", "finish")]
        assert order == sorted(order)
        assert obs.tracer.open_spans(request_track(0)) == []
        snap = obs.metrics.snapshot()
        assert snap["counters"]["pool.spills"] == 1
        assert snap["histograms"]["pool.spill_bytes"]["count"] == 1
        assert snap["histograms"]["pool.fetch_bytes"]["count"] == 1


# -- the scheduler cycle's spans ----------------------------------------------


def _span_tree(tracer: Tracer, track: str = SCHED_TRACK
               ) -> list[tuple[str, list[str]]]:
    """The track's outermost spans in order, each with its direct
    children's names."""
    tids = {e["args"]["name"]: e["tid"] for e in tracer.events
            if e["ph"] == "M"}
    top: list[tuple[str, list[str]]] = []
    depth = 0
    for e in tracer.events:
        if e["tid"] != tids[track] or e["ph"] not in ("B", "E"):
            continue
        if e["ph"] == "E":
            depth -= 1
            continue
        if depth == 0:
            top.append((e["name"], []))
        elif depth == 1:
            top[-1][1].append(e["name"])
        depth += 1
    return top


DECODE = ["sched.token_sync", "sched.pool_step", "sched.drain"]


class TestCycleSpans:
    def test_one_step_span_per_step_in_cycle_order(self):
        engine = fp_engine("retnet-1.3b")
        obs = Observability(tracer=Tracer())
        sched = RequestScheduler(engine, n_slots=1, cache_len=48,
                                 gen=GenerationConfig(max_new_tokens=4),
                                 chunk_size=8, obs=obs)
        sched.submit(Request(uid=0, prompt=prompt_list(engine, 20)))
        n = 0
        while sched.pending:
            sched.step()
            n += 1
        cycles = _span_tree(obs.tracer)
        assert [name for name, _ in cycles] == ["sched.step"] * n
        # One chunk per step while the admission is in flight; the step that
        # lands the last chunk decodes the first token; 3 more steps finish.
        chunks = sched.stats["prefill_chunks"]
        assert chunks >= 2
        assert [kids for _, kids in cycles] == (
            [["sched.admit"]] * (chunks - 1) + [["sched.admit"] + DECODE]
            + [DECODE] * 3)
        assert obs.tracer.open_spans() == []

    def test_frontend_yields_between_steps(self):
        engine = fp_engine("retnet-1.3b")
        clock = VirtualClock()
        obs = Observability(tracer=Tracer())
        sched = RequestScheduler(engine, classes=[(2, 48)],
                                 gen=GenerationConfig(max_new_tokens=4),
                                 chunk_size=8, obs=obs, clock=clock.now)
        fe = ServingFrontend(sched, clock=clock)

        async def main():
            async with fe:
                await clock.sleep(0.5)         # the stepper parks: no work
                streams = [fe.submit(prompt_list(engine, 9, seed=s))
                           for s in (1, 2)]
                for st in streams:
                    await st.result()

        clock.run(main())
        names = [name for name, _ in _span_tree(obs.tracer)]
        steps = sched.stats["steps"]
        assert steps > 0 and names[0] == "frontend.idle"
        assert [n for n in names if n != "frontend.idle"] == [
            "sched.step", "frontend.yield"] * steps
        assert obs.tracer.open_spans() == []

    def test_span_off_is_the_shared_nullcontext(self):
        a = Observability()
        b = Observability(metrics=MetricsRegistry(), tracer=NullTracer())
        assert a.span("sched.step") is b.span("engine.prefill", ENGINE_TRACK)
        assert a.span("sched.step") is repro.obs._NULL
        assert isinstance(repro.obs._NULL, contextlib.nullcontext)
        on = Observability(tracer=Tracer())
        with on.span("sched.step"):
            pass
        assert [(e["ph"], e["name"]) for e in on.tracer.events
                if e["ph"] != "M"] == [("B", "sched.step"),
                                       ("E", "sched.step")]


def _cycles(obs: Observability, durations_s) -> None:
    for d in durations_s:
        with obs.span("sched.step"):
            with obs.span("sched.token_sync"):
                time.sleep(d)


def test_clock_offset_from_a_cpu_capture(tmp_path):
    """Spans recorded on both clocks place the tracer's events on a real
    `jax.profiler` capture: every captured span lands within 100 us of its
    tracer twin, matched by order although the tracer also holds spans from
    before and after the capture."""
    from jax.profiler import ProfileData

    obs = Observability(tracer=Tracer(), profile=True)
    ms = [5e-4 * (k + 1) for k in range(14)]     # each cycle its own length
    _cycles(obs, ms[:3])
    jax.profiler.start_trace(str(tmp_path))
    _cycles(obs, ms[3:11])
    jax.profiler.stop_trace()
    _cycles(obs, ms[11:])

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    captured = [(ev.name, ev.start_ns, ev.end_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for ev in line.events]
    offset = clock_offset(obs.tracer.events, captured)

    ours = [(b["ts"], e["ts"]) for b, e in zip(
        [e for e in obs.tracer.events if e["ph"] == "B"
         and e["name"] == "sched.step"],
        [e for e in obs.tracer.events if e["ph"] == "E"
         and e["name"] == "sched.step"])]
    theirs = sorted((s, e) for n, s, e in captured if n == "sched.step")
    assert len(ours) == 14 and len(theirs) == 8
    for (cb, ce), (tb, te) in zip(theirs, ours[3:11]):
        assert abs(cb - (tb * 1e3 + offset)) < 100e3
        assert abs(ce - (te * 1e3 + offset)) < 100e3

    # One name's spans a millisecond off on one clock: the clocks do not
    # agree; one span alone (a pause between its two readings) is outvoted.
    moved = [(n, s + 1e6, e + 1e6) if n == "sched.token_sync" else (n, s, e)
             for n, s, e in captured]
    with pytest.raises(ValueError, match="disagree"):
        clock_offset(obs.tracer.events, moved)
    one = [(n, s + 1e6, e + 1e6) if (n, s) == ("sched.step", theirs[4][0])
           else (n, s, e) for n, s, e in captured]
    assert abs(clock_offset(obs.tracer.events, one) - offset) < 100e3
    with pytest.raises(ValueError, match="both clocks"):
        clock_offset(obs.tracer.events, [("MemoryAllocation", 0.0, 1.0)])


# -- zero-overhead contract: token identity with obs on -----------------------


GEN = GenerationConfig(max_new_tokens=6)


def _drain(engine, obs=None):
    kw = {"obs": obs} if obs is not None else {}
    sched = RequestScheduler(engine, n_slots=2, cache_len=64, gen=GEN,
                             chunk_size=8, **kw)
    for uid in range(3):
        sched.submit(Request(uid=uid, prompt=prompt_list(engine, 5 + uid,
                                                         seed=uid + 1)))
    return {u: f.tokens for u, f in sched.run().items()}


def test_greedy_identity_with_observability(cache_arch):
    """Full stack on (live tracer + profiler annotations + shared metrics)
    vs off: greedy output must be token-identical — the behavioral half of
    the A7 compiled-program byte-identity audit."""
    engine = fp_engine(cache_arch)
    base = _drain(engine)
    obs = Observability(tracer=Tracer(), profile=True)
    saved = engine.obs
    engine.obs = obs                      # engine-side spans + annotations
    try:
        traced = _drain(engine, obs=obs)
    finally:
        engine.obs = saved
    assert base == traced
    for uid in range(3):
        assert obs.tracer.open_spans(request_track(uid)) == []
    snap = obs.metrics.snapshot()
    assert snap["histograms"]["sched.ttft_s"]["count"] == 3
    assert snap["counters"]["sched.emitted"] == sum(
        len(t) for t in traced.values())
