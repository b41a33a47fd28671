"""Structured request-lifecycle tracer emitting Chrome-trace-event JSON.

Records the serving stack's lifecycle spans — submit → admit → prefill
chunks → first token → decode steps → preempt/resume → finish — as
Trace Event Format events (``B``/``E`` duration pairs, ``i`` instants,
``C`` counter series) that Perfetto / ``chrome://tracing`` load directly:
each request gets its own named track, the scheduler's sequencer cycle its
own, so a preemption reads as a gap on the request's track bracketed by
``preempt``/``resume`` markers while the high-priority request's admit span
runs on a sibling track (docs/observability.md shows a worked example).

`clock_offset` lays these events over a `jax.profiler` capture: the spans
`Observability.span` records on both clocks fix the offset between them.

Hot-path discipline:

  * Recording appends a dict to a python list — no device access, no
    serialization, no I/O.  `NullTracer` is the default everywhere and
    no-ops every method, so an untraced run pays one attribute check per
    potential span (`Observability.span`); the A7 program audit pins that
    the *compiled* serving programs are byte-identical either way.
  * Span/instant ``args`` may carry **device arrays**: they are stored
    as-is at record time and gathered in ONE `jax.device_get` at `flush`
    (export calls it) — deferred args never force a sync inside the
    sequencer cycle.  `Tracer.flush` is the single allowlisted host-sync
    point the ``host-sync`` lint rule grants this module.
"""

from __future__ import annotations

import contextlib
import json
import math
import statistics
import time
from typing import Any, Iterable, Iterator

__all__ = ["Tracer", "NullTracer", "SCHED_TRACK", "ENGINE_TRACK",
           "clock_offset", "request_track"]

SCHED_TRACK = "scheduler"
ENGINE_TRACK = "engine"
CLOCK_TOLERANCE_NS = 100e3     # the most one name's spans may sit off the rest


def request_track(uid: int) -> str:
    """The per-request track name (`tid`) a request's lifecycle lives on."""
    return f"req {uid}"


def _is_device_array(v: Any) -> bool:
    """Array-ish (has shape+dtype) but not already a host scalar/list."""
    return hasattr(v, "shape") and hasattr(v, "dtype") \
        and not isinstance(v, (int, float, bool))


class NullTracer:
    """The disabled tracer: every record is a no-op, `span` yields nothing.

    This is the default on every engine/scheduler — observability off means
    the serving loop executes the same statements it always did.
    """

    enabled = False

    def begin(self, name: str, track: str = SCHED_TRACK, **args) -> None:
        pass

    def end(self, name: str, track: str = SCHED_TRACK, **args) -> None:
        pass

    def instant(self, name: str, track: str = SCHED_TRACK, **args) -> None:
        pass

    def counter(self, name: str, value: float,
                track: str = SCHED_TRACK) -> None:
        pass

    @contextlib.contextmanager
    def span(self, name: str, track: str = SCHED_TRACK,
             **args) -> Iterator[None]:
        yield

    def flush(self) -> None:
        pass


class Tracer(NullTracer):
    """Chrome-trace-event recorder with per-track span nesting.

    ``ts`` is microseconds since the tracer's construction; every event
    lands on one process (``pid`` 0) with the *track* name as its thread,
    declared via ``thread_name`` metadata so Perfetto labels the lanes.
    ``B``/``E`` events must nest per track — `end` checks the name against
    the track's open-span stack and raises on a mismatch, so a mis-paired
    instrumentation site fails loudly in tests instead of producing a trace
    Perfetto silently mis-renders.
    """

    enabled = True

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._events: list[dict] = []
        self._open: dict[str, list[str]] = {}    # track -> span-name stack
        self._tids: dict[str, int] = {}
        self._pending_args: list[dict] = []      # device-array args to gather

    # -- recording ----------------------------------------------------------

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)
        if tid is None:
            tid = self._tids[track] = len(self._tids)
            self._events.append({"ph": "M", "pid": 0, "tid": tid,
                                 "name": "thread_name",
                                 "args": {"name": track}})
        return tid

    def _args(self, args: dict) -> dict | None:
        if not args:
            return None
        if any(_is_device_array(v) for v in args.values()):
            self._pending_args.append(args)
        return args

    def _event(self, ph: str, name: str, track: str, **fields) -> dict:
        ev = {"ph": ph, "name": name, "pid": 0, "tid": self._tid(track),
              "ts": self._now_us(), **fields}
        self._events.append(ev)
        return ev

    def begin(self, name: str, track: str = SCHED_TRACK, **args) -> None:
        """Open a span on ``track``; close it with `end` (LIFO per track)."""
        ev = self._event("B", name, track)
        a = self._args(args)
        if a is not None:
            ev["args"] = a
        self._open.setdefault(track, []).append(name)

    def end(self, name: str, track: str = SCHED_TRACK, **args) -> None:
        stack = self._open.get(track, [])
        if not stack or stack[-1] != name:
            raise ValueError(
                f"span end({name!r}) on track {track!r} does not match the "
                f"innermost open span ({stack[-1] if stack else None!r})")
        stack.pop()
        ev = self._event("E", name, track)
        a = self._args(args)
        if a is not None:
            ev["args"] = a

    def instant(self, name: str, track: str = SCHED_TRACK, **args) -> None:
        ev = self._event("i", name, track, s="t")
        a = self._args(args)
        if a is not None:
            ev["args"] = a

    def counter(self, name: str, value: float,
                track: str = SCHED_TRACK) -> None:
        """A Perfetto counter-series sample (e.g. queue depth per cycle)."""
        self._event("C", name, track, args={"value": value})

    @contextlib.contextmanager
    def span(self, name: str, track: str = SCHED_TRACK,
             **args) -> Iterator[None]:
        self.begin(name, track, **args)
        try:
            yield
        finally:
            self.end(name, track)

    # -- introspection / export ---------------------------------------------

    @property
    def events(self) -> list[dict]:
        return list(self._events)

    def open_spans(self, track: str = SCHED_TRACK) -> list[str]:
        """Names of the track's currently-open spans, outermost first."""
        return list(self._open.get(track, []))

    def flush(self) -> None:
        """Resolve deferred device-array args in one host gather.

        The ONLY point in the tracer that synchronizes with the device —
        called from `export` / end-of-run, never from the sequencer cycle
        (``host-sync`` lint allowlists exactly this qualname).
        """
        if not self._pending_args:
            return
        import jax

        pending, self._pending_args = self._pending_args, []
        for args in pending:
            arrays = {k: v for k, v in args.items() if _is_device_array(v)}
            host = jax.device_get(arrays)
            for k, v in host.items():
                args[k] = v.tolist() if hasattr(v, "tolist") else v

    def to_dict(self) -> dict:
        self.flush()
        return {"traceEvents": self._events,
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        """Write the Perfetto-loadable JSON (gathers deferred args first)."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
            f.write("\n")


# -- joining a profiler capture -----------------------------------------------


def _closed_spans(events: Iterable[dict]
                  ) -> dict[str, list[tuple[float, float]]]:
    """name -> (begin, end) in µs of every closed span, by begin."""
    open_: dict[int, list[dict]] = {}
    out: dict[str, list[tuple[float, float]]] = {}
    for e in events:
        if e["ph"] == "B":
            open_.setdefault(e["tid"], []).append(e)
        elif e["ph"] == "E" and open_.get(e["tid"]):
            b = open_[e["tid"]].pop()
            out.setdefault(b["name"], []).append((b["ts"], e["ts"]))
    return {k: sorted(v) for k, v in out.items()}


def _matched_offsets(tracer_events: Iterable[dict],
                     capture_spans: Iterable[tuple[str, float, float]]
                     ) -> dict[str, list[float]]:
    """name -> capture ns − tracer ns of the begin and the end of each of
    its spans found on both clocks.

    A capture holds a run of consecutive spans of each name, cut from the
    tracer's longer record, so each name's capture sequence is laid against
    every run of as many tracer spans (matched in order) and the run whose
    offsets spread least is taken."""
    ours = _closed_spans(tracer_events)
    theirs: dict[str, list[tuple[float, float]]] = {}
    for name, start, end in capture_spans:
        if name in ours:
            theirs.setdefault(name, []).append((start, end))
    out: dict[str, list[float]] = {}
    for name, cap in theirs.items():
        cap.sort()
        tr = ours[name]
        best_spread = math.inf
        for k in range(len(tr) - len(cap) + 1):
            offs: list[float] = []
            lo, hi = math.inf, -math.inf
            for (cb, ce), (tb, te) in zip(cap, tr[k:]):
                pair = (cb - tb * 1e3, ce - te * 1e3)
                offs += pair
                lo, hi = min(lo, *pair), max(hi, *pair)
                if hi - lo >= best_spread:
                    break               # no better than a run already seen
            else:
                out[name], best_spread = offs, hi - lo
    return out


def clock_offset(tracer_events: Iterable[dict],
                 capture_spans: Iterable[tuple[str, float, float]]) -> float:
    """The nanoseconds that place a tracer event on a profiler capture's
    clock: ``capture_ns = event["ts"] * 1e3 + clock_offset(...)``.

    ``tracer_events`` are a `Tracer`'s events (``ts`` in µs on its
    ``perf_counter`` clock); ``capture_spans`` are ``(name, start_ns,
    end_ns)`` host events of a `jax.profiler` capture, which count from the
    capture's start.  The spans `Observability.span` recorded with a live
    tracer and ``profile`` on are on both; other names are ignored.  The
    offset is the median over every matched begin and end.  A pause of the
    host between the two clock readings of one span (a collection of
    Python's garbage) moves that span alone, so what must agree is each
    name's median: raises ValueError when no span is on both clocks, or
    when a name's median offset sits more than ``CLOCK_TOLERANCE_NS`` off
    the whole median (a name matched at the wrong place, or two clocks that
    drift apart)."""
    offs = _matched_offsets(tracer_events, capture_spans)
    if not offs:
        raise ValueError("no span is recorded on both clocks")
    offset = statistics.median(o for v in offs.values() for o in v)
    for name, v in offs.items():
        off = statistics.median(v) - offset
        if abs(off) > CLOCK_TOLERANCE_NS:
            raise ValueError(f"matched spans disagree: {name} sits "
                             f"{off / 1e3:.1f} us off the median offset")
    return offset
