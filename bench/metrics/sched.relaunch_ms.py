"""sched.relaunch_ms: the host's critical path between two decode steps, in
milliseconds.  In each scheduler cycle (``sched.step``) that begins in the
window, the time from the end of ``sched.token_sync`` (the host holds the
previous step's tokens, so the chip has finished that step) to the end of
the ``sched.pool_step`` dispatch that follows it; the median over those
cycles.  The chip waits this long, plus the dispatch's own latency, before
the next step starts."""

import bisect
import statistics


def _within(spans, b, e):
    """The spans (sorted by begin) that begin in [b, e)."""
    i = bisect.bisect_left(spans, (b,))
    out = []
    while i < len(spans) and spans[i][0] < e:
        out.append(spans[i])
        i += 1
    return out


def read(ctx):
    w = ctx.window
    syncs = sorted(ctx.spans("sched.token_sync"))
    steps = sorted(ctx.spans("sched.pool_step"))
    gaps = []
    for b, e in ctx.spans("sched.step"):
        if w.t_open <= b < w.t_close:
            gaps += [pe - se for (_, se), (_, pe)
                     in zip(_within(syncs, b, e), _within(steps, b, e))]
    return 1e3 * statistics.median(gaps) if gaps else None
