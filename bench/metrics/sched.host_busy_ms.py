"""sched.host_busy_ms: the host's time per scheduler cycle that is not spent
waiting on the chip, in milliseconds.  For each cycle that begins in the
window and is followed by another: the time from this ``sched.step``'s
begin to the next one's, less this step's ``sched.token_sync`` time; the
median.  The cycle holds the stepper's ``frontend.yield``, the event-loop
turn in which clients take their tokens; a cycle in which the stepper
parked on its idle wait (``frontend.idle``) is left out."""

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
    steps = sorted(ctx.spans("sched.step"))
    syncs = sorted(ctx.spans("sched.token_sync"))
    idle = sorted(ctx.spans("frontend.idle"))
    busy = []
    for (b, e), (nb, _) in zip(steps, steps[1:]):
        if w.t_open <= b < w.t_close and not _within(idle, b, nb):
            busy.append(nb - b - sum(se - sb
                                     for sb, se in _within(syncs, b, e)))
    return 1e3 * statistics.median(busy) if busy else None
