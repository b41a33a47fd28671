"""sched.queue_wait_p95: 95th percentile of the time a request spent
queued before its admission began (the scheduler's ``queued`` span), over
the admissions that began in the window."""

import numpy as np


def read(ctx):
    waits = [e - b for b, e in ctx.spans("queued")
             if ctx.window.t_open <= e < ctx.window.t_close]
    return float(np.percentile(waits, 95)) if waits else None
