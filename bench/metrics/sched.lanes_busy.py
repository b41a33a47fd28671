"""sched.lanes_busy: mean of the scheduler's per-step ``active`` counter
(lanes holding a request) over the steps of the window."""


def read(ctx):
    vals = ctx.counter_values("active", ctx.window.t_open, ctx.window.t_close)
    return sum(vals) / len(vals) if vals else None
