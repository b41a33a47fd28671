"""device.idle_in_step: the share of the traced window in which the device
idled while the host was inside the scheduler's own cycle, in percent: the
idle gaps that the trace reduction names by a ``sched.*`` or ``engine.*``
span (the span holds the gap's middle), over the capture.  The rest of
``device.idle`` fell in ``frontend.yield`` or ``frontend.idle``, or in host
work no span covers.  Read only where the program marks its cycle
(``sched.step``); a program that annotates its dispatch calls alone gives
nothing."""


def read(ctx):
    if ctx.capture_s <= 0 or not ctx.spans("sched.step"):
        return None
    idle = sum(sec for sec, name in ctx.reduced.gaps
               if name.startswith(("sched.", "engine.")))
    return 100.0 * idle / ctx.capture_s
