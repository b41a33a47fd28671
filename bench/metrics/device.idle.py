"""device.idle: share of the traced window in which no operation ran on
the device (1 - busy union / window), in percent."""


def read(ctx):
    if ctx.capture_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.reduced.busy_s / ctx.capture_s)
