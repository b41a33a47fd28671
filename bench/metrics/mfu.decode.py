"""mfu.decode: the decode pool step's share of the chip's peak.

The least time a step could take at peak is the larger of its operations
over the peak rate and its bytes over the HBM bandwidth.  Bytes: every
decode weight once per step, and each busy lane's retention state read and
written once.  Operations: two per weight per busy lane.  Summed over the
pool steps the capture holds, over their device time.  At these sizes the
bytes bound it."""


def read(ctx):
    n, secs = ctx.reduced.program_seconds(r"pool_step")
    lanes = ctx.counter_values("active", ctx.t0, ctx.t1)
    if not n or secs <= 0 or not lanes:
        return None
    busy = sum(lanes) / len(lanes)
    weights = macs = 0
    for kernel, avals, _, times in ctx.kernels["pool_step"]:
        c = ctx.cost(kernel).cost(avals)
        weights += times * c["weight_bytes"]
        macs += times * c["k"] * c["n"]
    byts = weights + busy * 2 * ctx.state_bytes()
    t_min = max(2 * macs * busy / ctx.peaks["bf16_flops"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * n * t_min / secs
