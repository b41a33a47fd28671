"""mfu.decode: the decode pool step's share of the chip's peak.

The least time a step could take at peak is the larger of its operations
over the peak rate and its bytes over the HBM bandwidth.  Bytes: every
decode weight once per step, and what each busy lane moves of its own
state, read plus written, as its reference counts it
(``ctx.decode_lane_bytes``).  Operations: two per weight per busy lane.
Summed over the pool steps the capture holds, over their device time.  At
these sizes the bytes bound it."""


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
    byts = weights + busy * ctx.decode_lane_bytes
    t_min = max(2 * macs * busy / ctx.peaks["bf16_flops"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * n * t_min / secs
