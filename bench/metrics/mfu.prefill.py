"""mfu.prefill: the prompt-chunk programs' share of the chip's peak.

Per chunk of c tokens the least time is the larger of its W8A8 operations
(two per int8 weight per token) over the int8 peak and its bytes (the int8
weights once, and what the lane moves of its own state, read plus written,
as its reference counts it at the positions of the traffic's chunks of
that length: ``ctx.prefill_lane_bytes``) over the HBM bandwidth.  Its mean
over the chunks dispatched in the capture (the engine's
``engine.prefill_chunk_tokens`` record) counts once for every chunk program
the capture holds, over those programs' device time."""


def read(ctx):
    n, secs = ctx.reduced.program_seconds(r"prefill_chunk")
    sizes = ctx.captured_chunks
    if not n or secs <= 0 or not sizes:
        return None
    total = 0.0
    for c in sizes:
        weights = macs = 0
        for kernel, avals, _, times in ctx.kernels.get(
                f"prefill_chunk[{c}]", []):
            k = ctx.cost(kernel).cost(avals)
            weights += times * k["weight_bytes"]
            macs += times * k["k"] * k["n"]
        total += max(2 * macs * c / ctx.peaks["int8_ops"],
                     (weights + ctx.prefill_lane_bytes[c])
                     / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * n * total / len(sizes) / secs
