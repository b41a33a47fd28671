"""w8a8_matmul_roofline: the W8A8 kernel's device time against its
roofline (``bench/costs/w8a8_matmul.py`` on the operands as compiled).
The least time of one prompt chunk is the mean over the chunks dispatched
in the capture (the engine's ``engine.prefill_chunk_tokens`` record); it
counts once for every chunk program the capture holds, over the kernel's
device time in those programs."""


def read(ctx):
    sizes = ctx.captured_chunks
    if not sizes:
        return None
    per = {c: [(avals, times) for kernel, avals, _, times
               in ctx.kernels[f"prefill_chunk[{c}]"] if kernel == "w8a8_matmul"]
           for c in set(sizes)}
    n, secs = ctx.kernel_time("w8a8_matmul", r"prefill_chunk",
                              {sum(t for _, t in calls)
                               for calls in per.values()})
    if not n or secs <= 0:
        return None
    mean = sum(times * ctx.least_time("w8a8_matmul", avals)
               for c in sizes for avals, times in per[c]) / len(sizes)
    return 100.0 * n * mean / secs
