"""mxint4_matmul_roofline: the MXINT4 kernel's device time against its
roofline: per call the larger of operations over peak and bytes over HBM
bandwidth (``bench/costs/mxint4_matmul.py`` on the operands as compiled; a
call vmapped over L lanes streams its weights L times), summed over the
pool steps the capture holds, over the device time of the same calls in
those steps (every call of the step's jaxpr, the output head's included)."""


def read(ctx):
    calls = [(avals, times) for kernel, avals, _, times
             in ctx.kernels["pool_step"] if kernel == "mxint4_matmul"]
    n, secs = ctx.kernel_time("mxint4_matmul", r"pool_step",
                              {sum(times for _, times in calls)})
    if not n or secs <= 0:
        return None
    per_step = sum(times * ctx.least_time("mxint4_matmul", avals)
                   for avals, times in calls)
    return 100.0 * n * per_step / secs
