"""Pallas kernel microbench: block-config sweep of the MXINT4 dequant-matmul
plus the flash-decode (split-KV) attention kernel.

The *structural* numbers — HBM bytes per output tile, arithmetic
intensity, VMEM working set per BlockSpec — are exact on any backend.  The
wall-time cross-check runs the kernels compiled on a TPU backend and in
interpret mode elsewhere (slow, relative only); every timed row names the
platform it ran on.
"""

import numpy as np

from repro.core import mxint4 as mx
from repro.core.mxint4 import GROUP_SIZE

from benchmarks.bench_lib import emit, time_fn


def analyze(m, k, n, bm, bn, bk) -> dict:
    w_bytes = k * n * mx_bits() / 8
    x_bytes = m * k * 4
    out_bytes = m * n * 4
    flops = 2 * m * k * n + k * n * 2          # dot + dequant muls
    vmem = (bm * bk * 4) + (bk * bn // 2) + (bk * bn // (2 * GROUP_SIZE)) \
        + bm * bn * 4                          # x + packed + exps + acc
    return {
        "hbm_bytes": w_bytes + x_bytes + out_bytes,
        "intensity": flops / (w_bytes + x_bytes + out_bytes),
        "vmem_bytes": vmem,
    }


def mx_bits() -> float:
    return 4.25


def run() -> None:
    # decode matvec shapes (the paper's MVM) across block configs
    for (m, k, n) in ((8, 4096, 4096), (8, 4096, 14336), (128, 7168, 2048)):
        # Mosaic-legal blocks: bn = N or a multiple of 4096 (ops.py).
        for (bm, bn, bk) in ((8, 4096, 256), (8, 4096, 512), (8, 4096, 1024)):
            a = analyze(m, k, n, bm, bn, bk)
            emit(f"kernel.mxint4[{m}x{k}x{n}]b{bm}_{bn}_{bk}", 0.0,
                 f"AI={a['intensity']:.2f}flops/B "
                 f"vmem={a['vmem_bytes']/1024:.0f}KiB "
                 f"hbm={a['hbm_bytes']/1e6:.1f}MB")
    # memory-bound check: decode AI << v5e ridge (197e12/819e9 ~ 240)
    a = analyze(8, 4096, 4096, 8, 4096, 512)
    emit("kernel.decode_is_memory_bound", 0.0,
         f"AI={a['intensity']:.1f} << ridge 240 -> HBM-bound, "
         "EMA cut = speedup (C2)")
    run_flash_decode()


def analyze_flash_decode(b, kv, g, d, c, fmt) -> dict:
    """Structural bytes/flops of one flash-decode dispatch: the whole cache
    streams once (split across KV grid blocks), q/out are noise."""
    from repro.core import kvq
    cache_bytes = b * c * kv * 2 * kvq.nbytes_per_row(fmt, d)
    io_bytes = 2 * b * kv * g * d * 4            # q in + out
    flops = 4 * b * kv * g * c * d               # scores + weighted sum
    return {"hbm_bytes": cache_bytes + io_bytes,
            "intensity": flops / (cache_bytes + io_bytes)}


def run_flash_decode() -> None:
    """Flash-decode leg: the byte ladder per cache format at serving context
    lengths, plus a wall cross-check of the kernel vs the jnp reference on
    a tiny shape (compiled on TPU, interpret mode elsewhere — the row names
    which)."""
    import jax
    import jax.numpy as jnp

    from repro.core import kvq
    from repro.kernels import ops as kops

    b, kv, g, d = 1, 8, 4, 128                   # GQA decode matvec shape
    for c in (1024, 8192):
        for fmt in ("float32", "int8_tok", "mxint4_blk"):
            a = analyze_flash_decode(b, kv, g, d, c, fmt)
            emit(f"kernel.flash_decode[c{c}]{fmt}", 0.0,
                 f"AI={a['intensity']:.2f}flops/B "
                 f"hbm={a['hbm_bytes']/1e6:.2f}MB")

    c = 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, kv, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, c, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, c, kv, d), jnp.float32)
    kv_len = jnp.int32(c - 7)
    platform = jax.devices()[0].platform
    pallas_mode = "compiled" if platform == "tpu" else "interpret-mode"
    for fmt, kk, vv in (("fp32", k, v),
                        ("int8_tok", kvq.encode(k, "int8_tok"),
                         kvq.encode(v, "int8_tok"))):
        for impl in ("ref", "pallas"):
            us = time_fn(lambda: kops.flash_decode(q, kk, vv, kv_len,
                                                   impl=impl))
            mode = pallas_mode if impl == "pallas" else "jnp"
            emit(f"kernel.flash_decode[c{c}]{fmt}.{impl}", us,
                 f"{platform} {mode} wall, relative only")


if __name__ == "__main__":
    run()
