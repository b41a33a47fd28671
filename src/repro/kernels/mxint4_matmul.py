"""Pallas TPU kernel: MXINT4 dequant-fused matmul — the HSA MVM dataflow (C2).

This is the TPU realization of the paper's decode dataflow (Fig. 4c): packed
int4 mantissas and 4-bit group-shift exponents stream HBM -> VMEM (4.25
bits/weight instead of 8/16), dequantization (`m * 2^(S_g-2)`) happens in VMEM
immediately before the MXU dot — the analogue of "dequantize on idle PEs" —
and the Eq. (4) fused-RMSNorm epilogue (`* out_scale * row_scale + bias`) is
applied in-register on the final K step, so the normalized activation tensor
never makes an extra HBM round-trip.

Tiling: grid ``(M/bm, N/bn, K/bk)``, K innermost/sequential with an fp32 VMEM
accumulator (output-stationary — the same dataflow class as the paper's PE
array).  The packed operands are blocked ``(bk, bn/2)`` (mantissa bytes) and
``(bk, bn/32)`` (exponent bytes); Mosaic needs each block's last dim to be a
multiple of 128 or the whole array, so ``bn`` is either ``N`` or a multiple
of 4096 (`ops.mxint4_matmul` picks it).

Layout in VMEM, chosen so nothing moves across lanes:

  * Mantissas are never interleaved back into channel order.  The low
    nibbles (even channels) and high nibbles (odd channels) of a byte tile
    are two weight tiles of their own, each dotted separately; the kernel
    writes even and odd channels to ``out[0]`` / ``out[1]`` and the wrapper
    interleaves the [M, N] result once.
  * A packed byte column ``j`` holds channels ``2j, 2j+1``, both in group
    ``j // 8``.  The per-column scale is a lane expansion of the exponent
    bytes (byte ``j // 16``, nibble ``(j // 8) % 2``), done on the MXU as a
    product with a 0/1 selection matrix built from iota.  The scales are
    powers of two and exact in bf16, so the bf16 product is exact.
  * Bytes are widened to int32 before any shift (the VPU has no int8
    arithmetic) and the scale ``2^(code-11)`` is assembled from its float
    exponent bits, so no uint8 -> float cast is needed.

Weight VMEM per step is ``bk * bn * 0.53`` bytes (1.1 MB at the default
512 x 4096, double-buffered); dequantization runs over 128-lane slices so
its temporaries stay at ``bk x 128``.

The ASIC splits the shift into 2 LSBs (pre-shift) + 2 MSBs (accumulation-row
gating) because full shifters per PE are expensive in silicon; a VPU is not, so
we apply the whole exponent as one exact power-of-two multiply.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params

from repro.core.mxint4 import EXP_BIAS, GROUP_SIZE, MANT_SHIFT

# 2^(code - EXP_BIAS - MANT_SHIFT) as f32 bits: biased exponent << 23.
_F32_EXP_OFFSET = 127 - EXP_BIAS - MANT_SHIFT
_LANES = 128


def pow2_from_codes(codes: jax.Array, offset: int) -> jax.Array:
    """Exact ``2^(codes + offset - 127)`` for int32 ``codes`` (f32 bit
    assembly: no transcendental, no narrow-int -> float cast)."""
    return jax.lax.bitcast_convert_type((codes + offset) << 23, jnp.float32)


def _kernel(x_ref, packed_ref, exps_ref, oscale_ref, rscale_ref, bias_ref,
            out_ref, acc_ref, *, n_k: int, slice_w: int, out_dtype):
    """One (bm, bn) output tile; K iterated sequentially via the grid."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                      # [bm, bk]
    e32 = exps_ref[...].astype(jnp.int32) & 0xFF            # [bk, bn/32]
    s_lo = pow2_from_codes(e32 & 0x0F, _F32_EXP_OFFSET).astype(jnp.bfloat16)
    s_hi = pow2_from_codes(e32 >> 4, _F32_EXP_OFFSET).astype(jnp.bfloat16)
    n_bytes = e32.shape[1]
    half = packed_ref.shape[1]                              # bn / 2

    for t in range(half // slice_w):
        cols = pl.ds(t * slice_w, slice_w)
        # Selection matrices: exponent byte b feeds packed column j of this
        # slice iff b == j // 16, from its low nibble iff (j // 8) is even.
        b_idx = jax.lax.broadcasted_iota(jnp.int32, (n_bytes, slice_w), 0)
        j_idx = (jax.lax.broadcasted_iota(jnp.int32, (n_bytes, slice_w), 1)
                 + t * slice_w)
        hit = b_idx == j_idx // GROUP_SIZE
        odd = ((j_idx // (GROUP_SIZE // 2)) % 2) == 1
        sel_lo = jnp.where(hit & ~odd, 1.0, 0.0).astype(jnp.bfloat16)
        sel_hi = jnp.where(hit & odd, 1.0, 0.0).astype(jnp.bfloat16)
        dims = (((1,), (0,)), ((), ()))
        scale = (jax.lax.dot_general(s_lo, sel_lo, dims,
                                     precision=jax.lax.Precision.DEFAULT,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(s_hi, sel_hi, dims,
                                       precision=jax.lax.Precision.DEFAULT,
                                       preferred_element_type=jnp.float32))

        p32 = packed_ref[:, cols].astype(jnp.int32)         # [bk, slice_w]
        lo = ((p32 << 28) >> 28).astype(jnp.float32) * scale  # even channels
        hi = (p32 >> 4).astype(jnp.float32) * scale            # odd channels
        acc_ref[0, :, cols] += jax.lax.dot_general(
            x, lo, dims, preferred_element_type=jnp.float32)
        acc_ref[1, :, cols] += jax.lax.dot_general(
            x, hi, dims, preferred_element_type=jnp.float32)

    # ---- Eq. (4) epilogue on the last K step ------------------------------
    @pl.when(kk == n_k - 1)
    def _epilogue():
        for par in (0, 1):
            y = (acc_ref[par] * oscale_ref[par] * rscale_ref[...]
                 + bias_ref[par])
            out_ref[par] = y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def mxint4_matmul_pallas(
    x: jax.Array,            # [M, K] bf16/f32 (or int8 activations pre-scaled)
    packed: jax.Array,       # int8 [K, N//2]
    exps_packed: jax.Array,  # uint8 [K, N//(2*GROUP_SIZE)] — biased nibble codes
    out_scale: jax.Array,    # f32 [N]
    row_scale: jax.Array,    # f32 [M]
    bias: jax.Array,         # f32 [N]
    *,
    block_m: int = 8,
    block_n: int = 4096,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    n = packed.shape[1] * 2
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    if bn % (2 * GROUP_SIZE) != 0:
        raise ValueError(f"block_n {bn} must cover whole packed groups "
                         f"({2 * GROUP_SIZE})")
    n_k = k // bk
    half = bn // 2
    slice_w = min(_LANES, half)
    if half % slice_w:
        raise ValueError(f"block_n {bn} must be a multiple of "
                         f"{2 * _LANES} above {2 * _LANES}")

    def parity_split(v):                    # [N] -> [2, 1, N/2] even/odd
        return v.reshape(n // 2, 2).T.reshape(2, 1, n // 2)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, slice_w=slice_w,
                          out_dtype=out_dtype),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),             # x
            pl.BlockSpec((bk, half), lambda i, j, kk: (kk, j)),           # packed
            pl.BlockSpec((bk, bn // (2 * GROUP_SIZE)),
                         lambda i, j, kk: (kk, j)),                       # exps
            pl.BlockSpec((2, 1, half), lambda i, j, kk: (0, 0, j)),       # out_scale
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),               # row_scale
            pl.BlockSpec((2, 1, half), lambda i, j, kk: (0, 0, j)),       # bias
        ],
        out_specs=pl.BlockSpec((2, bm, half), lambda i, j, kk: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((2, m, n // 2), out_dtype),
        scratch_shapes=[pltpu.VMEM((2, bm, half), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed, exps_packed, parity_split(out_scale),
      row_scale.reshape(m, 1), parity_split(bias))
    # [2, M, N/2] (even, odd channels) -> [M, N] in channel order.
    return jnp.moveaxis(out, 0, -1).reshape(m, n)
