"""Pallas TPU kernel: W8A8 int8 matmul — the HSA MMM (prefill) dataflow (C1).

The paper's prefill runs the PE array output-stationary on INT8 activations x
INT8 weights with int32 accumulation (Fig. 4b).  On TPU the MXU natively
consumes int8 pairs with int32 accumulate; this kernel expresses the paper's
dataflow explicitly: grid ``(M/bm, N/bn, K/bk)`` with K sequential, an int32
VMEM accumulator per output tile (output-stationary), and the dequantization
epilogue (`acc * act_scale * w_scale * S_{n+1} * sigma^{-1} + B`) applied once
at drain time — the Eq. (4) fusion on the MMM path.

XLA lowers jnp int8 dots to the MXU already (ops.w8a8_matmul's default path);
this kernel exists so the prefill dataflow has the same explicit BlockSpec
treatment as the decode kernel, and is validated against ref.w8a8_matmul_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params


def _kernel(x_ref, w_ref, scale_ref, rscale_ref, bias_ref, out_ref, acc_ref,
            *, n_k: int, out_dtype):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Output-stationary int8 x int8 -> int32 accumulate (the PE array).
    # Integer dots are exact; the explicit precision keeps a float
    # precision default (`jax.default_matmul_precision`) off this dot,
    # which Mosaic rejects for int8 operands.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)

    @pl.when(kk == n_k - 1)
    def _drain():
        y = acc_ref[...].astype(jnp.float32) * scale_ref[...] \
            * rscale_ref[...] + bias_ref[...]
        out_ref[...] = y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def w8a8_matmul_pallas(
    x_q: jax.Array,          # int8 [M, K]
    w_q: jax.Array,          # int8 [K, N]
    out_scale: jax.Array,    # f32 [N] — act_scale * w_scale * S_{n+1}
    row_scale: jax.Array,    # f32 [M] — sigma^{-1} (Eq. 4)
    bias: jax.Array,         # f32 [N]
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x_q.shape
    n = w_q.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, out_dtype=out_dtype),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),     # x int8
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),     # w int8
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),       # out_scale
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),       # row_scale
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),       # bias
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_q, w_q, out_scale.reshape(1, n), row_scale.reshape(m, 1),
      bias.reshape(1, n))
