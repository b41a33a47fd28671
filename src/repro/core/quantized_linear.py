"""QuantizedLinear — one weight, three execution paths (the HSA's PE array
seen from software).

A `QuantizedLinear` owns a single logical weight ``W[K, N]`` stored in up to
three formats, mirroring the paper's storage scheme:

  * ``w``        — bf16/f32 master (training; absent in deploy-only mode)
  * ``w8``       — per-tensor INT8 (prefill MMM dataflow, Fig. 4b)
  * ``mx``       — MXINT4 packed + group shifts (decode MVM dataflow, Fig. 4c)

`apply` dispatches on the requested phase and implements the Eq. (4) epilogue
(`row_scale` = sigma^{-1} from the upstream fused RMSNorm, `bias` = folded
B_{n+1}).  The HSA engine (hsa.py) chooses the phase; models never pick
formats directly.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import mxint4 as mx
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedLinearParams:
    """Pytree of all stored formats for one linear layer."""

    w: jax.Array | None          # [K, N] master (None in deploy-only mode)
    w8: mx.Int8Weight | None     # prefill format
    mx: mx.MXINT4Weight | None   # decode format
    bias: jax.Array | None       # [N] (includes folded B_{n+1} when fused)


def quantize_params(w: jax.Array, bias: jax.Array | None = None,
                    keep_master: bool = True) -> QuantizedLinearParams:
    """PTQ one weight into all deploy formats (Section III pipeline)."""
    return QuantizedLinearParams(
        w=w if keep_master else None,
        w8=mx.quantize_int8_tensor(w),
        mx=mx.quantize_mxint4(w),
        bias=bias,
    )


def apply(
    params: QuantizedLinearParams,
    x: jax.Array,
    phase: str,                       # 'train' | 'prefill' | 'decode'
    *,
    row_scale: jax.Array | None = None,   # sigma^{-1}, per token (Eq. 4)
    out_scale: jax.Array | float | None = None,
    impl: str = "auto",
    out_dtype=jnp.float32,
    kernel_opts: dict[str, Any] | None = None,
) -> jax.Array:
    """Run ``y = (x @ W) * out_scale * row_scale + bias`` in the phase format."""
    kernel_opts = kernel_opts or {}
    if phase == "train" or (phase == "prefill" and params.w8 is None):
        if params.w is None:
            raise TypeError("master weight required for train phase")
        y = (x.astype(jnp.float32) @ params.w.astype(jnp.float32))
        if out_scale is not None:
            y = y * out_scale
        if row_scale is not None:
            y = y * row_scale[..., None]
        if params.bias is not None:
            y = y + params.bias
        return y.astype(out_dtype)

    if phase == "prefill":
        # MMM dataflow: dynamic A8, per-tensor W8, int32 accumulate on the MXU.
        xq, act_scale = mx.quantize_act_int8(x)
        combined = act_scale * params.w8.scale * (
            1.0 if out_scale is None else out_scale)
        return ops.w8a8_matmul(
            xq, params.w8.values, combined,
            row_scale=row_scale, bias=params.bias, out_dtype=out_dtype,
            impl=impl)

    if phase == "decode":
        # MVM dataflow: MXINT4 weights, dequant fused into the kernel (C2).
        os = None
        if out_scale is not None:
            os = jnp.broadcast_to(jnp.asarray(out_scale, jnp.float32),
                                  (params.mx.shape[1],))
        return ops.mxint4_matmul(
            x, params.mx, out_scale=os,
            row_scale=row_scale,
            bias=params.bias, out_dtype=out_dtype, impl=impl, **kernel_opts)

    raise ValueError(f"unknown phase: {phase!r}")


def streamed_bytes(params: QuantizedLinearParams, phase: str) -> int:
    """Weight bytes the phase's dataflow moves from HBM/DRAM (the EMA metric)."""
    if phase == "decode":
        return params.mx.nbytes_streamed()
    if phase == "prefill":
        return params.w8.nbytes_streamed()
    return int(params.w.size * params.w.dtype.itemsize)
