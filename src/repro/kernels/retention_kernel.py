"""Pallas TPU kernel: chunkwise multi-scale retention (RetNet prefill, C5).

Maps the chunkwise retention recurrence onto the TPU the way flash-attention
maps softmax attention: the grid walks ``(batch*heads, num_chunks)`` with the
chunk axis sequential; the running state ``S [dk, dv]`` lives in a VMEM
scratch accumulator across chunk steps (never spilled to HBM), and each step
does three MXU matmuls (scores, inner, cross) plus the decay-weighted state
update.  Intra-chunk decay matrices are built from `broadcasted_iota` on the
VPU — nothing is gathered from HBM.  The per-head log-decay is a whole
``[BH]`` operand in SMEM, read as a scalar by the grid's head index.

Why it matters for the paper: chunkwise retention is the MMM-shaped prefill
workload the HSA runs in systolic mode; O(S) memory with no softmax pass is
RetNet's advantage the paper leans on (Sec. II).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params


def _kernel(logg_ref, q_ref, k_ref, v_ref, y_ref, state_out_ref, state_ref,
            *, chunk: int, n_chunks: int, out_dtype):
    cc = pl.program_id(1)

    @pl.when(cc == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    log_g = logg_ref[pl.program_id(0)]                       # SMEM scalar
    q = q_ref[0].astype(jnp.float32)                         # [c, dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    # Decay structures (built on-chip; positions m = 1..c within the chunk).
    # Every exp runs on vectors: the scalar unit has no transcendentals.
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = (rows - cols).astype(jnp.float32)
    d = jnp.where(diff >= 0, jnp.exp(diff * log_g), 0.0)     # [c, c]
    m = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(
        jnp.float32) + 1.0
    in_decay = jnp.exp(m * log_g)                            # gamma^m      [c, 1]
    out_decay = jnp.exp((chunk - m) * log_g)                 # gamma^(c-m)  [c, 1]
    chunk_decay = jnp.exp(jnp.full((1, 1), float(chunk), jnp.float32) * log_g)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * d
    inner = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    cross = jax.lax.dot_general(q * in_decay, state_ref[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = (inner + cross).astype(out_dtype)

    kv = jax.lax.dot_general(k * out_decay, v, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [dk, dv]
    state_ref[...] = chunk_decay * state_ref[...] + kv

    @pl.when(cc == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "out_dtype", "interpret"))
def retention_chunkwise_pallas(
    q: jax.Array,        # [BH, S, dk]
    k: jax.Array,        # [BH, S, dk]
    v: jax.Array,        # [BH, S, dv]
    log_gamma: jax.Array,  # f32 [BH] — per-(batch,head) decay, log space
    *,
    chunk: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [BH, S, dv], final state [BH, dk, dv])."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    if s % chunk != 0:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    n_chunks = s // chunk

    grid = (bh, n_chunks)
    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # log_gamma
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),  # q
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),  # k
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),  # v
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),  # y
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),     # final state
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), out_dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(log_gamma, q, k, v)
    return y, state
