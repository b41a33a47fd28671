"""Pallas TPU kernel: split-KV flash-decode attention — the MVM-phase
attention hot loop.

The single-token decode step is memory-bound: per step, per layer, the whole
resident KV cache streams HBM -> VMEM once while the MXU does O(C*d) flops —
arithmetic intensity ~1, two orders below the ridge.  So the kernel's job is
to (a) touch each cache byte exactly once, (b) touch as *few* bytes as the
cache format allows, and (c) never materialize the [G, C] score matrix in HBM.

Dataflow (the neuronx ``flashdecode_attention`` split-KV idiom, on the
paper's HSA decode rung): grid ``(B, C/block_c)`` with the KV-length axis
innermost/sequential ("arbitrary"); each step loads one ``(block_c, KV*d)``
cache tile — every kv head of ``block_c`` tokens — into VMEM,
*dequantizes in-register* (int8 per-token scales or MXINT4 per-block shared
exponents — core/kvq.py; packed bytes are all HBM ever streams), computes
partial scores, and folds them into VMEM-resident online-softmax state
``(m, l, acc)`` — the same combine as layers._flash_fwd_impl, one token
wide.  The normalized output is written once on the final KV block.

TPU layout.  The cache leaves ``[B, C, KV, x]`` are viewed (free reshape) as
``[B, C, KV*x]``, so a block's last two dims are ``(block_c, KV*x)``: legal
for Mosaic at any KV, which a per-head ``(block_c, 1, x)`` block is not.
All ``KV*G`` query rows are scored against the whole tile at once with a
block-diagonal query ``[KV*G, KV*d]`` (zeros off the row's own head), and
the output keeps only the diagonal ``[KV*G, KV*dv]`` blocks — KV x the
minimal MXU work on a memory-bound step, traded for having no per-head lane
slicing.  Per-(token, head) int8 scales ride on the scores / probabilities
as a ``[KV*G, block_c]`` matrix instead of being broadcast across lanes;
MXINT4 mantissas are split into even/odd channel halves (the wrapper splits
q and re-interleaves the output) and their group scales are expanded across
lanes by an exact 0/1 bf16 matmul, as in kernels/mxint4_matmul.py.

GQA batches G = n_heads/n_kv_heads query heads per kv head; MLA maps to
KV=1 with a second score stream (the shared rope key) riding alongside the
latent stream: ``s = (q·k + q2·k2) * scale``.

CPU runs use ``interpret=True`` (ops.flash_decode sets it automatically off
TPU); correctness oracle: kernels/ref.py `flash_decode_ref`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params
from repro.core import kvq
from repro.core.mxint4 import GROUP_SIZE, MANT_SHIFT
from repro.kernels.mxint4_matmul import pow2_from_codes

_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_NN = (((1,), (0,)), ((), ()))        # a @ b


def _part_fmt(leaf) -> str:
    """Static dequant tag for one cache operand."""
    fmt = kvq.leaf_format(leaf)
    if fmt is not None:
        return fmt
    if leaf.dtype == jnp.int8:
        return "legacy_int8"
    return "fp"


def _parts(leaf) -> list[jax.Array]:
    """Flatten one cache operand into its HBM-resident arrays (key order is
    fixed per format so kernel ref order is deterministic)."""
    fmt = kvq.leaf_format(leaf)
    if fmt == "int8_tok":
        return [leaf["q"], leaf["s"]]
    if fmt == "mxint4_blk":
        return [leaf["m"], leaf["e"]]
    return [leaf]


def _n_par(fmt: str) -> int:
    """Channel halves a format is computed in (MXINT4: even/odd nibbles)."""
    return 2 if fmt == "mxint4_blk" else 1


def _block_diag(q: jax.Array, n_par: int) -> list[jax.Array]:
    """``[B, KV, G, d]`` -> per channel half ``[B, KV*G, KV*d/n_par]`` with
    row (h, g) holding q[h, g] in head h's lane block and zeros elsewhere."""
    b, kv, g, d = q.shape
    eye = jnp.eye(kv, dtype=q.dtype)
    halves = [q] if n_par == 1 else [q[..., 0::2], q[..., 1::2]]
    return [jnp.einsum("bhgd,hk->bhgkd", x, eye).reshape(b, kv * g, -1)
            for x in halves]


def _f32(ref) -> jax.Array:
    """Widen a block to f32 (narrow ints go through int32 first)."""
    x = ref[...]
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _dequant(parts, fmt: str) -> list[jax.Array]:
    """Cache part blocks -> f32 tiles ``[block_c, KV*x]``, one per channel
    half; int8_tok tiles are left unscaled (see `_head_scales`)."""
    if fmt == "mxint4_blk":
        p32 = parts[0][...].astype(jnp.int32)              # [bc, KV*d/2]
        e32 = parts[1][...].astype(jnp.int32)              # [bc, KV*d/16]
        s = pow2_from_codes(e32, 127 - MANT_SHIFT).astype(jnp.bfloat16)
        n_grp, n_col = e32.shape[1], p32.shape[1]
        grp = jax.lax.broadcasted_iota(jnp.int32, (n_grp, n_col), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n_grp, n_col), 1)
        sel = jnp.where(col // (GROUP_SIZE // 2) == grp, 1.0, 0.0
                        ).astype(jnp.bfloat16)
        scale = jax.lax.dot_general(s, sel, _NN,
                                    precision=jax.lax.Precision.DEFAULT,
                                    preferred_element_type=jnp.float32)
        lo = ((p32 << 28) >> 28).astype(jnp.float32) * scale
        hi = (p32 >> 4).astype(jnp.float32) * scale
        return [lo, hi]
    x = _f32(parts[0])
    if fmt == "legacy_int8":
        x = x / kvq.KV8_SCALE
    return [x]


def _head_scales(s_ref, g: int) -> jax.Array:
    """int8_tok scales ``[block_c, KV]`` -> ``[KV*G, block_c]``: row (h, g)
    carries head h's per-token scale.  An exact 0/1 matmul at f32
    precision."""
    s = s_ref[...]
    kvg, kv = s.shape[1] * g, s.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (kvg, kv), 0)
    heads = jax.lax.broadcasted_iota(jnp.int32, (kvg, kv), 1)
    sel = jnp.where(rows // g == heads, 1.0, 0.0)
    return jax.lax.dot_general(sel, s, _NT,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _scores(q_refs, parts, fmt: str, g: int) -> jax.Array:
    """``[KV*G, block_c]`` raw scores of one key stream."""
    tiles = _dequant(parts, fmt)
    s = sum(jax.lax.dot_general(qr[...].astype(jnp.float32), t, _NT,
                                preferred_element_type=jnp.float32)
            for qr, t in zip(q_refs, tiles))
    if fmt == "int8_tok":
        s = s * _head_scales(parts[1], g)
    return s


def _kernel(len_ref, *refs, scale: float, block_c: int, g: int,
            kfmt: str, vfmt: str, k2fmt: str | None, n_k: int, n_v: int,
            n_k2: int, out_dtype):
    """One batch lane, every kv head; KV blocks iterated sequentially."""
    refs = list(refs)
    q_refs = [refs.pop(0) for _ in range(_n_par(kfmt))]
    q2_refs = ([refs.pop(0) for _ in range(_n_par(k2fmt))]
               if k2fmt is not None else [])
    k_parts = [refs.pop(0) for _ in range(n_k)]
    v_parts = [refs.pop(0) for _ in range(n_v)]
    k2_parts = [refs.pop(0) for _ in range(n_k2)]
    out_ref, m_ref, l_ref, acc_ref = refs

    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = _scores(q_refs, k_parts, kfmt, g)
    if k2fmt is not None:
        s = s + _scores(q2_refs, k2_parts, k2fmt, g)
    s = s * scale                                       # [KV*G, block_c]

    # Rows at absolute index >= kv_len are masked out; this also covers the
    # padded tail of a non-dividing final block (kv_len <= C always).  V
    # rows (and int8 V scales) are zeroed too, with `where` so boundary-pad
    # garbage — NaN included — can't ride into acc.
    kv_len = len_ref[0]
    col_ok = kk * block_c + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
        < kv_len
    s = jnp.where(col_ok, s, -jnp.inf)

    m_prev = m_ref[...]                                 # [KV*G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)                             # masked rows -> 0
    corr = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe, -jnp.inf))
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    if vfmt == "int8_tok":
        p = p * jnp.where(col_ok, _head_scales(v_parts[1], g), 0.0)
    for par, vblk in enumerate(_dequant(v_parts, vfmt)):
        row_ok = kk * block_c + jax.lax.broadcasted_iota(
            jnp.int32, vblk.shape, 0) < kv_len
        vblk = jnp.where(row_ok, vblk, 0.0)
        acc_ref[par] = acc_ref[par] * corr + jax.lax.dot_general(
            p, vblk, _NN, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kk == pl.num_programs(1) - 1)
    def _emit():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        for par in range(acc_ref.shape[0]):
            out_ref[par] = (acc_ref[par] * inv).astype(out_dtype)


def _flat(x: jax.Array) -> jax.Array:
    """``[B, C, KV, x]`` -> ``[B, C, KV*x]`` (a free row-major reshape)."""
    return x.reshape(x.shape[0], x.shape[1], -1)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_c", "interpret"))
def flash_decode_pallas(q, k, v, kv_len, *, q2=None, k2=None,
                        scale: float | None = None, block_c: int = 128,
                        interpret: bool = False) -> jax.Array:
    """Split-KV decode attention.  q ``[B, KV, G, d]``; k/v cache leaves
    ``[B, C, KV, *]`` (fp/legacy-int8 arrays or kvq-encoded dicts); ``kv_len``
    a traced i32 scalar.  Optional second stream ``q2 [B, KV, G, d2]`` /
    ``k2 [B, C, KV, d2]`` (MLA rope term).  ``scale=None`` -> ``1/sqrt(d)``.

    Returns f32 ``[B, KV, G, dv]``.
    """
    b, kv_h, g, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    kfmt, vfmt = _part_fmt(k), _part_fmt(v)
    k2fmt = _part_fmt(k2) if q2 is not None else None
    k_parts, v_parts = _parts(k), _parts(v)
    k2_parts = _parts(k2) if q2 is not None else []
    c = k_parts[0].shape[1]
    dv = kvq.decoded_dim(v)
    bc = min(block_c, c)
    n_blocks = pl.cdiv(c, bc)
    n_out = _n_par(vfmt)
    kvg = kv_h * g

    operands = _block_diag(q, _n_par(kfmt))
    if q2 is not None:
        operands += _block_diag(q2, _n_par(k2fmt))
    in_specs = [pl.BlockSpec((None, kvg, x.shape[-1]),
                             lambda bi, kk: (bi, 0, 0)) for x in operands]
    for p in k_parts + v_parts + k2_parts:
        p = _flat(p)
        in_specs.append(pl.BlockSpec((None, bc, p.shape[-1]),
                                     lambda bi, kk: (bi, kk, 0)))
        operands.append(p)
    in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))   # kv_len
    operands.insert(0, jnp.asarray(kv_len, jnp.int32).reshape(1))

    kernel = functools.partial(
        _kernel, scale=scale, block_c=bc, g=g, kfmt=kfmt, vfmt=vfmt,
        k2fmt=k2fmt, n_k=len(k_parts), n_v=len(v_parts),
        n_k2=len(k2_parts), out_dtype=jnp.float32)

    w = kv_h * dv // n_out
    out = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, n_out, kvg, w),
                               lambda bi, kk: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_out, kvg, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kvg, 1), jnp.float32),      # m
                        pltpu.VMEM((kvg, 1), jnp.float32),      # l
                        pltpu.VMEM((n_out, kvg, w), jnp.float32)],  # acc
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    # Keep each row's own head block: [B, P, KV, G, KV, dv/P] -> diagonal.
    out = out.reshape(b, n_out, kv_h, g, kv_h, dv // n_out)
    out = jnp.diagonal(out, axis1=2, axis2=4)           # [B, P, G, dv/P, KV]
    out = jnp.moveaxis(out, -1, 2)                      # [B, P, KV, G, dv/P]
    # MXINT4 V: re-interleave even/odd channels.
    return jnp.moveaxis(out, 1, -1).reshape(b, kv_h, g, dv)
