"""Pallas TPU kernel: MXINT4 dequant-fused matmul — the HSA MVM dataflow (C2).

This is the TPU realization of the paper's decode dataflow (Fig. 4c): packed
int4 mantissas and 4-bit group-shift exponents stream HBM -> VMEM (4.25
bits/weight instead of 8/16), dequantization happens in VMEM immediately
before the MXU dot, and the Eq. (4) fused-RMSNorm epilogue
(``* out_scale * row_scale + bias``) is applied in-register on the final K
step, so the normalized activation tensor never makes an extra HBM
round-trip.  At decode M is 8 rows after padding, so the kernel must spend
less vector and MXU time per weight than the 0.53 bytes of it take to
stream; everything below serves that.

Tiling: grid ``(M/bm, N/bn, K/bk)``, K innermost/sequential with fp32 VMEM
accumulators (output-stationary — the same dataflow class as the paper's PE
array).  The packed operands are blocked ``(bk, bn/2)`` (mantissa bytes) and
``(bk, bn/32)`` (exponent bytes); Mosaic needs each block's last dim to be a
multiple of 128 or the whole array, so ``bn`` is either ``N`` or a multiple
of 4096 (`ops.mxint4_matmul` picks it).

Dequantization, four packed bytes at a time.  A packed block read as int32
holds in each word the bytes of four consecutive rows ``4s..4s+3`` of one
packed column ``j`` (channels ``2j`` and ``2j+1``, both in group ``j // 8``).
Each word becomes four words of two bf16 weights each with three integer
ops apiece (shift, mask, xor): the nibble lands in the top four mantissa
bits and the group's exponent fills the exponent field, so a half reads

    2^(c - 7) * (1 + n'/16) = 1.5 * 2^(c - 7) + m * 2^(c - 11),

with ``c`` the shift code, ``m`` the two's-complement mantissa and
``n' = m + 8`` its offset-binary form (the xor flips the nibble's top bit).
``m * 2^(c - 11) = m * 2^(S_g - 2)`` is the weight; the offset
``1.5 * 2^(c - 7)`` is summed once per K step as ``x @ offsets`` on the
exponent bytes (16x fewer columns) and subtracted in the epilogue.  The
words for rows ``(4s, 4s+2)`` and ``(4s+1, 4s+3)`` are bf16 tiles of the
even and the odd rows of K; the activation's columns are permuted to match
(within each 128-wide K chunk, one lane gather per vreg).

Exponents reach their lanes without the MXU: per K step the exponent
words are spread to one word per group (two lane gathers per 128 groups),
then each 128-column slice gathers its 16 groups' words (two gathers per
mantissa vreg, one for each row pairing).  Per packed int32 word — eight
weights — the vector work is 12 integer ops and 2 lane gathers, about 2
ops per weight; the MXU sees each weight once, as bf16.

The activation stays float32.  Every dequantized weight is exact in bf16
(4 significant bits), and ``x`` is split exactly into three bf16 parts
``hi + mid + lo`` (8 + 8 + 8 significand bits) stacked as 3*bm LHS rows of
one dot, so each weight tile is loaded into the MXU once and every product
is exact in the fp32 accumulator: the result is an fp32 matmul, not a
bf16-rounded one, at any ``default_matmul_precision``.  One inner loop
serves every M: `ops.mxint4_matmul` blocks rows by 8, so a prompt-size M
runs more row blocks of the same MVM.

Outputs: even and odd channels accumulate in two ``[bm, bn/2]`` planes (the
low and high nibbles of the same bytes); the epilogue interleaves them into
channel order with lane gathers, so the call returns ``[M, N]`` directly.

VMEM per grid step: the mantissa and exponent blocks ``bk * bn * 0.53``
bytes, double-buffered (2.2 MB at the default 512 x 4096), plus one slice's
dequantized bf16 tiles (``bk x 128`` x 2 planes, 256 KB) and the two fp32
accumulators (``2 * bm * bn/2`` and ``2 * bm * bn/32`` words).

The ASIC splits the shift into 2 LSBs (pre-shift) + 2 MSBs (accumulation-row
gating) because full shifters per PE are expensive in silicon; here the whole
exponent goes into the bf16 exponent field, exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params

from repro.core.mxint4 import EXP_BIAS, GROUP_SIZE, MANT_SHIFT

_LANES = 128
# Two bf16 weights per int32 word (low half: the lower row of the pair).
_MANT_FIELD = 0x00780078     # top four mantissa bits of each half
_EXP_FIELD = 0x7F807F80      # exponent field of each half
_NIBBLE_TOP = 0x00400040     # the nibble's sign bit, flipped to offset binary
# Exponent field = shift code + _CODE_TO_EXP (120), per byte of an exponent
# word, so a half reads 2^(code - 7) * (1 + n'/16) (module docstring) and
# its nibble term m * 2^(code - EXP_BIAS - MANT_SHIFT) is the weight.
_CODE_TO_EXP = 127 + 4 - EXP_BIAS - MANT_SHIFT


def pow2_from_codes(codes: jax.Array, offset: int) -> jax.Array:
    """Exact ``2^(codes + offset - 127)`` for int32 ``codes`` (f32 bit
    assembly: no transcendental, no narrow-int -> float cast)."""
    return jax.lax.bitcast_convert_type((codes + offset) << 23, jnp.float32)


def _lanes(rows: int) -> jax.Array:
    """Lane index, [rows, 128], one broadcast row."""
    return jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1), (rows, _LANES))


def _pad(v: jax.Array) -> jax.Array:
    """Zero-pad the lanes of ``v`` to a multiple of 128."""
    pad = -v.shape[1] % _LANES
    if not pad:
        return v
    return jnp.concatenate([v, jnp.zeros((v.shape[0], pad), v.dtype)], axis=1)


def _take(v: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-row lane gather ``v[r, idx[r, l]]`` within one 128-lane vreg."""
    return jnp.take_along_axis(_pad(v), idx, axis=1, mode="promise_in_bounds")


def _split3(x: jax.Array) -> jax.Array:
    """fp32 ``[r, k]`` -> bf16 ``[3r, k]``: ``hi; mid; lo`` with
    ``hi + mid + lo == x`` exactly."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _even_odd(x: jax.Array) -> jax.Array:
    """Columns of each ``min(k, 128)``-wide chunk reordered even, then odd."""
    r, k = x.shape
    w = min(k, _LANES)
    lane = _lanes(r)
    idx = (2 * lane) % w + (lane % w >= w // 2).astype(jnp.int32)
    return jnp.concatenate(
        [_take(x[:, c * w:(c + 1) * w], idx)[:, :w] for c in range(k // w)],
        axis=1)


def _group_words(e32: jax.Array, n_groups: int) -> list[jax.Array]:
    """Exponent words ``[bk/4, w]`` (byte ``i`` of a word: row ``4s+i``, two
    codes per byte) -> per 128 groups, the exponent fields of the row pairs
    ``(4s, 4s+2)`` and ``(4s+1, 4s+3)`` with the nibble flip folded in."""
    lane = _lanes(e32.shape[0])
    nibble = (lane % 2) * 4
    out = []
    for h in range(-(-n_groups // _LANES)):
        src = e32[:, (h // 2) * _LANES:(h // 2 + 1) * _LANES]
        byte = _take(src, (h % 2) * (_LANES // 2) + lane // 2)
        fields = (((byte >> nibble) & 0x0F0F0F0F)
                  + _CODE_TO_EXP * 0x01010101)        # one biased exp per byte
        out.append((((fields << 7) & _EXP_FIELD) ^ _NIBBLE_TOP,
                    ((fields >> 1) & _EXP_FIELD) ^ _NIBBLE_TOP))
    return out


def _kernel(x_ref, packed_ref, exps_ref, oscale_ref, rscale_ref, bias_ref,
            out_ref, acc_ref, offset_ref, *, n_k: int, out_dtype):
    """One (bm, bn) output tile; K iterated sequentially via the grid."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        offset_ref[...] = jnp.zeros_like(offset_ref)

    bm, bk = x_ref.shape
    half = packed_ref.shape[1]                              # bn / 2
    slice_w = min(_LANES, half)
    n_groups = 2 * exps_ref.shape[1]
    dims = (((1,), (0,)), ((), ()))

    def dot3(lhs, rhs):                  # [3bm, K] bf16 parts -> fp32 [bm, N]
        y = jax.lax.dot_general(lhs, rhs, dims,
                                preferred_element_type=jnp.float32)
        return (y[2 * bm:] + y[bm:2 * bm]) + y[:bm]

    x = x_ref[...].astype(jnp.float32)                      # [bm, bk]
    x_perm = _split3(_even_odd(x))

    # The offsets 1.5 * 2^(code - 7), summed per group over this K step.
    codes = exps_ref[...].astype(jnp.int32) & 0xFF          # [bk, bn/32]
    x_parts = _split3(x)
    for par, code in enumerate((codes & 0x0F, codes >> 4)):
        offsets = (pow2_from_codes(code, _CODE_TO_EXP) * 1.5).astype(jnp.bfloat16)
        offset_ref[par] += dot3(x_parts, offsets)

    words = _group_words(pltpu.bitcast(exps_ref[...], jnp.int32), n_groups)
    lane = _lanes(bk // 4)
    chunk = min(bk, _LANES) // 2
    for t in range(half // slice_w):
        cols = pl.ds(t * slice_w, slice_w)
        g0 = t * slice_w // 8                               # first group
        idx = g0 % _LANES + lane // 8
        exp_a, exp_b = (_take(w, idx)[:, :slice_w] for w in words[g0 // _LANES])
        p = pltpu.bitcast(packed_ref[:, cols], jnp.int32)   # [bk/4, slice_w]
        # Nibble of byte 0 / 2 (rows 4s, 4s+2) and of byte 1 / 3 to bits
        # 3..6 / 19..22: the low nibbles (even channels), then the high.
        for par, (pa, pb) in enumerate(((p << 3, p >> 5), (p >> 1, p >> 9))):
            even = pltpu.bitcast((pa & _MANT_FIELD) ^ exp_a,
                                 jnp.bfloat16)              # rows k = 2r
            odd = pltpu.bitcast((pb & _MANT_FIELD) ^ exp_b,
                                jnp.bfloat16)               # rows k = 2r + 1
            w = jnp.concatenate(
                [piece for c in range(bk // (2 * chunk))
                 for piece in (even[c * chunk:(c + 1) * chunk],
                               odd[c * chunk:(c + 1) * chunk])], axis=0)
            acc_ref[par, :, cols] += dot3(x_perm, w)

    # ---- Eq. (4) epilogue on the last K step ------------------------------
    @pl.when(kk == n_k - 1)
    def _epilogue():
        lane_m = _lanes(bm)
        odd_lane = lane_m % 2 == 1
        sums = [_pad(offset_ref[par]) for par in (0, 1)]    # per byte
        offsets = []                                        # per 128 groups
        for h in range(-(-n_groups // _LANES)):
            blk = slice((h // 2) * _LANES, (h // 2 + 1) * _LANES)
            i = (h % 2) * (_LANES // 2) + lane_m // 2
            offsets.append(jnp.where(odd_lane, _take(sums[1][:, blk], i),
                                     _take(sums[0][:, blk], i)))
        planes = []                                         # per slice
        for t in range(half // slice_w):
            g0 = t * slice_w // 8
            off = _take(offsets[g0 // _LANES], g0 % _LANES + lane_m // 8)
            cols = pl.ds(t * slice_w, slice_w)
            planes.append([_pad(acc_ref[par, :, cols]) - off
                           for par in (0, 1)])
        bn = 2 * half
        for q in range(-(-bn // _LANES)):                   # channel order
            width = min(_LANES, bn - q * _LANES)
            t, first = divmod(q * _LANES // 2, slice_w)
            i = first + lane_m // 2
            y = jnp.where(odd_lane, _take(planes[t][1], i),
                          _take(planes[t][0], i))[:, :width]
            cols = pl.ds(q * _LANES, width)
            out_ref[:, cols] = (y * oscale_ref[:, cols] * rscale_ref[...]
                                + bias_ref[:, cols]).astype(out_dtype)


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
    half = bn // 2
    if half > _LANES and half % _LANES:
        raise ValueError(f"block_n {bn} must be a multiple of "
                         f"{2 * _LANES} above {2 * _LANES}")
    if bk % 4 or (bk > _LANES and bk % _LANES):
        raise ValueError(f"block_k {bk} must be a multiple of 4, and of "
                         f"{_LANES} above it")
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, out_dtype=out_dtype),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),             # x
            pl.BlockSpec((bk, half), lambda i, j, kk: (kk, j)),           # packed
            pl.BlockSpec((bk, bn // (2 * GROUP_SIZE)),
                         lambda i, j, kk: (kk, j)),                       # exps
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),               # out_scale
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),               # row_scale
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),               # bias
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((2, bm, half), jnp.float32),
                        pltpu.VMEM((2, bm, bn // (2 * GROUP_SIZE)),
                                   jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed, exps_packed, out_scale.reshape(1, n),
      row_scale.reshape(m, 1), bias.reshape(1, n))
