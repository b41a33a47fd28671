"""Plain float32 reference for the RetNet configurations.

It imports nothing of the program under test and takes nothing the program
made: the weights are drawn again from the seed by the same published rule
(normal, std 1/sqrt(fan_in), 0.02 for the embedding and the output head,
norm gains 1, stored in bfloat16), and both deployed formats are derived
here from those masters:

  * W8A8 (prompt chunks): per-tensor int8 weights, scale absmax/127, and a
    per-tensor int8 activation scale taken over the whole chunk, int32
    accumulation;
  * MXINT4 (generated tokens): 4-bit mantissas with one power-of-two shift
    per 16 output channels, shift = floor(log2(group absmax)) clipped to
    [-9, 5], step 2^(shift-2); activations unquantized.

Everything else runs in float32 at `highest` matmul precision.  The block
is RetNet's (arXiv:2307.08621): RMSNorm, multi-scale retention with
gamma_h = 1 - 2^(-5-h), RoPE on q and k (interleaved pairs), q and k scaled
by dk^-0.5, per-head group norm, swish gate, then a GeLU FFN.

The reference is teacher-forced: a request's prompt goes through in the
chunks the configuration's chunk size makes (the activation scale is per
chunk), and its served tokens through the recurrent form written in
parallel, block by block.  It runs layer by layer, so one layer's weights
are on the device at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import chunk_schedule

FAMILIES = ("retnet",)
RMS_EPS = 1e-6
GN_EPS = 1e-6
ROPE_BASE = 10000.0
DECODE_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int                 # rows of the embedding and output head

    @property
    def dk(self) -> int:
        return self.d_model // self.n_heads

    @property
    def dv(self) -> int:
        return 2 * self.d_model // self.n_heads


def shape_of(cfg: dict) -> Shape:
    """Sizes from a configuration file's ``model`` block."""
    m = cfg["model"]
    pad = m.get("vocab_pad_multiple", 256)
    vocab = -(-m["vocab_size"] // pad) * pad
    return Shape(m["n_layers"], m["d_model"], m["n_heads"], m["d_ff"], vocab)


def lane_bytes(s: Shape, position: int, tokens: int) -> int:
    """One lane's retention state, f32 [layers, heads, dk, dv], read and
    written once: the same at every position, for any number of tokens."""
    return 2 * s.n_layers * s.n_heads * s.dk * s.dv * 4


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


class _Keys:
    """The key chain of the published init: each named child takes the next
    split of its parent's key, and each drawn tensor the next split of its
    own builder's key."""

    def __init__(self, key):
        self.key = key

    def next(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def child(self) -> "_Keys":
        return _Keys(self.next())


def _normal(keys: _Keys, shape, std):
    w = jax.random.normal(keys.next(), shape, jnp.float32) * std
    return w.astype(jnp.bfloat16)


def _linear(parent: _Keys, k: int, n: int, std=None):
    return _normal(parent.child(), (k, n), 1.0 / math.sqrt(k) if std is None
                   else std)


def _layer_masters(key, s: Shape) -> dict:
    """One block's bfloat16 masters, drawn in the published order."""
    b = _Keys(key)
    b.child()                                   # ln1 (gain 1, no draw)
    ret = b.child()
    d = s.d_model
    w = {"wq": _linear(ret, d, d), "wk": _linear(ret, d, d),
         "wv": _linear(ret, d, 2 * d), "wg": _linear(ret, d, 2 * d),
         "wo": _linear(ret, 2 * d, d)}
    b.child()                                   # ln2
    mlp = b.child()
    w["wi"] = _linear(mlp, d, s.d_ff)
    w["wd"] = _linear(mlp, s.d_ff, d)
    return w


def _root_keys(seed: int):
    """(embed key, per-layer keys, lm_head builder) of the whole model."""
    root = _Keys(jax.random.key(seed))
    k_embed = root.next()
    k_blocks = root.next()
    root.child()                                # final norm
    return k_embed, k_blocks, root.child()


def quantize_w8(w):
    """Per-tensor int8: (values, scale)."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8), scale


def quantize_w4_dequant(w):
    """MXINT4 values as real numbers (exact in bfloat16)."""
    w32 = w.astype(jnp.float32)
    k, n = w32.shape
    gmax = jnp.max(jnp.abs(w32).reshape(k, n // 16, 16), axis=-1)
    gmax = jnp.where(gmax > 0, gmax, 2.0 ** -9)
    shift = jnp.clip(jnp.frexp(gmax)[1] - 1, -9, 5).astype(jnp.float32)
    step = jnp.repeat(jnp.exp2(shift - 2.0), 16, axis=1)
    mant = jnp.clip(jnp.round(w32 / step), -8, 7)
    return (mant * step).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("s",))
def _deployed_layer(key, s: Shape):
    w = _layer_masters(key, s)
    w8 = {n: quantize_w8(v) for n, v in w.items()}
    w4 = {n: quantize_w4_dequant(v) for n, v in w.items()}
    return w8, w4


def model_ends(seed: int, s: Shape):
    """(embedding bf16, lm_head as (w8, scale), lm_head MXINT4 bf16,
    per-layer keys)."""
    k_embed, k_blocks, lm = _root_keys(seed)
    embed = (jax.random.normal(k_embed, (s.vocab, s.d_model), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
    head = _normal(lm, (s.d_model, s.vocab), 0.02)
    layer_keys = jax.random.split(k_blocks, s.n_layers)
    return embed, quantize_w8(head), quantize_w4_dequant(head), layer_keys


# ---------------------------------------------------------------------------
# Forward pieces (float32, highest precision)
# ---------------------------------------------------------------------------


def _rms_emit(x):
    x32 = x.astype(jnp.float32)
    sig = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + RMS_EPS)
    return x32.astype(x.dtype), sig          # norm gains are 1


def _act_q(x):
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8), scale


def lin_w8a8(wq, x, row=None):
    """Prompt-chunk linear: int8 x int8 -> int32, scaled per tensor."""
    vals, wscale = wq
    xq, ascale = _act_q(x)
    acc = jax.lax.dot_general(xq, vals, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * (ascale * wscale)
    return y if row is None else y * row[..., None]


def lin_w4(w, x, row=None):
    """Generated-token linear: activations times the MXINT4 values."""
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    return y if row is None else y * row[..., None]


def _rope(x, pos, dk):
    th = jnp.power(ROPE_BASE, -2.0 * jnp.arange(dk // 2, dtype=jnp.float32)
                   / dk)
    ang = pos.astype(jnp.float32)[:, None] * th            # [T, dk/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _retention(q, k, v, state, gamma):
    """Recurrent retention over T positions, written in parallel.

    q, k [T, H, dk]; v [T, H, dv]; state [H, dk, dv] before the first
    position.  y_n = q_n (gamma^n S_0 + sum_{m<=n} gamma^(n-m) k_m^T v_m),
    n = 1..T; returns (y [T, H, dv], state after position T)."""
    hp = jax.lax.Precision.HIGHEST
    t = q.shape[0]
    n = jnp.arange(1, t + 1, dtype=jnp.float32)
    lg = jnp.log(gamma)                                      # [H]
    diff = n[:, None] - n[None, :]
    decay = jnp.where(diff[None] >= 0,
                      jnp.exp(jnp.where(diff[None] >= 0, diff[None], 0.0)
                              * lg[:, None, None]), 0.0)     # [H, T, T]
    scores = jnp.einsum("nhd,mhd->hnm", q, k, precision=hp) * decay
    inner = jnp.einsum("hnm,mhv->nhv", scores, v, precision=hp)
    cross = jnp.einsum("nhd,hdv->nhv",
                       q * jnp.exp(n[:, None] * lg[None, :])[:, :, None],
                       state, precision=hp)
    out_decay = jnp.exp((t - n)[:, None] * lg[None, :])      # [T, H]
    new = (jnp.exp(t * lg)[:, None, None] * state
           + jnp.einsum("mhd,mhv->hdv", k * out_decay[:, :, None], v,
                        precision=hp))
    return inner + cross, new


def _block(w, x, state, pos, s: Shape, lin):
    """One RetNet block over T positions of one request.  x [T, D] bf16."""
    h, dk, dv = s.n_heads, s.dk, s.dv
    xs, sig = _rms_emit(x)
    q = lin(w["wq"], xs, sig).reshape(-1, h, dk) * dk ** -0.5
    k = lin(w["wk"], xs, sig).reshape(-1, h, dk) * dk ** -0.5
    v = lin(w["wv"], xs, sig).reshape(-1, h, dv)
    g = lin(w["wg"], xs, sig)
    q, k = _rope(q, pos, dk), _rope(k, pos, dk)
    gamma = 1.0 - jnp.exp2(-5.0 - jnp.arange(h, dtype=jnp.float32))
    y, state = _retention(q, k, v, state, gamma)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + GN_EPS)
    y = y.reshape(-1, 2 * s.d_model) * jax.nn.silu(g)
    x = x + lin(w["wo"], y)                                  # f32 from here
    xs2, sig2 = _rms_emit(x)
    up = jax.nn.gelu(lin(w["wi"], xs2, sig2))
    x = x + lin(w["wd"], up)
    return x.astype(jnp.bfloat16), state


@functools.partial(jax.jit, static_argnames=("s",))
def _prompt_layer(w8, x, state, pos0, s: Shape):
    pos = pos0 + jnp.arange(x.shape[0])
    return _block(w8, x, state, pos, s, lin_w8a8)


@functools.partial(jax.jit, static_argnames=("s",))
def _token_layer(w4, x, state, pos0, s: Shape):
    pos = pos0 + jnp.arange(x.shape[0])
    return _block(w4, x, state, pos, s, lin_w4)


def _final_rms(x):
    x32 = x.astype(jnp.float32)
    sig = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + RMS_EPS)
    return (x32 * sig[..., None]).astype(x.dtype)


@jax.jit
def _prompt_gap(head8, x_last, tok):
    logits = lin_w8a8(head8, _final_rms(x_last[None]))[0]
    return jnp.max(logits) - logits[tok]


@jax.jit
def _token_gaps(head4, xs, toks):
    logits = lin_w4(head4, _final_rms(xs))                   # [T, V]
    return jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, toks[:, None], axis=-1)[:, 0]


def served_gaps(seed: int, s: Shape, requests, chunk: int
                ) -> list[np.ndarray]:
    """Teacher-forced gaps of served tokens.

    ``requests``: (prompt ids, served ids) pairs.  For each request, returns
    float32 [n_served]: how far the served token's logit lies below the
    reference's best logit at the position that produced it (0 where the
    reference would have chosen the same token).
    """
    embed, head8, head4, layer_keys = model_ends(seed, s)
    streams = []
    for prompt, served in requests:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        chunks, off = [], 0
        for c in chunk_schedule(len(prompt), chunk):
            chunks.append((embed[jnp.asarray(prompt[off:off + c])], off))
            off += c
        blocks = []
        fed = served[:-1]                        # the last one is never fed
        for b0 in range(0, len(fed), DECODE_BLOCK):
            ids = np.zeros(DECODE_BLOCK, np.int32)
            part = fed[b0:b0 + DECODE_BLOCK]
            ids[:len(part)] = part
            blocks.append((embed[jnp.asarray(ids)], len(prompt) + b0,
                           len(part)))
        streams.append({"chunks": chunks, "blocks": blocks})
    del embed

    zero_state = jnp.zeros((s.n_heads, s.dk, s.dv), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for li in range(s.n_layers):
            w8, w4 = _deployed_layer(layer_keys[li], s)
            for st in streams:
                state = zero_state
                chunks = []
                for x, p0 in st["chunks"]:
                    x, state = _prompt_layer(w8, x, state, jnp.int32(p0), s)
                    chunks.append((x, p0))
                st["chunks"] = chunks
                blocks = []
                for x, p0, n in st["blocks"]:
                    # Only the last block is partial, and nothing follows it.
                    x, state = _token_layer(w4, x, state, jnp.int32(p0), s)
                    blocks.append((x, p0, n))
                st["blocks"] = blocks
            del w8, w4
        out = []
        for st, (_, served) in zip(streams, requests):
            served = np.asarray(served, np.int32)
            x_last = st["chunks"][-1][0][-1]
            g = [np.asarray(_prompt_gap(head8, x_last,
                                        jnp.int32(served[0])))[None]]
            for i, (x, _, n) in enumerate(st["blocks"]):
                b0 = i * DECODE_BLOCK
                toks = np.zeros(DECODE_BLOCK, np.int32)
                toks[:n] = served[1 + b0:1 + b0 + n]
                g.append(np.asarray(_token_gaps(head4, x,
                                                jnp.asarray(toks)))[:n])
            out.append(np.concatenate(g).astype(np.float32))
    return out
