"""RetNet block — the paper's target model (C5), built on core/retention.py.

Multi-scale retention with RoPE-rotated q/k (the paper's RoPE unit serves
exactly this block), v/gate at 2*d_model (RetNet's d_v = 2d), per-head
GroupNorm, swish gate, then a GeLU FFN.  Prefill/training uses the chunkwise
form (the Pallas kernel when on TPU); decode uses the O(1) recurrent form —
the reason the paper chose RetNet for bandwidth-starved edge decode.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import online_rope as orp
from repro.core import retention as ret
from repro.core.hsa import HSAEngine
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.modules import ParamBuilder

Params = dict[str, Any]


def retention_init(b: ParamBuilder, cfg: ModelConfig) -> None:
    d = cfg.d_model
    b.linear("wq", d, d, "embed", "heads")
    b.linear("wk", d, d, "embed", "heads")
    b.linear("wv", d, 2 * d, "embed", "heads")
    b.linear("wg", d, 2 * d, "embed", "heads")
    b.linear("wo", 2 * d, d, "heads", "embed")


def _project(p: Params, x_star, sig_inv, engine: HSAEngine, phase: str,
             cfg: ModelConfig):
    b, s, d = x_star.shape
    h = cfg.n_heads
    dk, dv = d // h, 2 * d // h
    q = engine.linear(p["wq"], x_star, phase, row_scale=sig_inv)
    k = engine.linear(p["wk"], x_star, phase, row_scale=sig_inv)
    v = engine.linear(p["wv"], x_star, phase, row_scale=sig_inv)
    g = engine.linear(p["wg"], x_star, phase, row_scale=sig_inv)
    q = q.reshape(b, s, h, dk) * (dk ** -0.5)
    k = k.reshape(b, s, h, dk) * (dk ** -0.5)   # RetNet scales k too
    v = v.reshape(b, s, h, dv)
    return q, k, v, g


def retention_apply(p: Params, x_star, sig_inv, engine: HSAEngine, phase: str,
                    cfg: ModelConfig, *, rope_sin=None, rope_cos=None,
                    cache: Params | None = None,
                    valid_len: jax.Array | None = None,
                    collect_states: bool = False
                    ) -> tuple[jax.Array, Params]:
    """Full-sequence (chunkwise) retention.  Returns (out, final-state cache).

    ``cache`` (chunked prefill) carries the O(1) retention state across
    chunks — outputs and the new state then include the decayed contribution
    of everything before this chunk.  ``valid_len`` (bucketed prefill) masks
    padded tail tokens out of the state: their k/v are zeroed and the final
    state is re-scaled by ``gamma^(valid_len - s)`` to undo the extra decay
    the padded steps applied (exact — see decay recurrence).

    ``collect_states`` (speculative verify; small s) runs the *recurrent*
    form instead and adds ``'s_all'`` — the state snapshot after every
    position, ``[B, S, H, dk, dv]`` — to the cache so a rejected draft rolls
    back to the exact state the accepted prefix produced (re-decaying the
    final state would amplify fp error by ``gamma^-(s-a)``).
    """
    b, s, d = x_star.shape
    h = cfg.n_heads
    q, k, v, g = _project(p, x_star, sig_inv, engine, phase, cfg)
    if rope_sin is not None:
        q = orp.apply_rope(q, rope_sin[None, :, None, :], rope_cos[None, :, None, :])
        k = orp.apply_rope(k, rope_sin[None, :, None, :], rope_cos[None, :, None, :])
    gamma = ret.head_decays(h)
    if valid_len is not None:
        keep = (jnp.arange(s) < valid_len)[None, :, None, None]
        k = k * keep
        v = v * keep
    qt, kt, vt = (jnp.moveaxis(t, 2, 1) for t in (q, k, v))   # [B,H,S,d*]
    state0 = cache["s"] if cache is not None else None
    s_all = None
    chunk = min(128, s)
    if collect_states:
        y, state, s_all = ret.retention_recurrent(qt, kt, vt, gamma,
                                                  state=state0,
                                                  return_states=True)
    elif s % chunk == 0:
        y, state = ops.retention_chunkwise(qt, kt, vt, gamma, chunk=chunk,
                                           state=state0,
                                           impl=engine.config.kernel_impl)
    elif state0 is None:
        y = ret.retention_parallel(qt, kt, vt, gamma)
        _, state = ret.retention_recurrent(qt, kt, vt, gamma)
    else:
        y, state = ret.retention_chunkwise(qt, kt, vt, gamma, chunk=s,
                                           state=state0)
    if valid_len is not None:
        undo = jnp.exp((valid_len - s).astype(jnp.float32) * jnp.log(gamma))
        state = state * undo[None, :, None, None]
    y = ret.group_norm_heads(y)
    y = jnp.moveaxis(y, 1, 2).reshape(b, s, 2 * d)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(y.dtype)
    out = engine.linear(p["wo"], y, phase)
    new_cache = {"s": state}
    if s_all is not None:
        new_cache["s_all"] = s_all
    return out, new_cache


def retention_decode(p: Params, x_star, sig_inv, engine: HSAEngine,
                     cfg: ModelConfig, cache: Params, *,
                     rope_sin=None, rope_cos=None
                     ) -> tuple[jax.Array, Params]:
    """O(1)-state recurrent step — the paper's decode workload."""
    b, _, d = x_star.shape
    h = cfg.n_heads
    q, k, v, g = _project(p, x_star, sig_inv, engine, "decode", cfg)
    if rope_sin is not None:
        q = orp.apply_rope(q, rope_sin, rope_cos)
        k = orp.apply_rope(k, rope_sin, rope_cos)
    gamma = ret.head_decays(h)
    y, state = ret.retention_recurrent_step(
        q[:, 0], k[:, 0], v[:, 0], cache["s"], gamma)
    y = ret.group_norm_heads(y)
    y = y.reshape(b, 1, 2 * d)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(y.dtype)
    out = engine.linear(p["wo"], y, "decode")
    return out, {"s": state}


def retention_make_cache(cfg: ModelConfig, batch: int) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    return {"s": jnp.zeros((batch, h, d // h, 2 * d // h), jnp.float32)}
