"""Public jit'd wrappers around the Pallas kernels.

Implementation selection (`impl`):
  'pallas'  — the TPU kernel; off a TPU backend it runs in interpret mode,
              so the kernel path stays testable (and auditable) on CPU
  'ref'     — the pure-jnp oracle from ref.py
  'auto'    — 'pallas' on a real TPU backend, 'ref' otherwise.  The ref path
              streams the identical packed-int4 + exponent buffers, so dry-run
              roofline byte counts match what the TPU kernel would move.

These wrappers own shape plumbing: M-padding to the block size, optional
epilogue operands defaulted to identities, and flattening of leading batch
dims.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.mxint4 import GROUP_SIZE, MXINT4Weight
from repro.kernels import ref as _ref
from repro.kernels.mxint4_matmul import mxint4_matmul_pallas
from repro.kernels.retention_kernel import retention_chunkwise_pallas
from repro.kernels.rmsnorm_stats import rmsnorm_stats_pallas


# Widest mxint4 output block: its exponent block (bn/32 bytes) is then one
# full 128-lane tile, the narrowest Mosaic accepts short of the whole row.
MXINT4_BLOCK_N = 4096


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _interpret(interpret: bool) -> bool:
    """Kernels run compiled on a TPU backend and interpreted anywhere else."""
    return interpret or jax.default_backend() != "tpu"


def mesh_impl(impl: str, n_devices: int) -> str:
    """The `impl` a program partitioned over ``n_devices`` can run.

    XLA cannot partition a compiled Mosaic kernel (it asks for a shard_map
    around the call, which the sharded serving stack does not have yet), so
    on a TPU mesh of more than one device 'auto' takes the jnp path and
    'pallas' is refused.  Interpret-mode kernels (any other backend) lower
    to ordinary HLO and partition like it, so nothing changes there.
    """
    if n_devices <= 1 or jax.default_backend() != "tpu":
        return impl
    if impl == "pallas":
        raise ValueError(
            f"kernel_impl='pallas' on a {n_devices}-device TPU mesh: Mosaic "
            "kernels cannot be partitioned automatically; use 'auto' or "
            "'ref'")
    return "ref"


def _pad_rows(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    m = x.shape[0]
    pad = (-m) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


def mxint4_matmul(
    x: jax.Array,
    q: MXINT4Weight,
    out_scale: jax.Array | None = None,
    row_scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    *,
    out_dtype=jnp.float32,
    impl: str = "auto",
    interpret: bool = False,
    block_m: int = 8,
    block_n: int | None = None,
    block_k: int = 512,
) -> jax.Array:
    """Decode-path quantized matmul with the Eq. (4) fused epilogue.

    ``x`` may have leading batch dims; they are flattened into M.
    ``block_n=None`` takes all of N up to `MXINT4_BLOCK_N` and tiles wider
    layers by it, zero-padding N to a multiple (a per-call weight copy, so
    only layers whose width is not a multiple of 4096 pay it).
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = q.shape[1]
    x2 = x.reshape(-1, k)
    rs = None if row_scale is None else row_scale.reshape(-1)

    impl = _resolve(impl)
    if impl == "ref":
        y = _ref.mxint4_matmul_ref(x2, q, out_scale, rs, bias, out_dtype)
        return y.reshape(*lead, n)

    x2p, m = _pad_rows(x2, block_m)
    os = jnp.ones((n,), jnp.float32) if out_scale is None else jnp.broadcast_to(
        jnp.asarray(out_scale, jnp.float32), (n,))
    bs = jnp.zeros((n,), jnp.float32) if bias is None else jnp.asarray(bias, jnp.float32)
    if rs is None:
        rsp = jnp.ones((x2p.shape[0],), jnp.float32)
    else:
        rsp = jnp.pad(rs.astype(jnp.float32), (0, x2p.shape[0] - m),
                      constant_values=1.0)
    packed, exps = q.packed, q.exps_packed
    if block_n is None:
        block_n = min(n, MXINT4_BLOCK_N)
    pad_n = (-n) % block_n
    if pad_n:
        packed = jnp.pad(packed, ((0, 0), (0, pad_n // 2)))
        exps = jnp.pad(exps, ((0, 0), (0, pad_n // (2 * GROUP_SIZE))))
        os, bs = jnp.pad(os, (0, pad_n)), jnp.pad(bs, (0, pad_n))
    y = mxint4_matmul_pallas(
        x2p, packed, exps, os, rsp, bs,
        block_m=block_m, block_n=block_n, block_k=block_k,
        out_dtype=out_dtype, interpret=_interpret(interpret),
    )
    return y[:m, :n].reshape(*lead, n)


def w8a8_matmul(
    x_q: jax.Array,
    w_q: jax.Array,
    combined_scale: jax.Array,
    row_scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    *,
    out_dtype=jnp.float32,
    impl: str = "auto",
    interpret: bool = False,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
) -> jax.Array:
    """Prefill MMM path (int8 MXU dot with the Eq. (4) drain epilogue).

    'pallas' (what 'auto' resolves to on TPU) runs the explicit
    output-stationary kernel (kernels/w8a8_matmul.py); 'ref' (and 'auto'
    elsewhere) the jnp int8 dot, which XLA maps to the MXU natively.  Both
    accumulate exactly in int32, so they agree bit for bit before the
    epilogue."""
    lead = x_q.shape[:-1]
    k = x_q.shape[-1]
    n = w_q.shape[1]
    x2 = x_q.reshape(-1, k)
    rs = None if row_scale is None else row_scale.reshape(-1)

    if _resolve(impl) == "ref":
        y = _ref.w8a8_matmul_ref(x2, w_q, combined_scale, rs, bias, out_dtype)
        return y.reshape(*lead, n)

    from repro.kernels.w8a8_matmul import w8a8_matmul_pallas
    x2p, m = _pad_rows(x2, block_m)
    os = jnp.broadcast_to(jnp.asarray(combined_scale, jnp.float32), (n,))
    bs = jnp.zeros((n,), jnp.float32) if bias is None \
        else jnp.asarray(bias, jnp.float32)
    if rs is None:
        rsp = jnp.ones((x2p.shape[0],), jnp.float32)
    else:
        rsp = jnp.pad(rs.astype(jnp.float32), (0, x2p.shape[0] - m),
                      constant_values=1.0)
    y = w8a8_matmul_pallas(x2p, w_q, os, rsp, bs, block_m=block_m,
                           block_n=block_n, block_k=block_k,
                           out_dtype=out_dtype,
                           interpret=_interpret(interpret))
    return y[:m].reshape(*lead, n)


def retention_chunkwise(
    q: jax.Array,          # [B, H, S, dk]
    k: jax.Array,
    v: jax.Array,          # [B, H, S, dv]
    gamma: jax.Array,      # [H]
    *,
    chunk: int = 128,
    state: jax.Array | None = None,
    impl: str = "auto",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "ref" or state is not None:
        # The kernel owns zero-initialized state; warm-state callers (decode
        # chunk continuation) use the oracle path.
        return _ref.retention_chunkwise_ref(q, k, v, gamma, chunk=chunk, state=state)

    b, h, s, dk = q.shape
    dv = v.shape[-1]
    log_g = jnp.broadcast_to(jnp.log(gamma.astype(jnp.float32))[None, :],
                             (b, h)).reshape(b * h)
    y, st = retention_chunkwise_pallas(
        q.reshape(b * h, s, dk), k.reshape(b * h, s, dk), v.reshape(b * h, s, dv),
        log_g, chunk=chunk, out_dtype=jnp.float32,
        interpret=_interpret(interpret),
    )
    return (y.reshape(b, h, s, dv).astype(v.dtype),
            st.reshape(b, h, dk, dv))


def flash_decode(
    q: jax.Array,          # GQA: [B, KV, G, d] | MLA: [B, H, r]
    k,                     # cache leaf: fp/int8 array or kvq-encoded dict
    v,
    kv_len: jax.Array,     # traced i32 scalar — valid cache prefix length
    *,
    q2: jax.Array | None = None,   # MLA rope query [B, H, dr]
    k2=None,                       # MLA rope key cache leaf [B, C, dr]
    scale=None,                    # None -> s/sqrt(d); MLA passes 1/sqrt(dn+dr)
    impl: str = "auto",
    interpret: bool = False,
    block_c: int = 128,
) -> jax.Array:
    """Single-token decode attention over the first ``kv_len`` cache rows —
    the MVM-phase hot loop (kernels/flash_decode.py).

    Two layouts (see ref.flash_decode_ref): GQA with ``[B, C, KV, d]`` cache
    leaves, and MLA (``q.ndim == 3``) attending in the compressed latent
    space with the shared rope key as a second score stream.  Cache leaves
    may be kvq-quantized dicts — the Pallas path dequantizes inside its KV
    block loads; the ref path is the bit-exact jnp oracle the engine decode
    loops are token-identical against.

    'pallas' off-TPU automatically runs in interpret mode, so the kernel
    path stays testable (and auditable) on CPU.
    """
    if _resolve(impl) == "ref":
        return _ref.flash_decode_ref(q, k, v, kv_len, q2=q2, k2=k2,
                                     scale=scale)
    from repro.kernels.flash_decode import flash_decode_pallas
    interpret = _interpret(interpret)
    sc = None if scale is None else float(scale)
    if q.ndim == 4:
        return flash_decode_pallas(q, k, v, kv_len, scale=sc,
                                   block_c=block_c, interpret=interpret)
    # MLA: insert a singleton kv-head axis around the kernel call.
    add_kv = lambda leaf: jax.tree.map(lambda x: x[:, :, None], leaf)
    out = flash_decode_pallas(
        q[:, None], add_kv(k), add_kv(v), kv_len, q2=q2[:, None],
        k2=add_kv(k2), scale=sc, block_c=block_c, interpret=interpret)
    return out[:, 0]


def rmsnorm_stats(
    y: jax.Array, *, eps: float = 1e-6, impl: str = "auto", interpret: bool = False
) -> jax.Array:
    """sigma^{-1} over the last axis; leading dims preserved."""
    lead = y.shape[:-1]
    y2 = y.reshape(-1, y.shape[-1])
    if _resolve(impl) == "ref":
        return _ref.rmsnorm_stats_ref(y2, eps).reshape(lead)
    y2p, m = _pad_rows(y2, 8)
    out = rmsnorm_stats_pallas(y2p, block_m=min(256, y2p.shape[0]),
                               block_d=min(512, y2.shape[1]), eps=eps,
                               interpret=_interpret(interpret))
    return out[:m, 0].reshape(lead)
