"""Pallas kernel validation: interpret-mode vs pure-jnp oracles (ref.py),
swept over shapes, dtypes and epilogue combinations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mxint4 as mx
from repro.core import retention as ret
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _w(k, n, scale=0.1):
    return jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32) * scale)


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (1, 64, 96, 8, 32, 32),       # matvec (decode MVM)
    (5, 64, 96, 8, 32, 32),       # non-divisible M -> padding path
    (16, 128, 256, 8, 64, 64),    # multi-block all dims
    (8, 256, 64, 8, 64, 128),     # K-major accumulation
    (3, 32, 32, 8, 32, 32),       # single block
])
def test_mxint4_matmul_shapes(m, k, n, bm, bn, bk):
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    q = mx.quantize_mxint4(_w(k, n))
    y_ref = ops.mxint4_matmul(x, q, impl="ref")
    y_pal = ops.mxint4_matmul(x, q, impl="pallas", interpret=True,
                              block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
def test_mxint4_matmul_dtypes(x_dtype):
    x = jnp.asarray(RNG.normal(size=(4, 64)).astype(np.float32)).astype(x_dtype)
    q = mx.quantize_mxint4(_w(64, 64))
    y_ref = ops.mxint4_matmul(x, q, impl="ref")
    y_pal = ops.mxint4_matmul(x, q, impl="pallas", interpret=True,
                              block_m=8, block_n=32, block_k=32)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=2e-2, atol=2e-2)


def test_mxint4_matmul_fused_epilogue():
    """The Eq. (4) epilogue: out_scale x row_scale + bias, fused in-kernel."""
    m, k, n = 6, 64, 96
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    q = mx.quantize_mxint4(_w(k, n))
    os_ = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    rs = jnp.asarray(RNG.normal(size=(m,)).astype(np.float32))
    bias = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    y_ref = ops.mxint4_matmul(x, q, os_, rs, bias, impl="ref")
    y_pal = ops.mxint4_matmul(x, q, os_, rs, bias, impl="pallas",
                              interpret=True, block_m=8, block_n=32, block_k=32)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [1, 8, 128])
def test_mxint4_matmul_keeps_f32_activation(m):
    """x is float32 with its low mantissa bits set: the kernel must match
    the fp32 oracle far inside what a bf16-rounded x would miss by."""
    k, n = 256, 512
    x = RNG.normal(size=(m, k)).astype(np.float32)
    x = jnp.asarray((x.view(np.uint32) | 0x7FFF).view(np.float32))
    q = mx.quantize_mxint4(_w(k, n))
    want = np.asarray(ref.mxint4_matmul_ref(x, q))
    got = np.asarray(ops.mxint4_matmul(x, q, impl="pallas", interpret=True,
                                       block_n=256, block_k=128))
    tol = 2e-5 * want.std()
    rounded = np.asarray(ref.mxint4_matmul_ref(
        x.astype(jnp.bfloat16).astype(jnp.float32), q))
    assert np.max(np.abs(rounded - want)) > 20 * tol   # the test can tell
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)


def test_mxint4_matmul_every_shift_code_at_every_group():
    """Every shift code 0..14 at every group position, across a 128-column
    slice boundary and a block_n boundary, with the Eq. (4) epilogue on."""
    m, k, n, bn = 8, 64, 1024, 512           # two blocks of two slices each
    groups = n // mx.GROUP_SIZE
    codes = (np.arange(k)[:, None] + np.arange(groups)[None, :]) % 15
    mant = RNG.integers(mx.MANT_MIN, mx.MANT_MAX + 1, size=(k, n))
    q = mx.MXINT4Weight(packed=mx.pack_int4(jnp.asarray(mant, jnp.int8)),
                        exps_packed=mx.pack_uint4(jnp.asarray(codes, jnp.uint8)),
                        shape=(k, n))
    assert all(set(codes[:, g]) == set(range(15)) for g in range(groups))
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    os_ = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    rs = jnp.asarray(RNG.normal(size=(m,)).astype(np.float32))
    bias = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    want = np.asarray(ops.mxint4_matmul(x, q, os_, rs, bias, impl="ref"))
    got = np.asarray(ops.mxint4_matmul(x, q, os_, rs, bias, impl="pallas",
                                       interpret=True, block_n=bn,
                                       block_k=32))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())


def test_mxint4_matmul_batched_input():
    x = jnp.asarray(RNG.normal(size=(2, 3, 64)).astype(np.float32))
    q = mx.quantize_mxint4(_w(64, 64))
    y_ref = ops.mxint4_matmul(x, q, impl="ref")
    y_pal = ops.mxint4_matmul(x, q, impl="pallas", interpret=True,
                              block_m=8, block_n=32, block_k=32)
    assert y_pal.shape == (2, 3, 64)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,h,s,dk,dv,chunk", [
    (1, 2, 32, 16, 16, 8),
    (2, 3, 64, 16, 24, 16),
    (2, 1, 128, 32, 64, 32),
])
def test_retention_kernel_vs_oracle(b, h, s, dk, dv, chunk):
    q = jnp.asarray(RNG.normal(size=(b, h, s, dk)).astype(np.float32)) * 0.3
    k = jnp.asarray(RNG.normal(size=(b, h, s, dk)).astype(np.float32)) * 0.3
    v = jnp.asarray(RNG.normal(size=(b, h, s, dv)).astype(np.float32)) * 0.3
    gamma = ret.head_decays(h)
    y_ref, st_ref = ref.retention_chunkwise_ref(q, k, v, gamma, chunk=chunk)
    y_pal, st_pal = ops.retention_chunkwise(q, k, v, gamma, chunk=chunk,
                                            impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_ref), np.asarray(st_pal),
                               rtol=1e-4, atol=1e-4)


def test_retention_kernel_matches_parallel_form():
    b, h, s, dk, dv = 2, 4, 64, 16, 32
    q = jnp.asarray(RNG.normal(size=(b, h, s, dk)).astype(np.float32)) * 0.3
    k = jnp.asarray(RNG.normal(size=(b, h, s, dk)).astype(np.float32)) * 0.3
    v = jnp.asarray(RNG.normal(size=(b, h, s, dv)).astype(np.float32)) * 0.3
    gamma = ret.head_decays(h)
    y_par = ret.retention_parallel(q, k, v, gamma)
    y_pal, _ = ops.retention_chunkwise(q, k, v, gamma, chunk=16,
                                       impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_pal),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,d", [(8, 64), (32, 512), (7, 96)])
def test_rmsnorm_stats_kernel(m, d):
    y = jnp.asarray(RNG.normal(size=(m, d)).astype(np.float32))
    got = ops.rmsnorm_stats(y, impl="pallas", interpret=True)
    want = ref.rmsnorm_stats_ref(y.reshape(-1, d))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_w8a8_matmul_scaled():
    x = jnp.asarray(RNG.integers(-127, 128, size=(4, 32)), jnp.int8)
    w = jnp.asarray(RNG.integers(-127, 128, size=(32, 16)), jnp.int8)
    y = ops.w8a8_matmul(x, w, jnp.float32(0.5))
    want = (x.astype(np.int32) @ w.astype(np.int32)).astype(np.float32) * 0.5
    np.testing.assert_allclose(np.asarray(y), np.asarray(want))


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (5, 64, 96, 8, 32, 32),       # padding path
    (16, 128, 64, 8, 64, 64),
    (1, 32, 32, 8, 32, 32),       # single-token prefill edge
])
def test_w8a8_kernel_vs_ref(m, k, n, bm, bn, bk):
    """The MMM (prefill) dataflow kernel — output-stationary int8, Eq. (4)
    drain epilogue — against the jnp oracle."""
    xq = jnp.asarray(RNG.integers(-127, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(RNG.integers(-127, 128, (k, n)), jnp.int8)
    rs = jnp.asarray(RNG.normal(size=(m,)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    y_ref = ops.w8a8_matmul(xq, wq, jnp.float32(0.01), rs, b, impl="ref")
    y_pal = ops.w8a8_matmul(xq, wq, jnp.float32(0.01), rs, b, impl="pallas",
                            interpret=True, block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_retnet_prefill_threads_kernel_impl(impl):
    """`EngineSpec.kernel_impl` reaches the retention call site: a reduced
    retnet prefill whose length is a multiple of the chunk traces zero
    pallas_calls under 'ref' and the chunkwise kernel under 'pallas'."""
    from repro.serving import EngineSpec, InferenceEngine

    eng = InferenceEngine.from_config(
        "retnet-1.3b", EngineSpec(reduced=True, quantize=False,
                                  kernel_impl=impl))
    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: eng._prefill_impl(p, {"tokens": t}, cache_len=32))(
            eng.params, tokens)
    n = str(jaxpr).count("pallas_call")
    if impl == "ref":
        assert n == 0
    else:
        assert n >= 1
