"""Compile rehearsals: every Pallas kernel of the serving path, at real
widths, compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret-mode parity tests cannot see what Mosaic refuses — unaligned
blocks, casts it has no lowering for, lane-changing reshapes.  These
compiles can, in about two seconds each, with no chip.  Nothing runs: a
pass here says the kernel compiles, not that it computes the right thing
(tests/test_kernels.py and tests/test_flash_decode.py check that).

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kvq
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.mxint4_matmul import mxint4_matmul_pallas
from repro.kernels.retention_kernel import retention_chunkwise_pallas
from repro.kernels.rmsnorm_stats import rmsnorm_stats_pallas
from repro.kernels.w8a8_matmul import w8a8_matmul_pallas

F32, I8, U8, I32 = jnp.float32, jnp.int8, jnp.uint8, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip; the persistent compile cache is off meanwhile
    (entries for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` on abstract operands placed on the described chip and
    check a Mosaic kernel made it into the program."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# retnet-1.3b decode linears: q/k (2048x2048), v/gate and mlp up
# (2048x4096), out and mlp down (4096x2048), LM head (2048x32768);
# retnet-6.7b: the same four at d_model 4096, FFN 8192.
@pytest.mark.parametrize("k,n", [(2048, 2048), (2048, 4096), (4096, 2048),
                                 (2048, 32768), (4096, 4096), (4096, 8192),
                                 (8192, 4096), (4096, 32768)])
def test_mxint4_matmul_compiles(one_chip, k, n):
    _compile(mxint4_matmul_pallas, one_chip, _s((8, k), F32),
             _s((k, n // 2), I8), _s((k, n // 32), U8), _s((n,), F32),
             _s((8,), F32), _s((n,), F32))


@pytest.mark.parametrize("precision", [None, "highest"])
def test_w8a8_matmul_compiles(one_chip, precision):
    """Prefill linear at M 256; 'highest' is the float-precision default
    chip_smoke.py compares under, which must not reach the int8 dot."""
    def fn(*a):
        with jax.default_matmul_precision(precision):
            return w8a8_matmul_pallas(*a)
    _compile(fn, one_chip, _s((256, 2048), I8), _s((2048, 4096), I8),
             _s((4096,), F32), _s((256,), F32), _s((4096,), F32))


def test_retention_chunkwise_compiles(one_chip):
    """retnet-1.3b prefill: batch 1 x 8 heads, 512 tokens, dk 256, dv 512."""
    _compile(retention_chunkwise_pallas, one_chip, _s((8, 512, 256), F32),
             _s((8, 512, 256), F32), _s((8, 512, 512), F32), _s((8,), F32))


def _cache(shape, fmt):
    if fmt == "fp":
        return _s(shape, F32)
    return jax.eval_shape(lambda x: kvq.encode(x, fmt), _s(shape, F32))


@pytest.mark.parametrize("fmt", ["fp", "int8_tok", "mxint4_blk"])
def test_flash_decode_gqa_compiles(one_chip, fmt):
    """qwen3-8b decode attention: 8 kv heads x 4 queries, d 128, C 4096."""
    b, kv, g, d, c = 4, 8, 4, 128, 4096
    leaf = _cache((b, c, kv, d), fmt)
    _compile(flash_decode_pallas, one_chip, _s((b, kv, g, d), F32), leaf,
             leaf, _s((), I32))


def test_flash_decode_mla_compiles(one_chip):
    """MLA latent decode (singleton kv axis): 128 heads, rank 512 plus a
    64-wide rope stream, int8_tok latent cache."""
    b, h, r, dr, c = 2, 128, 512, 64, 2048
    latent = _cache((b, c, 1, r), "int8_tok")

    def fn(q, k, v, n, q2, k2):
        return flash_decode_pallas(q, k, v, n, q2=q2, k2=k2, scale=0.04)
    _compile(fn, one_chip, _s((b, 1, h, r), F32), latent, latent,
             _s((), I32), _s((b, 1, h, dr), F32), _s((b, c, 1, dr), F32))


def test_rmsnorm_stats_compiles(one_chip):
    _compile(rmsnorm_stats_pallas, one_chip, _s((512, 2048), F32))
