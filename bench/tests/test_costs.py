"""Each kernel cost against a count made by hand at one shape."""

import importlib.util

from bench.tests.cells import ROOT


def _cost(kernel):
    path = ROOT / "bench" / "costs" / f"{kernel}.py"
    spec = importlib.util.spec_from_file_location(kernel, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.cost


def test_mxint4_matmul_vmapped_over_lanes():
    # 4 lanes, x padded to 8 rows, K 512, N 4096 (mantissas K x N/2,
    # shift bytes K x N/32).
    avals = [(4, 8, 512, "bfloat16"), (512, 2048, "int8"),
             (512, 128, "uint8"), (2, 1, 2048, "float32"),
             (8, 1, "float32"), (2, 1, 2048, "float32")]
    c = _cost("mxint4_matmul")(avals)
    assert c["ops"] == 2 * 4 * 8 * 512 * 4096
    weights = 512 * 4096 // 2 + 512 * 4096 // 32
    assert c["weight_bytes"] == weights == 1_114_112
    assert c["bytes"] == 4 * (weights + 8 * 512 * 2 + 8 * 4096 * 4)
    assert (c["lanes"], c["k"], c["n"], c["peak"]) == (4, 512, 4096,
                                                       "bf16_flops")


def test_mxint4_matmul_unbatched():
    avals = [(8, 2048, "float32"), (2048, 16384, "int8"),
             (2048, 1024, "uint8")]
    c = _cost("mxint4_matmul")(avals)
    assert c["lanes"] == 1
    assert c["ops"] == 2 * 8 * 2048 * 32768
    assert c["bytes"] == (2048 * 32768 // 2 + 2048 * 1024 + 8 * 2048 * 4
                          + 8 * 32768 * 4)


def test_w8a8_matmul():
    avals = [(128, 2048, "int8"), (2048, 4096, "int8"),
             (1, 4096, "float32"), (128, 1, "float32"), (1, 4096, "float32")]
    c = _cost("w8a8_matmul")(avals)
    assert c["ops"] == 2 * 128 * 2048 * 4096
    assert c["bytes"] == (128 * 2048 + 2048 * 4096 + 128 * 4096 * 4
                          + 3 * 4096 * 4 + 128 * 4)
    assert (c["weight_bytes"], c["peak"]) == (2048 * 4096, "int8_ops")
