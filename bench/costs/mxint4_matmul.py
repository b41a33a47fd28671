"""Operations and bytes of one `mxint4_matmul` call, from its operands.

Operands as the call is compiled: x [L?, M, K] (float), mantissas int8
[K, N/2], shift bytes uint8 [K, N/32], then three float32 epilogue vectors.
A leading L means the call was vmapped over L lanes: the kernel then runs
L matmuls and streams the weights once for each, so both operations and
bytes count L times.  Bytes: 4.25-bit weights, x once, the float32 output
once.  Operations run on the bf16 MXU path.
"""

import math

PEAK = "bf16_flops"


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "int8": 1, "uint8": 1}[dtype]


def cost(avals: list) -> dict:
    x, packed, exps = avals[0], avals[1], avals[2]
    *lead, m, k = x[:-1]
    lanes = math.prod(lead)
    n = 2 * packed[1]
    weights = k * n // 2 + math.prod(exps[:-1])
    per_lane = weights + m * k * _itemsize(x[-1]) + m * n * 4
    return {"ops": 2 * lanes * m * k * n, "bytes": lanes * per_lane,
            "peak": PEAK, "lanes": lanes, "k": k, "n": n,
            "weight_bytes": weights}
