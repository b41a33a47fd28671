"""Operations and bytes of one `w8a8_matmul` call, from its operands.

Operands as compiled: x int8 [M, K], w int8 [K, N], then float32 vectors
for the scale [1, N], row scale [M, 1] and bias [1, N].  Each operand is
read once and the float32 output written once; int8 operations at the int8
peak.
"""

import math

PEAK = "int8_ops"


def cost(avals: list) -> dict:
    x, w = avals[0], avals[1]
    *lead, m, k = x[:-1]
    lanes = math.prod(lead)
    n = w[1]
    per = m * k + k * n + m * n * 4 + 3 * n * 4 + m * 4
    return {"ops": 2 * lanes * m * k * n, "bytes": lanes * per, "peak": PEAK,
            "lanes": lanes, "k": k, "n": n, "weight_bytes": k * n}
