"""``(m, k) @ (k, n)``: 2mkn operations; both operands read once and the
result written once."""


def cost(m: int, k: int, n: int, in_bytes: int = 2, out_bytes: int = 2):
    return 2.0 * m * k * n, float((m * k + k * n) * in_bytes
                                  + m * n * out_bytes)
