"""Operations and bytes of one call of the fused sign EF kernel.

It reads g and e, writes the packed sign words, the group scales and the
new e: every operand and result crosses HBM once.  Per coordinate the
algorithm needs acc = lr * g + e (2), |acc| into the group sum (2), the
sign test (1), the scaled sign (1) and e' = acc - c (1): 7 operations.
"""
from bench.roofline import nbytes, size


def cost(operands, results):
    n = max(size(d) for _, d in operands)
    return 7.0 * n, float(nbytes(operands) + nbytes(results))
