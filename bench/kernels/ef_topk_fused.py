"""Operations and bytes of one call of the fused block top-K EF kernel.

It reads g and e, writes the kept indices, values and block scales and the
new e: every operand and result crosses HBM once.  Per coordinate the
algorithm needs acc = lr * g + e (2), |acc| (1), a comparison against the
block's k-th largest magnitude (1), the kept value (1) and e' = acc - c
(1); finding the k-th largest of a block of B needs about log2(B)
comparisons per coordinate.
"""
import math

from bench.roofline import nbytes, size


def cost(operands, results):
    n = max(size(d) for _, d in operands)
    block = n // min(size(d) for _, d in results)   # scales: one per block
    return (6.0 + math.log2(block)) * n, \
        float(nbytes(operands) + nbytes(results))
