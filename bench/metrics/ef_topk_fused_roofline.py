"""Share of its roofline the block top-K EF kernel reaches (see
bench.roofline)."""
from bench.roofline import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "ef_topk_fused")
