"""Share of its roofline the sign EF kernel reaches (see bench.roofline)."""
from bench.roofline import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "ef_sign_fused")
