"""Model FLOP/s utilisation of the whole step: the configuration's
analytic FLOPs per unique token (its reference's `flops_per_token`) times
the window's unique tokens per second, over chips x peak bf16 FLOP/s."""


def read(ctx):
    if ctx.peaks is None or not ctx.tokens_per_s:
        return None
    return (100.0 * ctx.flops_per_token * ctx.tokens_per_s
            / (ctx.chips * ctx.peaks["bf16_flops_per_s"]))
