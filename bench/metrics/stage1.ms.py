"""Device milliseconds per step of stage 1 (the forward and backward
passes under jax.vmap(grad_one)), from the trace."""


def read(ctx):
    r = ctx.reduction
    if r is None or not r.steps:
        return None
    s = r.stage_s().get("stage1")
    return None if s is None else 1e3 * s / r.steps
