"""Device milliseconds per step of stage 2 (flatten, cocoef_update with
its kernels, apply_update, unflatten), from the trace."""


def read(ctx):
    r = ctx.reduction
    if r is None or not r.steps:
        return None
    s = r.stage_s().get("stage2")
    return None if s is None else 1e3 * s / r.steps
