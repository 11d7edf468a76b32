"""Milliseconds per step in which the device sat idle while the host was
pulling the next batch from the program's feed (`batch_stream`, inside
the benchmark's `bench.input` span), from the trace."""


def read(ctx):
    r = ctx.reduction
    if r is None or not r.steps:
        return None
    return 1e3 * r.idle_in("bench.input") / r.steps
