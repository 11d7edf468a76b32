"""Seconds of lower + compile of the train step (host clock)."""


def read(ctx):
    return ctx.compile_s
