"""Device milliseconds per step of the expert layers' routing and data
movement, from the trace: self time of the step's ops whose op_name
carries `moe/route`, `moe/dispatch` or `moe/combine` (router matmul,
softmax, top-k and balance loss; the sort and the gather into rows
grouped by expert; the gated scatter-add back), forward, backward and
remat: the part of `moe.ms` that is not the experts' matmuls.  None
where no op of the step carries `moe/`."""
from bench.scopes import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "moe", "moe/(?:route|dispatch|combine)")
