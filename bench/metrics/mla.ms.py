"""Device milliseconds per step of multi-head latent attention, from the
trace: self time of the step's ops whose op_name carries the program's
`mla/` scope (the latent and query projections, rotations, the S x S
scores and softmax, the values and the output projection), forward,
backward and remat.  None where no op of the step carries it."""
from bench.scopes import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "mla", "mla")
