"""Device milliseconds per step of the expert layers, from the trace: self
time of the step's ops whose op_name carries the program's `moe/` scope
(routing, dispatch, the held experts' grouped matmuls, the combine, the
shared experts), forward, backward and remat, and of the ops the TPU
compiler makes of the grouped matmuls (op_name "ragged-dot-...": the
only ragged_dot in the program is the expert layer's).  None where no op
of the step carries `moe/`."""
from bench.scopes import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "moe", "moe", renamed="ragged-dot-")
