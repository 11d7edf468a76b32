"""Device milliseconds per step of stage 2's flat copies, from the trace:
self time of the step's ops whose op_name carries the program's
`stage2/flatten` or `stage2/unflatten` scope (the leaves and the EF and
optimizer state to flat vectors and back).  The layout copies that the
compiler inserts in front of them carry no op_name and are not counted.
None where no op of the step carries `stage2/`: a program without the
scopes."""

SCOPES = ("stage2/flatten/", "stage2/unflatten/")


def read(ctx):
    r = ctx.reduction
    if r is None or not r.steps:
        return None
    names = [r.names.get(o[1]) or "" if o[4] == r.step_module else ""
             for o in r.ops]
    if not any("stage2/" in n for n in names):
        return None
    ns = sum(r.self_ns[i] for i, n in enumerate(names)
             if any(s in n for s in SCOPES))
    return 1e-6 * ns / len(r.devices) / r.steps
