"""Device time of the step's ops under one of the program's named scopes.

A scope shows in an op's op_name as a path segment: "…/moe/route/…" in a
scanned block, "vmap(jvp(mla))" where it is the outermost scope of a
transformed function, "…/checkpoint/mla/…" under remat; backward ops
carry it under transpose(jvp(…)).  `scoped_ms` reads the self time of
the step's ops whose op_name holds a segment that matches, per device
and step; None where no op of the step carries `present` at all (a
program without the scopes, or a model without the layer).  The TPU
compiler rewrites `jax.lax.ragged_dot` into ops whose op_name is its own
("ragged-dot-none", "ragged-dot-metadata"): the scope does not reach
them, and a reader names them with `renamed`.
"""
from __future__ import annotations

import re


def segment(pattern: str):
    """A regex matching `pattern` as a whole segment of an op_name."""
    return re.compile(rf"(?:^|[/(])(?:{pattern})(?:[/)]|$)")


def scoped_ms(ctx, present: str, counted: str, renamed: str = ""):
    """`renamed`: a regex of op_names the compiler gives, in place of the
    scope's, to ops it rewrites (counted too)."""
    r = ctx.reduction
    if r is None or not r.steps:
        return None
    names = [r.names.get(o[1]) or "" if o[4] == r.step_module else ""
             for o in r.ops]
    has, count = segment(present), segment(counted)
    if not any(has.search(n) for n in names):
        return None
    own = re.compile(renamed) if renamed else None
    ns = sum(r.self_ns[i] for i, n in enumerate(names)
             if count.search(n) or (own and own.match(n)))
    return 1e-6 * ns / len(r.devices) / r.steps
