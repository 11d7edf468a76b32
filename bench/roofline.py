"""A kernel's share of its roofline, from the trace and its cost function.

For each call the least time the chip could take is the larger of its
operations over the peak FLOP/s and its bytes over the peak HBM bandwidth;
the share is the sum of those least times over the kernel's summed device
time in the traced window.  Operations and bytes come from the kernel's
cost function (bench/kernels/<kernel>.py), given the operand and result
shapes of its custom call in the compiled step.
"""
from __future__ import annotations

import re

DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
               "u64": 8}


def shapes(text: str) -> list:
    """[(dtype, dims), ...] of every array type in an HLO type string."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in re.finditer(r"\b([a-z]+\d*)\[([\d,]*)\]", text)
            if m.group(1) in DTYPE_BYTES]


def _braced(text: str, start: int) -> str:
    """The text inside the braces that open at text[start]."""
    depth = 0
    for i in range(start, len(text)):
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        if depth == 0:
            return text[start + 1:i]
    return text[start + 1:]


def custom_calls(hlo_text: str) -> dict:
    """kernel name -> (operand shapes, result shapes) of its Mosaic call.

    Operand types come from the call's operand list, or, where the
    scheduled text names operands without types, from its
    `operand_layout_constraints`."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)(?:\.\d+)? = (.*?) "
                     r"custom-call\((.*?)\), ", line)
        if not m:
            continue
        operands = shapes(m.group(3))
        at = line.find("operand_layout_constraints={")
        if not operands and at >= 0:
            operands = shapes(_braced(line, line.index("{", at)))
        out[m.group(1)] = (operands, shapes(m.group(2)))
    return out


def size(dims) -> int:
    n = 1
    for x in dims:
        n *= x
    return n


def nbytes(arrays) -> int:
    return sum(DTYPE_BYTES[dt] * size(dims) for dt, dims in arrays)


def kernel_roofline(ctx, kernel: str):
    r = ctx.reduction
    if r is None or ctx.peaks is None or kernel not in ctx.kernel_shapes:
        return None
    calls = len(r.kernel_calls(kernel))
    seconds = r.kernel_s(kernel) * len(r.devices)
    if not calls or seconds <= 0:
        return None
    flops, byts = ctx.kernel_cost(kernel).cost(*ctx.kernel_shapes[kernel])
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
