"""From a profiler trace to the numbers the per-layer metrics read.

`extract` reads an .xplane.pb into a compact, JSON-able record:

  device_ops  [[device, hlo_op, start_ns, duration_ns, module], ...]
              every event on a TPU device plane's "XLA Ops" line (the
              TensorCore's ops; the event name is the HLO instruction's
              text, of which the instruction name is kept), with the
              module whose execution on the "XLA Modules" line holds it
  modules     [[device, module, start_ns, duration_ns], ...]
  host_spans  [[name, start_ns, duration_ns], ...]
              the benchmark's own spans (names starting "bench."), on the
              same clock as the device events

`Reduction` turns that record, the compiled step's op names and the
window's span into busy time, per-stage and per-kernel device time, and
the breakdown.  Stage 1 is the step's forward and backward under
`jax.vmap(grad_one)`: op names carrying "vmap(" or no "jit(" prefix (the
bodies of the model's scans and remat).  Stage 2 is every other op of the
step: the flat copies, the `wire/`, `coded/` and `optim/` scopes and the
kernels.  Ops of other programs (the batches the feed makes) are "input".
"""
from __future__ import annotations

import bisect
import re

HOST_PREFIX = "bench."
_INSTR = re.compile(r"%([\w.\-]+) = ")


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name[len("/device:"):]
            lines = {line.name: line for line in plane.lines}
            mods = [[dev, ev.name.split("(")[0], int(ev.start_ns),
                     int(ev.duration_ns)]
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ())]
            modules += mods
            starts = [m[2] for m in mods]
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                       else ()):
                m = _INSTR.match(ev.name)
                t = int(ev.start_ns)
                i = bisect.bisect_right(starts, t) - 1
                mod = mods[i][1] if i >= 0 and t < mods[i][2] + mods[i][3] \
                    else ""
                ops.append([dev, m.group(1) if m else ev.name, t,
                            int(ev.duration_ns), mod])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"device_ops": ops, "modules": modules, "host_spans": spans}


def op_names(hlo_text: str) -> dict:
    """HLO instruction name -> its metadata op_name, over every computation."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*?metadata=\{op_name=\"([^\"]*)\"", hlo_text)}


def stage_of(op_name) -> str:
    if op_name is None:
        return "unattributed"
    if "vmap(" in op_name or not op_name.startswith("jit("):
        return "stage1"
    return "stage2"


def base_name(hlo_op: str) -> str:
    """'ef_sign_fused.3' -> 'ef_sign_fused'."""
    return re.sub(r"\.\d+$", "", hlo_op)


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of [start, start + dur) clipped to [lo, hi)."""
    total, end = 0, lo
    for s, d in sorted(intervals):
        s, e = max(s, end), min(s + d, hi)
        if e > s:
            total += e - s
            end = e
    return total


class Reduction:
    """A traced window reduced to what the per-layer metrics read."""

    def __init__(self, rec: dict, names: dict, step_module: str):
        win = [s for s in rec["host_spans"] if s[0] == "bench.window"]
        if not win:
            raise ValueError("the trace holds no bench.window span")
        self.lo, self.hi = win[0][1], win[0][1] + win[0][2]
        self.names = names
        self.step_module = step_module
        self.devices = sorted({o[0] for o in rec["device_ops"]})
        self.ops = [o for o in rec["device_ops"]
                    if o[2] < self.hi and o[2] + o[3] > self.lo]
        self._nest()
        self.steps = sum(1 for m in rec["modules"]
                         if m[0] == (self.devices[0] if self.devices else "")
                         and m[1] == step_module
                         and self.lo <= m[2] < self.hi)
        self.spans = [s for s in rec["host_spans"] if s[0] != "bench.window"]

    def _nest(self) -> None:
        """Ops nest on the "XLA Ops" line (a while loop holds its body's
        ops).  Each op's self time is its duration less its children's;
        an op the op names do not place takes its parent's category."""
        self.self_ns, self.cats = [], []
        order = sorted(range(len(self.ops)),
                       key=lambda i: (self.ops[i][0], self.ops[i][2],
                                      -self.ops[i][3]))
        self.self_ns = [o[3] for o in self.ops]
        self.cats = [""] * len(self.ops)
        stack: list = []
        for i in order:
            dev, _, start, dur, _ = self.ops[i]
            while stack and (self.ops[stack[-1]][0] != dev or
                             self.ops[stack[-1]][2]
                             + self.ops[stack[-1]][3] <= start):
                stack.pop()
            cat = self._category(self.ops[i])
            if stack:
                self.self_ns[stack[-1]] -= dur
                if cat == "unattributed":
                    cat = self.cats[stack[-1]]
            self.cats[i] = cat
            stack.append(i)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        per = [union_ns([(o[2], o[3]) for o in self.ops if o[0] == d],
                        self.lo, self.hi) for d in self.devices]
        return sum(per) / len(per) * 1e-9

    def _category(self, op) -> str:
        if op[4] != self.step_module:
            return "input"
        return stage_of(self.names.get(op[1]))

    def seconds_by(self, key) -> dict:
        """Device self seconds per key(op index), averaged over devices."""
        out: dict = {}
        for i in range(len(self.ops)):
            k = key(i)
            out[k] = out.get(k, 0.0) + self.self_ns[i] * 1e-9 / len(
                self.devices)
        return out

    def stage_s(self) -> dict:
        return self.seconds_by(lambda i: self.cats[i])

    def kernel_calls(self, kernel: str) -> list:
        """Indices of the ops that are calls of `kernel`."""
        return [i for i, o in enumerate(self.ops)
                if base_name(o[1]) == kernel and self.cats[i] != "input"]

    def kernel_s(self, kernel: str) -> float:
        return sum(self.self_ns[i] for i in self.kernel_calls(kernel)) \
            * 1e-9 / len(self.devices)

    def top_ops(self, n: int = 10) -> list:
        by = self.seconds_by(lambda i: f"{self.cats[i]}:{self.ops[i][1]}")
        return sorted(([k, v] for k, v in by.items()),
                      key=lambda kv: -kv[1])[:n]

    def _idle(self, dev) -> list:
        """The window's idle intervals on device `dev`, in order."""
        iv = sorted((o[2], o[2] + o[3]) for o in self.ops if o[0] == dev)
        gaps, end = [], self.lo
        for s, e in iv:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if end < self.hi:
            gaps.append((end, self.hi))
        return gaps

    def idle_in(self, span: str) -> float:
        """Idle seconds of the window that fall inside the host's spans
        named `span`, averaged over the devices."""
        spans = [(s, s + d) for name, s, d in self.spans if name == span]
        total = 0
        for dev in self.devices:
            for a, b in self._idle(dev):
                total += sum(max(0, min(b, e) - max(a, s)) for s, e in spans)
        return total * 1e-9 / max(1, len(self.devices))

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the first device, each named by the
        benchmark span the host was in at the gap's middle."""
        gaps = self._idle(self.devices[0] if self.devices else None)

        def host_at(t):
            best = None
            for name, s, d in self.spans:
                if s <= t < s + d and (best is None or d < best[1]):
                    best = (name, d)
            return best[0] if best else "bench.window"
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[host_at((a + b) // 2), (b - a) * 1e-9] for a, b in gaps[:n]]
