"""Where the host was in the window's slowest step.

The window calls `tick()` at the start of each step of its loop, before
its final wait and at its end.  A sampler thread reads the main thread's
innermost Python frames every 10 ms, and a `gc` callback notes every
collection.  `report(i)` gives interval i's wall seconds, its CPU seconds
(the main thread's and all threads'), the main thread's context switches
(voluntary: the thread waited; involuntary: the host took the core away)
and page faults, the seconds of garbage collection in it, the frames the
sampler saw most often, and the longest time in which it took no sample
(the whole process held off the cores): whether a stall is the host's or
the device's.
"""
from __future__ import annotations

import collections
import gc
import resource
import sys
import threading
import time
import traceback

PERIOD_S = 0.01
FRAMES = 3
# the calling thread's counters (Linux), else the process's
RUSAGE = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)


class HostProbe:
    def __init__(self):
        self.ticks: list = []
        self.samples: collections.deque = collections.deque(maxlen=100_000)
        self.collections: list = []
        self._gc_start = None
        self._main = threading.main_thread().ident
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def __enter__(self):
        gc.callbacks.append(self._gc)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._gc)

    def tick(self) -> None:
        ru = resource.getrusage(RUSAGE)
        self.ticks.append((time.perf_counter(), time.thread_time(),
                           ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_majflt,
                           ru.ru_minflt, time.process_time()))

    def _gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.collections.append((self._gc_start, time.perf_counter(),
                                     info["generation"]))

    def _sample(self) -> None:
        while not self._stop.wait(PERIOD_S):
            frame = sys._current_frames().get(self._main)
            if frame is None:
                continue
            stack = traceback.StackSummary.extract(
                traceback.walk_stack(frame), limit=FRAMES, lookup_lines=False)
            self.samples.append((time.perf_counter(), " < ".join(
                f"{f.name}@{f.filename.rsplit('/', 1)[-1]}:{f.lineno}"
                for f in stack)))

    def report(self, i: int) -> dict:
        """Step i: from its tick to the next."""
        a, b = self.ticks[i], self.ticks[i + 1]
        hi = b[0]
        inside = [(t, s) for t, s in self.samples if a[0] <= t < hi]
        seen = collections.Counter(s for _, s in inside)
        times = [a[0]] + [t for t, _ in inside] + [hi]
        out = {"step": i, "wall_s": hi - a[0],
               "longest_unsampled_s": max(y - x for x, y in
                                          zip(times, times[1:])),
               "gc_s": sum(min(e, hi) - max(s, a[0])
                           for s, e, _ in self.collections
                           if s < hi and e > a[0]),
               "frames": seen.most_common(3),
               "cpu_s": b[1] - a[1], "process_cpu_s": b[6] - a[6],
               "voluntary_switches": b[2] - a[2],
               "involuntary_switches": b[3] - a[3],
               "major_faults": b[4] - a[4], "minor_faults": b[5] - a[5]}
        return out
