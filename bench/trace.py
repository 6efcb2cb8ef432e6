"""From a profiler trace of the window to device time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote.  It keeps
the device's operation events (plane ``/device:TPU:<n>``, line ``XLA
Ops``; each named by its HLO text, shapes included), its program events
(line ``XLA Modules``, named ``jit_<function>(<id>)``) and the host's
events (plane ``/host:CPU``), all on the trace's clock, and only those
that start inside the traced window.  Busy time is the union of the
operation intervals within the window; the idle share is 1 minus busy over
the window.  Idle gaps are named by the host event that covers most of
each.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
# a host event that outlasts a gap this many times over (a thread's or the
# profiler's own lifetime) says nothing of what filled the gap
ENCLOSING = 10.0


@dataclasses.dataclass(frozen=True)
class Event:
    chip: int
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def short(name: str) -> str:
    """``%fusion.3 = f32[32,100]{...} fusion(...)`` -> ``fusion.3
    f32[32,100]``: the instruction and its first result shape."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (f" {shape.group(1)}" if shape else "")


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    host: List[Event]
    chips: int
    window: Tuple[float, float]           # ns, on the trace's clock

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, chip: int) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in self.ops
                if e.chip == chip and e.end_ns > lo and e.start_ns < hi]

    def busy_s(self) -> float:
        """Seconds of the window in which some operation ran, averaged over
        the chips."""
        total = sum(union_ns(self._clipped(c)) for c in range(self.chips))
        return total / self.chips / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    @staticmethod
    def seconds(events: List[Event]) -> float:
        return sum(e.dur_ns for e in events) / 1e9

    def modules_named(self, function: str) -> List[Event]:
        """Calls of the jitted ``function`` (``jit_<function>(<id>)``)."""
        prefix = f"jit_{function}("
        return [e for e in self.modules if e.name.startswith(prefix)]

    def ops_where(self, pred: Callable[[Event], bool]) -> List[Event]:
        return [e for e in self.ops if pred(e)]

    def gaps(self, chip: int = 0) -> List[Tuple[float, float, str]]:
        """(start, end, operation before) of each idle interval."""
        lo, hi = self.window
        out, reach, last = [], lo, "window start"
        for e in sorted((e for e in self.ops if e.chip == chip),
                        key=lambda e: e.start_ns):
            if e.start_ns >= hi:
                break
            if e.start_ns > reach:
                out.append((reach, e.start_ns, last))
            if e.end_ns > reach:
                reach, last = e.end_ns, short(e.name)
        if reach < hi:
            out.append((reach, hi, last))
        return out

    def host_during(self, start: float, end: float) -> str:
        """The host event that covers most of [start, end)."""
        span = end - start
        cover: Dict[str, float] = defaultdict(float)
        for e in self.host:
            if e.end_ns <= start or e.start_ns >= end:
                continue
            if e.dur_ns > ENCLOSING * span:
                continue
            cover[e.name] += min(e.end_ns, end) - max(e.start_ns, start)
        if not cover:
            return "no host event"
        return max(cover.items(), key=lambda kv: kv[1])[0]

    def breakdown(self) -> Dict[str, List[List]]:
        """The device operations that took most time, and the longest idle
        gaps, each named by the host's work in it and the operation before
        it."""
        per_op: Dict[str, float] = defaultdict(float)
        for e in self.ops:
            per_op[short(e.name)] += e.dur_ns / 1e9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[f"host {self.host_during(a, b)} after {op}",
                               (b - a) / 1e9] for a, b, op in gaps]}


def union_ns(intervals: List[Tuple[float, float]]) -> float:
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def parse(path: str, chips: int, window: Tuple[float, float]) -> Trace:
    """Events of ``chips`` devices and of the host that start inside
    ``window`` (ns on the trace's clock)."""
    import jax

    lo, hi = window
    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is not None and int(m.group(1)) < chips:
            chip = int(m.group(1))
            lines = {OPS_LINE: ops, MODULES_LINE: modules}
        elif plane.name == HOST_PLANE:
            chip, lines = -1, None
        else:
            continue
        for line in plane.lines:
            out = host if lines is None else lines.get(line.name)
            if out is None:
                continue
            for e in line.events:
                ev = Event(chip, e.name, float(e.start_ns),
                           float(e.duration_ns))
                if ev.end_ns > lo and ev.start_ns < hi:
                    out.append(ev)
    return Trace(ops=ops, modules=modules, host=host, chips=chips,
                 window=window)


def load(directory, chips: int, window: Tuple[float, float]) -> Trace:
    paths = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found "
                           f"{len(paths)}")
    return parse(paths[0], chips, window)
