"""The program's own spans in a traced run, with their arguments.

``trace.py`` keeps the host's events by name and time.  The program's
spans (``repro.tracing``: the wire, the batcher, the engine, GC) also carry
arguments, such as the batch a span belongs to, the bytes it uploaded or
the traversal trips it took.  ``of(run)`` re-reads the ``.xplane.pb`` that
``trace.load`` found in the run's scratch trace directory, keeps the host
events named in ``NAMES`` with their arguments, and clips each to the
traced window (those that overlap it, as ``trace.parse`` keeps them).  A
run's trace is parsed once.  A program without these spans gives an empty
list, and the readers then report nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bench import spec, trace

# where ``run.py``'s tracer writes, until the run's end
TRACE_DIR = spec.ROOT / ".bench_run" / "trace"
NAMES = ("wire.decode", "api.plan", "api.hits", "wire.encode",
         "batcher.idle", "batcher.fill", "batcher.resolve",
         "engine.lock", "engine.prep", "engine.filter", "engine.h2d",
         "engine.device", "engine.post", "python.gc")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    thread: int                  # the host line (one per thread)
    start_ns: float
    end_ns: float
    args: Dict[str, Any]

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def parse(path: str, window: Tuple[float, float]) -> List[Span]:
    """The spans in ``NAMES`` that overlap ``window`` (ns on the trace's
    clock), each clipped to it, in the order they started."""
    return list(_parse(str(path), os.path.getmtime(path), *window))


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime: float, lo: float, hi: float
           ) -> Tuple[Span, ...]:
    import jax

    wanted = set(NAMES)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name not in wanted:
                    continue
                start = float(e.start_ns)
                end = start + float(e.duration_ns)
                if end > lo and start < hi:
                    out.append(Span(e.name, thread, max(start, lo),
                                    min(end, hi), dict(e.stats)))
    return tuple(sorted(out, key=lambda s: s.start_ns))


def of(run) -> Optional[List[Span]]:
    """The run's spans, or None where the run was not traced."""
    if run.trace is None:
        return None
    paths = glob.glob(os.path.join(str(TRACE_DIR), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return None
    return parse(paths[0], run.trace.window)


def named(spans: Sequence[Span], *names: str) -> List[Span]:
    return [s for s in spans if s.name in names]


def union(intervals) -> List[Tuple[float, float]]:
    """Disjoint, sorted intervals covering the same time as ``intervals``."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def overlap_ns(a, b) -> float:
    """Time covered by both interval sets."""
    a, b = union(a), union(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ms_per_batch(run, *names: str) -> Optional[float]:
    """Milliseconds per batch in which the device was idle (the trace's
    gaps on chip 0) and one of the spans ``names`` was open on some
    thread; None where the run holds no program span at all."""
    found = of(run)
    batches = run.stats_traced.get("batches", 0) if found else 0
    if batches <= 0:
        return None
    covered = named(found, *names)
    gaps = [(a, b) for a, b, _ in run.trace.gaps()]
    spans = [(s.start_ns, s.end_ns) for s in covered]
    return overlap_ns(gaps, spans) / batches / 1e6
