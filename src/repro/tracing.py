"""Program spans on the served search path, on the profiler's clock.

``with span(name, **args):`` opens a ``jax.profiler.TraceAnnotation``
while an operator's ``jax.profiler.trace(dir)`` (or ``start_trace``) runs:
the span lands on the trace's ``/host:CPU`` plane, on the same clock as
the device's ``XLA Ops``, with its arguments as event stats.  With the
profiler off it only times itself (``seconds``), in under a microsecond.
``SPANS`` lists every span name and what it covers.

Spans go in host code only, never inside a jitted function or a kernel.
The batcher worker's spans tile its loop (at any instant it is in exactly
one of ``batcher.idle``, ``batcher.fill``, ``engine.*`` and
``batcher.resolve``, but for the few lines between two of them) and no span wraps a whole batch or a handler's wait on
its future, so that the span covering an idle gap of the device names the
layer that caused it.  Requests and batches are linked through arguments:
``req`` on the handler's spans, ``batch`` on the worker's, ``reqs`` on
``batcher.fill`` and ``batch`` on ``wire.encode`` (the batch that answered).
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

SPANS = {
    # HTTP handler threads, one set per request (argument ``req``)
    "wire.decode": "HTTP handler: body read, JSON parse, request object",
    "api.plan": "vector to array, query/plan build and validation, up to "
                "the batcher's submit (opened at two sites per request)",
    "api.hits": "answer rows to ids, hits and hit dicts (two sites)",
    "wire.encode": "JSON encode and socket write; ``batch`` is the batch "
                   "that answered",
    # the batcher worker thread: these tile its loop
    "batcher.idle": "worker blocked on an empty queue",
    "batcher.fill": "first request to hand-off: collect, stack, pad",
    "engine.lock": "a batch waiting for the collection lock",
    "engine.prep": "search arguments and query preparation before a pass",
    "engine.filter": "payload filter evaluation, mask combine and the "
                     "route it picks",
    "engine.h2d": "one host-to-device upload of a pass (``bytes``)",
    "engine.device": "one jitted pass dispatched and all its outputs "
                     "fetched (``pass``, ``queries``; HNSW ``trips``)",
    "engine.post": "host work on a pass's answers: masking, merges, the "
                   "flat fallback decision, rescore's gather",
    "batcher.resolve": "slicing the answers and resolving the futures",
    # anywhere
    "python.gc": "the interpreter's cyclic garbage collection "
                 "(``generation``)",
    "plan.stage": "one stage of a directly executed query plan "
                  "(``explain()`` reports its seconds)",
    "build.candidates": "bulk HNSW build: clustering and exact kNN "
                        "candidates",
    "build.prune": "bulk HNSW build: random candidates and heuristic prune",
    "build.merge": "bulk HNSW build: both-way edge merge, capped",
    "build.stitch": "bulk HNSW build: boundary nodes re-search the graph",
    "build.repair": "bulk HNSW build: reattach nodes unreachable from the "
                    "entry",
}

# the request a handler thread is serving, and the batch a worker thread is
# running (or, in a handler thread, the batch that answered its request);
# ``span`` attaches both as ``req`` and ``batch`` when set
REQUEST: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "quantixar_request", default=None)
BATCH: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "quantixar_batch", default=None)
# real (unpadded) queries of the running batch
REAL_QUERIES: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "quantixar_real_queries", default=None)

_ids = itertools.count(1)
enabled = TraceAnnotation.is_enabled


def next_id() -> int:
    """A process-unique id for a request or a batch."""
    return next(_ids)


class Span(TraceAnnotation):
    """One span; ``seconds`` holds its length once it has closed."""

    def __init__(self, name: str, **args):
        req, batch = REQUEST.get(), BATCH.get()
        if req is not None:
            args["req"] = req
        if batch is not None:
            args["batch"] = batch
        super().__init__(name, **args)
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        super().__enter__()
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.seconds = time.perf_counter() - self._t0

    def set(self, **args) -> None:
        """Arguments known only at the span's end."""
        self.set_metadata(**args)


class _Timer:
    """A span opened while the profiler is off: it only keeps its
    seconds."""

    __slots__ = ("_t0", "seconds")

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0

    def set(self, **args) -> None:
        pass


def span(name: str, **args):
    """A ``Span`` while the profiler records, else a bare timer."""
    if not enabled():
        return _Timer()
    return Span(name, **args)


class request:
    """Scope of one served request in a handler thread: a fresh ``req`` id,
    and no answering batch yet."""

    def __enter__(self):
        self._tokens = (REQUEST.set(next_id()), BATCH.set(None))
        return self

    def __exit__(self, *exc):
        REQUEST.reset(self._tokens[0])
        BATCH.reset(self._tokens[1])


def answered_by(batch: Optional[int]) -> None:
    """In a served request's scope, note the batch that answered it (read
    by ``wire.encode``)."""
    if REQUEST.get() is not None:
        BATCH.set(batch)


_gc_span: Optional[Span] = None
_gc_hooked = False
_gc_lock = threading.Lock()


def _on_gc(phase, info) -> None:
    global _gc_span
    if phase == "start":
        if enabled():
            _gc_span = Span("python.gc", generation=info["generation"])
            _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def hook_gc() -> None:
    """Put ``python.gc`` spans around every cyclic collection of this
    process (once; the serving path calls it when it first starts)."""
    global _gc_hooked
    with _gc_lock:
        if not _gc_hooked:
            gc.callbacks.append(_on_gc)
            _gc_hooked = True
