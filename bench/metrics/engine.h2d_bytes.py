"""Bytes uploaded from host to device per batch: over the batches with
both their ``batcher.fill`` span and an ``engine.h2d`` span in the traced
window (every pass uploads at least its queries), the ``bytes`` of their
``engine.h2d`` spans, linked to the batch by its ``batch`` argument, over
the number of those batches.  A batch cut by the window's edge counts on
neither side."""

from bench import spans


def read(run):
    found = spans.of(run) or []
    filled = {s.args["batch"] for s in spans.named(found, "batcher.fill")}
    uploads = [s for s in spans.named(found, "engine.h2d")
               if s.args.get("batch") in filled]
    batches = {s.args["batch"] for s in uploads}
    if not batches:
        return None
    return sum(s.args["bytes"] for s in uploads) / len(batches)
