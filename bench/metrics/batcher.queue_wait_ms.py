"""Milliseconds a request waits in the batcher's queue: over the traced
window's ``batcher.fill`` spans, the sum of their ``queue_wait_s``
(dispatch time minus enqueue time, summed over the batch's requests) over
the sum of their ``requests``."""

from bench import spans


def read(run):
    fills = spans.named(spans.of(run) or [], "batcher.fill")
    requests = sum(s.args["requests"] for s in fills)
    if requests <= 0:
        return None
    return sum(s.args["queue_wait_s"] for s in fills) / requests * 1e3
