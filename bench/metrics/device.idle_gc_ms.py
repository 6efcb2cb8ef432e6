"""Milliseconds per batch in which the device was idle while the
interpreter collected garbage on some thread: the idle gaps of the traced
window covered by ``python.gc`` spans, over the batches served in the
traced span.  GC may fall inside a worker span, so this overlaps the other
``device.idle_*_ms`` readings."""

from bench import spans


def read(run):
    return spans.idle_ms_per_batch(run, "python.gc")
