"""Milliseconds per batch in which the device was idle while the batcher's
worker waited for requests or gathered them: the idle gaps of the traced
window covered by ``batcher.idle`` or ``batcher.fill`` spans, over the
batches served in the traced span."""

from bench import spans


def read(run):
    return spans.idle_ms_per_batch(run, "batcher.idle", "batcher.fill")
