"""Milliseconds per batch in which the device was idle while the batcher's
worker was in the engine or resolving answers: the idle gaps of the traced
window covered by ``engine.lock``, ``engine.prep``, ``engine.filter``,
``engine.h2d``, ``engine.device``, ``engine.post`` or ``batcher.resolve``
spans, over the batches served in the traced span."""

from bench import spans

ENGINE = ("engine.lock", "engine.prep", "engine.filter", "engine.h2d",
          "engine.device", "engine.post", "batcher.resolve")


def read(run):
    return spans.idle_ms_per_batch(run, *ENGINE)
