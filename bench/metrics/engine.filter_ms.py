"""Milliseconds per filtered batch of payload-filter evaluation, mask
combine and routing in the engine: the mean duration of the traced
window's ``engine.filter`` spans (``core/engine.py``)."""

from bench import spans


def read(run):
    found = spans.named(spans.of(run) or [], "engine.filter")
    if not found:
        return None
    return sum(s.dur_ns for s in found) / len(found) / 1e6
