"""Host milliseconds per request in the HTTP handler's layers: the traced
window's ``wire.decode``, ``api.plan``, ``api.hits`` and ``wire.encode``
spans (``serving/http.py``, ``serving/service.py``, ``api/collection.py``),
summed, over the number of ``wire.encode`` spans (one per answer).  The
handler's wait on its batch is in none of them."""

from bench import spans

HANDLER = ("wire.decode", "api.plan", "api.hits", "wire.encode")


def read(run):
    found = spans.of(run)
    answers = spans.named(found or [], "wire.encode")
    if not answers:
        return None
    total = sum(s.dur_ns for s in spans.named(found, *HANDLER))
    return total / len(answers) / 1e6
