"""The exact flat scan's share of its roofline, in %.

Time: the device durations of the ``jit_flat_search`` program's events in
the trace.  Work (``roofline.flat_scan``): the configuration's rows read
once per call, plus that call's queries; the queries per call are the
window's real ones per batch over the traced span (padding is not work).
"""

from bench import roofline, spec


def read(run):
    if run.trace is None or run.stats_traced.get("batches", 0) <= 0:
        return None
    calls = run.trace.modules_named("flat_search")
    if not calls:
        return None
    per_batch = run.stats_traced["requests"] / run.stats_traced["batches"]
    one = roofline.flat_scan(run.config["rows"], run.config["dim"], per_batch)
    work = {k: v * len(calls) for k, v in one.items()}
    return roofline.share(work, run.trace.seconds(calls),
                          spec.peaks(run.device_kind))
