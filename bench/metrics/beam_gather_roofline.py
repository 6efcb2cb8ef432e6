"""The HNSW layer-0 gather kernel's share of its roofline, in %.

The kernel (``kernels/beam_gather.py``) carries no name of its own into
the trace: XLA names it ``%closed_call.<n>``, the Mosaic call that takes
one query's ids ``s32[L]`` and the served gather table ``f32[rows,1,Dp]``.
Under the traversal's ``vmap`` it runs once per query of the batch.  Time:
the device durations of those events.  Work (``roofline.beam_gather``): L
rows per call (expansion width x M0), read at the vector's own width.
"""

import re

from bench import roofline, spec

KERNEL = re.compile(r"^%closed_call[.\d]* = ")
IDS = re.compile(r"\bs32\[(\d+)\]")


def read(run):
    if run.trace is None:
        return None
    table = f"f32[{run.config['rows']},1,"
    calls = run.trace.ops_where(
        lambda e: KERNEL.match(e.name) is not None and table in e.name
        and IDS.search(e.name) is not None)
    if not calls:
        return None
    rows = sum(int(IDS.search(e.name).group(1)) for e in calls)
    return roofline.share(roofline.beam_gather(rows, run.config["dim"]),
                          run.trace.seconds(calls),
                          spec.peaks(run.device_kind))
