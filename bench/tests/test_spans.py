"""The program's spans in a trace (``bench/spans.py``) and the per-layer
readers built on them, on a small CPU trace of the served path
(``record_spans.py``) and on hand-made spans and gaps.

What the fixture holds, from ``record_spans.py``'s listing: 16 answers
(11 searches and 5 collection lookups); 7 batches, 4 of them flat (1, 3, 1
and 3 queries, padded to 1, 4, 1 and 4; the first filtered to 20 of 2,000
rows) and 3 HNSW batches of one query that took 21, 22 and 20 trips.
"""

import types
from pathlib import Path

import pytest

from bench import spans, spec, trace

FIXTURES = Path(__file__).parent / "fixtures"
SERVED = FIXTURES / "spans" / "served.xplane.pb"
ALL = (0.0, float("inf"))


def read(metric, run):
    return spec.metric_reader(metric)(run)


@pytest.fixture
def served(monkeypatch):
    """A run whose scratch trace directory holds the fixture."""
    monkeypatch.setattr(spans, "TRACE_DIR", SERVED.parent)
    rec = trace.parse(str(SERVED), chips=1, window=ALL)
    return types.SimpleNamespace(trace=rec, stats_traced={"batches": 7})


def test_spans_are_read_with_their_arguments():
    found = spans.parse(str(SERVED), ALL)
    assert {s.name for s in found} == set(spans.NAMES)
    fills = spans.named(found, "batcher.fill")
    assert [s.args["requests"] for s in fills] == [1, 3, 1, 3, 1, 1, 1]
    assert fills[1].args["reqs"] == "7;6;9"
    assert len({s.thread for s in fills}) == 2       # one worker each


def test_spans_are_clipped_to_the_window():
    # cuts the upload of batch 10's corpus (247,395,161-247,615,822 ns)
    # and of batch 12's (427,070,044-427,319,204 ns)
    found = spans.parse(str(SERVED), (247_500_000.0, 427_200_000.0))
    h2d = spans.named(found, "engine.h2d")
    assert [s.args["bytes"] for s in h2d] == [256000, 128, 256000]
    assert [s.dur_ns for s in h2d] == [115822.0, 517483.0, 129956.0]
    assert [s.args["batch"] for s in spans.named(found, "batcher.fill")] \
        == [12]


def test_wire_host_ms(served):
    # 71,677,506 ns in the four handler spans over 16 answers
    assert read("wire.host_ms", served) == pytest.approx(4.479844125)


def test_batcher_queue_wait_ms(served):
    waits = [0.016180796999833547, 0.553872330012382, 0.13835183999617584,
             0.04906977499194909, 0.020452769997064024,
             0.020496369994361885, 0.02050313600921072]
    assert read("batcher.queue_wait_ms", served) == pytest.approx(
        sum(waits) / 11 * 1e3, rel=1e-6)


def test_engine_filter_ms(served):
    # one filtered batch: 36,878,955-47,784,363 ns
    assert read("engine.filter_ms", served) == pytest.approx(10.905408)


def test_engine_h2d_bytes(served):
    # 4 flat batches: the 2000 x 32 float32 corpus each, queries at 128 B
    # a row of the padded batch (1 + 4 + 1 + 4 rows), the filtered batch's
    # 2,000-byte row mask; 3 HNSW batches: one query row each
    total = 4 * 256000 + 10 * 128 + 2000 + 3 * 128
    assert read("engine.h2d_bytes", served) == pytest.approx(total / 7)


def test_engine_h2d_bytes_leaves_out_batches_cut_by_the_window(
        monkeypatch):
    def arg_span(name, **args):
        return spans.Span(name, 0, 0.0, 1.0, args)

    found = [arg_span("engine.h2d", batch=7, bytes=1000),   # fill before
             arg_span("batcher.fill", batch=8),
             arg_span("engine.h2d", batch=8, bytes=300),
             arg_span("engine.h2d", batch=8, bytes=12),
             arg_span("batcher.fill", batch=9)]             # h2d after
    monkeypatch.setattr(spans, "of", lambda run: found)
    assert read("engine.h2d_bytes", types.SimpleNamespace()) == 312


def test_hnsw_trips(served):
    assert read("hnsw.trips", served) == pytest.approx((21 + 22 + 20) / 3)


def idle_run(monkeypatch, found, ops, window, batches):
    monkeypatch.setattr(spans, "of", lambda run: found)
    rec = trace.Trace(ops=[trace.Event(0, name, float(a), float(b - a))
                           for name, a, b in ops],
                      modules=[], host=[], chips=1, window=window)
    return types.SimpleNamespace(trace=rec,
                                 stats_traced={"batches": batches})


def span(name, start, end, thread=0):
    return spans.Span(name, thread, float(start), float(end), {})


def test_idle_readers_on_synthetic_gaps(monkeypatch):
    # device busy [10, 20) and [50, 60) ms of a [0, 100) ms window: gaps
    # [0, 10), [20, 50), [60, 100)
    ms = 1_000_000
    ops = [("a", 10 * ms, 20 * ms), ("b", 50 * ms, 60 * ms)]
    found = [
        span("batcher.idle", 0, 5 * ms),                  # 5 idle
        span("batcher.fill", 5 * ms, 12 * ms),            # 5 idle
        span("engine.h2d", 12 * ms, 30 * ms),             # 10 idle
        span("engine.device", 30 * ms, 55 * ms),          # 20 idle
        span("batcher.resolve", 55 * ms, 65 * ms),        # 5 idle
        span("engine.post", 58 * ms, 62 * ms),            # inside resolve
        span("batcher.idle", 65 * ms, 100 * ms),          # 35 idle
        span("python.gc", 40 * ms, 70 * ms, thread=1),    # 10 + 10 idle
        span("wire.decode", 0, 100 * ms, thread=2),       # read by none
    ]
    run = idle_run(monkeypatch, found, ops, (0.0, 100.0 * ms), batches=2)
    assert read("device.idle_queue_ms", run) == pytest.approx(45 / 2)
    assert read("device.idle_engine_ms", run) == pytest.approx(35 / 2)
    assert read("device.idle_gc_ms", run) == pytest.approx(20 / 2)


def test_idle_readers_read_zero_where_no_span_covers_a_gap(monkeypatch):
    run = idle_run(monkeypatch, [span("wire.decode", 0, 10)],
                   [("a", 0, 100)], (0.0, 100.0), batches=1)
    assert read("device.idle_gc_ms", run) == 0.0


def test_overlap_of_interval_sets():
    assert spans.union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25), (5, 6)]) == 10
    assert spans.overlap_ns([], [(0, 1)]) == 0


NEW = ["wire.host_ms", "batcher.queue_wait_ms", "engine.filter_ms",
       "engine.h2d_bytes", "hnsw.trips", "device.idle_queue_ms",
       "device.idle_engine_ms", "device.idle_gc_ms"]


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_spans_reads_nothing(metric, monkeypatch,
                                               tmp_path):
    """The device-only fixture holds no program span, as a trace of a
    program without them: every reader gives None and raises nothing."""
    device_only = tmp_path / "small_trace.xplane.pb"
    device_only.write_bytes((FIXTURES / "small_trace.xplane.pb").read_bytes())
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    rec = trace.parse(str(device_only), chips=1, window=ALL)
    run = types.SimpleNamespace(trace=rec, stats_traced={"batches": 3})
    assert read(metric, run) is None
    assert read(metric, types.SimpleNamespace(trace=None,
                                              stats_traced={})) is None
