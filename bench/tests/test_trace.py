"""The reduction from a device trace to busy time, idle share, program and
kernel time, on a small trace recorded on a TPU v5 lite
(``record_trace.py``) and on hand-made events."""

from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "small_trace.xplane.pb"


def ev(name, start, dur, chip=0):
    return trace.Event(chip, name, float(start), float(dur))


def test_union_counts_overlaps_once():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    assert trace.union_ns([]) == 0


def test_idle_share_and_gaps():
    t = trace.Trace(ops=[ev("a", 0, 2e8), ev("b", 1e8, 2e8),
                         ev("c", 6e8, 1e8)], modules=[],
                    host=[ev("upload", 3.5e8, 2e8, chip=-1)], chips=1,
                    window=(0.0, 1e9))
    assert t.busy_s() == pytest.approx(0.4)
    assert t.idle_share() == pytest.approx(0.6)
    b = t.breakdown()
    assert b["device_ops"][0] == ["a", pytest.approx(0.2)]
    assert b["idle_gaps"] == [
        ["host upload after b", pytest.approx(0.3)],
        ["host no host event after c", pytest.approx(0.3)]]


def test_busy_is_averaged_over_chips():
    t = trace.Trace(ops=[ev("a", 0, 1e9, chip=0), ev("a", 0, 5e8, chip=1)],
                    modules=[], host=[], chips=2, window=(0.0, 1e9))
    assert t.busy_s() == pytest.approx(0.75)


def test_busy_is_clipped_to_the_window():
    t = trace.Trace(ops=[ev("a", 0, 1e9)], modules=[], host=[], chips=1,
                    window=(2.5e8, 5e8))
    assert t.busy_s() == pytest.approx(0.25)
    assert t.idle_share() == pytest.approx(0.0)


@pytest.fixture(scope="module")
def recorded():
    return trace.parse(str(FIXTURE), chips=1, window=(0.0, float("inf")))


def test_recorded_trace_has_the_served_programs(recorded):
    flat = recorded.modules_named("flat_search")
    assert len(flat) == 3
    # 16384 x 768 scanned by 8 queries: about 0.2 ms a call on the v5e
    assert recorded.seconds(flat) == pytest.approx(0.000609596, rel=1e-6)
    assert len(recorded.modules_named("gather")) == 3
    assert recorded.host, "host events are read for the idle gaps"


def test_recorded_busy_time(recorded):
    rec = trace.Trace(ops=recorded.ops, modules=recorded.modules,
                      host=recorded.host, chips=1,
                      window=(min(e.start_ns for e in recorded.ops),
                              max(e.end_ns for e in recorded.ops)))
    busy = rec.busy_s()
    assert 0 < busy <= rec.window_s
    # the programs' own spans bound the ops they contain
    assert busy <= recorded.seconds(recorded.modules) + 1e-9
    b = rec.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= trace.TOP
    assert all(name.startswith("host ") for name, _ in b["idle_gaps"])


def test_recorded_gather_kernel_roofline(recorded):
    """The gather kernel is found by shape, and its share is under 100%."""
    import types

    from bench import spec

    read = spec.metric_reader("beam_gather_roofline")
    run = types.SimpleNamespace(
        trace=recorded, config={"rows": 16384, "dim": 768},
        device_kind="TPU v5 lite")
    share = read(run)
    calls = [e for e in recorded.ops if e.name.startswith("%closed_call")]
    assert len(calls) == 24                   # 3 calls x 8 queries
    assert 0 < share < 100
    least = 24 * 128 * 768 * 4 / 819e9
    assert share == pytest.approx(100 * least / recorded.seconds(calls))


def test_short_names():
    assert trace.short("%fusion.3 = (f32[32,100]{1,0}, s32[32,100]) fusion("
                       "f32[32,1000000] %f)") == "fusion.3 f32[32,100]"
    assert trace.short("jit_search(123)") == "jit_search(123)"
