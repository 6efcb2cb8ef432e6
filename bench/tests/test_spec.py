"""Discovery by name: every part a cell names is found, nothing else is."""

import pytest

from bench import spec

BENCH = spec.benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_parts(cell):
    w = spec.workload(BENCH, cell)
    assert spec.load_json("configs", w["config"])["name"] == w["config"]
    assert "k" in spec.load_json("traffic", w["traffic"])
    assert spec.load_json("limits", cell)["limits"]
    for section in ("end_to_end", "per_layer"):
        for m in spec.metrics_for(BENCH, cell, section):
            if section == "per_layer":
                assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("kind,name", [
    ("configs", "no-such-config"), ("traffic", "no-such-mix"),
    ("limits", "no-such-cell"), ("configs", "../BENCHMARK"),
    ("traffic", "a/b"), ("configs", "")])
def test_unknown_or_malformed_names_are_refused(kind, name):
    with pytest.raises(spec.UnknownName):
        spec.load_json(kind, name)


def test_unknown_metric_and_cell_are_refused():
    with pytest.raises(spec.UnknownName):
        spec.metric_reader("no.such.metric")
    with pytest.raises(spec.UnknownName):
        spec.workload(BENCH, "no-such-cell")


def test_unknown_device_kind_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.UnknownName):
        spec.peaks("cpu")


def test_metrics_follow_their_workload_lists():
    names = {m["name"] for m in spec.metrics_for(BENCH, "flat1m-search",
                                                 "per_layer")}
    assert "flat_scan_roofline" in names
    assert "beam_gather_roofline" not in names
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "hnsw100k-search",
                                               "end_to_end")}
    assert {"qps", "p95_ms", "recall", "build_s", "setup_s"} <= e2e


def with_extra(monkeypatch, kind, extra):
    """``spec.load_json`` with ``extra`` merged into every file of ``kind``."""
    real = spec.load_json

    def load(k, name):
        d = real(k, name)
        return dict(d, **extra) if k == kind else d

    monkeypatch.setattr(spec, "load_json", load)


@pytest.mark.parametrize("kind,extra", [
    ("configs", {"pq": {"m": 96}}),          # a group outside `vector`
    ("configs", {"rescore": True}),
    ("traffic", {"loop": "open"}),           # a mix the generator cannot run
    ("traffic", {"rate_qps": 100}),
])
def test_keys_the_harness_does_not_read_are_refused(kind, extra,
                                                    monkeypatch):
    from bench import run

    with_extra(monkeypatch, kind, extra)
    with pytest.raises(spec.UnknownName):
        run.load_cell(BENCH, "hnsw100k-search")


def test_vector_section_builds_every_group():
    from bench import run
    from repro.core.bq import BQConfig
    from repro.core.pq import PQConfig

    cfg = {"dim": 768, "vector": {
        "index": "hnsw", "quantization": "pq", "rescore_multiplier": 12,
        "hnsw": {"M": 16, "ef_construction": 100},
        "pq": {"m": 96}, "bq": {"bits": 768}}}
    field = run.vector_field(cfg)
    assert field.pq == PQConfig(m=96) and field.bq == BQConfig(bits=768)
    assert field.hnsw.M == 16 and field.rescore_multiplier == 12
    with pytest.raises(TypeError):
        run.vector_field({"dim": 768, "vector": {"hnsw": {"m": 16}}})
    with pytest.raises(TypeError):
        run.vector_field({"dim": 768, "vector": {"nlist": 64}})
