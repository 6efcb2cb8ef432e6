"""Program spans (`repro.tracing`) on the served search path.

A small flat collection and a small HNSW collection are served over HTTP
and searched by concurrent clients, one search filtered, inside
`jax.profiler.trace`; the trace is read back with `ProfileData` and its
spans are held to what the program did: which spans the traffic takes,
the batcher worker's spans tiling its loop without overlap, the links from
a request to the batch that answered it, the bytes uploaded and the HNSW
trips counted.  Counters must not depend on whether the profiler is on.
"""

import gc
import glob
import os
import re
import threading
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro import tracing
from repro.api import (BatcherConfig, Database, NumericField, Predicate,
                       QuantixarClient, VectorField)
from repro.core.hnsw_build import HNSWConfig, preprocess_vectors
from repro.core.hnsw_search import search as hnsw_search
from repro.data.synthetic import gaussian_mixture
from repro.launch.serve import serve_database
from repro.serving.batcher import RequestBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, DIM, K = 1200, 16, 10
FLAT_CLIENTS, FLAT_PER_CLIENT, HNSW_QUERIES = 6, 3, 5
WORKER = ("batcher.", "engine.")


def _database():
    """A flat and an HNSW collection (bulk coarse build) over N rows with a
    numeric payload ``tag`` = row number."""
    corpus = gaussian_mixture(N, DIM, n_clusters=8, scale=0.2, seed=0)
    db = Database()
    batcher = BatcherConfig(max_batch=8, max_wait_ms=20.0)
    hnsw = HNSWConfig(M=8, ef_construction=32, bulk_mode="coarse",
                      coarse_cluster=300, build_batch=256)
    for name, index in (("flat", "flat"), ("hnsw", "hnsw")):
        col = db.create_collection(
            name=name, vector=VectorField(dim=DIM, index=index, hnsw=hnsw),
            fields=(NumericField("tag"),), batcher=batcher)
        col.upsert([str(i) for i in range(N)], corpus,
                   [{"tag": i} for i in range(N)])
        col.seal()
    return db, corpus


def _queries():
    return gaussian_mixture(FLAT_CLIENTS * FLAT_PER_CLIENT + HNSW_QUERIES,
                            DIM, n_clusters=8, scale=0.2, seed=5)


def _traffic(url, queries):
    """Concurrent flat searches, one of them filtered, an explained flat
    search, and HNSW searches one at a time (one query per batch)."""
    flat_q = queries[:FLAT_CLIENTS * FLAT_PER_CLIENT]
    hnsw_q = queries[FLAT_CLIENTS * FLAT_PER_CLIENT:]
    errors = []

    def flat_client(c):
        try:
            remote = QuantixarClient(url, timeout=60).collection("flat")
            for i in range(FLAT_PER_CLIENT):
                q = remote.query(flat_q[c * FLAT_PER_CLIENT + i]).top_k(K)
                if c == 0 and i == 0:
                    q = q.filter(Predicate("tag", "ge", int(0.99 * N)))
                q.run()
        except Exception as exc:              # reported by the main thread
            errors.append(exc)

    def hnsw_client():
        try:
            remote = QuantixarClient(url, timeout=60).collection("hnsw")
            for q in hnsw_q:
                remote.query(q).top_k(K).run()
        except Exception as exc:
            errors.append(exc)

    threads = [threading.Thread(target=flat_client, args=(c,))
               for c in range(FLAT_CLIENTS)]
    threads.append(threading.Thread(target=hnsw_client))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    QuantixarClient(url, timeout=60).collection("flat").query(
        flat_q[0]).top_k(K).explain()
    gc.collect()


def _spans(trace_dir):
    """(name, thread line, start ns, end ns, args) of every program span."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line_no, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in tracing.SPANS:
                    out.append((e.name, line_no, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _counters(db):
    keys = ("h2d_bytes", "hnsw_trips", "hnsw_queries", "flat_fallbacks",
            "serving_requests_served", "serving_batches_served",
            "serving_requests_failed")
    return {name: {k: db[name].stats()[k] for k in keys}
            for name in ("flat", "hnsw")}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Build and serve inside one trace; returns (spans, db, corpus,
    counters after the traffic, build info)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    queries = _queries()
    with jax.profiler.trace(trace_dir):
        db, corpus = _database()
        server = serve_database(db).start()
        try:
            _traffic(server.url, queries)
        finally:
            server.shutdown(close_service=False)
    counters = _counters(db)
    build_info = db["hnsw"].stats()
    yield _spans(trace_dir), db, corpus, queries, counters, build_info
    db.close()


def _by_name(spans, name):
    return [s for s in spans if s[0] == name]


def test_traffic_takes_every_span(served):
    spans = served[0]
    seen = {s[0] for s in spans}
    # the fixture's bulk build ran inside the trace too
    assert set(tracing.SPANS) - seen == set(), \
        f"spans never seen: {sorted(set(tracing.SPANS) - seen)}"


def test_worker_spans_tile_without_overlap(served):
    spans = served[0]
    workers = {s[1] for s in _by_name(spans, "batcher.fill")}
    assert len(workers) == 2          # one batcher per collection
    for line in workers:
        mine = sorted((s for s in spans
                       if s[1] == line and s[0].startswith(WORKER)),
                      key=lambda s: s[2])
        assert mine
        for a, b in zip(mine, mine[1:]):
            assert b[2] >= a[3], f"{a[0]} overlaps {b[0]}"


def test_requests_link_to_their_batch(served):
    spans = served[0]
    fills = {s[4]["batch"]: s for s in _by_name(spans, "batcher.fill")}
    answered = [s for s in _by_name(spans, "wire.encode") if "batch" in s[4]]
    # every batched search, none of the explained one
    assert len(answered) == FLAT_CLIENTS * FLAT_PER_CLIENT + HNSW_QUERIES
    for s in answered:
        reqs = str(fills[s[4]["batch"]][4]["reqs"]).split(";")
        assert str(s[4]["req"]) in reqs
    for name in ("wire.decode", "api.plan", "api.hits"):
        assert all("req" in s[4] for s in _by_name(spans, name))
    for s in _by_name(spans, "batcher.fill"):
        assert s[4]["requests"] == len(str(s[4]["reqs"]).split(";"))
        assert s[4]["queue_wait_s"] >= 0


def test_h2d_bytes_are_the_arrays_uploaded(served):
    spans, counters = served[0], served[4]
    fills = {s[4]["batch"]: s[4] for s in _by_name(spans, "batcher.fill")}
    line_of = {s[4]["batch"]: s[1] for s in _by_name(spans, "batcher.fill")}
    flat_line = {s[1] for s in _by_name(spans, "engine.device")
                 if s[4]["pass"] == "flat" and "batch" in s[4]}
    assert len(flat_line) == 1
    uploads = defaultdict(list)
    for s in _by_name(spans, "engine.h2d"):
        if "batch" in s[4]:
            uploads[s[4]["batch"]].append(s[4]["bytes"])
    filtered = {s[4]["batch"] for s in _by_name(spans, "engine.filter")}
    assert len(filtered) == 1
    flat_batches = sorted(b for b in fills if line_of[b] in flat_line)
    for batch, fill in fills.items():
        want = [fill["bucket"] * DIM * 4]
        if batch == flat_batches[0]:
            want.append(N * DIM * 4)    # the corpus, once: it stays aboard
        if batch in filtered:
            want.append(N)              # the row mask, one byte a row
        assert sorted(uploads[batch]) == sorted(want)
    served_bytes = sum(s[4]["bytes"] for s in _by_name(spans, "engine.h2d")
                       if "batch" in s[4])
    explained = sum(s[4]["bytes"] for s in _by_name(spans, "engine.h2d")
                    if "batch" not in s[4])
    assert explained == DIM * 4
    assert (counters["flat"]["h2d_bytes"] + counters["hnsw"]["h2d_bytes"]
            == served_bytes + explained)


def test_hnsw_trips_match_a_direct_traversal(served):
    spans, db, _, queries, counters, _ = served
    device = sorted((s for s in _by_name(spans, "engine.device")
                     if s[4]["pass"] == "hnsw"), key=lambda s: s[2])
    assert len(device) == HNSW_QUERIES
    eng = db["hnsw"]._engine
    g, max_level, metric = eng._device_graph
    ef = min(max(eng.config.ef_search, K), N)
    want = []
    for q in queries[FLAT_CLIENTS * FLAT_PER_CLIENT:]:
        _, _, iters = hnsw_search(
            g, jax.numpy.asarray(preprocess_vectors(q[None], "cosine")),
            k=ef, ef=ef, max_level=max_level, metric=metric,
            expansion_width=eng.effective_expansion_width(), with_iters=True)
        want.append(int(iters[0]))
    assert [s[4]["trips"] for s in device] == want
    assert all(s[4]["queries"] == 1 for s in device)
    assert counters["hnsw"]["hnsw_trips"] == sum(want)
    assert counters["hnsw"]["hnsw_queries"] == HNSW_QUERIES


def test_queue_wait_counter_sums_the_span_arguments(served):
    spans, db = served[0], served[1]
    for name in ("flat", "hnsw"):
        st = db[name].stats()
        line = {s[1] for s in _by_name(spans, "engine.device")
                if s[4]["pass"] == name}
        if not line:
            continue
        fills = [s for s in _by_name(spans, "batcher.fill") if s[1] in line]
        # span arguments go out with six significant digits
        assert st["serving_queue_wait_s"] == pytest.approx(
            sum(s[4]["queue_wait_s"] for s in fills), rel=1e-4)
        assert st["serving_requests_served"] == sum(
            s[4]["requests"] for s in fills)
        assert st["serving_requests_failed"] == 0


def test_build_phase_seconds_are_their_spans(served):
    spans, build_info = served[0], served[5]
    for phase in ("candidates", "prune", "merge", "stitch", "repair"):
        (s,) = _by_name(spans, f"build.{phase}")
        assert build_info[f"build_s_{phase}"] == pytest.approx(
            (s[3] - s[2]) / 1e9, abs=5e-3)


def test_explain_stage_seconds_are_their_spans(served):
    spans = served[0]
    (stage,) = _by_name(spans, "plan.stage")
    assert stage[4]["op"] == "ann"
    inside = [s for s in spans if s[1] == stage[1] and s[0] != "plan.stage"
              and s[2] >= stage[2] and s[3] <= stage[3]]
    assert {s[0] for s in inside} >= {"engine.lock", "engine.h2d",
                                       "engine.device"}


def test_counters_do_not_depend_on_the_profiler(tmp_path):
    """The same sequential traffic (one query per batch) with the profiler
    on and off leaves the same counters."""
    queries = _queries()[:4]

    def run(traced):
        db, _ = _database()
        server = serve_database(db).start()
        try:
            def send():
                for name in ("flat", "hnsw"):
                    remote = QuantixarClient(server.url,
                                             timeout=60).collection(name)
                    for i, q in enumerate(queries):
                        query = remote.query(q).top_k(K)
                        if i == 0:
                            query = query.filter(
                                Predicate("tag", "ge", int(0.5 * N)))
                        query.run()
            if traced:
                with jax.profiler.trace(str(tmp_path)):
                    send()
            else:
                send()
            return _counters(db), db["flat"].stats()["serving_queue_wait_s"]
        finally:
            server.shutdown()

    off, wait_off = run(False)
    on, wait_on = run(True)
    assert on == off
    assert off["hnsw"]["hnsw_queries"] == len(queries)
    assert off["flat"]["h2d_bytes"] > 0
    assert wait_off > 0 and wait_on > 0


def test_span_with_the_profiler_off():
    assert not tracing.enabled()
    with tracing.span("engine.h2d", bytes=3) as s:
        s.set(extra=1)
    assert s.seconds > 0
    tracing.hook_gc()
    gc.collect()
    assert tracing._gc_span is None


def test_failed_batches_are_counted():
    def broken(queries, k):
        raise RuntimeError("search failed")

    batcher = RequestBatcher(broken, max_batch=4, max_wait_ms=1.0)
    try:
        futs = [batcher.submit(np.zeros(4, np.float32), 1) for _ in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="search failed"):
                f.result(timeout=10)
        st = batcher.stats()
        assert st["requests_failed"] == 3
        assert st["requests_served"] == 0 and st["batches_served"] == 0
        assert st["queue_wait_s"] > 0
    finally:
        batcher.close()


def test_every_span_in_the_source_is_registered_and_documented():
    literal = re.compile(r"""\b(?:span|Span)\(\s*["']([^"']+)["']""")
    used = set()
    for root, _, files in os.walk(os.path.join(REPO, "src")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    used |= set(literal.findall(fh.read()))
    assert used, "no span found under src/"
    assert used <= set(tracing.SPANS), sorted(used - set(tracing.SPANS))
    with open(os.path.join(REPO, "PERF.md")) as fh:
        perf = fh.read()
    missing = [n for n in tracing.SPANS if f"`{n}`" not in perf]
    assert not missing, f"not in PERF.md's layer table: {missing}"
