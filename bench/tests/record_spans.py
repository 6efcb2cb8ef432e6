"""Record the small trace of the program's spans that ``test_spans.py``
reads.

Run from the checkout root (on the CPU; no chip is needed):

    JAX_PLATFORMS=cpu python3 bench/tests/record_spans.py bench/tests/fixtures/spans/served.xplane.pb

It serves a flat and an HNSW collection (2,000 x 32, payload ``tag`` = row
number, batches of up to 4) over HTTP and traces, with the host tracer at
the level of the program's own spans: 4 clients that send 2 flat searches
each, the first of them filtered to 20 rows, then 3 HNSW searches one at a
time, then one garbage collection.  It prints each span's thread, name,
times and arguments, from which the test's expected values were counted.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

N, DIM, K = 2000, 32, 5


def main(out_path: str) -> int:
    import jax

    from bench import spans, trace
    from repro.api import (BatcherConfig, Database, NumericField, Predicate,
                           QuantixarClient, VectorField)
    from repro.core.hnsw_build import HNSWConfig
    from repro.data.synthetic import gaussian_mixture
    from repro.launch.serve import serve_database

    corpus = gaussian_mixture(N, DIM, n_clusters=8, scale=0.2, seed=0)
    queries = gaussian_mixture(11, DIM, n_clusters=8, scale=0.2, seed=1)
    db = Database()
    for name in ("flat", "hnsw"):
        col = db.create_collection(
            name=name, vector=VectorField(
                dim=DIM, index=name, hnsw=HNSWConfig(M=8,
                                                     ef_construction=32)),
            fields=(NumericField("tag"),),
            batcher=BatcherConfig(max_batch=4, max_wait_ms=20.0))
        col.upsert([str(i) for i in range(N)], corpus,
                   [{"tag": i} for i in range(N)])
        col.seal()
        col.search(queries[:1], K)                     # compile outside
    server = serve_database(db).start()

    def flat_client(c):
        client = QuantixarClient(server.url, timeout=60)
        remote = client.collection("flat")
        for i in range(2):
            q = remote.query(queries[2 * c + i]).top_k(K)
            if i == 0 and c == 0:
                q = q.filter(Predicate("tag", "ge", N - 20))
            q.run()
        client.close()

    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1           # the program's spans
        jax.profiler.start_trace(tmp, profiler_options=options)
        threads = [threading.Thread(target=flat_client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        client = QuantixarClient(server.url, timeout=60)
        remote = client.collection("hnsw")
        for q in queries[8:11]:
            remote.query(q).top_k(K).run()
        client.close()
        gc.collect()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        shutil.copy(path, out_path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        server.shutdown()
    print(f"trace: {os.path.getsize(out_path)} bytes -> {out_path}")
    found = spans.parse(out_path, (0.0, float("inf")))
    for s in sorted(found, key=lambda s: s.start_ns):
        print(s.thread, s.name, int(s.start_ns), int(s.end_ns), s.args)
    host = trace.parse(out_path, 1, (0.0, float("inf"))).host
    print(f"{len(found)} spans of {len(host)} host events")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
