"""One load-generator process: closed-loop search clients over HTTP.

Started by ``bench/run.py`` with ``JAX_PLATFORMS=cpu``, so it never holds
the chip.  It reads a JSON spec file (argument 1), opens one keep-alive
``QuantixarClient`` connection per client thread, sends one unmeasured
search per thread, prints ``ready`` and waits for one line on standard
input: ``go <start> <end>`` in ``time.monotonic()`` seconds, the clock all
processes of the host share.  From ``start`` each thread sends a search as
soon as its previous one is answered (a closed loop) and sends none after
``end``; the answer to the last one is awaited.  Then every request goes to
the spec's ``out`` file (``.npz``): query index, send and done times,
whether it was answered, and the returned row ids and scores.  The process
also reports its CPU seconds and how late its clients sent (the gap between
an answer and the next send), so that a starved generator is not read as a
slow server.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np


def search(remote, spec: Dict[str, Any], vector: np.ndarray):
    """The cell's one search: top-k, with the configuration's search
    options (each a ``Query`` method: ``ef``, ``expansion_width``,
    ``rescore``) and the mix's filter."""
    from repro.core.metadata import Predicate

    query = remote.query(vector).top_k(spec["k"])
    for name, value in spec["search"].items():
        query = getattr(query, name)(value)
    flt = spec["filter"]
    if flt is not None:
        query = query.filter(Predicate(flt["column"], flt["op"],
                                       flt["value"]))
    return query.run(timeout=spec["timeout_s"])


def client_thread(remote, spec: Dict[str, Any], queries: np.ndarray,
                  order: np.ndarray, ready: threading.Barrier,
                  window: Dict[str, float], go: threading.Event,
                  out: List[tuple]) -> None:
    """One client: an unmeasured search on its own connection, then the
    closed loop over ``window``."""
    search(remote, spec, queries[order[0]])
    ready.wait()
    go.wait()
    start, end = window["start"], window["end"]
    k = spec["k"]
    i = 0
    last_done = None
    while True:
        now = time.monotonic()
        if now < start:
            time.sleep(start - now)
            continue
        if now >= end:
            return
        q = int(order[i % len(order)])
        i += 1
        ids = np.full(k, -1, np.int64)
        scores = np.full(k, np.nan)
        t_send = time.monotonic()
        try:
            hits = search(remote, spec, queries[q])
            ok = True
        except Exception as exc:           # a failed request is recorded
            hits, ok = [], False
            print(f"[loadgen] request failed: {exc!r}", file=sys.stderr)
        t_done = time.monotonic()
        for j, h in enumerate(hits[:k]):
            ids[j] = int(h.id)
            scores[j] = h.score
        late = 0.0 if last_done is None else t_send - last_done
        last_done = t_done
        out.append((q, t_send, t_done, ok, len(hits), ids, scores, late))


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    sys.path.insert(0, spec["src"])
    from repro.api import QuantixarClient

    queries = np.load(spec["queries"])
    client = QuantixarClient(spec["url"], timeout=spec["timeout_s"])
    threads_n = spec["threads"]
    orders = []
    for t in range(threads_n):
        rng = np.random.default_rng([spec["seed"], spec["first_client"] + t])
        orders.append(rng.permutation(len(queries)))
    remotes = [client.collection(spec["collection"])
               for _ in range(threads_n)]
    records: List[List[tuple]] = [[] for _ in range(threads_n)]
    ready = threading.Barrier(threads_n + 1)
    go = threading.Event()
    window: Dict[str, float] = {}
    threads = [threading.Thread(target=client_thread, args=(
        remotes[t], spec, queries, orders[t], ready, window, go, records[t]))
        for t in range(threads_n)]
    for th in threads:
        th.start()
    ready.wait(timeout=spec["timeout_s"])
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 3 or line[0] != "go":
        window.update(start=0.0, end=0.0)   # an empty window: threads end
        go.set()
        return 1
    window.update(start=float(line[1]), end=float(line[2]))
    cpu0 = time.process_time()
    go.set()
    for th in threads:
        th.join()
    cpu_s = time.process_time() - cpu0
    rows = [r for rec in records for r in rec]
    k = spec["k"]
    np.savez(
        spec["out"],
        query=np.array([r[0] for r in rows], np.int64),
        t_send=np.array([r[1] for r in rows], np.float64),
        t_done=np.array([r[2] for r in rows], np.float64),
        ok=np.array([r[3] for r in rows], bool),
        n_hits=np.array([r[4] for r in rows], np.int64),
        ids=np.array([r[5] for r in rows], np.int64).reshape(-1, k),
        scores=np.array([r[6] for r in rows], np.float64).reshape(-1, k),
        late=np.array([r[7] for r in rows], np.float64),
        cpu_s=np.float64(cpu_s))
    client.close()
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
