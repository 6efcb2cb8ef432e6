"""Run one benchmark cell: a served deployment under closed-loop load.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``) names its
configuration (``bench/configs``) and traffic mix (``bench/traffic``).  The
run, in this one process, which alone holds the chip:

  set-up   draw the corpus and query pool on the device from the seed;
           upsert through ``Database``/``Collection``; build the index
           (``build_s``); warm every power-of-two batch bucket of the cell's
           own search; serve the database over HTTP; start the client
           processes (``JAX_PLATFORMS=cpu``, ``QuantixarClient``, see
           ``loadgen.py``) and open their connections
  window   ``--seconds`` of closed-loop searches; with ``--trace 1`` the
           profiler records the middle of it
  check    once the window has closed and the program is freed: the plain
           reference over the same rows and filter, and the comparison of
           every answer (``check.py``)

Earlier lines of standard output report compiles inside the window, peak
device bytes, the load generators' lateness and CPU use, and the set-up's
parts.  The last line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number beside its limit.  The same numbers are the
last lines of standard error.  Anything but a TPU with the chips the cell
asks for exits non-zero before a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

if __package__ in (None, ""):          # run as a script from the checkout
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import check, data, reference, spec  # noqa: E402

SCRATCH = spec.ROOT / ".bench_run"     # run-time files, inside the checkout
MISS_EVENT = "/jax/compilation_cache/cache_misses"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
LATE_WAIT_S = 60.0                     # answers may come this late
TRACE_S = 4.0                          # seconds traced, mid-window
SLICE_S = 1.0                          # answers are also counted per slice

# Every key of a cell's files is read by the harness or kept for readers;
# any other key is refused (``spec.known_keys``), so that no file states
# what a run would leave out.
CONFIG_KEYS = ("rows", "dim", "dtype", "queries", "data", "vector", "search",
               "payload_field", "batcher",
               # for readers
               "name", "source", "deployment", "precision", "payload",
               "guarantees", "device_bytes", "reduced", "reduced_why",
               "assumed")
DATA_KEYS = ("generator", "topics", "subtopics", "decay", "sub_scale",
             "noise")
# per-request options: keywords of ``Collection.search`` and methods of
# ``Query`` alike
SEARCH_KEYS = ("ef", "expansion_width", "rescore")
TRAFFIC_KEYS = ("clients", "processes", "k", "filter", "timeout_s", "why")
FILTER_KEYS = ("column", "op", "fraction_of_rows")


class NoDevice(RuntimeError):
    """The chips the cell asks for are not there: no result is printed."""


@dataclasses.dataclass
class Run:
    """What a per-layer reader (``bench/metrics/<name>.py``) may read."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    device_kind: str
    stats_window: Dict[str, float]      # counter deltas over the window
    stats_traced: Dict[str, float]      # counter deltas over the trace
    build_info: Dict[str, Any]          # the collection's stats after build
    trace: Any = None                   # trace.Trace of the traced window


class CompileCounter:
    """Counts programs compiled (persistent-cache misses) and programs
    loaded from the cache, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == MISS_EVENT:
            self.count += 1
        elif event == HIT_EVENT:
            self.hits += 1


def parse_args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(bench: Dict[str, Any], name: str):
    """(cell, configuration, traffic, limits) of the cell ``name``, each
    file held to the keys the harness reads."""
    cell = spec.workload(bench, name)
    cfg = spec.known_keys(f"configs/{cell['config']}",
                          spec.load_json("configs", cell["config"]),
                          CONFIG_KEYS)
    spec.known_keys("data", cfg["data"], DATA_KEYS)
    spec.known_keys("search", cfg.get("search", {}), SEARCH_KEYS)
    if cfg["data"]["generator"] != "embedding_like":
        raise spec.UnknownName(
            f"data generator {cfg['data']['generator']!r}")
    if cfg["dtype"] != "float32":
        raise spec.UnknownName(f"dtype {cfg['dtype']!r}: the harness draws "
                               "float32")
    traffic = spec.known_keys(f"traffic/{cell['traffic']}",
                              spec.load_json("traffic", cell["traffic"]),
                              TRAFFIC_KEYS)
    if traffic["filter"] is not None:
        spec.known_keys("filter", traffic["filter"], FILTER_KEYS)
        if traffic["filter"]["op"] != "ge":
            raise spec.UnknownName(
                f"filter op {traffic['filter']['op']!r}")
    return cell, cfg, traffic, spec.load_json("limits", cell["name"])


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoDevice(f"cell needs {chips} TPU chip(s); JAX has "
                       f"{len(devices)} {devices[0].platform} device(s)")
    return devices


def filter_of(traffic: Dict[str, Any], rows: int) -> Optional[Dict[str, Any]]:
    """The mix's filter made concrete for ``rows``: VectorDBBench's rule
    ``int_id >= floor(fraction * rows)``."""
    flt = traffic["filter"]
    if flt is None:
        return None
    return {"column": flt["column"], "op": flt["op"],
            "value": int(np.floor(flt["fraction_of_rows"] * rows))}


def allowed_rows(flt: Optional[Dict[str, Any]], rows: int
                 ) -> Optional[np.ndarray]:
    """The reference's own reading of the filter over the payload the
    harness wrote (``int_id`` = row number)."""
    if flt is None:
        return None
    return np.arange(rows) >= flt["value"]


def vector_field(cfg: Dict[str, Any]):
    """The configuration's ``vector`` section as the collection's
    ``VectorField``: each nested group (``hnsw``, ``pq``, ``bq``, ``ivf``)
    becomes the config class of that field.  A key that ``VectorField`` or
    the group's class does not have raises."""
    from repro.api import VectorField

    defaults = VectorField(dim=cfg["dim"])
    opts: Dict[str, Any] = {}
    for key, value in cfg["vector"].items():
        group = getattr(defaults, key, None)
        if isinstance(value, dict) and dataclasses.is_dataclass(group):
            value = type(group)(**value)
        opts[key] = value
    return VectorField(dim=cfg["dim"], **opts)


def build_collection(cfg: Dict[str, Any], corpus: np.ndarray):
    """Upsert the corpus through the embedded API and build the index.
    Returns (db, collection, upsert seconds, build seconds)."""
    from repro.api import BatcherConfig, Database, NumericField

    db = Database()
    col = db.create_collection(
        name="corpus",
        vector=vector_field(cfg),
        fields=(NumericField(cfg["payload_field"]),),
        batcher=BatcherConfig(**cfg["batcher"]))
    t0 = time.monotonic()
    n = len(corpus)
    col.upsert([str(i) for i in range(n)], corpus,
               [{cfg["payload_field"]: i} for i in range(n)])
    upsert_s = time.monotonic() - t0
    t0 = time.monotonic()
    col.seal()                 # train + build + ship to the device
    build_s = time.monotonic() - t0
    return db, col, upsert_s, build_s


def warm_buckets(col, cfg, traffic, queries, flt) -> None:
    """Every power-of-two batch the batcher can form, through the
    collection's own search with the cell's k, search options and
    filter."""
    from repro.core.metadata import Predicate

    pred = (None if flt is None
            else Predicate(flt["column"], flt["op"], flt["value"]))
    b = 1
    while b <= cfg["batcher"]["max_batch"]:
        col.search(queries[:b], traffic["k"], flt=pred,
                   **cfg.get("search", {}))
        b *= 2


def counters(col) -> Dict[str, float]:
    """The batcher's own counters (its lock only: reading them never waits
    for a batch in flight)."""
    st = col.batcher.stats()
    return {"requests": st["requests_served"],
            "batches": st["batches_served"]}


def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in a}


class Clients:
    """The load-generator processes, started and stopped as one."""

    def __init__(self, url, cfg, traffic, queries_path, flt, seed):
        self.procs: List[subprocess.Popen] = []
        self.outs: List[Path] = []
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(spec.ROOT / "src"))
        per = traffic["clients"] // traffic["processes"]
        for p in range(traffic["processes"]):
            out = SCRATCH / f"client{p}.npz"
            job = {"src": str(spec.ROOT / "src"), "url": url,
                   "collection": "corpus", "queries": str(queries_path),
                   "k": traffic["k"], "search": cfg.get("search", {}),
                   "filter": flt, "threads": per, "first_client": p * per,
                   "seed": seed, "timeout_s": traffic["timeout_s"],
                   "out": str(out)}
            path = SCRATCH / f"client{p}.json"
            path.write_text(json.dumps(job))
            self.outs.append(out)
            self.procs.append(subprocess.Popen(
                [sys.executable, str(spec.BENCH / "loadgen.py"), str(path)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
                text=True))

    def wait_ready(self) -> None:
        """Each process says ``ready`` once its clients' connections are
        open; one that exits first ends the run."""
        for p in self.procs:
            line = p.stdout.readline().strip()
            if line != "ready":
                raise RuntimeError(f"load generator not ready: {line!r}")

    def go(self, start: float, end: float) -> None:
        for p in self.procs:
            p.stdin.write(f"go {start!r} {end!r}\n")
            p.stdin.flush()

    def collect(self, timeout: float) -> List[Dict[str, np.ndarray]]:
        results = []
        for p, out in zip(self.procs, self.outs):
            p.wait(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(f"load generator exited {p.returncode}")
            with np.load(out) as z:
                results.append({k: z[k] for k in z.files})
        return results

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                if f is not None:
                    f.close()


def merge(results: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    keys = [k for k in results[0] if k != "cpu_s"]
    return {k: np.concatenate([r[k] for r in results]) for k in keys}


def end_to_end(resp, start, end, build_s, setup_s,
               timeout_s: float) -> Dict[str, float]:
    """qps: answers completed inside the window per second; p95_ms: 95th
    percentile latency of every request sent in the window, a failed one
    counting as the client's whole timeout (beyond any tail it would set);
    recall is filled in by the check."""
    sent = (resp["t_send"] >= start) & (resp["t_send"] < end)
    took = resp["t_done"] - resp["t_send"]
    lat = np.where(resp["ok"], took, np.maximum(took, timeout_s))[sent]
    done = resp["ok"] & (resp["t_done"] <= end) & (resp["t_send"] >= start)
    return {"qps": float(done.sum() / (end - start)),
            "p95_ms": float(np.percentile(lat, 95, method="higher") * 1e3),
            "build_s": build_s, "setup_s": setup_s}


class GcWatch:
    """The server process's cyclic garbage collections while ``on``, by
    generation, with their seconds: one long collection holds every
    request thread at once."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.on = False
        self._t0 = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0

    def close(self) -> None:
        self.on = False
        gc.callbacks.remove(self._event)


class Tracer:
    """Profiles ``[at, at + span)`` of the window from a side thread."""

    def __init__(self, col, at: float, span: float):
        self.col, self.at, self.span = col, at, span
        self.dir = SCRATCH / "trace"
        self.stats: Dict[str, float] = {}
        self.window_ns = (0.0, 0.0)
        self.error: Optional[BaseException] = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(max(0.0, self.at - time.monotonic()))
            t_call = time.monotonic()      # about the trace's time origin
            # no Python-function tracing: it slows every call of the host
            # path it would explain; the runtime's own host events stay
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir),
                                     profiler_options=options)
            before = counters(self.col)
            t0 = time.monotonic()
            time.sleep(self.span)
            t1 = time.monotonic()
            self.stats = delta(before, counters(self.col))
            # the window, on the trace's clock; writing the trace out
            # afterwards takes seconds more and is left out
            self.window_ns = ((t0 - t_call) * 1e9, (t1 - t_call) * 1e9)
            jax.profiler.stop_trace()
        except BaseException as exc:       # reported by the main thread
            self.error = exc

    def result(self, n_chips: int):
        from bench import trace

        self.thread.join()
        if self.error is not None:
            raise self.error
        return trace.load(self.dir, n_chips, self.window_ns)


def run(argv: Optional[List[str]] = None, require_tpu: bool = True) -> int:
    t_proc = time.monotonic()
    args = parse_args(argv)
    bench = spec.benchmark()
    cell, cfg, traffic, limits = load_cell(bench, args.workload)
    e2e = spec.metrics_for(bench, cell["name"], "end_to_end")
    layer = spec.metrics_for(bench, cell["name"], "per_layer")
    readers = {m["name"]: spec.metric_reader(m["name"]) for m in layer}

    import jax

    devices = (check_device(cell["chips"]) if require_tpu
               else jax.devices()[:cell["chips"]])
    dev = devices[0]
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import serve_database

    log(f"[setup] compile cache: {enable_compile_cache()}")
    # every program of the run goes to the cache, however quickly it
    # compiled, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    shutil.rmtree(SCRATCH, ignore_errors=True)
    SCRATCH.mkdir(parents=True)
    rows, dim = cfg["rows"], cfg["dim"]
    parts: Dict[str, float] = {}

    t0 = time.monotonic()
    corpus, queries = data.draw(args.seed, rows, cfg["queries"], dim,
                                cfg["data"])
    parts["data_s"] = time.monotonic() - t0
    flt = filter_of(traffic, rows)
    db, col, parts["upsert_s"], build_s = build_collection(cfg, corpus)
    parts["build_s"] = build_s
    build_info = col.stats()
    t0 = time.monotonic()
    warm_buckets(col, cfg, traffic, queries, flt)
    parts["warm_s"] = time.monotonic() - t0
    queries_path = SCRATCH / "queries.npy"
    np.save(queries_path, queries)
    server = serve_database(db, max_batch=cfg["batcher"]["max_batch"])
    server.start()
    clients = None
    gcw = GcWatch()
    try:
        t0 = time.monotonic()
        clients = Clients(server.url, cfg, traffic, queries_path, flt,
                          args.seed)
        clients.wait_ready()
        parts["clients_s"] = time.monotonic() - t0
        start = time.monotonic() + 0.2
        end = start + args.seconds
        setup_s = start - t_proc
        compiles_setup, hits_setup = compiles.count, compiles.hits
        tracer = None
        if args.trace:
            span = min(TRACE_S, args.seconds / 2)
            tracer = Tracer(col, start + (args.seconds - span) / 2, span)
        clients.go(start, end)
        time.sleep(max(0.0, start - time.monotonic()))
        before = counters(col)
        gcw.on = True
        time.sleep(max(0.0, end - time.monotonic()))
        gcw.on = False
        stats_window = delta(before, counters(col))
        compiles_window = (compiles.count - compiles_setup
                           + compiles.hits - hits_setup)
        results = clients.collect(timeout=traffic["timeout_s"] + LATE_WAIT_S)
        trace = tracer.result(len(devices)) if tracer is not None else None
    finally:
        gcw.close()
        if clients is not None:
            clients.stop()
        server.shutdown()
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    db.close()
    del db, col, server
    gc.collect()

    resp = merge(results)
    sent = (resp["t_send"] >= start) & (resp["t_send"] < end)
    metrics = end_to_end(resp, start, end, build_s, setup_s,
                         traffic["timeout_s"])
    late = resp["late"][sent]
    cpu = [float(r["cpu_s"]) for r in results]
    log(f"[window] {args.seconds:g}s; compiles inside the window: "
        f"{compiles_window}; in set-up: {compiles_setup} compiled, "
        f"{hits_setup} loaded from the compile cache")
    log(f"[device] {dev.device_kind} x{len(devices)}; peak bytes "
        f"{peak}")
    log(f"[clients] {traffic['clients']} clients in {len(results)} "
        f"processes; send lateness mean {late.mean() * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms; CPU "
        + ", ".join(f"{c / args.seconds:.2f}" for c in cpu)
        + " cores per process")
    log("[setup] " + " ".join(f"{k} {v:.3f}" for k, v in parts.items())
        + f" total {setup_s:.3f} ({compiles_setup} compiles)")
    log(f"[batcher] {stats_window['requests']} requests in "
        f"{stats_window['batches']} batches over the window")
    done = resp["ok"] & sent & (resp["t_done"] <= end)
    per_slice, _ = np.histogram(resp["t_done"][done] - start,
                                bins=np.arange(0.0, args.seconds + SLICE_S,
                                               SLICE_S))
    log(f"[window] answers per {SLICE_S:g} s: "
        + " ".join(str(int(n)) for n in per_slice))
    log("[gc] collections in the window by generation: "
        + ", ".join(f"{n} ({s:.3f} s)"
                    for n, s in zip(gcw.count, gcw.seconds)))

    # ---- the check, after the program's state is freed
    t0 = time.monotonic()
    allowed = allowed_rows(flt, rows)
    k = traffic["k"]
    top_ids, _ = reference.exact_topk(corpus, queries, k, allowed)
    kth = reference.kth_sims(corpus, queries, top_ids)
    answered = {key: resp[key][sent] for key in resp}
    numbers = check.compare(answered, corpus, queries, top_ids, kth,
                            allowed, k)
    judged = check.judge(numbers, limits)
    correct = check.passed(judged)
    metrics["recall"] = numbers["recall"]
    log(f"[check] reference and comparison {time.monotonic() - t0:.3f}s; "
        + " ".join(f"{n} {v!r}" for n, v in numbers.items()))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(sent.sum()),
        "failed": int((~resp["ok"][sent]).sum())}
    if args.trace:
        record = Run(cell=cell, config=cfg, traffic=traffic,
                     device_kind=dev.device_kind,
                     stats_window=stats_window, stats_traced=tracer.stats,
                     build_info=build_info, trace=trace)
        values = {}
        for m in layer:
            v = readers[m["name"]](record)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = values
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        result["device"] = device
        result["breakdown"] = trace.breakdown()
    else:
        result["metrics"] = {m["name"]: {"value": float(metrics[m["name"]]),
                                         "unit": m["unit"]} for m in e2e}
        result["device"] = device
    result["checks"] = judged
    for name, v in judged.items():
        print(f"[check] {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    shutil.rmtree(SCRATCH, ignore_errors=True)
    return 0


def main() -> int:
    try:
        return run()
    except NoDevice as exc:
        print(f"[device] {exc}: no result", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
