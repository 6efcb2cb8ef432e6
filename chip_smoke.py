"""One-chip smoke of the served search path at a 1M x 768 deployment.

The deployment is the VectorDBBench Performance768D1M case shape (Cohere
1M, 768-d, cosine, k=100), with rows drawn from a seed by
``repro.data.synthetic.embedding_like``.  Everything runs in this one
process, through the entry points a user calls: ``Database`` ->
``Collection`` -> HTTP server -> ``QuantixarClient``.

Phases, one after another (each collection is closed before the next, as
their device copies together approach the chip's 16 GB):

  device   JAX version and devices; anything but a TPU exits non-zero
  flat     flat/none at --rows x 768: exact, recall@100 vs brute force
  hnsw     HNSW/none at --rows x 768, bulk-built on the chip: recall@10
           at ef=128, plus the kernel phase for the float kernels
  pq, bq   HNSW+PQ (m=96) and HNSW+BQ (768 bits): recall@10 after exact
           rescore of 120 beam candidates, kernel phase for ADC /
           Hamming.  Their rows are cut from --rows only as far as the
           time left forces, by the HNSW/none phase's measured seconds
           (``quant_rows_for``)

Each served phase starts the HTTP server, answers --queries concurrent
client queries plus one filtered and one ``.stages()`` rescore query, and
prints data, upsert, build, compile, first-query and steady-query seconds
and the device's peak bytes.  The kernel phase compares every compiled
served-path kernel with its ``ref.py`` oracle at the deployment's widths and
checks the served programs hold Mosaic kernels (``tpu_custom_call``).

The last line of standard output is one JSON object; ``"ok": true`` only when
every phase passed.  Any failure raises and exits non-zero.

    python chip_smoke.py                      # the deployment (one chip)
    python chip_smoke.py --rows 200000        # quicker
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.hnsw_build import HNSWConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

DIM = 768
FLAT_K = 100                 # the case's k
HNSW_K, HNSW_EF = 10, 128
# recall@10 floors at ef=128, fixed before the chip runs.  CPU runs of the
# engine on the same generator gave HNSW/none 0.995 (100k rows), PQ 0.9855
# and BQ 0.994 after rescore (50k rows); a larger corpus lowers recall,
# hence the margins
FLOORS = {"hnsw": 0.90, "pq": 0.90, "bq": 0.90}
# HNSW M=16 / ef_construction=100 (the repo's defaults) with the serving
# width 4; the bulk build stitches boundary nodes 16k at a time, since each
# stitch batch re-merges the whole adjacency
HNSW = HNSWConfig(expansion_width=4, build_batch=16384)
# the whole run must end inside 1200 s; the quantized phases are sized to
# end by BUDGET_S, which leaves the rest for start-up, shutdown and what
# the quantized phases add (PQ training, code tables)
BUDGET_S = 1050.0
QUANT_MIN_ROWS = 100_000
# the quantized collections rescore 120 of the ef=128 beam's candidates
# (k x 12) exactly: PQ m=96 codes hold the true top-10 among their own best
# 40 (the engine's default 4x) only ~0.89 of the time at 100k rows of this
# generator (CPU, flat ADC scan), 0.99-1.0 among their best 120
QUANT_RESCORE = 12
# f32 similarities of unit vectors agree to ~1e-6 whatever the summation
# order; a flat hit whose exact similarity is this close to the true k-th
# one is a tie, not a miss
TIE = 1e-5


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------ measurements
class CompileClock:
    """Sums XLA backend-compile seconds (JAX's own monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


def device_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def exact_top(corpus_n: np.ndarray, queries_n: np.ndarray, k: int):
    """Independent brute force: ids (Q, k) by descending cosine, and the
    k-th similarity per query (float64 over the chosen rows)."""
    sims = queries_n @ corpus_n.T                      # (Q, N) f32 BLAS
    top = np.argpartition(-sims, k - 1, axis=1)[:, :k]
    kth = np.empty(len(queries_n))
    for i, row in enumerate(top):
        kth[i] = np.min(corpus_n[row].astype(np.float64)
                        @ queries_n[i].astype(np.float64))
    return top, kth


def build_breakdown(col) -> str:
    """The bulk builder's per-phase host seconds, from the engine stats."""
    st = col.stats()
    parts = " ".join(f"{k[len('build_s_'):]} {v:.3f}" for k, v in st.items()
                     if k.startswith("build_s_"))
    return (f"build {st.get('builder_mode')} "
            f"({st.get('build_clusters')} clusters): {parts}")


def hit_rows(hits) -> list:
    return [int(h.id.split("-")[1]) for h in hits]


def check_exact(rows, corpus_n, q_n, top, kth, what: str) -> int:
    """Every returned row is in the exact top-k, or ties its k-th
    similarity within TIE.  Returns how many rows were such ties."""
    extra = sorted(set(rows) - set(top.tolist()))
    if extra:
        sims = corpus_n[extra].astype(np.float64) @ q_n.astype(np.float64)
        check(bool(np.all(sims >= kth - TIE)),
              f"{what}: a hit outside the exact top-{len(top)}")
    return len(extra)


def recall(results, truth, k: int) -> float:
    got = sum(len(set(hit_rows(r)) & set(t.tolist()))
              for r, t in zip(results, truth))
    return got / (len(results) * k)


def quant_rows_for(seconds_left: float, hnsw_s: float,
                   hnsw_compile_s: float, hnsw_rows: int,
                   phases: int = 2) -> int:
    """Rows for each of ``phases`` quantized HNSW collections so that they
    end in ``seconds_left``, scaled from the HNSW/none phase as measured:
    its compile seconds count once per phase whatever the rows, the rest
    grows linearly with them.  Rounded down to 100k rows, within
    [QUANT_MIN_ROWS, hnsw_rows]."""
    per_phase = seconds_left / phases - hnsw_compile_s
    per_row = max(hnsw_s - hnsw_compile_s, 1e-9) / hnsw_rows
    rows = int(per_phase / per_row) // 100_000 * 100_000
    return min(hnsw_rows, max(QUANT_MIN_ROWS, rows))


# ------------------------------------------------------------ kernel phase
def served_kernels_compiled(program_text: str, what: str) -> int:
    n = program_text.count("tpu_custom_call")
    check(n > 0, f"{what}: no Mosaic kernel in the lowered program")
    return n


def kernel_phase_float(graph, rng) -> None:
    """beam_gather l2/dot and pair_gather on the served graph's table."""
    import jax
    import jax.numpy as jnp
    from repro.core import hnsw_bulk
    from repro.kernels import ops

    table = graph.vectors
    n = table.shape[0]
    q = jnp.asarray(rng.standard_normal((32, DIM), dtype=np.float32))
    for l in (1, 128, 256):
        ids = jnp.asarray(rng.integers(0, n, (32, l)), jnp.int32)
        for mode in ("l2", "dot"):
            def run(force_ref):
                return jax.vmap(lambda a, b: ops.beam_gather_distances(
                    a, b, table, mode=mode, force_ref=force_ref))(q, ids)
            got, want = np.asarray(run(False)), np.asarray(run(True))
            check(np.allclose(got, want, rtol=1e-4, atol=1e-4),
                  f"beam_gather {mode} L={l} differs from its oracle: max "
                  f"{np.max(np.abs(got - want))}")
            print(f"[kernels] beam_gather {mode:3s} L={l:3d} x32 queries "
                  f"matches ref (max abs diff "
                  f"{np.max(np.abs(got - want)):.3g})")
    cand = jnp.asarray(rng.integers(0, n, (512, 60)), jnp.int32)
    for mode in ("l2", "dot"):
        def run(force_ref):
            return jax.vmap(lambda r: ops.pair_gather_distances(
                r, table, mode=mode, force_ref=force_ref))(cand)
        got, want = np.asarray(run(False)), np.asarray(run(True))
        check(np.allclose(got, want, rtol=1e-4, atol=1e-4),
              f"pair_gather {mode} differs from its oracle: max "
              f"{np.max(np.abs(got - want))}")
        print(f"[kernels] pair_gather {mode:3s} C=60 x512 nodes matches ref "
              f"(max abs diff {np.max(np.abs(got - want)):.3g})")
    text = hnsw_bulk._prune_batch.lower(
        table, jnp.zeros((512,), jnp.int32), cand,
        jnp.zeros((512, 60), jnp.float32), m=32, mode="dot",
        keep_pruned=True).as_text()
    print(f"[kernels] bulk-build prune program: "
          f"{served_kernels_compiled(text, 'prune')} tpu_custom_call")


def kernel_phase_codes(graph, q_codes, quant: str, rng) -> None:
    """beam_gather_adc / beam_gather_hamming on the served code table."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    table = graph.codes
    n = table.shape[0]
    fn = ops.beam_gather_adc if quant == "pq" else ops.beam_gather_hamming
    name = "beam_gather_adc" if quant == "pq" else "beam_gather_hamming"
    for l in (1, 128, 256):
        ids = jnp.asarray(rng.integers(0, n, (q_codes.shape[0], l)),
                          jnp.int32)

        def run(force_ref):
            return jax.vmap(lambda a, b: fn(a, b, table,
                                            force_ref=force_ref))(q_codes, ids)
        got, want = np.asarray(run(False)), np.asarray(run(True))
        if quant == "pq":
            check(np.allclose(got, want, rtol=1e-5, atol=1e-5),
                  f"{name} L={l} differs from its oracle: max "
                  f"{np.max(np.abs(got - want))}")
        else:
            check(np.array_equal(got, want),
                  f"{name} L={l} differs from its oracle")
        print(f"[kernels] {name} L={l:3d} x{q_codes.shape[0]} queries "
              f"matches ref ({'exact' if quant == 'bq' else 'allclose'})")


def served_search_text(col, queries) -> str:
    """Lower the engine's HNSW search exactly as the served pass calls it."""
    import jax.numpy as jnp
    from repro.core import bq as bq_mod
    from repro.core import pq as pq_mod
    from repro.core.hnsw_build import preprocess_vectors
    from repro.core.hnsw_search import search

    eng = col._engine
    cfg = eng.config
    g, max_level, metric = eng._device_graph
    q, q_codes = preprocess_vectors(queries, "cosine"), None
    if cfg.quantization == "pq":
        metric = "adc"
        q_codes = pq_mod.build_adc_lut(jnp.asarray(queries),
                                       eng._pq.codebooks,
                                       normalize_inputs=True)
    elif cfg.quantization == "bq":
        metric = "hamming"
        q_codes = eng._bq.encode(jnp.asarray(queries))
        q = np.asarray(bq_mod.unpack_bits(q_codes, cfg.bq.bits),
                       dtype=np.float32) * 2.0 - 1.0
    return search.lower(g, jnp.asarray(q), k=HNSW_EF, ef=HNSW_EF,
                        max_level=max_level, metric=metric,
                        expansion_width=eng.effective_expansion_width(),
                        q_codes=q_codes).as_text()


# ---------------------------------------------------------- served phases
def serve_phase(name, index, quant, corpus, queries, truth_fn, k, ef,
                clock, **vector_opts):
    from repro.api import QuantixarClient
    from repro.launch.serve import (build_database, concurrent_queries,
                                    serve_database)

    n = len(corpus)
    print(f"[{name}] {index}+{quant} over {n} x {corpus.shape[1]} rows "
          f"(k={k}{', ef=%d' % ef if ef else ''})")
    c0 = clock.total
    t0 = time.perf_counter()
    db, _ = build_database(n, DIM, index, quant, corpus=corpus,
                           max_batch=32, **vector_opts)
    upsert_s = time.perf_counter() - t0
    col = db["corpus"]
    t0 = time.perf_counter()
    col.seal()                               # train + build + ship to device
    build_s = time.perf_counter() - t0
    server = serve_database(db, max_batch=32)
    server.start()
    try:
        client = QuantixarClient(server.url, timeout=600)
        remote = client.collection("corpus")

        def query(v):
            qb = remote.query(v).top_k(k)
            return qb.ef(ef).run() if ef else qb.run()

        t0 = time.perf_counter()
        first = query(queries[0])
        first_s = time.perf_counter() - t0
        check(len(first) == k, f"first query returned {len(first)}/{k}")
        # warm every batch bucket, then the measured wave
        concurrent_queries(lambda i: query(queries[i]), min(64, len(queries)),
                           timeout=600)
        c1 = clock.total
        t0 = time.perf_counter()
        results = concurrent_queries(lambda i: query(queries[i]),
                                     len(queries), timeout=600)
        steady_s = time.perf_counter() - t0
        steady_compile = clock.total - c1
        check(all(r is not None for r in results),
              "some client queries never completed")
        check(all(len(r) == k for r in results), "short result lists")
        stats = remote.stats()
        print(f"[{name}] seconds: upsert {upsert_s:.3f} "
              f"build {build_s:.3f} compile {c1 - c0:.3f} "
              f"first_query {first_s:.3f} steady {steady_s:.3f} for "
              f"{len(queries)} concurrent queries "
              f"({len(queries) / steady_s:.1f}/s, compile inside "
              f"{steady_compile:.3f}); batches "
              f"{stats['serving_batches_served']} for "
              f"{stats['serving_requests_served']} requests")
        truth = truth_fn(k)
        rec = recall(results, truth[0], k)

        # one filtered query: every hit satisfies the filter
        flt = remote.query(queries[1]).filter(shard="s3").top_k(k)
        flt_hits = (flt.ef(ef) if ef else flt).run()
        check(len(flt_hits) == k and all(h.payload["shard"] == "s3"
                                         for h in flt_hits),
              "filtered query returned rows outside the filter")
        # one coarse-to-fine query: ann -> exact rescore
        ex = remote.query(queries[2]).top_k(k).stages(oversample=4).explain()
        shape = [s["stage"] for s in ex.stages]
        check(shape == ["ann", "rescore"] and len(ex.hits) == k,
              f".stages() query ran {shape} with {len(ex.hits)} hits")
        print(f"[{name}] filtered query: {k}/{k} hits with shard == s3; "
              f".stages() query: {shape} -> {len(ex.hits)} hits")
        return db, col, rec, truth, results, flt_hits, ex
    finally:
        server.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1_000_000,
                    help="flat and HNSW/none corpus rows")
    ap.add_argument("--queries", type=int, default=300)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()

    # ------------------------------------------------------------ (a) device
    import jax
    print(f"[device] jax {jax.__version__}; devices {jax.devices()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[device] platform {dev.platform!r} is not a TPU: no result",
              file=sys.stderr)
        return 2
    print(f"[device] compile cache: {enable_compile_cache()}")
    import jax.numpy as jnp
    from repro.core import pq as pq_mod
    from repro.core.bq import BQConfig
    from repro.core.pq import PQConfig
    from repro.data.synthetic import embedding_like
    from repro.kernels import ops

    check(not ops._interpret() and not ops._use_ref(None),
          "ops would dispatch interpret-mode or oracle kernels on this TPU")
    print("[device] ops dispatch: compiled Mosaic kernels (no interpret, "
          "no ref oracle)")
    clock = CompileClock()
    rng = np.random.default_rng(args.seed)
    if args.rows != 1_000_000:
        print(f"[cut] flat and HNSW/none rows: 1000000 -> {args.rows}")

    t0 = time.perf_counter()
    rows = args.rows
    data = embedding_like(rows + args.queries, DIM, seed=args.seed)
    queries = data[rows:]
    print(f"[data] embedding_like {rows + args.queries} x {DIM} "
          f"(seed {args.seed}) in {time.perf_counter() - t0:.3f}s; the last "
          f"{args.queries} rows are the queries")

    def normed(x):
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    qn = normed(queries)

    # ------------------------------------------------------------- (b) flat
    corpus = data[:args.rows]
    cn = normed(corpus)
    db, col, rec, truth, results, flt_hits, ex = serve_phase(
        "flat", "flat", "none", corpus, queries,
        lambda k: exact_top(cn, qn, k), FLAT_K, None, clock)
    top, kth = truth
    ties = sum(check_exact(hit_rows(r), cn, qn[i], top[i], kth[i],
                           f"flat query {i}")
               for i, r in enumerate(results))
    print(f"[flat] recall@{FLAT_K} = {rec:.6f} by strict id match; the "
          f"{ties} other hits tie the exact k-th similarity within {TIE} "
          f"-> recall@{FLAT_K} = 1.0 counting f32 ties")
    shard3 = np.flatnonzero(np.arange(args.rows) % 8 == 3)
    f_top, f_kth = exact_top(cn[shard3], qn[1:2], FLAT_K)
    check_exact(hit_rows(flt_hits), cn, qn[1], shard3[f_top[0]], f_kth[0],
                "flat filtered query")
    check_exact(hit_rows(ex.hits), cn, qn[2], top[2], kth[2],
                "flat .stages() query")
    print(f"[flat] filtered query == exact filtered top-{FLAT_K}; .stages() "
          f"query == exact top-{FLAT_K} (ties within {TIE}); "
          f"{device_bytes()}")
    db.close()
    del db, col, results
    gc.collect()

    # -------------------------------------------------------------- (c) hnsw
    t_hnsw, c_hnsw = time.perf_counter(), clock.total
    db, col, rec, truth, results, _, _ = serve_phase(
        "hnsw", "hnsw", "none", corpus, queries,
        lambda k: exact_top(cn, qn, k), HNSW_K, HNSW_EF, clock, hnsw=HNSW)
    print(f"[hnsw] recall@{HNSW_K} at ef={HNSW_EF} = {rec:.4f} "
          f"(floor {FLOORS['hnsw']}); {build_breakdown(col)}; "
          f"{device_bytes()}")
    kernel_phase_float(col._engine._device_graph[0], rng)
    text = served_search_text(col, queries[:32])
    print(f"[kernels] served HNSW/none search program: "
          f"{served_kernels_compiled(text, 'hnsw search')} tpu_custom_call")
    check(rec >= FLOORS["hnsw"], f"HNSW recall {rec} < {FLOORS['hnsw']}")
    db.close()
    del db, col, results, cn
    gc.collect()
    hnsw_s = time.perf_counter() - t_hnsw
    hnsw_compile_s = clock.total - c_hnsw
    print(f"[hnsw] phase seconds {hnsw_s:.3f}, compile inside "
          f"{hnsw_compile_s:.3f}")

    # ------------------------------------------------------------ (d) pq, bq
    left = BUDGET_S - (time.perf_counter() - t_start)
    quant_rows = quant_rows_for(left, hnsw_s, hnsw_compile_s, args.rows)
    if quant_rows != 1_000_000:
        print(f"[cut] HNSW+PQ and HNSW+BQ rows: 1000000 -> {quant_rows} "
              f"({left:.3f}s left of {BUDGET_S:.0f}s; the HNSW/none phase "
              f"took {hnsw_s:.3f}s, {hnsw_compile_s:.3f}s of it compile, "
              f"for {args.rows} rows)")
    corpus = data[:quant_rows]
    cn = normed(corpus)
    quant_opts = {"pq": {"pq": PQConfig(m=96, k=256)},
                  "bq": {"bq": BQConfig(bits=768)}}
    print(f"[quant] rescore: exact over the best {HNSW_K * QUANT_RESCORE} "
          f"of the ef={HNSW_EF} beam (rescore_multiplier {QUANT_RESCORE})")
    for quant, opts in quant_opts.items():
        db, col, rec, truth, results, _, _ = serve_phase(
            quant, "hnsw", quant, corpus, queries,
            lambda k: exact_top(cn, qn, k), HNSW_K, HNSW_EF, clock,
            hnsw=HNSW, rescore_multiplier=QUANT_RESCORE, **opts)
        print(f"[{quant}] recall@{HNSW_K} at ef={HNSW_EF} after rescore = "
              f"{rec:.4f} (floor {FLOORS[quant]}); {build_breakdown(col)}; "
              f"{device_bytes()}")
        eng = col._engine
        if quant == "pq":
            q_codes = pq_mod.build_adc_lut(jnp.asarray(queries[:32]),
                                           eng._pq.codebooks,
                                           normalize_inputs=True)
        else:
            q_codes = eng._bq.encode(jnp.asarray(queries[:32]))
        kernel_phase_codes(eng._device_graph[0], q_codes, quant, rng)
        text = served_search_text(col, queries[:32])
        print(f"[kernels] served HNSW+{quant.upper()} search program: "
              f"{served_kernels_compiled(text, quant + ' search')} "
              f"tpu_custom_call")
        check(rec >= FLOORS[quant], f"{quant} recall {rec} < {FLOORS[quant]}")
        db.close()
        del db, col, results
        gc.collect()

    print(f"[done] total backend compile {clock.total:.3f}s; "
          f"{device_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
