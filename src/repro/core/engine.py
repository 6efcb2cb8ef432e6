"""QuantixarEngine — config-driven composition of index × quantization × metric
(paper §III: Query Processing + Quantization + Indexing modules).

Composition matrix (all user-configurable, as the paper emphasises):

  index ∈ {flat, hnsw}   ×   quantization ∈ {none, pq, bq}   ×   metric
  + optional exact-rescore pass for quantized first-pass candidates
  + MEVS: predicate filter -> mask threaded into the search

Quantized HNSW traversal uses the *exact ADC identity*: the ADC distance of a
PQ code equals the squared-L2 distance to its reconstruction, and packed-code
Hamming distance is monotone in the dot product of ±1 sign vectors.  The
device graph therefore stores the reconstruction (PQ) or sign (BQ) vectors,
giving traversal orderings identical to code-domain arithmetic.  On a real TPU
deployment the same traversal gathers codes and evaluates the Pallas ADC /
Hamming kernels (see kernels/); numerics are the same by construction.

Segmented write path (see segment.py): after the first `build()`, inserts
land in a mutable **delta segment** — encode-only against the trained
codebooks, exact flat scan at query time — while the **sealed segment**
keeps its quantizers and graph.  `search()` fans out over sealed + delta and
merges top-k in the sealed pass's distance space; `seal()` folds the delta
into a new sealed segment (graph rebuild, no quantizer retraining) on the
`SealPolicy` schedule instead of billing an O(N) rebuild to one query.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from . import bq as bq_mod
from . import pq as pq_mod
from .distances import get_metric
from .executor import AnnParams
from .flat import flat_search
from .hnsw_build import (HNSWConfig, PackedHNSW, ProgressFn, build,
                         bulk_build, preprocess_vectors)
from .hnsw_bulk import bulk_build_device
from .ivf import IVFConfig, IVFIndex
from .hnsw_search import to_device, search as hnsw_search
from .metadata import Filter, MetadataStore
from .segment import (ChunkedArray, DeltaSegment, SealPolicy,
                      merge_candidates)


@dataclasses.dataclass
class EngineConfig:
    dim: int
    metric: str = "cosine"               # default per paper §I
    index: str = "hnsw"                  # "hnsw" | "flat" | "ivf"
    quantization: str = "none"           # "none" | "pq" | "bq"
    pq: pq_mod.PQConfig = dataclasses.field(default_factory=pq_mod.PQConfig)
    bq: bq_mod.BQConfig = dataclasses.field(default_factory=bq_mod.BQConfig)
    hnsw: HNSWConfig = dataclasses.field(default_factory=HNSWConfig)
    ivf: IVFConfig = dataclasses.field(default_factory=IVFConfig)
    # "incremental" (faithful one-at-a-time inserts) | "bulk" (device-
    # parallel batched build, core/hnsw_bulk.py) | "bulk_ref" (the slow
    # numpy exactness reference)
    builder: str = "incremental"
    ef_search: int = 64
    # wide-beam candidates popped per HNSW iteration; None defers to
    # hnsw.expansion_width (per-query override rides search())
    expansion_width: Optional[int] = None
    rescore: bool = True                 # exact second pass for quantized search
    rescore_multiplier: int = 4          # first pass fetches k * multiplier
    filter_flat_threshold: float = 0.10  # MEVS: selectivity below which we
    #                                      scan the filtered subset exactly
    seal: SealPolicy = dataclasses.field(default_factory=SealPolicy)

    def __post_init__(self):
        if self.index not in ("hnsw", "flat", "ivf"):
            raise ValueError(f"index {self.index!r}")
        self.ivf = dataclasses.replace(self.ivf, metric=(
            "cosine" if self.metric == "cosine" else "l2"))
        if self.quantization not in ("none", "pq", "bq"):
            raise ValueError(f"quantization {self.quantization!r}")
        if self.builder not in ("incremental", "bulk", "bulk_ref"):
            raise ValueError(f"builder {self.builder!r}")
        # HNSW metric follows the engine metric
        self.hnsw = dataclasses.replace(self.hnsw, metric=self.metric)


class QuantixarEngine:
    """The paper's "Quantixar Engine": entities in, similarity queries out."""

    def __init__(self, config: EngineConfig):
        self.config = config
        self._vectors = ChunkedArray()            # raw entity vectors
        self._n = 0
        self.metadata = MetadataStore()
        self._pq: Optional[pq_mod.ProductQuantizer] = None
        self._bq: Optional[bq_mod.BinaryQuantizer] = None
        self._code_chunks = ChunkedArray()         # pq codes or bq packed words
        self._packed: Optional[PackedHNSW] = None
        self._device_graph = None                  # (HNSWGraph, max_level, metric)
        self._ivf: Optional[IVFIndex] = None
        # the (N, D) matrix IVF lists index into, as a store so that its
        # device copy is kept (and dropped with it on a rebuild)
        self._ivf_effective: Optional[ChunkedArray] = None
        self._dirty = True          # no usable sealed segment yet: build first
        self._sealed_n = 0          # rows covered by the sealed segment
        self._delta: Optional[DeltaSegment] = None  # exists once sealed
        self._delta_cache = None    # (delta, version, eff_device, metric)
        self.build_seconds: float = 0.0
        # search-path counters (the caller's lock serializes searches)
        self.h2d_bytes = 0          # host-to-device bytes uploaded
        self.hnsw_trips = 0         # layer-0 traversal trips, real queries
        self.hnsw_queries = 0       # real queries the traversal answered
        self.flat_fallbacks = 0     # masked beams that under-delivered
        # device copies of whole-corpus stores (raw vectors, codes, IVF
        # matrix): passes served from a copy, whole-store uploads, uploads
        # of appended rows only
        self.resident_hits = 0
        self.resident_uploads = 0
        self.resident_appends = 0
        # observability for the segmented write path: a post-build add() must
        # bump none of these; seal() bumps seal/index, never quantizer_trains
        self.index_builds = 0       # HNSW-graph / IVF-list constructions
        self.quantizer_trains = 0   # PQ/BQ codebook (re)trainings
        self.seals = 0              # delta -> sealed folds

    # ------------------------------------------------------------------ data
    def __len__(self) -> int:
        return self._n

    @property
    def vectors(self) -> np.ndarray:
        v = self._vectors.view()
        return v if v is not None \
            else np.zeros((0, self.config.dim), dtype=np.float32)

    @property
    def _codes(self) -> Optional[np.ndarray]:
        """Full-corpus code matrix, concatenated lazily: a post-build add()
        only appends its batch chunk — an eager concat would make every
        quantized insert O(corpus) instead of O(batch)."""
        return self._code_chunks.view()

    @_codes.setter
    def _codes(self, value: Optional[np.ndarray]) -> None:
        self._code_chunks = ChunkedArray(
            [] if value is None else [value])

    @property
    def delta_rows(self) -> int:
        return len(self._delta) if self._delta is not None else 0

    def add(self, vectors: np.ndarray,
            metadata: Optional[Sequence[Optional[Dict[str, Any]]]] = None) -> None:
        """Insert a batch of entities (vector + optional metadata record).

        Before the first `build()` this only appends (the build is lazy).
        After it, the batch lands in the delta segment: quantized engines
        encode the rows against the existing codebooks (no retraining), the
        sealed graph is untouched, and the rows are immediately searchable
        via the exact delta scan.  The seal policy may then fold the delta.
        """
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.config.dim:
            raise ValueError(
                f"expected (n, {self.config.dim}) vectors, got {vectors.shape}")
        if metadata is None:
            metadata = [None] * len(vectors)
        if len(metadata) != len(vectors):
            raise ValueError("metadata length mismatch")
        self._vectors.append(vectors)
        self._n += len(vectors)
        self.metadata.append_batch(metadata)
        if self._dirty or self._delta is None:
            self._dirty = True                    # first build covers everything
        else:
            codes = self._encode(vectors)
            self._delta.append(vectors, codes)
            if codes is not None:
                self._code_chunks.append(codes)
            if self.config.seal.auto and self.config.seal.should_seal(
                    self._sealed_n, len(self._delta)):
                self.seal()

    def _encode(self, vectors: np.ndarray) -> Optional[np.ndarray]:
        """Encode-only against trained codebooks (never retrains)."""
        if self._pq is not None:
            return np.asarray(self._pq.encode(jnp.asarray(vectors)))
        if self._bq is not None:
            return np.asarray(self._bq.encode(jnp.asarray(vectors)))
        return None

    # ----------------------------------------------------------------- build
    def build(self, seed: int = 0,
              progress: Optional[ProgressFn] = None) -> None:
        """Train quantizers + build the index over everything inserted so far.

        This is the full O(N) path — retrains codebooks and rebuilds the
        graph.  Post-build inserts do *not* re-enter it; they ride the delta
        segment until `seal()` folds them (encode-only, no retraining).
        ``progress`` is an optional ``(phase, done, total)`` callback
        threaded through to the graph builder (serve layers report build
        progress without builders writing to stdout).
        """
        t0 = time.perf_counter()
        cfg = self.config
        raw = self.vectors
        if len(raw) == 0:
            raise RuntimeError("nothing to build: add() vectors first")

        if cfg.quantization == "pq":
            self._pq = pq_mod.ProductQuantizer(
                dataclasses.replace(cfg.pq, metric=(
                    "cosine" if cfg.metric == "cosine" else "l2")))
            self._pq.train(jnp.asarray(raw), seed=seed)
            self._codes = np.asarray(self._pq.encode(jnp.asarray(raw)))
            self.quantizer_trains += 1
        elif cfg.quantization == "bq":
            self._bq = bq_mod.BinaryQuantizer(cfg.bq)
            self._bq.train(jnp.asarray(raw), seed=seed)
            self._codes = np.asarray(self._bq.encode(jnp.asarray(raw)))
            self.quantizer_trains += 1
        else:
            self._codes = None

        self._ivf = None                    # full build retrains coarse centroids
        self._build_index(raw, seed, progress=progress)
        self._mark_sealed()
        self._dirty = False
        self.build_seconds = time.perf_counter() - t0

    def seal(self, seed: int = 0,
             progress: Optional[ProgressFn] = None) -> bool:
        """Fold the delta segment into a new sealed segment.

        Codebooks are reused (the delta rows were already encoded at insert),
        so this rebuilds only the index structure — the size-/ratio-triggered
        merge of the segmented write path, also reachable through
        `Collection.compact()`.  Returns True if anything changed.
        """
        if self._dirty or self._delta is None:
            if self._n == 0:
                return False                # nothing inserted yet
            self.build(seed, progress=progress)  # never built: train + build
            return True
        if len(self._delta) == 0:
            return False
        t0 = time.perf_counter()
        self._build_index(self.vectors, seed, progress=progress)
        self._mark_sealed()
        self.seals += 1
        self.build_seconds = time.perf_counter() - t0
        return True

    def _mark_sealed(self) -> None:
        self._sealed_n = self._n
        self._delta = DeltaSegment(start=self._n, dim=self.config.dim)
        self._delta_cache = None

    def _build_index(self, raw: np.ndarray, seed: int,
                     progress: Optional[ProgressFn] = None) -> None:
        """(Re)build the sealed index structure over `raw` using whatever
        quantizers/codes currently exist — trains nothing except an IVF
        coarse quantizer that does not exist yet."""
        cfg = self.config
        if cfg.index == "hnsw":
            eff, eff_metric = self._effective_vectors()
            hnsw_cfg = dataclasses.replace(cfg.hnsw, metric=eff_metric)
            builder = {"incremental": build, "bulk": bulk_build_device,
                       "bulk_ref": bulk_build}[cfg.builder]
            self._packed = builder(eff, hnsw_cfg, progress=progress)
            self._device_graph = self._to_device_graph()
        elif cfg.index == "ivf":
            # IVF-PQ scans probed lists over reconstructions (the ADC
            # identity, as in the quantized-HNSW path).  BQ's ±1 sign vectors
            # live in code space (bits ≠ dim), so IVF+BQ probes and scans
            # raw vectors — BQ then only compresses the stored codes.
            if cfg.quantization == "pq":
                eff, eff_metric = self._effective_vectors()
            else:
                eff, eff_metric = raw, cfg.metric
            if self._ivf is None or not self._ivf.is_trained:
                self._ivf = IVFIndex(dataclasses.replace(
                    cfg.ivf, metric="l2" if eff_metric != "cosine" else "cosine"))
                self._ivf.train(jnp.asarray(raw), seed=seed)
            self._ivf.build_lists(jnp.asarray(raw))
            self._ivf_effective = ChunkedArray([eff])
        else:
            self._packed = None
            self._device_graph = None
        self.index_builds += 1

    def _to_device_graph(self):
        """Ship the sealed graph to device.  Quantized engines additionally
        ship the code matrix (PQ uint codes / packed BQ uint32 words) so
        layer-0 traversal runs in code domain through the fused beam-gather
        kernels; the float proxy vectors stay aboard for the entry scan."""
        codes = None
        if self.config.quantization in ("pq", "bq") and self._codes is not None:
            codes = self._codes[: self._packed.n]
        return to_device(self._packed, codes=codes)

    def _effective_vectors(self) -> Tuple[np.ndarray, str]:
        """Vectors the graph traverses + the traversal metric (see module doc)."""
        cfg = self.config
        raw = self.vectors
        if cfg.quantization == "pq":
            recon = np.asarray(self._pq.decode(jnp.asarray(self._codes)))
            # ADC == L2-to-reconstruction (exact identity); cosine inputs were
            # normalized inside the quantizer already.
            return recon, "l2"
        if cfg.quantization == "bq":
            signs = np.asarray(bq_mod.unpack_bits(
                jnp.asarray(self._codes), cfg.bq.bits), dtype=np.float32)
            return signs * 2.0 - 1.0, "dot"   # hamming ~ -dot of ±1 vectors
        return raw, cfg.metric

    # ---------------------------------------------------------------- search
    def search(self, queries: np.ndarray, k: int,
               flt: Optional[Filter] = None,
               ef: Optional[int] = None,
               mask: Optional[np.ndarray] = None,
               rescore: Optional[bool] = None,
               expansion_width: Optional[int] = None,
               params: Optional[AnnParams] = None,
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k similarity search (Vector Query / MEVS).

        `mask` is an optional precomputed (N,) bool row mask (e.g. the API
        layer's tombstone liveness mask) AND-ed with the metadata filter.
        `rescore` overrides the config's exact-rescore setting per query.
        `expansion_width` overrides the configured wide-beam width for HNSW
        traversal (1 == classic single-pop).  `params` carries the same
        three knobs as one `AnnParams` struct — the form the API layer's
        plan executor and serving batcher thread through — and is mutually
        exclusive with the individual keywords.

        The sealed segment is searched through its index; a non-empty delta
        segment is exact-scanned in the same distance space and merged, so
        freshly inserted rows are visible without any rebuild.  Masks and the
        rescore pass apply across the sealed+delta union.

        Returns (distances (Q,k) in the engine metric, ids (Q,k); -1 = none).
        """
        with tracing.span("engine.prep"):
            if params is not None:
                if (ef, rescore, expansion_width) != (None, None, None):
                    raise ValueError(
                        "pass ef/rescore/expansion_width either as keywords "
                        "or inside params=AnnParams(...), not both")
                ef, rescore = params.ef, params.rescore
                expansion_width = params.expansion_width
            if k < 1:
                raise ValueError(f"k must be >= 1, got {k}")
            if self._dirty:
                self.build()
            cfg = self.config
            queries = np.asarray(queries, dtype=np.float32)
            if queries.ndim == 1:
                queries = queries[None, :]
            # `ef or ...` would silently turn an explicit ef=0 into the
            # default
            ef = ef if ef is not None else max(cfg.ef_search, k)
        to_flat = cfg.index == "flat"
        if flt is not None or mask is not None:
            with tracing.span("engine.filter", rows=self._n):
                flt_mask = (self.metadata.evaluate(flt) if flt is not None
                            else None)
                if mask is not None:
                    mask = np.asarray(mask, dtype=bool)
                    mask = flt_mask & mask if flt_mask is not None else mask
                else:
                    mask = flt_mask
                to_flat = to_flat or self._route_to_flat(mask)
        do_rescore = cfg.rescore if rescore is None else rescore
        do_rescore = do_rescore and cfg.quantization != "none"

        fetch = k * cfg.rescore_multiplier if do_rescore else k

        if to_flat:
            # the flat scan covers the whole corpus (delta rows included:
            # their codes were appended at insert time)
            d, ids = self._flat_pass(queries, fetch, mask)
        else:
            if cfg.index == "ivf":
                d, ids = self._ivf_pass(queries, fetch, mask)
            else:
                d, ids = self._hnsw_pass(queries, fetch, ef, mask,
                                         expansion_width)
            if self.delta_rows:
                dd, dids = self._delta_pass(queries, fetch, mask)
                with tracing.span("engine.post"):
                    d, ids = merge_candidates(d, ids, dd, dids, fetch)
            fallback = False
            if mask is not None:
                with tracing.span("engine.post") as post:
                    # a beam that under-delivered under the filter falls
                    # back to the exact masked scan
                    fallback = bool(
                        (ids[:, : min(fetch, ids.shape[1])] == -1).any())
                    post.set(fallback=int(fallback))
            if fallback:
                self.flat_fallbacks += 1
                d, ids = self._flat_pass(queries, fetch, mask)

        if do_rescore:
            d, ids = self.exact_rescore(queries, ids, k, mask=mask)
        with tracing.span("engine.post"):
            d, ids = d[:, :k], ids[:, :k]
            # contract: +inf slots (masked-out / padded) never expose a row
            return d, np.where(np.isfinite(d), ids, -1)

    def _route_to_flat(self, mask: Optional[np.ndarray]) -> bool:
        """MEVS routing (paper: filter first, then search the subset): at low
        selectivity an exact masked scan is both faster and exact."""
        if mask is None:
            return False
        sel = mask.mean() if len(mask) else 0.0
        return sel <= self.config.filter_flat_threshold

    def _h2d(self, x) -> jax.Array:
        """Upload one host array for a search pass.  Every upload of the
        search path comes through here, so that its bytes are counted."""
        x = np.asarray(x)
        with tracing.span("engine.h2d", bytes=x.nbytes):
            out = jnp.asarray(x)
        self.h2d_bytes += x.nbytes
        return out

    def _resident(self, store: ChunkedArray) -> jax.Array:
        """The device copy of a whole-corpus store for one pass: uploaded
        (through `_h2d`) on first use, extended by appended rows only, and
        kept after the pass."""
        out, how = store.on_device(self._h2d)
        if how == "hit":
            self.resident_hits += 1
        elif how == "upload":
            self.resident_uploads += 1
        else:
            self.resident_appends += 1
        return out

    @staticmethod
    def _real(n: int) -> int:
        """Real queries of an ``n``-row pass: a served batch is padded."""
        real = tracing.REAL_QUERIES.get()
        return n if real is None else min(real, n)

    def _device(self, name: str, n: int):
        """Span of one jitted pass over ``n`` queries: its dispatch and one
        fetch of all its outputs."""
        return tracing.span("engine.device", queries=self._real(n),
                            **{"pass": name})

    def _flat_pass(self, queries, k, mask):
        cfg = self.config
        mask_j = None if mask is None else self._h2d(mask)
        q = self._h2d(queries)
        if cfg.quantization in ("pq", "bq"):
            codes = self._resident(self._code_chunks)
            with self._device("flat", len(queries)):
                if cfg.quantization == "pq":
                    lut = pq_mod.build_adc_lut(
                        q, self._pq.codebooks,
                        normalize_inputs=cfg.metric == "cosine")
                    d = pq_mod.adc_distances(lut, codes)
                else:
                    d = bq_mod.hamming_distances(self._bq.encode(q), codes)
                    d = d.astype(jnp.float32)
                if mask_j is not None:
                    d = jnp.where(mask_j[None, :], d, jnp.inf)
                neg_top, idx = jax.device_get(
                    jax.lax.top_k(-d, min(k, d.shape[1])))
                del q, mask_j, d            # freed inside the pass's span
            return -neg_top, idx.astype(np.int32)
        corpus = self._resident(self._vectors)
        with self._device("flat", len(queries)):
            out = jax.device_get(flat_search(q, corpus, min(k, self._n),
                                             metric=cfg.metric, mask=mask_j))
            del q, mask_j               # the pass's uploads are freed here
        return out

    def _hnsw_pass(self, queries, k, ef, mask, expansion_width=None):
        """Wide-beam-search the sealed graph only (delta rows merge
        separately).  Quantized engines traverse layer 0 in *code domain*:
        PQ pops evaluate per-query ADC LUTs against the uint code matrix,
        BQ pops XOR+popcount packed words — both through the fused
        beam-gather kernel path (kernels/ops.py), never a float32
        reconstruction gather."""
        cfg = self.config
        g, max_level, metric = self._device_graph
        n_sealed = self._packed.n
        width = self.effective_expansion_width(expansion_width)
        ef_eff = max(ef, k)
        if mask is not None:
            ef_eff = min(max(ef_eff * 2, k * 4), n_sealed)
        q = queries
        q_codes = None
        if metric == "dot" and cfg.quantization == "none":
            with tracing.span("engine.prep"):
                q = preprocess_vectors(queries, cfg.metric)
        elif cfg.quantization == "bq":
            raw_q = self._h2d(queries)
            with self._device("encode", len(queries)):
                packed_q = self._bq.encode(raw_q)        # (Q, W) uint32
                signs = np.asarray(bq_mod.unpack_bits(packed_q,
                                                      cfg.bq.bits),
                                   dtype=np.float32)
            q = signs * 2.0 - 1.0            # descent proxy (±1 sign vectors)
            if g.codes is not None:
                metric = "hamming"
                q_codes = packed_q
        elif cfg.quantization == "pq":
            if cfg.metric == "cosine":
                q = preprocess_vectors(queries, "cosine")
            if g.codes is not None:
                metric = "adc"
                q_codes = pq_mod.build_adc_lut(
                    self._h2d(queries), self._pq.codebooks,
                    normalize_inputs=cfg.metric == "cosine")
        q = self._h2d(q)
        with self._device("hnsw", len(queries)) as dev:
            d, ids, iters = jax.device_get(hnsw_search(
                g, q, k=min(ef_eff, n_sealed), ef=min(ef_eff, n_sealed),
                max_level=max_level, metric=metric, expansion_width=width,
                q_codes=q_codes, with_iters=True))
            real = self._real(len(queries))
            trips = int(iters[:real].sum())
            dev.set(trips=trips)
        self.hnsw_trips += trips
        self.hnsw_queries += real
        with tracing.span("engine.post"):
            if metric == "hamming":
                # back to the -dot space the delta scan / merge uses:
                # dot(±1) = bits - 2·hamming, so -dot = 2·hamming - bits
                # (exact)
                d = np.where(np.isfinite(d), 2.0 * d - float(cfg.bq.bits),
                             d)
            d, ids = self._apply_mask(d, ids, mask, n_sealed)
            return d[:, :k], ids[:, :k]

    def effective_expansion_width(self, override: Optional[int] = None) -> int:
        """Per-query override > EngineConfig.expansion_width > HNSWConfig."""
        width = (override if override is not None
                 else self.config.expansion_width
                 if self.config.expansion_width is not None
                 else self.config.hnsw.expansion_width)
        if width < 1:
            raise ValueError(f"expansion_width must be >= 1, got {width}")
        return int(width)

    def _ivf_pass(self, queries, k, mask):
        """Probe the sealed IVF lists only (delta rows merge separately)."""
        eff, q = self._resident(self._ivf_effective), self._h2d(queries)
        with self._device("ivf", len(queries)):
            d, ids = jax.device_get(self._ivf.search(eff, q, k))
        with tracing.span("engine.post"):
            d, ids = self._apply_mask(d, ids, mask, self._sealed_n)
            return d[:, :k], ids[:, :k]

    @staticmethod
    def _apply_mask(d, ids, mask, n_rows):
        """Demote masked-out candidates to +inf/-1 and re-sort.  `mask` is
        corpus-global; candidate ids come from the sealed structure, so only
        its first `n_rows` entries apply (-1 padding maps to False)."""
        if mask is None:
            return d, ids
        allowed = np.concatenate([mask[:n_rows], [False]])
        ok = allowed[ids]
        d = np.where(ok, d, np.inf)
        order = np.argsort(d, axis=1, kind="stable")
        d = np.take_along_axis(d, order, axis=1)
        ids = np.where(np.take_along_axis(ok, order, axis=1),
                       np.take_along_axis(ids, order, axis=1), -1)
        return d, ids

    def _delta_pass(self, queries, k, mask):
        """Exact scan of the delta segment in the *sealed pass's* distance
        space, so `merge_candidates` can interleave the two lists directly:

          * hnsw + none  — graph traverses preprocessed raw vectors with the
            device metric ("dot" for cosine/dot, "l2" for l2);
          * hnsw + pq    — squared L2 to reconstructions (== ADC, exactly);
          * hnsw + bq    — -dot of ±1 sign vectors (monotone in Hamming);
          * ivf          — squared L2 of `_prep`-ed vectors, the same
            contraction `_ivf_search` evaluates inside probed lists.

        Returned ids are global (delta start offset applied).
        """
        cfg = self.config
        delta = self._delta
        n_d = len(delta)
        eff_dev, metric = self._delta_effective()
        if cfg.index == "ivf":
            raw_q = self._h2d(queries)
            with self._device("encode", len(queries)):
                q = np.asarray(self._ivf._prep(raw_q))
        elif cfg.quantization == "pq":
            q = preprocess_vectors(queries, "cosine") \
                if cfg.metric == "cosine" else queries
        elif cfg.quantization == "bq":
            raw_q = self._h2d(queries)
            with self._device("encode", len(queries)):
                q = np.asarray(bq_mod.unpack_bits(
                    self._bq.encode(raw_q), cfg.bq.bits),
                    dtype=np.float32) * 2.0 - 1.0
        else:
            q = preprocess_vectors(queries, cfg.metric)
        padded = int(eff_dev.shape[0])
        live = (np.ones(n_d, dtype=bool) if mask is None
                else np.asarray(mask[delta.start:], dtype=bool))
        if padded > n_d:
            live = np.concatenate([live, np.zeros(padded - n_d, dtype=bool)])
        q, live = self._h2d(q), self._h2d(live)
        with self._device("delta", len(queries)):
            d, ids = jax.device_get(flat_search(
                q, eff_dev, min(k, padded), metric=metric, mask=live,
                base_index=delta.start))
        return d, ids.astype(np.int32)

    def _delta_effective(self):
        """Device-resident distance-space matrix for the delta scan, padded
        to a power of two.  Its inputs only change on append, so it is
        cached per (segment, version) — the padding additionally keeps the
        jitted scan from retracing as the delta grows row by row.  Returns
        (device matrix, flat_search metric)."""
        cfg = self.config
        delta = self._delta
        cached = self._delta_cache
        if (cached is not None and cached[0] is delta
                and cached[1] == delta.version):
            return cached[2], cached[3]
        if cfg.index == "ivf":
            eff = (np.asarray(self._pq.decode(jnp.asarray(delta.codes)))
                   if cfg.quantization == "pq" else delta.raw)
            eff = np.asarray(self._ivf._prep(jnp.asarray(eff)))
            metric = "l2"
        elif cfg.quantization == "pq":
            eff = np.asarray(self._pq.decode(jnp.asarray(delta.codes)))
            metric = "l2"
        elif cfg.quantization == "bq":
            eff = np.asarray(bq_mod.unpack_bits(
                jnp.asarray(delta.codes), cfg.bq.bits),
                dtype=np.float32) * 2.0 - 1.0
            metric = "dot"
        else:
            eff = preprocess_vectors(delta.raw, cfg.metric)
            metric = "l2" if cfg.metric == "l2" else "dot"
        n_d = len(delta)
        padded = 1 << max(0, n_d - 1).bit_length()
        if padded > n_d:
            eff = np.concatenate(
                [eff, np.zeros((padded - n_d, eff.shape[1]), eff.dtype)])
        eff_dev = self._h2d(eff)
        self._delta_cache = (delta, delta.version, eff_dev, metric)
        return eff_dev, metric

    def exact_rescore(self, queries, cand_ids, k, mask=None):
        """Exact re-ranking of first-pass candidates in the engine metric
        (paper's optional precision knob) — also the public backend of the
        plan layer's explicit `rescore` stage.  The row mask must be
        re-applied here: exact distances would otherwise resurrect
        masked-out candidates that the first pass only demoted to +inf."""
        pair = get_metric(self.config.metric)
        with tracing.span("engine.post"):
            safe = np.maximum(cand_ids, 0)
            cand_vecs = self.vectors[safe]             # (Q, k', D)
        n = len(queries)
        q = [self._h2d(queries[i: i + 1]) for i in range(n)]
        cand = [self._h2d(cand_vecs[i]) for i in range(n)]
        with self._device("rescore", n):
            d = np.stack([r[0] for r in jax.device_get(
                [pair(q[i], cand[i]) for i in range(n)])])
        with tracing.span("engine.post"):
            ok = cand_ids >= 0
            if mask is not None:
                ok &= mask[safe]
            d = np.where(ok, d, np.inf)
            order = np.argsort(d, axis=1, kind="stable")[:, :k]
            d = np.take_along_axis(d, order, axis=1)
            ids = np.take_along_axis(cand_ids, order, axis=1)
            return d, np.where(np.isfinite(d), ids, -1)

    # ----------------------------------------------------------- persistence
    def state_dict(self) -> Dict[str, Any]:
        state: Dict[str, Any] = {
            "vectors": self.vectors,
            "n": np.array([self._n], dtype=np.int64),
            # rows in [0, sealed_n) are covered by the serialized index;
            # rows beyond it round-trip as the delta segment (no rebuild)
            "sealed_n": np.array([self._sealed_n], dtype=np.int64),
            "dirty": np.array([self._dirty]),
        }
        if self._codes is not None:
            state["codes"] = self._codes
        if self._pq is not None:
            state.update({f"pq.{k}": v for k, v in self._pq.state_dict().items()})
        if self._bq is not None:
            state.update({f"bq.{k}": v for k, v in self._bq.state_dict().items()})
        if self._packed is not None:
            state.update({f"hnsw.{k}": v
                          for k, v in self._packed.state_dict().items()})
        if self._ivf is not None:
            state.update({f"ivf.{k}": v
                          for k, v in self._ivf.state_dict().items()})
        state.update({f"meta.{k}": v
                      for k, v in self.metadata.state_dict().items()})
        return state

    @classmethod
    def from_state_dict(cls, config: EngineConfig,
                        state: Dict[str, Any]) -> "QuantixarEngine":
        eng = cls(config)
        eng._vectors = ChunkedArray(
            [np.asarray(state["vectors"], dtype=np.float32)])
        eng._n = int(state["n"][0])
        eng.metadata = MetadataStore.from_state_dict(
            {k[5:]: v for k, v in state.items() if k.startswith("meta.")})
        if "codes" in state:
            eng._codes = np.asarray(state["codes"])
        pq_state = {k[3:]: v for k, v in state.items() if k.startswith("pq.")}
        if pq_state:
            eng._pq = pq_mod.ProductQuantizer(dataclasses.replace(
                config.pq, metric="cosine" if config.metric == "cosine" else "l2"))
            eng._pq.load_state_dict(pq_state)
        bq_state = {k[3:]: v for k, v in state.items() if k.startswith("bq.")}
        if bq_state:
            eng._bq = bq_mod.BinaryQuantizer(config.bq)
            eng._bq.load_state_dict(bq_state)
        sealed_n = int(state["sealed_n"][0]) if "sealed_n" in state else eng._n
        ivf_state = {k[4:]: v for k, v in state.items()
                     if k.startswith("ivf.")}
        if ivf_state:
            # mirror _build_index exactly: PQ probes reconstructions under L2
            # (the ADC identity), everything else probes raw vectors under
            # the engine metric — a mismatch here silently changes results
            if config.quantization == "pq":
                eng._ivf = IVFIndex(dataclasses.replace(config.ivf,
                                                        metric="l2"))
                eff, _ = eng._effective_vectors()
            else:
                eng._ivf = IVFIndex(config.ivf)
                eff = eng.vectors
            eng._ivf.load_state_dict(ivf_state)
            # lists cover sealed rows only
            eng._ivf_effective = ChunkedArray([eff[:sealed_n]])
            eng._dirty = False
        hnsw_state = {k[5:]: v for k, v in state.items()
                      if k.startswith("hnsw.")}
        if hnsw_state:
            eff_metric = ("l2" if config.quantization == "pq" else
                          "dot" if config.quantization == "bq" else config.metric)
            eng._packed = PackedHNSW.from_state_dict(
                hnsw_state, dataclasses.replace(config.hnsw, metric=eff_metric))
            eng._device_graph = eng._to_device_graph()
            eng._dirty = False
        elif config.index == "flat" and eng._n:
            eng._dirty = False
        if "dirty" in state and bool(state["dirty"][0]):
            eng._dirty = True
        if not eng._dirty:
            # reconstruct the segment split: sealed index + delta tail
            eng._sealed_n = sealed_n
            eng._delta = DeltaSegment(start=sealed_n, dim=config.dim)
            if eng._n > sealed_n:
                tail_codes = (eng._codes[sealed_n:]
                              if eng._codes is not None else None)
                eng._delta.append(eng.vectors[sealed_n:], tail_codes)
        return eng

    def stats(self) -> Dict[str, Any]:
        out = {"n": self._n, "dim": self.config.dim,
               "index": self.config.index,
               "quantization": self.config.quantization,
               "metric": self.config.metric,
               "build_seconds": self.build_seconds,
               "h2d_bytes": self.h2d_bytes,
               "hnsw_trips": self.hnsw_trips,
               "hnsw_queries": self.hnsw_queries,
               "flat_fallbacks": self.flat_fallbacks,
               "resident_hits": self.resident_hits,
               "resident_uploads": self.resident_uploads,
               "resident_appends": self.resident_appends,
               "sealed_rows": self._sealed_n,
               "delta_rows": self.delta_rows,
               "index_builds": self.index_builds,
               "quantizer_trains": self.quantizer_trains,
               "seals": self.seals}
        if self.config.index == "hnsw":
            out["builder"] = self.config.builder
        if self._packed is not None:
            out.update(self._packed.degree_stats())
            out.update(self._packed.build_info)
        if self._ivf is not None and self._ivf.list_sizes is not None:
            sizes = np.asarray(self._ivf.list_sizes)
            out["ivf_lists"] = int(sizes.shape[0])
            out["ivf_mean_list"] = float(sizes.mean())
            out["ivf_max_list"] = int(sizes.max())
        if self._pq is not None:
            out["compression"] = self._pq.compression_ratio(self.config.dim)
        if self._bq is not None:
            out["compression"] = self._bq.compression_ratio(self.config.dim)
        return out
