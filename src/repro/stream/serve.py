"""Serving-grade cluster assignment for unseen vectors.

The clustering engines label points *of the database*; serving needs
the other direction — given live-traffic query embeddings that are not
in the database, which maintained cluster does each belong to, and how
sure are we?  ``ClusterIndex`` is an immutable snapshot built from a
:class:`~repro.stream.ingest.StreamingLAF` (or any labels + data pair):

1. **centroid shortlist** — score the query against the per-cluster
   centroids (one small matmul) and expand only the best ``shortlist``
   clusters, the retrieval trick ``examples/recsys_serving.py`` serves;
2. **band-verified range query** — inside the shortlist, candidates are
   pruned with the same signed-RP Hamming band the index uses (signature
   XOR+popcount, sure-accept below ``t_lo``, exact dot only for the
   band), so per-query cost is |shortlist members| signature words plus
   a handful of dots — never an O(n·d) scan.  On device this runs
   through the shared sweep engine (``repro.index.sweep``): a block of
   queries is verified against the union of its shortlisted clusters'
   members in **one** launch (``device="auto"`` routes through it
   whenever a TPU backs JAX; the host numpy band loop is
   retained as the oracle);
3. **assignment** — the query joins the cluster holding the plurality
   of its eps-neighbors (DBSCAN's border rule, generalized to ties);
   confidence is the fraction of its found eps-neighbors in that
   cluster.  No eps-neighbor in the shortlist => noise (-1), confidence
   0 — exactly how DBSCAN treats a point no core reaches.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..index.signatures import band_hits, hamming_numpy, sign_signatures
from ..obs import metrics as _metrics, slo as _slo, span as _span

__all__ = ["AssignResult", "ClusterIndex", "bucket_shape"]


def bucket_shape(
    n_cand: int, n_block: int, *, db_tile: int = 256, chunk: int = 256,
    q_tile: int = 128,
) -> tuple[int, int]:
    """Quantized ``(db_bucket, query_chunk)`` launch shape for one serve
    verification block.

    The candidate side rounds up to a power of two no smaller than the
    kernel db tile and the query chunk clamps to the power-of-two block
    size (floored at one q tile), so the jitted engine compiles O(log n)
    distinct shapes over any traffic mix — the compile lattice
    ``repro.analysis``'s recompile check enumerates is exactly this
    function's image."""
    bucket = max(db_tile, 1 << int(np.ceil(np.log2(max(n_cand, 1)))))
    chunk = min(chunk, max(q_tile, 1 << int(np.ceil(np.log2(max(n_block, 1))))))
    return bucket, chunk


@dataclass
class AssignResult:
    labels: np.ndarray       # (q,) int64: cluster id or -1 (noise/unmatched)
    confidence: np.ndarray   # (q,) float32 in [0, 1]
    n_hits: np.ndarray       # (q,) int64: eps-neighbors found in the shortlist

    def __len__(self) -> int:
        return len(self.labels)


class ClusterIndex:
    """Immutable serving snapshot: centroids + per-cluster members (+ the
    signature table when the backing index is signed-RP)."""

    def __init__(
        self,
        data: np.ndarray,
        labels: np.ndarray,
        eps: float,
        *,
        sigs: Optional[np.ndarray] = None,
        projection: Optional[np.ndarray] = None,
        band: Optional[tuple[int, int]] = None,
        version: int = 0,
        device="auto",
        sweep_kw: Optional[dict] = None,
        centroids: Optional[np.ndarray] = None,
    ):
        if device not in (True, False, "auto"):
            raise ValueError(f"device must be True, False, or 'auto', got {device!r}")
        self.eps = float(eps)
        self.version = version
        self.device = device
        # kernel/engine knobs (chunk, q_tile, db_tile, interpret, ...)
        # forwarded to repro.index.sweep — from_stream copies them off
        # the backing index so serving verifies on the same evaluator
        self.sweep_kw = dict(sweep_kw or {})
        self._data = data
        self._sigs = sigs
        self._projection = projection
        self._band = band
        labels = np.asarray(labels)
        self.n_clusters = int(labels.max()) + 1 if labels.size and labels.max() >= 0 else 0
        # members grouped by label: one argsort, then slice per cluster
        mask = labels >= 0
        idx = np.nonzero(mask)[0]
        order = np.argsort(labels[idx], kind="stable")
        self._members = idx[order]
        self._offsets = np.searchsorted(labels[idx][order], np.arange(self.n_clusters + 1))
        if centroids is not None and centroids.shape[0] == self.n_clusters:
            # snapshot restore hands the saved centroids back so a
            # replica skips the per-cluster mean pass at build time
            self.centroids = np.ascontiguousarray(centroids, dtype=np.float32)
        else:
            cents = np.zeros((self.n_clusters, data.shape[1]), dtype=np.float32)
            for c in range(self.n_clusters):
                cents[c] = data[self.members(c)].mean(axis=0)
            norms = np.linalg.norm(cents, axis=1, keepdims=True)
            self.centroids = cents / np.maximum(norms, 1e-12)
        # candidate-bucket shapes this snapshot has launched (each new
        # power-of-two bucket is one engine compile — O(log n) total)
        self._seen_buckets: set = set()

    @classmethod
    def from_stream(cls, stream, centroids: Optional[np.ndarray] = None) -> "ClusterIndex":
        bk = stream.backend
        sweep_kw = {
            k: getattr(bk, a)
            for k, a in (
                ("chunk", "chunk"), ("q_tile", "q_tile"), ("db_tile", "db_tile"),
                ("interpret", "interpret"), ("chunks_per_launch", "chunks_per_launch"),
                ("donate", "donate"),
            )
            if hasattr(bk, a)
        }
        return cls(
            bk.data,
            stream.state.labels(),
            stream.eps,
            sigs=getattr(bk, "signatures", None),
            projection=getattr(bk, "projection", None),
            band=bk.band(stream.eps) if hasattr(bk, "band") else None,
            version=stream.state.version,
            device=getattr(bk, "device", "auto"),
            sweep_kw=sweep_kw,
            centroids=centroids,
        )

    def members(self, c: int) -> np.ndarray:
        """Database row indices of cluster ``c``."""
        return self._members[self._offsets[c] : self._offsets[c + 1]]

    def shortlist(self, queries: np.ndarray, k: int) -> np.ndarray:
        """(q, k) best cluster ids by centroid cosine score."""
        q = _unit_rows(queries)
        k = min(k, self.n_clusters)
        scores = q @ self.centroids.T
        top = np.argpartition(-scores, k - 1, axis=1)[:, :k]
        # order the shortlist best-first (argpartition is unordered)
        row = np.arange(len(q))[:, None]
        return top[row, np.argsort(-scores[row, top], axis=1)]

    def assign(
        self, queries: np.ndarray, *, shortlist: int = 8, min_hits: int = 1
    ) -> AssignResult:
        """Cluster ids + confidence for unseen query vectors."""
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        t0 = time.perf_counter()
        with _span("serve.assign", nq=queries.shape[0], shortlist=shortlist):
            res = self._assign(queries, shortlist=shortlist, min_hits=min_hits)
        if _metrics.enabled():
            # per-call latency into the log-bucket histogram — the p50/
            # p95/p99 the SLO serving roadmap item reports come from here
            _metrics.histogram(
                "serve.assign.latency_s", "assign() wall seconds per call"
            ).observe(time.perf_counter() - t0)
            calls = _metrics.counter("serve.assign.calls")
            calls.inc()
            _metrics.counter("serve.assign.queries").inc(queries.shape[0])
            _metrics.gauge("serve.shortlist").set(min(shortlist, self.n_clusters))
            # periodic SLO sweep: the p99 rule fires (rate-limited) as a
            # structured slo.violation line, never an exception
            if calls.value % _slo.EVAL_EVERY_CALLS == 0:
                _slo.check_and_alert(_slo.SERVE_SLOS)
        return res

    def _assign(
        self, queries: np.ndarray, *, shortlist: int, min_hits: int
    ) -> AssignResult:
        nq = queries.shape[0]
        labels = np.full(nq, -1, dtype=np.int64)
        conf = np.zeros(nq, dtype=np.float32)
        hits_out = np.zeros(nq, dtype=np.int64)
        if self.n_clusters == 0:
            return AssignResult(labels, conf, hits_out)
        q = _unit_rows(queries)
        top = self.shortlist(q, shortlist)
        q_sig = (
            sign_signatures(q, self._projection)
            if self._sigs is not None and self._projection is not None and self._band is not None
            else None
        )
        thresh = 1.0 - self.eps
        cluster_of = np.empty(len(self._data), dtype=np.int64)
        cluster_of[self._members] = np.repeat(
            np.arange(self.n_clusters), np.diff(self._offsets)
        )
        if q_sig is not None and self._use_engine():
            self._assign_engine(
                q, q_sig, top, cluster_of, labels, conf, hits_out, min_hits
            )
            return AssignResult(labels, conf, hits_out)
        for i in range(nq):
            cand = np.concatenate([self.members(c) for c in top[i]])
            if q_sig is not None:
                # the one shared dual-threshold predicate (band_hits):
                # dots are only materialized for the ambiguous band
                t_lo, t_hi = self._band
                ham = hamming_numpy(q_sig[i : i + 1], self._sigs[cand])[0]
                dots = np.zeros(len(cand), dtype=np.float32)
                bi = np.nonzero((ham <= t_hi) & (ham > t_lo))[0]
                if len(bi):
                    dots[bi] = self._data[cand[bi]] @ q[i]
                hit = band_hits(dots, ham, self.eps, t_lo, t_hi)
            else:
                hit = (self._data[cand] @ q[i]) > thresh
            hit_members = cand[hit]
            self._record(
                i, cluster_of[hit_members], labels, conf, hits_out, min_hits
            )
        return AssignResult(labels, conf, hits_out)

    def _record(self, i, hit_clusters, labels, conf, hits_out, min_hits) -> None:
        """Plurality cluster + confidence from one query's eps-neighbor
        cluster ids — the single definition both the host loop and the
        engine path record through, so they stay label-identical."""
        total = len(hit_clusters)
        hits_out[i] = total
        if total < max(min_hits, 1):
            return
        tally = np.bincount(hit_clusters, minlength=self.n_clusters)
        best = int(tally.argmax())
        labels[i] = best
        conf[i] = tally[best] / total

    # -- device-resident assignment (the shared sweep engine) --------------
    def _use_engine(self) -> bool:
        if self.device == "auto":
            from ..kernels.hamming_filter.ops import default_interpret

            return not default_interpret()
        return bool(self.device)

    def _assign_engine(
        self, q, q_sig, top, cluster_of, labels, conf, hits_out, min_hits
    ) -> None:
        """Batch the band verification: one sweep launch per query block
        against the union of the block's shortlisted clusters' members
        (per-query results are then restricted to that query's own
        shortlist, so labels/confidence are identical to the per-query
        host loop)."""
        from ..core.range_query import unpack_bitmap
        from ..index.sweep import sweep_bitmap

        t_lo, t_hi = self._band
        sizes = np.diff(self._offsets)

        def verify(s: int, e: int) -> None:
            ids = np.unique(top[s:e])
            n_cand = int(sizes[ids].sum())
            if n_cand == 0:
                return
            # the block shares one launch over the union of its
            # shortlisted clusters; low-overlap traffic would inflate a
            # query's verified set from |own shortlist| to |union|, so
            # split the block until the shared work stays within ~4x
            # the per-query shortlist totals
            if e - s > 8 and n_cand * (e - s) > 4 * int(sizes[top[s:e]].sum()):
                mid = (s + e) // 2
                verify(s, mid)
                verify(mid, e)
                return
            cand = np.concatenate([self.members(c) for c in ids])
            # bucket the candidate side to a power-of-two row count, no
            # smaller than the kernel db tile (the padding quantum the
            # engine applies anyway; zero rows + zero signatures are
            # exactly the capacity-slack shape its pad correction
            # handles) so the jitted launch compiles O(log n) shapes,
            # not one per shortlist union size — the serving hot path
            kw = dict(self.sweep_kw)
            # the query chunk clamps to the (power-of-two bucketed) leaf
            # size: a split-down leaf of 8 queries must not pad to a
            # full 256-row kernel pass
            bucket, kw["chunk"] = bucket_shape(
                len(cand), e - s,
                db_tile=kw.get("db_tile", 256),
                chunk=kw.get("chunk", 256),
                q_tile=kw.get("q_tile", 128),
            )
            db = np.zeros((bucket, self._data.shape[1]), dtype=np.float32)
            db[: len(cand)] = self._data[cand]
            db_sig = np.zeros((bucket, self._sigs.shape[1]), dtype=np.uint32)
            db_sig[: len(cand)] = self._sigs[cand]
            if (bucket, kw["chunk"]) not in self._seen_buckets:
                self._seen_buckets.add((bucket, kw["chunk"]))
                _metrics.counter("serve.bucket_compiles").inc()
            _metrics.counter("serve.verify_launches").inc()
            _metrics.counter("serve.candidates").inc(int(len(cand)))
            _, bm = sweep_bitmap(
                q[s:e], q_sig[s:e], db, db_sig,
                len(cand), self.eps, t_lo, t_hi, **kw,
            )
            hit = unpack_bitmap(bm, len(cand))
            cl = cluster_of[cand]
            for bi in range(e - s):
                i = s + bi
                sel = cl[hit[bi]]
                # restrict to the query's own shortlist (<= `shortlist`
                # ids) — isin over the few hits, never an O(n_clusters)
                # mask per query
                self._record(
                    i, sel[np.isin(sel, top[i])], labels, conf, hits_out, min_hits
                )

        for s in range(0, q.shape[0], 256):
            verify(s, min(s + 256, q.shape[0]))


def _unit_rows(x: np.ndarray) -> np.ndarray:
    x = np.ascontiguousarray(x, dtype=np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
