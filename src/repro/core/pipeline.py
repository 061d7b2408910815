"""End-to-end LAF pipeline: train estimator on the 80% split, cluster the
20% split, with the paper's timing discipline (prediction time counts,
training time does not — §3.1 Metrics).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from ..data.synthetic import train_test_split
from ..obs import span as _span
from .cardinality import TrainedEstimator, train_rmi
from .dbscan import DBSCANResult, dbscan_parallel
from .dbscan_pp import auto_sample_fraction, dbscan_pp, laf_dbscan_pp
from .laf_dbscan import laf_dbscan

__all__ = ["LAFPipeline", "ClusterOutcome"]


@dataclass
class ClusterOutcome:
    result: DBSCANResult
    elapsed_s: float               # clustering time incl. estimator predict
    predict_s: float = 0.0         # estimator prediction share
    method: str = ""
    params: Dict = field(default_factory=dict)


class LAFPipeline:
    """Owns a trained cardinality estimator + the LAF-enhanced engines.

    ``backend`` selects the range-query engine for every clustering
    method (``repro.index``): ``"exact"`` (default), ``"random_projection"``,
    or a constructed ``RangeBackend`` instance; per-call ``backend=``
    kwargs override it.  ``device`` picks the backend evaluator (fused
    Pallas tile vs host numpy; ``"auto"`` = tile iff a TPU is present)
    and is likewise overridable per call.  ``cluster_device`` routes
    cluster formation (``laf_dbscan``'s packed one-launch program vs
    the host union-find oracle; see ``LAFClusterConfig``).
    """

    def __init__(
        self,
        *,
        eps_grid=None,
        epochs: int = 200,
        batch_size: int = 512,
        lr: float = 1e-3,
        seed: int = 0,
        backend="exact",
        device="auto",
        cluster_device="auto",
    ):
        self.eps_grid = eps_grid
        self.epochs = epochs
        self.batch_size = batch_size
        self.lr = lr
        self.seed = seed
        self.backend = backend
        self.device = device
        self.cluster_device = cluster_device
        self.estimator: Optional[TrainedEstimator] = None
        self._stream = None  # StreamingLAF, created by the first partial_fit

    # -- estimator ---------------------------------------------------------
    def fit(self, train_vectors: np.ndarray) -> "LAFPipeline":
        self.estimator = train_rmi(
            train_vectors,
            eps_grid=self.eps_grid,
            epochs=self.epochs,
            batch_size=self.batch_size,
            lr=self.lr,
            seed=self.seed,
        )
        return self

    def fit_split(self, data: np.ndarray, frac_train: float = 0.8):
        """Paper protocol: 8:2 split; returns the test split to cluster."""
        train, test = train_test_split(data, frac_train, self.seed)
        self.fit(train)
        return test

    def predict_counts(self, vectors: np.ndarray, eps: float) -> np.ndarray:
        assert self.estimator is not None, "call fit() first"
        return self.estimator.predict_counts(vectors, eps)

    # -- streaming (repro.stream) ------------------------------------------
    @property
    def stream(self):
        """The live ``StreamingLAF`` (None until the first ``partial_fit``)."""
        return self._stream

    def partial_fit(self, batch: np.ndarray, *, eps: float = None, tau: int = None, **kw):
        """Ingest an embedding batch into the maintained online clustering.

        The first call fixes the (eps, tau) operating point and builds a
        ``repro.stream.StreamingLAF`` on this pipeline's backend/device;
        a trained estimator (from ``fit``) is wired in as the ingest
        fast path automatically (pass ``use_estimator=False`` to force
        the exact path).  Later calls just stream batches in — the
        maintained counts are eps-specific, so changing eps/tau
        mid-stream is an error, not a silent no-op.  Returns the
        per-batch ``IngestReport``.
        """
        if self._stream is None:
            if eps is None or tau is None:
                raise ValueError("the first partial_fit must fix eps= and tau=")
            from ..stream import StreamingLAF

            from ..index.base import RangeBackend

            if self.estimator is not None:
                kw.setdefault("estimator", self.estimator)
                kw.setdefault("use_estimator", True)
            kw.setdefault("backend", self.backend)
            if not isinstance(kw["backend"], RangeBackend):
                # a constructed instance keeps its own evaluator; only
                # registry names take the pipeline's device choice
                kw.setdefault("device", self.device)
            self._stream = StreamingLAF(eps, tau, **kw)
            return self._stream.partial_fit(batch)
        if (eps is not None and eps != self._stream.eps) or (
            tau is not None and tau != self._stream.tau
        ):
            raise ValueError(
                f"stream is live at eps={self._stream.eps}, tau={self._stream.tau}; "
                f"got eps={eps}, tau={tau} — the maintained counts are "
                f"operating-point-specific (start a new pipeline/stream to change)"
            )
        if kw:
            raise ValueError(
                f"stream is live; constructor kwargs {sorted(kw)} cannot be "
                f"applied after the first partial_fit"
            )
        return self._stream.partial_fit(batch)

    def assign(self, queries: np.ndarray, **kw):
        """Serving API: cluster ids + confidence for unseen vectors
        against the streamed clustering (``repro.stream.serve``)."""
        assert self._stream is not None, "call partial_fit() first"
        return self._stream.assign(queries, **kw)

    # -- engines -----------------------------------------------------------
    def cluster_laf_dbscan(
        self, vectors: np.ndarray, eps: float, tau: int, alpha: float, **kw
    ) -> ClusterOutcome:
        kw.setdefault("backend", self.backend)
        kw.setdefault("device", self.device)
        kw.setdefault("cluster_device", self.cluster_device)
        # forced spans: JAX dispatch is async, so reported phase times
        # must come from synced span durations, not bare wall clocks
        with _span("laf.run", n=len(vectors), eps=float(eps), tau=int(tau),
                   force=True) as run:
            with _span("laf.predict", n=len(vectors), force=True) as pre:
                pred = self.predict_counts(vectors, eps)
                pre.sync_on(pred)
            res = laf_dbscan(vectors, eps, tau, alpha, pred, seed=self.seed, **kw)
            run.sync_on((res.labels, res.core))
        return ClusterOutcome(res, run.dur, pre.dur, "LAF-DBSCAN",
                              {"eps": eps, "tau": tau, "alpha": alpha})

    def cluster_dbscan(self, vectors: np.ndarray, eps: float, tau: int, **kw) -> ClusterOutcome:
        kw.setdefault("backend", self.backend)
        kw.setdefault("device", self.device)
        with _span("dbscan.run", n=len(vectors), force=True) as run:
            res = dbscan_parallel(vectors, eps, tau, **kw)
            run.sync_on((res.labels, res.core))
        return ClusterOutcome(res, run.dur, 0.0, "DBSCAN", {"eps": eps, "tau": tau})

    def cluster_dbscan_pp(
        self, vectors: np.ndarray, eps: float, tau: int,
        *, delta: float = 0.2, alpha: float = 1.0, p: Optional[float] = None, **kw
    ) -> ClusterOutcome:
        kw.setdefault("backend", self.backend)
        kw.setdefault("device", self.device)
        with _span("dbscanpp.run", n=len(vectors), force=True) as run:
            if p is None:
                pred = self.predict_counts(vectors, eps)
                p = auto_sample_fraction(pred, tau, alpha, delta)
            res = dbscan_pp(vectors, eps, tau, p, seed=self.seed, **kw)
            run.sync_on((res.labels, res.core))
        return ClusterOutcome(res, run.dur, 0.0, "DBSCAN++",
                              {"eps": eps, "tau": tau, "p": p})

    def cluster_laf_dbscan_pp(
        self, vectors: np.ndarray, eps: float, tau: int,
        *, delta: float = 0.2, alpha: float = 1.0, p: Optional[float] = None, **kw
    ) -> ClusterOutcome:
        kw.setdefault("backend", self.backend)
        kw.setdefault("device", self.device)
        with _span("laf.run", n=len(vectors), force=True) as run:
            with _span("laf.predict", n=len(vectors), force=True) as pre:
                pred_all = self.predict_counts(vectors, eps)
                if p is None:
                    p = auto_sample_fraction(pred_all, tau, alpha, delta)
                n = vectors.shape[0]
                m = max(1, int(round(p * n)))
                rng = np.random.default_rng(self.seed)
                sample_idx = np.sort(rng.choice(n, size=m, replace=False))
                pre.sync_on(pred_all)
            res = laf_dbscan_pp(
                vectors, eps, tau, p, pred_all[sample_idx],
                alpha=alpha, seed=self.seed, sample_idx=sample_idx, **kw
            )
            run.sync_on((res.labels, res.core))
        return ClusterOutcome(res, run.dur, pre.dur, "LAF-DBSCAN++",
                              {"eps": eps, "tau": tau, "p": p, "alpha": alpha})
