"""Exact DBSCAN: faithful sequential transcription + batch-parallel engine.

``dbscan_sequential`` is the line-by-line classic algorithm (Ester et al.
1996) exactly as the black text of the paper's Algorithm 1 — it is the
ground-truth producer (the paper uses original DBSCAN's output as ground
truth for ARI/AMI).

``dbscan_parallel`` is the TPU-shaped reformulation (see DESIGN.md §2):
   1. neighbor counts for ALL points via blocked matmul  -> core mask
   2. connected components of the core-core eps-graph    -> cluster ids
      (vectorized star-unions: one union-find hook per core row, no
      per-edge Python — dense clusters are cliques, per-edge is O(n^2))
   3. border points attach to their first core finder's cluster
Both return labels with the same convention: -1 noise, clusters 0..k-1.
The partitions are identical up to border-point ties (a border point
within eps of two clusters may legally join either); tests compare via
ARI and structural invariants.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .range_query import range_counts
from .union_find import compact_labels_from_parent, union_star

__all__ = ["DBSCANResult", "dbscan_sequential", "dbscan_parallel", "core_mask", "NOISE", "UNDEFINED"]

UNDEFINED = -2
NOISE = -1


@dataclass
class DBSCANResult:
    labels: np.ndarray          # (n,) int64: -1 noise, else cluster id
    core: np.ndarray            # (n,) bool
    n_clusters: int
    n_range_queries: int        # executed range queries (the paper's cost unit)
    extras: dict = field(default_factory=dict)

    @property
    def noise_ratio(self) -> float:
        return float(np.mean(self.labels == NOISE))


def dbscan_sequential(
    data: np.ndarray, eps: float, tau: int, *, precomputed_neighbors=None
) -> DBSCANResult:
    """Classic DBSCAN (the black text of the paper's Algorithm 1)."""
    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    neigh = precomputed_neighbors
    queries = 0
    thresh = 1.0 - eps

    def range_query(i: int) -> np.ndarray:
        nonlocal queries
        queries += 1
        if neigh is not None:
            return neigh[i]
        return np.nonzero(data[i] @ data.T > thresh)[0]

    labels = np.full(n, UNDEFINED, dtype=np.int64)
    core = np.zeros(n, dtype=bool)
    c = 0
    for p in range(n):
        if labels[p] != UNDEFINED:
            continue
        nbrs = range_query(p)
        if len(nbrs) < tau:
            labels[p] = NOISE
            continue
        core[p] = True
        labels[p] = c
        seeds = deque(int(q) for q in nbrs if q != p)
        while seeds:
            q = seeds.popleft()
            if labels[q] == NOISE:
                labels[q] = c  # noise -> border
            if labels[q] != UNDEFINED:
                continue
            labels[q] = c
            qn = range_query(q)
            if len(qn) >= tau:
                core[q] = True
                seeds.extend(int(x) for x in qn)
        c += 1
    return DBSCANResult(labels, core, c, queries)


def core_mask(data: np.ndarray, eps: float, tau: int, block_size: int = 2048) -> np.ndarray:
    counts = np.asarray(range_counts(data, data, eps, block_size=block_size))
    return counts >= tau


def dbscan_parallel(
    data: np.ndarray,
    eps: float,
    tau: int,
    *,
    block_size: int = 2048,
    backend="exact",
    device="auto",
) -> DBSCANResult:
    """Batch-parallel DBSCAN (blocked core detection + star unions).

    ``backend`` selects the range-query engine (``repro.index``): the
    default ``"exact"`` reproduces brute-force DBSCAN; an ANN backend
    (``"random_projection"`` or a fit instance) makes every range query
    cheaper at a bounded recall cost.  ``device`` picks the backend's
    evaluator (``True`` = fused Pallas tile, ``False`` = host numpy,
    ``"auto"`` = tile iff a TPU is present); constructed backend
    instances keep their own setting.
    """
    from ..index import as_fitted

    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    bk = as_fitted(backend, data, block_size=block_size, device=device)
    counts = bk.query_counts(np.arange(n), eps)
    core = counts >= tau
    core_idx = np.nonzero(core)[0]

    parent = np.arange(n, dtype=np.int64)
    owner = np.full(n, -1, dtype=np.int64)  # first core finder per column

    for start in range(0, len(core_idx), block_size):
        rows = core_idx[start : start + block_size]
        hit = bk.query_hits(rows, eps)  # (b, n)
        hit_core = hit & core[None, :]
        for bi, i in enumerate(rows):
            members = np.nonzero(hit_core[bi])[0]
            union_star(parent, members)
        # border claim: first core row in this block to hit an unclaimed col
        claimed = hit.any(axis=0)
        todo = claimed & (owner < 0) & ~core
        if todo.any():
            first = hit[:, todo].argmax(axis=0)
            owner[todo] = rows[first]

    labels = compact_labels_from_parent(parent, core)
    borders = np.nonzero(~core & (owner >= 0))[0]
    labels[borders] = labels[owner[borders]]
    n_clusters = int(labels.max()) + 1 if labels.max() >= 0 else 0
    return DBSCANResult(labels, core, n_clusters, n)
