"""LAF post-processing — Algorithms 2 and 3 of the paper.

``UpdatePartialNeighbors`` (Alg. 2): after every *executed* range query
(P, N), every neighbor P_n already registered in the partial-neighbor
map 𝓔 gains P as a partial neighbor.

``PostProcessing`` (Alg. 3): a registered point P with |𝓔(P)| ≥ τ is a
detected false-negative core prediction.  The clusters of its partial
neighbors were wrongly separated by P, so they are merged into one
destination cluster (that of a randomly selected non-noise member).  We
additionally assign P itself to the destination cluster — P is a proven
core point, and leaving it noise would contradict DBSCAN semantics; the
paper's published code does the same (merge implies membership).
Merging is transitive across rescue points; a union-find over cluster
ids realizes exactly the sequential chain of merges.

Two forms of Algorithm 3 live here:

* ``post_processing`` reads 𝓔 as a ``PartialNeighborMap`` of Python
  sets, verbatim.  It is the oracle: ``laf_dbscan_sequential`` and
  LAF-DBSCAN++ (``dbscan_pp``) run it.
* ``post_processing_incidence`` reads 𝓔 as arrays: |𝓔(P)| per rescued
  point and the distinct (pre-merge cluster, rescued point) pairs among
  its members.  Algorithm 3 needs nothing else, so ``laf_dbscan`` builds
  only these, with array reductions over each block of subset hits.

The two give the same partition.  The random destination cannot change
it: every cluster of 𝓔(P) is unioned with the destination, so the
destination's root after the merges is the root of any one of them,
whichever member was drawn.  Only the label numbers before compaction
may differ; the array form takes P's least cluster id as its anchor.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set

import numpy as np

from ..obs import metrics as _metrics
from .union_find import UnionFind, find_roots_vec

__all__ = [
    "PartialNeighborMap",
    "update_partial_neighbors",
    "post_processing",
    "post_processing_incidence",
]

NOISE = -1
UNDEFINED = -2


class PartialNeighborMap:
    """The map 𝓔: predicted-stop point -> set of partial neighbors."""

    def __init__(self):
        self._map: Dict[int, Set[int]] = {}

    def register(self, p: int) -> None:
        """Lines 8 / 27 of Algorithm 1: ``if P not in 𝓔 then 𝓔(P) := ∅``."""
        self._map.setdefault(int(p), set())

    def __contains__(self, p: int) -> bool:
        return int(p) in self._map

    def __getitem__(self, p: int) -> Set[int]:
        return self._map[int(p)]

    def items(self):
        return self._map.items()

    def __len__(self):
        return len(self._map)


def update_partial_neighbors(p: int, neighbors, emap: PartialNeighborMap) -> PartialNeighborMap:
    """Algorithm 2, verbatim."""
    for pn in neighbors:
        pn = int(pn)
        if pn in emap:
            emap[pn].add(int(p))
    return emap


def post_processing(
    labels: np.ndarray,
    emap: PartialNeighborMap,
    tau: int,
    *,
    rng: Optional[np.random.Generator] = None,
) -> np.ndarray:
    """Algorithm 3 with transitive merges via union-find.

    Returns updated labels (same id space; merged clusters collapse onto
    the destination's representative id).  The points it assigns are
    counted into ``laf.rescue.merged``.
    """
    rng = rng or np.random.default_rng(0)
    labels = labels.copy()
    n_clusters = int(labels.max()) + 1 if labels.max() >= 0 else 0
    if n_clusters == 0:
        return labels
    uf = UnionFind(n_clusters)
    rescued: List[tuple[int, int]] = []  # (point, destination cluster id)

    for p, partial in emap.items():
        if len(partial) < tau:
            continue
        members = np.fromiter(partial, dtype=np.int64)
        member_labels = labels[members]
        non_noise = member_labels[member_labels >= 0]
        if len(non_noise) == 0:
            continue
        # line 3: randomly select a non-noise neighbor P' in 𝓔(P)
        dest = int(rng.choice(non_noise))
        # line 5: merge the clusters of 𝓔(P) into the destination cluster
        for c in np.unique(non_noise):
            uf.union(dest, int(c))
        rescued.append((int(p), dest))

    _metrics.counter("laf.rescue.merged").inc(len(rescued))
    remap = np.array([uf.find(c) for c in range(n_clusters)], dtype=np.int64)
    mask = labels >= 0
    labels[mask] = remap[labels[mask]]
    for p, dest in rescued:
        labels[p] = remap[dest]
    return labels


def post_processing_incidence(
    labels: np.ndarray,
    emap_size: np.ndarray,
    cluster_ids: np.ndarray,
    point_cols: np.ndarray,
    rescue_idx: np.ndarray,
    tau: int,
) -> np.ndarray:
    """Algorithm 3 over the (pre-merge cluster, rescued point) incidence.

    Point ``rescue_idx[j]`` has ``emap_size[j]`` = |𝓔| partial neighbors;
    each pair ``(cluster_ids[k], point_cols[k])`` says that a member of
    𝓔(``rescue_idx[point_cols[k]]``) lies in cluster ``cluster_ids[k]``
    (pairs may repeat; noise has none).  Returns labels in the same id
    space as ``post_processing``, with the same partition.  The distinct
    pairs are counted into ``laf.rescue.links``, the points it assigns
    into ``laf.rescue.merged``.
    """
    labels = labels.copy()
    n_clusters = int(labels.max()) + 1 if labels.max() >= 0 else 0
    if n_clusters == 0:
        return labels
    # sorted by point, then cluster: each point's first pair is its anchor
    key = np.unique(
        np.asarray(point_cols, dtype=np.int64) * n_clusters
        + np.asarray(cluster_ids, dtype=np.int64)
    )
    _metrics.counter("laf.rescue.links").inc(len(key))
    col, cluster = np.divmod(key, n_clusters)
    keep = emap_size[col] >= tau
    col, cluster = col[keep], cluster[keep]
    first = np.ones(len(col), dtype=bool)
    first[1:] = col[1:] != col[:-1]
    anchor = cluster[first]
    _metrics.counter("laf.rescue.merged").inc(len(anchor))
    rest = ~first
    root = _cluster_roots(n_clusters, anchor[np.cumsum(first)[rest] - 1], cluster[rest])
    mask = labels >= 0
    labels[mask] = root[labels[mask]]
    labels[rescue_idx[col[first]]] = root[anchor]
    return labels


def _cluster_roots(n: int, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Least id of each of ``n`` ids' component under the edges (a, b):
    every round hooks the larger root of each edge that still crosses
    two components onto the smaller."""
    parent = np.arange(n, dtype=np.int64)
    while len(a):
        ra, rb = find_roots_vec(parent, a), find_roots_vec(parent, b)
        cross = ra != rb
        a, b, ra, rb = a[cross], b[cross], ra[cross], rb[cross]
        np.minimum.at(parent, np.maximum(ra, rb), np.minimum(ra, rb))
    return find_roots_vec(parent, np.arange(n))
