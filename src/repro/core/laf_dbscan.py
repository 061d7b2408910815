"""LAF-DBSCAN — Algorithm 1 of the paper.

Two interchangeable engines:

* ``laf_dbscan_sequential`` — a line-by-line transcription of the
  pseudocode (black + red text), used for validation.  The red-text LAF
  insertions are marked ``# LAF:`` inline.

* ``laf_dbscan`` — the batch-parallel TPU-shaped engine (DESIGN.md §2).
  Identical skip/execute decisions (every predicted-core point executes
  exactly one range query in both engines — see DESIGN.md §2), identical
  executed-core cluster structure, and a partial-neighbor map 𝓔 that is
  a superset of the sequential one (post-processing can only rescue
  *more* false negatives).  Range queries for the whole predicted-core
  set are blocked matmuls; cluster formation is vectorized star-unions
  over the executed-core graph.

Both report ``n_range_queries`` — the paper's unit of saved work.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional

import numpy as np

from ..obs import device as _obs_device, get_logger, metrics as _metrics, rate_limited_warn, span as _span
from ..testing import faults as _faults
from .dbscan import NOISE, UNDEFINED, DBSCANResult
from .postprocess import (
    PartialNeighborMap,
    post_processing,
    post_processing_incidence,
    update_partial_neighbors,
)
from .range_query import pack_bitmap, unpack_bitmap
from .union_find import compact_labels, compact_labels_from_parent, union_star

__all__ = ["laf_dbscan_sequential", "laf_dbscan"]


def laf_dbscan_sequential(
    data: np.ndarray,
    eps: float,
    tau: int,
    alpha: float,
    card_est: Callable[[int], float],
    *,
    seed: int = 0,
) -> DBSCANResult:
    """Algorithm 1, faithful transcription.

    ``card_est(i)`` returns the predicted cardinality of point i (the
    RMI estimator, or an oracle in tests).
    """
    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    labels = np.full(n, UNDEFINED, dtype=np.int64)
    core = np.zeros(n, dtype=bool)
    queries = 0
    emap = PartialNeighborMap()                        # LAF: map 𝓔 (line 2)
    thresh = 1.0 - eps

    def range_query(i: int) -> np.ndarray:
        nonlocal queries
        queries += 1
        return np.nonzero(data[i] @ data.T > thresh)[0]

    c = 0
    for p in range(n):
        if labels[p] != UNDEFINED:                     # line 5
            continue
        if card_est(p) < alpha * tau:                  # LAF: line 6
            labels[p] = NOISE                          # line 7
            emap.register(p)                           # LAF: line 8
            continue                                   # line 9
        nbrs = range_query(p)                          # line 10
        update_partial_neighbors(p, nbrs, emap)        # LAF: line 11
        if len(nbrs) < tau:                            # line 12
            labels[p] = NOISE                          # line 13
            continue                                   # line 14
        core[p] = True
        labels[p] = c                                  # line 15
        seeds = deque(int(q) for q in nbrs if q != p)  # line 16: S := N - {P}
        while seeds:                                   # line 17
            q = seeds.popleft()
            if labels[q] == NOISE:                     # line 18
                labels[q] = c
            if labels[q] != UNDEFINED:                 # line 19
                continue
            labels[q] = c                              # line 21
            if card_est(q) >= alpha * tau:             # LAF: line 22
                qn = range_query(q)                    # line 23
                update_partial_neighbors(q, qn, emap)  # LAF: line 24
                if len(qn) >= tau:                     # line 25
                    core[q] = True
                    seeds.extend(int(x) for x in qn)
            else:
                emap.register(q)                       # LAF: line 26-27
        c += 1
    labels = post_processing(                          # LAF: line 28
        labels, emap, tau, rng=np.random.default_rng(seed)
    )
    labels = _compact(labels)
    n_clusters = int(labels.max()) + 1 if labels.max() >= 0 else 0
    return DBSCANResult(labels, core, n_clusters, queries, {"n_registered": len(emap)})


# single-pass np.unique relabeling shared with the union-find module
_compact = compact_labels


def laf_dbscan(
    data: np.ndarray,
    eps: float,
    tau: int,
    alpha: float,
    predicted_counts: np.ndarray,
    *,
    block_size: int = 2048,
    seed: int = 0,
    backend="exact",
    device="auto",
    cluster_device="auto",
    on_device_fault: str = "raise",
) -> DBSCANResult:
    """Batch-parallel LAF-DBSCAN engine.

    Args:
      predicted_counts: (n,) estimator predictions for every point at
        this eps (one batched RMI pass by the caller — kept as an input
        so engines and estimators compose freely; tests pass oracles).
      backend: range-query backend (``repro.index``) — LAF's skip rule
        composes with an ANN backend: the estimator skips whole queries,
        the index then prunes the candidates inside each executed one.
      device: backend evaluator choice (fused Pallas tile vs host; see
        ``dbscan_parallel``); ignored by constructed instances.
      cluster_device: where cluster formation (core test + core-graph
        components + border rule) runs.  ``"auto"`` follows the
        backend: when it packs adjacency natively on device
        (``packs_natively``), the sweep's bitmap slab feeds the packed
        label-propagation program directly and the entire clustering
        syncs to the host exactly once (final labels); otherwise the
        host unpack -> union-find pass runs (the parity oracle).
        ``True`` forces the device program even for host backends (the
        packed blocks are uploaded once — the exact-backend parity
        mode); ``False`` forces the host pass.
      seed: accepted for symmetry with ``laf_dbscan_sequential``; the
        rescue here draws no random destination (its partition does not
        depend on one — see ``core/postprocess.py``), so it is unused.
      on_device_fault: ``"raise"`` (default) surfaces a failure of
        the device cluster launch; ``"degrade"`` (opt-in) falls back to
        the bit-exact host unpack → union-find pass instead (recording
        ``stream.degraded.cluster`` and an ``slo.violation``).
    """
    from ..index import as_fitted

    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    cluster_span = _span("laf.cluster", n=n, eps=float(eps), tau=int(tau))
    cluster_span.__enter__()
    try:
        return _laf_dbscan_body(
            data, eps, tau, alpha, predicted_counts, as_fitted,
            block_size=block_size, backend=backend, device=device,
            cluster_device=cluster_device, on_device_fault=on_device_fault,
        )
    finally:
        cluster_span.__exit__(None, None, None)


def _cluster_pass_device(bk, eps, tau, exec_idx, n, native, block_size):
    """Device-resident pass 1 + pass 2: sweep slab -> packed label
    propagation, one ``device_get`` of the results.

    Returns ``(labels, core, exact_counts, partial_counts)`` with
    identical values to the host pass (min-core-index component
    representatives are what ``union_star``'s min-root merging produces,
    so even the label *numbers* match after ``np.unique``).
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.label_prop import packed_cluster_labels

    _faults.maybe_fail("cluster.launch", n=int(n), n_exec=int(len(exec_idx)))
    n_exec = len(exec_idx)
    mesh = getattr(bk, "mesh", None) if native else None
    with _span("laf.pass1", n=n, n_exec=int(n_exec), block_size=block_size,
               device=True):
        if native:
            # async dispatch: the slab never leaves the device
            with _span("laf.sweep", rows=int(n_exec), synced=False):
                slab, plan = bk.query_bitmap_device(exec_idx, eps)
            rows_op = np.full(plan.nq_padded, n, dtype=np.int64)
            rows_op[:n_exec] = exec_idx
        else:
            # forced parity mode for host backends: pack per block on
            # the host, upload the slab once
            blocks = []
            for start in range(0, n_exec, block_size):
                rows = exec_idx[start : start + block_size]
                with _span("laf.sweep", block=start // block_size, rows=len(rows)):
                    blocks.append(pack_bitmap(bk.query_hits(rows, eps)))
            slab = jnp.asarray(np.concatenate(blocks, axis=0))
            rows_op = exec_idx
    telemetry = _obs_device.device_enabled()
    # only the per-round cluster counters ride this launch: the bitmap
    # sweep carries no occupancy slab (that statistic lives on the count
    # sweeps — see index/sweep.py), so THE device_get fetches exactly
    # the fixpoint outputs
    lp_span = _span("laf.label_prop", rows=int(len(rows_op)), n=n,
                    telemetry=telemetry)
    with lp_span:
        if mesh is not None:
            from ..distributed.index_plane import sharded_cluster_labels

            outs = sharded_cluster_labels(
                slab, rows_op, tau, mesh=mesh, axes=bk._plan.axes, n=n,
                telemetry=telemetry,
            )
        else:
            outs = packed_cluster_labels(
                slab, jnp.asarray(rows_op), tau, n=n, telemetry=telemetry,
            )
        # THE host sync: everything above dispatched asynchronously —
        # telemetry rides the same get, never a second one
        outs_h = jax.device_get(outs)
        _metrics.counter("laf.cluster.device_get").inc()
    rep, owner, col_sum, counts, rounds = outs_h[:5]
    _metrics.counter("laf.cluster.rounds").inc(int(rounds))
    if mesh is not None:
        from ..distributed.index_plane import count_cluster_collectives

        count_cluster_collectives(len(rows_op), int(rounds), bk._plan.n_shards,
                                  telemetry=telemetry)
    if telemetry and len(outs_h) > 5:
        per_round = _obs_device.harvest_cluster_telemetry(outs_h[5], rounds)
        _obs_device.emit_round_spans(getattr(lp_span, "_rec", None), per_round)

    with _span("laf.assemble", n=n):
        exact_counts = np.zeros(n, dtype=np.int64)
        exact_counts[exec_idx] = np.asarray(counts[:n_exec], dtype=np.int64)
        partial_counts = np.asarray(col_sum[:n], dtype=np.int64)
        core = np.zeros(n, dtype=bool)
        core[exec_idx] = exact_counts[exec_idx] >= tau
        rep = np.asarray(rep[:n])
        owner = np.asarray(owner[:n], dtype=np.int64)
        labels = np.full(n, -1, dtype=np.int64)
        ci = np.nonzero(core)[0]
        if len(ci):
            # rep = min core index per component == the union-find root the
            # host pass produces (union_star merges by min root)
            _, inv = np.unique(rep[ci], return_inverse=True)
            labels[ci] = inv
        borders = np.nonzero(~core & (owner < n))[0]
        labels[borders] = labels[owner[borders]]
        # the slab and the program's outputs are released here, inside
        # the span, and not at the function's return
        del slab, outs
    return labels, core, exact_counts, partial_counts


def _laf_dbscan_body(
    data, eps, tau, alpha, predicted_counts, as_fitted,
    *, block_size, backend, device, cluster_device="auto",
    on_device_fault="raise",
):
    n = data.shape[0]
    with _span("laf.fit_index", backend=str(backend)):
        bk = as_fitted(backend, data, block_size=block_size, device=device)
    predicted_core = np.asarray(predicted_counts) >= alpha * tau  # LAF skip rule
    exec_idx = np.nonzero(predicted_core)[0]
    n_exec = len(exec_idx)

    _metrics.counter("laf.runs").inc()
    _metrics.counter("laf.predicted_core").inc(int(n_exec))
    _metrics.counter("laf.skipped").inc(int(n - n_exec))

    native = bool(getattr(bk, "packs_natively", False))
    use_device_cluster = (
        native if cluster_device == "auto" else bool(cluster_device)
    )
    if use_device_cluster and n_exec:
        # ---- device-resident pass 1 + pass 2: one host sync ------------
        try:
            labels, core, exact_counts, partial_counts = _cluster_pass_device(
                bk, eps, tau, exec_idx, n, native, block_size
            )
        except (RuntimeError, OSError) as exc:
            if on_device_fault != "degrade":
                raise
            # fall through to the bit-exact host unpack -> union-find pass
            from ..obs import slo as _slo

            _metrics.counter("stream.degraded.events").inc()
            _metrics.counter("stream.degraded.cluster").inc()
            rate_limited_warn(
                get_logger("cluster"), "degraded", "cluster_degraded",
                error=type(exc).__name__, n=int(n), n_exec=int(n_exec),
            )
            _slo.check_and_alert(_slo.DEGRADED_SLOS)
        else:
            partial_counts[predicted_core] = 0  # 𝓔 keys: predicted-stop only
            return _rescue_and_finish(
                bk, eps, tau, block_size, n, exec_idx, predicted_core,
                labels, core, partial_counts,
            )

    exact_counts = np.zeros(n, dtype=np.int64)
    partial_counts = np.zeros(n, dtype=np.int64)  # |𝓔(q)| for predicted-stop q

    # ---- pass 1 (the only range-query pass): predicted-core queries ----
    packed_blocks: list[tuple[np.ndarray, np.ndarray]] = []
    with _span("laf.pass1", n=n, n_exec=int(n_exec), block_size=block_size):
        for start in range(0, n_exec, block_size):
            rows = exec_idx[start : start + block_size]
            with _span("laf.sweep", block=start // block_size, rows=len(rows)):
                hit = bk.query_hits(rows, eps)  # (b, n)
            exact_counts[rows] = hit.sum(axis=1)
            # Alg.2 superset: every predicted-stop neighbor of an executed
            # query gains one partial neighbor.
            partial_counts += hit.sum(axis=0)
            # pack in the shared LSB-first uint32 word order (pack_bitmap ==
            # index signatures == device kernel bitmaps), so a backend that
            # returns packed adjacency can feed pass 2 without a re-pack
            packed_blocks.append((rows, pack_bitmap(hit)))
    partial_counts[predicted_core] = 0  # 𝓔 keys are predicted-stop points only

    core = np.zeros(n, dtype=bool)
    core[exec_idx] = exact_counts[exec_idx] >= tau

    # ---- pass 2 (no matmul): core-core unions + border ownership -------
    parent = np.arange(n, dtype=np.int64)
    owner = np.full(n, -1, dtype=np.int64)
    with _span("laf.union_find", blocks=len(packed_blocks)):
        for rows, packed in packed_blocks:
            with _span("laf.unpack", rows=len(rows)):
                hit = unpack_bitmap(packed, n)
            row_is_core = core[rows]
            hit_core = hit & core[None, :]
            for bi in np.nonzero(row_is_core)[0]:
                union_star(parent, np.nonzero(hit_core[bi])[0])
            if row_is_core.any():
                sub = hit[row_is_core]
                subrows = rows[row_is_core]
                claimed = sub.any(axis=0)
                todo = claimed & (owner < 0) & ~core
                if todo.any():
                    first = sub[:, todo].argmax(axis=0)
                    owner[todo] = subrows[first]

        labels = compact_labels_from_parent(parent, core)
        borders = np.nonzero(~core & (owner >= 0))[0]
        labels[borders] = labels[owner[borders]]
    return _rescue_and_finish(
        bk, eps, tau, block_size, n, exec_idx, predicted_core,
        labels, core, partial_counts,
    )


def rescue_incidence(bk, carry, exec_idx, rescue_idx, labels, eps):
    """``(emap_size, cluster_ids, point_cols, visits)`` from a rescue's
    folded ``carry``, fetched in one ``device_get``: |𝓔| per rescued
    point and its (pre-merge cluster, rescued column) pairs, the input
    of Algorithm 3, and the carry's ``visits``.  A column holds exactly
    the clusters ``cmin`` and ``cmax``, save the few whose members span
    3 or more (``multi``): those are read again, exactly, with one subset
    query of every executed row, and counted in ``laf.rescue.multi_cols``.
    Pairs may repeat."""
    import jax

    count, cmin, cmax, multi, visits = (
        np.asarray(v) for v in jax.device_get(tuple(carry))
    )
    col = np.arange(len(rescue_idx))
    hit_one, hit_two = cmax >= 0, cmax > cmin
    cluster_ids = [cmin[hit_one], cmax[hit_two]]
    point_cols = [col[hit_one], col[hit_two]]
    flagged = np.flatnonzero(multi)
    _metrics.counter("laf.rescue.multi_cols").inc(len(flagged))
    if len(flagged):
        i, k = np.nonzero(bk.query_hits_subset(exec_idx, rescue_idx[flagged], eps))
        cluster = labels[exec_idx[i]]
        member = cluster >= 0
        cluster_ids.append(cluster[member])
        point_cols.append(flagged[k[member]])
    return (count.astype(np.int64), np.concatenate(cluster_ids).astype(np.int64),
            np.concatenate(point_cols).astype(np.int64), int(visits))


def _rescue_and_finish(
    bk, eps, tau, block_size, n, exec_idx, predicted_core,
    labels, core, partial_counts,
):
    """Post-processing rescue (Algorithm 3) + result assembly, shared by
    the host and device cluster passes.  No Python loop runs per rescued
    point or per hit: 𝓔 is built and merged as arrays."""
    n_exec = len(exec_idx)
    n_pre_clusters = int(labels.max()) + 1 if labels.max() >= 0 else 0

    # ---- post-processing: rescue false negatives (Algorithm 3) ---------
    # 𝓔 is reduced per rescued point as the blocks sweep (``rescue_reduce``:
    # on the device when the backend packs natively) and read once: |𝓔|
    # and the distinct pre-merge clusters of its members are all that
    # Algorithm 3 reads (core/postprocess.py)
    rescue_idx = np.nonzero(~predicted_core & (partial_counts >= tau))[0]
    n_rescue = len(rescue_idx)
    _metrics.counter("laf.rescued").inc(int(n_rescue))
    emap_size = np.zeros(n_rescue, dtype=np.int64)
    cluster_ids = point_cols = np.zeros(0, dtype=np.int64)
    visits = 0
    with _span("laf.postprocess", n_rescue=int(n_rescue)):
        if n_rescue and n_exec:
            carry = None
            for start in range(0, n_exec, block_size):
                rows = exec_idx[start : start + block_size]
                with _span("laf.rescue.sweep", rows=len(rows),
                           cols=n_rescue) as sweep:
                    carry = bk.rescue_reduce(rows, rescue_idx, labels, eps, carry)
                    sweep.sync_on(carry)
            with _span("laf.rescue.emap", cols=n_rescue):
                emap_size, cluster_ids, point_cols, visits = rescue_incidence(
                    bk, carry, exec_idx, rescue_idx, labels, eps
                )
        with _span("laf.rescue.merge", entries=int(np.count_nonzero(emap_size))):
            labels = post_processing_incidence(
                labels, emap_size, cluster_ids, point_cols, rescue_idx, tau
            )
            labels = _compact(labels)
    _metrics.counter("laf.rescue.pairs").inc(int(emap_size.sum()))
    _metrics.counter("laf.rescue.visits").inc(visits)

    extras = {
        "n_predicted_core": int(n_exec),
        "n_skipped": int(n - n_exec),
        "n_rescued": int(len(rescue_idx)),
        "n_pre_merge_clusters": n_pre_clusters,
        "false_negative_core": int(np.sum(~predicted_core & (partial_counts >= tau))),
    }
    n_clusters = int(labels.max()) + 1 if labels.max() >= 0 else 0
    return DBSCANResult(labels, core, n_clusters, n_exec, extras)
