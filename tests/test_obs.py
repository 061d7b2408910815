"""repro.obs: span nesting + Chrome-trace export roundtrip, histogram
quantile accuracy vs numpy, recompile accounting (the sweep engine's
once-per-capacity-doubling contract, the serving path's O(log n)
power-of-two bucket compiles), and device/host band-occupancy parity.
"""

import json
import math

import numpy as np
import pytest

from repro import obs
from repro.data.synthetic import make_angular_clusters
from repro.core.laf_dbscan import laf_dbscan
from repro.index import RandomProjectionBackend
from repro.index.random_projection import record_occupancy
from repro.obs import metrics
from repro.stream import StreamingLAF

EPS = 0.55


@pytest.fixture(autouse=True)
def obs_sandbox():
    """Clean, enabled obs state per test; the ambient switches (tier-1
    may run under REPRO_OBS=1) are restored afterwards."""
    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    obs.enable(trace=True, metrics_on=True)
    obs.clear_trace()
    metrics.reset()
    yield
    obs.clear_trace()
    metrics.reset()
    if was_trace or was_metrics:
        obs.enable(trace=was_trace, metrics_on=was_metrics)
    else:
        obs.disable()


# ---------------------------------------------------------------------------
# spans: nesting, export roundtrip, the disabled fast path
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_export_roundtrip(tmp_path):
    with obs.span("outer", a=1):
        with obs.span("inner.one"):
            pass
        with obs.span("inner.two", k="v"):
            pass
    recs = obs.spans()
    outer = next(r for r in recs if r.name == "outer")
    inners = [r for r in recs if r.name.startswith("inner")]
    assert outer.parent_id == 0
    assert len(inners) == 2
    assert all(r.parent_id == outer.span_id for r in inners)
    assert outer.dur >= max(r.dur for r in inners)

    p = tmp_path / "trace.json"
    doc = obs.export_chrome_trace(str(p))
    loaded = json.loads(p.read_text())  # the file IS valid JSON
    assert loaded == json.loads(json.dumps(doc, default=float))
    evs = loaded["traceEvents"]
    assert {e["name"] for e in evs} == {"outer", "inner.one", "inner.two"}
    for e in evs:  # Chrome trace_event "complete" records
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] > 0
        assert {"name", "cat", "pid", "tid", "args"} <= set(e)
    by_name = {e["name"]: e for e in evs}
    # parent linkage and attributes survive the export through args
    assert (by_name["inner.one"]["args"]["parent_id"]
            == by_name["outer"]["args"]["span_id"])
    assert by_name["outer"]["args"]["a"] == 1
    assert by_name["inner.two"]["args"]["k"] == "v"


def test_disabled_span_is_shared_noop():
    obs.disable()
    s1, s2 = obs.span("x"), obs.span("y")
    assert s1 is s2  # the shared null object: no per-call allocation
    with s1:
        pass
    obs.enable(trace=True, metrics_on=True)
    assert obs.spans("x") == []


def test_force_span_measures_without_recording():
    obs.disable()
    sp = obs.span("bench.t", force=True)
    with sp:
        out = sum(range(10_000))
        sp.sync_on(out)  # numpy/python leaves pass through block_until_ready
    assert sp.dur > 0
    obs.enable(trace=True, metrics_on=True)
    assert obs.spans("bench.t") == []  # measured, never buffered


def test_coverage_is_union_of_child_intervals():
    root = obs.SpanRecord("r", t0=0.0, dur=10.0, span_id=1)
    kids = [
        obs.SpanRecord("a", t0=0.0, dur=4.0, span_id=2, parent_id=1),
        obs.SpanRecord("b", t0=3.0, dur=4.0, span_id=3, parent_id=1),  # overlap
        obs.SpanRecord("c", t0=9.0, dur=5.0, span_id=4, parent_id=1),  # clipped
    ]
    # union [0,7) + [9,10) clipped to the root = 8 of 10 seconds
    assert obs.coverage(root, [root] + kids) == pytest.approx(0.8)


# ---------------------------------------------------------------------------
# histogram: log-bucket quantiles vs exact numpy percentiles
# ---------------------------------------------------------------------------


def test_histogram_quantiles_match_numpy_within_bucket_width():
    rng = np.random.default_rng(0)
    # latency-like: log-normal spanning ~3 decades around a millisecond
    samples = rng.lognormal(mean=-6.5, sigma=1.2, size=5000)
    h = metrics.histogram("test.latency")
    for v in samples:
        h.observe(float(v))
    for q in (0.50, 0.95, 0.99):
        exact = float(np.quantile(samples, q))
        est = h.quantile(q)
        # the default layout is 20 buckets/decade: adjacent bounds differ
        # by 10^(1/20) ~ 1.122, the documented quantile resolution
        assert abs(est - exact) / exact < 0.13, (q, est, exact)
    s = h.summary()
    assert s["count"] == len(samples)
    assert s["min"] == pytest.approx(samples.min())
    assert s["max"] == pytest.approx(samples.max())
    assert s["sum"] == pytest.approx(samples.sum())
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_metrics_disabled_records_nothing():
    obs.disable()
    metrics.counter("test.c").inc(5)
    metrics.gauge("test.g").set(3.0)
    metrics.histogram("test.h").observe(1.0)
    assert metrics.counter("test.c").value == 0
    assert metrics.histogram("test.h").count == 0
    snap = metrics.snapshot("test.")
    assert snap["test.c"] == 0
    assert "test.g" in json.loads(metrics.to_json()) or True  # serializable


# ---------------------------------------------------------------------------
# recompile accounting: the sweep engine across partial_fit appends
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def obs_data():
    # 613: not a multiple of the chunk, the kernel tiles, or 32 (the
    # same shape discipline as test_sweep — every pad layer exercised)
    data, _ = make_angular_clusters(613, 32, 8, kappa=120, noise_frac=0.3, seed=2)
    return data


CFG = dict(n_bits=64, margin=3.0, seed=3, chunk=64, q_tile=32, db_tile=64)


def test_sweep_recompiles_once_per_capacity_doubling(obs_data):
    """Appends that fit in capacity re-launch cached executables; only a
    capacity doubling (new padded operand shape) compiles fresh ones."""
    bk = RandomProjectionBackend(device=True, interpret=True, sweep=True, **CFG)
    bk.fit(obs_data[:128])
    rows = np.arange(64)
    bk.query_counts(rows, EPS)  # first sweep pays the initial compile
    base_rc = metrics.counter("sweep.recompiles").value
    base_db = metrics.counter("index.capacity_doublings").value
    for start in range(128, 613, 97):
        bk.partial_fit(obs_data[start : start + 97])
        bk.query_counts(rows, EPS)  # same query shape: capacity is the
        # only thing that can change the jit signature
    doublings = metrics.counter("index.capacity_doublings").value - base_db
    recompiles = metrics.counter("sweep.recompiles").value - base_rc
    assert doublings >= 2  # 128 -> 613 must double at least twice
    assert recompiles == doublings


# ---------------------------------------------------------------------------
# recompile accounting: serving buckets are O(log n), reused across calls
# ---------------------------------------------------------------------------


def test_serve_assign_bucket_compiles_log_bounded(obs_data):
    bk = RandomProjectionBackend(device=True, interpret=True, sweep=True, **CFG)
    stream = StreamingLAF(0.35, 5, backend=bk, block_size=256)
    stream.partial_fit(obs_data)
    idx = stream.snapshot()

    rng = np.random.default_rng(7)
    member = np.nonzero(stream.labels() >= 0)[0]
    queries = obs_data[rng.choice(member, size=96)] + 0.02 * rng.standard_normal(
        (96, obs_data.shape[1])
    ).astype(np.float32)

    metrics.reset()
    for size in (1, 3, 17, 41, 96):  # ragged batches: many union sizes
        for s in range(0, 96, size):
            idx.assign(queries[s : s + size])
    compiles = metrics.counter("serve.bucket_compiles").value
    launches = metrics.counter("serve.verify_launches").value
    assert launches > 0 and compiles > 0
    # buckets are powers of two in [db_tile, 2^ceil(log2 n)], chunks
    # powers of two in [q_tile, chunk]: O(log n) distinct shapes total
    max_buckets = int(math.log2((1 << math.ceil(math.log2(len(obs_data)))) // CFG["db_tile"])) + 1
    max_chunks = int(math.log2(CFG["chunk"] // CFG["q_tile"])) + 1
    assert compiles <= max_buckets * max_chunks
    assert compiles < launches  # shapes are reused, not one per launch

    # a repeat of the same traffic compiles nothing new
    before = compiles
    for s in range(0, 96, 17):
        idx.assign(queries[s : s + 17])
    assert metrics.counter("serve.bucket_compiles").value == before
    assert metrics.counter("serve.assign.calls").value > 0
    assert metrics.histogram("serve.assign.latency_s").count > 0


# ---------------------------------------------------------------------------
# band occupancy: device kernel counters == host table on ragged n
# ---------------------------------------------------------------------------


def test_occupancy_device_matches_host_on_ragged_n(obs_data):
    """613 rows: the kernel's per-tile [accept, band, reject] counters
    run on the padded grid; after the pad corrections the device
    measurement must price exactly the same real pairs as one host
    Hamming sweep."""
    host = RandomProjectionBackend(device=False, **CFG).fit(obs_data)
    dev = RandomProjectionBackend(device=True, interpret=True, **CFG).fit(obs_data)
    rows = np.arange(0, len(obs_data), 7)

    metrics.reset()
    row_h = record_occupancy(host, EPS, rows)
    host_counts = {
        k: metrics.counter(f"index.band.{k}").value
        for k in ("accept", "band", "reject")
    }
    metrics.reset()
    row_d = record_occupancy(dev, EPS, rows)
    dev_counts = {
        k: metrics.counter(f"index.band.{k}").value
        for k in ("accept", "band", "reject")
    }

    assert sum(host_counts.values()) == len(rows) * len(obs_data)
    assert dev_counts == host_counts
    assert row_d["accept_frac"] == pytest.approx(row_h["accept_frac"])
    assert row_d["band_frac"] == pytest.approx(row_h["band_frac"])
    assert row_d["t_lo"] == row_h["t_lo"] and row_d["t_hi"] == row_h["t_hi"]


def test_band_lazily_records_occupancy_once_per_eps(obs_data):
    bk = RandomProjectionBackend(device=False, **CFG).fit(obs_data)
    metrics.reset()
    bk.band(EPS)
    accepted = metrics.counter("index.band.accept").value
    total = sum(
        metrics.counter(f"index.band.{k}").value
        for k in ("accept", "band", "reject")
    )
    assert total > 0  # one sampled measurement was taken
    bk.band(EPS)  # memoized per (backend, eps): no second measurement
    assert metrics.counter("index.band.accept").value == accepted
    bk.band(0.4)  # a new eps is a new measurement
    assert (
        sum(
            metrics.counter(f"index.band.{k}").value
            for k in ("accept", "band", "reject")
        )
        > total
    )


# ---------------------------------------------------------------------------
# the rescue's three parts, the database uploads and the host assembly
# ---------------------------------------------------------------------------

RESCUE_TAU = 5


def _run_rescue_case(data, trace: bool) -> dict:
    """One device-path LAF run (interpreted kernels) whose rescue is not
    empty: 40% of the points are predicted to stop whatever their count.
    Records the spans, the counters, the arrays that Algorithm 3 received
    (|𝓔| per rescued point and the (cluster, point) incidence of its
    members), its answer, and every ``jax.block_until_ready`` made from
    inside the rescue."""
    import importlib
    import sys

    import jax

    from repro.core.range_query import range_counts

    laf_mod = importlib.import_module("repro.core.laf_dbscan")

    counts = np.asarray(range_counts(data, data, EPS)).astype(np.float64)
    rng = np.random.default_rng(0)
    pred = np.where(rng.random(len(data)) < 0.4, 0.0, counts)
    seen, blocks = {}, []
    real_pp, real_block = laf_mod.post_processing_incidence, jax.block_until_ready

    def recording_pp(labels, emap_size, cluster_ids, point_cols, rescue_idx, tau):
        seen.update(labels=labels.copy(), emap_size=emap_size.copy(),
                    cluster_ids=cluster_ids.copy(), point_cols=point_cols.copy(),
                    rescue_idx=rescue_idx.copy())
        seen["merged_labels"] = real_pp(labels, emap_size, cluster_ids, point_cols,
                                        rescue_idx, tau)
        return seen["merged_labels"]

    def recording_block(x):
        f = sys._getframe(1)
        while f is not None:
            if f.f_code.co_name == "_rescue_and_finish":
                blocks.append(f.f_code.co_name)
                break
            f = f.f_back
        return real_block(x)

    was = (obs.trace_enabled(), obs.metrics_enabled())
    obs.enable(trace=trace, metrics_on=True)
    obs.clear_trace()
    metrics.reset()
    laf_mod.post_processing_incidence, jax.block_until_ready = recording_pp, recording_block
    try:
        bk = RandomProjectionBackend(device=True, interpret=True, **CFG)
        res = laf_dbscan(data, EPS, RESCUE_TAU, 1.0, pred, backend=bk, block_size=128)
        return {"res": res, "bk": bk, "spans": obs.spans(), "blocks": blocks,
                "counters": metrics.snapshot(), "pred": pred, **seen}
    finally:
        laf_mod.post_processing_incidence, jax.block_until_ready = real_pp, real_block
        obs.enable(trace=was[0], metrics_on=was[1])


@pytest.fixture(scope="module")
def rescue_case(obs_data):
    return {"traced": _run_rescue_case(obs_data, True),
            "untraced": _run_rescue_case(obs_data, False)}


def canonical(labels) -> list:
    """Cluster ids renumbered in the order of their lowest-index point,
    noise -1: two labelings hold one partition iff these are equal."""
    labels = np.asarray(labels)
    out = np.full(len(labels), -1)
    pos = labels >= 0
    _, first, inv = np.unique(labels[pos], return_index=True, return_inverse=True)
    out[pos] = np.argsort(np.argsort(first))[inv]
    return out.tolist()


def _children(recs, parent):
    return [r for r in recs if r.parent_id == parent.span_id]


def test_rescue_spans_nest_under_postprocess_and_account_for_it(rescue_case):
    case = rescue_case["traced"]
    recs = case["spans"]
    assert case["res"].extras["n_rescued"] > 0
    (post,) = [r for r in recs if r.name == "laf.postprocess"]
    kids = _children(recs, post)
    names = [r.name for r in kids]
    blocks = -(-int(case["res"].extras["n_predicted_core"]) // 128)
    assert names.count("laf.rescue.sweep") == blocks
    assert names.count("laf.rescue.emap") == names.count("laf.rescue.merge") == 1
    assert set(names) == {"laf.rescue.sweep", "laf.rescue.emap", "laf.rescue.merge"}
    assert sum(r.dur for r in kids) >= 0.9 * post.dur
    for r in kids:
        if r.name == "laf.rescue.sweep":
            assert r.attrs["cols"] == case["res"].extras["n_rescued"]
            assert r.dispatch_s is not None  # synced on its hits


def test_rescue_counters_match_the_partial_neighbor_map(rescue_case):
    """The arrays the rescue hands Algorithm 3 are the oracle's map 𝓔,
    rebuilt with Algorithm 2 from the same backend's hits, reduced; the
    counters count that map; the merge gives the oracle's partition."""
    from repro.core.postprocess import (PartialNeighborMap, post_processing,
                                        update_partial_neighbors)

    case = rescue_case["traced"]
    labels, c, rescue_idx = case["labels"], case["counters"], case["rescue_idx"]
    exec_idx = np.nonzero(case["pred"] >= RESCUE_TAU)[0]
    hit = case["bk"].query_hits_subset(exec_idx, rescue_idx, EPS)
    emap = PartialNeighborMap()
    for ri in np.nonzero(hit.any(axis=0))[0]:
        emap.register(rescue_idx[ri])
    for k, p in enumerate(exec_idx):
        update_partial_neighbors(p, rescue_idx[hit[k]], emap)
    np.testing.assert_array_equal(
        case["emap_size"], [len(emap[r]) if r in emap else 0 for r in rescue_idx])
    assert c["laf.rescue.pairs"] == sum(len(s) for _, s in emap.items()) > 0
    # one count per (block, rescued point hit): at least one per entry
    assert c["laf.rescue.visits"] >= len(emap) > 0
    merged = sum(1 for _, s in emap.items() if len(s) >= RESCUE_TAU
                 and (labels[np.fromiter(s, np.int64)] >= 0).any())
    assert c["laf.rescue.merged"] == merged > 0
    col = {int(r): j for j, r in enumerate(rescue_idx)}
    links = {(int(labels[q]), col[p]) for p, s in emap.items() for q in s
             if labels[q] >= 0}
    assert set(zip(case["cluster_ids"].tolist(), case["point_cols"].tolist())) == links
    assert 0 < c["laf.rescue.links"] == len(links) < c["laf.rescue.pairs"]
    oracle = post_processing(labels, emap, RESCUE_TAU)
    assert canonical(case["merged_labels"]) == canonical(oracle)
    # the same work, counted the same, with tracing off
    off = rescue_case["untraced"]["counters"]
    for k in ("laf.rescue.pairs", "laf.rescue.visits", "laf.rescue.merged",
              "laf.rescue.links", "index.upload.bytes"):
        assert off[k] == c[k]


def test_rescue_rereads_exactly_the_points_in_three_clusters(rescue_case):
    """``laf.rescue.multi_cols`` counts the rescued points whose partial
    neighbours lie in 3 or more pre-merge clusters: the columns the
    device carry cannot name by its least and greatest label alone."""
    case = rescue_case["traced"]
    labels, rescue_idx = case["labels"], case["rescue_idx"]
    exec_idx = np.nonzero(case["pred"] >= RESCUE_TAU)[0]
    hit = case["bk"].query_hits_subset(exec_idx, rescue_idx, EPS)
    rows_lab = labels[exec_idx]
    spans = [len(set(rows_lab[hit[:, j] & (rows_lab >= 0)].tolist()))
             for j in range(len(rescue_idx))]
    want = sum(k >= 3 for k in spans)
    for run in ("traced", "untraced"):
        assert rescue_case[run]["counters"].get("laf.rescue.multi_cols", 0) == want


def test_database_uploads_are_spanned_and_counted(rescue_case):
    case = rescue_case["traced"]
    recs, bk = case["spans"], case["bk"]
    ups = [r for r in recs if r.name == "laf.upload"]
    by_id = {r.span_id: r for r in recs}
    n, d = bk._data.shape
    words = bk._sigs.shape[1]
    padded = -(-n // bk.db_tile) * bk.db_tile
    want = {"corpus": (n, 4 * n * d), "data": (n, 4 * n * d),
            "sigs": (n, 4 * n * words), "sweep_data": (padded, 4 * padded * d),
            "sweep_sigs": (padded, 4 * padded * words)}
    assert {r.attrs["what"]: (r.attrs["rows"], r.attrs["bytes"]) for r in ups} == want
    assert len(ups) == len(want)
    assert case["counters"]["index.upload.bytes"] == sum(b for _, b in want.values())
    for r in ups:
        parent = by_id[r.parent_id].name
        assert parent == ("laf.fit_index" if r.attrs["what"] == "corpus" else "laf.sweep")
        assert r.dispatch_s is not None  # synced on the device array


def test_host_assembly_is_spanned_inside_the_cluster_span(rescue_case):
    recs = rescue_case["traced"]["spans"]
    (cluster,) = [r for r in recs if r.name == "laf.cluster"]
    names = [r.name for r in _children(recs, cluster)]
    assert names == ["laf.fit_index", "laf.pass1", "laf.label_prop", "laf.assemble",
                     "laf.postprocess"]
    assert obs.coverage(cluster, recs) >= 0.95


def test_untraced_rescue_records_no_span_and_never_blocks(rescue_case):
    case = rescue_case["untraced"]
    assert case["res"].extras["n_rescued"] > 0
    assert case["spans"] == []
    assert case["blocks"] == []
    # the traced run did sync from inside the rescue: the probe sees it
    assert rescue_case["traced"]["blocks"]
    np.testing.assert_array_equal(case["res"].labels, rescue_case["traced"]["res"].labels)
