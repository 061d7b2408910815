"""The rescue's running reduction (``rescue_reduce``): the device fold of
the packed subset hits equals the host fold of the boolean hits, block
by block, and the incidence read from it is the exact (pre-merge
cluster, rescued point) set, with ``laf.rescue.multi_cols`` counting
the columns whose members span 3 or more clusters."""

import numpy as np
import pytest

from repro import obs
from repro.core.laf_dbscan import rescue_incidence
from repro.core.range_query import pack_bitmap
from repro.index import RandomProjectionBackend
from repro.index.base import RescueCarry, fold_subset_hits
from repro.index.random_projection import _rescue_fold
from repro.obs import metrics

EPS = 0.1
CFG = dict(n_bits=64, margin=3.0, seed=3, chunk=32, q_tile=32, db_tile=64)

# five separated groups, one rescued column each; every executed row sits
# in one group, so it hits that group's column alone.  Row labels by
# group (-1 noise), in sweep order over blocks of 5, the last one ragged:
#   a: 0 and 5 in block 1, then 3 (the middle, a block extreme) in block 2
#   b: exactly two clusters, 1 and 2
#   c: noise rows only
#   d: 6, 7 and 8 inside one block
#   e: no executed row
BLOCKS = [
    [("a", 0), ("a", 5), ("b", 1), ("c", -1), ("a", -1)],
    [("a", 3), ("b", 2), ("d", 6), ("d", 7), ("d", 8)],
    [("c", -1), ("b", 2), ("a", 5)],
]
GROUPS = "abcde"


@pytest.fixture(scope="module")
def grouped():
    rng = np.random.default_rng(7)
    d = 32
    centers = np.linalg.qr(rng.normal(size=(d, d)))[0][: len(GROUPS)]
    rows_of = [g for block in BLOCKS for g, _ in block]
    members = [GROUPS.index(g) for g in GROUPS] + [GROUPS.index(g) for g in rows_of]
    pts = centers[members] + 0.01 * rng.normal(size=(len(members), d))
    data = (pts / np.linalg.norm(pts, axis=1, keepdims=True)).astype(np.float32)
    cols = np.arange(len(GROUPS))
    labels = np.full(len(data), -1, dtype=np.int64)
    labels[len(GROUPS):] = [lab for block in BLOCKS for _, lab in block]
    blocks, start = [], len(GROUPS)
    for block in BLOCKS:
        blocks.append(np.arange(start, start + len(block)))
        start += len(block)
    return data, cols, labels, blocks


def _host(carry):
    return RescueCarry(*(np.asarray(v) for v in carry))


def test_device_carry_equals_host_carry_block_by_block(grouped):
    data, cols, labels, blocks = grouped
    dev = RandomProjectionBackend(device=True, interpret=True, **CFG).fit(data)
    host = RandomProjectionBackend(device=False, **CFG).fit(data)
    assert dev.packs_natively and not host.packs_natively
    cd = ch = None
    for rows in blocks:
        cd = dev.rescue_reduce(rows, cols, labels, EPS, cd)
        ch = host.rescue_reduce(rows, cols, labels, EPS, ch)
        a, b = _host(cd), _host(ch)
        for f in RescueCarry._fields:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)
    got = _host(cd)
    np.testing.assert_array_equal(got.count, [5, 3, 2, 3, 0])
    np.testing.assert_array_equal(got.cmin, [0, 1, np.iinfo(np.int32).max, 6,
                                             np.iinfo(np.int32).max])
    np.testing.assert_array_equal(got.cmax, [5, 2, -1, 8, -1])
    np.testing.assert_array_equal(got.multi, [True, False, False, True, False])
    assert got.visits == 3 + 3 + 3


@pytest.mark.parametrize("device", [True, False])
def test_incidence_keys_equal_the_host_unique_keys(grouped, device):
    data, cols, labels, blocks = grouped
    bk = RandomProjectionBackend(device=device, interpret=True, **CFG).fit(data)
    carry = None
    for rows in blocks:
        carry = bk.rescue_reduce(rows, cols, labels, EPS, carry)
    exec_idx = np.concatenate(blocks)
    was = obs.metrics_enabled()
    obs.enable(metrics_on=True)
    metrics.reset()
    try:
        emap_size, cluster_ids, point_cols, visits = rescue_incidence(
            bk, carry, exec_idx, cols, labels, EPS)
        multi_cols = metrics.snapshot()["laf.rescue.multi_cols"]
    finally:
        obs.enable(metrics_on=was)
    hit = RandomProjectionBackend(device=False, **CFG).fit(data).query_hits_subset(
        exec_idx, cols, EPS)
    i, j = np.nonzero(hit)
    cluster = labels[exec_idx[i]]
    stride = len(cols)
    want = np.unique(cluster[cluster >= 0] * stride + j[cluster >= 0])
    np.testing.assert_array_equal(np.unique(cluster_ids * stride + point_cols), want)
    np.testing.assert_array_equal(emap_size, hit.sum(axis=0))
    assert visits == sum(int(hit[k : k + len(b)].any(axis=0).sum())
                         for k, b in zip(np.cumsum([0] + [len(b) for b in blocks]),
                                         blocks))
    clusters_per_col = [len(set(cluster[(j == c) & (cluster >= 0)])) for c in range(stride)]
    assert clusters_per_col == [3, 2, 0, 3, 0]
    assert multi_cols == sum(k >= 3 for k in clusters_per_col) == 2


def test_fold_masks_pad_rows_and_equals_the_host_fold():
    """The jitted fold over random packed words: query rows past the
    block's length and the tail columns of the last word carry bits and
    are masked, two row chunks of 256 are merged, and two blocks chained
    give the host fold of the live rows and columns."""
    rng = np.random.default_rng(11)
    n_cols, n = 90, 900
    labels = rng.integers(-1, 5, size=n)
    host = dev = None
    for nq, n_rows in ((512, 300), (256, 256)):
        # sparse live hits, so columns see 0 to about 8 clusters; the pad
        # rows and tail columns are dense, so a leak would show
        hit = rng.random((nq, 96)) < np.where(np.arange(nq) < n_rows, 0.01, 0.5)[:, None]
        hit[:, n_cols:] = rng.random((nq, 96 - n_cols)) < 0.5
        rows = rng.integers(0, n, size=nq).astype(np.int32)
        if dev is None:
            dev = RescueCarry.empty(n_cols)
        dev = _rescue_fold(dev, pack_bitmap(hit), rows, np.int32(n_rows),
                           labels.astype(np.int32), n_cols=n_cols)
        host = fold_subset_hits(host, hit[:n_rows, :n_cols], labels[rows[:n_rows]])
    got = RescueCarry(*(np.asarray(v) for v in dev))
    for f in RescueCarry._fields:
        np.testing.assert_array_equal(getattr(got, f), getattr(host, f), err_msg=f)
    assert got.multi.any() and not got.multi.all()


def test_laf_rescue_on_the_device_rereads_three_cluster_points_exactly():
    """End to end: with 60% of the points predicted to stop, one rescued
    point's partial neighbours span 3 pre-merge clusters; the device
    rescue flags it, re-reads it, and gives the host rescue's labels and
    counters."""
    from repro.core.laf_dbscan import laf_dbscan
    from repro.core.range_query import range_counts
    from repro.data.synthetic import make_angular_clusters

    data, _ = make_angular_clusters(613, 32, 8, kappa=120, noise_frac=0.3, seed=2)
    eps, tau = 0.55, 3
    counts = np.asarray(range_counts(data, data, eps)).astype(np.float64)
    pred = np.where(np.random.default_rng(0).random(len(data)) < 0.6, 0.0, counts)
    cfg = dict(CFG, chunk=64)
    was = obs.metrics_enabled()
    obs.enable(metrics_on=True)
    out = {}
    try:
        for device in (True, False):
            metrics.reset()
            bk = RandomProjectionBackend(device=device, interpret=True, **cfg)
            res = laf_dbscan(data, eps, tau, 1.0, pred, backend=bk, block_size=128)
            out[device] = (res, {k: v for k, v in metrics.snapshot().items()
                                 if k.startswith("laf.rescue")})
    finally:
        obs.enable(metrics_on=was)
    (dev, dev_c), (host, host_c) = out[True], out[False]
    np.testing.assert_array_equal(dev.labels, host.labels)
    assert dev_c == host_c
    assert dev_c["laf.rescue.multi_cols"] >= 1
