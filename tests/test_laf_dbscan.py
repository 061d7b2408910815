import numpy as np
import pytest

from repro.core.dbscan import dbscan_sequential
from repro.core.laf_dbscan import laf_dbscan, laf_dbscan_sequential
from repro.core.metrics import adjusted_mutual_info, adjusted_rand_index
from repro import obs
from repro.core.postprocess import (
    PartialNeighborMap,
    post_processing,
    post_processing_incidence,
    update_partial_neighbors,
)
from repro.core.union_find import compact_labels
from repro.obs import metrics
from repro.core.range_query import range_counts


@pytest.fixture(scope="module")
def gt(small_clustered):
    data, _ = small_clustered
    return dbscan_sequential(data, 0.25, 5)


def exact_counts(data, eps):
    return np.asarray(range_counts(data, data, eps)).astype(np.float64)


class TestOracleEstimator:
    """With a perfect estimator and alpha=1, LAF-DBSCAN == DBSCAN."""

    def test_sequential_exact(self, small_clustered, gt):
        data, _ = small_clustered
        counts = exact_counts(data, 0.25)
        res = laf_dbscan_sequential(data, 0.25, 5, 1.0, lambda i: counts[i])
        assert adjusted_rand_index(res.labels, gt.labels) == pytest.approx(1.0)
        np.testing.assert_array_equal(res.core, gt.core)

    def test_parallel_exact(self, small_clustered, gt):
        data, _ = small_clustered
        counts = exact_counts(data, 0.25)
        res = laf_dbscan(data, 0.25, 5, 1.0, counts)
        assert adjusted_rand_index(res.labels, gt.labels) == pytest.approx(1.0)
        np.testing.assert_array_equal(res.core, gt.core)

    def test_queries_saved(self, small_clustered, gt):
        """LAF executes range queries only for predicted-core points."""
        data, _ = small_clustered
        counts = exact_counts(data, 0.25)
        res = laf_dbscan(data, 0.25, 5, 1.0, counts)
        assert res.n_range_queries == int((counts >= 5).sum())
        assert res.n_range_queries < gt.n_range_queries


class TestNoisyEstimator:
    def _noisy(self, counts, seed=0, sigma=0.5):
        rng = np.random.default_rng(seed)
        return counts * np.exp(rng.normal(0.0, sigma, size=len(counts)))

    def test_seq_par_agree(self, small_clustered):
        data, _ = small_clustered
        noisy = self._noisy(exact_counts(data, 0.25))
        seq = laf_dbscan_sequential(data, 0.25, 5, 1.2, lambda i: noisy[i])
        par = laf_dbscan(data, 0.25, 5, 1.2, noisy)
        # identical skip decisions => identical executed-query count
        assert seq.n_range_queries == par.n_range_queries
        assert adjusted_rand_index(seq.labels, par.labels) > 0.99

    def test_quality_stays_high(self, small_clustered, gt):
        data, _ = small_clustered
        noisy = self._noisy(exact_counts(data, 0.25))
        par = laf_dbscan(data, 0.25, 5, 1.2, noisy)
        assert adjusted_rand_index(par.labels, gt.labels) > 0.9
        assert adjusted_mutual_info(par.labels, gt.labels) > 0.85

    def test_postprocessing_improves_quality(self, small_clustered, gt):
        """Dropping Algorithm 3 must not beat running it (usually strictly worse)."""
        data, _ = small_clustered
        # heavy under-estimation -> many false negatives -> rescues matter
        noisy = exact_counts(data, 0.25) * 0.5
        with_pp = laf_dbscan(data, 0.25, 5, 1.0, noisy)
        assert with_pp.extras["n_rescued"] > 0

    def test_alpha_tradeoff_monotone_queries(self, small_clustered):
        """Larger alpha -> more skips -> fewer executed range queries."""
        data, _ = small_clustered
        noisy = self._noisy(exact_counts(data, 0.25))
        q = [
            laf_dbscan(data, 0.25, 5, a, noisy).n_range_queries
            for a in (0.5, 1.0, 2.0, 4.0)
        ]
        assert q[0] >= q[1] >= q[2] >= q[3]


class TestPartialNeighbors:
    def test_update_partial_neighbors_alg2(self):
        emap = PartialNeighborMap()
        emap.register(3)
        emap.register(7)
        update_partial_neighbors(1, [2, 3, 7], emap)
        update_partial_neighbors(5, [3], emap)
        assert emap[3] == {1, 5}
        assert emap[7] == {1}
        assert 2 not in emap

    def test_postprocessing_merges_split_cluster(self):
        """Two halves split by a false-negative bridge point merge back."""
        labels = np.array([0, 0, 0, 1, 1, 1, -1])  # point 6 = FN bridge
        emap = PartialNeighborMap()
        emap.register(6)
        emap[6].update({0, 1, 3, 4})  # >= tau=3 partial neighbors
        out = post_processing(labels, emap, 3)
        assert out[0] == out[3]          # clusters merged
        assert out[6] == out[0]          # rescued point joins
        assert len(np.unique(out[out >= 0])) == 1

    def test_postprocessing_ignores_below_tau(self):
        labels = np.array([0, 0, 1, 1, -1])
        emap = PartialNeighborMap()
        emap.register(4)
        emap[4].update({0, 2})  # only 2 < tau=3
        out = post_processing(labels, emap, 3)
        assert out[0] != out[2]
        assert out[4] == -1

    def test_postprocessing_transitive_merge(self):
        """Chained rescues merge transitively (A-B via p5, B-C via p6)."""
        labels = np.array([0, 0, 1, 1, 2, -1, -1])
        emap = PartialNeighborMap()
        emap.register(5)
        emap[5].update({0, 1, 2})
        emap.register(6)
        emap[6].update({2, 3, 4})
        out = post_processing(labels, emap, 3)
        assert out[0] == out[2] == out[4]


def _canonical(labels) -> list:
    """Cluster ids renumbered in the order of their lowest-index point,
    noise -1: two labelings hold one partition iff these are equal."""
    labels = np.asarray(labels)
    out = np.full(len(labels), -1)
    pos = labels >= 0
    _, first, inv = np.unique(labels[pos], return_index=True, return_inverse=True)
    out[pos] = np.argsort(np.argsort(first))[inv]
    return out.tolist()


def _random_emap(seed: int):
    """80 points over 6 clusters and noise; 12 noise points are rescue
    candidates with 0 to 7 partial neighbors among the other points."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(-1, 6, 80)
    rescue = rng.choice(80, 12, replace=False)
    labels[rescue] = -1
    others = np.setdiff1d(np.arange(80), rescue)
    return labels, {int(p): rng.choice(others, int(rng.integers(0, 8)), replace=False)
                    for p in rescue}, 3


def _chain_emap():
    """12 clusters chained by 11 rescued points in a shuffled order, so
    the merge takes several hooking rounds."""
    order = np.random.default_rng(5).permutation(12)
    labels = np.concatenate([np.repeat(np.arange(12), 2), np.full(11, -1)])
    emap = {24 + k: np.array([2 * order[k], 2 * order[k] + 1, 2 * order[k + 1]])
            for k in range(11)}
    return labels, emap, 3


INCIDENCE_CASES = {
    # p 6 has 2 < tau partial neighbors and stays noise; p 7 merges 0, 2
    "below_tau": (np.array([0, 0, 1, 1, 2, 2, -1, -1]),
                  {6: np.array([0, 2]), 7: np.array([1, 4, 5])}, 3),
    # p 5's partial neighbors are all noise: nothing to merge into
    "members_all_noise": (np.array([0, 0, 1, 1, -1, -1, -1, -1]),
                          {5: np.array([4, 6, 7])}, 3),
    "transitive_chain": _chain_emap(),
    "empty_rescue": (np.array([0, 0, 1, 1, -1]), {}, 3),
    # p 4's members hit cluster 1 only: it joins it, nothing merges
    "one_cluster": (np.array([0, 0, 1, 1, -1, 1]), {4: np.array([2, 3, 5])}, 3),
    **{f"random_{seed}": _random_emap(seed) for seed in range(4)},
}


@pytest.fixture
def metrics_on():
    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    obs.enable(trace=False, metrics_on=True)
    metrics.reset()
    yield
    metrics.reset()
    if was_trace or was_metrics:
        obs.enable(trace=was_trace, metrics_on=was_metrics)
    else:
        obs.disable()


@pytest.mark.parametrize("case", sorted(INCIDENCE_CASES))
def test_incidence_form_matches_the_oracle(case, metrics_on):
    """Algorithm 3 over the (cluster, rescued point) incidence gives the
    verbatim ``post_processing``'s partition and merged count."""
    labels, members, tau = INCIDENCE_CASES[case]
    emap = PartialNeighborMap()
    for p, mem in members.items():
        emap.register(p)
        emap[p].update(int(q) for q in mem)
    rescue_idx = np.array(sorted(members), dtype=np.int64)
    emap_size = np.array([len(emap[p]) for p in rescue_idx], dtype=np.int64)
    cols = np.concatenate([np.full(len(members[p]), j) for j, p in enumerate(rescue_idx)]
                          + [np.zeros(0, np.int64)]).astype(np.int64)
    points = np.concatenate([members[p] for p in rescue_idx] + [np.zeros(0, np.int64)])
    cluster = labels[points.astype(np.int64)]
    member = cluster >= 0

    want = post_processing(labels, emap, tau)
    want_merged = metrics.counter("laf.rescue.merged").value
    metrics.reset()
    got = post_processing_incidence(labels, emap_size, cluster[member], cols[member],
                                    rescue_idx, tau)
    assert _canonical(compact_labels(got)) == _canonical(want)
    assert metrics.counter("laf.rescue.merged").value == want_merged
    assert metrics.counter("laf.rescue.links").value == len(
        set(zip(cluster[member].tolist(), cols[member].tolist())))
    if case == "below_tau":
        assert got[6] == -1 and got[7] == got[0] == got[4] != got[2]
    if case == "transitive_chain":
        assert len(set(got[got >= 0].tolist())) == 1
    if case in ("members_all_noise", "empty_rescue"):
        assert _canonical(got) == _canonical(labels)
    if case == "one_cluster":
        assert got[4] == got[2] != got[0]


class TestFullyMissedClusters:
    def test_missed_cluster_stats(self, small_clustered, gt):
        """Table 6 machinery: clusters fully missed when every core is FN."""
        data, _ = small_clustered
        counts = exact_counts(data, 0.25)
        # kill the estimator for points of one ground-truth cluster
        target = 0
        pred = counts.copy()
        members = gt.labels == target
        pred[members] = 0.0
        res = laf_dbscan(data, 0.25, 5, 1.0, pred)
        # rescue may re-find it via partial neighbors from outside; at
        # minimum the pipeline must not crash and others stay intact
        others = ~members
        assert adjusted_rand_index(res.labels[others], gt.labels[others]) > 0.95
