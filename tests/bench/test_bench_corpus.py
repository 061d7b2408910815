"""The benchmark's copy of the corpus generator."""

import numpy as np
import pytest

from repro.data.synthetic import make_angular_clusters

TIGHT = {"n_clusters": 7, "size_pareto": 1.2, "noise_frac": 0.35, "delta": [0.3, 0.3]}


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 3])
def test_equal_deltas_reproduce_make_angular_clusters_bit_for_bit(seed):
    import corpus

    want, want_lab = make_angular_clusters(1500, 48, 7, kappa=47 / 0.3,
                                           noise_frac=0.35, seed=seed)
    got, got_lab = corpus.mixture(1500, 48, TIGHT, seed)
    assert np.array_equal(got, want) and np.array_equal(got_lab, want_lab)


def test_tight_traffic_is_the_smoke_corpus_in_the_run_order():
    import corpus
    import registry

    mix = registry.load_json("traffic", "tight")
    assert mix["delta"][0] == mix["delta"][1] == 0.3 and mix["pool_seed"] == 0
    cfg = {"n": 900, "d": 32, "rmi_train_rows": 300}
    got, train, pool = corpus.corpus_and_train(cfg, dict(mix, n_clusters=5), 9)
    assert pool == 0
    # the smoke run's draw at its default seed 0, split as it splits it
    data, _ = make_angular_clusters(1200, 32, 5, kappa=31 / 0.3, noise_frac=0.35, seed=0)
    perm = np.random.default_rng(1).permutation(1200)
    order = np.random.default_rng(9).permutation(900)
    assert np.array_equal(got, data[perm[:900]][order])
    assert np.array_equal(train, data[perm[900:]])


def test_every_run_clusters_the_same_points_in_another_order():
    import corpus

    mix = dict(TIGHT, delta=[0.25, 1.05], pool_seed=4)
    cfg = {"n": 1200, "d": 32, "rmi_train_rows": 400}
    a, ta, sa = corpus.corpus_and_train(cfg, mix, 1)
    b, tb, sb = corpus.corpus_and_train(cfg, mix, 2**31 + 5)
    assert not np.array_equal(a, b)
    assert np.array_equal(np.unique(a, axis=0), np.unique(b, axis=0))
    assert np.array_equal(ta, tb) and sa == sb == 4
    assert np.allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-5)
