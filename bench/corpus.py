"""Seeded corpus generator of the benchmark: vMF clusters plus uniform noise.

A copy of the arithmetic of ``repro.data.synthetic.make_angular_clusters``
(the benchmark imports nothing of the program to make its inputs), with
one extension: each cluster has its own concentration
``kappa_k = (d - 1) / delta_k``.  With every ``delta_k`` equal the random
stream and the output are those of ``make_angular_clusters`` bit for
bit, so the ``tight`` mix reproduces the smoke run's corpus.

A traffic file (``bench/traffic/<name>.json``) gives the parameters:

* ``n_clusters``, ``size_pareto`` (Pareto shape of the cluster sizes),
  ``noise_frac`` (share of uniform noise on the sphere);
* ``delta``: ``[lo, hi]``; cluster k draws ``delta_k`` uniformly in it
  (``lo == hi`` draws nothing);
* ``pool_seed``: the seed the whole mixture, and the RMI's training
  rows, are drawn from.  The run's seed only orders the corpus: every
  run clusters the same points, in another order, with the same RMI,
  so runs with different seeds do the same work.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["mixture", "corpus_and_train"]


def _uniform_sphere(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _vmf_w(rng, kappa, d, n):
    """Wood (1994) rejection sampler for the vMF cosine to the mean."""
    b = (-2.0 * kappa + math.sqrt(4.0 * kappa**2 + (d - 1.0) ** 2)) / (d - 1.0)
    x0 = (1.0 - b) / (1.0 + b)
    c = kappa * x0 + (d - 1.0) * math.log(1.0 - x0**2)
    out = np.empty(n, dtype=np.float64)
    filled = 0
    while filled < n:
        m = (n - filled) * 2 + 16
        z = rng.beta((d - 1.0) / 2.0, (d - 1.0) / 2.0, size=m)
        w = (1.0 - (1.0 + b) * z) / (1.0 - (1.0 - b) * z)
        u = rng.uniform(size=m)
        ok = kappa * w + (d - 1.0) * np.log1p(-x0 * w) - c >= np.log(u)
        take = min(int(ok.sum()), n - filled)
        out[filled : filled + take] = w[ok][:take]
        filled += take
    return out


def _vmf(rng, mu, kappa, n):
    d = mu.shape[0]
    w = _vmf_w(rng, kappa, d, n)
    v = rng.standard_normal((n, d))
    v -= (v @ mu)[:, None] * mu[None, :]
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    x = w[:, None] * mu[None, :] + np.sqrt(np.maximum(1.0 - w**2, 0.0))[:, None] * v
    return x.astype(np.float32)


def _sizes(rng, n_clustered, n_clusters, alpha):
    raw = rng.pareto(alpha, size=n_clusters) + 1.0
    sizes = np.maximum((raw / raw.sum() * n_clustered).astype(int), 1)
    while sizes.sum() < n_clustered:
        sizes[rng.integers(n_clusters)] += 1
    while sizes.sum() > n_clustered:
        i = rng.integers(n_clusters)
        if sizes[i] > 1:
            sizes[i] -= 1
    return sizes


def mixture(n: int, d: int, traffic: dict, seed: int):
    """(data (n, d) float32 unit rows, true labels (n,) with -1 noise)."""
    k = int(traffic["n_clusters"])
    lo, hi = (float(x) for x in traffic["delta"])
    rng = np.random.default_rng(seed)
    n_noise = int(round(n * float(traffic["noise_frac"])))
    sizes = _sizes(rng, n - n_noise, k, float(traffic["size_pareto"]))
    deltas = np.full(k, lo) if lo == hi else rng.uniform(lo, hi, size=k)
    centers = _uniform_sphere(rng, k, d)
    xs, ys = [], []
    for c in range(k):
        kappa = (d - 1) / deltas[c]
        xs.append(_vmf(rng, centers[c].astype(np.float64), kappa, int(sizes[c])))
        ys.append(np.full(int(sizes[c]), c, dtype=np.int64))
    if n_noise:
        xs.append(_uniform_sphere(rng, n_noise, d))
        ys.append(np.full(n_noise, -1, dtype=np.int64))
    data = np.concatenate(xs, axis=0)
    labels = np.concatenate(ys, axis=0)
    perm = rng.permutation(n)
    data = data[perm]
    data /= np.linalg.norm(data, axis=1, keepdims=True)
    return data.astype(np.float32), labels[perm]


def corpus_and_train(cfg: dict, traffic: dict, seed: int):
    """(corpus (n, d), train (train_rows, d), pool seed): disjoint draws
    from one mixture of n + train_rows rows drawn from the traffic's
    ``pool_seed`` and split as the smoke run splits it; the corpus is
    then put in the order the run's ``seed`` gives."""
    n, d, n_train = int(cfg["n"]), int(cfg["d"]), int(cfg["rmi_train_rows"])
    pool = int(traffic["pool_seed"])
    data, _ = mixture(n + n_train, d, traffic, pool)
    perm = np.random.default_rng(pool + 1).permutation(len(data))
    corpus, train = data[perm[:n]], data[perm[n:]]
    return corpus[np.random.default_rng(seed).permutation(n)], train, pool
