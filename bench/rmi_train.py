"""The benchmark's own RMI training: the weights both the program and the
plain reference consume.

Nothing here comes from the program.  The model is the paper's (section
3.1): three stages of 1 / 2 / 4 fully connected nets, each with hidden
layers 512, 512, 256, 128 and ReLU, input the query vector with the
distance threshold appended, output z = log2(1 + count).  Stage 0 trains
on every example; each deeper stage's nets train on the examples the
trained stage above routes to them, by floor(z / z_max * k).  Adam, MSE,
batches of 512.

The targets are counted against the corpus being clustered: for each
training row and each threshold of the grid, the number of corpus rows
at cosine distance below it (dot > 1 - eps, float32).  The predicted
counts are then on the corpus's own scale, and the skip rule
``count >= alpha * tau`` reads them as the deployment would.

The returned weights have the layout the program's RMI loads: stage0 a
list of (w, b), stages 1 and 2 the same with a leading expert axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["HIDDEN", "STAGES", "targets", "train"]

HIDDEN = (512, 512, 256, 128)
STAGES = (1, 2, 4)
BATCH = 512
LR = 1e-3


@functools.partial(jax.jit, static_argnames=("eps_grid",))
def _count_block(q, corpus, eps_grid):
    dots = jnp.matmul(q, corpus.T, precision=jax.lax.Precision.HIGHEST)
    thr = 1.0 - jnp.asarray(eps_grid, jnp.float32)
    return jnp.sum(dots[None] > thr[:, None, None], axis=2, dtype=jnp.int32)


def targets(rows, corpus, eps_grid, block: int = 1024) -> np.ndarray:
    """(len(eps_grid), len(rows)) neighbour counts of ``rows`` in
    ``corpus`` at each threshold."""
    grid = tuple(float(e) for e in eps_grid)
    c = jnp.asarray(corpus)
    out = []
    for s in range(0, len(rows), block):
        q = rows[s : s + block]
        pad = block - len(q)
        qp = jnp.asarray(np.pad(q, ((0, pad), (0, 0))) if pad else q)
        out.append(np.asarray(_count_block(qp, c, grid))[:, : len(q)])
    return np.concatenate(out, axis=1)


def _init(key, dim):
    dims = (dim, *HIDDEN, 1)
    layers = []
    for a, b in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (a, b), jnp.float32) * np.float32(np.sqrt(2.0 / a))
        layers.append((w, jnp.zeros((b,), jnp.float32)))
    return layers


def _apply(layers, x):
    for w, b in layers[:-1]:
        x = jax.nn.relu(x @ w + b)
    w, b = layers[-1]
    return (x @ w + b)[:, 0]


@jax.jit
def _fit(layers, feats, z, idx):
    """Adam over the batches ``idx`` (steps, batch) of ``feats``."""
    b1, b2, tiny = 0.9, 0.999, 1e-8
    zeros = jax.tree_util.tree_map(jnp.zeros_like, layers)

    def step(carry, rows):
        p, m, v, t = carry
        x, y = feats[rows], z[rows]
        g = jax.grad(lambda q: jnp.mean(jnp.square(_apply(q, x) - y)))(p)
        t = t + 1
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        lr = LR * jnp.sqrt(1 - b2**t) / (1 - b1**t)
        p = jax.tree_util.tree_map(lambda a, mm, vv: a - lr * mm / (jnp.sqrt(vv) + tiny),
                                   p, m, v)
        return (p, m, v, t), None

    (layers, _, _, _), _ = jax.lax.scan(step, (layers, zeros, zeros, jnp.float32(0)), idx)
    return layers


_predict = jax.jit(_apply)


def _batches(rng, share, epochs):
    """(steps, batch) row indices: each epoch a permutation of ``share``,
    cut into whole batches (a share under one batch is one batch)."""
    size = min(BATCH, len(share))
    nb = max(1, len(share) // size)
    return np.concatenate([rng.permutation(share)[: nb * size].reshape(nb, size)
                           for _ in range(epochs)])


def train(rows, corpus, eps_grid, *, epochs: int, seed: int):
    """(weights, z_max): the RMI trained on ``rows`` with targets counted
    against ``corpus``; z_max is the largest target (the routing scale)."""
    grid = np.asarray(eps_grid, np.float32)
    counts = targets(rows, corpus, eps_grid)
    n, d = rows.shape
    feats = jnp.concatenate([
        jnp.tile(jnp.asarray(rows), (len(grid), 1)),
        jnp.repeat(jnp.asarray(grid), n)[:, None],
    ], axis=1)
    z = np.log2(1.0 + counts.reshape(-1)).astype(np.float32)
    z_max = float(z.max()) + 1e-6
    zd = jnp.asarray(z)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    everything = np.arange(len(z))

    def net(share):
        nonlocal key
        key, sub = jax.random.split(key)
        layers = _init(sub, d + 1)
        if len(share) < 2:  # too few to train on: left at its init
            return layers
        return _fit(layers, feats, zd, jnp.asarray(_batches(rng, share, epochs)))

    stage0 = net(everything)
    weights = {"stage0": stage0}
    pred = np.asarray(_predict(stage0, feats))
    for s, k in enumerate(STAGES[1:], start=1):
        route = np.clip(np.floor(pred / z_max * k).astype(np.int64), 0, k - 1)
        nets, new = [], np.zeros_like(pred)
        for e in range(k):
            share = np.nonzero(route == e)[0]
            layers = net(share)
            nets.append(layers)
            if len(share):  # every row through one program shape, kept where routed
                new[share] = np.asarray(_predict(layers, feats))[share]
        weights[f"stage{s}"] = [
            (jnp.stack([n_[i][0] for n_ in nets]), jnp.stack([n_[i][1] for n_ in nets]))
            for i in range(len(HIDDEN) + 1)
        ]
        pred = new
    return jax.device_get(weights), z_max
