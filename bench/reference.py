"""Plain reference of LAF-DBSCAN over a random-projection index.

Written from the paper's definitions and the configuration file, and
importing nothing of the program.  Given the corpus, the trained RMI's
weights and the configuration, it computes what one
``LAFPipeline.cluster_laf_dbscan`` call must return:

1. RMI predict (3 stages of 1/2/4 MLPs, routed by floor(z / z_max * k))
   and the LAF skip rule ``count >= alpha * tau``;
2. sign signatures ``x @ R >= 0`` with ``R`` the seeded Gaussian
   projection, and the hit predicate of the index: Hamming distance
   ``<= t_lo`` is a hit, ``<= t_hi`` is a hit when the dot exceeds
   ``1 - eps``, anything further is not;
3. the core test over the executed rows, the components of the core
   graph (min-label hooking rounds), and the border rule (a non-core
   point joins the cluster of the lowest-index core row that hits it);
4. Algorithm 3: a skipped point hit by at least tau executed rows merges
   the clusters of those rows and joins the merged cluster.

Every matmul takes its operands rounded to the precision the
configuration states for that stage (``STATED``), or one step lower
(``CONTROL``), and accumulates in float32.  All device work is plain
``jax.numpy`` in blocks of rows, so it fits beside nothing else on one
chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["STATED", "CONTROL", "projection", "hamming_band", "cluster", "canonical"]

# operand precision per stage: what the configuration states, and the
# control one step below it (bfloat16 -> fp8, float32 -> bfloat16)
STATED = {"rmi": "bf16", "proj": "bf16", "verify": "f32"}
CONTROL = {"rmi": "fp8", "proj": "fp8", "verify": "bf16"}

_FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _mm(a, b, kind):
    """a @ b with float32 accumulation and operands in ``kind``: float32
    at full precision, or bfloat16 (fp8 values are rounded first, then
    held exactly in bfloat16) on the matrix unit's native path."""
    if kind == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if kind == "fp8":  # saturating, as an fp8 kernel would scale and clip
        a, b = (jnp.clip(v, -_FP8_MAX, _FP8_MAX).astype(jnp.float8_e4m3fn)
                for v in (a, b))
    elif kind != "bf16":
        raise ValueError(f"unknown precision {kind!r}")
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def projection(d: int, n_bits: int, seed: int) -> np.ndarray:
    """(d, n_bits) float32 standard Gaussian directions from ``seed``."""
    return np.random.default_rng(seed).standard_normal((d, n_bits)).astype(np.float32)


def hamming_band(eps: float, n_bits: int, margin: float) -> tuple[int, int]:
    """(t_lo, t_hi): the Hamming distance of a pair at cosine distance
    eps is Binomial(n_bits, p), p = arccos(1 - eps) / pi; the band is
    ``margin`` standard deviations either side of its mean."""
    p = math.acos(float(np.clip(1.0 - eps, -1.0, 1.0))) / math.pi
    sd = math.sqrt(max(p * (1.0 - p), 1e-12) / n_bits)
    t_hi = min(n_bits, int(math.ceil(n_bits * (p + margin * sd))))
    t_lo = int(math.floor(n_bits * (p - margin * sd)))
    return t_lo, t_hi


# -- RMI ---------------------------------------------------------------------


def _mlp(layers, h, kind):
    for w, b in layers[:-1]:
        h = jax.nn.relu(_mm(h, w, kind) + b)
    w, b = layers[-1]
    return (_mm(h, w, kind) + b)[:, 0]


@functools.partial(jax.jit, static_argnames=("kind",))
def _rmi_block(params, x, eps, z_max, kind):
    feats = jnp.concatenate([x, jnp.full((x.shape[0], 1), eps, x.dtype)], axis=1)
    z = _mlp(params["stage0"], feats, kind)
    for s in (1, 2):
        stage = params[f"stage{s}"]
        k = stage[0][0].shape[0]
        route = jnp.clip(jnp.floor(z / z_max * k).astype(jnp.int32), 0, k - 1)
        per = jnp.stack([
            _mlp([(w[e], b[e]) for w, b in stage], feats, kind) for e in range(k)
        ])
        z = jnp.take_along_axis(per, route[None, :], axis=0)[0]
    return jnp.maximum(jnp.exp2(z) - 1.0, 0.0)


def rmi_counts(params, z_max, x, eps, kind, block=16384):
    """Predicted neighbour counts of every row of ``x`` at ``eps``."""
    params = jax.tree_util.tree_map(jnp.asarray, params)
    out = [
        np.asarray(_rmi_block(params, jnp.asarray(x[s : s + block]),
                              jnp.float32(eps), jnp.float32(z_max), kind))
        for s in range(0, len(x), block)
    ]
    return np.concatenate(out)


# -- hit predicate -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kind",))
def _signs(x, r, kind):
    return jnp.where(_mm(x, r, kind) >= 0.0, 1.0, -1.0).astype(jnp.bfloat16)


def _hit(xq, sq, x, s, band, thr, kind):
    """(rows, n) hits of query rows against every column."""
    agree = jnp.matmul(sq, s.T, preferred_element_type=jnp.float32)
    ham = (s.shape[1] - agree) * 0.5
    dots = _mm(xq, x.T, kind)
    return (ham <= band[0]) | ((ham <= band[1]) & (dots > thr))


@functools.partial(jax.jit, static_argnames=("kind",))
def _pass_counts(xq, sq, valid, x, s, band, thr, kind):
    """Hits per row and per column, and the pairs inside the Hamming band
    (the least verify work any implementation does)."""
    agree = jnp.matmul(sq, s.T, preferred_element_type=jnp.float32)
    ham = (s.shape[1] - agree) * 0.5
    hit = _hit(xq, sq, x, s, band, thr, kind) & valid[:, None]
    in_band = (ham > band[0]) & (ham <= band[1]) & valid[:, None]
    return (jnp.sum(hit, axis=1, dtype=jnp.int32), jnp.sum(hit, axis=0, dtype=jnp.int32),
            jnp.sum(in_band, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("kind",))
def _pass_hook(xq, sq, qidx, xc, sc, lab, band, thr, kind):
    """Per row: the least label among the columns it hits (``lab`` is
    INT32_MAX off the core); per column: the least row index that hits
    it (the border owner)."""
    hit = _hit(xq, sq, xc, sc, band, thr, kind)
    big = jnp.int32(np.iinfo(np.int32).max)
    m = jnp.min(jnp.where(hit, lab[None, :], big), axis=1)
    owner = jnp.min(jnp.where(hit, qidx[:, None], big), axis=0)
    return m, owner


@functools.partial(jax.jit, static_argnames=("kind", "k"))
def _pass_touch(xq, sq, qlab, xr, sr, band, thr, kind, k):
    """(k, rescue columns): whether any row of cluster id < k hits the
    column (rows with id -1 touch nothing)."""
    hit = _hit(xq, sq, xr, sr, band, thr, kind).astype(jnp.bfloat16)
    onehot = (qlab[:, None] == jnp.arange(k)[None, :]).astype(jnp.bfloat16)
    return jnp.matmul(onehot.T, hit, preferred_element_type=jnp.float32) > 0


def _blocks(idx, block):
    for s in range(0, len(idx), block):
        yield idx[s : s + block]


def _padded(rows, block, fill):
    """Pad a row block to the block size so one program serves all."""
    if len(rows) == block:
        return rows
    return np.concatenate([rows, np.full(block - len(rows), fill, rows.dtype)])


def _find_all(parent):
    while True:
        nxt = parent[parent]
        if np.array_equal(nxt, parent):
            return parent
        parent = nxt


def cluster(corpus, cfg: dict, rmi_params, z_max: float, prec: dict = STATED,
            block: int = 1024) -> dict:
    """The reference's answer for one corpus: ``exec`` (skip rule),
    ``core``, ``labels`` (canonical, -1 noise), ``n_rescued``, the
    hooking ``rounds`` it took and the executed pairs inside the Hamming
    band (``band_pairs``)."""
    n, d = corpus.shape
    eps, tau, alpha = float(cfg["eps"]), int(cfg["tau"]), float(cfg["alpha"])
    pred = rmi_counts(rmi_params, z_max, corpus, eps, prec["rmi"])
    execm = pred >= alpha * tau
    exec_idx = np.nonzero(execm)[0]

    x = jnp.asarray(corpus)
    r = jnp.asarray(projection(d, int(cfg["n_bits"]), int(cfg["projection_seed"])))
    s = jnp.concatenate([_signs(x[i : i + 8192], r, prec["proj"])
                         for i in range(0, n, 8192)])
    band = jnp.asarray(hamming_band(eps, int(cfg["n_bits"]), float(cfg["margin"])),
                       jnp.float32)
    thr = jnp.float32(1.0 - eps)
    kind = prec["verify"]
    block = min(block, max(len(exec_idx), 1))

    # pass 1: counts of the executed rows, hits per column (partial counts)
    counts = np.zeros(n, np.int64)
    colsum = np.zeros(n, np.int64)
    band_pairs = 0
    for rows in _blocks(exec_idx, block):
        q = jnp.asarray(_padded(rows, block, rows[0]))
        valid = jnp.arange(block) < len(rows)
        c, cs, nb = _pass_counts(x[q], s[q], valid, x, s, band, thr, kind)
        counts[rows] = np.asarray(c)[: len(rows)]
        colsum += np.asarray(cs)
        band_pairs += int(nb)
    core = execm & (counts >= tau)
    core_idx = np.nonzero(core)[0]

    # pass 2: components of the core graph by min-label hooking rounds
    # (the first over every column, which also gives each column its
    # border owner; the rest over the core columns alone)
    big = np.iinfo(np.int32).max
    parent = np.arange(n, dtype=np.int64)
    owner = np.full(n, big, np.int64)
    xc, sc = x[jnp.asarray(core_idx)], s[jnp.asarray(core_idx)]
    rounds = 0
    while len(core_idx):
        if rounds == 0:
            lab = np.full(n, big, np.int32)
            lab[core_idx] = parent[core_idx]
            cols = (x, s)
        else:
            lab = parent[core_idx].astype(np.int32)
            cols = (xc, sc)
        lab_d = jnp.asarray(lab)
        m = np.empty(len(core_idx), np.int64)
        for k, rows in enumerate(_blocks(core_idx, block)):
            q = jnp.asarray(_padded(rows, block, rows[0]))
            mb, ob = _pass_hook(x[q], s[q], q.astype(jnp.int32), *cols, lab_d,
                                band, thr, kind)
            m[k * block : k * block + len(rows)] = np.asarray(mb)[: len(rows)]
            if rounds == 0:
                owner = np.minimum(owner, np.asarray(ob))
        rounds += 1
        cur = parent[core_idx]
        if np.all(m >= cur):
            break
        hook = m < cur
        np.minimum.at(parent, cur[hook], m[hook])
        parent = _find_all(parent)
    labels = np.full(n, -1, np.int64)
    labels[core_idx] = parent[core_idx]
    border = ~core & (owner < big)
    labels[border] = labels[owner[border]]

    # Algorithm 3: a skipped point hit by >= tau executed rows merges the
    # clusters of the rows that hit it, and joins the merged cluster
    rescue = np.nonzero(~execm & (colsum >= tau))[0]
    ids = np.unique(labels[labels >= 0])
    if len(rescue) and len(ids):
        kid = np.full(n, -1, np.int64)
        kid[labels >= 0] = np.searchsorted(ids, labels[labels >= 0])
        k_pad = -(-len(ids) // 128) * 128
        xr, sr = x[jnp.asarray(rescue)], s[jnp.asarray(rescue)]
        touch = np.zeros((k_pad, len(rescue)), bool)
        for rows in _blocks(exec_idx, block):
            q = _padded(rows, block, rows[0])
            qlab = np.where(np.arange(block) < len(rows), kid[q], -1)
            touch |= np.asarray(_pass_touch(x[jnp.asarray(q)], s[jnp.asarray(q)],
                                            jnp.asarray(qlab, jnp.int32), xr, sr,
                                            band, thr, kind, k_pad))
        touch = touch[: len(ids)]
        got = touch.any(axis=0)
        first = np.argmax(touch, axis=0)
        ks, rs = np.nonzero(touch)
        root = np.arange(len(ids))
        for a, b in np.unique(np.stack([first[rs], ks], axis=1), axis=0):
            while root[a] != a:
                a = root[a]
            while root[b] != b:
                b = root[b]
            if a != b:
                root[max(a, b)] = min(a, b)
        root = _find_all(root)
        has = labels >= 0
        labels[has] = root[kid[has]]
        labels[rescue[got]] = root[first[got]]
    return {
        "exec": execm,
        "core": core,
        "labels": canonical(labels),
        "n_rescued": int(len(rescue)),
        "rounds": rounds,
        "band_pairs": band_pairs,
    }


def canonical(labels) -> np.ndarray:
    """Relabel clusters 0, 1, ... in the order of their lowest-index
    point; noise stays -1.  Two partitions are equal iff their canonical
    forms are."""
    labels = np.asarray(labels)
    out = np.full(labels.shape, -1, np.int64)
    pts = np.nonzero(labels >= 0)[0]
    if len(pts) == 0:
        return out
    ids, first = np.unique(labels[pts], return_index=True)
    order = np.argsort(pts[first], kind="stable")
    rank = np.empty(len(ids), np.int64)
    rank[order] = np.arange(len(ids))
    out[pts] = rank[np.searchsorted(ids, labels[pts])]
    return out
