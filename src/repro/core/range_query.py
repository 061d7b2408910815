"""Blocked range-query engine (the DBSCAN hot path).

A DBSCAN range query for point P returns N = {Q : d(P, Q) < eps}.  On
normalized vectors with cosine distance this is a thresholded matmul.
The engine processes the database in blocks so the working set stays
bounded (HBM->VMEM streaming on TPU; cache-friendly on CPU), producing:

  * counts         -- |N(P)| per query                  (exact cardinality)
  * bitmap         -- packed uint32 adjacency rows       (for label propagation)
  * neighbor lists -- host-side python lists              (for the faithful
                      sequential Algorithm-1 transcription)

The Pallas kernel in ``repro.kernels.range_count`` implements the fused
tile (distance + threshold + count + bitmap) for TPU; this module is the
pure-jnp engine and the oracle the kernel is validated against.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "range_counts",
    "range_bitmap",
    "range_counts_and_bitmap",
    "bitmap_row_to_indices",
    "neighbor_lists",
    "pack_bitmap",
    "unpack_bitmap",
]


def _num_words(n: int) -> int:
    return (n + 31) // 32


@functools.partial(jax.jit, static_argnames=("block_size",))
def range_counts(
    queries: jax.Array, db: jax.Array, eps: float, *, block_size: int = 2048
) -> jax.Array:
    """Exact neighbor counts |{j : d_cos(q_i, db_j) < eps}| per query.

    Streams the database in ``block_size`` chunks via ``lax.scan`` so the
    (nq, block) score tile is the only large intermediate.
    """
    nq, d = queries.shape
    nd = db.shape[0]
    nblocks = -(-nd // block_size)
    pad = nblocks * block_size - nd
    dbp = jnp.pad(db, ((0, pad), (0, 0)))
    valid = jnp.arange(nblocks * block_size) < nd
    dbp = dbp.reshape(nblocks, block_size, d)
    validb = valid.reshape(nblocks, block_size)

    def body(acc, blk):
        dbb, vb = blk
        # distance < eps  <=>  dot > 1 - eps
        dots = queries @ dbb.T
        hit = (dots > 1.0 - eps) & vb[None, :]
        return acc + jnp.sum(hit, axis=1, dtype=jnp.int32), None

    counts, _ = jax.lax.scan(body, jnp.zeros((nq,), jnp.int32), (dbp, validb))
    return counts


def pack_bitmap(hits: np.ndarray) -> np.ndarray:
    """Pack a boolean (nq, nd) matrix into uint32 words (nq, ceil(nd/32)).

    Bit j of word w in row i is set iff hits[i, 32*w + j].
    """
    nq, nd = hits.shape
    # little-endian bytes of LSB-first bits, viewed 4 at a time, are the
    # LSB-first uint32 words
    packed = np.zeros((nq, _num_words(nd) * 4), dtype=np.uint8)
    packed[:, : -(-nd // 8)] = np.packbits(hits, axis=1, bitorder="little")
    return packed.view("<u4").astype(np.uint32, copy=False)


def unpack_bitmap(bitmap: np.ndarray, nd: int) -> np.ndarray:
    """Inverse of :func:`pack_bitmap`."""
    words = np.ascontiguousarray(bitmap, dtype="<u4")
    return np.unpackbits(
        words.view(np.uint8), axis=1, count=nd, bitorder="little"
    ).view(bool)


@functools.partial(jax.jit, static_argnames=("block_size",))
def range_bitmap(
    queries: jax.Array, db: jax.Array, eps: float, *, block_size: int = 2048
) -> jax.Array:
    """Packed uint32 adjacency rows: bit j of row i set iff d(q_i, db_j) < eps.

    block_size must be a multiple of 32.
    """
    assert block_size % 32 == 0
    nq, d = queries.shape
    nd = db.shape[0]
    nblocks = -(-nd // block_size)
    pad = nblocks * block_size - nd
    dbp = jnp.pad(db, ((0, pad), (0, 0))).reshape(nblocks, block_size, d)
    valid = (jnp.arange(nblocks * block_size) < nd).reshape(nblocks, block_size)
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def body(_, blk):
        dbb, vb = blk
        dots = queries @ dbb.T
        hit = (dots > 1.0 - eps) & vb[None, :]
        words = hit.reshape(nq, block_size // 32, 32).astype(jnp.uint32)
        packed = jnp.sum(words << shifts[None, None, :], axis=2, dtype=jnp.uint32)
        return None, packed

    _, packed = jax.lax.scan(body, None, (dbp, valid))
    # (nblocks, nq, words_per_block) -> (nq, total_words)
    packed = jnp.transpose(packed, (1, 0, 2)).reshape(nq, -1)
    return packed[:, : _num_words(nd)]


@functools.partial(jax.jit, static_argnames=("block_size",))
def range_counts_and_bitmap(
    queries: jax.Array, db: jax.Array, eps: float, *, block_size: int = 2048
) -> Tuple[jax.Array, jax.Array]:
    """Counts and packed adjacency in one database pass."""
    assert block_size % 32 == 0
    nq, d = queries.shape
    nd = db.shape[0]
    nblocks = -(-nd // block_size)
    pad = nblocks * block_size - nd
    dbp = jnp.pad(db, ((0, pad), (0, 0))).reshape(nblocks, block_size, d)
    valid = (jnp.arange(nblocks * block_size) < nd).reshape(nblocks, block_size)
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def body(acc, blk):
        dbb, vb = blk
        dots = queries @ dbb.T
        hit = (dots > 1.0 - eps) & vb[None, :]
        cnt = acc + jnp.sum(hit, axis=1, dtype=jnp.int32)
        words = hit.reshape(nq, block_size // 32, 32).astype(jnp.uint32)
        packed = jnp.sum(words << shifts[None, None, :], axis=2, dtype=jnp.uint32)
        return cnt, packed

    counts, packed = jax.lax.scan(body, jnp.zeros((nq,), jnp.int32), (dbp, valid))
    packed = jnp.transpose(packed, (1, 0, 2)).reshape(nq, -1)
    return counts, packed[:, : _num_words(nd)]


def bitmap_row_to_indices(row: np.ndarray, nd: int) -> np.ndarray:
    """Decode one packed row to sorted neighbor indices (host-side)."""
    return np.nonzero(unpack_bitmap(row[None, :], nd)[0])[0]


def neighbor_lists(
    data: np.ndarray, eps: float, block_size: int = 4096, *, backend="exact",
    device="auto",
):
    """Host-side neighbor lists for the whole dataset.

    Returns ``list[np.ndarray]`` — used by the faithful sequential
    Algorithm-1 transcription and by tests.  Self is included (d(P,P)=0).
    ``backend`` selects the range-query engine (``repro.index``); any
    non-default backend is fit on ``data`` and queried block by block,
    with ``device`` choosing its evaluator (fused Pallas tile vs host).
    """
    data = np.asarray(data)
    if backend != "exact":  # name or RangeBackend instance
        from ..index import as_fitted  # deferred: repro.index imports this module

        return as_fitted(
            backend, np.asarray(data, np.float32), device=device
        ).neighbor_lists(eps, block_size=block_size)
    n = data.shape[0]
    out = []
    thresh = 1.0 - eps
    for start in range(0, n, block_size):
        q = data[start : start + block_size]
        dots = q @ data.T
        for i in range(q.shape[0]):
            out.append(np.nonzero(dots[i] > thresh)[0])
    return out
