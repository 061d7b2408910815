"""Public jit'd wrappers for the Hamming-filter kernel: padding to tile
alignment, padded-row corrections, interpret switch — mirroring
``repro.kernels.range_count.ops``.

``interpret=None`` (the default) resolves per platform: the compiled
kernel runs whenever a TPU backs the default JAX backend (the kernels
are Mosaic TPU kernels), and the Pallas interpreter is used otherwise
(CPU containers, CI) — so callers get the fast path automatically
without every call site having to remember the switch.  Tests pin
``interpret=True`` explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...index.signatures import band_hits
from .kernel import DEFAULT_DB_TILE, DEFAULT_Q_TILE, hamming_filter_pallas

__all__ = ["hamming_filter_count", "hamming_filter_bitmap", "default_interpret"]


def default_interpret() -> bool:
    """True iff the compiled kernel cannot run here (no TPU)."""
    return jax.default_backend() != "tpu"


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _tail_word_mask(n_words: int, n: int) -> jax.Array:
    """uint32 per-word masks clearing bitmap bits for rows >= n — the
    single definition of the LSB-first tail mask (the bitmap wrapper and
    the sharded index plane both clear pad bits through here)."""
    bit_valid = jnp.arange(n_words * 32) < n
    return jnp.sum(
        bit_valid.reshape(n_words, 32).astype(jnp.uint32)
        << jnp.arange(32, dtype=jnp.uint32)[None, :],
        axis=1,
        dtype=jnp.uint32,
    )


def _pad_col_hits(q_sig: jax.Array, eps, t_lo, t_hi, n_pad: int) -> jax.Array:
    """Per-query hits contributed by zero-padded db rows.

    A padded db row has signature 0 and vector 0, so its Hamming
    distance to query i is popcount(q_sig_i) and its dot is 0 — feeding
    those into the shared ``band_hits`` predicate gives the exact count
    to subtract: a sure-accept when popcount <= t_lo, a band hit only
    when eps > 1 (like range_count's padded-hit correction, but
    signature-dependent on both thresholds)."""
    pop = jnp.sum(jax.lax.population_count(q_sig).astype(jnp.int32), axis=1)
    passes = band_hits(
        jnp.float32(0.0),
        pop,
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(t_lo, jnp.int32),
        jnp.asarray(t_hi, jnp.int32),
    )
    return jnp.where(passes, n_pad, 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("q_tile", "db_tile", "interpret", "return_stats")
)
def hamming_filter_count(
    q: jax.Array,
    db: jax.Array,
    q_sig: jax.Array,
    db_sig: jax.Array,
    eps,
    t_hi,
    *,
    t_lo=-1,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: bool | None = None,
    return_stats: bool = False,
):
    """Filtered-and-verified neighbor counts; pads to tiles and subtracts
    the padded-row hits exactly.  ``t_lo=-1`` is full-verify mode.

    ``return_stats=True`` returns ``(counts, stats)`` where stats is the
    kernel's raw (1, 3) whole-call occupancy — [sure-accepts, band
    candidates, rejects] summed over the *padded* tile grid (see
    ``hamming_filter_pallas``); the margin auto-tuner reads the band
    column to price the verify matmuls a margin would cost.
    """
    if interpret is None:
        interpret = default_interpret()
    nq, nd = q.shape[0], db.shape[0]
    qp, dbp = _pad_rows(q, q_tile), _pad_rows(db, db_tile)
    qsp, dbsp = _pad_rows(q_sig, q_tile), _pad_rows(db_sig, db_tile)
    out = hamming_filter_pallas(
        qp, dbp, qsp, dbsp, eps, t_lo, t_hi,
        q_tile=q_tile, db_tile=db_tile, interpret=interpret,
        with_stats=return_stats,
    )
    counts, stats = out if return_stats else (out, None)
    counts = counts[:nq]
    n_pad = dbp.shape[0] - nd
    if n_pad:
        counts = counts - _pad_col_hits(q_sig, eps, t_lo, t_hi, n_pad)
    return (counts, stats) if return_stats else counts


@functools.partial(
    jax.jit, static_argnames=("q_tile", "db_tile", "interpret", "return_stats")
)
def hamming_filter_bitmap(
    q: jax.Array,
    db: jax.Array,
    q_sig: jax.Array,
    db_sig: jax.Array,
    eps,
    t_hi,
    *,
    t_lo=-1,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: bool | None = None,
    return_stats: bool = False,
):
    """(counts, packed adjacency) with padded bits cleared; the bitmap
    covers ceil(nd/32) words.  ``t_lo=-1`` is full-verify mode.
    ``return_stats=True`` appends the raw (1, 3) occupancy triple
    (see ``hamming_filter_count``)."""
    if interpret is None:
        interpret = default_interpret()
    nq, nd = q.shape[0], db.shape[0]
    qp, dbp = _pad_rows(q, q_tile), _pad_rows(db, db_tile)
    qsp, dbsp = _pad_rows(q_sig, q_tile), _pad_rows(db_sig, db_tile)
    out = hamming_filter_pallas(
        qp, dbp, qsp, dbsp, eps, t_lo, t_hi,
        q_tile=q_tile, db_tile=db_tile, interpret=interpret, with_bitmap=True,
        with_stats=return_stats,
    )
    counts, bitmap = out[0], out[1]
    stats = out[2] if return_stats else None
    counts = counts[:nq]
    bitmap = bitmap[:nq]
    n_pad = dbp.shape[0] - nd
    if n_pad:
        counts = counts - _pad_col_hits(q_sig, eps, t_lo, t_hi, n_pad)
        bitmap = bitmap & _tail_word_mask(bitmap.shape[1], nd)[None, :]
    words_needed = -(-nd // 32)
    bitmap = bitmap[:, :words_needed]
    return (counts, bitmap, stats) if return_stats else (counts, bitmap)
