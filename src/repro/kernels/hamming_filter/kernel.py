"""Fused Hamming-filter + exact-verify Pallas kernel (dual-threshold).

The TPU tile of the ``random_projection`` range backend, implementing
the backend's real ``verify="band"`` contract: for a (query-tile,
db-tile) pair the kernel XOR+popcounts the packed sign signatures (VPU,
``n_bits/32`` uint32 words per pair) and splits pairs on the
``(t_lo, t_hi)`` Hamming band —

  * ``ham <= t_lo``         sure-accept, **no MXU work at all**;
  * ``t_lo < ham <= t_hi``  ambiguous band, exact dot verify (MXU);
  * ``ham > t_hi``          pruned.

Only if the tile contains a *band* candidate does the exact-dot
verification matmul run — a tile whose pairs are all sure-accepts or
all pruned skips its matmul entirely, which is where the pre-filter's
pruning turns into saved FLOPs.  ``t_lo = -1`` recovers full-verify
semantics (every candidate exact-checked).  Outputs match
``range_count``'s contract (per-query int32 counts, optional packed
uint32 adjacency in the shared ``pack_bits`` bit order) so the two
kernels are drop-in alternates for the engines.

Layout.  Inside the kernel a tile is held **db-major**: db rows on the
sublanes, query rows on the 128 lanes, so every per-query quantity is a
lane-dense row vector.  Query signatures arrive word-major ``(w, nq)``,
the counts leave as a ``(1, nq)`` row, and the packed adjacency leaves
word-major ``(nd/32, nq)`` — a ``(db_tile/32, q_tile)`` = (8, 128) block
per tile, bit ``b`` of word ``k`` being db row ``32k + b`` (LSB first,
the ``pack_bits`` order).  :func:`hamming_filter_pallas` transposes the
signatures in and the bitmap out, so callers keep the row-major
``(nq, nd/32)`` contract.  The band scalars ride in SMEM.

Compiled tiling: ``q_tile`` a multiple of 128 and ``db_tile`` a multiple
of 256 (or the whole axis); q tile 128×d, db tile 256×d keeps the q/db
tiles, the (256, 128) score tile and the signature tiles well under
VMEM.  :func:`check_tiles` enforces this for compiled calls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_Q_TILE = 128
DEFAULT_DB_TILE = 256
# compiled-kernel alignment: query rows ride the 128 lanes; db rows pack
# 32 to a word and the word axis rides the 8 sublanes
Q_TILE_ALIGN = 128
DB_TILE_ALIGN = 256


def check_tiles(nq: int, nd: int, q_tile: int, db_tile: int, interpret: bool) -> None:
    """Raise unless the tiles divide the padded operands and, for a
    compiled call, satisfy the TPU (8, 128) block rule."""
    if nq % q_tile or nd % db_tile or db_tile % 32:
        raise ValueError(
            f"tiles (q_tile={q_tile}, db_tile={db_tile}) must divide the padded "
            f"operands ({nq}, {nd}) and db_tile must be a multiple of 32"
        )
    if interpret:
        return
    if q_tile % Q_TILE_ALIGN and q_tile != nq:
        raise ValueError(
            f"compiled hamming_filter needs q_tile % {Q_TILE_ALIGN} == 0, got {q_tile}"
        )
    if db_tile % DB_TILE_ALIGN and db_tile != nd:
        raise ValueError(
            f"compiled hamming_filter needs db_tile % {DB_TILE_ALIGN} == 0, got {db_tile}"
        )


def _tile_masks(qs_ref, dbs_ref, band_ref):
    """(accept, band) masks, db-major (db_tile, q_tile), from the packed
    signatures: ``dbs_ref`` (db_tile, w) row-major, ``qs_ref`` (w,
    q_tile) word-major; band_ref (SMEM) holds [t_lo, t_hi]."""
    qs = qs_ref[...]
    dbs = dbs_ref[...]
    ham = jnp.zeros((dbs.shape[0], qs.shape[1]), jnp.int32)
    for k in range(qs.shape[0]):
        x = dbs[:, k : k + 1] ^ qs[k : k + 1, :]
        ham = ham + jax.lax.population_count(x).astype(jnp.int32)
    accept = ham <= band_ref[0]
    band = (ham <= band_ref[1]) & jnp.logical_not(accept)
    return accept, band


def _verify_dots(q_ref, db_ref, thresh_ref):
    db = db_ref[...].astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32)
    dots = jax.lax.dot_general(
        db, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dots > thresh_ref[0]


def _any(mask):
    return jnp.max(mask.astype(jnp.int32)) > 0


def _col_count(hit):
    """(db_tile, q_tile) bool -> (1, q_tile) int32 hits per query."""
    return jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)


def _pack_words(hit):
    """(db_tile, q_tile) bool -> (db_tile/32, q_tile) uint32, bit b of
    word k = db row 32k + b (LSB first; bits are disjoint, so the sum
    is the OR)."""
    td, tq = hit.shape
    bits = hit.astype(jnp.int32).reshape(td // 32, 32, tq)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    words = jnp.sum(bits << shifts, axis=1)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _tile_stats(accept, band):
    """(1, 3) [sure-accepts, band candidates, rejects] for one tile —
    the occupancy triple the margin auto-tuner consumes (a tile's verify
    matmul runs iff its band count is nonzero)."""
    n_acc = jnp.sum(accept.astype(jnp.int32))
    n_band = jnp.sum(band.astype(jnp.int32))
    total = accept.shape[0] * accept.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 3), 1)
    return jnp.where(
        lane == 0, n_acc, jnp.where(lane == 1, n_band, total - n_acc - n_band)
    )


def _filter_kernel(
    q_ref, db_ref, qs_ref, dbs_ref, thresh_ref, band_ref, *out_refs,
    with_bitmap: bool, with_stats: bool,
):
    """Grid (nq_tiles, nd_tiles); counts (and the stats block) accumulate
    over the db axis, the bitmap is written once per tile."""
    counts_ref = out_refs[0]
    bitmap_ref = out_refs[1] if with_bitmap else None
    stats_ref = out_refs[-1] if with_stats else None
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    accept, band = _tile_masks(qs_ref, dbs_ref, band_ref)
    if with_stats:
        # one small accumulate per tile into the single whole-call block
        # (revisited on every grid step) instead of a per-tile slab
        @pl.when((i == 0) & (j == 0))
        def _init_stats():
            stats_ref[...] = jnp.zeros_like(stats_ref)

        stats_ref[...] += _tile_stats(accept, band)
    any_band = _any(band)

    @pl.when(any_band)
    def _verify():
        hit = accept | (band & _verify_dots(q_ref, db_ref, thresh_ref))
        counts_ref[...] += _col_count(hit)
        if with_bitmap:
            bitmap_ref[...] = _pack_words(hit)

    @pl.when(jnp.logical_not(any_band))
    def _prune():
        # band-free tile: sure-accepts (possibly none) are the whole
        # answer — no matmul
        counts_ref[...] += _col_count(accept)
        if with_bitmap:
            bitmap_ref[...] = _pack_words(accept)


@functools.partial(
    jax.jit,
    static_argnames=("q_tile", "db_tile", "interpret", "with_bitmap", "with_stats"),
)
def hamming_filter_pallas(
    q: jax.Array,
    db: jax.Array,
    q_sig: jax.Array,
    db_sig: jax.Array,
    eps: jax.Array | float,
    t_lo: jax.Array | int,
    t_hi: jax.Array | int,
    *,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: bool = False,
    with_bitmap: bool = False,
    with_stats: bool = False,
):
    """Raw kernel entry; inputs must already be tile-aligned (see ops.py).

    ``q_sig``/``db_sig`` are packed uint32 sign signatures (same bit
    order as ``repro.index.signatures``, one row per q/db row);
    ``(t_lo, t_hi)`` is the Hamming band (``t_lo = -1`` = full verify).
    Both thresholds are traced, so sweeping eps never recompiles.
    Returns counts ``(nq,)`` int32, then with ``with_bitmap`` the packed
    adjacency ``(nq, nd // 32)`` uint32 (row-major, LSB first).

    ``with_stats`` appends a (1, 3) int32 whole-call occupancy output:
    [sure-accepts, band candidates, rejects] summed over every tile's
    ``q_tile * db_tile`` pairs (padded rows included — the caller sees
    raw pair occupancy, which is what decides how many verify matmuls
    ran).  Accumulated in-kernel across the sequential grid, so the
    telemetry build adds one small block to the launch instead of a
    per-tile slab.
    """
    nq, d = q.shape
    nd = db.shape[0]
    w = q_sig.shape[1]
    assert db_sig.shape[1] == w
    check_tiles(nq, nd, q_tile, db_tile, interpret)
    grid = (nq // q_tile, nd // db_tile)
    thresh = jnp.asarray([1.0 - eps], jnp.float32)
    band_t = jnp.stack(
        [jnp.asarray(t_lo, jnp.int32), jnp.asarray(t_hi, jnp.int32)]
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((q_tile, d), lambda i, j: (i, 0)),
        pl.BlockSpec((db_tile, d), lambda i, j: (j, 0)),
        pl.BlockSpec((w, q_tile), lambda i, j: (0, i)),
        pl.BlockSpec((db_tile, w), lambda i, j: (j, 0)),
        smem,
        smem,
    ]
    out_specs = [pl.BlockSpec((1, q_tile), lambda i, j: (0, i))]
    out_shape = [jax.ShapeDtypeStruct((1, nq), jnp.int32)]
    if with_bitmap:
        out_specs.append(pl.BlockSpec((db_tile // 32, q_tile), lambda i, j: (j, i)))
        out_shape.append(jax.ShapeDtypeStruct((nd // 32, nq), jnp.uint32))
    if with_stats:
        out_specs.append(pl.BlockSpec((1, 3), lambda i, j: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, 3), jnp.int32))
    outs = pl.pallas_call(
        functools.partial(
            _filter_kernel, with_bitmap=with_bitmap, with_stats=with_stats
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(q, db, q_sig.T, db_sig, thresh, band_t)
    res = [outs[0][0]]
    if with_bitmap:
        res.append(outs[1].T)
    if with_stats:
        res.append(outs[-1])
    return res[0] if len(res) == 1 else tuple(res)
