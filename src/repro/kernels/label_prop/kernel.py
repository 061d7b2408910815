"""Min-label-propagation Pallas kernels over packed adjacency bitmaps.

Connected components (cluster formation) on the core-core ε-graph is the
second DBSCAN hot spot after range counting.  The adjacency rows are the
packed uint32 bitmaps the ``hamming_filter`` sweep already emits; one
kernel round computes   labels'[i] = min(labels[i], min over set bits of
labels[j])   streaming the bitmap tile-by-tile through VMEM.

The bit unpack never leaves the (rows, words) word tile: the kernels
loop over the 32 bit positions, each step testing one bit of every word
of the tile.  Per-column labels are therefore held **bit-major**, a
``(32, W)`` array whose row ``b`` holds the labels of columns ``32k + b``
(LSB-first, the ``pack_bits`` order), so the labels a bit position needs
are one lane-dense row.  Per-row vectors are ``(R, 1)`` columns.  The
wrappers convert between these layouts and the flat ``(W*32,)`` /
``(R,)`` vectors callers pass.

Tiling: rows 256 × words 128 (=4096 columns) per grid step: the uint32
tile is 128 KiB and the running-min tile the same — VMEM-resident with
room for double buffering.  Compiled calls need ``row_tile % 8 == 0``
and ``word_tile % 128 == 0`` (or the whole axis); :func:`check_tiles`
raises otherwise.  ``ops.py`` iterates rounds with pointer jumping
until fixpoint (O(log n) rounds for any topology).

Two grid orientations over the same packed words:

* row reduction (``label_prop_rect_pallas``) — per slab row, the min
  label over set bits; grid (row_tiles, word_tiles), word tiles
  accumulate.  This is the gather half of a propagation round, and it
  works on *rectangular* slabs (R executed rows × W words of database
  columns), which is the shape the sweep engine emits.
* column reduction (``col_reduce_pallas``) — per database column, the
  min row-value over set bits plus a weighted popcount down the rows;
  grid (word_tiles, row_tiles), row tiles accumulate.  One launch
  yields both the min-core-neighbor border owner and the transposed
  partial-count column sums without ever unpacking the slab.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROW_TILE = 256
DEFAULT_WORD_TILE = 128  # 128 words = 4096 columns per step
# compiled-kernel alignment: rows ride the 8 sublanes, words the 128 lanes
ROW_TILE_ALIGN = 8
WORD_TILE_ALIGN = 128

BIG = jnp.iinfo(jnp.int32).max


def check_tiles(r: int, w: int, row_tile: int, word_tile: int, interpret: bool) -> None:
    """Raise unless the tiles divide the (R, W) slab and, for a compiled
    call, satisfy the TPU (8, 128) block rule."""
    if r % row_tile or w % word_tile:
        raise ValueError(
            f"tiles (row_tile={row_tile}, word_tile={word_tile}) must divide "
            f"the packed slab ({r}, {w})"
        )
    if interpret:
        return
    if row_tile % ROW_TILE_ALIGN and row_tile != r:
        raise ValueError(
            f"compiled label_prop needs row_tile % {ROW_TILE_ALIGN} == 0, got {row_tile}"
        )
    if word_tile % WORD_TILE_ALIGN and word_tile != w:
        raise ValueError(
            f"compiled label_prop needs word_tile % {WORD_TILE_ALIGN} == 0, got {word_tile}"
        )


def _bit(words, b: int):
    return ((words >> b) & 1) != 0


def _label_prop_kernel(bitmap_ref, cols_ref, rows_ref, out_ref):
    """Grid (row_tiles, word_tiles); accumulates the running min over
    word tiles into out (one (TR, 1) row tile)."""
    j = pl.program_id(1)
    words = bitmap_ref[...]                         # (TR, TW) uint32
    acc = jnp.full(words.shape, BIG, jnp.int32)
    for b in range(32):
        # cols_ref row b: labels of the columns held in bit b
        acc = jnp.minimum(acc, jnp.where(_bit(words, b), cols_ref[b : b + 1, :], BIG))
    neigh = jnp.min(acc, axis=1, keepdims=True)     # (TR, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.minimum(rows_ref[...], neigh)

    @pl.when(j != 0)
    def _acc():
        out_ref[...] = jnp.minimum(out_ref[...], neigh)


@functools.partial(
    jax.jit, static_argnames=("row_tile", "word_tile", "interpret")
)
def label_prop_rect_pallas(
    row_labels: jax.Array,
    col_labels: jax.Array,
    bitmap: jax.Array,
    *,
    row_tile: int = DEFAULT_ROW_TILE,
    word_tile: int = DEFAULT_WORD_TILE,
    interpret: bool = False,
):
    """Rectangular row reduction: ``out[i] = min(row_labels[i],
    min over set bits of bitmap[i] of col_labels)``.

    ``bitmap`` is an (R, W) slab — R executed rows against W*32 database
    columns — with R % row_tile == 0 and W % word_tile == 0;
    ``col_labels`` is (W*32,) int32 (pad columns must hold INT32_MAX or
    have zero bits).
    """
    r, w = bitmap.shape
    check_tiles(r, w, row_tile, word_tile, interpret)
    assert row_labels.shape[0] == r and col_labels.shape[0] == w * 32
    grid = (r // row_tile, w // word_tile)
    out = pl.pallas_call(
        _label_prop_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, word_tile), lambda i, j: (i, j)),
            pl.BlockSpec((32, word_tile), lambda i, j: (0, j)),
            pl.BlockSpec((row_tile, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
    )(bitmap, col_labels.reshape(w, 32).T, row_labels.reshape(r, 1))
    return out[:, 0]


def label_prop_round_pallas(
    labels: jax.Array,
    bitmap: jax.Array,
    *,
    row_tile: int = DEFAULT_ROW_TILE,
    word_tile: int = DEFAULT_WORD_TILE,
    interpret: bool = False,
):
    """One square propagation round — the ``R == N`` case of
    :func:`label_prop_rect_pallas`.  labels (N,) int32; bitmap (N, W)
    uint32 with W*32 >= N (padded bits must be zero; padded labels must
    be INT32_MAX)."""
    n = labels.shape[0]
    cap = bitmap.shape[1] * 32
    col_labels = jnp.full((cap,), BIG, jnp.int32).at[:n].set(labels)
    return label_prop_rect_pallas(
        labels, col_labels, bitmap,
        row_tile=row_tile, word_tile=word_tile, interpret=interpret,
    )


def _col_reduce_kernel(bitmap_ref, row_vals_ref, row_weights_ref, min_ref, sum_ref):
    """Grid (word_tiles, row_tiles); accumulates the per-column min of
    ``row_vals`` and the per-column weighted popcount over row tiles
    into bit-major (32, TW) blocks."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full(min_ref.shape, BIG, jnp.int32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.int32)

    words = bitmap_ref[...]                         # (TR, TW) uint32
    row_vals = row_vals_ref[...]                    # (TR, 1) int32
    row_weights = row_weights_ref[...]              # (TR, 1) int32
    for b in range(32):
        bit = _bit(words, b)
        cmin = jnp.min(jnp.where(bit, row_vals, BIG), axis=0, keepdims=True)
        csum = jnp.sum(jnp.where(bit, row_weights, 0), axis=0, keepdims=True)
        min_ref[b : b + 1, :] = jnp.minimum(min_ref[b : b + 1, :], cmin)
        sum_ref[b : b + 1, :] = sum_ref[b : b + 1, :] + csum


@functools.partial(
    jax.jit, static_argnames=("row_tile", "word_tile", "interpret")
)
def col_reduce_pallas(
    bitmap: jax.Array,
    row_vals: jax.Array,
    row_weights: jax.Array,
    *,
    row_tile: int = DEFAULT_ROW_TILE,
    word_tile: int = DEFAULT_WORD_TILE,
    interpret: bool = False,
):
    """Transposed reduction over a packed (R, W) slab, one launch:

    * ``col_min[j] = min over rows i with bit (i, j) of row_vals[i]``
      (INT32_MAX where no bit is set) — with ``row_vals =
      where(core_row, row_index, MAX)`` this is exactly the
      min-core-neighbor border-owner rule;
    * ``col_sum[j] = sum over those rows of row_weights[i]`` — with unit
      weights on valid rows this is the transposed partial-count bump
      (``hit.sum(axis=0)``) without unpacking.
    """
    r, w = bitmap.shape
    check_tiles(r, w, row_tile, word_tile, interpret)
    assert row_vals.shape[0] == r and row_weights.shape[0] == r
    grid = (w // word_tile, r // row_tile)
    vals_spec = pl.BlockSpec((row_tile, 1), lambda i, j: (j, 0))
    out_spec = pl.BlockSpec((32, word_tile), lambda i, j: (0, i))
    cmin, csum = pl.pallas_call(
        _col_reduce_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, word_tile), lambda i, j: (j, i)),
            vals_spec,
            vals_spec,
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((32, w), jnp.int32),
            jax.ShapeDtypeStruct((32, w), jnp.int32),
        ],
        interpret=interpret,
    )(
        bitmap,
        row_vals.astype(jnp.int32).reshape(r, 1),
        row_weights.astype(jnp.int32).reshape(r, 1),
    )
    return cmin.T.reshape(w * 32), csum.T.reshape(w * 32)
