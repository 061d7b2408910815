"""EmbeddingBag Pallas kernel — the recsys lookup hot path.

Huge sparse tables (10^6-10^9 rows) live in HBM; only the gathered rows
ever enter VMEM.  The kernel uses scalar prefetch (PrefetchScalarGridSpec)
for the bag indices so the index stream is available to DMA row slices
of the HBM-resident table, accumulating the bag reduction in a VMEM
accumulator — one pass, no (B, L, D) intermediate (the jnp formulation
materializes it; at B=65536, L=64, D=128 that is 2 TiB — the reason this
kernel exists).

Grid: (batch_tiles,).  Each step owns a (TB, L) slice of the index
matrix and accumulates TB bags of width D.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BATCH_TILE = 8


def _make_kernel(bag_len: int, batch_tile: int):
    def kernel(ids_ref, table_ref, out_ref):
        # ids_ref: (TB, L) int32 (scalar-prefetched); table_ref: full (V, D)
        # in ANY/HBM; out_ref: (TB, D) VMEM accumulator.
        d = out_ref.shape[-1]
        acc = jnp.zeros((batch_tile, d), jnp.float32)

        def body(l, acc):
            idx = ids_ref[:, l]                      # (TB,)
            safe = jnp.where(idx >= 0, idx, 0)
            rows = table_ref[safe, :]                # dynamic row gather
            rows = jnp.where((idx >= 0)[:, None], rows.astype(jnp.float32), 0.0)
            return acc + rows

        acc = jax.lax.fori_loop(0, bag_len, body, acc)
        out_ref[...] = acc

    return kernel


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def embedding_bag_pallas(
    table: jax.Array,
    ids: jax.Array,
    *,
    batch_tile: int = DEFAULT_BATCH_TILE,
    interpret: bool = False,
):
    """table (V, D); ids (B, L) -> (B, D) fp32 bag sums.  B % TB == 0."""
    b, l = ids.shape
    v, d = table.shape
    assert b % batch_tile == 0
    grid = (b // batch_tile,)
    kernel = _make_kernel(l, batch_tile)
    ids_spec = pl.BlockSpec((batch_tile, l), lambda i: (i, 0))
    table_spec = pl.BlockSpec(memory_space=pl.ANY)  # stays in HBM; rows DMA'd
    out_spec = pl.BlockSpec((batch_tile, d), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[ids_spec, table_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
    )(ids, table)
