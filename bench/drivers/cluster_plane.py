"""Closed-loop batch clustering on the sharded index plane: the loop of
``cluster_loop`` (set-up, window, guards and reference unchanged), with
every fresh random-projection index built with ``mesh=`` a 1-D ``data``
mesh over the cell's chips.  The index then co-shards the database rows
and their signatures over the chips at each fit, the slab sweep and the
label propagation run shard-locally with their collectives, and the
RMI, the rescue and the reference stay on chip 0.
"""

from __future__ import annotations

import functools
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def run(ctx) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import registry

    mesh = Mesh(np.array(jax.devices()[: ctx.chips]), ("data",))
    ctx.make_backend = functools.partial(ctx.make_backend, mesh=mesh)
    return registry.load_module("drivers", "cluster_loop", BENCH).run(ctx)
