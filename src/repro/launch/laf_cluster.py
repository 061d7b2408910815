"""LAF clustering lowering (the paper's workload), built on the sharded
index plane.

One frontier round = batched RMI cardinality prediction for the
frontier + range counting of the whole frontier against the
device-sharded database.  With ``backend="random_projection"`` the
round carries the ANN index: packed sign signatures ride along
row-sharded *with* the database (``repro.distributed.index_plane``'s
co-sharding contract), frontier signatures are projected in-step, and
hits follow the backend's dual-threshold band contract (sure-accept
below ``t_lo``, exact-verify only the band).

``index_device`` picks the evaluator for that predicate:

* ``True``  — the fused ``hamming_filter`` Pallas tile on every mesh
  size: single-device meshes call the wrapper directly and multi-device
  meshes run it shard-locally through
  :func:`repro.distributed.index_plane.sharded_band_marginals`
  (the same tile per shard, one psum of per-query counts, partial
  per-row counts left sharded in place).
* ``False`` — the shardable jnp dataflow of the identical
  :func:`repro.index.signatures.band_hits` predicate (XLA partitions
  the matmul + popcount).
* ``"auto"`` (default) — the fused tile whenever it earns its keep: on
  any multi-device mesh (the sharded plane is the only evaluator that
  keeps range queries local to the data shard) and on single-device
  meshes backed by a TPU; a single CPU device keeps the
  BLAS dataflow.  There is no single-device special case left in the
  routing — the plane degenerates to the plain wrapper on one device.

``index_axes`` ("auto" = every mesh axis, matching the database's
row sharding) names the mesh axes the database and signature table are
co-sharded over.

:func:`build_one_launch_cluster` is the second lowering: cluster
*formation*.  Where ``build_laf_cluster`` lowers one frontier round
(predict + sweep), the one-launch cell consumes the sweep's packed
bitmap slab and runs the entire clustering — exact counts (popcount),
the tau core test, min-label propagation over the core-core graph to
fixpoint under ``lax.while_loop`` (with pointer jumping), and the
min-core-neighbor border rule — as a single jitted ``shard_map``
program.  The slab stays column-sharded over ``index_axes`` and the
packed words never enter a collective: per round only the (R,) s32 row
minima cross the network (``lax.pmin``), plus one counts psum up front
(the LAF202 invariant).  ``rows`` is donated and aliases the exact
counts output, so the launch adds no slab-sized live buffer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..configs.registry import ArchSpec, ShapeSpec
from ..distributed.sharding import auto_axes, axis_size, named, replicated, tree_replicated
from .cell import LoweredCell

F32 = jnp.float32
I32 = jnp.int32

__all__ = ["build_laf_cluster", "build_one_launch_cluster"]


def build_laf_cluster(arch: ArchSpec, shape: ShapeSpec, mesh: Mesh) -> LoweredCell:
    from ..configs.laf_dbscan import LAFClusterConfig
    from ..core.cardinality.rmi import RMIConfig, init_rmi, rmi_predict_counts

    mesh = auto_axes(mesh)
    base: LAFClusterConfig = arch.make_config()
    n, d = shape.meta["n_points"], shape.meta["dim"]
    # pad the database to a device multiple (zero rows never pass the
    # eps threshold for eps < 1, and counts subtract exactly otherwise;
    # the fused sharded path masks zero rows inside each shard)
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    n = -(-n // n_dev) * n_dev
    dtype = jnp.bfloat16 if n > 10_000_000 else F32
    frontier = base.frontier
    rmi_cfg = RMIConfig(input_dim=d + 1)
    abstract_rmi = jax.eval_shape(lambda: init_rmi(jax.random.PRNGKey(0), rmi_cfg))
    all_axes = tuple(mesh.axis_names)
    thresh = 1.0 - base.eps

    use_rp = base.backend == "random_projection"
    use_kernel = False
    if use_rp:
        from ..index.signatures import hamming_band, make_projection
        from ..kernels.hamming_filter.ops import default_interpret

        n_bits = base.index_bits
        sig_words = n_bits // 32
        # the projection is part of the cell contract: db_sig passed in
        # must be packed with this (index_seed, index_bits) projection —
        # both are recorded in the cell meta below
        proj = jnp.asarray(make_projection(d, n_bits, seed=base.index_seed))
        t_lo, t_hi = hamming_band(base.eps, n_bits, margin=base.index_margin)
        if base.index_verify == "full":
            t_lo = -1
        # which mesh axes co-shard the db rows + signature table
        # ("auto" = all of them, i.e. exactly the db's row sharding)
        axes = all_axes if base.index_axes == "auto" else tuple(base.index_axes)
        n_shards = axis_size(mesh, axes)
        if base.index_device == "auto":
            use_kernel = n_dev > 1 or not default_interpret()
        else:
            use_kernel = bool(base.index_device)
    else:
        axes = all_axes
        n_shards = n_dev

    def cluster_step(rmi_params, db, queries, db_sig=None):
        """One frontier round: RMI predicts frontier cardinalities; the
        whole frontier's range counts + partial-neighbor increments are
        computed against the device-sharded database, as one
        device-resident sweep (frontier signatures packed once, chunks
        software-pipelined through the plane)."""
        # named scopes (not host spans — this whole function is traced
        # once and replayed) label the phases inside XLA profiler
        # captures, mirroring the host-side laf.* span names
        with jax.named_scope("laf.predict"):
            feats = jnp.concatenate(
                [queries, jnp.full((queries.shape[0], 1), base.eps, queries.dtype)],
                axis=1,
            )
            pred = rmi_predict_counts(rmi_params, feats.astype(F32), rmi_cfg)
            gate = (pred >= base.alpha * base.tau).astype(F32)  # skip decisions

        if use_rp and not use_kernel:
            # caller-level padding (n rounded to a device multiple) adds
            # zero db rows whose *signatures* are not zero (sign(0) >= 0
            # packs to all-ones); sure-accepts bypass the dot test, so
            # padded columns must be masked out explicitly (the sharded
            # plane applies the same mask shard-locally)
            db_valid = jnp.any(db != 0, axis=1)

        # bound the live (chunk, n_local) fp32 score tile to ~0.5 GiB
        # the rp path adds a (chunk, n_local) int32 ham matrix + uint32
        # XOR temporaries on top of the fp32 score tile: halve the budget
        elems_budget = 0.625e8 if use_rp else 1.25e8
        rows_budget = max(32, int(elems_budget / max(n // n_dev, 1)))
        n_chunks = 1
        while frontier // n_chunks > rows_budget and n_chunks < frontier:
            n_chunks *= 2
        qs = queries.reshape(n_chunks, frontier // n_chunks, d)

        if use_rp:
            from ..index.signatures import band_hits, hamming_words, pack_bits

            # signatures for the *whole frontier* packed once per sweep
            # (one matmul + one pack), not once per chunk
            with jax.named_scope("laf.pack_sigs"):
                q_sig_all = pack_bits((queries.astype(F32) @ proj) >= 0.0)
            q_sigs = q_sig_all.reshape(n_chunks, frontier // n_chunks, sig_words)

        if use_kernel:
            from ..distributed.index_plane import sharded_sweep_marginals

            # the fused tile, shard-local on every mesh size, all
            # chunks in one launch: popcount band split + MXU verify of
            # band tiles only (band-free tiles skip their matmul); only
            # per-query count psums cross the network — double-buffered
            # against the next chunk's popcount+verify at
            # index_pipeline >= 2 — and per-row partials stay sharded
            # where the database lives
            with jax.named_scope("laf.sweep"):
                counts, partial_counts = sharded_sweep_marginals(
                    qs.astype(F32), db, q_sigs, db_sig, base.eps, t_hi,
                    t_lo=t_lo, mesh=mesh, axes=axes, depth=base.index_pipeline,
                )
            counts = counts.reshape(frontier)
            counts = (counts.astype(F32) * gate).astype(I32)
            return counts, partial_counts, pred

        def chunk_counts(xs):
            qc = xs[0] if use_rp else xs
            # native-dtype MXU dot with fp32 accumulation: upcasting the
            # database to f32 first doubles HBM traffic and halves the
            # bf16 MXU rate (§Perf iteration on web_1b)
            dots = jax.lax.dot_general(
                qc, db, (((1,), (1,)), ((), ())),
                preferred_element_type=F32,
            )                                                  # (C, n)
            if use_rp:
                ham = hamming_words(xs[1], db_sig)
                hit = band_hits(dots, ham, base.eps, t_lo, t_hi) & db_valid[None, :]
            else:
                hit = dots > thresh
            return hit.sum(axis=1, dtype=I32), hit.sum(axis=0, dtype=I32)

        with jax.named_scope("laf.sweep"):
            counts, partials = jax.lax.map(
                chunk_counts, (qs, q_sigs) if use_rp else qs
            )
        counts = counts.reshape(frontier)
        partial_counts = partials.sum(axis=0)
        # masked by skip decisions (skipped queries contribute nothing)
        counts = (counts.astype(F32) * gate).astype(I32)
        return counts, partial_counts, pred

    args = (
        abstract_rmi,
        jax.ShapeDtypeStruct((n, d), dtype),
        jax.ShapeDtypeStruct((frontier, d), dtype),
    )
    in_sh = (
        tree_replicated(mesh, abstract_rmi),
        named(mesh, axes, None),       # db row-sharded over the index axes
        replicated(mesh),
    )
    if use_rp:
        # packed signatures row-sharded exactly like the database
        args = args + (jax.ShapeDtypeStruct((n, sig_words), jnp.uint32),)
        in_sh = in_sh + (named(mesh, axes, None),)
    out_sh = (replicated(mesh), named(mesh, axes), replicated(mesh))
    meta = {
        "kind": "cluster", "n_points": n, "dim": d, "frontier": frontier,
        # the XLA-profiler scope names cluster_step's phases carry (the
        # host-side span names in core.pipeline/core.laf_dbscan mirror
        # these, so traces from either layer line up)
        "obs_scopes": ("laf.predict", "laf.pack_sigs", "laf.sweep"),
    }
    if use_rp:
        # the db_sig contract: signatures must be packed with this exact
        # projection (repro.index.make_projection(dim, bits, seed))
        meta.update(
            index_bits=base.index_bits,
            index_seed=base.index_seed,
            index_margin=base.index_margin,
            index_verify=base.index_verify,
            index_band=(t_lo, t_hi),
            index_axes=axes,
            n_shards=n_shards,
            fused_kernel=use_kernel,
            sharded=use_kernel and n_shards > 1,
            index_pipeline=base.index_pipeline,
        )
    return LoweredCell(
        f"{arch.name}:{shape.name}", cluster_step, args, in_sh, out_sh, meta,
    )


def build_one_launch_cluster(
    arch: ArchSpec, shape: ShapeSpec, mesh: Mesh
) -> LoweredCell:
    """Lower the one-launch device-resident cluster pass (see module
    docstring).  Inputs: the packed slab (R, cap/32) uint32 from the
    bitmap sweep (column-words sharded over ``index_axes``, tail bits
    past n cleared), the (R,) int32 row->database-index map (sentinel
    >= n on padding rows), and tau as a (1,) int32 operand.  Outputs:
    ``(labels, owner, col_sum, counts, rounds)`` exactly as
    :func:`repro.kernels.label_prop.packed_cluster_fixpoint` documents,
    with owner/col_sum left column-sharded where the slab lives.
    """
    import math

    from jax.sharding import PartitionSpec as P

    from ..configs.laf_dbscan import LAFClusterConfig
    from ..kernels.hamming_filter.ops import default_interpret
    from ..kernels.label_prop import packed_cluster_fixpoint
    from ..kernels.label_prop.kernel import (
        DEFAULT_ROW_TILE,
        DEFAULT_WORD_TILE,
        check_tiles,
    )

    mesh = auto_axes(mesh)
    base: LAFClusterConfig = arch.make_config()
    n = shape.meta["n_points"]
    frontier = base.frontier
    all_axes = tuple(mesh.axis_names)
    axes = all_axes if base.index_axes == "auto" else tuple(base.index_axes)
    n_shards = axis_size(mesh, axes)
    # the column capacity rounds n up so every shard holds whole words
    cap = -(-n // (32 * n_shards)) * (32 * n_shards)
    w = cap // 32
    # tiles must divide the shard-local slab exactly (local padding
    # would shift every later shard's global column indices); a
    # compiled build raises on a tile the TPU block rule refuses
    row_tile = math.gcd(frontier, DEFAULT_ROW_TILE)
    word_tile = math.gcd(w // n_shards, DEFAULT_WORD_TILE)
    interpret = default_interpret()
    check_tiles(frontier, w // n_shards, row_tile, word_tile, interpret)
    if base.telemetry == "auto":
        from ..obs import device_enabled

        tele_on = device_enabled()
    else:
        tele_on = bool(base.telemetry)

    def cluster_one_launch(bitmap, rows, tau):
        cap_loc = bitmap.shape[1] * 32
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return packed_cluster_fixpoint(
            bitmap, rows, tau[0], idx * cap_loc,
            n=n, cap=cap, row_tile=row_tile, word_tile=word_tile,
            interpret=interpret, axes=axes, telemetry=tele_on,
        )

    out_specs = (P(None), P(axes), P(axes), P(None), P())
    if tele_on:
        # per-round telemetry vectors: psum'd in-loop, replicated out
        out_specs = out_specs + ((P(None),) * 4,)
    step = jax.shard_map(
        cluster_one_launch,
        mesh=mesh,
        in_specs=(P(None, axes), P(None), P(None)),
        out_specs=out_specs,
        check_vma=False,
    )
    args = (
        jax.ShapeDtypeStruct((frontier, w), jnp.uint32),
        jax.ShapeDtypeStruct((frontier,), I32),
        jax.ShapeDtypeStruct((1,), I32),
    )
    in_sh = (named(mesh, None, axes), replicated(mesh), replicated(mesh))
    out_sh = (
        replicated(mesh),      # labels (cap,) — the while-loop carry
        named(mesh, axes),     # owner, column-sharded with the slab
        named(mesh, axes),     # col_sum, likewise
        replicated(mesh),      # counts (R,) — aliases the donated rows
        replicated(mesh),      # rounds
    )
    if tele_on:
        out_sh = out_sh + (tuple(replicated(mesh) for _ in range(4)),)
    meta = {
        "kind": "one_launch_cluster", "n_points": n, "cap": cap,
        "frontier": frontier, "index_axes": axes, "n_shards": n_shards,
        "row_tile": row_tile, "word_tile": word_tile, "telemetry": tele_on,
        # rows (R,) i32 -> counts (R,) i32: same shape/dtype/sharding
        "donate_argnums": (1,),
    }
    return LoweredCell(
        f"{arch.name}:{shape.name}:one_launch", step, args, in_sh, out_sh, meta,
    )
