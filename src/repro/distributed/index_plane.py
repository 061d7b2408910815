"""Device-sharded index plane: the fused ``hamming_filter`` tile on any
mesh size.

The database rows and their packed sign-signature table are sharded
*identically* over the mesh's data axes (sDBSCAN's observation: the
random-projection summary is small enough to live with the points it
summarizes), so every range query runs shard-locally — KNN-DBSCAN's
rule that distributed high-dimensional DBSCAN lives or dies on keeping
neighborhood queries next to their data shard.  Inside each shard the
existing single-device machinery is reused unchanged: the ops wrapper
pads the local block to the kernel tile multiple and applies the
dual-threshold padded-row correction per shard.  Only per-shard results
cross the network —

* counts: one ``psum`` of (nq,) int32 partial counts;
* bitmaps: an all-gather of the (nq, n_local/32) packed uint32 words
  (the shard axis concatenates on the word dim, so the gathered array
  *is* the global bitmap);
* marginals: ``psum`` of per-query counts + the per-row partial counts
  left sharded in place —

never the (nq, n) boolean hit matrix, the database, or the signature
table.  Plane-level padding (to a shard multiple of rows) uses zero
rows with zero signatures, exactly the shape the kernel wrappers'
``_pad_col_hits`` correction was built for, so non-shard-multiple
databases stay exact — including the eps > 1 corner where zero rows
pass the dot test.

A 1-device mesh degenerates to the plain wrapper call (the ``psum`` and
gather are trivial), which is what lets ``index_device="auto"`` stop
special-casing single-device lowerings.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.signatures import shard_signatures, unpack_bits
from ..obs import metrics as _metrics
from ..kernels.hamming_filter.ops import (
    DEFAULT_DB_TILE,
    DEFAULT_Q_TILE,
    _pad_col_hits,
    _tail_word_mask,
    default_interpret,
    hamming_filter_bitmap,
    hamming_filter_count,
)
from ..kernels.label_prop.kernel import (
    DEFAULT_ROW_TILE,
    DEFAULT_WORD_TILE,
    check_tiles as check_lp_tiles,
)
from .sharding import auto_axes, axis_size, data_axes

__all__ = [
    "ShardPlan",
    "shard_plan",
    "shard_database",
    "sharded_hamming_count",
    "sharded_hamming_bitmap",
    "sharded_band_marginals",
    "sharded_sweep_launch",
    "sharded_sweep_marginals",
    "sharded_cluster_labels",
]

I32 = jnp.int32


def _count_collectives(kind: str, nq: int, n_chunks: int, n_shards: int,
                       words: int = 0, pipelined: bool = False) -> None:
    """Analytic per-call collective accounting (the traced program runs
    the psums, so they are counted here at dispatch, from the launch
    shape): each chunk's count psum moves ``chunk * 4`` bytes per shard
    hop, bitmap gathers move each shard's word block to every peer."""
    if not _metrics.enabled() or n_shards <= 1:
        return
    chunk = nq // max(n_chunks, 1)
    _metrics.counter("plane.psum.calls").inc(n_chunks)
    _metrics.counter("plane.psum.bytes").inc(n_chunks * chunk * 4)
    if kind == "bitmap":
        _metrics.counter("plane.gather.calls").inc(1)
        _metrics.counter("plane.gather.bytes").inc(nq * words * 4)
    _metrics.counter(
        "plane.chunks.pipelined" if pipelined else "plane.chunks.serialized"
    ).inc(n_chunks)


@dataclass(frozen=True)
class ShardPlan:
    """Row layout of one database over one mesh.

    ``n_padded`` is ``n`` rounded up to ``32 * n_shards`` so every shard
    holds the same number of rows *and* its packed bitmap rows are
    word-aligned (a shard's words concatenate into the global bitmap
    without bit shifting).
    """

    axes: Tuple[str, ...]
    n_shards: int
    n: int
    n_padded: int

    @property
    def n_local(self) -> int:
        return self.n_padded // self.n_shards

    @property
    def n_pad(self) -> int:
        return self.n_padded - self.n


def shard_plan(mesh: Mesh, n: int, axes=None, *, tile: int = 32) -> ShardPlan:
    """Row plan for an ``n``-row database sharded over ``axes`` (default:
    the mesh's data axes).  ``tile`` (a multiple of 32, e.g. the kernel
    db tile) additionally aligns every shard's row count to that
    multiple, so shard-local kernel calls never re-pad per launch —
    what the sweep engine's one-launch scans rely on."""
    axes = data_axes(mesh) if axes is None else tuple(axes)
    n_shards = axis_size(mesh, axes)
    if tile % 32:
        raise ValueError(f"tile must be a multiple of 32, got {tile}")
    mult = max(32, tile) * n_shards
    return ShardPlan(axes, n_shards, n, -(-n // mult) * mult)


def _pad_rows_to(x, n_padded: int):
    pad = n_padded - x.shape[0]
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def shard_database(mesh: Mesh, data, sigs, axes=None, *, tile: int = 32):
    """Co-shard a database and its packed signature table.

    Returns ``(db, db_sig, plan)`` where both arrays are padded to
    ``plan.n_padded`` zero rows / zero signature words and placed with
    ``P(axes, None)`` — one ``device_put`` each at fit time, so queries
    never move the table again.  ``tile`` aligns every shard to the
    kernel db tile (see :func:`shard_plan`).
    """
    mesh = auto_axes(mesh)
    plan = shard_plan(mesh, data.shape[0], axes, tile=tile)
    spec = P(plan.axes, None)
    db = jax.device_put(
        _pad_rows_to(jnp.asarray(data, jnp.float32), plan.n_padded),
        NamedSharding(mesh, spec),
    )
    db_sig = shard_signatures(mesh, sigs, spec, n_padded=plan.n_padded)
    return db, db_sig, plan


@functools.lru_cache(maxsize=None)
def _build_plane_fn(mesh: Mesh, axes, kind: str, q_tile: int, db_tile: int, interpret: bool):
    """shard_map'd evaluator, cached per (mesh, axes, variant, tiles).

    eps and the band thresholds ride in as traced operands (``eps``
    f32[1], ``band`` i32[2]) so eps sweeps never rebuild or recompile.
    """
    # body only runs on an lru_cache miss — i.e. a genuine plane rebuild
    _metrics.counter("plane.builds").inc()
    mesh = auto_axes(mesh)
    rep = P(None, None)
    row_sharded = P(axes, None)

    if kind == "count":

        def body(qc, db, qs, dbs, eps, band):
            c = hamming_filter_count(
                qc, db, qs, dbs, eps[0], band[1], t_lo=band[0],
                q_tile=q_tile, db_tile=db_tile, interpret=interpret,
            )
            return jax.lax.psum(c, axes)

        out_specs = P()
    elif kind == "bitmap":

        def body(qc, db, qs, dbs, eps, band):
            c, bm = hamming_filter_bitmap(
                qc, db, qs, dbs, eps[0], band[1], t_lo=band[0],
                q_tile=q_tile, db_tile=db_tile, interpret=interpret,
            )
            return jax.lax.psum(c, axes), bm

        out_specs = (P(), P(None, axes))
    else:  # marginals

        def body(qc, db, qs, dbs, eps, band):
            _, bm = hamming_filter_bitmap(
                qc, db, qs, dbs, eps[0], band[1], t_lo=band[0],
                q_tile=q_tile, db_tile=db_tile, interpret=interpret,
            )
            # all-zero db rows are padding by construction (unit-norm
            # data never has a zero row): whatever their signatures say
            # — zero words from plane padding, all-ones from the
            # lowering's sign(0) packing — they must never count
            hit = unpack_bits(bm, db.shape[0]) & jnp.any(db != 0, axis=1)[None, :]
            return (
                jax.lax.psum(hit.sum(axis=1, dtype=I32), axes),
                hit.sum(axis=0, dtype=I32),
            )

        out_specs = (P(), P(axes))

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, row_sharded, rep, row_sharded, P(None), P(None)),
        out_specs=out_specs,
        check_vma=False,
    )


def _prep(q, db, q_sig, db_sig, eps, t_lo, t_hi, mesh, axes, interpret):
    plan = shard_plan(mesh, db.shape[0], axes)
    if interpret is None:
        interpret = default_interpret()
    db = _pad_rows_to(jnp.asarray(db), plan.n_padded)
    db_sig = _pad_rows_to(jnp.asarray(db_sig, jnp.uint32), plan.n_padded)
    # eps rides as a traced (1,) operand (the wrappers derive the dot
    # threshold themselves) so eps sweeps never rebuild the plane
    eps_op = jnp.asarray([eps], jnp.float32)
    band = jnp.stack([jnp.asarray(t_lo, I32), jnp.asarray(t_hi, I32)])
    return plan, db, db_sig, eps_op, band, interpret


def sharded_hamming_count(
    q,
    db,
    q_sig,
    db_sig,
    eps,
    t_hi,
    *,
    mesh: Mesh,
    t_lo=-1,
    axes=None,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: Optional[bool] = None,
):
    """(nq,) int32 global band-contract counts; queries replicated, db +
    signatures row-sharded, one psum on the wire."""
    plan, db, db_sig, eps_op, band, interpret = _prep(
        q, db, q_sig, db_sig, eps, t_lo, t_hi, mesh, axes, interpret
    )
    f = _build_plane_fn(mesh, plan.axes, "count", q_tile, db_tile, interpret)
    _count_collectives("count", q.shape[0], 1, plan.n_shards)
    counts = f(jnp.asarray(q), db, jnp.asarray(q_sig, jnp.uint32), db_sig, eps_op, band)
    if plan.n_pad:
        counts = counts - _pad_col_hits(jnp.asarray(q_sig, jnp.uint32), eps, t_lo, t_hi, plan.n_pad)
    return counts


def sharded_hamming_bitmap(
    q,
    db,
    q_sig,
    db_sig,
    eps,
    t_hi,
    *,
    mesh: Mesh,
    t_lo=-1,
    axes=None,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: Optional[bool] = None,
):
    """(counts, packed adjacency) with plane-pad bits cleared.

    Each shard emits its word-aligned (nq, n_local/32) block; the
    gather concatenates blocks on the word axis into the global
    (nq, ceil(n/32)) bitmap — identical to the single-device wrapper's
    output on the same inputs.
    """
    nd = db.shape[0]
    plan, db, db_sig, eps_op, band, interpret = _prep(
        q, db, q_sig, db_sig, eps, t_lo, t_hi, mesh, axes, interpret
    )
    f = _build_plane_fn(mesh, plan.axes, "bitmap", q_tile, db_tile, interpret)
    _count_collectives("bitmap", q.shape[0], 1, plan.n_shards,
                       words=plan.n_padded // 32)
    q_sig = jnp.asarray(q_sig, jnp.uint32)
    counts, bitmap = f(jnp.asarray(q), db, q_sig, db_sig, eps_op, band)
    if plan.n_pad:
        counts = counts - _pad_col_hits(q_sig, eps, t_lo, t_hi, plan.n_pad)
        bitmap = bitmap & _tail_word_mask(bitmap.shape[1], nd)[None, :]
    return counts, bitmap[:, : -(-nd // 32)]


def sharded_band_marginals(
    q,
    db,
    q_sig,
    db_sig,
    eps,
    t_hi,
    *,
    mesh: Mesh,
    t_lo=-1,
    axes=None,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: Optional[bool] = None,
):
    """Both marginals of the hit matrix without gathering it: per-query
    counts (replicated, psum'd) and per-db-row partial counts (left
    sharded ``P(axes)`` — the layout the clustering lowering keeps its
    partial-neighbor accumulator in).  All-zero db rows never count, so
    callers that pad with zero rows need no correction here.
    """
    nd = db.shape[0]
    plan, db, db_sig, eps_op, band, interpret = _prep(
        q, db, q_sig, db_sig, eps, t_lo, t_hi, mesh, axes, interpret
    )
    f = _build_plane_fn(mesh, plan.axes, "marginals", q_tile, db_tile, interpret)
    _count_collectives("count", q.shape[0], 1, plan.n_shards)
    counts, partial = f(
        jnp.asarray(q), db, jnp.asarray(q_sig, jnp.uint32), db_sig, eps_op, band
    )
    return counts, partial[:nd] if plan.n_pad else partial


# ---------------------------------------------------------------------------
# device-resident sweeps: all chunks of a launch inside one shard_map,
# software-pipelined so chunk k's psum overlaps chunk k+1's popcount
# ---------------------------------------------------------------------------


def _pipeline(local, combine, items, depth: int):
    """Run ``combine(local(item))`` per item as a lax.scan.

    ``depth >= 2`` double-buffers: iteration *k* computes
    ``local(items[k])`` while combining ``local(items[k-1])`` — the two
    have no data dependence, so the compiler is free to overlap the
    previous chunk's collective with the next chunk's shard-local
    popcount+verify.  ``depth == 1`` keeps the serialized
    compute→combine chain per chunk (the parity/latency baseline).
    Items is a pytree of stacked leading-axis operands; local may
    return a pytree, combine maps local results to outputs.
    """
    n_items = jax.tree_util.tree_leaves(items)[0].shape[0]
    if depth >= 2 and n_items > 1:
        head = jax.tree_util.tree_map(lambda x: x[0], items)
        tail = jax.tree_util.tree_map(lambda x: x[1:], items)

        def step(carry, xs):
            return local(xs), combine(carry)

        last, outs = jax.lax.scan(step, local(head), tail)
        return jax.tree_util.tree_map(
            lambda o, l: jnp.concatenate([o, l[None]], axis=0), outs, combine(last)
        )
    return jax.lax.map(lambda xs: combine(local(xs)), items)


@functools.lru_cache(maxsize=None)
def _build_sweep_plane_fn(
    mesh: Mesh, axes, kind: str, chunk: int, q_tile: int, db_tile: int,
    interpret: bool, depth: int, telemetry: bool = False,
):
    """One-launch sharded sweep, cached per (mesh, axes, variant, tiles,
    chunk, pipeline depth).  The launch's query rows arrive stacked
    ``(cpl * chunk, ...)`` replicated; the db + signature table arrive
    row-sharded (the plane arrays from ``shard_database``).

    ``telemetry`` appends a replicated ``(cpl, 3)`` s32 output of
    per-chunk ``[accept, band, reject]`` kernel-tile occupancy, psum'd
    across shards per chunk (an s32 triple on the wire — it rides the
    same double-buffered slot as the count psum, so the pipeline
    overlap is unchanged)."""
    _metrics.counter("plane.builds").inc()
    mesh = auto_axes(mesh)
    rep = P(None, None)
    row_sharded = P(axes, None)
    kw = dict(q_tile=q_tile, db_tile=db_tile, interpret=interpret)

    def _tile_sum(s):
        return s.reshape(-1, 3).sum(axis=0).astype(I32)

    if kind == "count":

        def body(q, qs, db, dbs, eps, band):
            cpl = q.shape[0] // chunk
            items = (q.reshape(cpl, chunk, -1), qs.reshape(cpl, chunk, -1))

            def local(xs):
                out = hamming_filter_count(
                    xs[0], db, xs[1], dbs, eps[0], band[1], t_lo=band[0],
                    return_stats=telemetry, **kw
                )
                return (out[0], _tile_sum(out[1])) if telemetry else out

            if telemetry:
                outs, stats = _pipeline(
                    local,
                    lambda cs: (jax.lax.psum(cs[0], axes),
                                jax.lax.psum(cs[1], axes)),
                    items, depth,
                )
                return outs.reshape(cpl * chunk), stats
            outs = _pipeline(local, lambda c: jax.lax.psum(c, axes), items, depth)
            return outs.reshape(cpl * chunk)

        out_specs = (P(None), P(None, None)) if telemetry else P(None)
    else:  # bitmap

        def body(q, qs, db, dbs, eps, band):
            cpl = q.shape[0] // chunk
            items = (q.reshape(cpl, chunk, -1), qs.reshape(cpl, chunk, -1))

            def local(xs):
                out = hamming_filter_bitmap(
                    xs[0], db, xs[1], dbs, eps[0], band[1], t_lo=band[0],
                    return_stats=telemetry, **kw
                )
                if telemetry:
                    return out[0], out[1], _tile_sum(out[2])
                return out

            # only the per-chunk count psum (and the s32 occupancy
            # triple under telemetry) crosses the network; the
            # word-aligned bitmap blocks stay shard-local until the
            # out_specs gather at launch end
            if telemetry:
                outs_c, outs_bm, stats = _pipeline(
                    local,
                    lambda cbs: (jax.lax.psum(cbs[0], axes), cbs[1],
                                 jax.lax.psum(cbs[2], axes)),
                    items, depth,
                )
                return (
                    outs_c.reshape(cpl * chunk),
                    outs_bm.reshape(cpl * chunk, outs_bm.shape[-1]),
                    stats,
                )
            outs_c, outs_bm = _pipeline(
                local, lambda cb: (jax.lax.psum(cb[0], axes), cb[1]), items, depth
            )
            return (
                outs_c.reshape(cpl * chunk),
                outs_bm.reshape(cpl * chunk, outs_bm.shape[-1]),
            )

        out_specs = (
            (P(None), P(None, axes), P(None, None))
            if telemetry
            else (P(None), P(None, axes))
        )

    # jit the shard_map'd sweep so the launch program (the whole chunk
    # scan) is traced once per shape and every later sweep is a single
    # cached dispatch — eager shard_map re-traces per call, which would
    # cost more than the sweep itself
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(rep, rep, row_sharded, row_sharded, P(None), P(None)),
            out_specs=out_specs,
            check_vma=False,
        )
    )


def sharded_sweep_launch(
    kind: str,
    q,
    q_sig,
    db,
    db_sig,
    eps_op,
    band_op,
    *,
    mesh: Mesh,
    axes,
    chunk: int,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: bool = False,
    depth: int = 2,
    n: int,
    telemetry: bool = False,
):
    """One launch of the device-resident sharded sweep (driven by
    :mod:`repro.index.sweep`): ``(result, n_pad)`` where ``n_pad`` is
    the plane's zero-row column slack the driver corrects once per
    sweep.  ``db``/``db_sig`` are the plane-sharded arrays; each shard's
    rows should be db-tile aligned (``shard_database(..., tile=)``) so
    the scanned kernel calls never re-pad inside the loop.  With
    ``telemetry`` the result tuple grows a trailing replicated
    ``(cpl, 3)`` per-chunk occupancy array (count results become a
    2-tuple)."""
    axes = data_axes(mesh) if axes is None else tuple(axes)
    f = _build_sweep_plane_fn(
        mesh, axes, kind, chunk, q_tile, db_tile, interpret, depth,
        bool(telemetry),
    )
    _count_collectives(
        kind, q.shape[0], q.shape[0] // chunk, axis_size(mesh, axes),
        words=db.shape[0] // 32, pipelined=depth >= 2,
    )
    out = f(q, jnp.asarray(q_sig, jnp.uint32), db, db_sig, eps_op, band_op)
    return out, db.shape[0] - n


def sharded_sweep_marginals(
    qs,
    db,
    q_sigs,
    db_sig,
    eps,
    t_hi,
    *,
    mesh: Mesh,
    t_lo=-1,
    axes=None,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret: Optional[bool] = None,
    depth: int = 2,
):
    """One-launch, software-pipelined form of
    :func:`sharded_band_marginals` over pre-chunked frontiers.

    ``qs``/``q_sigs`` are the whole frontier stacked ``(n_chunks, C,
    ·)`` — signatures packed once per sweep, not once per chunk.  The
    per-chunk count psum is double-buffered against the next chunk's
    shard-local popcount+verify (``depth=2``); per-row partials
    accumulate in the scan carry and stay sharded ``P(axes)``.  Returns
    ``(counts (n_chunks, C) replicated, partial (n,) sharded)``.
    """
    nd = db.shape[0]
    plan = shard_plan(mesh, nd, axes, tile=db_tile)
    if interpret is None:
        interpret = default_interpret()
    db = _pad_rows_to(jnp.asarray(db), plan.n_padded)
    db_sig = _pad_rows_to(jnp.asarray(db_sig, jnp.uint32), plan.n_padded)
    eps_op = jnp.asarray([eps], jnp.float32)
    band = jnp.stack([jnp.asarray(t_lo, I32), jnp.asarray(t_hi, I32)])
    f = _build_sweep_marginals_fn(
        mesh, plan.axes, q_tile, db_tile, interpret, depth
    )
    qs = jnp.asarray(qs)
    _count_collectives(
        "count", qs.shape[0] * qs.shape[1], qs.shape[0],
        plan.n_shards, pipelined=depth >= 2,
    )
    counts, partial = f(
        qs, jnp.asarray(q_sigs, jnp.uint32), db, db_sig, eps_op, band
    )
    return counts, partial[:nd] if plan.n_pad else partial


@functools.lru_cache(maxsize=None)
def _build_sweep_marginals_fn(
    mesh: Mesh, axes, q_tile: int, db_tile: int, interpret: bool, depth: int
):
    _metrics.counter("plane.builds").inc()
    mesh = auto_axes(mesh)
    kw = dict(q_tile=q_tile, db_tile=db_tile, interpret=interpret)

    def body(qs, qss, db, dbs, eps, band):
        # all-zero db rows are padding by construction (unit-norm data
        # never has a zero row) — computed once per sweep, masked per
        # chunk (see sharded_band_marginals for why signatures alone
        # cannot be trusted on pad rows)
        db_valid = jnp.any(db != 0, axis=1)

        def local(xs):
            _, bm = hamming_filter_bitmap(
                xs[0], db, xs[1], dbs, eps[0], band[1], t_lo=band[0], **kw
            )
            hit = unpack_bits(bm, db.shape[0]) & db_valid[None, :]
            return hit.sum(axis=1, dtype=I32), hit.sum(axis=0, dtype=I32)

        if depth >= 2 and qs.shape[0] > 1:
            c0, p0 = local((qs[0], qss[0]))

            def step(carry, xs):
                c_prev, p_acc = carry
                c_k, p_k = local(xs)
                # psum of the *previous* chunk's per-query counts: no
                # data dependence on this chunk's popcount+verify, so
                # the collective and the compute overlap
                return (c_k, p_acc + p_k), jax.lax.psum(c_prev, axes)

            (c_last, partial), counts = jax.lax.scan(
                step, (c0, p0), (qs[1:], qss[1:])
            )
            counts = jnp.concatenate(
                [counts, jax.lax.psum(c_last, axes)[None]], axis=0
            )
        else:

            def step(p_acc, xs):
                c_k, p_k = local(xs)
                return p_acc + p_k, jax.lax.psum(c_k, axes)

            partial, counts = jax.lax.scan(
                step, jnp.zeros((db.shape[0],), I32), (qs, qss)
            )
        return counts, partial

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(None, None, None), P(None, None, None),
                P(axes, None), P(axes, None), P(None), P(None),
            ),
            out_specs=(P(None, None), P(axes)),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# device-resident clustering: the packed cluster fixpoint on the plane —
# per round only s32 label vectors ride collectives (pmin of the row
# minima, one counts psum up front); the packed words stay shard-local
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_cluster_plane_fn(
    mesh: Mesh, axes, n: int, max_iters: int,
    row_tile: int, word_tile: int, interpret: bool,
    telemetry: bool = False,
):
    """shard_map'd one-launch cluster pass, cached per (mesh, axes, n,
    tiles).  The slab arrives with its words sharded ``P(None, axes)``
    (the sweep plane's bitmap layout: shard k's words are the columns of
    shard k's database rows); ``rows`` and ``tau`` ride replicated.
    With ``telemetry`` the fixpoint's four per-round s32 vectors come
    back replicated (``P(None)``) — the shard-wins marginal is psum'd
    inside the round, so the outputs are replication-clean (LAF104).
    """
    _metrics.counter("plane.builds").inc()
    mesh = auto_axes(mesh)
    from ..kernels.label_prop import packed_cluster_fixpoint

    ax = axes if isinstance(axes, tuple) else (axes,)
    n_shards = axis_size(mesh, ax)

    def body(bitmap, rows, tau):
        cap_loc = bitmap.shape[1] * 32
        # flattened shard index in P(axes) concatenation order (major
        # axis first) -> this shard's global column offset
        idx = jnp.int32(0)
        for a in ax:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return packed_cluster_fixpoint(
            bitmap, rows, tau[0], idx * cap_loc,
            n=n, cap=cap_loc * n_shards, max_iters=max_iters,
            row_tile=row_tile, word_tile=word_tile, interpret=interpret,
            axes=ax, telemetry=telemetry,
        )

    out_specs = (P(None), P(axes), P(axes), P(None), P())
    if telemetry:
        out_specs = out_specs + ((P(None),) * 4,)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, axes), P(None), P(None)),
            out_specs=out_specs,
            check_vma=False,
        )
    )


def sharded_cluster_labels(
    bitmap,
    rows,
    tau,
    *,
    mesh: Mesh,
    axes,
    n: int,
    max_iters: int = 64,
    row_tile: int = DEFAULT_ROW_TILE,
    word_tile: int = DEFAULT_WORD_TILE,
    interpret=None,
    telemetry=None,
):
    """One-launch cluster pass over a column-sharded packed slab.

    ``bitmap`` is the (R, W) device slab from
    :func:`repro.index.sweep.sweep_bitmap_device` under ``mesh=`` —
    words sharded ``P(None, axes)``, tail bits past ``n`` cleared —
    and ``rows`` the (R,) database indices of the slab rows (sentinel
    >= n on padding).  Same contract as
    :func:`repro.kernels.label_prop.packed_cluster_labels`: returns
    device arrays ``(labels, owner, col_sum, counts, rounds)`` with no
    host sync; ``owner``/``col_sum`` come back column-sharded and
    reassemble on fetch.  ``telemetry`` (default: the ``repro.obs``
    device switch) appends the replicated per-round tuple.
    """
    if interpret is None:
        interpret = default_interpret()
    if telemetry is None:
        from ..obs import device_enabled

        telemetry = device_enabled()
    axes = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    w_loc = bitmap.shape[1] // axis_size(mesh, axes)
    # tiles must divide the shard-local slab exactly — padding local
    # words would shift every later shard's global column indices — and
    # a compiled call must also meet the TPU block rule: raise, never
    # fall back to a smaller illegal tile
    row_tile = math.gcd(bitmap.shape[0], row_tile)
    word_tile = math.gcd(w_loc, word_tile)
    check_lp_tiles(bitmap.shape[0], w_loc, row_tile, word_tile, interpret)
    _metrics.counter("labelprop.launches").inc()
    f = _build_cluster_plane_fn(
        mesh, axes, n, max_iters, row_tile, word_tile, interpret,
        bool(telemetry),
    )
    return f(
        bitmap,
        jnp.asarray(rows, I32),
        jnp.asarray([tau], I32),
    )
