"""Device-resident sweep engine: one launch per query sweep.

The per-chunk query paths dispatch one kernel launch plus one
synchronous device->host round-trip *per 256-row chunk* — at 40k rows
that is ~160 dispatches and ~160 pipeline stalls for a single
whole-database sweep, with the band thresholds, the db tile padding,
and the padded-row correction re-materialized every chunk.  This module
replaces that loop with a device-resident sweep:

* all query chunks of a launch run inside one jitted
  ``lax.fori_loop`` over the capacity-shaped operands, each iteration
  writing its chunk's counts (and packed bitmap words) into
  preallocated output slabs;
* the slabs are **donated** back into every subsequent launch
  (``donate_argnums``) so a multi-launch sweep threads one buffer
  through the whole sweep instead of copying it per launch —
  ``donate=False`` is the opt-out for backends that reject aliasing;
* the db-side tile padding, the dual-threshold padded-row correction
  (``_pad_col_hits``) and the bitmap tail mask are computed **once per
  sweep**, not once per chunk;
* results are synced to host exactly once, via a single ``device_get``
  at sweep end — every launch in between is dispatched asynchronously.

Launch shapes are quantized so compilation stays amortized: a sweep is
cut into launches of ``chunks_per_launch`` fixed-size chunks (the tail
launch is padded with zero query rows, which are sliced off after the
final sync), so the engine compiles one program per
``(chunk, chunks_per_launch, n, d)`` signature regardless of how many
rows a caller sweeps.

Under ``mesh=`` the same driver routes each launch through the sharded
index plane's pipelined evaluator
(:func:`repro.distributed.index_plane.sharded_sweep_launch`): chunks
are software-pipelined through a ``lax.scan`` carry so chunk *k*'s
cross-shard ``psum`` overlaps chunk *k+1*'s shard-local
popcount+verify (the plane's double-buffer — ``depth=1`` serializes
them, the parity baseline).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.hamming_filter.kernel import (
    DEFAULT_DB_TILE,
    DEFAULT_Q_TILE,
    hamming_filter_pallas,
)
from ..kernels.hamming_filter.ops import (
    _pad_col_hits,
    _tail_word_mask,
    default_interpret,
)
from ..obs import device as _obs_device
from ..obs import metrics as _metrics, span as _span, watch_recompiles

__all__ = [
    "SweepPlan",
    "plan_sweep",
    "sweep_counts",
    "sweep_bitmap",
    "sweep_bitmap_device",
]

DEFAULT_CHUNKS_PER_LAUNCH = 8


@dataclass(frozen=True)
class SweepPlan:
    """Launch layout of one query sweep.

    ``chunk`` is the caller's chunk rounded up to the q-tile multiple;
    a launch processes ``cpl`` chunks, and the sweep issues
    ``n_launches`` launches whose last one is padded with zero query
    rows up to ``nq_padded``.
    """

    nq: int
    chunk: int
    cpl: int
    n_launches: int

    @property
    def rows_per_launch(self) -> int:
        return self.chunk * self.cpl

    @property
    def nq_padded(self) -> int:
        return self.n_launches * self.rows_per_launch


def plan_sweep(
    nq: int,
    chunk: int,
    q_tile: int = DEFAULT_Q_TILE,
    chunks_per_launch: int = DEFAULT_CHUNKS_PER_LAUNCH,
) -> SweepPlan:
    chunk = -(-max(chunk, 1) // q_tile) * q_tile
    n_chunks = max(1, -(-nq // chunk))
    cpl = max(1, min(chunks_per_launch, n_chunks))
    n_launches = -(-n_chunks // cpl)
    return SweepPlan(nq, chunk, cpl, n_launches)


# ---------------------------------------------------------------------------
# launch bodies: fori_loop over chunks, slab accumulators
# ---------------------------------------------------------------------------


def _counts_launch_impl(
    out, tele, start, q, q_sig, db, db_sig, eps, band,
    *, chunk, q_tile, db_tile, interpret, telemetry=False,
):
    """One launch: ``cpl`` chunks of band-contract counts written into
    the (donated) ``out`` slab at ``start``.

    ``tele`` is the sweep-wide (n_chunks, 3) s32 per-chunk occupancy
    slab (donated alongside ``out``); with ``telemetry`` each chunk's
    kernel-tile ``[accept, band, reject]`` triple is written into row
    ``start // chunk + k``, otherwise the slab passes through untouched
    (the pass-through still aliases, so donation is unconditional)."""
    cpl = q.shape[0] // chunk
    qs = q.reshape(cpl, chunk, q.shape[1])
    qss = q_sig.reshape(cpl, chunk, q_sig.shape[1])

    def body(k, carry):
        acc, tl = carry
        qk = jax.lax.dynamic_index_in_dim(qs, k, 0, keepdims=False)
        qsk = jax.lax.dynamic_index_in_dim(qss, k, 0, keepdims=False)
        c = hamming_filter_pallas(
            qk, db, qsk, db_sig, eps[0], band[0], band[1],
            q_tile=q_tile, db_tile=db_tile, interpret=interpret,
            with_stats=telemetry,
        )
        if telemetry:
            c, s = c
            tl = jax.lax.dynamic_update_slice(
                tl, _obs_device.sweep_stats_tile_sum(s)[None],
                (start // chunk + k, 0),
            )
        acc = jax.lax.dynamic_update_slice(acc, c, (start + k * chunk,))
        return acc, tl

    return jax.lax.fori_loop(0, cpl, body, (out, tele))


def _bitmap_launch_impl(
    out, bm_out, tele, start, q, q_sig, db, db_sig, eps, band,
    *, chunk, q_tile, db_tile, interpret, telemetry=False,
):
    cpl = q.shape[0] // chunk
    qs = q.reshape(cpl, chunk, q.shape[1])
    qss = q_sig.reshape(cpl, chunk, q_sig.shape[1])

    def body(k, carry):
        acc, bm, tl = carry
        qk = jax.lax.dynamic_index_in_dim(qs, k, 0, keepdims=False)
        qsk = jax.lax.dynamic_index_in_dim(qss, k, 0, keepdims=False)
        outk = hamming_filter_pallas(
            qk, db, qsk, db_sig, eps[0], band[0], band[1],
            q_tile=q_tile, db_tile=db_tile, interpret=interpret,
            with_bitmap=True, with_stats=telemetry,
        )
        c, w = outk[0], outk[1]
        if telemetry:
            tl = jax.lax.dynamic_update_slice(
                tl, _obs_device.sweep_stats_tile_sum(outk[2])[None],
                (start // chunk + k, 0),
            )
        acc = jax.lax.dynamic_update_slice(acc, c, (start + k * chunk,))
        bm = jax.lax.dynamic_update_slice(bm, w, (start + k * chunk, 0))
        return acc, bm, tl

    return jax.lax.fori_loop(0, cpl, body, (out, bm_out, tele))


_STATIC = ("chunk", "q_tile", "db_tile", "interpret", "telemetry")
_counts_launch = jax.jit(_counts_launch_impl, static_argnames=_STATIC)
_counts_launch_donated = jax.jit(
    _counts_launch_impl, static_argnames=_STATIC, donate_argnums=(0, 1)
)
_bitmap_launch = jax.jit(_bitmap_launch_impl, static_argnames=_STATIC)
_bitmap_launch_donated = jax.jit(
    _bitmap_launch_impl, static_argnames=_STATIC, donate_argnums=(0, 1, 2)
)


# ---------------------------------------------------------------------------
# sweep driver
# ---------------------------------------------------------------------------


def _resolve_donate(donate) -> bool:
    # "auto" donates everywhere: XLA aliases the slabs in place on every
    # current backend (incl. CPU), so a multi-launch sweep threads one
    # buffer through all launches instead of copying it per launch;
    # donate=False is the escape hatch for backends that reject aliasing
    return True if donate == "auto" else bool(donate)


def _pad_q(q, q_sig, nq_padded: int):
    """Zero query rows up to the launch multiple (results sliced off)."""
    q = jnp.asarray(q, jnp.float32)
    q_sig = jnp.asarray(q_sig, jnp.uint32)
    pad = nq_padded - q.shape[0]
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        q_sig = jnp.pad(q_sig, ((0, pad), (0, 0)))
    return q, q_sig


def _pad_db(db, db_sig, db_tile: int):
    db = jnp.asarray(db)
    db_sig = jnp.asarray(db_sig, jnp.uint32)
    pad = (-db.shape[0]) % db_tile
    if pad:
        db = jnp.pad(db, ((0, pad), (0, 0)))
        db_sig = jnp.pad(db_sig, ((0, pad), (0, 0)))
    return db, db_sig


@functools.partial(jax.jit, static_argnames=("n_pad",))
def _count_correction(q_sig, eps, band, n_pad: int):
    return _pad_col_hits(q_sig, eps[0], band[0], band[1], n_pad)


def _prep(nq, eps, t_lo, t_hi, chunk, q_tile, chunks_per_launch, interpret):
    if interpret is None:
        interpret = default_interpret()
    plan = plan_sweep(nq, chunk, q_tile, chunks_per_launch)
    eps_op = jnp.asarray([eps], jnp.float32)
    band_op = jnp.stack(
        [jnp.asarray(t_lo, jnp.int32), jnp.asarray(t_hi, jnp.int32)]
    )
    return plan, eps_op, band_op, interpret


def _sweep(
    kind: str,
    q,
    q_sig,
    db,
    db_sig,
    n: int,
    eps,
    t_lo,
    t_hi,
    *,
    chunk: int,
    chunks_per_launch: int,
    q_tile: int,
    db_tile: int,
    interpret,
    donate,
    mesh,
    axes,
    depth: int,
):
    """Shared driver for both sweep variants — one place owns the
    launch loop, the donate selection, the pad corrections, and the
    single end-of-sweep host sync."""
    nq = q.shape[0]
    plan, eps_op, band_op, interpret = _prep(
        nq, eps, t_lo, t_hi, chunk, q_tile, chunks_per_launch, interpret
    )
    sweep_span = _span(
        "sweep.sweep", kind=kind, nq=nq, n=n, chunk=plan.chunk,
        launches=plan.n_launches, chunks_per_launch=plan.cpl,
        sharded=mesh is not None, pipelined=mesh is not None and depth >= 2,
    )
    sweep_span.__enter__()
    try:
        _metrics.counter("sweep.sweeps").inc()
        _metrics.counter("sweep.launches").inc(plan.n_launches)
        q, q_sig = _pad_q(q, q_sig, plan.nq_padded)
        bitmap = kind == "bitmap"
        # per-chunk occupancy telemetry rides the COUNT sweeps only (the
        # engine's scan behind query_counts / serve / stream).  The bitmap
        # sweeps feed the one-launch cluster pass, whose band occupancy is
        # the *same* statistic the count path and the auto-tuner's
        # record_occupancy already measure — and on interpret-mode backends
        # the per-tile stats ops cost real wall time per chunk, so the
        # clustering hot path keeps only its own per-round counters.
        telemetry = _obs_device.device_enabled() and not bitmap
        tele = None
        if mesh is not None:
            from ..distributed.index_plane import sharded_sweep_launch

            n_pad, parts = None, []
            for L in range(plan.n_launches):
                sl = slice(L * plan.rows_per_launch, (L + 1) * plan.rows_per_launch)
                # per-launch spans record dispatch wall time only — the
                # engine's point is async launches with ONE sync at
                # sweep end, so nothing blocks here (synced=False)
                with _span("sweep.launch", L=L, sharded=True, synced=False,
                           pipelined=depth >= 2):
                    part, n_pad = sharded_sweep_launch(
                        kind, q[sl], q_sig[sl], db, db_sig, eps_op, band_op,
                        mesh=mesh, axes=axes, chunk=plan.chunk, q_tile=q_tile,
                        db_tile=db_tile, interpret=interpret, depth=depth, n=n,
                        telemetry=telemetry,
                    )
                parts.append(part if isinstance(part, tuple) else (part,))
            outs = tuple(
                jnp.concatenate(p) if len(p) > 1 else p[0] for p in zip(*parts)
            )
            if telemetry:
                outs, tele = outs[:-1], outs[-1]
        else:
            db, db_sig = _pad_db(db, db_sig, db_tile)
            n_pad = db.shape[0] - n
            donated = _resolve_donate(donate)
            tele0 = jnp.zeros((plan.n_launches * plan.cpl, 3), jnp.int32)
            if bitmap:
                launch = _bitmap_launch_donated if donated else _bitmap_launch
                outs = (
                    jnp.zeros((plan.nq_padded,), jnp.int32),
                    jnp.zeros((plan.nq_padded, db.shape[0] // 32), jnp.uint32),
                    tele0,
                )
            else:
                launch = _counts_launch_donated if donated else _counts_launch
                outs = (jnp.zeros((plan.nq_padded,), jnp.int32), tele0)
            # donated-slab accounting: one fresh allocation per sweep;
            # every launch past the first threads (or copies) the slab
            _metrics.counter("sweep.slab_alloc").inc()
            _metrics.counter(
                "sweep.slab_donated" if donated else "sweep.slab_copied"
            ).inc(max(plan.n_launches - 1, 0))
            recompiles = watch_recompiles(
                (_counts_launch, _counts_launch_donated,
                 _bitmap_launch, _bitmap_launch_donated),
                "sweep.recompiles",
            )
            for L in range(plan.n_launches):
                sl = slice(L * plan.rows_per_launch, (L + 1) * plan.rows_per_launch)
                with _span("sweep.launch", L=L, donated=donated, synced=False):
                    outs = launch(
                        *outs, jnp.int32(L * plan.rows_per_launch), q[sl], q_sig[sl],
                        db, db_sig, eps_op, band_op,
                        chunk=plan.chunk, q_tile=q_tile, db_tile=db_tile,
                        interpret=interpret, telemetry=telemetry,
                    )
                recompiles.delta()
            if telemetry:
                outs, tele = outs[:-1], outs[-1]
            else:
                outs = outs[:-1]
        out = outs[0]
        words_needed = -(-n // 32)
        if n_pad:
            out = out - _count_correction(q_sig, eps_op, band_op, n_pad)
        if not bitmap:
            # THE sweep sync: counts (and the telemetry slab) in one get
            host = jax.device_get((out, tele) if telemetry else (out,))
            if telemetry:
                _obs_device.harvest_sweep_telemetry(host[1])
            return np.asarray(host[0][:nq]).astype(np.int64)
        bm_out = outs[1]
        if n_pad:
            bm_out = (
                bm_out[:, :words_needed] & _tail_word_mask(words_needed, n)[None, :]
            )
        # bitmap kind: telemetry is scoped off above, so the single sync
        # fetches exactly the PR 8 pair
        host = jax.device_get((out, bm_out))
        counts, bm = host[0], host[1]
        return (
            np.asarray(counts)[:nq].astype(np.int64),
            np.ascontiguousarray(np.asarray(bm)[:nq, :words_needed]),
        )
    finally:
        # the device_get above IS the sweep's single host sync, so the
        # span closing here measures execution, not dispatch
        sweep_span.__exit__(None, None, None)


def sweep_bitmap_device(
    q,
    q_sig,
    db,
    db_sig,
    n: int,
    eps,
    t_lo,
    t_hi,
    *,
    chunk: int = 256,
    chunks_per_launch: int = DEFAULT_CHUNKS_PER_LAUNCH,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret=None,
    donate="auto",
    mesh=None,
    axes=None,
    depth: int = 2,
):
    """Device-resident sweep with **no host sync**: the packed bitmap
    slab stays on device for a downstream consumer (the one-launch
    cluster pass).

    Same launch layout and donation discipline as :func:`sweep_bitmap`,
    but the result is the *capacity-width* device slab
    ``(plan.nq_padded, W)`` with every bit for columns >= n cleared
    (tail mask applied on device) — under ``mesh=`` its words stay
    physically sharded across the plane.  Returns ``(slab, plan)``;
    rows past ``plan.nq`` are zero-query padding.
    """
    nq = q.shape[0]
    plan, eps_op, band_op, interpret = _prep(
        nq, eps, t_lo, t_hi, chunk, q_tile, chunks_per_launch, interpret
    )
    with _span(
        "sweep.sweep", kind="bitmap_device", nq=nq, n=n, chunk=plan.chunk,
        launches=plan.n_launches, chunks_per_launch=plan.cpl,
        sharded=mesh is not None, synced=False,
    ):
        _metrics.counter("sweep.sweeps").inc()
        _metrics.counter("sweep.launches").inc(plan.n_launches)
        q, q_sig = _pad_q(q, q_sig, plan.nq_padded)
        # no occupancy telemetry on this path (see _sweep): the bitmap
        # feeds the one-launch cluster pass, which carries its own
        # per-round counters — the band-occupancy statistic is already
        # measured by the count sweeps and record_occupancy, and keeping
        # the stats ops out of the interpreted kernel keeps the fused
        # clustering's telemetry-on build within the SLO of the plain one
        if mesh is not None:
            from ..distributed.index_plane import sharded_sweep_launch

            parts = []
            for L in range(plan.n_launches):
                sl = slice(L * plan.rows_per_launch, (L + 1) * plan.rows_per_launch)
                with _span("sweep.launch", L=L, sharded=True, synced=False,
                           pipelined=depth >= 2):
                    part, _ = sharded_sweep_launch(
                        "bitmap", q[sl], q_sig[sl], db, db_sig, eps_op, band_op,
                        mesh=mesh, axes=axes, chunk=plan.chunk, q_tile=q_tile,
                        db_tile=db_tile, interpret=interpret, depth=depth, n=n,
                    )
                parts.append(part)
            bm_out = _join_tail_masked([p[1] for p in parts], n)
        else:
            db, db_sig = _pad_db(db, db_sig, db_tile)
            donated = _resolve_donate(donate)
            launch = _bitmap_launch_donated if donated else _bitmap_launch
            outs = (
                jnp.zeros((plan.nq_padded,), jnp.int32),
                jnp.zeros((plan.nq_padded, db.shape[0] // 32), jnp.uint32),
                # stats placeholder: the launch signature always carries a
                # telemetry slab (so the donated aliasing is unconditional);
                # with telemetry off it passes through untouched
                jnp.zeros((plan.n_launches * plan.cpl, 3), jnp.int32),
            )
            _metrics.counter("sweep.slab_alloc").inc()
            _metrics.counter(
                "sweep.slab_donated" if donated else "sweep.slab_copied"
            ).inc(max(plan.n_launches - 1, 0))
            recompiles = watch_recompiles(
                (_counts_launch, _counts_launch_donated,
                 _bitmap_launch, _bitmap_launch_donated),
                "sweep.recompiles",
            )
            for L in range(plan.n_launches):
                sl = slice(L * plan.rows_per_launch, (L + 1) * plan.rows_per_launch)
                with _span("sweep.launch", L=L, donated=donated, synced=False):
                    outs = launch(
                        *outs, jnp.int32(L * plan.rows_per_launch), q[sl], q_sig[sl],
                        db, db_sig, eps_op, band_op,
                        chunk=plan.chunk, q_tile=q_tile, db_tile=db_tile,
                        interpret=interpret,
                    )
                recompiles.delta()
            bm_out = outs[1] & _tail_word_mask(outs[1].shape[1], n)[None, :]
        return bm_out, plan


@functools.partial(jax.jit, static_argnames=("n",))
def _join_tail_masked(blocks, n: int):
    """The sharded launches' bitmap blocks as one slab, every bit for
    columns >= n cleared, in one program: the slab is allocated once
    beside its blocks.  A concatenation and then a mask held three
    slabs at once (15.8 GB on the fullest of four v5e at 480,000 rows)."""
    bm = jnp.concatenate(blocks)
    return bm & _tail_word_mask(bm.shape[1], n)[None, :]


def sweep_counts(
    q,
    q_sig,
    db,
    db_sig,
    n: int,
    eps,
    t_lo,
    t_hi,
    *,
    chunk: int = 256,
    chunks_per_launch: int = DEFAULT_CHUNKS_PER_LAUNCH,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret=None,
    donate="auto",
    mesh=None,
    axes=None,
    depth: int = 2,
) -> np.ndarray:
    """Band-contract neighbor counts of every query row against the
    first ``n`` db rows, as one device-resident sweep.

    ``db``/``db_sig`` may carry capacity slack past ``n`` — rows there
    must be zero vectors with zero signature words (the streaming append
    shape); tile padding and the dual-threshold correction for *all*
    pad rows are applied once per sweep.  Under ``mesh=`` they must be
    the plane-sharded arrays from ``shard_database`` and each launch
    runs the pipelined sharded evaluator instead.  Returns int64
    ``(nq,)`` counts after exactly one host sync.
    """
    return _sweep(
        "count", q, q_sig, db, db_sig, n, eps, t_lo, t_hi,
        chunk=chunk, chunks_per_launch=chunks_per_launch, q_tile=q_tile,
        db_tile=db_tile, interpret=interpret, donate=donate,
        mesh=mesh, axes=axes, depth=depth,
    )


def sweep_bitmap(
    q,
    q_sig,
    db,
    db_sig,
    n: int,
    eps,
    t_lo,
    t_hi,
    *,
    chunk: int = 256,
    chunks_per_launch: int = DEFAULT_CHUNKS_PER_LAUNCH,
    q_tile: int = DEFAULT_Q_TILE,
    db_tile: int = DEFAULT_DB_TILE,
    interpret=None,
    donate="auto",
    mesh=None,
    axes=None,
    depth: int = 2,
):
    """(counts int64 ``(nq,)``, packed adjacency uint32
    ``(nq, ceil(n/32))``) for every query row vs the first ``n`` db
    rows — the one-launch counterpart of the per-chunk
    ``hamming_filter_bitmap`` loop; pad bits are cleared and results
    sync to host exactly once.
    """
    return _sweep(
        "bitmap", q, q_sig, db, db_sig, n, eps, t_lo, t_hi,
        chunk=chunk, chunks_per_launch=chunks_per_launch, q_tile=q_tile,
        db_tile=db_tile, interpret=interpret, donate=donate,
        mesh=mesh, axes=axes, depth=depth,
    )
