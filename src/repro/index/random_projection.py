"""Signed-random-projection ANN range backend (sDBSCAN-style).

Pipeline per query block:

1. **Hamming pre-filter** — XOR + popcount between the block's packed
   sign signatures and the whole database's, as one fused jit'd pass
   (``n_bits/32`` uint32 words per pair instead of ``d`` fp32 FMAs — the
   orders-of-magnitude candidate pruning the related work reports).
2. **Band split** — Binomial concentration (see ``signatures``) puts
   true eps-neighbors below ``t_lo`` with probability ~Phi(margin) and
   non-neighbors above ``t_hi``; only the band in between is ambiguous.
3. **Exact verify** — band pairs get exact dot products (gathered
   pairwise einsum when the band is sparse; dense matmul fallback when
   a block's band saturates, so adversarial eps degrade to exact cost
   rather than wrong answers).

``verify="full"`` disables the sure-accept shortcut and exact-verifies
every candidate (hits then have no false positives; misses are bounded
by the pre-filter's margin).  ``verify="band"`` is the fast default and
what the benchmarks run.

Execution paths — **one contract, three evaluators**:

* ``device=False`` — the host numpy path above (the oracle).
* ``device=True`` — every query routes through the fused Pallas
  ``hamming_filter`` kernel (``repro.kernels.hamming_filter``), which
  implements the identical dual-threshold predicate per
  (q_tile × db_tile) tile: sure-accepts never touch the MXU and
  band-free tiles skip their verify matmul entirely.
* ``device="auto"`` (default) — the kernel when a TPU backs JAX, the
  host path otherwise, so CPU containers keep BLAS speed while TPU
  hosts get the fused tile with zero configuration.
* ``mesh=`` — device evaluation additionally routes whole-database
  queries through the sharded index plane
  (``repro.distributed.index_plane``): ``fit`` co-shards the database
  rows and the packed signature table over the mesh's data axes once,
  and every sweep runs the fused tile shard-locally, moving only
  per-shard counts/bitmap words.  Column-subset queries gather their
  (small) column side to one device and reuse the plain kernel.

Device evaluation runs through the **device-resident sweep engine**
(``repro.index.sweep``, ``sweep=True``, the default): all chunks of a
query sweep execute inside one jitted launch (``chunks_per_launch``
chunks per compiled program, results synced to host exactly once), with
the db tile padding and the padded-row corrections applied once per
sweep.  Under ``mesh=`` the engine software-pipelines the plane:
chunk k's cross-shard psum overlaps chunk k+1's shard-local
popcount+verify (``pipeline_depth=2``; ``1`` serializes — the parity
baseline).  ``sweep=False`` keeps the legacy per-chunk dispatch loop
(one launch + one synchronous device→host round-trip per chunk) as the
measured comparison baseline — see ``benchmarks/index_bench.py
--sweep``.

All paths evaluate :func:`repro.index.signatures.band_hits`, so hit
sets are identical (up to fp summation order on exact-boundary dots).
"""

from __future__ import annotations

import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.hamming_filter.ops import (
    DEFAULT_DB_TILE,
    DEFAULT_Q_TILE,
    _pad_col_hits,
    default_interpret,
    hamming_filter_bitmap,
    hamming_filter_count,
)
from ..kernels.label_prop.kernel import WORD_TILE_ALIGN
from ..obs import get_logger, metrics as _metrics, rate_limited_warn
from ..testing import faults as _faults
from ..train.fault_tolerance import GuardedStep
from .base import RangeBackend, register_backend
from .signatures import (
    hamming_band,
    hamming_numpy,
    hamming_words,
    make_projection,
    sign_signatures,
)
from .sweep import (
    DEFAULT_CHUNKS_PER_LAUNCH,
    sweep_bitmap,
    sweep_bitmap_device,
    sweep_counts,
)

__all__ = ["RandomProjectionBackend", "suggest_margin", "record_occupancy"]

# jit'd full-database sweep (fused XOR+popcount+reduce)
_hamming_sweep = jax.jit(hamming_words)


@register_backend
class RandomProjectionBackend(RangeBackend):
    name = "random_projection"

    def __init__(
        self,
        *,
        n_bits: int = 512,
        margin: float = 3.0,
        seed: int = 0,
        verify: str = "band",
        block_size: int = 2048,
        chunk: int = 256,
        max_band_frac: float = 0.05,
        device: Union[bool, str] = "auto",
        interpret: Optional[bool] = None,
        q_tile: int = DEFAULT_Q_TILE,
        db_tile: int = DEFAULT_DB_TILE,
        mesh=None,
        mesh_axes=None,
        sweep: bool = True,
        chunks_per_launch: int = DEFAULT_CHUNKS_PER_LAUNCH,
        pipeline_depth: int = 2,
        donate="auto",
        on_device_fault: str = "raise",
        fault_retries: int = 2,
        fault_backoff_s: float = 0.02,
    ):
        if verify not in ("band", "full"):
            raise ValueError(f"verify must be 'band' or 'full', got {verify!r}")
        if device not in (True, False, "auto"):
            raise ValueError(f"device must be True, False, or 'auto', got {device!r}")
        if on_device_fault not in ("degrade", "raise"):
            raise ValueError(
                f"on_device_fault must be 'degrade' or 'raise', got {on_device_fault!r}"
            )
        self.n_bits = n_bits
        self.margin = margin
        self.seed = seed
        self.verify = verify
        self.block_size = block_size
        self.chunk = chunk
        self.max_band_frac = max_band_frac
        self.device = device
        self.interpret = interpret
        self.q_tile = q_tile
        self.db_tile = db_tile
        # mesh= shards device evaluation through the index plane; the
        # host path ignores it (the oracle stays single-process)
        self.mesh = mesh
        self.mesh_axes = None if mesh_axes is None else tuple(mesh_axes)
        # sweep=True: device queries run through the one-launch sweep
        # engine (repro.index.sweep); False keeps the legacy per-chunk
        # dispatch loop as the measured baseline
        self.sweep = bool(sweep)
        self.chunks_per_launch = int(chunks_per_launch)
        self.pipeline_depth = int(pipeline_depth)
        self.donate = donate
        # device-fault policy: "raise" (default) surfaces a failure
        # that survives ``fault_retries`` exponential-backoff retries;
        # "degrade" (opt-in) falls back to the bit-exact host oracle
        # instead.  Under "degrade", three consecutive degraded queries
        # trip the sticky device-loss breaker (``_device_disabled``) —
        # further queries go straight to host with no retry latency
        # until the breaker is reset.
        self.on_device_fault = on_device_fault
        self.fault_retries = int(fault_retries)
        self.fault_backoff_s = float(fault_backoff_s)
        self._fault_streak = 0
        self._device_disabled = False
        self._data: Optional[np.ndarray] = None
        self._sigs: Optional[np.ndarray] = None
        # append buffers: ``_data``/``_sigs`` are row views into these;
        # ``partial_fit`` grows them by amortized doubling so streaming
        # ingest is O(batch), not O(n), per batch.  Device copies hold
        # the *capacity*-shaped buffers (zero rows, zero signature words
        # past ``n`` — exactly the padded-row shape ``_pad_col_hits``
        # corrects), so the kernel and the jit'd host sweep recompile
        # once per doubling instead of once per batch.
        self._data_buf: Optional[np.ndarray] = None
        self._sigs_buf: Optional[np.ndarray] = None
        self._sigs_dev = None
        self._data_dev = None
        # sweep-engine caches: db-tile-padded capacity operands (device
        # path) and the host-view signature upload (host path) — both
        # invalidated with the raw device copies
        self._sweep_dev = None
        self._host_sigs_dev = None
        self._plan = None
        self.projection: Optional[np.ndarray] = None
        # eps values whose band occupancy was already measured into the
        # index.band.* metrics (one sampled pass per (backend, eps))
        self._occ_recorded: set = set()

    @property
    def use_device(self) -> bool:
        """Whether queries run through the fused Pallas tile."""
        if self._device_disabled:
            return False
        if self.device == "auto":
            return not default_interpret()
        return bool(self.device)

    @property
    def _interpret(self) -> bool:
        """Whether the kernels run in the Pallas interpreter."""
        return default_interpret() if self.interpret is None else bool(self.interpret)

    @property
    def _launch_site(self) -> str:
        """Fault-injection site name for this backend's device dispatch."""
        if self.mesh is not None:
            return "plane.launch"
        return "sweep.launch" if self.sweep else "chunk.launch"

    def reset_device(self) -> None:
        """Re-arm the device path after a sticky device-loss degrade."""
        self._device_disabled = False
        self._fault_streak = 0

    def _guard_device(self, op: str, device_fn, host_fn):
        """Run ``device_fn`` under retry-with-backoff; on exhaustion
        degrade to ``host_fn`` (the bit-exact host oracle) per the
        ``on_device_fault`` policy.  All degradation evidence flows
        through the obs plane: ``stream.degraded.*`` counters, a
        rate-limited structured warn, and an ``slo.violation`` event via
        the degraded-SLO sweep."""
        if self._device_disabled:
            return host_fn()
        step = GuardedStep(
            device_fn,
            max_retries=self.fault_retries,
            retryable=(RuntimeError, OSError),
            backoff_s=self.fault_backoff_s,
        )
        try:
            res = step()
        except (RuntimeError, OSError) as e:
            if self.on_device_fault != "degrade":
                raise
            _metrics.counter("stream.degraded.events").inc()
            _metrics.counter(f"stream.degraded.{op}").inc()
            if len(step.failures) > 1:
                _metrics.counter("stream.degraded.retries").inc(len(step.failures) - 1)
            self._fault_streak += 1
            rate_limited_warn(
                get_logger("index"), "degraded", "device_degraded",
                op=op, error=type(e).__name__, streak=self._fault_streak,
            )
            if self._fault_streak >= 3 and not self._device_disabled:
                # device loss: every query is failing through all its
                # retries — stop paying retry latency and pin to host
                self._device_disabled = True
                _metrics.counter("stream.degraded.device_disabled").inc()
                rate_limited_warn(
                    get_logger("index"), "device_loss", "device_disabled",
                    op=op, streak=self._fault_streak,
                )
            from ..obs import slo as _slo

            _slo.check_and_alert(_slo.DEGRADED_SLOS)
            return host_fn()
        if res.attempts > 1:
            _metrics.counter("stream.degraded.retries").inc(res.attempts - 1)
        self._fault_streak = 0
        return res.value

    # -- index build -------------------------------------------------------
    def fit(self, data: np.ndarray) -> "RandomProjectionBackend":
        if self._data is data:
            return self
        data = np.ascontiguousarray(data, dtype=np.float32)
        if (
            self._data is not None
            and self._data.shape == data.shape
            and np.array_equal(self._data, data)
        ):
            # same content through a fresh array object (engines
            # re-asarray their inputs): one O(n*d) compare beats the
            # O(n*d*n_bits) rebuild; adopt the new object so the
            # identity fast-path hits next call
            self._data = data
            return self
        d = data.shape[1]
        self.projection = make_projection(d, self.n_bits, self.seed)
        self._sigs = sign_signatures(data, self.projection)
        self._data = data
        self._data_buf, self._sigs_buf = self._data, self._sigs  # cap == n
        self._sigs_dev = None  # device copies are lazy: rebuilt on demand
        self._data_dev = None
        self._sweep_dev = None
        self._host_sigs_dev = None
        self._reshard()
        return self

    def partial_fit(self, rows: np.ndarray) -> "RandomProjectionBackend":
        """Append rows + their packed signatures (streaming ingest).

        Host-side work is amortized O(rows · (d + n_bits)) per batch:
        the new rows are signed through the *existing* projection and
        written into the doubling buffers; nothing about the
        already-indexed points is recomputed.  Device copies are
        invalidated and lazily re-uploaded at capacity shape — an O(n)
        transfer on the next device-path query (kernel *compilation*
        stays amortized per doubling; a device-side in-place append is a
        possible future upgrade).  Under ``mesh=`` the database and
        signature table are likewise re-co-sharded per append through
        ``shard_database`` / ``shard_signatures`` so the index plane
        keeps its padded-tile invariants (zero pad rows with zero
        signature words).
        """
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        if self._data is None:
            return self.fit(rows)
        n, b = self._data.shape[0], rows.shape[0]
        if b == 0:
            return self
        if n + b > self._data_buf.shape[0]:
            # every doubling is also (at most) one recompile of each
            # capacity-shaped kernel signature — the pairing
            # tests/test_obs.py asserts against sweep.recompiles
            _metrics.counter("index.capacity_doublings").inc()
            # round capacity to the db tile so the capacity-padded
            # kernel operands stay tile-aligned across doublings (the
            # fit()-shaped index has cap == n and may alias caller
            # memory, so the first append always lands here and copies
            # into owned buffers)
            cap = max(2 * self._data_buf.shape[0], n + b)
            cap = -(-cap // self.db_tile) * self.db_tile
            data_buf = np.zeros((cap, self._data.shape[1]), dtype=np.float32)
            sigs_buf = np.zeros((cap, self._sigs.shape[1]), dtype=np.uint32)
            data_buf[:n] = self._data
            sigs_buf[:n] = self._sigs
            self._data_buf, self._sigs_buf = data_buf, sigs_buf
        self._data_buf[n : n + b] = rows
        self._sigs_buf[n : n + b] = sign_signatures(rows, self.projection)
        self._data = self._data_buf[: n + b]
        self._sigs = self._sigs_buf[: n + b]
        self._sigs_dev = None
        self._data_dev = None
        self._sweep_dev = None
        self._host_sigs_dev = None
        self._reshard()
        return self

    def _reshard(self) -> None:
        """(Re-)place the database + signature table on the mesh; no-op
        without one.  Called at fit and after every append — the plane's
        row plan depends on n, so an append re-pads and re-places the
        (host-resident) views in one ``device_put`` each."""
        if self.mesh is None:
            return
        from ..distributed.index_plane import shard_database

        # tile= aligns every shard to the kernel db tile so the sweep
        # engine's scanned kernel calls never re-pad inside the loop;
        # compiled, each shard's slab words must also fill whole
        # label-prop word tiles (the cluster pass cannot pad them)
        tile = self.db_tile
        if not self._interpret:
            tile = math.lcm(tile, 32 * WORD_TILE_ALIGN)
        self._db_plane, self._sig_plane, self._plan = shard_database(
            self.mesh, self._data, self._sigs, self.mesh_axes, tile=tile
        )

    # -- durability --------------------------------------------------------
    def state_export(self):
        """Capacity-faithful snapshot: the *full* doubling buffers (rows
        + packed signatures, append slack included) plus the live row
        count and the projection.  Importing on a fresh instance
        reproduces identical operand shapes, so a restored replica
        re-enters the pre-crash jit compile caches — restore is
        recompile-free (the laf-lint restored-replica target pins this).
        """
        assert self._data is not None, "call fit() first"
        return {
            "n": np.int64(self._data.shape[0]),
            "data_buf": np.ascontiguousarray(self._data_buf),
            "sigs_buf": np.ascontiguousarray(self._sigs_buf),
            "projection": np.ascontiguousarray(self.projection),
            # config echo: a restore onto a differently-configured
            # instance would silently change signatures / tile shapes
            "n_bits": np.int64(self.n_bits),
            "seed": np.int64(self.seed),
            "db_tile": np.int64(self.db_tile),
        }

    def state_import(self, state) -> "RandomProjectionBackend":
        if int(state["n_bits"]) != self.n_bits:
            raise ValueError(
                f"snapshot n_bits={int(state['n_bits'])} != backend n_bits={self.n_bits}"
            )
        if int(state["db_tile"]) != self.db_tile:
            raise ValueError(
                f"snapshot db_tile={int(state['db_tile'])} != backend db_tile={self.db_tile}"
            )
        n = int(state["n"])
        self._data_buf = np.ascontiguousarray(state["data_buf"], dtype=np.float32)
        self._sigs_buf = np.ascontiguousarray(state["sigs_buf"], dtype=np.uint32)
        self._data = self._data_buf[:n]
        self._sigs = self._sigs_buf[:n]
        self.projection = np.ascontiguousarray(state["projection"], dtype=np.float32)
        self.seed = int(state["seed"])
        self._sigs_dev = None
        self._data_dev = None
        self._sweep_dev = None
        self._host_sigs_dev = None
        self._reshard()
        return self

    @property
    def signatures(self) -> np.ndarray:
        assert self._sigs is not None, "call fit() first"
        return self._sigs

    def band(self, eps: float) -> tuple[int, int]:
        """(t_lo, t_hi) for this index; t_lo is -1 in full-verify mode."""
        t_lo, t_hi = hamming_band(eps, self.n_bits, self.margin)
        if self.verify == "full":
            t_lo = -1
        if (
            _metrics.enabled()
            and self._data is not None
            and float(eps) not in self._occ_recorded
        ):
            # one sampled occupancy pass per (backend, eps) — feeds the
            # index.band.* metrics the acceptance snapshot reports.  On
            # device it runs the stats build of the kernel, so a failure
            # there is a device fault and propagates like one
            self._occ_recorded.add(float(eps))
            record_occupancy(self, eps)
        return t_lo, t_hi

    # -- host evaluation ---------------------------------------------------
    def _band_split(self, ham: np.ndarray, eps: float):
        t_lo, t_hi = self.band(eps)
        accept = ham <= t_lo
        band = (ham <= t_hi) & ~accept
        return accept, band

    def _tile_hits(
        self, rows: np.ndarray, cols: Optional[np.ndarray], ham: np.ndarray, eps: float
    ) -> np.ndarray:
        """Band-split + exact verify for one (rows, cols) tile given its
        Hamming distances; ``cols=None`` means the whole database."""
        data = self._data
        thresh = 1.0 - eps
        accept, band = self._band_split(ham, eps)
        pi, pj = np.nonzero(band)
        if len(pi) > self.max_band_frac * band.size:
            # band saturated (eps in the bulk of the pair-distance
            # distribution): dense exact verify of the band for this
            # tile — same predicate as the sparse path (sure-accepts
            # stay accepted), only the evaluation strategy changes
            cdata = data if cols is None else data[cols]
            dots = data[rows] @ cdata.T
            return accept | (band & (dots > thresh))
        hit = accept
        if len(pi):
            cj = pj if cols is None else cols[pj]
            dots = np.einsum("ij,ij->i", data[rows[pi]], data[cj], optimize=True)
            hit = accept.copy()
            hit[pi, pj] = dots > thresh
        return hit

    def _tile_counts(
        self, rows: np.ndarray, ham: np.ndarray, eps: float
    ) -> np.ndarray:
        """Per-row hit counts for one tile without materializing the hit
        matrix: sure-accepts are a row reduction of the Hamming mask and
        band survivors are scatter-added from the verified pairs."""
        data = self._data
        thresh = 1.0 - eps
        accept, band = self._band_split(ham, eps)
        counts = accept.sum(axis=1, dtype=np.int64)
        pi, pj = np.nonzero(band)
        if len(pi) > self.max_band_frac * band.size:
            dots = data[rows] @ data.T
            counts += (band & (dots > thresh)).sum(axis=1, dtype=np.int64)
        elif len(pi):
            dots = np.einsum("ij,ij->i", data[rows[pi]], data[pj], optimize=True)
            # bincount over the verified rows beats np.add.at by an
            # order of magnitude (ufunc.at is unbuffered scalar-at-a-
            # time); this is the host oracle's band-accumulation loop
            counts += np.bincount(
                pi[dots > thresh], minlength=counts.shape[0]
            ).astype(np.int64)
        return counts

    # -- device evaluation (fused Pallas tile) -----------------------------
    @property
    def _dev_pad(self) -> int:
        """Zero rows past n in the capacity-shaped device operands."""
        return self._data_buf.shape[0] - self._data.shape[0]

    def _device_data(self):
        if self._data_dev is None:
            self._data_dev = jnp.asarray(self._data_buf)
        return self._data_dev

    def _device_sigs(self):
        if self._sigs_dev is None:
            self._sigs_dev = jnp.asarray(self._sigs_buf)
        return self._sigs_dev

    def _host_sigs(self):
        """Signature operand for the jit'd host-path Hamming sweep.

        For a fitted index (cap == n, the nominal host/batch case) this
        is the host ``_sigs`` view uploaded once — never the
        capacity-shaped device buffers.  With append slack (host-path
        streaming) it falls back to the capacity buffers on purpose:
        exact-n views would change shape every ``partial_fit`` and
        re-trace the jit'd sweep per batch, where the capacity shape
        amortizes recompiles to once per doubling (callers slice the
        slack columns off with ``[:, :n]``)."""
        if self._sigs_buf is not self._sigs:
            return self._device_sigs()
        if self._host_sigs_dev is None:
            self._host_sigs_dev = jnp.asarray(self._sigs)
        return self._host_sigs_dev

    # -- device-resident sweep engine (repro.index.sweep) ------------------
    def _sweep_db(self):
        """Capacity-shaped operands pre-padded to the db tile, cached so
        a sweep never re-pads.  Tile-aligned capacity (the partial_fit
        shape) shares the plain device copies; otherwise the padded
        copies are built straight from the host buffers so sweep mode
        holds ONE device-resident database, never padded + unpadded."""
        if self._sweep_dev is None:
            pad = (-self._data_buf.shape[0]) % self.db_tile
            if pad == 0:
                self._sweep_dev = (self._device_data(), self._device_sigs())
            else:
                db = np.zeros(
                    (self._data_buf.shape[0] + pad, self._data_buf.shape[1]),
                    dtype=np.float32,
                )
                db[: self._data_buf.shape[0]] = self._data_buf
                dbs = np.zeros(
                    (self._sigs_buf.shape[0] + pad, self._sigs_buf.shape[1]),
                    dtype=np.uint32,
                )
                dbs[: self._sigs_buf.shape[0]] = self._sigs_buf
                self._sweep_dev = (jnp.asarray(db), jnp.asarray(dbs))
        return self._sweep_dev

    def _sweep_q(self, rows: np.ndarray):
        """(q, q_sig) for a whole sweep — one gather, not one per chunk.
        Single-device gathers index the padded sweep operands (row
        indices are < n, so values are identical) instead of forcing a
        second, unpadded device copy into the cache."""
        if self.mesh is not None:
            return jnp.asarray(self._data[rows]), jnp.asarray(self._sigs[rows])
        db, dbs = self._sweep_db()
        ridx = jnp.asarray(rows)
        return db[ridx], dbs[ridx]

    def _sweep_kw(self):
        return dict(
            chunk=self.chunk,
            chunks_per_launch=self.chunks_per_launch,
            q_tile=self.q_tile,
            db_tile=self.db_tile,
            interpret=self.interpret,
            donate=self.donate,
        )

    def _sweep_hits(self, rows: np.ndarray, eps: float) -> np.ndarray:
        _, bitmap = self._sweep_hits_packed(rows, eps)
        from ..core.range_query import unpack_bitmap

        return unpack_bitmap(bitmap, self._data.shape[0])

    def _sweep_hits_packed(self, rows: np.ndarray, eps: float):
        _faults.maybe_fail(self._launch_site, op="hits")
        t_lo, t_hi = self.band(eps)
        q, q_sig = self._sweep_q(rows)
        n = self._data.shape[0]
        if self.mesh is not None:
            return sweep_bitmap(
                q, q_sig, self._db_plane, self._sig_plane, n, eps, t_lo, t_hi,
                mesh=self.mesh, axes=self._plan.axes, depth=self.pipeline_depth,
                **self._sweep_kw(),
            )
        db, dbs = self._sweep_db()
        return sweep_bitmap(q, q_sig, db, dbs, n, eps, t_lo, t_hi, **self._sweep_kw())

    def query_bitmap_device(self, rows: np.ndarray, eps: float):
        """Packed adjacency slab for ``rows`` as **device arrays, no
        host sync** — the feed for the one-launch cluster pass.

        Returns ``(slab, plan)`` from
        :func:`repro.index.sweep.sweep_bitmap_device`: the slab is
        ``(plan.nq_padded, W)`` uint32 over the capacity-padded column
        space with all bits past ``n_points`` cleared; under a mesh its
        words stay sharded on the index plane.  Only meaningful when
        ``packs_natively`` — host callers keep ``query_hits_packed``.
        """
        t_lo, t_hi = self.band(eps)
        q, q_sig = self._sweep_q(rows)
        n = self._data.shape[0]
        if self.mesh is not None:
            return sweep_bitmap_device(
                q, q_sig, self._db_plane, self._sig_plane, n, eps, t_lo, t_hi,
                mesh=self.mesh, axes=self._plan.axes, depth=self.pipeline_depth,
                **self._sweep_kw(),
            )
        db, dbs = self._sweep_db()
        return sweep_bitmap_device(
            q, q_sig, db, dbs, n, eps, t_lo, t_hi, **self._sweep_kw()
        )

    def _sweep_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        _faults.maybe_fail(self._launch_site, op="counts")
        t_lo, t_hi = self.band(eps)
        q, q_sig = self._sweep_q(rows)
        n = self._data.shape[0]
        if self.mesh is not None:
            return sweep_counts(
                q, q_sig, self._db_plane, self._sig_plane, n, eps, t_lo, t_hi,
                mesh=self.mesh, axes=self._plan.axes, depth=self.pipeline_depth,
                **self._sweep_kw(),
            )
        db, dbs = self._sweep_db()
        return sweep_counts(q, q_sig, db, dbs, n, eps, t_lo, t_hi, **self._sweep_kw())

    def _q_block(self, rows: np.ndarray):
        """(q, q_sig) jnp arrays for one row chunk.  Under ``mesh=`` the
        gather runs on the host copies — queries are tiny and the device
        database is row-sharded, so a device gather would be a scattered
        collective for no benefit."""
        if self.mesh is not None:
            return jnp.asarray(self._data[rows]), jnp.asarray(self._sigs[rows])
        ridx = jnp.asarray(rows)
        return self._device_data()[ridx], self._device_sigs()[ridx]

    def _device_hits(self, q, q_sig, db, db_sig, nd: int, eps: float) -> np.ndarray:
        """Boolean hits for one query block through
        ``hamming_filter_bitmap`` against a pre-gathered (db, db_sig)
        column side."""
        from ..core.range_query import unpack_bitmap

        _faults.maybe_fail("chunk.launch", op="hits")
        t_lo, t_hi = self.band(eps)
        _, bitmap = hamming_filter_bitmap(
            q, db, q_sig, db_sig, eps, t_hi, t_lo=t_lo,
            q_tile=self.q_tile, db_tile=self.db_tile, interpret=self.interpret,
        )
        return unpack_bitmap(np.asarray(bitmap), nd)

    def _device_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        _faults.maybe_fail("chunk.launch", op="counts")
        t_lo, t_hi = self.band(eps)
        q, q_sig = self._q_block(rows)
        counts = hamming_filter_count(
            q, self._device_data(), q_sig, self._device_sigs(),
            eps, t_hi, t_lo=t_lo,
            q_tile=self.q_tile, db_tile=self.db_tile, interpret=self.interpret,
        )
        if self._dev_pad:
            # the capacity tail past n is zero rows with zero signature
            # words — the exact shape the kernel wrappers' padded-row
            # correction models, applied here for the append slack
            counts = counts - _pad_col_hits(q_sig, eps, t_lo, t_hi, self._dev_pad)
        return np.asarray(counts).astype(np.int64)

    # -- sharded evaluation (the index plane) ------------------------------
    def _plane_hits(self, rows: np.ndarray, eps: float) -> np.ndarray:
        """One row chunk through the shard_map'd tile: only the gathered
        per-shard bitmap words come back (the plane pad rows occupy the
        trailing bits, so unpacking the true n drops them)."""
        from ..core.range_query import unpack_bitmap
        from ..distributed.index_plane import sharded_hamming_bitmap

        _faults.maybe_fail("plane.launch", op="hits")
        t_lo, t_hi = self.band(eps)
        q, q_sig = self._q_block(rows)
        _, bitmap = sharded_hamming_bitmap(
            q, self._db_plane, q_sig, self._sig_plane, eps, t_hi, t_lo=t_lo,
            mesh=self.mesh, axes=self._plan.axes,
            q_tile=self.q_tile, db_tile=self.db_tile, interpret=self.interpret,
        )
        return unpack_bitmap(np.asarray(bitmap), self._data.shape[0])

    def _plane_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        from ..distributed.index_plane import sharded_hamming_count

        _faults.maybe_fail("plane.launch", op="counts")
        t_lo, t_hi = self.band(eps)
        q, q_sig = self._q_block(rows)
        counts = sharded_hamming_count(
            q, self._db_plane, q_sig, self._sig_plane, eps, t_hi, t_lo=t_lo,
            mesh=self.mesh, axes=self._plan.axes,
            q_tile=self.q_tile, db_tile=self.db_tile, interpret=self.interpret,
        )
        if self._plan.n_pad:
            # the plane saw a pre-padded database (pad rows are zero
            # vectors with zero signatures), so subtract their hits with
            # the same correction the kernel wrappers apply to tile pads
            counts = counts - _pad_col_hits(q_sig, eps, t_lo, t_hi, self._plan.n_pad)
        return np.asarray(counts).astype(np.int64)

    # -- queries -----------------------------------------------------------
    def _padded_chunks(self, rows: np.ndarray):
        """Fixed-size index chunks (padded with row 0) so both the jit'd
        host sweep and the kernel compile once per (chunk, n) shape."""
        c = self.chunk
        for start in range(0, len(rows), c):
            sub = rows[start : start + c]
            padded = np.zeros(c, dtype=np.int64)
            padded[: len(sub)] = sub
            yield start, sub, padded

    def _host_query_hits(self, rows: np.ndarray, eps: float) -> np.ndarray:
        """The host oracle path (also the degraded-mode fallback)."""
        n = self._data.shape[0]
        hit = np.zeros((len(rows), n), dtype=bool)
        sigs = self._host_sigs()
        for start, sub, padded in self._padded_chunks(rows):
            ham = np.asarray(_hamming_sweep(sigs[padded], sigs))[: len(sub), :n]
            hit[start : start + len(sub)] = self._tile_hits(sub, None, ham, eps)
        return hit

    def _dev_query_hits(self, rows: np.ndarray, eps: float) -> np.ndarray:
        if self.sweep:
            return self._sweep_hits(rows, eps)
        n = self._data.shape[0]
        hit = np.zeros((len(rows), n), dtype=bool)
        plane = self.mesh is not None
        for start, sub, padded in self._padded_chunks(rows):
            if plane:
                hit[start : start + len(sub)] = self._plane_hits(padded, eps)[
                    : len(sub)
                ]
                continue
            q, q_sig = self._q_block(padded)
            # nd=n truncates the capacity-pad columns off the bitmap
            hit[start : start + len(sub)] = self._device_hits(
                q, q_sig, self._device_data(), self._device_sigs(), n, eps
            )[: len(sub)]
        return hit

    def query_hits(self, rows: np.ndarray, eps: float) -> np.ndarray:
        assert self._data is not None, "call fit() first"
        rows = np.asarray(rows, dtype=np.int64)
        if self.use_device:
            return self._guard_device(
                "hits",
                lambda: self._dev_query_hits(rows, eps),
                lambda: self._host_query_hits(rows, eps),
            )
        return self._host_query_hits(rows, eps)

    @property
    def packs_natively(self) -> bool:
        return self.use_device and self.sweep

    def _host_query_hits_packed(self, rows: np.ndarray, eps: float):
        from ..core.range_query import pack_bitmap

        hit = self._host_query_hits(rows, eps)
        return hit.sum(axis=1, dtype=np.int64), pack_bitmap(hit)

    def query_hits_packed(self, rows: np.ndarray, eps: float):
        """(counts, packed bitmap) — the sweep engine's native output;
        streaming ingest stores/replays adjacency packed, so this skips
        an unpack→repack round-trip per batch.  Falls back to packing
        the boolean hits on the non-sweep paths."""
        assert self._data is not None, "call fit() first"
        rows = np.asarray(rows, dtype=np.int64)
        if self.packs_natively:
            return self._guard_device(
                "packed",
                lambda: self._sweep_hits_packed(rows, eps),
                lambda: self._host_query_hits_packed(rows, eps),
            )
        return super().query_hits_packed(rows, eps)

    def _dev_query_hits_subset(
        self, rows: np.ndarray, cols: np.ndarray, eps: float
    ) -> np.ndarray:
        # gather the column side once, not per row chunk; subset
        # queries stay single-device even under mesh= (the gathered
        # column side is small, the row-sharded plane only pays off
        # on whole-database sweeps)
        if self.mesh is not None:
            db, db_sig = jnp.asarray(self._data[cols]), jnp.asarray(self._sigs[cols])
        elif self.sweep:
            sdb, sdbs = self._sweep_db()
            cidx = jnp.asarray(cols)
            db, db_sig = sdb[cidx], sdbs[cidx]
        else:
            cidx = jnp.asarray(cols)
            db, db_sig = self._device_data()[cidx], self._device_sigs()[cidx]
        if self.sweep:
            from ..core.range_query import unpack_bitmap

            _faults.maybe_fail(self._launch_site, op="subset")
            t_lo, t_hi = self.band(eps)
            q, q_sig = self._sweep_q(rows)
            _, bitmap = sweep_bitmap(
                q, q_sig, db, db_sig, len(cols), eps, t_lo, t_hi,
                **self._sweep_kw(),
            )
            return unpack_bitmap(bitmap, len(cols))
        hit = np.zeros((len(rows), len(cols)), dtype=bool)
        for start, sub, padded in self._padded_chunks(rows):
            q, q_sig = self._q_block(padded)
            hit[start : start + len(sub)] = self._device_hits(
                q, q_sig, db, db_sig, len(cols), eps
            )[: len(sub)]
        return hit

    def _host_query_hits_subset(
        self, rows: np.ndarray, cols: np.ndarray, eps: float
    ) -> np.ndarray:
        # tile both axes: the host popcount materializes a
        # (rows, cols, words) XOR tensor, so keep tiles bounded even
        # when cols is a large core set
        hit = np.zeros((len(rows), len(cols)), dtype=bool)
        col_tile = 2048
        for rs in range(0, len(rows), self.chunk):
            rsub = rows[rs : rs + self.chunk]
            for cs in range(0, len(cols), col_tile):
                csub = cols[cs : cs + col_tile]
                ham = hamming_numpy(self._sigs[rsub], self._sigs[csub])
                hit[rs : rs + len(rsub), cs : cs + len(csub)] = self._tile_hits(
                    rsub, csub, ham, eps
                )
        return hit

    def query_hits_subset(
        self, rows: np.ndarray, cols: np.ndarray, eps: float
    ) -> np.ndarray:
        assert self._data is not None and self._sigs is not None
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        if self.use_device:
            return self._guard_device(
                "subset",
                lambda: self._dev_query_hits_subset(rows, cols, eps),
                lambda: self._host_query_hits_subset(rows, cols, eps),
            )
        return self._host_query_hits_subset(rows, cols, eps)

    def query_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        """Counts fast-path: never materializes a (block, n) hit matrix.

        On device the fused count kernel (no bitmap output) runs per
        chunk; on host each chunk reduces its accepts and scatter-adds
        its verified band pairs directly into the counts vector.
        """
        assert self._data is not None, "call fit() first"
        rows = np.asarray(rows, dtype=np.int64)
        if self.use_device:
            return self._guard_device(
                "counts",
                lambda: self._dev_query_counts(rows, eps),
                lambda: self._host_query_counts(rows, eps),
            )
        return self._host_query_counts(rows, eps)

    def _dev_query_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        if self.sweep:
            return self._sweep_counts(rows, eps)
        counts = np.zeros(len(rows), dtype=np.int64)
        plane = self.mesh is not None
        for start, sub, padded in self._padded_chunks(rows):
            if plane:
                counts[start : start + len(sub)] = self._plane_counts(padded, eps)[
                    : len(sub)
                ]
                continue
            counts[start : start + len(sub)] = self._device_counts(padded, eps)[
                : len(sub)
            ]
        return counts

    def _host_query_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        counts = np.zeros(len(rows), dtype=np.int64)
        sigs = self._host_sigs()
        for start, sub, padded in self._padded_chunks(rows):
            ham = np.asarray(_hamming_sweep(sigs[padded], sigs))[
                : len(sub), : self._data.shape[0]
            ]
            counts[start : start + len(sub)] = self._tile_counts(sub, ham, eps)
        return counts


# ---------------------------------------------------------------------------
# margin auto-tune: price candidate Hamming bands with the kernel's
# occupancy stats (or the host Hamming sweep) and pick the
# widest band — best recall, ~Phi(margin) — the verify budget affords
# ---------------------------------------------------------------------------


def suggest_margin(
    backend: RandomProjectionBackend,
    eps: float,
    rows: Optional[np.ndarray] = None,
    *,
    margins=(4.0, 3.5, 3.0, 2.5, 2.0, 1.5, 1.0),
    max_band_frac: Optional[float] = None,
    report: bool = False,
):
    """Suggest an ``index_margin`` for a fitted backend at one eps.

    Recall of the dual-threshold contract is set by the band's upper
    edge (misses are pairs beyond ``t_hi``, probability ~1 - Phi(margin))
    while its *cost* is the exact-verify work on band pairs — so the
    auto-tune question is "what is the widest band whose band-pair
    fraction stays under ``max_band_frac``" (default: the backend's own
    saturation threshold).  Occupancy is measured on a deterministic row
    sample: through ``hamming_filter_count(..., return_stats=True)``
    (the kernel's [accept, band, reject] occupancy counters) when the
    backend evaluates on device, through one host Hamming sweep
    otherwise.  Both thresholds are traced in the kernel, so sweeping
    candidate margins re-runs nothing but the popcount pass.

    Returns the chosen margin, or ``(margin, rows)`` with the per-margin
    ``{margin, t_lo, t_hi, band_frac, accept_frac}`` table when
    ``report=True``.  If no candidate fits the budget the narrowest
    (cheapest) one is returned.
    """
    assert backend._data is not None, "call fit() first"
    if max_band_frac is None:
        max_band_frac = backend.max_band_frac
    n = backend._data.shape[0]
    if rows is None:
        rows = np.unique(np.linspace(0, n - 1, min(n, 4 * backend.q_tile)).astype(np.int64))
    rows = np.asarray(rows, dtype=np.int64)

    dev = backend.use_device
    if dev:
        q = jnp.asarray(backend._data[rows])
        q_sig = jnp.asarray(backend._sigs[rows])
        # occupancy stats must price real pairs only, never streaming
        # append slack — reuse the cached device buffers when they are
        # exactly the fitted rows, upload exact-shaped copies otherwise
        if backend._dev_pad:
            db, db_sig = jnp.asarray(backend._data), jnp.asarray(backend._sigs)
        else:
            db, db_sig = backend._device_data(), backend._device_sigs()
        # the kernel's counters run on the *padded* tile grid; pad rows
        # and cols are zero-signature pairs whose Hamming distance to a
        # real row is that row's signature popcount — classify those
        # popcounts per band and subtract, so the table prices real
        # pairs only and agrees with the host table on any n
        zero = np.zeros((1, backend._sigs.shape[1]), np.uint32)
        q_pop = hamming_numpy(backend._sigs[rows], zero)[:, 0].astype(np.int64)
        db_pop = hamming_numpy(backend._sigs, zero)[:, 0].astype(np.int64)
        q_pad = (-len(rows)) % backend.q_tile
        db_pad = (-n) % backend.db_tile
    else:
        ham = hamming_numpy(backend._sigs[rows], backend._sigs)

    table = []
    for m in sorted(margins, reverse=True):
        t_lo, t_hi = hamming_band(eps, backend.n_bits, m)
        if backend.verify == "full":
            t_lo = -1
        if dev:
            _, stats = hamming_filter_count(
                q, db, q_sig, db_sig, eps, t_hi, t_lo=t_lo,
                q_tile=backend.q_tile, db_tile=backend.db_tile,
                interpret=backend.interpret, return_stats=True,
            )
            stats = np.asarray(stats, dtype=np.int64).reshape(-1, 3).sum(axis=0)
            acc, bnd = int(stats[0]), int(stats[1])
            if q_pad or db_pad:
                # real q rows vs zero-padded db cols
                acc -= db_pad * int((q_pop <= t_lo).sum())
                bnd -= db_pad * int(((q_pop > t_lo) & (q_pop <= t_hi)).sum())
                # zero-padded q rows vs real db rows
                acc -= q_pad * int((db_pop <= t_lo).sum())
                bnd -= q_pad * int(((db_pop > t_lo) & (db_pop <= t_hi)).sum())
                # pad-vs-pad corner: Hamming distance 0
                if t_lo >= 0:
                    acc -= q_pad * db_pad
                else:
                    bnd -= q_pad * db_pad
            total = len(rows) * n
            acc_frac, band_frac = acc / total, bnd / total
        else:
            accept = ham <= t_lo
            band = (ham <= t_hi) & ~accept
            acc_frac = accept.mean()
            band_frac = band.mean()
        table.append(
            dict(margin=m, t_lo=t_lo, t_hi=t_hi,
                 band_frac=float(band_frac), accept_frac=float(acc_frac))
        )

    fits = [r for r in table if r["band_frac"] <= max_band_frac]
    chosen = fits[0]["margin"] if fits else table[-1]["margin"]
    chosen_row = next(r for r in table if r["margin"] == chosen)
    _feed_occupancy(chosen_row, len(rows), n)
    return (chosen, table) if report else chosen


def _feed_occupancy(row: dict, nq: int, n: int) -> None:
    """Write one occupancy measurement into the index.band.* metrics:
    raw pair counts (counters, accumulated over measurements) and the
    latest fractions (gauges)."""
    total = nq * n
    acc = int(round(row["accept_frac"] * total))
    bnd = int(round(row["band_frac"] * total))
    _metrics.counter("index.band.accept").inc(acc)
    _metrics.counter("index.band.band").inc(bnd)
    _metrics.counter("index.band.reject").inc(total - acc - bnd)
    _metrics.gauge("index.band.accept_frac").set(row["accept_frac"])
    _metrics.gauge("index.band.band_frac").set(row["band_frac"])
    _metrics.gauge("index.band.reject_frac").set(
        1.0 - row["accept_frac"] - row["band_frac"]
    )


def record_occupancy(
    backend: RandomProjectionBackend, eps: float, rows: Optional[np.ndarray] = None
) -> dict:
    """Measure the dual-threshold occupancy of the backend's own band at
    one eps and feed the ``index.band.*`` metrics.

    Rides the :func:`suggest_margin` machinery with a single candidate
    (the backend's configured margin), so the device path uses the
    kernel's ``return_stats=`` [accept, band, reject] occupancy counters
    with the exact pad-row corrections — on any n, device and host
    measurements agree (the ``tests/test_obs.py`` parity assert).
    Returns the ``{margin, t_lo, t_hi, band_frac, accept_frac}`` row.
    """
    n = backend._data.shape[0]
    if rows is None:
        rows = np.unique(
            np.linspace(0, n - 1, min(n, 4 * backend.q_tile)).astype(np.int64)
        )
    _, table = suggest_margin(
        backend, eps, rows, margins=(backend.margin,),
        max_band_frac=backend.max_band_frac, report=True,
    )
    return table[0]
