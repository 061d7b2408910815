"""Range-query backend protocol + registry.

Every clustering engine in ``repro.core`` consumes eps-neighborhoods
through three primitives — boolean hit rows against the whole database,
hit rows against a column subset, and neighbor counts.  A
``RangeBackend`` supplies those primitives for one database (``fit``
binds the data; queries are rows *of that database*, which is exactly
how DBSCAN uses them).  Backends are interchangeable:

* ``exact``             — the blocked-matmul oracle (bit-for-bit the
                          engine behaviour before this subsystem).
* ``random_projection`` — signed-random-projection ANN prefilter with
                          exact verification (sDBSCAN-style).

Engines accept ``backend=`` as either a registry name, a
``(name, kwargs)``-style constructed instance, or an already-fit
instance; ``as_fitted`` normalizes all three.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Type, Union

import numpy as np

__all__ = [
    "RangeBackend", "RescueCarry", "fold_subset_hits", "BACKENDS",
    "register_backend", "make_backend", "as_fitted",
]

NO_LABEL_MIN = np.iinfo(np.int32).max  # ``cmin`` of a column with no member yet


class RescueCarry(NamedTuple):
    """Running reduction of the rescue's subset hits, one entry per
    rescued column.

    ``count`` is |𝓔| so far, over every executed row, noise included;
    ``cmin``/``cmax`` are the least and greatest pre-merge label among
    the hit rows labelled >= 0 (``NO_LABEL_MIN`` and -1 when none);
    ``multi`` is set once those labels hold 3 or more distinct values;
    ``visits`` sums, over the folded blocks, the columns each block hit.
    A column with ``multi`` clear has exactly the clusters ``cmin`` and
    ``cmax``.  Every field is int32 (``multi`` bool), on the host or the
    device alike.
    """

    count: Any
    cmin: Any
    cmax: Any
    multi: Any
    visits: Any

    @classmethod
    def empty(cls, width: int, xp=np) -> "RescueCarry":
        """The carry of no rows: the identity of :meth:`merge`."""
        return cls(
            xp.zeros(width, xp.int32),
            xp.full(width, NO_LABEL_MIN, xp.int32),
            xp.full(width, -1, xp.int32),
            xp.zeros(width, bool),
            xp.zeros((), xp.int32),
        )

    def merge(self, other: "RescueCarry", xp=np) -> "RescueCarry":
        """The carry of both carries' rows together.  Exact with constant
        state: a column holds 3 or more labels iff one side does, or one
        side's extreme lies strictly inside the joint range (a label stops
        being an extreme only when a more extreme one arrives, so it is
        caught at that step).  The sentinels never lie inside a range."""
        lo = xp.minimum(self.cmin, other.cmin)
        hi = xp.maximum(self.cmax, other.cmax)
        multi = self.multi | other.multi
        for v in (self.cmin, self.cmax, other.cmin, other.cmax):
            multi = multi | ((v > lo) & (v < hi))
        return RescueCarry(self.count + other.count, lo, hi, multi,
                           self.visits + other.visits)


def fold_subset_hits(carry, hit: np.ndarray, row_labels: np.ndarray) -> RescueCarry:
    """``carry`` (``None``: no rows yet) merged with one block's boolean
    ``(rows, cols)`` subset hits, whose rows carry ``row_labels``."""
    lab = np.asarray(row_labels).astype(np.int32)[:, None]
    member = hit & (lab >= 0)
    lo = np.where(member, lab, NO_LABEL_MIN).min(axis=0, initial=NO_LABEL_MIN)
    hi = np.where(member, lab, -1).max(axis=0, initial=-1)
    count = hit.sum(axis=0, dtype=np.int32)
    block = RescueCarry(
        count, lo, hi, (member & (lab > lo) & (lab < hi)).any(axis=0),
        np.int32(np.count_nonzero(count)),
    )
    if carry is None:
        return block
    return RescueCarry(*(np.asarray(v) for v in carry)).merge(block)


class RangeBackend:
    """Interface + shared glue for eps-range query backends.

    Subclasses must implement ``fit`` and ``query_hits``; the remaining
    primitives have correct (if not always optimal) defaults on top.
    ``fit`` must be idempotent when handed the same array object so
    engines can re-enter with a shared backend without paying a rebuild.
    """

    name: str = "base"

    def fit(self, data: np.ndarray) -> "RangeBackend":
        raise NotImplementedError

    def partial_fit(self, rows: np.ndarray) -> "RangeBackend":
        """Append ``rows`` to the fitted database (streaming ingest).

        Row indices of the appended points are ``n_points_before ..
        n_points_after - 1`` — existing indices never move, which is the
        invariant the streaming cluster state builds on.  The base
        implementation is the correct-but-quadratic fallback
        (concatenate + refit); incremental backends override it with a
        real append (see ``RandomProjectionBackend.partial_fit``).
        Calling it on an unfitted backend is the same as ``fit``.
        """
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        data = getattr(self, "_data", None)
        if data is None:
            return self.fit(rows)
        return self.fit(np.concatenate([data, rows], axis=0))

    # -- primitives --------------------------------------------------------
    def query_hits(self, rows: np.ndarray, eps: float) -> np.ndarray:
        """Boolean (len(rows), n) adjacency of db[rows] against the db."""
        raise NotImplementedError

    def query_hits_subset(
        self, rows: np.ndarray, cols: np.ndarray, eps: float
    ) -> np.ndarray:
        """Boolean (len(rows), len(cols)) adjacency against db[cols]."""
        return self.query_hits(rows, eps)[:, cols]

    def rescue_reduce(
        self,
        rows: np.ndarray,
        cols: np.ndarray,
        labels: np.ndarray,
        eps: float,
        carry: "RescueCarry | None" = None,
    ) -> RescueCarry:
        """Fold the hits of one block of executed ``rows`` on the rescued
        ``cols`` into ``carry`` (``None`` starts a rescue); ``labels`` is
        the pre-merge label of every database point (-1 noise).

        This host fold reduces ``query_hits_subset``'s boolean hits; a
        backend whose sweep packs natively reduces on the device and
        fetches nothing until the caller reads the carry."""
        return fold_subset_hits(
            carry, self.query_hits_subset(rows, cols, eps), np.asarray(labels)[rows]
        )

    @property
    def packs_natively(self) -> bool:
        """True when ``query_hits_packed`` produces packed words without
        materializing (and re-packing) the boolean hit matrix — callers
        that need *both* forms (streaming ingest) branch on this so the
        host paths never pay an unpack→repack round-trip."""
        return False

    def query_hits_packed(self, rows: np.ndarray, eps: float):
        """(counts int64 (len(rows),), packed uint32 bitmap of the hit
        rows in ``repro.core.range_query.pack_bitmap`` bit order).

        Streaming ingest stores and replays adjacency packed; backends
        whose evaluator produces packed words natively (the sweep
        engine) override this to skip the unpack→repack round-trip.
        """
        from ..core.range_query import pack_bitmap

        hit = self.query_hits(rows, eps)
        return hit.sum(axis=1, dtype=np.int64), pack_bitmap(hit)

    def query_counts(self, rows: np.ndarray, eps: float) -> np.ndarray:
        """Neighbor counts |N_eps(db[i])| for i in rows (int64).

        Chunked over rows so the boolean hit matrix never exceeds
        (block, n) even when asked for counts of the whole database.
        """
        rows = np.asarray(rows)
        block = getattr(self, "block_size", 2048)
        counts = np.zeros(len(rows), dtype=np.int64)
        for start in range(0, len(rows), block):
            sub = rows[start : start + block]
            counts[start : start + len(sub)] = self.query_hits(sub, eps).sum(axis=1)
        return counts

    # -- durability --------------------------------------------------------
    def state_export(self) -> Dict[str, np.ndarray]:
        """Snapshot the fitted state as a flat dict of host arrays.

        The contract is **capacity-faithful**: backends that keep
        capacity-padded append buffers (amortized-doubling slabs whose
        shapes key the jit compile-signature lattice) export the *full*
        buffers plus the live row count, so ``state_import`` on a fresh
        instance reproduces identical operand shapes and a restored
        replica re-enters the pre-crash compile cache — restore is
        recompile-free by construction, not by luck.
        """
        raise NotImplementedError(f"{self.name!r} backend does not export state")

    def state_import(self, state: Dict[str, np.ndarray]) -> "RangeBackend":
        """Rebuild fitted state from a ``state_export`` dict (see its
        capacity contract).  Returns self."""
        raise NotImplementedError(f"{self.name!r} backend does not import state")

    # -- conveniences ------------------------------------------------------
    def neighbor_lists(self, eps: float, block_size: int = 2048) -> List[np.ndarray]:
        """Per-point sorted neighbor index arrays for the whole database."""
        n = self.n_points
        out: List[np.ndarray] = []
        for start in range(0, n, block_size):
            rows = np.arange(start, min(start + block_size, n))
            hit = self.query_hits(rows, eps)
            for i in range(hit.shape[0]):
                out.append(np.nonzero(hit[i])[0])
        return out

    @property
    def n_points(self) -> int:
        return self._data.shape[0]  # type: ignore[attr-defined]

    @property
    def data(self) -> np.ndarray:
        """The fitted database rows (read-only view; row i is query row i)."""
        assert getattr(self, "_data", None) is not None, "call fit() first"
        return self._data  # type: ignore[attr-defined]


BACKENDS: Dict[str, Type[RangeBackend]] = {}


def register_backend(cls: Type[RangeBackend]) -> Type[RangeBackend]:
    BACKENDS[cls.name] = cls
    return cls


def make_backend(spec: Union[str, RangeBackend], **kwargs) -> RangeBackend:
    """Normalize a backend spec (registry name or instance) to an instance."""
    if isinstance(spec, RangeBackend):
        return spec
    if spec not in BACKENDS:
        # registration happens at module import; the heavyweight backends
        # are imported lazily (see the package __init__), so pull in any
        # sibling module named after the backend before giving up
        import importlib

        mod_name = f"{__package__}.{spec}"
        try:
            importlib.import_module(mod_name)
        except ModuleNotFoundError as e:
            # only "no such sibling module" means unknown backend; a
            # missing dependency *inside* an existing module must surface
            if e.name != mod_name:
                raise
        except (TypeError, ValueError):
            pass  # not a module-path-shaped spec: fall through to KeyError
    if spec not in BACKENDS:
        raise ValueError(
            f"unknown range backend {spec!r}; registered backends: {sorted(BACKENDS)}"
        )
    return BACKENDS[spec](**kwargs)


def as_fitted(spec: Union[str, RangeBackend], data: np.ndarray, **kwargs) -> RangeBackend:
    """Backend instance bound to ``data`` (no-op refit on the same array).

    ``kwargs`` configure construction when ``spec`` is a registry name;
    an already-constructed instance keeps its own configuration.
    """
    return make_backend(spec, **kwargs).fit(data)
