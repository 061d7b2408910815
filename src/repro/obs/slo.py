"""Declarative SLO thresholds over the metrics registry.

The observability stack records *what happened* (counters, histograms,
spans, device telemetry); this module says *what is acceptable* and
turns the gap into an alert.  An :class:`SLO` is one declarative rule —
``metric op threshold`` — where ``metric`` names a registry instrument
(``"serve.assign.latency_s:p99"`` selects a histogram summary field,
plain names read counters/gauges) or a caller-supplied derived value
(skip rate, ARI, device_get count per run).

Evaluation never raises on missing data: a metric with no observations
yields ``ok=None`` ("no data"), so SLOs can be declared up front and
only start firing once the path they guard actually runs.  Violations
are emitted as structured, rate-limited log lines
(``slo.violation name=... value=... threshold=...``) — grep-stable for
CI and quiet enough for a serving loop to call per batch.

``serve.assign`` evaluates :data:`SERVE_SLOS` every
:data:`EVAL_EVERY_CALLS` calls; ``stream.partial_fit`` evaluates
:data:`INGEST_SLOS` per batch with the batch's derived skip rate.  The
default thresholds are intentionally loose sanity floors — deployment
configs replace them via :func:`set_slos`.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from . import metrics as _metrics
from .log import get_logger, rate_limited_warn

__all__ = [
    "SLO",
    "SLOResult",
    "SERVE_SLOS",
    "INGEST_SLOS",
    "CLUSTER_SLOS",
    "DEGRADED_SLOS",
    "EVAL_EVERY_CALLS",
    "set_slos",
    "resolve_metric",
    "evaluate",
    "check_and_alert",
]

_log = get_logger("obs.slo")

_OPS = {
    "<=": lambda v, t: v <= t,
    ">=": lambda v, t: v >= t,
    "<": lambda v, t: v < t,
    ">": lambda v, t: v > t,
    "==": lambda v, t: v == t,
}


@dataclass(frozen=True)
class SLO:
    """One declarative rule: ``metric op threshold``.

    ``metric`` is a registry name, optionally ``name:field`` to select
    one field of a histogram summary (p50/p95/p99/min/max/count/sum),
    or any key the caller passes via ``values=`` for derived quantities
    the registry does not hold (per-batch skip rate, run ARI).
    """

    name: str
    metric: str
    op: str
    threshold: float
    description: str = ""

    def __post_init__(self):
        if self.op not in _OPS:
            raise ValueError(f"unknown SLO op {self.op!r} (use one of {sorted(_OPS)})")


@dataclass(frozen=True)
class SLOResult:
    slo: SLO
    value: Optional[float]
    ok: Optional[bool]  # None = no data (metric absent / no observations)

    @property
    def violated(self) -> bool:
        return self.ok is False


# default rule sets — loose sanity floors, replaced per deployment via
# set_slos()
SERVE_SLOS: List[SLO] = [
    SLO(
        "serve-assign-p99", "serve.assign.latency_s:p99", "<=", 0.5,
        "p99 assign() wall seconds per call",
    ),
]
INGEST_SLOS: List[SLO] = [
    SLO(
        "ingest-skip-floor", "ingest.skip_rate", ">=", 0.0,
        "estimator fast-path fraction of the batch (derived per batch)",
    ),
]
CLUSTER_SLOS: List[SLO] = [
    SLO(
        "cluster-one-device-get", "cluster.device_get_per_run", "==", 1.0,
        "host syncs per device-resident cluster pass (derived per run)",
    ),
    SLO("cluster-ari", "cluster.ari", ">=", 0.99, "parity vs the host oracle"),
]

DEGRADED_SLOS: List[SLO] = [
    SLO(
        "stream-degraded", "stream.degraded.events", "<=", 0.0,
        "device query paths degraded to the host oracle (fault fallback)",
    ),
]

# serve evaluates its rules every N assign() calls — cheap enough to
# leave on in production, frequent enough to catch a latency regression
# within one traffic burst
EVAL_EVERY_CALLS = 64

_lock = threading.Lock()


def set_slos(kind: str, slos: Sequence[SLO]) -> None:
    """Replace a default rule set ("serve" | "ingest" | "cluster" |
    "degraded")."""
    target = {
        "serve": SERVE_SLOS,
        "ingest": INGEST_SLOS,
        "cluster": CLUSTER_SLOS,
        "degraded": DEGRADED_SLOS,
    }[kind]
    with _lock:
        target[:] = list(slos)


def resolve_metric(metric: str, values: Optional[Dict[str, float]] = None):
    """Current value of ``metric``: caller-supplied ``values`` win, then
    the registry (histograms via ``name:field``).  None = no data."""
    if values and metric in values:
        return float(values[metric])
    name, _, field = metric.partition(":")
    snap = _metrics.snapshot(prefix=name)
    v = snap.get(name)
    if v is None:
        return None
    if isinstance(v, dict):  # histogram summary
        if not v.get("count"):
            return None
        return float(v.get(field or "p99", 0.0))
    return float(v)


def evaluate(
    slos: Sequence[SLO], values: Optional[Dict[str, float]] = None
) -> List[SLOResult]:
    """Evaluate rules against ``values`` + the live registry."""
    out = []
    for s in slos:
        v = resolve_metric(s.metric, values)
        ok = None if v is None else _OPS[s.op](v, s.threshold)
        out.append(SLOResult(s, v, ok))
    return out


def check_and_alert(
    slos: Sequence[SLO],
    values: Optional[Dict[str, float]] = None,
    *,
    interval_s: float = 60.0,
) -> List[SLOResult]:
    """Evaluate and emit one rate-limited structured warning per
    violated rule (``slo.violation name=... value=... threshold=...``);
    every evaluation also bumps ``slo.evaluations`` /
    ``slo.violations`` counters so the SLO plane is itself observable.
    """
    results = evaluate(slos, values)
    _metrics.counter("slo.evaluations").inc(len(results))
    for r in results:
        if r.violated:
            _metrics.counter("slo.violations").inc()
            rate_limited_warn(
                _log, f"slo:{r.slo.name}", "slo.violation",
                interval_s=interval_s,
                name=r.slo.name, metric=r.slo.metric, value=r.value,
                op=r.slo.op, threshold=r.slo.threshold,
            )
    return results
