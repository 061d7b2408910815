"""Reduce a JAX profiler trace of the measured window to device numbers.

``load_events`` reads the ``.xplane.pb`` the profiler wrote and keeps two
kinds of events, on the trace's one clock:

* device ops: every event on the ``XLA Ops`` line of a ``/device:TPU:<i>``
  plane (one plane per chip);
* host spans: the ``TraceAnnotation`` events the benchmark and the
  program's spans put on the host plane (names starting ``bench.`` or
  ``laf.``).

``reduce_events`` turns them into what the per-layer readers need:

* ``window_s``: first ``bench.call`` start to last ``bench.call`` end;
* ``busy_s``: per chip, the union of its op intervals inside the window,
  averaged over the chips used; ``idle`` is ``1 - busy_s / window_s``;
* ``kernels``: device seconds per kernel of ``bench/kernels/<kernel>.py``,
  matched by the HLO op names each module lists in ``EVENTS`` (a Pallas
  kernel's op is its ``tpu_custom_call``, named after the wrapper that
  calls ``pallas_call``, with an instance suffix: ``col_reduce_pallas.1``);
* ``kernels_within``: the same, counting only the events that lie inside
  an op the module names in ``WITHIN`` (the call site whose work its
  byte count describes: the slab sweep's filter ops run inside a
  ``while`` loop, its other builds do not); a module without ``WITHIN``
  counts every event;
* ``breakdown``: the ten device ops that took most time, and the idle
  time inside the window by the innermost host span open at each moment.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
import re
from pathlib import Path

HOST_PREFIXES = ("bench.", "laf.")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

__all__ = ["load_events", "reduce_events", "reduce_dir", "kernel_sites"]


def load_events(path) -> list:
    """Device ops and host spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIXES):
                    continue
                # a device op's event name is its HLO text; keep the op name
                name = ev.name.split(" = ", 1)[0].lstrip("%") if device else ev.name
                out.append({"plane": plane.name, "name": name,
                            "t0": ev.start_ns * 1e-9, "dur": ev.duration_ns * 1e-9})
    return out


def _base(op: str) -> str:
    """HLO op name without its instance suffix: ``col_reduce_pallas.1``
    -> ``col_reduce_pallas``."""
    return re.sub(r"\.\d+$", "", op)


def kernel_sites(bench=None) -> dict:
    """``{kernel module name: (op names, enclosing op names)}`` from
    ``bench/kernels/*.py`` (``EVENTS``, and ``WITHIN`` where it is set)."""
    import registry

    bench = registry.BENCH if bench is None else Path(bench)
    out = {}
    for p in sorted((bench / "kernels").glob("*.py")):
        mod = registry.load_module("kernels", p.stem, bench)
        out[p.stem] = (tuple(mod.EVENTS), tuple(getattr(mod, "WITHIN", ())))
    return out


def _inside(e, merged) -> bool:
    """Whether event ``e`` lies inside one of the merged intervals."""
    i = bisect.bisect_right(merged, [e["t0"], math.inf]) - 1
    return i >= 0 and e["t0"] + e["dur"] <= merged[i][1]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _innermost(host, t):
    """Name of the latest-starting host span open at time t."""
    best = None
    for h in host:
        if h["t0"] <= t < h["t0"] + h["dur"] and (best is None or h["t0"] >= best["t0"]):
            best = h
    return best["name"] if best else "outside any span"


def reduce_events(events: list, chips: int, kernels: dict) -> dict:
    """``kernels``: ``{name: (op names, enclosing op names)}``, as
    ``kernel_sites`` gives it."""
    calls = [e for e in events if e["name"] == "bench.call"]
    if not calls:
        raise ValueError("the trace holds no bench.call span")
    w0 = min(e["t0"] for e in calls)
    w1 = max(e["t0"] + e["dur"] for e in calls)
    host = [e for e in events if not e["plane"].startswith(DEVICE_PREFIX)]
    planes = sorted({e["plane"] for e in events if e["plane"].startswith(DEVICE_PREFIX)},
                    key=lambda p: int(p[len(DEVICE_PREFIX):].split()[0]))[:chips]
    busy, idle = [], {}
    op_s: dict = {}
    within = dict.fromkeys(kernels, 0.0)
    for i, plane in enumerate(planes):
        ops = [e for e in events if e["plane"] == plane]
        ivs = [(max(e["t0"], w0), min(e["t0"] + e["dur"], w1)) for e in ops]
        merged = _union([(a, b) for a, b in ivs if b > a])
        busy.append(sum(b - a for a, b in merged))
        inside = [e for e in ops if w0 <= e["t0"] < w1]
        for e in inside:
            op_s[e["name"]] = op_s.get(e["name"], 0.0) + e["dur"]
        for k, (pats, encl) in kernels.items():
            spans = _union((e["t0"], e["t0"] + e["dur"]) for e in ops
                           if _base(e["name"]) in encl)
            within[k] += sum(e["dur"] for e in inside if _base(e["name"]) in pats
                             and (not encl or _inside(e, spans)))
        if i == 0:  # idle gaps of the first chip, named by the host
            edges = [w0] + [t for iv in merged for t in iv] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                # split the gap where host spans open or close, and give
                # each piece to the innermost span open over it
                cuts = sorted({a, b} | {t for h in host for t in (h["t0"], h["t0"] + h["dur"])
                                        if a < t < b})
                for c0, c1 in zip(cuts, cuts[1:]):
                    name = _innermost(host, 0.5 * (c0 + c1))
                    idle[name] = idle.get(name, 0.0) + (c1 - c0)
    ksec = {k: sum(s for name, s in op_s.items() if _base(name) in pats)
            for k, (pats, _) in kernels.items()}
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "chips_traced": len(planes),
        "kernels": ksec,
        "kernels_within": within,
        "kernel_found": {k: any(_base(name) in pats for name in op_s)
                         for k, (pats, _) in kernels.items()},
        "breakdown": {"device_ops": [list(kv) for kv in top],
                      "idle_gaps": [list(kv) for kv in gaps]},
    }


def reduce_dir(trace_dir, chips: int) -> dict:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return reduce_events(load_events(found[0]), chips, kernel_sites())
