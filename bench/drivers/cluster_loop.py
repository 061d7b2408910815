"""Closed-loop batch clustering: one client, ``cluster_laf_dbscan`` calls
back to back on one corpus, each with a fresh random-projection index.

Set-up: the corpus and a disjoint RMI training draw (``bench/corpus.py``),
the RMI's weights trained by the benchmark's own code
(``bench/rmi_train.py``, targets counted against the corpus) and loaded
into the pipeline, and one warm-up call that compiles (or loads from
the compile cache) every program the window runs.  The window then starts calls until ``seconds`` have passed; each
call ends when its labels are on the host.  After the window the peak
device memory is read, the program's state is dropped, and the plain
reference (``bench/reference.py``) clusters the same corpus: every call
of the window is compared with it.
"""

from __future__ import annotations

import gc
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import corpus as _corpus  # noqa: E402
import reference as _reference  # noqa: E402
import rmi_train as _rmi_train  # noqa: E402

# kernels of the timed path; each must be built, and built compiled
MAIN_KERNELS = ("_filter_kernel", "_label_prop_kernel", "_col_reduce_kernel")


def watch_pallas_builds(builds: list):
    """Record (kernel name, interpret flag) of every Pallas kernel traced
    from here on; returns a function that undoes the patch."""
    from jax.experimental import pallas

    real = pallas.pallas_call

    def recording(kernel, *args, interpret=False, **kw):
        name = getattr(getattr(kernel, "func", kernel), "__name__", repr(kernel))
        builds.append((name, bool(interpret)))
        return real(kernel, *args, interpret=interpret, **kw)

    pallas.pallas_call = recording
    return lambda: setattr(pallas, "pallas_call", real)


def _counters(metrics) -> dict:
    return {k: v for k, v in metrics.snapshot().items() if isinstance(v, (int, float))}


def cluster_mismatch(a, b) -> int:
    """Clusters, of either partition (canonical labels, -1 noise), whose
    members are not exactly the members of one cluster of the other."""
    ka, kb = int(a.max()) + 1, int(b.max()) + 1
    both = (a >= 0) & (b >= 0)
    pair, count = np.unique(a[both] * np.int64(kb) + b[both], return_counts=True)
    size_a = np.bincount(a[a >= 0], minlength=ka)
    size_b = np.bincount(b[b >= 0], minlength=kb)
    ia, ib = pair // kb, pair % kb
    same = int(np.count_nonzero((count == size_a[ia]) & (count == size_b[ib])))
    return ka + kb - 2 * same


def member_mismatch(prog: dict, ref: dict) -> tuple[int, int]:
    """(points that disagree, points left aside).  A point disagrees when
    the answers differ on its skip decision or core flag, or, away from
    the clusters of either answer that hold such a point (left aside:
    which clusters a disagreement joins depends on the order of the
    points, through the border rule and Algorithm 3), when it is noise in
    one answer only or shares its cluster with a different set of points
    in the two."""
    a, b = prog["labels"], ref["labels"]
    off = (prog["exec"] != ref["exec"]) | (prog["core"] != ref["core"])
    keep = ~off & ~np.isin(a, a[off & (a >= 0)]) & ~np.isin(b, b[off & (b >= 0)])
    a, b = a[keep], b[keep]
    bad = (a < 0) != (b < 0)
    both = (a >= 0) & (b >= 0)
    pair = np.unique(np.stack([a[both], b[both]]), axis=1)
    ida, na = np.unique(pair[0], return_counts=True)
    idb, nb = np.unique(pair[1], return_counts=True)
    bad |= both & (np.isin(a, ida[na > 1]) | np.isin(b, idb[nb > 1]))
    left_aside = len(keep) - int(keep.sum()) - int(off.sum())
    return int(off.sum()) + int(bad.sum()), left_aside


def readings(prog: dict, ref: dict) -> dict:
    """The numbers of one call, as points where the program and the
    reference disagree: on the skip rule, on the core test, on whether a
    point is noise (the border rule and the rescue), and on which points
    share a cluster (``member_mismatch``); then two kept for the record:
    points labelled differently, and clusters whose members differ."""
    member, aside = member_mismatch(prog, ref)
    return {
        "skip_mismatch": int(np.count_nonzero(prog["exec"] != ref["exec"])),
        "core_mismatch": int(np.count_nonzero(prog["core"] != ref["core"])),
        "noise_mismatch": int(np.count_nonzero((prog["labels"] < 0) != (ref["labels"] < 0))),
        "member_mismatch": member,
        "member_left_aside": aside,
        "label_mismatch": int(np.count_nonzero(prog["labels"] != ref["labels"])),
        "cluster_mismatch": cluster_mismatch(prog["labels"], ref["labels"]),
    }


def judge(per_call: list, checks: dict, limits: dict) -> tuple[bool, int]:
    """(correct, calls failed): every number of ``limits`` against its
    limit in every call, and every guard of ``checks``; the numbers'
    largest readings join ``checks``, last."""
    guards_ok = all(c["value"] <= c["limit"] for c in checks.values())
    failed = sum(1 for r in per_call
                 if not guards_ok or any(r[k] > lim for k, lim in limits.items()))
    for k, lim in limits.items():
        checks[k] = {"value": max(r[k] for r in per_call), "limit": lim}
    return failed == 0 and all(c["value"] <= c["limit"] for c in checks.values()), failed


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core.cardinality import TrainedEstimator
    from repro.core.cardinality.rmi import RMIConfig
    from repro.core.pipeline import LAFPipeline
    from repro.obs import metrics

    cfg, traffic = ctx.cfg, ctx.traffic
    eps, tau, alpha = float(cfg["eps"]), int(cfg["tau"]), float(cfg["alpha"])
    phase = {"imports_s": time.perf_counter() - ctx.t_start}
    t = time.perf_counter()
    corpus, train, pool = _corpus.corpus_and_train(cfg, traffic, ctx.seed)
    n, d = corpus.shape
    phase["corpus_s"] = time.perf_counter() - t
    t = time.perf_counter()
    params, z_max = _rmi_train.train(train, corpus, cfg["rmi_eps_grid"],
                                     epochs=int(cfg["rmi_epochs"]), seed=pool)
    del train
    phase["rmi_train_s"] = time.perf_counter() - t

    obs.enable(trace=ctx.trace, jax_annotations=ctx.trace)
    builds: list = []
    unpatch = watch_pallas_builds(builds)
    try:
        pipe = LAFPipeline(backend="random_projection", seed=pool % 2**31)
        pipe.estimator = TrainedEstimator(
            jax.tree_util.tree_map(jnp.asarray, params),
            RMIConfig(input_dim=d + 1, target_max=z_max))

        # the window's own predictions, as the skip rule reads them
        predict = pipe.predict_counts
        preds: list = []

        def recording_predict(vectors, e):
            p = predict(vectors, e)
            preds.append(p)
            return p

        pipe.predict_counts = recording_predict

        def call():
            bk = ctx.make_backend(
                n_bits=int(cfg["n_bits"]), margin=float(cfg["margin"]),
                seed=int(cfg["projection_seed"]),
            )
            res = pipe.cluster_laf_dbscan(corpus, eps, tau, alpha, backend=bk).result
            return {
                "exec": np.asarray(preds[-1]) >= alpha * tau,
                "core": np.asarray(res.core, bool),
                "labels": _reference.canonical(res.labels),
                "n_rescued": int(res.extras["n_rescued"]),
            }

        t = time.perf_counter()
        call()  # warm-up: every program of the window compiles or loads here
        phase["warmup_s"] = time.perf_counter() - t
        setup_s = time.perf_counter() - ctx.t_start
        before = _counters(metrics)
        obs.clear_trace()
        outs, call_s = [], []
        ctx.tracer.start()
        t_first = t_last = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                outs.append(call())
            t0, t_last = t_last, time.perf_counter()
            call_s.append(t_last - t0)
            if t_last - t_first >= ctx.seconds:
                break
        ctx.tracer.stop()
    finally:
        unpatch()
    after = _counters(metrics)
    delta = {k: v - before.get(k, 0) for k, v in after.items()}
    spans: dict = {}
    for rec in obs.spans():
        spans.setdefault(rec.name, []).append(rec.dur)
    calls = len(outs)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[: ctx.chips])

    # the smoke's guards: a compiled main path, no fallback to the host,
    # one device_get per call
    names = {name for name, _ in builds}
    checks = {
        "kernels_interpreted": {"value": sum(1 for _, i in builds if i), "limit": 0},
        "kernels_missing": {"value": sum(1 for k in MAIN_KERNELS if k not in names),
                            "limit": 0},
        "degraded_events": {"value": int(sum(v for k, v in delta.items()
                                             if k.startswith("stream.degraded."))),
                            "limit": 0},
        "device_get_gap": {"value": abs(int(delta.get("laf.cluster.device_get", 0)) - calls),
                           "limit": 0},
    }

    # the reference runs with the program's state gone
    del pipe, preds
    gc.collect()
    t_ref = time.perf_counter()
    ref = _reference.cluster(corpus, cfg, params, z_max, ctx.precision)
    ref_s = time.perf_counter() - t_ref
    per_call = [readings(o, ref) for o in outs]
    extra = ctx.on_reference(outs, ref, corpus, params, z_max) if ctx.on_reference else {}
    correct, failed = judge(per_call, checks, ctx.limits)

    n_exec = int(delta.get("laf.predicted_core", 0)) // max(calls, 1)
    return {
        "correct": correct,
        "attempted": calls,
        "failed": failed,
        "end_to_end": {
            "cluster_s": (t_last - t_first) / calls,
            "peak_hbm_gb": peak / 1e9,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": peak,
        "checks": checks,
        "record": {
            "calls": calls, "n": n, "d": int(cfg["d"]), "n_bits": int(cfg["n_bits"]),
            "n_exec": n_exec, "band_pairs": ref["band_pairs"], "spans": spans,
            "counters": delta,
            "window_host_s": t_last - t_first,
        },
        "info": {
            "n_exec": n_exec,
            "n_rescued": outs[-1]["n_rescued"],
            "ref_n_rescued": ref["n_rescued"],
            "ref_rounds": ref["rounds"],
            "ref_band_pairs": ref["band_pairs"],
            **{k: max(r[k] for r in per_call) for k in per_call[0] if k not in ctx.limits},
            "n_clusters": int(outs[-1]["labels"].max()) + 1,
            "label_prop_rounds_per_call": int(delta.get("laf.cluster.rounds", 0)) // max(calls, 1),
            "compiles_in_window": int(delta.get("jax.compile.events", 0)),
            "reference_s": ref_s,
            "call_s": call_s,
            "setup_phases": phase,
            **extra,
        },
    }
