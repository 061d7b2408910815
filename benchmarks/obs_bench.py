"""Observability benchmark: one standard LAF-DBSCAN run under full
tracing + metrics, emitting the per-phase cost breakdown and a
Chrome/Perfetto trace as CI artifacts.

This is the PR-6 acceptance harness: a standard ``laf_dbscan`` run on a
``--mesh N`` forced-host-device mesh with ``repro.obs`` enabled must
produce a trace whose spans cover >= 95% of the run's wall time, and a
metrics snapshot that accounts for the run (per-phase seconds, sweep
recompile count, estimator fast-path skip rate, band occupancy).  The
JSON payload is the perf-trajectory artifact (``BENCH_PR6.json``); the
trace file loads straight into https://ui.perfetto.dev.

  PYTHONPATH=src python -m benchmarks.obs_bench --mesh 4 \
      --json BENCH_PR6.json --trace laf_trace.json
"""

from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import numpy as np

N_CLUSTERS = 40
NOISE_FRAC = 0.35


def _phase_seconds(records, parent_id: int) -> dict:
    """Sum span durations by name among the direct children of one span."""
    out: dict = {}
    for r in records:
        if r.parent_id == parent_id:
            out[r.name] = out.get(r.name, 0.0) + r.dur
    return out


def run(args) -> dict:
    from repro import obs
    from repro.core.pipeline import LAFPipeline
    from repro.data.synthetic import make_angular_clusters
    from repro.index import RandomProjectionBackend

    # --cluster-device: the one-launch fused clustering config — device
    # telemetry on, engine-backed index, cluster formation under a
    # single lax.while_loop.  The fused interval (laf.label_prop) has no
    # host-observable phase boundaries, so its coverage is restored by
    # the synthetic per-round spans the telemetry harvest emits.
    obs.enable(trace=True, metrics_on=True, telemetry=args.cluster_device)
    obs.clear_trace()
    obs.metrics.reset()

    data, _ = make_angular_clusters(
        args.n, args.d, N_CLUSTERS, kappa=(args.d - 1) / 0.30,
        noise_frac=NOISE_FRAC, seed=args.seed,
    )
    mesh = None
    if args.mesh > 1:
        import jax

        mesh = jax.make_mesh((args.mesh,), ("data",))
    backend = RandomProjectionBackend(
        n_bits=args.n_bits, seed=args.seed,
        # cluster-device mode needs the engine's packed slabs on device
        device=True if (mesh is not None or args.cluster_device) else "auto",
        mesh=mesh,
    )
    pipe = LAFPipeline(
        eps_grid=(args.eps,), epochs=args.epochs, seed=args.seed,
        backend=backend,
    )
    cluster_kw = {"cluster_device": True} if args.cluster_device else {}
    test = pipe.fit_split(data)  # estimator training is NOT the traced run
    obs.clear_trace()  # the artifact traces the clustering run only
    out = pipe.cluster_laf_dbscan(test, args.eps, args.tau, args.alpha,
                                  **cluster_kw)

    records = obs.spans()
    root = next(r for r in reversed(records) if r.name == "laf.run")
    cluster = next(r for r in reversed(records) if r.name == "laf.cluster")
    cov_run = obs.coverage(root, records)
    cov_cluster = obs.coverage(cluster, records)
    run_kids = _phase_seconds(records, root.span_id)
    cluster_kids = _phase_seconds(records, cluster.span_id)
    cov_label_prop = round_spans = None
    if args.cluster_device:
        # the fused one-launch interval: without the synthetic per-round
        # telemetry spans its coverage is 0 (no host-observable phase
        # boundaries inside a single lax.while_loop)
        lp = next(
            (r for r in reversed(records) if r.name == "laf.label_prop"), None
        )
        if lp is None:
            raise SystemExit(
                "--cluster-device run never entered the fused label-prop "
                "pass (estimator predicted 0 core points at this operating "
                "point — raise --n/--epochs or lower --tau)"
            )
        cov_label_prop = obs.coverage(lp, records)
        round_spans = sum(
            1 for r in records
            if r.name == "laf.cluster.round" and r.parent_id == lp.span_id
        )

    predict_s = run_kids.get("laf.predict", 0.0)
    sweep_s = cluster_kids.get("laf.pass1", 0.0)
    post_s = (cluster_kids.get("laf.union_find", 0.0)
              + cluster_kids.get("laf.postprocess", 0.0))
    wall = root.dur

    snap = obs.metrics.snapshot()
    skipped = snap.get("laf.skipped", 0)
    executed = snap.get("laf.predicted_core", 0)

    trace_path = None
    if args.trace:
        obs.export_chrome_trace(args.trace)
        trace_path = str(args.trace)

    # instrumentation overhead, warm-vs-warm: the traced run above paid
    # every jit compile, so both passes here ride hot caches and the
    # delta isolates the obs layer itself
    disabled_wall = enabled_wall = None
    if not args.no_overhead_check:
        import time

        def _pass():
            bk = RandomProjectionBackend(
                n_bits=args.n_bits, seed=args.seed,
                device=True if (mesh is not None or args.cluster_device)
                else "auto",
                mesh=mesh,
            )
            t0 = time.perf_counter()
            pipe.cluster_laf_dbscan(test, args.eps, args.tau, args.alpha,
                                    backend=bk, **cluster_kw)
            return time.perf_counter() - t0

        obs.disable()
        disabled_wall = _pass()
        obs.enable(trace=True, metrics_on=True,
                   telemetry=args.cluster_device)
        enabled_wall = _pass()

    payload = {
        "n": args.n, "d": args.d, "eps": args.eps, "tau": args.tau,
        "alpha": args.alpha, "mesh": args.mesh, "n_bits": args.n_bits,
        "wall_s": wall,
        "phases": {
            "predict_s": predict_s,
            "fit_index_s": cluster_kids.get("laf.fit_index", 0.0),
            "sweep_s": sweep_s,
            "union_find_s": cluster_kids.get("laf.union_find", 0.0),
            "postprocess_s": cluster_kids.get("laf.postprocess", 0.0),
            "predict_frac": predict_s / wall if wall else 0.0,
            "sweep_frac": sweep_s / wall if wall else 0.0,
            "postprocess_frac": post_s / wall if wall else 0.0,
        },
        "coverage": {"laf.run": cov_run, "laf.cluster": cov_cluster},
        "span_coverage": cov_run,  # trajectory-gate key
        "recompiles": {
            "sweep": snap.get("sweep.recompiles", 0),
            "jax_backend_compiles": snap.get("jax.compile.events", 0),
        },
        "estimator_fast_path": {
            "skipped": skipped,
            "executed": executed,
            "skip_rate": skipped / (skipped + executed)
            if (skipped + executed) else 0.0,
            "rescued": snap.get("laf.rescued", 0),
        },
        "band_occupancy": {
            k.rsplit(".", 1)[1]: v
            for k, v in snap.items() if k.startswith("index.band.")
        },
        "result": {
            "n_clusters": int(out.result.n_clusters),
            "noise_ratio": float(out.result.noise_ratio),
        },
        "metrics": snap,
        "trace": trace_path,
        "spans_recorded": len(records),
    }
    if args.cluster_device:
        # ``snap`` was taken right after the traced run, before the
        # overhead passes bumped the counters again
        payload["cluster_device"] = {
            "coverage_label_prop": cov_label_prop,
            "round_spans": round_spans,
            "rounds": snap.get("laf.cluster.rounds", 0),
            "device_get": snap.get("laf.cluster.device_get", 0),
            "telemetry_totals": {
                k.rsplit(".", 1)[1]: v
                for k, v in snap.items() if k.startswith("laf.telemetry.")
            },
        }
    if disabled_wall is not None:
        payload["obs_disabled_wall_s"] = disabled_wall
        payload["obs_enabled_wall_s"] = enabled_wall
        payload["obs_overhead_frac"] = (enabled_wall - disabled_wall) / disabled_wall

    print(
        f"laf run {args.n}x{args.d} mesh={args.mesh}: {wall:.2f}s | "
        f"predict {payload['phases']['predict_frac']:.1%} "
        f"sweep {payload['phases']['sweep_frac']:.1%} "
        f"post {payload['phases']['postprocess_frac']:.1%} | "
        f"coverage run={cov_run:.3f} cluster={cov_cluster:.3f} | "
        f"skip_rate={payload['estimator_fast_path']['skip_rate']:.2f} "
        f"sweep_recompiles={payload['recompiles']['sweep']}"
    )
    if cov_label_prop is not None:
        print(
            f"  cluster-device: label_prop coverage {cov_label_prop:.3f} "
            f"({round_spans} synthetic round spans, "
            f"{payload['cluster_device']['rounds']} rounds)", flush=True,
        )
    if cov_run < args.min_coverage:
        raise SystemExit(
            f"span coverage {cov_run:.3f} below --min-coverage "
            f"{args.min_coverage} — an uninstrumented phase opened up"
        )
    if cov_label_prop is not None and cov_label_prop < args.min_coverage:
        raise SystemExit(
            f"fused label_prop coverage {cov_label_prop:.3f} below "
            f"--min-coverage {args.min_coverage} — the synthetic per-round "
            "telemetry spans stopped attributing the one-launch interval"
        )
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--eps", type=float, default=0.55)
    ap.add_argument("--tau", type=int, default=5)
    ap.add_argument("--alpha", type=float, default=1.2)
    ap.add_argument("--n-bits", type=int, default=128)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--mesh", type=int, default=4, metavar="N",
        help="force N host devices (set before jax initializes) and run "
        "the sweep through the sharded index plane; 0/1 = single device",
    )
    ap.add_argument("--json", type=Path, default=None,
                    help="write the payload here (BENCH_PR6.json in CI)")
    ap.add_argument("--trace", type=Path, default=None,
                    help="write the Chrome/Perfetto trace here")
    ap.add_argument(
        "--cluster-device", action="store_true",
        help="trace the one-launch fused clustering (cluster_device=True) "
        "with device telemetry on: the laf.label_prop coverage gate then "
        "rides the synthetic per-round spans (BENCH_PR9 leg)",
    )
    ap.add_argument("--min-coverage", type=float, default=0.95,
                    help="fail if laf.run span coverage drops below this "
                    "(under --cluster-device, also gates laf.label_prop)")
    ap.add_argument("--no-overhead-check", action="store_true",
                    help="skip the second (obs-disabled) clustering pass")
    args = ap.parse_args(argv)
    if args.mesh > 1:
        # must land before the first jax import anywhere in the process
        import sys

        assert "jax" not in sys.modules, "--mesh requires jax to be uninitialized"
        inherited = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        os.environ["XLA_FLAGS"] = " ".join(
            [f"--xla_force_host_platform_device_count={args.mesh}"] + inherited
        )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    payload = run(args)
    if args.json:
        args.json.write_text(json.dumps(payload, indent=2, default=float))
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
