"""Streaming ingest benchmark: amortized partial_fit vs full refit, plus
serving-grade ``assign`` latency.

Scenario (the ISSUE-4 acceptance shape): a database of ``--n0`` rows is
already clustered; live traffic then streams ``--n - --n0`` more rows
in ``--batches`` batches.  For each batch we time the incremental path
(``StreamingLAF.partial_fit``: index append + new-vs-all range queries +
promotions).  The baseline is what the repo had to do before this
subsystem existed — a **full refit** at the final size: rebuild the
index and recluster all n rows from scratch (timed through the same
streaming code path, one n-row batch, so the comparison is engine-fair).
Quality is checked by ARI between the streamed labels and the refit
labels.  Serving latency is measured per single-query ``assign`` call
(p50/p95 over ``--queries`` calls) against the final snapshot.

``--failover`` instead benchmarks the durable plane
(``repro.stream.durability``): the same traffic rides a
``DurableStream`` (WAL per batch + periodic snapshots), the primary
"dies" with a snapshot lag and an un-rotated WAL tail, and a replica is
recovered from disk.  Reported: snapshot overhead (durable vs plain
ingest p50), recovery time, WAL replay throughput, and the ARI of the
recovered labels vs the uninterrupted run (the kill-restore parity
acceptance — 1.0 means the replica is label-identical).

  PYTHONPATH=src python -m benchmarks.stream_bench                    # 20k -> 40k, d=768
  PYTHONPATH=src python -m benchmarks.stream_bench --n0 2000 --n 4000 --d 64 --n-bits 128
  PYTHONPATH=src python -m benchmarks.stream_bench --json BENCH_PR4.json   # CI artifact
  PYTHONPATH=src python -m benchmarks.stream_bench --failover --json BENCH_PR10.json
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

N_CLUSTERS = 80
NOISE_FRAC = 0.35


def _dataset(n: int, d: int, seed: int):
    from repro.data.synthetic import make_angular_clusters

    data, _ = make_angular_clusters(
        n, d, N_CLUSTERS, kappa=(d - 1) / 0.30, noise_frac=NOISE_FRAC, seed=seed
    )
    return data[np.random.default_rng(seed).permutation(n)]


def _fresh_stream(args):
    from repro.stream import StreamingLAF

    return StreamingLAF(
        args.eps, args.tau,
        backend="random_projection", device=args.device,
        n_bits=args.n_bits, seed=0,
    )


def run(args) -> dict:
    from repro import obs
    from repro.core.metrics import adjusted_rand_index

    from .common import timed

    data = _dataset(args.n, args.d, seed=0)

    # -- streaming path: n0 warm rows, then batches to n -------------------
    stream = _fresh_stream(args)
    warm_s, _ = timed(stream.partial_fit, data[: args.n0], _name="bench.warm_ingest")
    step = -(-(args.n - args.n0) // args.batches)
    batches = []
    for start in range(args.n0, args.n, step):
        rows = data[start : start + step]
        rep = stream.partial_fit(rows)
        batches.append(
            dict(
                n_after=rep.n_points,
                rows=len(rows),
                seconds=rep.elapsed_s,
                rows_per_s=len(rows) / max(rep.elapsed_s, 1e-9),
                n_promoted=rep.n_promoted,
            )
        )
        print(
            f"  batch -> n={rep.n_points:>7d}  {len(rows)} rows in "
            f"{rep.elapsed_s:6.2f}s  ({batches[-1]['rows_per_s']:,.0f} rows/s, "
            f"{rep.n_promoted} promoted)"
        )
    stream_labels = stream.labels()

    # -- baseline: full refit at the final size -----------------------------
    refit = _fresh_stream(args)
    refit_s, _ = timed(refit.partial_fit, data, _name="bench.refit")
    refit_labels = refit.labels()
    ari = adjusted_rand_index(stream_labels, refit_labels)

    mean_batch_s = float(np.mean([b["seconds"] for b in batches]))
    last_batch_s = batches[-1]["seconds"]
    amortized_speedup = refit_s / mean_batch_s
    print(
        f"refit {args.n} rows: {refit_s:.2f}s | mean batch: {mean_batch_s:.2f}s "
        f"(last {last_batch_s:.2f}s) -> amortized speedup {amortized_speedup:.1f}x | "
        f"ARI stream-vs-refit {ari:.4f}"
    )

    # -- serving latency ----------------------------------------------------
    rng = np.random.default_rng(7)
    member = np.nonzero(stream_labels >= 0)[0]
    qidx = rng.choice(member, size=args.queries, replace=len(member) < args.queries)
    noise = 0.02 * rng.standard_normal((args.queries, args.d)).astype(np.float32)
    queries = data[qidx] + noise
    stream.snapshot()  # build the serving snapshot outside the timed region
    # latency percentiles come from the obs log-bucket histogram that
    # serve.assign feeds (the serving process's own SLO instrument),
    # not a benchmark-side sample array
    was_on = obs.metrics_enabled()
    obs.metrics.enable()
    hist = obs.metrics.histogram("serve.assign.latency_s")
    hist._reset()
    for i in range(args.queries):
        stream.assign(queries[i : i + 1])
    s = hist.summary()
    if not was_on:
        obs.metrics.disable()
    p50, p95, p99 = (float(s[k] * 1e3) for k in ("p50", "p95", "p99"))
    print(
        f"assign latency over {args.queries} single queries: "
        f"p50 {p50:.2f} ms, p95 {p95:.2f} ms, p99 {p99:.2f} ms"
    )

    return dict(
        n0=args.n0, n=args.n, d=args.d, n_bits=args.n_bits,
        eps=args.eps, tau=args.tau, device=args.device, batches=batches,
        warm_ingest_seconds=warm_s,
        refit_seconds=refit_s,
        mean_batch_seconds=mean_batch_s,
        last_batch_seconds=last_batch_s,
        amortized_speedup=amortized_speedup,
        ari_stream_vs_refit=float(ari),
        n_clusters=int(stream.n_clusters),
        assign=dict(
            p50_ms=p50, p95_ms=p95, p99_ms=p99, n_queries=args.queries,
            mean_ms=float(s["sum"] / max(s["count"], 1) * 1e3),
        ),
    )


def run_failover(args) -> dict:
    import tempfile
    import time

    from repro.core.metrics import adjusted_rand_index
    from repro.stream import DurableStream

    data = _dataset(args.n, args.d, seed=0)
    step = -(-args.n // args.batches)
    batches = [data[i : i + step] for i in range(0, args.n, step)]
    fsync = not args.no_fsync

    # -- plain ingest baseline: per-batch p50 + reference labels -----------
    bare = _fresh_stream(args)
    bare_s = [bare.partial_fit(b).elapsed_s for b in batches]
    ingest_p50 = float(np.median(bare_s))
    ref_labels = bare.labels()

    with tempfile.TemporaryDirectory() as root:
        # -- durable primary: WAL per batch + periodic snapshots -----------
        primary = DurableStream(
            _fresh_stream(args), root,
            snapshot_every=args.snapshot_every, fsync=fsync,
        )
        dur_s = []
        for b in batches:
            t0 = time.perf_counter()
            primary.partial_fit(b)
            dur_s.append(time.perf_counter() - t0)
        durable_p50 = float(np.median(dur_s))
        # the primary dies here: no close(), the WAL tail past the last
        # snapshot is what recovery must replay
        replica = DurableStream.recover(
            root, lambda: _fresh_stream(args), fsync=fsync
        )
        info = dict(replica.recovery_info)
        ari = adjusted_rand_index(replica.labels(), ref_labels)
        replica.close()
        primary.close()

    replay_rate = info["wal_rows"] / max(info["replay_s"], 1e-9)
    overhead = durable_p50 / max(ingest_p50, 1e-9) - 1.0
    print(
        f"failover: {args.n} rows / {args.batches} batches, snapshot every "
        f"{args.snapshot_every} (fsync={fsync})\n"
        f"  ingest p50 {ingest_p50 * 1e3:.1f} ms -> durable p50 "
        f"{durable_p50 * 1e3:.1f} ms (snapshot overhead {overhead:+.1%})\n"
        f"  recovery {info['recovery_s']:.3f}s = restore {info['restore_s']:.3f}s "
        f"(snapshot step {info['snapshot_step']}) + replay "
        f"{info['replay_s']:.3f}s ({info['wal_records']} records, "
        f"{info['wal_rows']} rows, {replay_rate:,.0f} rows/s)\n"
        f"  ARI recovered-vs-uninterrupted: {ari:.4f}"
    )

    return dict(
        mode="failover",
        n=args.n, d=args.d, n_bits=args.n_bits, eps=args.eps, tau=args.tau,
        device=args.device, n_batches=args.batches,
        failover=dict(
            snapshot_every=args.snapshot_every,
            fsync=fsync,
            ingest_p50_s=ingest_p50,
            durable_p50_s=durable_p50,
            snapshot_overhead=overhead,
            recovery_s=float(info["recovery_s"]),
            restore_s=float(info["restore_s"]),
            replay_s=float(info["replay_s"]),
            snapshot_step=int(info["snapshot_step"]),
            seq=int(info["seq"]),
            wal_records=int(info["wal_records"]),
            wal_rows=int(info["wal_rows"]),
            wal_replay_rows_per_s=float(replay_rate),
            ari_recovered=float(ari),
        ),
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n0", type=int, default=20000, help="warm database size")
    ap.add_argument("--n", type=int, default=40000, help="final database size")
    ap.add_argument("--d", type=int, default=768)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--n-bits", type=int, default=512)
    ap.add_argument("--eps", type=float, default=0.3)
    ap.add_argument("--tau", type=int, default=5)
    ap.add_argument("--device", default="auto")
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--failover", action="store_true",
                    help="benchmark the durable plane: snapshot overhead, "
                    "recovery time, WAL replay throughput, recovered-ARI")
    ap.add_argument("--snapshot-every", type=int, default=3,
                    help="failover: batches between snapshots (a non-divisor "
                    "of --batches leaves a WAL tail for recovery to replay)")
    ap.add_argument("--no-fsync", action="store_true",
                    help="failover: skip per-append fsync (CI-runner mode)")
    ap.add_argument("--json", type=Path, default=None)
    args = ap.parse_args()

    payload = run_failover(args) if args.failover else run(args)
    if args.json:
        args.json.write_text(json.dumps(payload, indent=2))
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
