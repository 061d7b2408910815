"""Index benchmark: recall / speedup / clustering quality of the
``repro.index`` range backends across (n, d, eps).

For each operating point the same query sweep runs through the exact
blocked-matmul backend and the random-projection ANN backend
(interleaved block by block so recall is measured pair-exactly without
materializing an n^2 adjacency), then LAF-DBSCAN runs end-to-end on
both backends with an oracle cardinality estimator so the ARI delta
isolates the index, not the estimator.

  PYTHONPATH=src python -m benchmarks.index_bench                  # 20k x 768
  PYTHONPATH=src python -m benchmarks.index_bench --grid           # n x d x eps sweep
  PYTHONPATH=src python -m benchmarks.index_bench --n 5000 --d 256
  PYTHONPATH=src python -m benchmarks.index_bench \
      --n 2000 --d 64 --device device --json BENCH_PR2.json        # CI trajectory
  PYTHONPATH=src python -m benchmarks.index_bench \
      --n 2000 --d 64 --mesh 4 --json BENCH_PR3.json  # sharded index plane
  PYTHONPATH=src python -m benchmarks.index_bench \
      --n 40000 --d 768 --sweep --json BENCH_PR5.json # sweep engine
  PYTHONPATH=src python -m benchmarks.index_bench \
      --n 2000 --d 64 --sweep --mesh 4 --json BENCH_PR5.json

``--device device`` routes the ANN backend through the fused Pallas
``hamming_filter`` tile (interpret mode off-accelerator), so the CI
artifact tracks the kernel path's recall/speedup/ARI, not just the
host oracle's.  ``--mesh N`` forces N host devices (the flag must be
set before jax initializes, which is why the repro imports below are
deferred into the functions) and runs the same sweep through the
shard_mapped index plane — the row payload then carries both the
sharded and single-device fused sweep times plus per-device shard
numbers.

``--sweep`` benchmarks the device-resident sweep engine
(``repro.index.sweep``) instead: the legacy per-chunk dispatch loop
(one kernel launch + one synchronous device→host round-trip per chunk)
vs the one-launch engine on a whole-database sweep, plus — under
``--mesh N`` — the serialized plane vs the double-buffered
(software-pipelined) plane, with LAF-DBSCAN end-to-end ARI vs the
exact backend through the engine-backed index in the same payload.

``--cluster`` benchmarks cluster *formation* (BENCH_PR8.json): the
same engine-backed index runs LAF-DBSCAN twice, once with
``cluster_device=False`` (the PR 5 path — device sweep, then host
unpack + union-find per block) and once with ``cluster_device=True``
(the one-launch program: packed label propagation under a single
``lax.while_loop``, exactly one device→host transfer for the whole
clustering).  The row carries per-phase span costs, rounds-to-fixpoint
and the ``laf.cluster.device_get`` counter delta — the one-launch run
asserts that delta is exactly 1 — plus exact label parity between the
two paths.

  PYTHONPATH=src python -m benchmarks.index_bench \
      --n 2000 --d 64 --cluster --json BENCH_PR8.json
  PYTHONPATH=src python -m benchmarks.index_bench \
      --n 2000 --d 64 --cluster --mesh 4 --json BENCH_PR8.json
"""

from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import numpy as np

N_CLUSTERS = 80
NOISE_FRAC = 0.35


def _dataset(n: int, d: int, seed: int):
    from repro.data.synthetic import make_angular_clusters

    # kappa = (d-1)/0.30 puts same-cluster pairs near d_cos ~ 0.3
    # (see benchmarks.common DATASETS rationale)
    return make_angular_clusters(
        n, d, N_CLUSTERS, kappa=(d - 1) / 0.30, noise_frac=NOISE_FRAC, seed=seed
    )


def bench_point(
    n: int,
    d: int,
    eps: float,
    tau: int,
    *,
    n_bits: int = 512,
    margin: float = 3.0,
    verify: str = "band",
    device: str = "host",
    mesh_devices: int = 0,
    seed: int = 0,
    block: int = 2048,
) -> dict:
    from repro.core.laf_dbscan import laf_dbscan
    from repro.core.metrics import adjusted_rand_index
    from repro.index import ExactBackend, RandomProjectionBackend

    from .common import timed

    data, _ = _dataset(n, d, seed)
    exact = ExactBackend().fit(data)
    mesh = None
    if mesh_devices > 1:
        import jax

        mesh = jax.make_mesh((mesh_devices,), ("data",))
    build_s, rp = timed(
        lambda: RandomProjectionBackend(
            n_bits=n_bits, margin=margin, verify=verify, seed=seed,
            # the plane is a device evaluator: --mesh implies the fused tile
            device=True if mesh is not None else (device == "device"), mesh=mesh,
        ).fit(data),
        _name="bench.build",
    )
    # same index configuration WITHOUT the mesh: the single-device fused
    # tile, so the sharded-vs-single sweep delta isolates the plane
    rp_single = None
    if mesh is not None:
        rp_single = RandomProjectionBackend(
            n_bits=n_bits, margin=margin, verify=verify, seed=seed, device=True,
        ).fit(data)

    counts = np.zeros(n, dtype=np.int64)
    shard_hits = None
    tp = pos = pred = 0
    t_exact = t_rp = t_rp_single = 0.0
    for start in range(0, n, block):
        rows = np.arange(start, min(start + block, n))
        dt, h_ex = timed(exact.query_hits, rows, eps, _name="bench.sweep_exact")
        t_exact += dt
        dt, h_rp = timed(rp.query_hits, rows, eps, _name="bench.sweep_rp")
        t_rp += dt
        if rp_single is not None:
            dt, _ = timed(
                rp_single.query_hits, rows, eps, _name="bench.sweep_rp_single"
            )
            t_rp_single += dt
            # per-device hit totals: slice the hit matrix at the plane's
            # shard boundaries (rows n_local*k .. n_local*(k+1) live on
            # device k)
            n_local = rp._plan.n_local
            if shard_hits is None:
                shard_hits = np.zeros(mesh_devices, dtype=np.int64)
            for k in range(mesh_devices):
                shard_hits[k] += int(h_rp[:, k * n_local : (k + 1) * n_local].sum())
        counts[rows] = h_ex.sum(axis=1)
        tp += int((h_ex & h_rp).sum())
        pos += int(h_ex.sum())
        pred += int(h_rp.sum())

    # end-to-end LAF-DBSCAN, oracle estimator, backend is the only delta
    t_laf_exact, res_ex = timed(
        laf_dbscan, data, eps, tau, 1.0, counts,
        seed=seed, backend=exact, _name="bench.laf_exact",
    )
    t_laf_rp, res_rp = timed(
        laf_dbscan, data, eps, tau, 1.0, counts,
        seed=seed, backend=rp, _name="bench.laf_rp",
    )

    row = {
        "n": n, "d": d, "eps": eps, "tau": tau,
        "n_bits": n_bits, "margin": margin, "verify": verify,
        # the evaluator that actually ran (--mesh forces the fused tile)
        "device": "device" if mesh is not None else device,
        "mesh": mesh_devices,
        "build_s": build_s,
        "sweep_exact_s": t_exact, "sweep_rp_s": t_rp,
        "sweep_speedup": t_exact / t_rp if t_rp else float("inf"),
        "recall": tp / pos if pos else 1.0,
        "precision": tp / pred if pred else 1.0,
        "laf_exact_s": t_laf_exact, "laf_rp_s": t_laf_rp,
        "laf_speedup": t_laf_exact / t_laf_rp if t_laf_rp else float("inf"),
        "ari_rp_vs_exact": adjusted_rand_index(res_ex.labels, res_rp.labels),
        "noise_exact": res_ex.noise_ratio, "noise_rp": res_rp.noise_ratio,
    }
    if mesh is not None:
        plan = rp._plan
        row["sweep_rp_single_s"] = t_rp_single
        # >1 means the plane beat the single-device tile (expect <1 on a
        # CPU runner: N interpret-mode kernels on 2 cores is a parity
        # harness, not a speed win — the trajectory tracks the ratio)
        row["sharded_speedup"] = t_rp_single / t_rp if t_rp else float("inf")
        row["per_device"] = [
            {
                "device": k,
                "rows": int(min(max(plan.n - k * plan.n_local, 0), plan.n_local)),
                "hits": int(shard_hits[k]),
            }
            for k in range(mesh_devices)
        ]
    return row


def bench_sweep_point(
    n: int,
    d: int,
    eps: float,
    tau: int,
    *,
    n_bits: int = 512,
    margin: float = 3.0,
    mesh_devices: int = 0,
    seed: int = 0,
    block: int = 2048,
    chunks_per_launch: int = 8,
    with_ari: bool = True,
    chunk: int = 256,
    q_tile: int = 128,
    db_tile: int = 256,
) -> dict:
    """Per-chunk loop vs one-launch sweep (vs the pipelined plane under
    ``--mesh``) on one whole-database query sweep.

    ``chunk``/``q_tile``/``db_tile`` apply to *both* variants (the
    comparison is per-chunk dispatch vs one launch at identical tiling);
    off-accelerator the interpreter's per-tile-step overhead dominates,
    so CPU runs of the big operating points should raise the tiles
    (e.g. ``--chunk 1024 --q-tile 256 --db-tile 2048``).
    """
    from repro.core.laf_dbscan import laf_dbscan
    from repro.core.metrics import adjusted_rand_index
    from repro.index import ExactBackend, RandomProjectionBackend

    from .common import timed

    data, _ = _dataset(n, d, seed)
    mesh = None
    if mesh_devices > 1:
        import jax

        mesh = jax.make_mesh((mesh_devices,), ("data",))
    cfg = dict(
        n_bits=n_bits, margin=margin, seed=seed, device=True, mesh=mesh,
        chunk=chunk, q_tile=q_tile, db_tile=db_tile,
    )
    variants = {
        "per_chunk": RandomProjectionBackend(sweep=False, **cfg),
        "one_launch": RandomProjectionBackend(
            sweep=True, chunks_per_launch=chunks_per_launch, pipeline_depth=1, **cfg
        ),
    }
    if mesh is not None:
        # under a mesh "one_launch" is the serialized (depth-1) plane;
        # the pipelined variant double-buffers chunk k's psum against
        # chunk k+1's shard-local popcount+verify
        variants["pipelined"] = RandomProjectionBackend(
            sweep=True, chunks_per_launch=chunks_per_launch, pipeline_depth=2, **cfg
        )
    times = {}
    for name, bk in variants.items():
        bk.fit(data)
        bk.query_hits(np.arange(min(block, n)), eps)  # warm/compile

        def _sweep_all(bk=bk):
            for start in range(0, n, block):
                rows = np.arange(start, min(start + block, n))
                bk.query_hits(rows, eps)

        times[name], _ = timed(_sweep_all, _name=f"bench.sweep_{name}")
        print(f"  sweep[{name}]: {times[name]:.2f}s", flush=True)

    row = {
        "n": n, "d": d, "eps": eps, "tau": tau,
        "n_bits": n_bits, "margin": margin, "mesh": mesh_devices,
        "chunks_per_launch": chunks_per_launch,
        "chunk": chunk, "q_tile": q_tile, "db_tile": db_tile,
        "sweep_per_chunk_s": times["per_chunk"],
        "sweep_one_launch_s": times["one_launch"],
        "one_launch_speedup": times["per_chunk"] / times["one_launch"],
    }
    if mesh is not None:
        row["sweep_pipelined_s"] = times["pipelined"]
        row["pipelined_speedup"] = times["per_chunk"] / times["pipelined"]
        row["pipelined_vs_serial_launch"] = times["one_launch"] / times["pipelined"]
    if with_ari:
        # LAF e2e through the engine-backed index, oracle estimator —
        # the sweep rewiring must not move a single label
        exact = ExactBackend().fit(data)
        pred = exact.query_counts(np.arange(n), eps)
        res_ex = laf_dbscan(data, eps, tau, 1.0, pred, seed=seed, backend=exact)
        eng = variants["pipelined" if mesh is not None else "one_launch"]
        res_sw = laf_dbscan(data, eps, tau, 1.0, pred, seed=seed, backend=eng)
        row["ari_sweep_vs_exact"] = adjusted_rand_index(res_ex.labels, res_sw.labels)
    return row


def bench_cluster_point(
    n: int,
    d: int,
    eps: float,
    tau: int,
    *,
    n_bits: int = 512,
    margin: float = 3.0,
    mesh_devices: int = 0,
    seed: int = 0,
    chunk: int = 256,
    q_tile: int = 128,
    db_tile: int = 256,
    chunks_per_launch: int = 8,
) -> dict:
    """Host union-find vs one-launch device clustering on one dataset.

    Both variants run through the *same* fitted engine-backed index
    with the *same* oracle predicted counts at ``alpha=1.0`` (so no
    point is rescued and the device path's single fetch is the only
    device→host transfer of the whole clustering) — the delta isolates
    cluster formation, not the index or the estimator.
    """
    from repro import obs
    from repro.core.laf_dbscan import laf_dbscan
    from repro.core.metrics import adjusted_rand_index
    from repro.index import ExactBackend, RandomProjectionBackend

    from .common import timed

    data, _ = _dataset(n, d, seed)
    mesh = None
    if mesh_devices > 1:
        import jax

        mesh = jax.make_mesh((mesh_devices,), ("data",))
    bk = RandomProjectionBackend(
        n_bits=n_bits, margin=margin, seed=seed, device=True, mesh=mesh,
        sweep=True, chunks_per_launch=chunks_per_launch,
        chunk=chunk, q_tile=q_tile, db_tile=db_tile,
    ).fit(data)
    # oracle predicted counts + alpha=1.0: pred >= true for every row,
    # so the skip rule never under-predicts and rescue stays empty
    pred = np.asarray(ExactBackend().fit(data).query_counts(np.arange(n), eps))

    obs.enable(trace=True, metrics_on=True)
    variants = {"host_union_find": False, "one_launch": True}
    phase_names = (
        "laf.pass1", "laf.union_find", "laf.label_prop", "laf.postprocess",
    )
    row = {
        "n": n, "d": d, "eps": eps, "tau": tau,
        "n_bits": n_bits, "margin": margin, "mesh": mesh_devices,
        "chunk": chunk, "q_tile": q_tile, "db_tile": db_tile,
    }
    results = {}
    for name, on_device in variants.items():
        kw = dict(seed=seed, backend=bk, cluster_device=on_device)
        laf_dbscan(data, eps, tau, 1.0, pred, **kw)  # warm/compile
        obs.clear_trace()
        c_get = obs.metrics.counter("laf.cluster.device_get").value
        c_rounds = obs.metrics.counter("laf.cluster.rounds").value
        c_launch = obs.metrics.counter("labelprop.launches").value
        t_e2e, res = timed(
            laf_dbscan, data, eps, tau, 1.0, pred, **kw,
            _name=f"bench.cluster_{name}",
        )
        results[name] = res
        phases = {
            p: sum(s.dur for s in obs.spans(p)) for p in phase_names
        }
        row[name] = {
            "e2e_s": t_e2e,
            "phases_s": {p: t for p, t in phases.items() if t > 0.0},
            "device_get": obs.metrics.counter("laf.cluster.device_get").value
            - c_get,
            "rounds": obs.metrics.counter("laf.cluster.rounds").value
            - c_rounds,
            "labelprop_launches": obs.metrics.counter(
                "labelprop.launches"
            ).value - c_launch,
            "n_rescued": res.extras["n_rescued"],
        }
        print(
            f"  cluster[{name}]: {t_e2e:.2f}s rounds={row[name]['rounds']} "
            f"device_get={row[name]['device_get']}", flush=True,
        )
    dev = row["one_launch"]
    assert dev["device_get"] == 1, (
        f"one-launch clustering did {dev['device_get']} device fetches, "
        "expected exactly 1"
    )
    assert dev["n_rescued"] == 0, (
        "oracle counts at alpha=1.0 must be rescue-free, got "
        f"{dev['n_rescued']}"
    )
    lab_host = results["host_union_find"].labels
    lab_dev = results["one_launch"].labels
    row["labels_exact_match"] = bool(np.array_equal(lab_host, lab_dev))
    row["ari_one_launch_vs_host"] = adjusted_rand_index(lab_host, lab_dev)
    row["cluster_speedup"] = (
        row["host_union_find"]["e2e_s"] / dev["e2e_s"]
        if dev["e2e_s"] else float("inf")
    )

    # device-telemetry overhead, warm-vs-warm on the one-launch path:
    # the telemetry flag is a compile-time static (each state owns its
    # executable), so warm both programs first, then time back to back.
    # The telemetry-on run must keep the single-fetch contract and move
    # no label — the counters ride the existing device_get.
    from repro.obs import device as obs_device

    was_on = obs_device.device_enabled()
    kw = dict(seed=seed, backend=bk, cluster_device=True)
    obs_device.disable_device()
    laf_dbscan(data, eps, tau, 1.0, pred, **kw)  # warm (telemetry off)
    obs_device.enable_device()
    try:
        laf_dbscan(data, eps, tau, 1.0, pred, **kw)  # compile+warm (on)
        base = {
            k: obs.metrics.counter(k).value
            for k in (
                "laf.cluster.device_get",
                "laf.telemetry.frontier", "laf.telemetry.changed",
                "laf.telemetry.hops", "laf.telemetry.shard_wins",
            )
        }
        t_on0, res_tele = timed(
            laf_dbscan, data, eps, tau, 1.0, pred, **kw,
            _name="bench.cluster_tele_on",
        )
        delta = {
            k: obs.metrics.counter(k).value - v for k, v in base.items()
        }
        # both programs are warm: the overhead ratio is gated in CI, so
        # measure it as interleaved min-of-N — at the tens-of-ms scale of
        # this operating point a single back-to-back pair carries more
        # scheduler noise than the 5% budget being measured
        t_offs, t_ons = [], [t_on0]
        for _ in range(4):
            obs_device.disable_device()
            t, _ = timed(
                laf_dbscan, data, eps, tau, 1.0, pred, **kw,
                _name="bench.cluster_tele_off",
            )
            t_offs.append(t)
            obs_device.enable_device()
            t, _ = timed(
                laf_dbscan, data, eps, tau, 1.0, pred, **kw,
                _name="bench.cluster_tele_on",
            )
            t_ons.append(t)
        t_off, t_on = min(t_offs), min(t_ons)
    finally:
        if not was_on:
            obs_device.disable_device()
    assert delta["laf.cluster.device_get"] == 1, (
        "telemetry-on one-launch clustering did "
        f"{delta['laf.cluster.device_get']} device fetches, expected 1"
    )
    assert np.array_equal(res_tele.labels, lab_dev), (
        "device telemetry moved clustering labels"
    )
    row["telemetry"] = {
        "off_s": t_off,
        "on_s": t_on,
        "telemetry_overhead": t_on / t_off - 1.0 if t_off else 0.0,
        "device_get": delta["laf.cluster.device_get"],
        "totals": {
            f: delta[f"laf.telemetry.{f}"]
            for f in obs_device.CLUSTER_ROUND_FIELDS
        },
    }
    print(
        f"  cluster[telemetry]: off {t_off:.2f}s on {t_on:.2f}s "
        f"overhead {row['telemetry']['telemetry_overhead']:+.1%}",
        flush=True,
    )
    return row


def run_cluster(
    *,
    ns=(2000,),
    ds=(64,),
    epss=(0.55,),
    tau: int = 5,
    n_bits: int = 512,
    margin: float = 3.0,
    mesh_devices: int = 0,
    seed: int = 0,
    chunk: int = 256,
    q_tile: int = 128,
    db_tile: int = 256,
):
    from .common import save_json

    rows = []
    for n in ns:
        for d in ds:
            for eps in epss:
                row = bench_cluster_point(
                    n, d, eps, tau, n_bits=n_bits, margin=margin,
                    mesh_devices=mesh_devices, seed=seed,
                    chunk=chunk, q_tile=q_tile, db_tile=db_tile,
                )
                rows.append(row)
                print(
                    f"  n={n} d={d} eps={eps}: one-launch "
                    f"x{row['cluster_speedup']:.2f} "
                    f"rounds={row['one_launch']['rounds']} "
                    f"exact_match={row['labels_exact_match']} "
                    f"ARI={row['ari_one_launch_vs_host']:.4f}",
                    flush=True,
                )
    save_json("index_bench_cluster", rows)
    return rows


def run_sweep(
    *,
    ns=(40000,),
    ds=(768,),
    epss=(0.55,),
    tau: int = 5,
    n_bits: int = 512,
    margin: float = 3.0,
    mesh_devices: int = 0,
    seed: int = 0,
    with_ari: bool = True,
    chunk: int = 256,
    q_tile: int = 128,
    db_tile: int = 256,
):
    from .common import save_json

    rows = []
    for n in ns:
        for d in ds:
            for eps in epss:
                row = bench_sweep_point(
                    n, d, eps, tau, n_bits=n_bits, margin=margin,
                    mesh_devices=mesh_devices, seed=seed, with_ari=with_ari,
                    chunk=chunk, q_tile=q_tile, db_tile=db_tile,
                )
                rows.append(row)
                extra = (
                    f" pipelined x{row['pipelined_speedup']:.2f}"
                    if "pipelined_speedup" in row else ""
                )
                ari = (
                    f" ARI={row['ari_sweep_vs_exact']:.4f}"
                    if "ari_sweep_vs_exact" in row else ""
                )
                print(
                    f"  n={n} d={d} eps={eps}: one-launch "
                    f"x{row['one_launch_speedup']:.2f}{extra}{ari}",
                    flush=True,
                )
    save_json("index_bench_sweep", rows)
    return rows


def run(
    profile: str = "standard",
    *,
    ns=(20000,),
    ds=(768,),
    epss=(0.55,),
    tau: int = 5,
    n_bits: int = 512,
    margin: float = 3.0,
    verify: str = "band",
    device: str = "host",
    mesh_devices: int = 0,
    seed: int = 0,
):
    from .common import save_json

    if profile == "quick":  # keep `-m benchmarks.run --profile quick` cheap
        ns, ds = tuple(min(x, 5000) for x in ns), tuple(min(x, 256) for x in ds)
    rows = []
    for n in ns:
        for d in ds:
            for eps in epss:
                row = bench_point(
                    n, d, eps, tau,
                    n_bits=n_bits, margin=margin, verify=verify, device=device,
                    mesh_devices=mesh_devices, seed=seed,
                )
                rows.append(row)
                extra = (
                    f" sharded speedup x{row['sharded_speedup']:.2f}"
                    if "sharded_speedup" in row else ""
                )
                print(
                    f"  n={n} d={d} eps={eps}: recall={row['recall']:.4f} "
                    f"sweep x{row['sweep_speedup']:.2f} laf x{row['laf_speedup']:.2f} "
                    f"ARI={row['ari_rp_vs_exact']:.4f}{extra}",
                    flush=True,
                )
    save_json("index_bench", rows)
    return rows


def summarize(rows) -> str:
    lines = [
        "index_bench: random_projection vs exact backend",
        f"{'n':>7} {'d':>5} {'eps':>5} | {'recall':>7} {'prec':>6} | "
        f"{'sweep x':>8} {'laf x':>6} | {'ARI':>6}",
    ]
    for r in rows:
        lines.append(
            f"{r['n']:>7} {r['d']:>5} {r['eps']:>5.2f} | {r['recall']:>7.4f} "
            f"{r['precision']:>6.3f} | {r['sweep_speedup']:>8.2f} "
            f"{r['laf_speedup']:>6.2f} | {r['ari_rp_vs_exact']:>6.3f}"
        )
    worst_recall = min(r["recall"] for r in rows)
    worst_ari = min(r["ari_rp_vs_exact"] for r in rows)
    lines.append(f"worst recall {worst_recall:.4f}; worst ARI vs exact {worst_ari:.4f}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, nargs="+", default=[20000])
    ap.add_argument("--d", type=int, nargs="+", default=[768])
    ap.add_argument("--eps", type=float, nargs="+", default=[0.55])
    ap.add_argument("--tau", type=int, default=5)
    ap.add_argument("--n-bits", type=int, default=512)
    ap.add_argument("--margin", type=float, default=3.0)
    ap.add_argument("--verify", choices=["band", "full"], default="band")
    ap.add_argument(
        "--device", choices=["host", "device"], default="host",
        help="ANN backend evaluator: host numpy band logic or the fused "
        "Pallas hamming_filter tile (interpret mode off-accelerator)",
    )
    ap.add_argument(
        "--mesh", type=int, default=0, metavar="N",
        help="benchmark the sharded index plane on N forced host devices "
        "(sets --xla_force_host_platform_device_count before jax "
        "initializes; implies the device evaluator); rows then include "
        "the single-device fused sweep time and per-device shard numbers",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--json", type=Path, default=None,
        help="also write {rows, summary} to this path (CI perf-trajectory "
        "artifact, e.g. BENCH_PR2.json)",
    )
    ap.add_argument(
        "--grid", action="store_true",
        help="sweep n in {5000, 20000}, d in {256, 768}, eps in {0.5, 0.55, 0.6}",
    )
    ap.add_argument(
        "--sweep", action="store_true",
        help="benchmark the device-resident sweep engine: per-chunk loop "
        "vs one-launch (vs the double-buffered plane under --mesh), with "
        "LAF e2e ARI vs exact in the payload (BENCH_PR5.json)",
    )
    ap.add_argument(
        "--cluster", action="store_true",
        help="benchmark cluster formation: host unpack+union-find "
        "(cluster_device=False, the PR 5 path) vs the one-launch packed "
        "label-propagation program (cluster_device=True), with phase "
        "costs, rounds-to-fixpoint, the device_get==1 assertion and "
        "exact label parity (BENCH_PR8.json)",
    )
    ap.add_argument(
        "--no-ari", action="store_true",
        help="--sweep only: skip the exact-backend LAF e2e ARI pass "
        "(the O(n^2) part of the sweep benchmark)",
    )
    ap.add_argument(
        "--max-telemetry-overhead", type=float, default=None, metavar="FRAC",
        help="--cluster only: fail (exit 1) when the warm telemetry-on "
        "one-launch pass is more than FRAC slower than telemetry-off "
        "(CI passes 0.05)",
    )
    ap.add_argument("--chunk", type=int, default=256,
                    help="--sweep only: query rows per kernel pass")
    ap.add_argument("--q-tile", type=int, default=128,
                    help="--sweep only: kernel query tile")
    ap.add_argument("--db-tile", type=int, default=256,
                    help="--sweep only: kernel db tile")
    args = ap.parse_args(argv)
    if args.mesh > 1:
        # must land before the first jax import anywhere in the process
        # (the repro imports are deferred into the functions for this);
        # any inherited force-count is replaced, other flags are kept
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
    ns, ds, epss = tuple(args.n), tuple(args.d), tuple(args.eps)
    if args.grid:
        ns, ds, epss = (5000, 20000), (256, 768), (0.5, 0.55, 0.6)
    if args.cluster:
        rows = run_cluster(
            ns=ns, ds=ds, epss=epss, tau=args.tau, n_bits=args.n_bits,
            margin=args.margin, mesh_devices=args.mesh, seed=args.seed,
            chunk=args.chunk, q_tile=args.q_tile, db_tile=args.db_tile,
        )
        worst_overhead = max(
            r["telemetry"]["telemetry_overhead"] for r in rows
        )
        if args.json is not None:
            payload = {
                "rows": rows,
                "best_cluster_speedup": max(r["cluster_speedup"] for r in rows),
                "worst_ari": min(r["ari_one_launch_vs_host"] for r in rows),
                "all_labels_exact": all(r["labels_exact_match"] for r in rows),
                "max_device_get": max(r["one_launch"]["device_get"] for r in rows),
                "max_rounds": max(r["one_launch"]["rounds"] for r in rows),
                "worst_telemetry_overhead": worst_overhead,
            }
            args.json.write_text(json.dumps(payload, indent=2, default=float))
            print(f"wrote {args.json}")
        if (
            args.max_telemetry_overhead is not None
            and worst_overhead > args.max_telemetry_overhead
        ):
            raise SystemExit(
                f"warm telemetry-on overhead {worst_overhead:.1%} exceeds "
                f"--max-telemetry-overhead {args.max_telemetry_overhead:.0%}"
            )
        return
    if args.sweep:
        rows = run_sweep(
            ns=ns, ds=ds, epss=epss, tau=args.tau, n_bits=args.n_bits,
            margin=args.margin, mesh_devices=args.mesh, seed=args.seed,
            with_ari=not args.no_ari,
            chunk=args.chunk, q_tile=args.q_tile, db_tile=args.db_tile,
        )
        if args.json is not None:
            payload = {
                "rows": rows,
                "best_one_launch_speedup": max(
                    r["one_launch_speedup"] for r in rows
                ),
            }
            if args.mesh > 1:
                payload["best_pipelined_speedup"] = max(
                    r["pipelined_speedup"] for r in rows
                )
            if not args.no_ari:
                payload["worst_ari"] = min(r["ari_sweep_vs_exact"] for r in rows)
            args.json.write_text(json.dumps(payload, indent=2, default=float))
            print(f"wrote {args.json}")
        return
    rows = run(
        ns=ns, ds=ds, epss=epss, tau=args.tau, n_bits=args.n_bits,
        margin=args.margin, verify=args.verify, device=args.device,
        mesh_devices=args.mesh, seed=args.seed,
    )
    print(summarize(rows))
    if args.json is not None:
        payload = {
            "rows": rows,
            "worst_recall": min(r["recall"] for r in rows),
            "worst_ari": min(r["ari_rp_vs_exact"] for r in rows),
            "best_sweep_speedup": max(r["sweep_speedup"] for r in rows),
        }
        if args.mesh > 1:
            payload["mesh_summary"] = {
                "mesh": args.mesh,
                "sweep_sharded_s": sum(r["sweep_rp_s"] for r in rows),
                "sweep_single_device_s": sum(r["sweep_rp_single_s"] for r in rows),
            }
        args.json.write_text(json.dumps(payload, indent=2, default=float))
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
