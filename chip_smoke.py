"""On-chip smoke run of the LAF-DBSCAN main path at the MS-150k width.

    python chip_smoke.py               # one chip, the whole LAF path
    python chip_smoke.py --four-chips  # the sharded index plane on 4 chips

Default (one chip): a seeded MS-150k-shaped corpus (152,185 x 768 f32,
paper Table 1) of vMF clusters plus 35% uniform noise; the RMI is trained
through ``LAFPipeline(backend="random_projection")`` on a separate draw
from the same mixture, then ``cluster_laf_dbscan(eps=0.55, tau=5,
alpha=1.5)`` clusters the whole corpus: RMI predict -> index fit ->
one-launch hamming_filter sweep -> packed label propagation ->
Algorithm-3 rescue.  Then it checks, and fails on any miss:

  (a) the device hit predicate equals the host oracle (``device=False``)
      bit for bit on a seeded sample of executed rows against all n;
  (b) device cluster formation gives the labels of the host union-find
      pass (``cluster_device=False``) on the same backend and
      predictions, label for label;
  (c) prints the ARI against the exact backend (the plain matmul
      reference) with the same predictions;

and that the platform is a TPU, no Pallas kernel was built for the
interpreter, no ``stream.degraded.*`` counter moved,
``laf.cluster.device_get`` is exactly 1, and a forced device fault
raises instead of being hidden.

``--four-chips`` runs only the sharded path and what it is compared with:
the same corpus through ``RandomProjectionBackend(mesh=<4-chip mesh>)``,
whose labels must equal the one-chip device labels.

The last line of standard output is one JSON object with ``"ok": true``
and the device as JAX reports it.  Without an accelerator, or without
the repository's ``src/`` beside this file, the script exits nonzero and
prints no result.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# MS-150k operating point (paper Table 1; configs/laf_dbscan.make_config)
N, D = 152_185, 768
N_CLUSTERS = 100            # vMF mixture components of the synthetic corpus
NOISE_FRAC = 0.35
N_TRAIN = 16_384            # the RMI's separate draw from the same mixture
EPOCHS = 2                  # RMI training is outside the timed path
EPS, TAU, ALPHA = 0.55, 5, 1.5
PARITY_ROWS = 2048          # executed rows in the bit-for-bit check (a)


class SmokeFailure(Exception):
    pass


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(msg: str) -> None:
    print(msg, flush=True)


class _Phases:
    """Wall time per phase, printed as each one ends."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        self.seconds[name] = dt = time.perf_counter() - t0
        _log(f"phase {name}: {dt:.3f} s")


def _watch_pallas_builds(builds: list) -> None:
    """Record (kernel name, interpret flag) of every Pallas kernel traced
    in this process, so the run can prove no kernel was interpreted."""
    from jax.experimental import pallas

    real = pallas.pallas_call

    def recording(kernel, *args, interpret=False, **kw):
        name = getattr(getattr(kernel, "func", kernel), "__name__", repr(kernel))
        builds.append((name, bool(interpret)))
        return real(kernel, *args, interpret=interpret, **kw)

    pallas.pallas_call = recording


def _corpus(seed: int):
    """(corpus, train): disjoint seeded draws from one MS-150k-shaped
    mixture — the corpus is clustered, the train draw fits the RMI."""
    import numpy as np

    from repro.data.synthetic import make_angular_clusters

    data, _ = make_angular_clusters(
        N + N_TRAIN, D, N_CLUSTERS, kappa=(D - 1) / 0.3,
        noise_frac=NOISE_FRAC, seed=seed,
    )
    perm = np.random.default_rng(seed + 1).permutation(len(data))
    return data[perm[:N]], data[perm[N:]]


def _trained_pipeline(train, seed: int):
    from repro.core.pipeline import LAFPipeline

    return LAFPipeline(backend="random_projection", epochs=EPOCHS, seed=seed).fit(train)


def _counters(prefix: str) -> dict:
    from repro.obs import metrics

    return {k: v for k, v in metrics.snapshot(prefix).items() if isinstance(v, int)}


def one_chip(seed: int, phase) -> dict:
    import jax
    import numpy as np

    from repro.core.metrics import adjusted_rand_index
    from repro.index import ExactBackend, RandomProjectionBackend
    from repro.obs import metrics
    from repro.testing import faults

    with phase("data"):
        corpus, train = _corpus(seed)
    with phase("rmi_train"):
        pipe = _trained_pipeline(train, seed)

    bk = RandomProjectionBackend()  # device="auto", on_device_fault="raise"
    gets0 = metrics.counter("laf.cluster.device_get").value
    with phase("cluster_device"):
        out = pipe.cluster_laf_dbscan(corpus, EPS, TAU, ALPHA, backend=bk)
    res = out.result
    device_gets = metrics.counter("laf.cluster.device_get").value - gets0
    rounds = metrics.counter("laf.cluster.rounds").value
    _require(bk.use_device and bk.packs_natively, "backend did not take the device path")
    _require(device_gets == 1, f"laf.cluster.device_get = {device_gets}, expected 1")
    summary = {
        "n": N, "d": D,
        "n_exec": int(res.extras["n_predicted_core"]),
        "n_rescued": int(res.extras["n_rescued"]),
        "n_clusters": int(res.n_clusters),
        "rounds": int(rounds),
        "predict_s": out.predict_s,
        "cluster_s": out.elapsed_s,
    }

    with phase("parity_hits"):
        pred = pipe.predict_counts(corpus, EPS)
        exec_idx = np.nonzero(pred >= ALPHA * TAU)[0]
        _require(len(exec_idx) == summary["n_exec"], "predictions are not deterministic")
        rows = np.sort(
            np.random.default_rng(seed + 2).choice(
                exec_idx, min(PARITY_ROWS, len(exec_idx)), replace=False
            )
        )
        dev_hits = bk.query_hits(rows, EPS)
        host = RandomProjectionBackend(device=False).fit(corpus)
        host_hits = host.query_hits(rows, EPS)
        mismatch = int(np.count_nonzero(dev_hits != host_hits))
        _log(f"parity (a): {len(rows)} rows x {N} cols, "
             f"{int(host_hits.sum())} hits, {mismatch} mismatches")
        _require(mismatch == 0, f"(a) device hits differ from the host oracle at {mismatch} pairs")
        del dev_hits, host_hits, host

    with phase("parity_host_cluster"):
        host_res = pipe.cluster_laf_dbscan(
            corpus, EPS, TAU, ALPHA, backend=bk, cluster_device=False
        ).result
        differ = int(np.count_nonzero(host_res.labels != res.labels))
        _log(f"parity (b): {differ} labels differ from the host union-find pass")
        _require(differ == 0, f"(b) device labels differ from the host pass at {differ} points")

    with phase("exact_reference"):
        ex = pipe.cluster_laf_dbscan(
            corpus, EPS, TAU, ALPHA, backend=ExactBackend()
        ).result
        summary["ari_vs_exact"] = float(adjusted_rand_index(ex.labels, res.labels))
        _log(f"(c) ARI vs exact backend: {summary['ari_vs_exact']!r}")

    with phase("forced_fault"):
        try:
            with faults.inject(f"seed={seed},sweep.launch=1.0"):
                bk.query_counts(rows[:256], EPS)
        except faults.InjectedFault:
            pass
        else:
            raise SmokeFailure("a forced device fault was not raised")

    summary["labels"] = res.labels
    summary["compile_s"] = metrics.counter("jax.compile.ms").value / 1e3
    summary["compiles"] = metrics.counter("jax.compile.events").value
    stats = jax.devices()[0].memory_stats() or {}
    summary["peak_device_bytes"] = stats.get("peak_bytes_in_use", "not reported")
    return summary


def four_chips(seed: int, phase) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.index import RandomProjectionBackend
    from repro.obs import metrics

    devices = jax.devices()
    _require(len(devices) >= 4, f"--four-chips needs 4 chips, JAX sees {len(devices)}")
    with phase("data"):
        corpus, train = _corpus(seed)
    with phase("rmi_train"):
        pipe = _trained_pipeline(train, seed)

    gets0 = metrics.counter("laf.cluster.device_get").value
    with phase("cluster_one_chip"):
        one = pipe.cluster_laf_dbscan(
            corpus, EPS, TAU, ALPHA, backend=RandomProjectionBackend()
        ).result
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    plane = RandomProjectionBackend(mesh=mesh)
    with phase("cluster_four_chips"):
        out = pipe.cluster_laf_dbscan(corpus, EPS, TAU, ALPHA, backend=plane)
    four = out.result
    device_gets = metrics.counter("laf.cluster.device_get").value - gets0
    _require(device_gets == 2, f"laf.cluster.device_get = {device_gets}, expected 2")
    _require(plane._plan.n_shards == 4, "the plane is not sharded over 4 chips")
    differ = int(np.count_nonzero(four.labels != one.labels))
    _log(f"four chips vs one chip: {differ} labels differ")
    _require(differ == 0, f"sharded labels differ from the one-chip labels at {differ} points")
    return {
        "n": N, "d": D, "shards": plane._plan.n_shards,
        "n_exec": int(four.extras["n_predicted_core"]),
        "n_rescued": int(four.extras["n_rescued"]),
        "n_clusters": int(four.n_clusters),
        "rounds_both": metrics.counter("laf.cluster.rounds").value,
        "cluster_s": out.elapsed_s,
        "compile_s": metrics.counter("jax.compile.ms").value / 1e3,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded index plane on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms:
        print(f"chip_smoke: JAX_PLATFORMS={platforms} excludes the TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})", file=sys.stderr)
        return 2
    _log(f"device: {dev.device_kind} x{len(devices)}; compile cache: {cache}")

    builds: list = []
    _watch_pallas_builds(builds)
    from repro import obs
    from repro.kernels.hamming_filter.ops import default_interpret

    obs.enable(trace=False)
    phase = _Phases()
    try:
        _require(not default_interpret(), "kernels would run in the interpreter")
        run = four_chips if args.four_chips else one_chip
        summary = run(args.seed, phase)
        summary.pop("labels", None)
        kernels = sorted({name for name, _ in builds})
        interpreted = sorted({name for name, interp in builds if interp})
        _log(f"pallas kernels built: {kernels}")
        _require(not interpreted, f"kernels built for the interpreter: {interpreted}")
        needed = {"_filter_kernel", "_label_prop_kernel", "_col_reduce_kernel"}
        _require(needed <= set(kernels), f"main-path kernels not built: {sorted(needed - set(kernels))}")
        degraded = {k: v for k, v in _counters("stream.degraded").items() if v}
        _require(not degraded, f"device path degraded to the host: {degraded}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    summary["phase_s"] = phase.seconds
    _log("summary: " + json.dumps(summary, default=str))
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
