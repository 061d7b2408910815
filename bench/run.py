"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload ms150k.spread --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` -> workloads) names a configuration and a
traffic mix; the mix names the driver that sets up, runs the measured
window and checks the outputs against the plain reference.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the metrics are
the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``.

The run needs the program's ``src/`` beside ``bench/`` and as many TPU
chips as the cell asks for: without them it exits nonzero and prints no
result.  The numbers compared for ``correct`` go to standard error as
the last lines, and into the result under ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import registry  # noqa: E402


class Tracer:
    """The JAX profiler around the measured window (Python tracer off:
    the host spans come from ``TraceAnnotation``)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()


class Context:
    """What a driver gets: the cell's files, the run's arguments, and the
    hooks a test may steer (backend factory, reference precision)."""

    def __init__(self, cell, cfg, traffic, limits, *, seed, seconds, trace,
                 make_backend, precision, t_start=T_START):
        self.cell, self.cfg, self.traffic, self.limits = cell, cfg, traffic, limits
        self.chips = int(cell["chips"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.make_backend, self.precision = make_backend, precision
        self.t_start = t_start
        self.tracer = Tracer(self.trace)
        # called after the reference with (outs, ref, corpus, params,
        # z_max); what it returns joins the run's info (bench/control.py)
        self.on_reference = None


def context(name: str, *, seed, seconds, trace, make_backend=None, precision=None,
            man=None, bench=registry.BENCH) -> Context:
    man = man if man is not None else registry.manifest()
    cell = registry.workload(name, man)
    if make_backend is None:
        from repro.index import RandomProjectionBackend as make_backend
    if precision is None:
        import reference

        precision = reference.STATED
    limits = {k: v["limit"] for k, v in registry.load_json("limits", name, bench).items()
              if isinstance(v, dict)}
    return Context(
        cell, registry.load_json("configs", cell["config"], bench),
        registry.load_json("traffic", cell["traffic"], bench), limits,
        seed=seed, seconds=seconds, trace=trace, make_backend=make_backend,
        precision=precision,
    )


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def run_cell(ctx: Context, man: dict, device: dict, bench=registry.BENCH) -> dict:
    """Drive the cell and assemble the result line (a dict)."""
    driver = registry.load_module("drivers", ctx.traffic["driver"], bench)
    out = driver.run(ctx)
    name = ctx.cell["name"]
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"]}
    if not ctx.trace:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
                   for m in man["end_to_end"] if _applies(m, name)}
    else:
        import trace_reduce

        rec = dict(out["record"])
        try:
            rec["device"] = trace_reduce.reduce_dir(ctx.tracer.dir, ctx.chips)
        finally:
            shutil.rmtree(ctx.tracer.dir, ignore_errors=True)
        import peaks

        rec["peaks"] = peaks.peaks(device["kind"])
        metrics = {}
        for m in man["per_layer"]:
            if not _applies(m, name):
                continue
            v = registry.load_module("metrics", m["name"], bench).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = rec["device"]["busy_s"]
        device["window_s"] = rec["device"]["window_s"]
        result["breakdown"] = rec["device"]["breakdown"]
    result["metrics"] = metrics
    result["device"] = device
    result["info"] = out["info"]
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = registry.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    man = registry.manifest()
    chips = int(registry.workload(args.workload, man)["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX sees "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    ctx = context(args.workload, seed=args.seed, seconds=args.seconds,
                  trace=args.trace, man=man)
    result = run_cell(ctx, man, device)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
