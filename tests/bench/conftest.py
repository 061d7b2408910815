"""Shared set-up of the benchmark's tests: ``bench/`` on the import
path, and a tiny cell built beside a copy of ``bench/`` in a temporary
directory (the real cells are the chip's size)."""

import copy
import functools
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

TINY_CELL = "tiny.spread8"
COMPARED = ("skip_mismatch", "core_mismatch", "noise_mismatch", "member_mismatch")
# every stage in float32: what XLA computes on the CPU at default precision
F32 = {"rmi": "f32", "proj": "f32", "verify": "f32"}


def _tiny_tree(dest: Path) -> tuple[Path, dict]:
    bench = dest / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((BENCH / "configs" / "ms150k.json").read_text())
    cfg.update(name="tiny", n=1024, d=32, rmi_train_rows=384, rmi_epochs=1)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "spread.json").read_text())
    mix["n_clusters"] = 8
    (bench / "traffic" / "spread8.json").write_text(json.dumps(mix))
    limits = {k: {"limit": 0} for k in COMPARED}
    (bench / "limits" / f"{TINY_CELL}.json").write_text(json.dumps(limits))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": TINY_CELL, "config": "tiny", "traffic": "spread8",
                             "chips": 1, "why": "test size"})
    for m in man["per_layer"]:
        m.setdefault("workloads", []).append(TINY_CELL)
    return bench, man


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    return _tiny_tree(tmp_path_factory.mktemp("tiny_bench"))


@pytest.fixture
def tiny_run(tiny_tree):
    """Run the tiny cell through the harness on the CPU: the backend's
    device path with interpreted kernels, the reference in float32.
    The observability switches the run turns on are put back after."""
    import jax

    import run
    from repro import obs
    from repro.index import RandomProjectionBackend

    bench, man = tiny_tree
    make = functools.partial(RandomProjectionBackend, device=True, interpret=True)

    def go(seed=12345, trace=0, seconds=0.0, precision=F32, on_reference=None,
           fresh=False):
        if fresh:  # build (and so watch) every kernel anew, as a new process does
            jax.clear_caches()
        ctx = run.context(TINY_CELL, seed=seed, seconds=seconds, trace=trace,
                          make_backend=make, precision=precision,
                          man=copy.deepcopy(man), bench=bench)
        ctx.on_reference = on_reference
        device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
        return run.run_cell(ctx, man, device, bench=bench)

    state = obs._trace_state
    was = (obs.trace_enabled(), obs.metrics_enabled(), state.jax_annotations)
    yield go
    obs.enable(trace=was[0], metrics_on=was[1])
    state.jax_annotations = was[2]
    obs.clear_trace()
