"""Find the benchmark's parts by the names ``BENCHMARK.json`` gives.

Every configuration, traffic mix, driver, kernel byte count, per-layer
metric and set of correctness limits is a file of its own:

    bench/configs/<config>.json     sizes and settings of a deployment
    bench/traffic/<traffic>.json    parameters of a mix; its "driver" key
                                    names the module that runs it
    bench/drivers/<driver>.py       set-up, measured window, correctness
    bench/kernels/<kernel>.py       least bytes of one kernel's work
    bench/metrics/<metric>.py       reader of one per-layer metric
    bench/limits/<cell>.json        limits of the numbers compared

A later cell or metric is a new file here and an entry in
``BENCHMARK.json``; nothing that exists has to change.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = ["BENCH", "ROOT", "manifest", "workload", "load_json", "load_module"]


def manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(name: str, man: dict) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                   f"known: {[w['name'] for w in man['workloads']]}")


def load_json(kind: str, name: str, bench: Path = BENCH) -> dict:
    """``bench/<kind>/<name>.json`` (kind: configs, traffic, limits)."""
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` (kind: drivers, kernels, metrics) as a
    module; names may hold dots, so the file is loaded by its path."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
