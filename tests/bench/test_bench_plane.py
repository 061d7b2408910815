"""The four-chip cell's files, and a tiny copy of it run through the
harness on a forced 4-host-device mesh: the ``cluster_plane`` driver
builds every index on the sharded plane, and the run agrees with the
plain reference and with the one-device run of the same seed."""

import json
from pathlib import Path

import pytest
from conftest import COMPARED, TINY_CELL, _tiny_tree

ROOT = Path(__file__).resolve().parents[2]

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
PLANE_CELL = "tiny.spread8_plane"


def test_the_cell_runs_on_four_chips_through_the_plane_driver():
    import registry

    cell = registry.workload("ms480k.spread_plane", MAN)
    assert cell["chips"] == 4 and cell["config"] == "ms480k"
    traffic = registry.load_json("traffic", cell["traffic"])
    assert traffic["driver"] == "cluster_plane"
    spread = registry.load_json("traffic", "spread")
    assert {k: v for k, v in traffic.items() if k not in ("why", "driver")} == \
        {k: v for k, v in spread.items() if k not in ("why", "driver")}
    assert set(registry.load_json("limits", cell["name"])) - {"why"} == set(COMPARED)


def test_the_configuration_is_ms150k_at_480000_rows():
    import registry

    big, small = registry.load_json("configs", "ms480k"), registry.load_json("configs", "ms150k")
    assert big["n"] == 480_000
    for k in ("d", "dtype", "eps", "tau", "alpha", "n_bits", "margin", "projection_seed",
              "rmi_train_rows", "rmi_epochs", "rmi_eps_grid", "precision"):
        assert big[k] == small[k], k
    entry = next(c for c in MAN["configs"] if c["name"] == "ms480k")
    assert entry["source"] == big["source"] and len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(big["reduced"]) == {"n", "rmi_train_rows", "rmi_epochs"}


_SNIPPET = """
import copy, functools, sys
from pathlib import Path
BENCH = Path({bench!r})
sys.path.insert(0, str(BENCH))
import run
from repro.index import RandomProjectionBackend
from repro.obs import metrics

man = json.loads({man!r})
F32 = {{"rmi": "f32", "proj": "f32", "verify": "f32"}}
make = functools.partial(RandomProjectionBackend, device=True, interpret=True)
labels, out = {{}}, {{}}


def keep_labels(cell):
    def hook(outs, *rest):
        labels[cell] = [o["labels"].tolist() for o in outs]
        return {{}}
    return hook


for cell in ({one!r}, {plane!r}):
    ctx = run.context(cell, seed=2**31 + 29, seconds=0.0, trace=0, make_backend=make,
                      precision=F32, man=copy.deepcopy(man), bench=BENCH)
    ctx.on_reference = keep_labels(cell)
    before = metrics.counter("plane.collective.bytes").value
    res = run.run_cell(ctx, man, {{"platform": "cpu", "kind": "TPU v5 lite", "count": 4}},
                       bench=BENCH)
    out[cell] = {{"correct": res["correct"], "checks": res["checks"],
                 "metrics": sorted(res["metrics"]), "n_rescued": res["info"]["n_rescued"],
                 "collective_bytes": metrics.counter("plane.collective.bytes").value - before}}
out["same_labels"] = labels[{one!r}] == labels[{plane!r}]
print("RESULT:" + json.dumps(out))
"""


def test_tiny_plane_cell_matches_the_reference_and_one_device(tmp_path, forced_device_run):
    bench, man = _tiny_tree(tmp_path)
    mix = json.loads((bench / "traffic" / "spread8.json").read_text())
    mix["driver"] = "cluster_plane"
    (bench / "traffic" / "spread8_plane.json").write_text(json.dumps(mix))
    limits = {k: {"limit": 0} for k in COMPARED}
    (bench / "limits" / f"{PLANE_CELL}.json").write_text(json.dumps(limits))
    man["workloads"].append({"name": PLANE_CELL, "config": "tiny", "traffic": "spread8_plane",
                             "chips": 4, "why": "test size"})
    out = forced_device_run(_SNIPPET.format(bench=str(bench), man=json.dumps(man),
                                            one=TINY_CELL, plane=PLANE_CELL), timeout=600)
    plane, one = out[PLANE_CELL], out[TINY_CELL]
    assert plane["collective_bytes"] > 0 and one["collective_bytes"] == 0
    assert plane["n_rescued"] == one["n_rescued"] > 0
    assert out["same_labels"]
    checks = plane["checks"]
    assert all(checks[k]["value"] == 0 for k in COMPARED), checks
    for guard in ("kernels_missing", "degraded_events", "device_get_gap"):
        assert checks[guard]["value"] == 0, guard
    # on the CPU the kernels are interpreted, which the guard refuses:
    # that guard alone keeps the tiny run from reading correct
    assert checks["kernels_interpreted"]["value"] > 0 and plane["correct"] is False
    assert plane["metrics"] == ["cluster_s", "peak_hbm_gb", "setup_s"]
