"""One tiny cell through the harness's own window, guards, reference and
metric code on the CPU, with the kernels interpreted (steered here)."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
from conftest import COMPARED


def test_untraced_run_reports_the_end_to_end_metrics_and_matches_the_reference(tiny_run):
    res = tiny_run(seed=2**31 + 17, fresh=True)
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in MAN["end_to_end"]}
    assert all(v["value"] > 0 for k, v in res["metrics"].items() if k != "peak_hbm_gb")
    checks = res["checks"]
    assert list(checks)[-len(COMPARED):] == list(COMPARED)
    assert all(checks[k]["value"] == 0 for k in COMPARED), checks
    assert checks["kernels_missing"]["value"] == 0
    assert checks["degraded_events"]["value"] == 0
    assert checks["device_get_gap"]["value"] == 0
    # on the CPU the kernels are interpreted, which the guard refuses
    assert checks["kernels_interpreted"]["value"] > 0 and res["correct"] is False
    assert res["info"]["n_rescued"] == res["info"]["ref_n_rescued"]
    assert list(res)[-1] == "checks"


def test_traced_run_reads_the_span_and_counter_metrics(tiny_run):
    res = tiny_run(seed=4242, trace=1)
    m = res["metrics"]
    for name in ("predict_ms", "fit_index_ms", "rescue_ms"):
        assert m[name]["value"] > 0
    assert 0 < m["rmi.exec_share"]["value"] <= 100
    assert m["rescue.points"]["value"] == res["info"]["n_rescued"]
    # no TPU plane in a CPU trace: the device metrics stay silent
    for name in ("sweep.kernel_ms", "hamming_filter_roofline", "label_prop.kernel_ms",
                 "label_prop_roofline", "device.idle"):
        assert name not in m
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0.0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(res["checks"][k]["value"] == 0 for k in COMPARED)
