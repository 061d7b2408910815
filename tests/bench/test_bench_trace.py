"""The reduction from a profiler trace to device numbers, on a hand-made
trace with known answers and on a slice recorded on a TPU v5e chip."""

import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"

DEV = "/device:TPU:0"
KERNELS = {"hamming_filter": (("hamming_filter_pallas",), ()),
           "label_prop": (("label_prop_rect_pallas", "col_reduce_pallas"), ())}


def _ev(plane, name, t0, dur):
    return {"plane": plane, "name": name, "t0": t0, "dur": dur}


def test_hand_made_trace():
    import trace_reduce

    events = [
        _ev("/host:CPU", "bench.call", 1.0, 4.0),      # window [1, 9)
        _ev("/host:CPU", "bench.call", 5.0, 4.0),
        _ev("/host:CPU", "laf.postprocess", 6.0, 2.0),
        _ev(DEV, "hamming_filter_pallas.3", 0.5, 1.0),        # clipped to [1, 1.5)
        _ev(DEV, "fusion.1", 1.2, 0.8),                # overlaps: busy [1, 2)
        _ev(DEV, "label_prop_rect_pallas.2", 3.0, 1.0),      # [3, 4)
        _ev(DEV, "col_reduce_pallas", 4.0, 0.5),      # [4, 4.5)
        _ev(DEV, "fusion.1", 8.5, 1.0),                # clipped to [8.5, 9)
    ]
    out = trace_reduce.reduce_events(events, 1, KERNELS)
    assert out["window_s"] == pytest.approx(8.0)
    assert out["busy_s"] == pytest.approx(1.0 + 1.5 + 0.5)
    # ops starting inside the window count whole; the one before it does not
    assert out["kernels"] == pytest.approx({"hamming_filter": 0.0, "label_prop": 1.5})
    gaps = dict(out["breakdown"]["idle_gaps"])
    # idle [2, 3) and [4.5, 8.5): [6, 8) under laf.postprocess, the rest
    # under the bench.call open at the time
    assert gaps == pytest.approx({"laf.postprocess": 2.0, "bench.call": 3.0})
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(1.8)]


def test_a_kernel_can_be_timed_at_one_call_site():
    import trace_reduce

    events = [
        _ev("/host:CPU", "bench.call", 0.0, 10.0),
        _ev(DEV, "while.2", 1.0, 3.0),
        _ev(DEV, "hamming_filter_pallas.9", 1.5, 1.0),     # inside the loop
        _ev(DEV, "hamming_filter_pallas.9", 2.5, 1.0),     # inside the loop
        _ev(DEV, "hamming_filter_pallas.1", 5.0, 0.25),    # another build
        _ev(DEV, "hamming_filter_pallas.1", 3.5, 1.0),     # ends past the loop
    ]
    sites = {"hamming_filter": (("hamming_filter_pallas",), ("while",))}
    out = trace_reduce.reduce_events(events, 1, sites)
    assert out["kernels"]["hamming_filter"] == pytest.approx(3.25)
    assert out["kernels_within"]["hamming_filter"] == pytest.approx(2.0)


def test_recorded_chip_trace():
    import trace_reduce

    rec = json.loads((BENCH / "testdata" / "trace_v5e.json").read_text())
    events = rec["events"]
    out = trace_reduce.reduce_events(events, 1, trace_reduce.kernel_sites())
    w0 = min(e["t0"] for e in events if e["name"] == "bench.call")
    w1 = max(e["t0"] + e["dur"] for e in events if e["name"] == "bench.call")
    assert out["window_s"] == pytest.approx(w1 - w0)
    assert out["chips_traced"] == 1 and 0 < out["busy_s"] <= out["window_s"]
    idle = sum(s for _, s in out["breakdown"]["idle_gaps"])
    assert idle + out["busy_s"] == pytest.approx(out["window_s"], rel=1e-6)
    for kernel, (pats, _) in trace_reduce.kernel_sites().items():
        want = sum(e["dur"] for e in events
                   if e["plane"].startswith("/device:TPU:") and w0 <= e["t0"] < w1
                   and trace_reduce._base(e["name"]) in pats)
        assert want > 0 and out["kernels"][kernel] == pytest.approx(want)
    assert out["kernels"] == pytest.approx(rec["expect"]["kernels"])
    # the slab sweep's filter ops, inside its while loop: the rest of the
    # filter builds (the occupancy stats) fall outside
    whiles = [e for e in events if trace_reduce._base(e["name"]) == "while"]
    slab = sum(e["dur"] for e in events
               if trace_reduce._base(e["name"]) == "hamming_filter_pallas"
               and w0 <= e["t0"] < w1
               and any(w["t0"] <= e["t0"] and e["t0"] + e["dur"] <= w["t0"] + w["dur"]
                       for w in whiles))
    assert 0 < slab < out["kernels"]["hamming_filter"]
    assert out["kernels_within"]["hamming_filter"] == pytest.approx(slab)
    assert out["kernels_within"]["label_prop"] == pytest.approx(out["kernels"]["label_prop"])
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
