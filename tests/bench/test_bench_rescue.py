"""The metrics of the rescue's parts and of the database uploads: each
reader on a hand-made record, the idle time of a hand-made trace going to
the innermost of those spans, and the tiny cell's traced run reading them."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())

DEV = "/device:TPU:0"
KERNELS = {"hamming_filter": (("hamming_filter_pallas",), ()),
           "label_prop": (("label_prop_rect_pallas", "col_reduce_pallas"), ())}

# a hand-made traced record of 2 calls: 3 blocks in one, 2 in the other
SPANS = {"laf.rescue.sweep": [0.5, 0.25, 0.25, 1.0, 0.5],
         "laf.rescue.emap": [1.0, 1.0, 1.0, 2.0, 2.0],
         "laf.rescue.merge": [0.25, 0.75],
         "laf.upload": [0.125, 0.125, 0.25, 0.25, 0.125, 0.125],
         "laf.assemble": [0.5, 0.25]}
COUNTERS = {"laf.rescue.pairs": 3_000_001, "laf.rescue.visits": 40_001,
            "laf.rescue.merged": 22_273, "index.upload.bytes": 1_900_000_000}


def _ev(plane, name, t0, dur):
    return {"plane": plane, "name": name, "t0": t0, "dur": dur}


@pytest.mark.parametrize("name,want,reads", [
    ("rescue.sweep_ms", 1e3 * 2.5 / 2, "laf.rescue.sweep"),
    ("rescue.emap_ms", 1e3 * 7.0 / 2, "laf.rescue.emap"),
    ("rescue.merge_ms", 1e3 * 1.0 / 2, "laf.rescue.merge"),
    ("upload_ms", 1e3 * 1.0 / 2, "laf.upload"),
    ("rescue.pairs", 3_000_001 / 2, "laf.rescue.pairs"),
    ("upload.bytes", 0.95, "index.upload.bytes"),
    ("rescue.visits", 40_001 / 2, "laf.rescue.visits"),
    ("rescue.merged", 22_273 / 2, "laf.rescue.merged"),
    ("assemble_ms", 1e3 * 0.75 / 2, "laf.assemble"),
])
def test_rescue_and_upload_readers_sum_per_call(name, want, reads):
    import registry

    reader = registry.load_module("metrics", name)
    rec = {"calls": 2, "n": 1, "d": 1, "n_bits": 32, "n_exec": 0, "device": None,
           "spans": dict(SPANS), "counters": dict(COUNTERS)}
    assert reader.read(rec) == pytest.approx(want)
    # the parent program has no such span or counter: the reader says nothing
    rec["spans"].pop(reads, None)
    rec["counters"].pop(reads, None)
    assert reader.read(rec) is None
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["ms150k.spread", "glove150k.spread"]
    assert entry["better"] == "lower" and entry["moves"] == "cluster_s"


def test_idle_goes_to_the_innermost_rescue_and_upload_spans():
    import trace_reduce

    events = [
        _ev("/host:CPU", "bench.call", 0.0, 20.0),             # window [0, 20)
        _ev("/host:CPU", "laf.cluster", 0.0, 20.0),
        _ev("/host:CPU", "laf.pass1", 0.0, 4.0),
        _ev("/host:CPU", "laf.sweep", 0.0, 4.0),
        _ev("/host:CPU", "laf.upload", 0.5, 1.0),              # idle [0.5, 1.5)
        _ev("/host:CPU", "laf.upload", 1.5, 1.0),              # idle [1.5, 2.5)
        _ev(DEV, "while.4", 2.5, 1.5),                          # busy [2.5, 4)
        _ev("/host:CPU", "laf.postprocess", 5.0, 14.0),
        _ev("/host:CPU", "laf.rescue.sweep", 5.0, 2.0),
        _ev(DEV, "hamming_filter_pallas.1", 5.5, 1.0),          # busy [5.5, 6.5)
        _ev("/host:CPU", "laf.rescue.emap", 7.0, 8.0),
        _ev("/host:CPU", "laf.rescue.merge", 15.5, 3.0),
    ]
    out = trace_reduce.reduce_events(events, 1, KERNELS)
    assert out["window_s"] == pytest.approx(20.0) and out["busy_s"] == pytest.approx(2.5)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "laf.sweep": 0.5,                      # [0, 0.5) before the first copy
        "laf.upload": 2.0,
        "laf.cluster": 2.0,                    # [4, 5) and [19, 20)
        "laf.rescue.sweep": 1.0,               # [5, 5.5) and [6.5, 7)
        "laf.rescue.emap": 8.0,
        "laf.rescue.merge": 3.0,
        "laf.postprocess": 1.0,                # [15, 15.5) and [18.5, 19)
    })
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(out["window_s"])


def test_traced_run_reads_the_rescue_and_upload_metrics(tiny_run):
    res = tiny_run(seed=4242, trace=1)
    m = res["metrics"]
    assert res["info"]["n_rescued"] > 0
    assert m["rescue.points"]["value"] == res["info"]["n_rescued"]
    for name in ("rescue.sweep_ms", "rescue.emap_ms", "rescue.merge_ms", "upload_ms",
                 "assemble_ms"):
        assert m[name]["value"] > 0
    assert m["rescue.visits"]["value"] >= m["rescue.points"]["value"]
    assert 0 < m["rescue.merged"]["value"] <= m["rescue.points"]["value"]
    parts = sum(m[k]["value"] for k in ("rescue.sweep_ms", "rescue.emap_ms",
                                        "rescue.merge_ms"))
    assert parts <= m["rescue_ms"]["value"]
    assert m["rescue.pairs"]["value"] >= m["rescue.points"]["value"]
    assert m["upload.bytes"]["value"] > 2 * 1024 * 32 * 4 / 1e9
