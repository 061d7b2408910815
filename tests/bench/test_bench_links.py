"""The metric of the rescue's merge input, ``rescue.links``: its reader
on a hand-made record, and the tiny cell's traced run reading it."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("calls,links,want", [
    (2, 61_001, 61_001 / 2),
    (3, 0, 0.0),
    (1, 22_273, 22_273.0),
])
def test_links_reader_sums_per_call(calls, links, want):
    import registry

    reader = registry.load_module("metrics", "rescue.links")
    rec = {"calls": calls, "n": 1, "d": 1, "n_bits": 32, "n_exec": 0, "device": None,
           "spans": {"laf.rescue.merge": [0.25] * calls},
           "counters": {"laf.rescue.links": links, "laf.rescue.pairs": 3 * links}}
    assert reader.read(rec) == pytest.approx(want)
    # the parent program has no such counter: the reader says nothing
    del rec["counters"]["laf.rescue.links"]
    assert reader.read(rec) is None
    assert reader.read({**rec, "calls": 0, "counters": {"laf.rescue.links": links}}) is None


def test_links_metric_is_appended_to_the_rescue_layer():
    entry = MAN["per_layer"][-1]
    assert entry == {"name": "rescue.links", "unit": "links", "better": "lower",
                     "source": "program_counter", "layer": "rescue",
                     "moves": "cluster_s",
                     "workloads": ["ms150k.spread", "glove150k.spread"]}


def test_traced_run_reads_the_links_metric(tiny_run):
    res = tiny_run(seed=4243, trace=1)
    m = res["metrics"]
    assert res["info"]["n_rescued"] > 0
    # at least one (cluster, point) incidence per point merged, and no
    # more than the hits they come from
    assert m["rescue.merged"]["value"] <= m["rescue.links"]["value"]
    assert m["rescue.links"]["value"] <= m["rescue.pairs"]["value"]
