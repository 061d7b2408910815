"""The benchmark's files: the manifest, and every configuration, traffic
mix, driver, kernel byte count, metric reader and set of limits found by
the name the manifest gives it."""

import json
import re
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_has_the_contract_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert {m["name"] for m in MAN["end_to_end"]} == {"cluster_s", "peak_hbm_gb", "setup_s"}
    assert all(0 < m["bound"] <= 0.25 for m in MAN["end_to_end"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MAN[group]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    entry_keys = {"configs": {"name", "source", "file", "reduced", "why"},
                  "workloads": {"name", "config", "traffic", "chips", "why"},
                  "end_to_end": {"name", "unit", "better", "bound", "source"},
                  "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for group, keys in entry_keys.items():
        for e in MAN[group]:
            assert set(e) - {"workloads"} == keys, (group, e["name"])
            texts = [e.get("why"), e.get("layer")]
            texts += [e["source"]] if group == "configs" else []
            for t in filter(None, texts):
                assert len(t) <= 200 and "\n" not in t and "\t" not in t
    configs = {c["name"] for c in MAN["configs"]}
    assert {w["config"] for w in MAN["workloads"]} == configs
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert m["moves"] == "cluster_s" and set(m["workloads"]) <= cells


def _named():
    out = [("configs", c["name"]) for c in MAN["configs"]]
    out += [("traffic", w["traffic"]) for w in MAN["workloads"]]
    out += [("traffic", "tight")]
    out += [("limits", w["name"]) for w in MAN["workloads"]]
    return out


@pytest.mark.parametrize("kind,name", _named())
def test_data_file_loads_by_name(kind, name):
    import registry

    data = registry.load_json(kind, name)
    assert data
    if kind == "configs":
        entry = next(c for c in MAN["configs"] if c["name"] == name)
        assert (ROOT / entry["file"]).resolve() == BENCH / "configs" / f"{name}.json"
        assert set(entry["reduced"]) == set(data["reduced"]) <= set(data)


@pytest.mark.parametrize("name", [m["name"] for m in MAN["per_layer"]])
def test_metric_reader_loads_and_reads_nothing_from_an_empty_run(name):
    import registry

    rec = {"calls": 0, "n": 1, "d": 1, "n_bits": 32, "n_exec": 0, "spans": {},
           "counters": {}, "device": None}
    assert registry.load_module("metrics", name).read(rec) is None


def test_new_files_are_found_without_editing_any(tmp_path):
    import registry
    import trace_reduce

    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "traffic" / "later.json").write_text('{"driver": "cluster_loop"}')
    (bench / "metrics" / "later.metric.py").write_text("def read(rec):\n    return 1.0\n")
    (bench / "kernels" / "later_kernel.py").write_text('EVENTS = ("_later",)\n')
    assert registry.load_json("traffic", "later", bench) == {"driver": "cluster_loop"}
    assert registry.load_module("metrics", "later.metric", bench).read({}) == 1.0
    assert registry.load_module("kernels", "later_kernel", bench).EVENTS == ("_later",)
    assert set(trace_reduce.kernel_sites()) == {"hamming_filter", "label_prop"}
    assert trace_reduce.kernel_sites(bench)["later_kernel"] == (("_later",), ())
    with pytest.raises(FileNotFoundError):
        registry.load_json("configs", "absent", bench)


def test_byte_counts_match_hand_counts():
    import registry

    hf = registry.load_module("kernels", "hamming_filter")
    lp = registry.load_module("kernels", "label_prop")
    # 32 queries and 64 points of 8 f32 (32 B) + 64-bit signatures (8 B),
    # and a 32 x 64-bit slab (2 words a row)
    assert hf.least_bytes(n=64, d=8, n_exec=32, n_bits=64) == 32 * 40 + 64 * 40 + 32 * 8
    assert lp.least_bytes(n=64, d=8, n_exec=32, n_bits=64) == 32 * 8
    # the verify: one f32 dot of 2 d flops per pair inside the band
    assert hf.least_flops(d=8, band_pairs=5) == 80
    # a partial last word still costs a whole word
    assert lp.least_bytes(n=65, d=8, n_exec=1, n_bits=64) == 12


def test_peaks_are_keyed_by_device_kind():
    import peaks

    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
