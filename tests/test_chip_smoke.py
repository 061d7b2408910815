"""``chip_smoke.py`` on the CPU: its refusal without a TPU, and its one-
and four-chip phases at a tiny size with the kernels interpreted.

The phases take the backend's device path (``default_interpret`` is
steered here, in the test) while every Pallas kernel still runs in the
interpreter; the script's own TPU gate is what ``main`` checks on the
chip.
"""

import importlib.util
from pathlib import Path

import pytest

from repro import obs
from repro.obs import metrics

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(N=3000, D=64, N_TRAIN=1024, N_CLUSTERS=8, PARITY_ROWS=512)


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for k, v in TINY.items():
        monkeypatch.setattr(mod, k, v)
    return mod


def test_smoke_refuses_without_tpu(smoke, monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_one_chip_phases_tiny(smoke, monkeypatch):
    import repro.index.random_projection as rp
    from jax.experimental import pallas

    monkeypatch.setattr(rp, "default_interpret", lambda: False)
    monkeypatch.setattr(pallas, "pallas_call", pallas.pallas_call)
    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    obs.enable(trace=False)
    try:
        builds = []
        smoke._watch_pallas_builds(builds)
        summary = smoke.one_chip(0, smoke._Phases())
        assert summary["n_exec"] > 0 and summary["ari_vs_exact"] == 1.0
        assert {"_filter_kernel", "_label_prop_kernel", "_col_reduce_kernel"} <= {
            name for name, _ in builds
        }
        assert not any(smoke._counters("stream.degraded").values())
    finally:
        metrics.reset()
        if was_trace or was_metrics:
            obs.enable(trace=was_trace, metrics_on=was_metrics)
        else:
            obs.disable()


def test_smoke_four_chips_phases_tiny(forced_device_run):
    out = forced_device_run(
        f"""
        import chip_smoke as cs
        import repro.index.random_projection as rp
        from repro import obs

        for k, v in {TINY!r}.items():
            setattr(cs, k, v)
        rp.default_interpret = lambda: False
        obs.enable(trace=False)
        s = cs.four_chips(0, cs._Phases())
        print("RESULT:" + json.dumps({{"shards": s["shards"], "n_exec": s["n_exec"]}}))
        """,
        n_devices=4,
    )
    assert out["shards"] == 4 and out["n_exec"] > 0
