"""``correct`` comes out false when the timed path is broken underneath,
and for the control: the reference one precision step lower put in the
program's place, judged by the driver's own ``judge``.

Each fault is planted here, under the harness, which runs as it does on
the chip (the kernels interpreted, the reference in float32)."""

import numpy as np
import pytest

from conftest import COMPARED


def _altered_answer(monkeypatch):
    """An answer altered where it is produced: the members of the
    smallest cluster come out as noise."""
    import importlib

    ld = importlib.import_module("repro.core.laf_dbscan")
    real = ld._rescue_and_finish

    def merged(*a, **kw):
        res = real(*a, **kw)
        ids, counts = np.unique(res.labels[res.labels >= 0], return_counts=True)
        res.labels[res.labels == ids[np.argmin(counts)]] = -1
        return res

    monkeypatch.setattr(ld, "_rescue_and_finish", merged)
    return "noise_mismatch"


def _half_batch(monkeypatch):
    """Half of the batch left out: the second half of the executed rows
    get no hits in the sweep's slab."""
    from repro.index.random_projection import RandomProjectionBackend

    real = RandomProjectionBackend.query_bitmap_device

    def half(self, rows, eps):
        slab, plan = real(self, rows, eps)
        return slab.at[len(rows) // 2 : len(rows)].set(0), plan

    monkeypatch.setattr(RandomProjectionBackend, "query_bitmap_device", half)
    return "core_mismatch"


def _skip_rule(monkeypatch):
    """The RMI's answer altered where it is produced: every prediction
    halved, so the skip rule executes fewer points."""
    from repro.core.cardinality.training import TrainedEstimator

    real = TrainedEstimator.predict_counts
    monkeypatch.setattr(TrainedEstimator, "predict_counts",
                        lambda self, q, e, **kw: 0.5 * real(self, q, e, **kw))
    return "skip_mismatch"


def _labels_unchanged(monkeypatch):
    """A step that returns its state unchanged: label propagation hands
    back its first labels (every row its own component), so no cluster
    grows past a core point and the points that hang off it."""
    import jax.numpy as jnp

    import repro.kernels.label_prop as lp

    real = lp.packed_cluster_labels

    def unchanged(*a, **kw):
        rep, *rest = real(*a, **kw)
        return (jnp.arange(rep.shape[0], dtype=rep.dtype), *rest)

    monkeypatch.setattr(lp, "packed_cluster_labels", unchanged)
    return "member_mismatch"


@pytest.mark.parametrize("plant", [_altered_answer, _half_batch, _skip_rule,
                                   _labels_unchanged])
def test_a_planted_fault_makes_the_run_incorrect(tiny_run, monkeypatch, plant):
    caught = plant(monkeypatch)
    res = tiny_run(seed=777)
    assert res["correct"] is False
    assert res["checks"][caught]["value"] > res["checks"][caught]["limit"]
    assert res["failed"] == res["attempted"]


def test_the_control_is_not_correct(tiny_run, tiny_tree):
    """The reference in the control's precision, in the program's place
    and judged as the program is: ``correct`` comes out false."""
    import registry
    import reference

    bench, _ = tiny_tree
    cfg = registry.load_json("configs", "tiny", bench)
    drv = registry.load_module("drivers", "cluster_loop", bench)
    limits = {k: v["limit"] for k, v in registry.load_json("limits", "tiny.spread8",
                                                           bench).items()}
    seen = {}

    def control(outs, ref, corpus, params, z_max):
        alt = reference.cluster(corpus, cfg, params, z_max, reference.CONTROL)
        seen["verdict"] = drv.judge([drv.readings(alt, ref)], {}, limits)
        return {}

    res = tiny_run(seed=31337, on_reference=control)
    assert all(res["checks"][k]["value"] == 0 for k in COMPARED)
    assert seen["verdict"] == (False, 1)
