import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cardinality.features import (
    DEFAULT_EPS_GRID,
    build_training_set,
    featurize,
    multi_eps_counts,
)
from repro.core.cardinality.rmi import (
    RMIConfig,
    init_mlp,
    init_rmi,
    mlp_apply,
    rmi_predict,
    rmi_predict_counts,
    rmi_route,
)
from repro.core.cardinality.training import train_rmi
from repro.core.range_query import range_counts
from repro.data.synthetic import make_angular_clusters, train_test_split


def test_featurize_shape():
    q = np.ones((5, 8), np.float32)
    f = np.asarray(featurize(q, 0.3))
    assert f.shape == (5, 9)
    np.testing.assert_allclose(f[:, -1], 0.3)


def test_multi_eps_counts_match_single():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((40, 8)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    grid = (0.2, 0.5, 0.8)
    multi = np.asarray(multi_eps_counts(x, x, grid, block_size=16))
    for ei, e in enumerate(grid):
        single = np.asarray(range_counts(x, x, e, block_size=16))
        np.testing.assert_array_equal(multi[ei], single)


def test_build_training_set_targets():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((30, 6)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    feats, targets = build_training_set(x, (0.3, 0.6))
    assert feats.shape == (60, 7)
    assert targets.shape == (60,)
    # targets are log2(1+count); invert and check one entry exactly
    counts = np.asarray(range_counts(x, x, 0.3))
    np.testing.assert_allclose(2.0 ** targets[:30] - 1.0, counts, rtol=1e-5)


def test_mlp_paper_architecture():
    """Paper: 4 hidden layers, widths 512, 512, 256, 128."""
    params = init_mlp(jax.random.PRNGKey(0), 65, (512, 512, 256, 128))
    assert [w.shape for w, _ in params] == [
        (65, 512), (512, 512), (512, 256), (256, 128), (128, 1),
    ]
    out = mlp_apply(params, jnp.ones((3, 65)))
    assert out.shape == (3,)


def test_rmi_stage_structure():
    """Paper: 3 stages with 1, 2, 4 nets."""
    cfg = RMIConfig(input_dim=9)
    params = init_rmi(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"stage0", "stage1", "stage2"}
    # stacked expert axes
    assert params["stage1"][0][0].shape[0] == 2
    assert params["stage2"][0][0].shape[0] == 4


def test_rmi_route_bounds():
    pred = jnp.array([-5.0, 0.0, 7.9, 8.0, 100.0])
    idx = np.asarray(rmi_route(pred, 4, 16.0))
    assert idx.min() >= 0 and idx.max() <= 3
    np.testing.assert_array_equal(idx, [0, 0, 1, 2, 3])


def test_rmi_predict_shapes():
    cfg = RMIConfig(input_dim=9)
    params = init_rmi(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((12, 9))
    z = rmi_predict(params, x, cfg)
    c = rmi_predict_counts(params, x, cfg)
    assert z.shape == (12,) and c.shape == (12,)
    assert (np.asarray(c) >= 0).all()


@pytest.mark.slow
def test_trained_estimator_learns(small_clustered):
    """Short training run: the estimator must clearly beat a constant
    predictor on its learned function (counts w.r.t. the train split —
    the paper's per-dataset α absorbs the train/test scale gap)."""
    data, _ = small_clustered
    train, test = train_test_split(data, 0.8, seed=0)
    est = train_rmi(train, epochs=8, batch_size=256, eps_grid=(0.15, 0.25, 0.35, 0.5))
    eps, tau = 0.25, 5
    pred = est.predict_counts(test, eps)
    # ground truth for unseen queries, against the db the estimator learned
    true = np.asarray(range_counts(test, train, eps)).astype(np.float64)
    z_pred = np.log2(1 + pred)
    z_true = np.log2(1 + true)
    resid = float(np.mean((z_pred - z_true) ** 2))
    const = float(np.var(z_true))
    assert resid < 0.5 * const, f"estimator MSE {resid} vs constant {const}"
    # classification quality at the paper's decision rule (scale-matched)
    scale = len(train) / len(test)
    true_test = np.asarray(range_counts(test, test, eps)).astype(np.float64)
    pred_core = pred >= scale * tau
    true_core = true_test >= tau
    acc = float(np.mean(pred_core == true_core))
    assert acc > 0.8, f"core classification accuracy {acc}"


def test_calibrated_prediction(small_clustered):
    """predict_counts(reference_n=...) rescales to the target dataset size."""
    data, _ = small_clustered
    train, test = train_test_split(data, 0.8, seed=0)
    from repro.core.cardinality.rmi import RMIConfig, init_rmi

    cfg = RMIConfig(input_dim=train.shape[1] + 1)
    # untrained params: just verify the scaling plumbing
    from repro.core.cardinality.training import TrainedEstimator

    est = TrainedEstimator(init_rmi(jax.random.PRNGKey(0), cfg), cfg)
    est.train_n = len(train)
    a = est.predict_counts(test[:8], 0.25)
    b = est.predict_counts(test[:8], 0.25, reference_n=len(test))
    np.testing.assert_allclose(b, a * len(test) / len(train), rtol=1e-5)


@pytest.mark.parametrize("eps", [0.25, "per_row"])
def test_predict_counts_runs_in_blocks(monkeypatch, eps):
    """A batch longer than ``PREDICT_BLOCK`` is predicted block by block
    and gives what one batch gives (on the CPU, where one batch is
    sound), scalar or per-row eps alike."""
    from repro.core.cardinality import training
    from repro.core.cardinality.training import TrainedEstimator

    cfg = RMIConfig(input_dim=9)
    est = TrainedEstimator(init_rmi(jax.random.PRNGKey(1), cfg), cfg)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (50, 8)), np.float32)
    e = np.linspace(0.1, 0.9, 50, dtype=np.float32) if eps == "per_row" else eps
    whole = est.predict_counts(x, e)
    batches = []
    real = training.rmi_predict_counts

    def counting(params, feats, c):
        batches.append(feats.shape[0])
        return real(params, feats, c)

    monkeypatch.setattr(training, "PREDICT_BLOCK", 16)
    monkeypatch.setattr(training, "rmi_predict_counts", counting)
    blocked = est.predict_counts(x, e)
    assert batches == [16, 16, 16, 2]
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)
    assert est.predict_counts(x[:0], e if eps != "per_row" else e[:0]).shape == (0,)
