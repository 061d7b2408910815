"""RMI estimator training — stage-by-stage, per Kraska et al. / the paper.

Paper §3.1: "On each training set, the cardinality estimator is trained
for 200 epochs with batch size 512."  Stage 0 trains on all examples;
examples are then routed by the *trained* stage-0 predictions to the
stage-1 experts, each of which trains on its share; likewise stage 2.
Loss is MSE on z = log2(1 + count).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...train.optimizer import adam, apply_updates
from .features import build_training_set, featurize
from .rmi import RMIConfig, init_mlp, mlp_apply, rmi_predict, rmi_predict_counts, rmi_route, stack_stage

__all__ = ["TrainedEstimator", "train_mlp", "train_rmi"]

# rows per RMI dispatch.  On a v5e, 480,000 rows predicted in one batch
# came back with 22,954 predictions near zero (the plain reference and
# the same rows in blocks of this size agree on all but 9 skip
# decisions); at 152,185 rows the blocks give bit-identical predictions
PREDICT_BLOCK = 16384


@dataclass
class TrainedEstimator:
    params: Dict[str, Any]
    cfg: RMIConfig
    history: Dict[str, List[float]] = field(default_factory=dict)
    train_seconds: float = 0.0
    train_n: int = 0  # size of the split the counts were learned against

    def predict_z(self, queries, eps) -> jax.Array:
        return rmi_predict(self.params, featurize(queries, eps), self.cfg)

    def predict_counts(self, queries, eps, *, reference_n: Optional[int] = None) -> np.ndarray:
        """Predicted cardinalities, in blocks of ``PREDICT_BLOCK`` rows.
        ``reference_n`` rescales from the training-split scale to a
        target dataset size (the paper instead absorbs this gap in the
        per-dataset error factor α)."""
        per_row = np.size(eps) > 1
        c = np.asarray(jnp.concatenate([
            rmi_predict_counts(
                self.params,
                featurize(queries[s : s + PREDICT_BLOCK],
                          eps[s : s + PREDICT_BLOCK] if per_row else eps),
                self.cfg)
            for s in range(0, max(len(queries), 1), PREDICT_BLOCK)
        ]))
        if reference_n is not None and self.train_n:
            c = c * (reference_n / self.train_n)
        return c


@functools.partial(jax.jit, static_argnames=("opt_update",), donate_argnums=(0, 1))
def _train_step(params, opt_state, x, y, opt_update):
    def loss_fn(p):
        pred = mlp_apply(p, x)
        return jnp.mean(jnp.square(pred - y))

    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = opt_update(grads, opt_state)
    params = apply_updates(params, updates)
    return params, opt_state, loss


def train_mlp(
    key: jax.Array,
    feats: np.ndarray,
    targets: np.ndarray,
    cfg: RMIConfig,
    *,
    epochs: int,
    batch_size: int,
    lr: float,
) -> Tuple[Any, List[float]]:
    """Train one FC net (4 hidden layers, widths per cfg) with Adam/MSE."""
    n = feats.shape[0]
    params = init_mlp(key, cfg.input_dim, cfg.hidden, cfg.dtype)
    opt = adam(lr)
    opt_state = opt.init(params)
    losses: List[float] = []
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key)).ravel()[-1])
    nb = max(1, n // batch_size)
    for _ in range(epochs):
        perm = rng.permutation(n)
        epoch_loss = 0.0
        for b in range(nb):
            idx = perm[b * batch_size : (b + 1) * batch_size]
            if len(idx) == 0:
                continue
            x = jnp.asarray(feats[idx])
            y = jnp.asarray(targets[idx])
            params, opt_state, loss = _train_step(params, opt_state, x, y, opt.update)
            epoch_loss += float(loss)
        losses.append(epoch_loss / nb)
    return params, losses


def train_rmi(
    train_vectors: np.ndarray,
    *,
    eps_grid=None,
    epochs: int = 200,
    batch_size: int = 512,
    lr: float = 1e-3,
    seed: int = 0,
    feats_targets: Optional[Tuple[np.ndarray, np.ndarray]] = None,
) -> TrainedEstimator:
    """Full stage-wise RMI training on a training split."""
    from .features import DEFAULT_EPS_GRID

    t0 = time.time()
    if feats_targets is None:
        feats, targets = build_training_set(
            train_vectors, eps_grid or DEFAULT_EPS_GRID
        )
    else:
        feats, targets = feats_targets
    cfg = RMIConfig(input_dim=feats.shape[1], target_max=float(targets.max()) + 1e-6)
    key = jax.random.PRNGKey(seed)
    history: Dict[str, List[float]] = {}

    # ---- stage 0: one net on everything -------------------------------
    key, sub = jax.random.split(key)
    stage0, losses0 = train_mlp(
        sub, feats, targets, cfg, epochs=epochs, batch_size=batch_size, lr=lr
    )
    history["stage0"] = losses0
    params: Dict[str, Any] = {"stage0": stage0}

    # ---- deeper stages: route by previous stage's prediction ----------
    feats_j = jnp.asarray(feats)
    pred = np.asarray(mlp_apply(stage0, feats_j))
    for s in range(1, len(cfg.stage_sizes)):
        n_exp = cfg.stage_sizes[s]
        route = np.asarray(rmi_route(jnp.asarray(pred), n_exp, cfg.target_max))
        nets, new_pred = [], np.zeros_like(pred)
        for e in range(n_exp):
            sel = route == e
            key, sub = jax.random.split(key)
            if sel.sum() < 2:  # degenerate share: clone previous-stage behaviour
                net = init_mlp(sub, cfg.input_dim, cfg.hidden, cfg.dtype)
                losses = []
            else:
                net, losses = train_mlp(
                    sub, feats[sel], targets[sel], cfg,
                    epochs=epochs, batch_size=batch_size, lr=lr,
                )
            history[f"stage{s}_expert{e}"] = losses
            nets.append(net)
            if sel.any():
                new_pred[sel] = np.asarray(mlp_apply(net, jnp.asarray(feats[sel])))
        params[f"stage{s}"] = stack_stage(nets)
        pred = new_pred

    return TrainedEstimator(params, cfg, history, time.time() - t0, train_n=len(train_vectors))
