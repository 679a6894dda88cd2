"""The STEP step scorer (paper §4.1, Appendix A).

A 2-layer MLP ``d_model -> 512 (ReLU) -> 1`` over last-layer hidden states
at reasoning-step boundaries, trained with class-balanced weighted BCE
(alpha = K^- / K^+) on trace-level correctness pseudo-labels propagated to
every step.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.training.optimizer import AdamW


SCORER_HIDDEN = 512  # paper Appendix A: Input -> 512 (ReLU) -> 1


def init_scorer(rng: jax.Array, d_model: int,
                hidden: int = SCORER_HIDDEN) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "w1": jax.random.normal(k1, (d_model, hidden), jnp.float32)
        * (2.0 / d_model) ** 0.5,
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(k2, (hidden, 1), jnp.float32)
        * (1.0 / hidden) ** 0.5,
        "b2": jnp.zeros((1,), jnp.float32),
    }


def scorer_logits(params: dict, h: jax.Array) -> jax.Array:
    """h [..., D] -> pre-sigmoid logits [...].

    f32 matmuls at full precision: a TPU's default would round their
    inputs to bf16, and the fused ``kernels/step_score`` kernel, which
    serves the same scores on a one-chip engine, does not.
    """
    hi = jax.lax.Precision.HIGHEST
    z = jax.nn.relu(jnp.matmul(h.astype(jnp.float32), params["w1"],
                               precision=hi) + params["b1"])
    return (jnp.matmul(z, params["w2"], precision=hi) + params["b2"])[..., 0]


def scorer_score(params: dict, h: jax.Array) -> jax.Array:
    """Correctness probability in [0, 1]."""
    return jax.nn.sigmoid(scorer_logits(params, h))


def weighted_bce_loss(params: dict, h: jax.Array, y: jax.Array,
                      alpha: float) -> jax.Array:
    """Paper Eq. (loss): -(1/N) sum alpha*y*log p + (1-y)*log(1-p),
    numerically stable logits form (BCEWithLogits)."""
    logits = scorer_logits(params, h)
    yf = y.astype(jnp.float32)
    log_p = jax.nn.log_sigmoid(logits)
    log_1mp = jax.nn.log_sigmoid(-logits)
    return -jnp.mean(alpha * yf * log_p + (1 - yf) * log_1mp)


@dataclasses.dataclass
class ScorerTrainConfig:
    """Paper Table 5 hyper-parameters."""
    batch_size: int = 128
    max_epochs: int = 20
    patience: int = 5
    learning_rate: float = 1e-4
    weight_decay: float = 1e-5
    val_fraction: float = 0.1
    seed: int = 0


def train_scorer(hiddens: np.ndarray, labels: np.ndarray,
                 cfg: Optional[ScorerTrainConfig] = None,
                 params: Optional[dict] = None,
                 verbose: bool = False) -> Tuple[dict, dict]:
    """Train the step scorer. hiddens [M, D]; labels [M] in {0,1}
    (step pseudo-labels = trace correctness). Returns (params, info)."""
    cfg = cfg or ScorerTrainConfig()
    rng = np.random.RandomState(cfg.seed)
    M, D = hiddens.shape
    perm = rng.permutation(M)
    hiddens, labels = hiddens[perm], labels[perm]
    n_val = max(1, int(M * cfg.val_fraction))
    hv, yv = jnp.asarray(hiddens[:n_val]), jnp.asarray(labels[:n_val])
    ht, yt = hiddens[n_val:], labels[n_val:]

    k_pos = max(int((yt == 1).sum()), 1)
    k_neg = max(int((yt == 0).sum()), 1)
    alpha = k_neg / k_pos  # paper: ratio of negative to positive samples

    if params is None:
        params = init_scorer(jax.random.PRNGKey(cfg.seed), D)
    opt = AdamW(learning_rate=cfg.learning_rate,
                weight_decay=cfg.weight_decay, grad_clip=None)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, hb, yb):
        loss, grads = jax.value_and_grad(weighted_bce_loss)(
            params, hb, yb, alpha)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    @jax.jit
    def val_loss(params):
        return weighted_bce_loss(params, hv, yv, alpha)

    best_val, best_params, bad_epochs = np.inf, params, 0
    history = []
    n_train = len(ht)
    for epoch in range(cfg.max_epochs):
        order = rng.permutation(n_train)
        losses = []
        for i in range(0, n_train, cfg.batch_size):
            idx = order[i:i + cfg.batch_size]
            params, opt_state, loss = step(
                params, opt_state, jnp.asarray(ht[idx]), jnp.asarray(yt[idx]))
            losses.append(float(loss))
        vl = float(val_loss(params))
        history.append({"epoch": epoch, "train_loss": float(np.mean(losses)),
                        "val_loss": vl})
        if verbose:
            print(f"scorer epoch {epoch}: train={np.mean(losses):.4f} "
                  f"val={vl:.4f}")
        if vl < best_val - 1e-5:
            best_val, best_params, bad_epochs = vl, params, 0
        else:
            bad_epochs += 1
            if bad_epochs >= cfg.patience:  # early stopping (paper: 5)
                break
    info = {"alpha": alpha, "best_val_loss": best_val, "history": history}
    return best_params, info


def rank_accuracy(scores_pos: np.ndarray, scores_neg: np.ndarray) -> float:
    """Pairwise RankAcc (paper §5.3.2): P[s(p) > s(n)] over all pairs."""
    if len(scores_pos) == 0 or len(scores_neg) == 0:
        return float("nan")
    sp = scores_pos[:, None]
    sn = scores_neg[None, :]
    return float(np.mean((sp > sn) + 0.5 * (sp == sn)))
