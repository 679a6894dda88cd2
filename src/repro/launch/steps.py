"""The training step the training launcher runs: forward with remat,
backward, AdamW update, optional gradient accumulation."""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import lm_loss
from repro.training.optimizer import AdamW


def make_train_step(cfg: ModelConfig, lr: float = 1e-4,
                    microbatches: int = 1,
                    moment_dtype: str = "float32",
                    accum_dtype: str = "float32"
                    ) -> Tuple[Callable, AdamW]:
    """Full training step: fwd (remat) + bwd + AdamW update.

    ``microbatches > 1`` enables gradient accumulation: the global batch
    is split into k sequential microbatches whose fp32 gradients
    accumulate before one optimizer update, which bounds peak activation
    memory.
    """
    opt = AdamW(learning_rate=lr, weight_decay=0.01,
                moment_dtype=moment_dtype)

    def loss_fn(p, batch):
        return lm_loss(p, cfg, batch["tokens"], batch["labels"],
                       remat=True,
                       modality_embeds=batch.get("modality_embeds"),
                       encoder_embeds=batch.get("encoder_embeds"))

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            k = microbatches

            def split(x):
                return jnp.moveaxis(
                    x.reshape(k, x.shape[0] // k, *x.shape[1:]), 0, 0)

            mbs = {key: split(v) for key, v in batch.items()}

            adt = jnp.dtype(accum_dtype)

            def body(acc, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                acc = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  + b.astype(jnp.float32)).astype(adt),
                    acc, g)
                return acc, l

            gacc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, adt), params)
            gacc, losses = jax.lax.scan(body, gacc0, mbs)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) / k, gacc)
            loss = jnp.mean(losses)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return train_step, opt
