"""Train-step factory: loss → grads → AdamW, with microbatching."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.transformer import loss_fn
from repro.optim.adamw import AdamW, accumulate_grads


def make_train_step(cfg: ArchConfig, opt: AdamW, n_micro: int = 1
                    ) -> Callable:
    """Returns ``train_step(state, batch) -> (state, metrics)``.

    state = {"params", "opt", "step"}; batch leaves have the global
    batch dim first.  With n_micro > 1 the batch is split on axis 0 and
    scanned (activation memory / n_micro).
    """

    def _loss(params, batch):
        return loss_fn(cfg, params, batch)

    def train_step(state: dict[str, Any], batch: dict[str, jax.Array]
                   ) -> tuple[dict[str, Any], dict[str, jax.Array]]:
        params = state["params"]
        if n_micro > 1:
            micro = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]), batch)
            grads, loss, aux = accumulate_grads(_loss, params, micro, n_micro)
        else:
            (loss, aux), grads = jax.value_and_grad(
                _loss, has_aux=True)(params, batch)

        new_params, new_opt, opt_metrics = opt.update(
            grads, state["opt"], params)
        metrics = {"loss": loss, **aux, **opt_metrics}
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, metrics

    return train_step


def init_train_state(cfg: ArchConfig, opt: AdamW, key: jax.Array
                     ) -> dict[str, Any]:
    from repro.models.transformer import init_params
    params = init_params(cfg, key)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(cfg: ArchConfig, opt: AdamW) -> Any:
    """The state's ShapeDtypeStructs, without allocating it: what the
    sharding rules and ``jit(...).lower`` take before the state is made."""
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda k: init_train_state(cfg, opt, k), key)
