"""AdamW + schedules + gradient utilities (pure JAX, no optax dependency).

Besides the optimizer: global-norm clipping and the gradient
accumulation (microbatching) helper.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Pytree = Any


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

def cosine_schedule(base_lr: float, warmup: int, total: int,
                    min_frac: float = 0.1) -> Callable[[jax.Array], jax.Array]:
    def fn(step: jax.Array) -> jax.Array:
        step = step.astype(jnp.float32)
        warm = base_lr * step / jnp.maximum(1.0, warmup)
        progress = jnp.clip((step - warmup) / jnp.maximum(1.0, total - warmup),
                            0.0, 1.0)
        cos = base_lr * (min_frac + (1 - min_frac)
                         * 0.5 * (1 + jnp.cos(jnp.pi * progress)))
        return jnp.where(step < warmup, warm, cos)
    return fn


def linear_schedule(base_lr: float, warmup: int, total: int
                    ) -> Callable[[jax.Array], jax.Array]:
    def fn(step: jax.Array) -> jax.Array:
        step = step.astype(jnp.float32)
        warm = base_lr * step / jnp.maximum(1.0, warmup)
        lin = base_lr * jnp.clip(1.0 - (step - warmup)
                                 / jnp.maximum(1.0, total - warmup), 0.0, 1.0)
        return jnp.where(step < warmup, warm, lin)
    return fn


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdamW:
    """AdamW with fp32 master weights.

    The training params may live in bf16 (halving weight HBM traffic and
    gradient-reduction bytes); the optimizer keeps the fp32 master copy
    in its state, where ZeRO-1 shards it over the data axis.
    """

    schedule: Callable[[jax.Array], jax.Array]
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0

    def init(self, params: Pytree) -> dict[str, Pytree]:
        zeros = lambda p: jax.tree.map(  # noqa: E731
            lambda x: jnp.zeros(x.shape, jnp.float32), p)
        return {"m": zeros(params), "v": zeros(params),
                # copy=True: fp32 params would otherwise ALIAS the master
                # (astype is a no-op) and break buffer donation
                "master": jax.tree.map(
                    lambda x: jnp.array(x, dtype=jnp.float32, copy=True),
                    params),
                "count": jnp.zeros((), jnp.int32)}

    @jax.named_scope("optimizer")
    def update(self, grads: Pytree, state: dict[str, Pytree],
               params: Pytree) -> tuple[Pytree, dict[str, Pytree],
                                        dict[str, jax.Array]]:
        """One step, clipping included, in the named scope
        ``optimizer``."""
        count = state["count"] + 1
        gnorm = global_norm(grads)
        if self.clip_norm:
            scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2 = self.b1, self.b2
        c = count.astype(jnp.float32)
        m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1)
                         * g.astype(jnp.float32), state["m"], grads)
        v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2)
                         * jnp.square(g.astype(jnp.float32)),
                         state["v"], grads)
        lr = self.schedule(count)

        def upd(w, mm, vv):
            mhat = mm / (1 - b1 ** c)
            vhat = vv / (1 - b2 ** c)
            step = mhat / (jnp.sqrt(vhat) + self.eps)
            if self.weight_decay and w.ndim >= 2:   # decay matrices only
                step = step + self.weight_decay * w
            return w - lr * step

        new_master = jax.tree.map(upd, state["master"], m, v)
        new_params = jax.tree.map(
            lambda w, p: w.astype(p.dtype), new_master, params)
        metrics = {"lr": lr, "grad_norm": gnorm}
        return new_params, {"m": m, "v": v, "master": new_master,
                            "count": count}, metrics


def global_norm(tree: Pytree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


# ---------------------------------------------------------------------------
# Gradient accumulation
# ---------------------------------------------------------------------------

def accumulate_grads(loss_fn: Callable, params: Pytree, batches: Pytree,
                     n_micro: int) -> tuple[Pytree, jax.Array, Pytree]:
    """Scan over ``n_micro`` microbatches (leading axis of ``batches``),
    averaging grads — the memory/throughput lever for large global
    batches."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def body(carry, micro):
        acc, loss_acc = carry
        (loss, aux), g = grad_fn(params, micro)
        acc = jax.tree.map(jnp.add, acc, g)
        return (acc, loss_acc + loss), aux

    zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    (gsum, loss_sum), auxs = jax.lax.scan(body, (zero, 0.0), batches)
    grads = jax.tree.map(lambda g: g / n_micro, gsum)
    aux_last = jax.tree.map(lambda x: x[-1], auxs)
    return grads, loss_sum / n_micro, aux_last

