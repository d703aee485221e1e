"""Plain float32 language model and its first training steps.

The parameters come as the pytree the benchmark made from the seed:
``embed`` (V, d), ``segments`` (one entry per run of equal layer kinds,
each leaf stacked over the run's layers), ``final_norm`` and, when the
head is not tied to the embedding, ``lm_head`` (d, V). The loss is the
mean next-token cross entropy of ``head(norm(layers(embed[tokens])))``.
Every layer is recomputed in the backward pass.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from . import decoder, mamba2
from .common import (
    adamw_step, cosine_lr, cross_entropy, global_norm, mm, rms_norm,
)

HEAD_ROWS = 1024
LAYERS: dict[str, Callable] = {"swa": decoder.layer, "attn": decoder.layer,
                               "ssm": mamba2.layer}


def runs(layer_types: Sequence[str]) -> list[str]:
    """The kind of each run of equal consecutive layer kinds."""
    out: list[str] = []
    for kind in layer_types:
        if not out or out[-1] != kind:
            out.append(kind)
    return out


def loss(m: Mapping[str, Any], params: Mapping[str, Any],
         tokens: jax.Array, labels: jax.Array,
         lowp: str | None = None) -> jax.Array:
    x = params["embed"][tokens]
    for kind, seg in zip(runs(m["layer_types"]), params["segments"]):
        body = jax.checkpoint(
            lambda h, lp, _k=kind: (LAYERS[_k](h, lp, m, lowp), None))
        x, _ = jax.lax.scan(body, x, seg)
    x = rms_norm(x, params["final_norm"], m["norm_eps"])
    head = (params["embed"].T if m["tie_embeddings"] else params["lm_head"])
    # the head and the loss over blocks of tokens, so that the logits of
    # only one block are alive
    n = x.shape[0] * x.shape[1]
    rows = min(n, HEAD_ROWS)
    xs = x.reshape(n // rows, rows, x.shape[-1])
    ys = labels.reshape(n // rows, rows)
    block = jax.checkpoint(lambda a: cross_entropy(mm(a[0], head, lowp), a[1]))
    return jnp.mean(jax.lax.map(block, (xs, ys)))


def first_steps(m: Mapping[str, Any], make_params: Callable[[], Any],
                batches: Sequence[Mapping[str, Any]], hp: Mapping[str, Any],
                leaf_norms: Callable, lowp: str | None = None
                ) -> dict[str, Any]:
    """Train the parameters ``make_params()`` returns on ``batches`` in
    order, one AdamW step each.

    Returns the loss of every step, the global norm of the first
    (unclipped) gradient, ``leaf_norms`` of that gradient, and
    ``leaf_norms`` of the parameters' change over all the steps.
    ``hp`` holds ``lr``, ``warmup``, ``total_steps`` and ``adamw``.
    ``make_params`` is called twice, so that the starting point need
    not be held while training.
    """
    grad_fn = jax.value_and_grad(
        lambda p, t, lab: loss(m, p, t, lab, lowp))

    def step(p, mom, vel, count, tokens, labels):
        value, g = grad_fn(p, tokens, labels)
        lr = cosine_lr(hp["lr"], hp["warmup"], hp["total_steps"], count)
        norms = (global_norm(g), leaf_norms(g))
        p, mom, vel = adamw_step(p, g, mom, vel, count, lr, hp["adamw"])
        return p, mom, vel, value, norms

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), make_params())
        mom = jax.tree.map(jnp.zeros_like, p)
        vel = jax.tree.map(jnp.zeros_like, p)
        out: dict[str, Any] = {"losses": []}
        for i, b in enumerate(batches):
            p, mom, vel, value, norms = step(
                p, mom, vel, jnp.int32(i + 1), jnp.asarray(b["tokens"]),
                jnp.asarray(b["labels"]))
            out["losses"].append(float(value))
            if i == 0:
                out["grad_norm"] = float(norms[0])
                out["grad_leaves"] = jax.device_get(norms[1])
        del mom, vel
        out["update_leaves"] = jax.device_get(jax.jit(
            lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))(
                p, make_params()))
    return out
