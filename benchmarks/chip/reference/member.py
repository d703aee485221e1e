"""Plain float32 training of one study member: the final loss of a
decoder trained from its seed, as a member of an lr x seed study does.

A member with learning rate ``lr`` and seed ``seed`` trains for
``steps`` AdamW steps on batches of uniform random tokens, with the
cosine schedule scaled by ``lr`` (warm-up ``max(1, steps // 10)``), and
reports the loss of its last step. Its weights and batches are drawn
from ``fold_in(PRNGKey(0), seed)`` in the order the study engine's
trainer draws them: ``split(key, layers + 4)`` gives the embedding
(key 0), layer ``i`` (key ``2 + i``: attention from the first of its
four sub-keys, the MLP from the fourth) and the head (key
``2 + layers``); batch ``t`` is ``randint`` of key ``t`` of
``split(fold_in(key, 1), steps)``, and its labels are the tokens
shifted left by one, wrapping around. Projections are normal with
std 0.02; norm gains start at 0 (``1 + scale``).
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

from .common import adamw_step, cosine_lr
from .model import loss

STD = 0.02


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * STD


def init(m: Mapping[str, Any], key: jax.Array) -> dict[str, Any]:
    d, v, n = m["d_model"], m["vocab_size"], m["n_layers"]
    ad, kd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    keys = jax.random.split(key, n + 4)
    layers = []
    for i in range(n):
        ks = jax.random.split(keys[2 + i], 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[3], 3)
        layers.append({
            "norm1": jnp.zeros((d,)), "norm2": jnp.zeros((d,)),
            "attn": {"wq": _normal(ka[0], (d, ad)), "wk": _normal(ka[1], (d, kd)),
                     "wv": _normal(ka[2], (d, kd)), "wo": _normal(ka[3], (ad, d))},
            "mlp": {"wi_gate": _normal(km[0], (d, m["d_ff"])),
                    "wi_up": _normal(km[1], (d, m["d_ff"])),
                    "wo": _normal(km[2], (m["d_ff"], d))},
        })
    params = {"embed": _normal(keys[0], (v, d)),
              "segments": [jax.tree.map(lambda *xs: jnp.stack(xs), *layers)],
              "final_norm": jnp.zeros((d,))}
    if not m["tie_embeddings"]:
        params["lm_head"] = _normal(keys[2 + n], (d, v))
    return params


def final_loss(m: Mapping[str, Any], lr: jax.Array, seed: jax.Array, *,
               steps: int, batch: int, seq: int, adamw: Mapping[str, Any],
               rows: int | None = None, lowp: str | None = None
               ) -> jax.Array:
    """The member's last-step loss. ``rows`` trains on only the first
    ``rows`` rows of every batch (a fault the checks must catch)."""
    if any(k != "swa" for k in m["layer_types"]):
        raise ValueError("study members are decoders of swa layers")
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    params = init(m, key)
    warmup = max(1, steps // 10)
    zeros = jax.tree.map(jnp.zeros_like, params)

    def body(carry, step_key):
        p, mom, vel, count = carry
        toks = jax.random.randint(step_key, (batch, seq), 0, m["vocab_size"])
        toks = toks[:rows]
        labels = jnp.roll(toks, -1, axis=1)
        value, g = jax.value_and_grad(
            lambda q: loss(m, q, toks, labels, lowp))(p)
        count = count + 1
        rate = lr * cosine_lr(1.0, warmup, steps, count)
        p, mom, vel = adamw_step(p, g, mom, vel, count, rate, adamw)
        return (p, mom, vel, count), value

    keys = jax.random.split(jax.random.fold_in(key, 1), steps)
    _, losses = jax.lax.scan(body, (params, zeros, zeros, jnp.int32(0)), keys)
    return losses[-1]
