"""Public model API: a thin functional wrapper over the substrate.

``Model`` binds an ArchConfig to init/loss/decode callables;
``synthetic_batch`` makes a random batch of its inputs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .transformer import (
    decode_step, forward, init_cache, init_params, loss_fn,
)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    def init(self, key: jax.Array) -> dict[str, Any]:
        return init_params(self.cfg, key)

    def init_abstract(self, key: jax.Array | None = None):
        """Parameter shapes without allocation (for sharding rules)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: init_params(self.cfg, k), key)

    def forward(self, params, batch):
        return forward(self.cfg, params, batch)

    def loss(self, params, batch):
        return loss_fn(self.cfg, params, batch)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return init_cache(self.cfg, batch, max_len, dtype)

    def decode_step(self, params, cache, token):
        return decode_step(self.cfg, params, cache, token)


def synthetic_batch(cfg: ArchConfig, batch: int, seq: int,
                    key: jax.Array) -> dict[str, jax.Array]:
    """Materialized random batch for smoke tests / examples."""
    ks = jax.random.split(key, 3)
    if cfg.input_mode == "tokens":
        toks = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size)
        return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.input_mode == "embeds":
        emb = jax.random.normal(ks[0], (batch, seq, cfg.d_model),
                                jnp.bfloat16) * 0.1
        labels = jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size)
        return {"embeds": emb, "labels": labels}
    npatch = min(cfg.n_patches, seq // 2)
    st = seq - npatch
    toks = jax.random.randint(ks[0], (batch, st), 0, cfg.vocab_size)
    patches = jax.random.normal(ks[1], (batch, npatch, cfg.d_model),
                                jnp.bfloat16) * 0.1
    labels = jnp.concatenate(
        [jnp.full((batch, npatch), -100, jnp.int32),
         jax.random.randint(ks[2], (batch, st), 0, cfg.vocab_size)], axis=1)
    return {"tokens": toks, "patch_embeds": patches, "labels": labels}
