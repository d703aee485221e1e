"""Batches and weights made from ``--seed``.

``train_batch`` is a copy of the program's synthetic stream
(``data/pipeline.SyntheticStream.batch_at``, token input, one host):
uniform tokens from ``SeedSequence([seed, step, 0])`` and labels shifted
left by one, wrapping around. Every step draws different rows.

``make_params`` draws every parameter of the program's pytree in one
traced function, so that a jitted call makes all of them on the device:
leaf ``i`` (in flattening order) from ``fold_in(key, i)``, by the
leaf's name: projections normal with std 0.02, the SSD conv normal with
std 0.2, ``A_log`` = log(1..16 spread over the heads), ``D`` = 1, norm
gains and biases 0. A leaf name not listed here is an error, so a
change of the program's layout is noticed rather than filled with a
guess.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

NORMAL = {"embed": 0.02, "lm_head": 0.02, "wq": 0.02, "wk": 0.02,
          "wv": 0.02, "wo": 0.02, "wi_gate": 0.02, "wi_up": 0.02,
          "in_proj": 0.02, "out_proj": 0.02, "conv_w": 0.2}
ZERO = {"norm1", "norm2", "final_norm", "norm", "conv_b", "dt_bias"}


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    toks = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_params(abstract: Any, key: jax.Array) -> Any:
    """Parameters shaped like ``abstract`` (ShapeDtypeStructs), from key."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name in NORMAL:
            x = jax.random.normal(k, leaf.shape, jnp.float32) * NORMAL[name]
        elif name in ZERO:
            x = jnp.zeros(leaf.shape, jnp.float32)
        elif name == "D":
            x = jnp.ones(leaf.shape, jnp.float32)
        elif name == "A_log":
            x = jnp.broadcast_to(
                jnp.log(jnp.linspace(1.0, 16.0, leaf.shape[-1])), leaf.shape)
        else:
            raise KeyError(f"no rule to make parameter {name!r}")
        out.append(x.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
