"""Shared neural layers: norms, rotary embeddings, gated MLPs.

Pure-functional jnp; parameters are plain dict pytrees.  Parameters are
stored in ``param_dtype`` (fp32 by default) and cast to ``compute_dtype``
at the point of use (mixed-precision training).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def cast(x: jax.Array, dtype: Any) -> jax.Array:
    return x.astype(dtype) if x.dtype != jnp.dtype(dtype) else x


def maybe_shard(x: jax.Array, *entries: Any) -> jax.Array:
    """Sharding constraint against the ambient abstract mesh; no-op when
    no mesh (or no "model" axis) is active — keeps model code usable on
    a single device and fully sharded under an active mesh."""
    names = jax.sharding.get_abstract_mesh().axis_names
    if "model" not in names:
        return x
    fixed = tuple(e if (e is None or (isinstance(e, str) and e in names)
                        or (isinstance(e, tuple)
                            and all(a in names for a in e)))
                  else None for e in entries)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*fixed))


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm computed in fp32 (numerics), output in x.dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for half the head dim (fp32)."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]).

    x: (B, S, H, D); positions: (B, S) int32.
    """
    dtype = x.dtype
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (B,S,D/2)
    cos = jnp.cos(angles)[..., None, :]                          # (B,S,1,D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def _act(name: str):
    if name in ("silu", "swiglu"):
        return jax.nn.silu
    if name in ("gelu", "geglu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "gelu_nogate":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name!r}")


def mlp(x: jax.Array, p: dict[str, jax.Array], act: str,
        compute_dtype: Any = jnp.bfloat16) -> jax.Array:
    """Gated (SwiGLU/GeGLU) or plain two-layer MLP."""
    fn = _act(act)
    xc = cast(x, compute_dtype)
    if act == "gelu_nogate":
        h = fn(xc @ cast(p["wi"], compute_dtype) + cast(p["bi"], compute_dtype))
        return h @ cast(p["wo"], compute_dtype) + cast(p["bo"], compute_dtype)
    gate = xc @ cast(p["wi_gate"], compute_dtype)
    up = xc @ cast(p["wi_up"], compute_dtype)
    return (fn(gate) * up) @ cast(p["wo"], compute_dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_tokens(tokens: jax.Array, table: jax.Array, scale: bool,
                 compute_dtype: Any = jnp.bfloat16) -> jax.Array:
    x = cast(jnp.take(table, tokens, axis=0), compute_dtype)
    if scale:
        x = x * jnp.asarray(table.shape[-1] ** 0.5, compute_dtype)
    return x


def unembed(x: jax.Array, table: jax.Array,
            compute_dtype: Any = jnp.bfloat16) -> jax.Array:
    """Logits; computed in compute dtype, cast up by the loss."""
    return cast(x, compute_dtype) @ cast(table, compute_dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def normal_init(key: jax.Array, shape: tuple[int, ...], dtype: Any,
                stddev: float = 0.02) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


def zeros_init(shape: tuple[int, ...], dtype: Any) -> jax.Array:
    return jnp.zeros(shape, dtype)
