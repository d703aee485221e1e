"""Pieces the plain references share: matmuls at a stated precision,
RMSNorm, rotary embeddings, cross entropy, the cosine schedule and one
AdamW step.

Everything here is written from the published descriptions in plain
``jax.numpy``; nothing is imported from the program. Matmuls run in
float32 at ``Precision.HIGHEST``. ``lowp="fp8"`` turns the same code
into the control: every matmul input is rounded to float8 e4m3 with a
per-tensor scale on the way in, and its cotangent to float8 e5m2 on the
way back, the usual recipe of fp8 training.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round_to(x: jax.Array, dtype) -> jax.Array:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def lower(x: jax.Array, lowp: str | None) -> jax.Array:
    """``x`` as a matmul input at the precision ``lowp`` names."""
    if lowp is None:
        return x
    if lowp == "fp8":
        return fp8(x)
    raise ValueError(f"unknown precision {lowp!r}")


def mm(a: jax.Array, b: jax.Array, lowp: str | None = None) -> jax.Array:
    return jnp.matmul(lower(a, lowp), lower(b, lowp), precision=HI)


def einsum(spec: str, a: jax.Array, b: jax.Array,
           lowp: str | None = None) -> jax.Array:
    return jnp.einsum(spec, lower(a, lowp), lower(b, lowp), precision=HI)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with the gain stored as ``1 + scale`` (scale starts at 0)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of (B, S, H, D), rotating the two halves of D."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross entropy over every position."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def cosine_lr(base: float, warmup: int, total: int, count: jax.Array,
              min_frac: float = 0.1) -> jax.Array:
    """Linear warm-up to ``base``, then cosine decay to ``min_frac``."""
    c = count.astype(jnp.float32)
    warm = base * c / max(1, warmup)
    prog = jnp.clip((c - warmup) / max(1, total - warmup), 0.0, 1.0)
    cos = base * (min_frac + (1 - min_frac) * 0.5
                  * (1 + jnp.cos(math.pi * prog)))
    return jnp.where(c < warmup, warm, cos)


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(tree)))


def adamw_step(params: Any, grads: Any, m: Any, v: Any, count: jax.Array,
               lr: jax.Array, hp: dict) -> tuple[Any, Any, Any]:
    """One AdamW step after clipping the gradient to a global norm.

    Weight decay applies to every stored leaf of rank 2 or more, which
    is the rule of the optimizer under test as it stores its layers
    stacked (so stacked norm gains and SSD vectors are decayed too).
    """
    b1, b2 = hp["b1"], hp["b2"]
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, hp["clip_norm"] / (gn + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c = count.astype(jnp.float32)

    def upd(w, a, b):
        step = (a / (1 - b1 ** c)) / (jnp.sqrt(b / (1 - b2 ** c)) + hp["eps"])
        if w.ndim >= 2:
            step = step + hp["weight_decay"] * w
        return w - lr * step

    return jax.tree.map(upd, params, m, v), m, v
