"""Plain float32 decoder layer with sliding-window attention (H2O-Danube,
arXiv:2401.16818: Llama/Mistral layout).

    h = x + Wo . attn(RoPE(Wq n1(x)), RoPE(Wk n1(x)), Wv n1(x))
    y = h + W_down (silu(W_gate n2(h)) * W_up n2(h))

Attention is grouped-query (query head i reads key/value head
``i // (n_heads / n_kv_heads)``), causal and limited to the last
``window`` positions (``0 <= i - j < window``), softmax over
``q.k / sqrt(head_dim)``. It runs one batch row at a time and is
recomputed in the backward pass, so that the (heads, S, S) scores of
only one row are alive.
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

from .common import einsum, mm, rms_norm, rope


def attention(x: jax.Array, p: Mapping[str, jax.Array],
              m: Mapping[str, Any], lowp: str | None) -> jax.Array:
    b, s, _ = x.shape
    h, kv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = mm(x, p["wq"], lowp).reshape(b, s, h, d)
    k = mm(x, p["wk"], lowp).reshape(b, s, kv, d)
    v = mm(x, p["wv"], lowp).reshape(b, s, kv, d)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    window = m.get("window") or s
    allowed = (i - j >= 0) & (i - j < window)

    @jax.checkpoint
    def one_row(args):
        qr, kr, vr = args                                   # (S, H, D)
        scores = einsum("qhd,khd->hqk", qr, kr, lowp) / jnp.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return einsum("hqk,khd->qhd", probs, vr, lowp)

    out = jax.lax.map(one_row, (q, k, v))
    return mm(out.reshape(b, s, h * d), p["wo"], lowp)


def swiglu(x: jax.Array, p: Mapping[str, jax.Array],
           lowp: str | None) -> jax.Array:
    gate = mm(x, p["wi_gate"], lowp)
    return mm(jax.nn.silu(gate) * mm(x, p["wi_up"], lowp), p["wo"], lowp)


def layer(x: jax.Array, lp: Mapping[str, Any], m: Mapping[str, Any],
          lowp: str | None) -> jax.Array:
    eps = m["norm_eps"]
    x = x + attention(rms_norm(x, lp["norm1"], eps), lp["attn"], m, lowp)
    return x + swiglu(rms_norm(x, lp["norm2"], eps), lp["mlp"], lowp)
