"""Plain float32 Mamba-2 layer (arXiv:2405.21060), with the SSD state
space model run as its sequential recurrence rather than in chunks.

    [z, xBC, dt] = W_in n1(x)
    xBC = silu(causal depthwise conv_K(xBC) + b);  [x, B, C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)             per head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t          (P, N)
    y_t = h_t . C_t + D x_t
    out = x_in + W_out gated_rmsnorm(y * silu(z))

Groups of B and C are shared by ``heads / groups`` consecutive heads.
The mixer runs one batch row at a time, and the recurrence over blocks
of ``BLOCK`` steps whose inner steps are recomputed in the backward
pass, so that only one state per block is kept.
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

from .common import mm, rms_norm

BLOCK = 64
GATED_NORM_EPS = 1e-5


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """y_t = sum_k w_k x_{t-K+1+k} + b over (B, S, C), zero history."""
    k, s = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(pad[:, i:i + s] * w[i] for i in range(k)) + b


def ssd_sequential(x: jax.Array, dt: jax.Array, a: jax.Array,
                   b_mat: jax.Array, c_mat: jax.Array) -> jax.Array:
    """x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,G,N) -> y (B,S,H,P)."""
    bsz, s, h, p = x.shape
    rep = h // b_mat.shape[2]
    bh = jnp.repeat(b_mat, rep, axis=2)
    ch = jnp.repeat(c_mat, rep, axis=2)

    def step(state, inp):
        xt, dtt, bt, ct = inp                     # (B,H,P) (B,H) (B,H,N)x2
        decay = jnp.exp(dtt * a)[..., None, None]
        state = decay * state + (dtt[..., None] * xt)[..., None] \
            * bt[:, :, None, :]
        return state, jnp.sum(state * ct[:, :, None, :], -1)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    blk = min(BLOCK, s)
    nb = s // blk
    inp = tuple(t.swapaxes(0, 1).reshape(nb, blk, *t.shape[:1], *t.shape[2:])
                for t in (x, dt, bh, ch))
    state0 = jnp.zeros((bsz, h, p, b_mat.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, state0, inp)       # (nb, blk, B, H, P)
    return y.reshape(s, bsz, h, p).swapaxes(0, 1)


def mixer(x: jax.Array, p: Mapping[str, jax.Array], m: Mapping[str, Any],
          lowp: str | None) -> jax.Array:
    bsz, s, d = x.shape
    di = m["ssm_expand"] * d
    n, g, hp = m["ssm_state"], m["ssm_groups"], m["ssm_head_dim"]
    h = di // hp
    z, xbc, dt = jnp.split(mm(x, p["in_proj"], lowp),
                           [di, 2 * di + 2 * g * n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, b_mat, c_mat = jnp.split(xbc, [di, di + g * n], axis=-1)
    xs = xs.reshape(bsz, s, h, hp)
    y = ssd_sequential(xs, dt, -jnp.exp(p["A_log"]),
                       b_mat.reshape(bsz, s, g, n), c_mat.reshape(bsz, s, g, n))
    y = (y + xs * p["D"][:, None]).reshape(bsz, s, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], GATED_NORM_EPS)
    return mm(y, p["out_proj"], lowp)


def layer(x: jax.Array, lp: Mapping[str, Any], m: Mapping[str, Any],
          lowp: str | None) -> jax.Array:
    """One residual layer; the mixer runs one batch row at a time."""
    row = jax.checkpoint(lambda r: mixer(r[None], lp["ssm"], m, lowp)[0])
    return x + jax.lax.map(row, rms_norm(x, lp["norm1"], m["norm_eps"]))
