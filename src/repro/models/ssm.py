"""Mamba2 SSD (state-space duality) block — arXiv:2405.21060.

The SSD recurrence per head h with state (P, N):

    a_t = exp(dt_t · A_h)                       (scalar decay, A_h < 0)
    h_t = a_t · h_{t-1} + dt_t · x_t ⊗ B_t      (outer product update)
    y_t = C_t · h_t + D_h · x_t

Production XLA path: the chunked SSD algorithm — quadratic *within*
chunks of length Q (matmul-friendly for the MXU), associative scan
*across* chunk states — O(S·Q) work instead of O(S²).  The Pallas kernel
(``repro.kernels.ssd_scan``) fuses the intra-chunk stage on TPU.

Block layout follows mamba_ssm's Mamba2: fused in_proj → causal depthwise
conv over (x,B,C) → SSD → gated RMSNorm → out_proj.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .layers import cast, maybe_shard, rms_norm


def ssd_chunked(
    x: jax.Array,      # (B, S, H, P)  — dt-scaled inputs
    log_a: jax.Array,  # (B, S, H)     — per-step log decay (dt·A, ≤ 0)
    b_mat: jax.Array,  # (B, S, G, N)
    c_mat: jax.Array,  # (B, S, G, N)
    chunk: int,
    initial_state: jax.Array | None = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (B,S,H,P), final_state (B,H,P,N)).

    G groups broadcast over H heads (H % G == 0).
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} not divisible by chunk {q}")
    c = s // q
    rep = h // g

    xq = x.reshape(bsz, c, q, h, p)
    la = log_a.reshape(bsz, c, q, h).astype(jnp.float32)
    bq = b_mat.reshape(bsz, c, q, g, n)
    cq = c_mat.reshape(bsz, c, q, g, n)
    # broadcast groups → heads
    bh = jnp.repeat(bq, rep, axis=3)                      # (B,C,Q,H,N)
    ch = jnp.repeat(cq, rep, axis=3)

    cum = jnp.cumsum(la, axis=2)                          # (B,C,Q,H) inclusive
    seg_total = cum[:, :, -1, :]                          # (B,C,H)

    # ---- intra-chunk (quadratic in Q) --------------------------------
    # decay(i←j) = exp(cum_i - cum_j) for j ≤ i.  The masked (j > i)
    # entries have POSITIVE exponents: exp would overflow and poison the
    # where-gradient (NaN), so the argument is masked BEFORE exp.
    li = cum[:, :, :, None, :]                            # (B,C,Q,1,H)
    lj = cum[:, :, None, :, :]                            # (B,C,1,Q,H)
    mask = jnp.tril(jnp.ones((q, q), jnp.bool_))[None, None, :, :, None]
    delta = jnp.where(mask, li - lj, 0.0)
    decay = jnp.where(mask, jnp.exp(delta), 0.0)          # (B,C,Q,Q,H) fp32
    scores = jnp.einsum("bcihn,bcjhn->bcijh", ch.astype(jnp.float32),
                        bh.astype(jnp.float32)) * decay
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores.astype(x.dtype), xq)

    # ---- chunk states -------------------------------------------------
    # state contribution of step j within its chunk: decay to chunk end
    w = jnp.exp(seg_total[:, :, None, :] - cum)           # (B,C,Q,H)
    states = jnp.einsum("bcqhp,bcqhn,bcqh->bchpn",
                        xq.astype(jnp.float32), bh.astype(jnp.float32), w)

    # ---- inter-chunk associative scan over (decay, state) -------------
    seg = jnp.exp(seg_total.astype(jnp.float32))          # (B,C,H)

    def combine(left, right):
        a_l, s_l = left
        a_r, s_r = right
        return a_l * a_r, s_l * a_r[..., None, None] + s_r

    a_scan, s_scan = jax.lax.associative_scan(
        combine, (seg, states), axis=1)
    # state entering chunk c = scanned state of chunk c-1 (+ injected
    # initial state decayed by the cumulative product a_scan[c-1])
    if initial_state is not None:
        init = initial_state.astype(jnp.float32)[:, None]   # (B,1,H,P,N)
        prev = jnp.concatenate(
            [init, s_scan[:, :-1] + init * a_scan[:, :-1, :, None, None]],
            axis=1)
        final_state = s_scan[:, -1] + init[:, 0] * a_scan[:, -1, :, None, None]
    else:
        prev = jnp.concatenate(
            [jnp.zeros_like(s_scan[:, :1]), s_scan[:, :-1]], axis=1)
        final_state = s_scan[:, -1]

    # ---- inter-chunk output contribution ------------------------------
    dec_in = jnp.exp(cum)                                  # decay from chunk start
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                         ch.astype(jnp.float32), prev, dec_in)
    y = (y_intra.astype(jnp.float32) + y_inter).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final_state.astype(x.dtype)


def ssd_step(
    state: jax.Array,   # (B, H, P, N) fp32
    x_t: jax.Array,     # (B, H, P) — dt-scaled input
    log_a_t: jax.Array, # (B, H)
    b_t: jax.Array,     # (B, G, N)
    c_t: jax.Array,     # (B, G, N)
) -> tuple[jax.Array, jax.Array]:
    """Single decode step of the SSD recurrence."""
    h = x_t.shape[1]
    g = b_t.shape[1]
    rep = h // g
    bh = jnp.repeat(b_t, rep, axis=1).astype(jnp.float32)   # (B,H,N)
    ch = jnp.repeat(c_t, rep, axis=1).astype(jnp.float32)
    a = jnp.exp(log_a_t.astype(jnp.float32))[..., None, None]
    new_state = state * a + jnp.einsum(
        "bhp,bhn->bhpn", x_t.astype(jnp.float32), bh)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, ch)
    return new_state, y.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------

def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array | None]:
    """Depthwise causal conv1d, kernel size K.  x (B,S,C); w (K,C).

    y_t = b + Σ_k w_k · x_{t-K+1+k}, summed as K shifted slices of the
    left-padded input (no (B,S,K,C) window tensor, so the gradient is
    slices and pads, not a scatter-add).  With ``state`` (B,K-1,C) the
    pad is the previous K-1 inputs (a streaming step) and the new state
    is the last K-1 rows."""
    k = w.shape[0]
    s = x.shape[1]
    if state is None:
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))   # (B,S+K-1,C)
    else:
        padded = jnp.concatenate([state, x], axis=1)
    wf = w.astype(jnp.float32)
    y = padded[:, :s].astype(jnp.float32) * wf[0]
    for i in range(1, k):
        y = y + padded[:, i:i + s].astype(jnp.float32) * wf[i]
    y = (y + b.astype(jnp.float32)).astype(x.dtype)
    return y, None if state is None else padded[:, s:]


def mamba2_block(
    x: jax.Array,                # (B, S, d)
    p: dict[str, Any],
    *,
    d_inner: int,
    state_dim: int,
    head_dim: int,
    n_groups: int,
    conv_width: int,
    chunk: int,
    compute_dtype: Any = jnp.bfloat16,
    cache: dict[str, jax.Array] | None = None,
    use_kernels: bool = False,
) -> tuple[jax.Array, dict[str, jax.Array] | None]:
    """Mamba2 mixer.  With ``cache`` performs one decode step (S==1)."""
    bsz, s, d = x.shape
    n_heads = d_inner // head_dim
    gn = n_groups * state_dim
    xc = cast(x, compute_dtype)

    zxbcdt = xc @ cast(p["in_proj"], compute_dtype)
    z, xbc, dt_raw = jnp.split(
        zxbcdt, [d_inner, d_inner + d_inner + 2 * gn], axis=-1)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    if cache is not None:
        xbc_act, conv_state = _causal_conv(
            xbc, p["conv_w"], p["conv_b"], cache["conv"])
    else:
        xbc_act, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xbc_act = jax.nn.silu(xbc_act.astype(jnp.float32)).astype(compute_dtype)
    xs, b_mat, c_mat = jnp.split(xbc_act, [d_inner, d_inner + gn], axis=-1)
    xs = xs.reshape(bsz, s, n_heads, head_dim)
    b_mat = b_mat.reshape(bsz, s, n_groups, state_dim)
    c_mat = c_mat.reshape(bsz, s, n_groups, state_dim)

    a = -jnp.exp(p["A_log"].astype(jnp.float32))            # (H,) negative
    log_a = dt.reshape(bsz, s, n_heads) * a                  # (B,S,H)
    x_scaled = xs * dt.reshape(bsz, s, n_heads, 1).astype(compute_dtype)

    new_cache = None
    if cache is not None:
        new_state, y = ssd_step(
            cache["ssm"], x_scaled[:, 0], log_a[:, 0],
            b_mat[:, 0], c_mat[:, 0])
        y = y[:, None]
        new_cache = {"conv": conv_state, "ssm": new_state,
                     "pos": cache["pos"] + 1}
    elif use_kernels:
        from repro.kernels import ops as kops
        y, _ = kops.ssd_scan(x_scaled, log_a, b_mat, c_mat, chunk=chunk)
    else:
        y, _ = ssd_chunked(x_scaled, log_a, b_mat, c_mat, chunk=chunk)

    y = y + xs.astype(y.dtype) * p["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(bsz, s, d_inner)
    # gated RMSNorm (mamba2: norm(y * silu(z)))
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(compute_dtype), p["norm"], 1e-5)
    out = y @ cast(p["out_proj"], compute_dtype)
    return out, new_cache


def init_ssm_cache(bsz: int, d_inner: int, state_dim: int, head_dim: int,
                   n_groups: int, conv_width: int,
                   dtype: Any = jnp.float32) -> dict[str, jax.Array]:
    n_heads = d_inner // head_dim
    conv_ch = d_inner + 2 * n_groups * state_dim
    return {
        "conv": jnp.zeros((bsz, conv_width - 1, conv_ch), dtype),
        "ssm": jnp.zeros((bsz, n_heads, head_dim, state_dim), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }
