"""Pallas TPU kernels for the Mamba2 SSD chunked scan.

Decomposition (mirrors the reference ``ssd_chunked``):

1. ``_intra_kernel`` — grid (B, H, C): per chunk computes the
   intra-chunk output Y_intra (decay-masked C·Bᵀ "attention" — two MXU
   matmuls of (Q,N)·(N,Q) and (Q,Q)·(Q,P)) and the end-of-chunk state
   contribution (P,N).
2. host: tiny ``jax.lax.associative_scan`` across the C chunk states
   (O(C·H·P·N) — negligible).
3. ``_inter_kernel`` — grid (B, H, C): adds the inter-chunk term
   C·state_prev scaled by the within-chunk decay (one (Q,N)·(N,P) MXU
   matmul per chunk).

VMEM per program: Q·N + Q·P + Q·Q + P·N fp32 ≈ 0.9 MB for
(Q,P,N)=(256,64,128) — comfortably under the ~16 MB/core budget, and
every matmul dimension is a multiple of 64/128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _intra_kernel(x_ref, cr_ref, cc_ref, w_ref, b_ref, c_ref, y_ref,
                  st_ref, *, chunk: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)      # (Q, P)
    cum_row = cr_ref[0, 0, 0]                   # (1, Q) inclusive cumsum
    cum_col = cc_ref[0, 0, 0]                   # (Q, 1) the same, as a column
    w = w_ref[0, 0, 0]                          # (Q, 1) exp(total - cum)
    bm = b_ref[0, 0, 0].astype(jnp.float32)     # (Q, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)     # (Q, N)

    # intra-chunk decay-masked scores
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    delta = jnp.where(mask, cum_col - cum_row, 0.0)  # mask BEFORE exp
    decay = jnp.where(mask, jnp.exp(delta), 0.0)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * decay        # (Q, Q)
    y_ref[0, 0, 0] = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    # chunk state: Σ_j exp(total - cum_j) x_j ⊗ B_j   → (P, N)
    xw = x * w                                             # (Q, P)
    st_ref[0, 0, 0] = jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(st_ref.dtype)


def _inter_kernel(c_ref, prev_ref, cc_ref, yin_ref, y_ref):
    cm = c_ref[0, 0, 0].astype(jnp.float32)      # (Q, N)
    prev = prev_ref[0, 0, 0].astype(jnp.float32) # (P, N)
    dec = jnp.exp(cc_ref[0, 0, 0])               # (Q, 1) decay from chunk start
    y_inter = jax.lax.dot_general(
        cm, prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dec          # (Q, P)
    y_ref[0, 0, 0] = (yin_ref[0, 0, 0].astype(jnp.float32) + y_inter
                   ).astype(y_ref.dtype)


def ssd_scan(
    x: jax.Array,      # (B, S, H, P) — dt-scaled inputs
    log_a: jax.Array,  # (B, S, H)
    b_mat: jax.Array,  # (B, S, G, N)
    c_mat: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 256,
    initial_state: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full SSD scan via two Pallas kernels + a host associative scan.

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    c = s // q
    rep = h // g

    # layout: (B, H, C, Q, ·) so the grid walks contiguous VMEM blocks
    xr = x.transpose(0, 2, 1, 3).reshape(bsz, h, c, q, p)
    lar = log_a.transpose(0, 2, 1).reshape(bsz, h, c, q)
    # within-chunk cumulative log-decay, once in XLA; the kernels take it
    # as a row and as a column, which the TPU tiling needs as 2-D blocks
    cum = jnp.cumsum(lar.astype(jnp.float32), axis=-1)    # (B,H,C,Q)
    cum_row, cum_col = cum[..., None, :], cum[..., None]
    total = cum[..., -1:]                                  # (B,H,C,1)
    w_col = jnp.exp(total - cum)[..., None]                # decay to chunk end
    seg = jnp.exp(total[..., 0])                           # (B,H,C)
    bh = jnp.repeat(b_mat, rep, axis=2)
    ch = jnp.repeat(c_mat, rep, axis=2)
    bhr = bh.transpose(0, 2, 1, 3).reshape(bsz, h, c, q, n)
    chr_ = ch.transpose(0, 2, 1, 3).reshape(bsz, h, c, q, n)

    grid = (bsz, h, c)
    bspec = lambda *blk: pl.BlockSpec(  # noqa: E731
        (1, 1, 1) + blk, lambda bb, hh, cc: (bb, hh, cc) + (0,) * len(blk))

    y_intra, states = pl.pallas_call(
        functools.partial(_intra_kernel, chunk=q),
        grid=grid,
        in_specs=[bspec(q, p), bspec(1, q), bspec(q, 1), bspec(q, 1),
                  bspec(q, n), bspec(q, n)],
        out_specs=[bspec(q, p), bspec(p, n)],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, c, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, c, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xr, cum_row, cum_col, w_col, bhr, chr_)

    # ---- host: inter-chunk associative scan (tiny) --------------------
    def combine(left, right):
        a_l, s_l = left
        a_r, s_r = right
        return a_l * a_r, s_l * a_r[..., None, None] + s_r

    a_scan, s_scan = jax.lax.associative_scan(combine, (seg, states), axis=2)
    if initial_state is not None:
        init = initial_state.astype(jnp.float32)[:, :, None]
        prev = jnp.concatenate(
            [init, s_scan[:, :, :-1]
             + init * a_scan[:, :, :-1, None, None]], axis=2)
        final = s_scan[:, :, -1] + init[:, :, 0] * a_scan[:, :, -1, None, None]
    else:
        prev = jnp.concatenate(
            [jnp.zeros_like(s_scan[:, :, :1]), s_scan[:, :, :-1]], axis=2)
        final = s_scan[:, :, -1]

    y = pl.pallas_call(
        _inter_kernel,
        grid=grid,
        in_specs=[bspec(q, n), bspec(p, n), bspec(q, 1), bspec(q, p)],
        out_specs=bspec(q, p),
        out_shape=jax.ShapeDtypeStruct((bsz, h, c, q, p), x.dtype),
        interpret=interpret,
    )(chr_, prev, cum_col, y_intra)

    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)      # (B,S,H,P)
    return y, final.astype(x.dtype)
