"""Pallas TPU flash attention (GQA, causal, sliding-window) with its backward.

TPU-native design notes (hardware adaptation, see DESIGN.md):
* Operands are (B·H, S, D): the wrapper lays (B, S, H, D) out head-major
  once, and every kernel tiles (BQ, D) query and (BK, D) key blocks into
  VMEM. D is the block's whole last dim, so any head size compiles
  (80 fills 80 of the 128 lanes).
* Forward, grid = (B·Hq, S/BQ, S/BK): the KV axis is the innermost grid
  axis, so the online-softmax state (m, l, acc) lives in VMEM scratch
  across KV steps (TPU grids run sequentially per core, the analogue of
  a CUDA persistent-CTA loop). It also writes each row's log-sum-exp,
  the one residual the backward needs besides q, k, v and the output.
* Backward, the standard two kernels, each recomputing P = exp(S - lse)
  per tile: ``flash_dq`` (grid as the forward) accumulates dq over key
  blocks; ``flash_dkv``, grid = (B·Hkv, S/BK, Hq/Hkv, S/BQ), accumulates
  dk and dv of one KV head over its query heads and query blocks, so
  GQA needs no reduction outside the kernel. It works on transposed
  (BK, BQ) tiles, where lse and D = rowsum(dO·O) are lane rows.
* GQA is resolved in the index maps: query head h reads KV head
  h // (Hq/Hkv); nothing is repeated in HBM.
* Masking is per (BQ, BK) tile: a tile that the causal or window mask
  covers whole does no MXU work, and its index maps re-point at a block
  already in VMEM, so it costs no DMA either; only tiles that the mask
  cuts build and apply one.
* Matmul operands keep their dtype (bf16 in the model); scores,
  accumulators, the running max and sum and lse are f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
LANES = 128
#: (Q rows) x (K rows)ᵀ: contract the last dim of both
NT = (((1,), (1,)), ((), ()))
#: scoped VMEM of each kernel: 1024-row blocks keep several (1024, 1024)
#: f32 tiles live at once (a v5e core has 128 MiB)
VMEM_LIMIT = 64 * 1024 * 1024


def block_size(s: int) -> int:
    """The query and key block for sequence length ``s``: the largest of
    1024, 512 and 256 that divides ``s``, else 128 (``s`` padded to a
    multiple). At danube's (S 2048, D 80) on a v5e, 1024 was the fastest
    of 128 to 1024, forward and backward (PERF.md, Findings)."""
    for b in (1024, 512, 256):
        if s % b == 0:
            return b
    return LANES


def _extent(q_start, k_start, *, block, causal, window, valid_len):
    """(live, cut) of the tile at (q_start, k_start): some pair in it is
    attended; some pair in it is masked. ``valid_len`` None: no keys
    are padding."""
    last = block - 1
    live, cut = True, False
    if causal:
        live = k_start <= q_start + last
        cut = k_start + last > q_start
    if window:
        live = jnp.logical_and(live, k_start + last > q_start - window)
        cut = jnp.logical_or(cut, q_start + last - k_start >= window)
    if valid_len is not None:
        cut = jnp.logical_or(cut, k_start + block > valid_len)
    return live, cut


def _mask(q_start, k_start, shape, q_axis, *, causal, window, valid_len):
    """Attended pairs of a tile whose query positions run along axis
    ``q_axis`` of ``shape``."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask = q_pos >= k_pos
    if window:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    if valid_len is not None:
        mask = jnp.logical_and(mask, k_pos < valid_len)
    return mask


def _live_range(i, block, n, *, causal, window, keys):
    """First and last block of the other sequence axis that block ``i``
    of this one attends to (``keys``: this axis is the query axis)."""
    start = i * block
    if keys:          # query block i: key blocks up to the diagonal
        lo = jnp.maximum(start - window + 1, 0) // block if window else 0
        hi = (start + block - 1) // block if causal else n - 1
    else:             # key block i: query blocks from the diagonal
        lo = start // block if causal else 0
        hi = ((start + block + window - 2) // block if window else n - 1)
    return lo, jnp.minimum(hi, n - 1)


def _when_tile(live, cut, body):
    """Run ``body(masked)`` on a live tile: masked only where cut."""
    if isinstance(cut, bool):         # no mask at all: fixed at trace time
        pl.when(live)(lambda: body(cut))
        return

    @pl.when(jnp.logical_and(live, cut))
    def _masked():
        body(True)

    @pl.when(jnp.logical_and(live, jnp.logical_not(cut)))
    def _whole():
        body(False)


def _query_grid_specs(block, d, n, group, causal, window):
    """Block specs of a (B·Hq, S/BQ, S/BK) grid: the query head's row
    block, its KV head's key block (a dead tile re-points at the last
    live one, so it costs no DMA), and the row block's lse or D lane row."""
    def key_block(bh, qi, ki):
        lo, hi = _live_range(qi, block, n, causal=causal, window=window,
                             keys=True)
        return (bh // group, jnp.clip(ki, lo, hi), 0)

    return (pl.BlockSpec((1, block, d), lambda bh, qi, _: (bh, qi, 0)),
            pl.BlockSpec((1, block, d), key_block),
            pl.BlockSpec((1, 1, block), lambda bh, qi, _: (bh, 0, qi)))


def _row_to_col(row):
    """(1, n) lane row -> (n, LANES) with each row's value in every lane."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block, n, geom):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * block, ki * block

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = jax.lax.dot_general(q_ref[0], k_ref[0], NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            mask = _mask(q_start, k_start, (block, block), 0, **geom)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                   # (BQ, 128)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        if masked:   # a row masked whole in this tile adds nothing
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    _when_tile(*_extent(q_start, k_start, block=block, **geom), body)

    @pl.when(ki == n - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l_safe)                    # (BQ, 128)
        lse_ref[0] = jnp.transpose(lse)[:1]                   # (1, BQ)


def _forward(q, k, v, *, group, block, interpret, geom):
    """o (B·Hq, S, D) and lse (B·Hq, 1, S) f32."""
    bhq, s, d = q.shape
    n = s // block
    rows, keys, stats = _query_grid_specs(block, d, n, group, geom["causal"],
                                          geom["window"])
    kernel = functools.partial(_fwd_kernel, scale=d ** -0.5, block=block,
                               n=n, geom=geom)
    return pl.pallas_call(
        kernel,
        grid=(bhq, n, n),
        in_specs=[rows, keys, keys],
        out_specs=[rows, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bhq, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_scr, di_scr, acc_scr, *, scale, block, n, geom):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * block, ki * block

    @pl.when(ki == 0)
    def _init():
        lse_scr[...] = _row_to_col(lse_ref[0])
        di_scr[...] = _row_to_col(di_ref[0])
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_mask(q_start, k_start, (block, block), 0, **geom),
                          s, NEG_INF)
        p = jnp.exp(s - lse_scr[:, :1])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_scr[:, :1])
        acc_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    _when_tile(*_extent(q_start, k_start, block=block, **geom), body)

    @pl.when(ki == n - 1)
    def _finalize():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, block, n, group, geom):
    ki, g, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    q_start, k_start = qi * block, ki * block

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        st = jax.lax.dot_general(k_ref[0], q, NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = jnp.where(_mask(q_start, k_start, (block, block), 1, **geom),
                           st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                         # (BK, BQ)
        dv_scr[...] += jax.lax.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0])
        dk_scr[...] += jax.lax.dot(dst.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _when_tile(*_extent(q_start, k_start, block=block, **geom), body)

    @pl.when(jnp.logical_and(g == group - 1, qi == n - 1))
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, *, group, block, interpret, geom):
    bhq, s, d = q.shape
    bhkv = k.shape[0]
    n = s // block
    causal, window = geom["causal"], geom["window"]
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, None, :]                         # (B·Hq, 1, S)
    params = functools.partial(pltpu.CompilerParams,
                               vmem_limit_bytes=VMEM_LIMIT)
    rows, keys, stats = _query_grid_specs(block, d, n, group, causal, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=d ** -0.5, block=block, n=n,
                          geom=geom),
        grid=(bhq, n, n),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, di)

    def query_block(bkv, ki, g, qi):
        """Query head g of the KV head's group; a dead tile re-points at
        the last live query block."""
        lo, hi = _live_range(ki, block, n, causal=causal, window=window,
                             keys=False)
        return bkv * group + g, jnp.clip(qi, lo, hi)

    def q_rows(*idx):
        head, qb = query_block(*idx)
        return (head, qb, 0)

    def q_stats(*idx):
        head, qb = query_block(*idx)
        return (head, 0, qb)

    rows = pl.BlockSpec((1, block, d), q_rows)
    stats = pl.BlockSpec((1, 1, block), q_stats)
    own = pl.BlockSpec((1, block, d), lambda bkv, ki, g, qi: (bkv, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=d ** -0.5, block=block, n=n,
                          group=group, geom=geom),
        grid=(bhkv, n, group, n),
        in_specs=[rows, own, own, rows, stats, stats],
        out_specs=[own, own],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=params(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable entry point


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, group, block, interpret, geom):
    return _forward(q, k, v, group=group, block=block, interpret=interpret,
                    geom=dict(geom))[0]


def _attention_fwd(q, k, v, group, block, interpret, geom):
    o, lse = _forward(q, k, v, group=group, block=block,
                      interpret=interpret, geom=dict(geom))
    return o, (q, k, v, o, lse)


def _attention_bwd(group, block, interpret, geom, res, do):
    return _backward(*res, do, group=group, block=block,
                     interpret=interpret, geom=dict(geom))


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(
    q: jax.Array,            # (B, S, Hq, D)
    k: jax.Array,            # (B, S, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block: int | None = None,
    interpret: bool = False,
    valid_len: int | None = None,
) -> jax.Array:
    """Blockwise attention, exact (online softmax), differentiable
    through its own backward kernels. S must be a multiple of the block
    (``block_size(S)`` by default; the ops wrapper pads); keys at or past
    ``valid_len`` are masked."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    block = block or block_size(s)
    assert s % block == 0, (s, block)
    geom = (("causal", causal), ("window", window),
            ("valid_len", None if valid_len in (None, s) else valid_len))

    def heads_major(x):      # (B, S, H, D) -> (B·H, S, D)
        return x.transpose(0, 2, 1, 3).reshape(-1, s, d)

    out = _attention(heads_major(q), heads_major(k), heads_major(v),
                     hq // hkv, block, interpret, geom)
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
