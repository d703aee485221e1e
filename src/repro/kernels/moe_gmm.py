"""Pallas TPU grouped (ragged) expert matmul — megablox-style.

``grouped_matmul(x, w, group_sizes)`` computes, for tokens sorted by
expert id, ``y[t] = x[t] @ w[expert_of(t)]`` without densifying the
expert dimension.

TPU adaptation: rows are re-packed so every expert's segment occupies
whole (BT)-row blocks (static worst-case padding of E·BT rows keeps the
shape jittable).  A per-block expert-id array is passed through
*scalar prefetch* (``pltpu.PrefetchScalarGridSpec``) so the weight
BlockSpec's index map can select the right expert slab — the TPU
equivalent of megablocks' block-sparse GEMM descriptor.  Each program
runs one (BT×d)·(d×BF) MXU matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack(x: jax.Array, group_sizes: jax.Array, block_rows: int
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pack sorted rows so each group starts on a block boundary.

    Returns (x_packed (Tp, d), block_expert (Tp/BT,), row_map (T,))
    where row_map gives each original row's position in the packed
    buffer.  Tp = T + E·BT is static worst case.
    """
    t, d = x.shape
    e = group_sizes.shape[0]
    tp = t + e * block_rows

    padded = ((group_sizes + block_rows - 1) // block_rows) * block_rows
    pad_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(padded)[:-1].astype(jnp.int32)])
    raw_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])

    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(t, dtype=jnp.int32)
    expert_of = jnp.searchsorted(ends, rows, side="right",
                                 method="compare_all").astype(jnp.int32)
    row_map = pad_off[expert_of] + (rows - raw_off[expert_of])

    nblocks = tp // block_rows
    block_start = jnp.arange(nblocks, dtype=jnp.int32) * block_rows
    block_expert = jnp.searchsorted(
        jnp.cumsum(padded), block_start, side="right",
        method="compare_all").astype(jnp.int32)
    block_expert = jnp.minimum(block_expert, e - 1)

    # packed row -> source row, as a gather: a scatter of (T, d) rows
    # takes the TPU compiler many seconds at MoE widths, a gather does not
    prow = jnp.arange(tp, dtype=jnp.int32)
    pe = jnp.repeat(block_expert, block_rows, total_repeat_length=tp)
    offset = prow - pad_off[pe]
    valid = offset < group_sizes[pe]
    src = jnp.clip(raw_off[pe] + offset, 0, t - 1)
    x_packed = jnp.where(valid[:, None], jnp.take(x, src, axis=0),
                         jnp.zeros((), x.dtype))
    return x_packed, block_expert, row_map


def _gmm_kernel(block_expert_ref, x_ref, w_ref, o_ref):
    del block_expert_ref  # consumed by the index maps
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_matmul(
    x: jax.Array,             # (T, d) rows sorted by expert
    w: jax.Array,             # (E, d, f)
    group_sizes: jax.Array,   # (E,) int32, sums to T
    *,
    block_rows: int = 128,
    block_cols: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Ragged grouped matmul → (T, f)."""
    t, d = x.shape
    e, _, f = w.shape
    assert f % block_cols == 0, (f, block_cols)
    x_packed, block_expert, row_map = _pack(x, group_sizes, block_rows)
    nblocks = x_packed.shape[0] // block_rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks, f // block_cols),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i, j, be: (i, 0)),
            pl.BlockSpec((1, d, block_cols), lambda i, j, be: (be[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols),
                               lambda i, j, be: (i, j)),
    )
    out_packed = pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((x_packed.shape[0], f), x.dtype),
        interpret=interpret,
    )(block_expert, x_packed, w)
    return jnp.take(out_packed, row_map, axis=0)
