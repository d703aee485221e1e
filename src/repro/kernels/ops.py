"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs as plain JAX ops, which validates correctness. Every
other backend compiles the real kernels, so a device that cannot run
them fails loudly instead of silently interpreting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import moe_gmm as _gmm
from . import ssd_scan as _ssd


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """Flash attention, differentiable, with the sequence padded to a
    multiple of the kernel's block (``block_size(S)``)."""
    s = q.shape[1]
    block = _fa.block_size(s)
    pad = (-s) % block
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    out = _fa.flash_attention(
        q, k, v, causal=causal, window=window, block=block,
        interpret=_interpret_default(), valid_len=s)
    return out[:, :s] if pad else out


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, log_a, b_mat, c_mat, *, chunk: int = 256,
             initial_state=None):
    return _ssd.ssd_scan(x, log_a, b_mat, c_mat, chunk=chunk,
                         initial_state=initial_state,
                         interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols"))
def grouped_matmul(x, w, group_sizes, *, block_rows: int = 128,
                   block_cols: int = 128):
    f = w.shape[-1]
    bc = min(block_cols, f)
    while f % bc:
        bc -= 1
    return _gmm.grouped_matmul(x, w, group_sizes,
                               block_rows=block_rows, block_cols=bc,
                               interpret=_interpret_default())
