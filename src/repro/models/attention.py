"""Attention: GQA/MQA/MHA, causal + bidirectional, sliding-window.

Three structural XLA code paths:

* ``full_attention``  — S×S masked attention (causal or bidirectional).
* ``local_attention`` — chunk-banded SWA: each W-query chunk attends to
  its own and the previous chunk, so FLOPs scale as S·2W not S².
* ``decode_attention``— one query against a KV cache.

On a TPU the causal full-sequence case runs the Pallas flash kernel
(``kernels/flash_attention.py``, with its own backward) instead of
``full_attention``; ``flash_selected`` decides from what the code
observes, and ``attn_block`` tallies each choice
(``papas.attn.kernel`` / ``papas.attn.xla``, ``core/telemetry.py``).

Shapes: q (B,S,Hq,D); k,v (B,S,Hkv,D); GQA groups Hq into Hkv bundles.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import apply_rope, cast, maybe_shard, rms_norm

NEG_INF = -2.0e38


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return jnp.tanh(scores / cap) * cap
    return scores


def _repeat_kv(k: jax.Array, n_q: int) -> jax.Array:
    """GQA → MHA expansion: (B,S,Hkv,D) → (B,S,Hq,D).

    The repeated-KV formulation keeps every XLA attention einsum
    shardable over the *query*-head axis (Hq is a multiple of the TP
    degree even when Hkv is not, e.g. kv=8 on a 16-way model axis); the
    expansion is a cheap gather that GSPMD shards on the head dim. The
    flash kernel needs no expansion: it takes the model axis only where
    that axis divides Hkv too (``flash_selected``)."""
    hkv = k.shape[2]
    if hkv == n_q:
        return k
    return jnp.repeat(k, n_q // hkv, axis=2)


def _sdp(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array | None,
         softcap: float) -> jax.Array:
    """Masked softmax(QKᵀ)V on (B,S,H,D) operands (softmax fp32).

    fp32 comes from the dot's ACCUMULATOR (preferred_element_type), not a
    result cast: ``convert(dot_bf16)`` is algebraically rewritten to
    ``dot(convert(k))`` — materializing an fp32 copy of the whole KV
    cache in the decode path."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * (d ** -0.5), k,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores, softcap)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True,
    softcap: float = 0.0,
) -> jax.Array:
    """Masked softmax attention over the whole (Sq, Sk) score matrix."""
    sq, hq = q.shape[1], q.shape[2]
    kf = _repeat_kv(k, hq)
    vf = _repeat_kv(v, hq)
    sk = kf.shape[1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
    return _sdp(q, kf, vf, mask, softcap)


def local_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    window: int,
    causal: bool = True,
) -> jax.Array:
    """Chunk-banded sliding-window attention.

    Queries in chunk c attend to keys in chunks c-1 and c, masked to the
    true window: allowed iff 0 <= q_pos - k_pos < window.  Exact for
    window <= chunk width (we use chunk = window).
    """
    b, s, hq, d = q.shape
    w = min(window, s)
    if s % w != 0:
        pad = w - s % w
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        pad = 0
    sp = q.shape[1]
    c = sp // w
    kf = _repeat_kv(k, hq)
    vf = _repeat_kv(v, hq)
    qc = q.reshape(b, c, w, hq, d)
    kc = kf.reshape(b, c, w, hq, d)
    vc = vf.reshape(b, c, w, hq, d)
    # previous chunk: shift right; chunk 0's "previous" is masked out
    k2 = jnp.concatenate([jnp.roll(kc, 1, axis=1), kc], axis=2)  # (B,C,2W,·)
    v2 = jnp.concatenate([jnp.roll(vc, 1, axis=1), vc], axis=2)

    i = jnp.arange(w)[:, None]
    j = jnp.arange(2 * w)[None, :]
    dist = i + w - j
    band = (dist >= 0) & (dist < w) if causal else (jnp.abs(dist) < w)

    scores = jnp.einsum("bcqhd,bckhd->bchqk", qc * (d ** -0.5), k2
                        ).astype(jnp.float32)
    chunk_idx = jnp.arange(c)[:, None, None]
    mask = band[None] & ~((chunk_idx == 0) & (j[None] < w))  # (C,W,2W)
    scores = jnp.where(mask[None, :, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bchqk,bckhd->bcqhd", probs, v2)
    out = out.reshape(b, sp, hq, d)
    return out[:, :s] if pad else out


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    lengths: jax.Array,
    softcap: float = 0.0,
) -> jax.Array:
    """One new query per sequence against the KV cache.

    q (B,1,Hq,D); caches (B,T,Hkv,D); lengths (B,) valid entries.

    Formulated as broadcast-multiply-reduce rather than dots: XLA fuses
    the product into the reduction, so neither a GQA-expanded KV copy
    nor an fp32-converted cache is ever materialized (XLA-CPU emulates
    bf16 dots by fp32-converting whole operands — fatal at 32k-deep
    caches; TPU Mosaic is unaffected but the fused form is never worse).
    """
    b, _, hq, d = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = (q[:, 0].reshape(b, hkv, g, d) * (d ** -0.5))      # (B,Hkv,G,D)
    # flash-decode: stream KV blocks with an online softmax.  Block-wise
    # dynamic slices defeat XLA's loop-invariant convert hoisting (which
    # otherwise materializes an fp32 copy of the WHOLE cache) and bound
    # live temps to one (B,blk,Hkv,G,D) product.
    blk = t if t % 4096 else 4096
    nb = t // blk

    def body(carry, idx):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k_cache, idx * blk, blk, 1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_cache, idx * blk, blk, 1)
        s = jnp.sum(qg[:, None] * k_blk[:, :, :, None, :], axis=-1,
                    dtype=jnp.float32)                       # (B,blk,Hkv,G)
        s = _softcap(s, softcap)
        kpos = idx * blk + jnp.arange(blk)
        valid = (kpos[None, :] < lengths[:, None])[:, :, None, None]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))           # (B,Hkv,G)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[..., None] + jnp.sum(
            p[..., None].astype(v_blk.dtype) * v_blk[:, :, :, None, :],
            axis=1, dtype=jnp.float32)                       # (B,Hkv,G,D)
        return (m_new, l, acc), None

    init = (jnp.full((b, hkv, g), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g), jnp.float32),
            jnp.zeros((b, hkv, g, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nb))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# The Pallas flash kernel: where it applies
# ---------------------------------------------------------------------------

def flash_selected(*, backend: str, cached: bool, softcap: float, seq: int,
                   n_heads: int, n_kv_heads: int, model_axis: int) -> bool:
    """Causal full-sequence attention runs the flash kernel on a TPU,
    without a KV cache (training, forward), without a logit softcap
    (the kernel has none), over at least one kernel block, with whole
    GQA groups on each device: the ``model`` mesh axis divides both the
    query and the key/value heads (32/8 heads over 4 leave 8/2)."""
    if backend != "tpu":          # Pallas (and its import) only on a TPU
        return False
    from repro.kernels.flash_attention import LANES
    return (not cached and not softcap and seq >= LANES
            and n_heads % model_axis == 0 and n_kv_heads % model_axis == 0)


def _flash(q: jax.Array, k: jax.Array, v: jax.Array, window: int
           ) -> jax.Array:
    """The kernel on each device's share: a Mosaic kernel is not
    partitioned by the compiler, so on a mesh it runs under
    ``shard_map``, the batch over the data axes and, where the
    ``model`` axis is larger than one, the heads over it. Each device
    then holds whole GQA groups, so neither pass needs a collective."""
    from repro.kernels import ops

    def fn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, window=window)

    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1:
        return fn(q, k, v)
    batch = tuple(a for a in am.axis_names if a != "model")
    heads = "model" if am.shape.get("model", 1) > 1 else None
    spec = P(batch, None, heads, None)
    return jax.shard_map(fn, mesh=am, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Full attention sub-block (projections + rope + attention + out-proj)
# ---------------------------------------------------------------------------

def attn_block(
    x: jax.Array,
    p: dict[str, jax.Array],
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    kind: str,                 # attn | swa | enc
    window: int,
    positions: jax.Array,
    rope_theta: float,
    softcap: float = 0.0,
    qk_norm: bool = False,
    norm_eps: float = 1e-6,
    compute_dtype: Any = jnp.bfloat16,
    cache: dict[str, jax.Array] | None = None,
) -> tuple[jax.Array, dict[str, jax.Array] | None]:
    """Complete attention sub-layer.  With ``cache`` (decode), x is
    (B,1,d) and the cache is updated at ``cache['pos']``."""
    b, s, _ = x.shape
    xc = cast(x, compute_dtype)
    q = (xc @ cast(p["wq"], compute_dtype)).reshape(b, s, n_heads, head_dim)
    k = (xc @ cast(p["wk"], compute_dtype)).reshape(b, s, n_kv_heads, head_dim)
    v = (xc @ cast(p["wv"], compute_dtype)).reshape(b, s, n_kv_heads, head_dim)
    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    # every kind takes RoPE, encoders too (their positional signal here)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)

    new_cache = None
    flash = False
    if cache is not None:
        # decode: write k,v at pos (ring for SWA), then attend over cache
        t = cache["k"].shape[1]
        pos = cache["pos"]                                  # scalar int32
        slot = jnp.where(jnp.asarray(window > 0), pos % t, pos) if kind == "swa" else pos
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        lengths = jnp.minimum(pos + 1, t) * jnp.ones((b,), jnp.int32)
        out = decode_attention(q, k_cache, v_cache, lengths, softcap)
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}
    elif kind == "swa" and window and s > window:
        out = local_attention(q, k, v, window, causal=True)
    elif kind == "enc":
        out = full_attention(q, k, v, causal=False, softcap=softcap)
    elif flash_selected(
            backend=jax.default_backend(), cached=cache is not None,
            softcap=softcap, seq=s, n_heads=n_heads, n_kv_heads=n_kv_heads,
            model_axis=jax.sharding.get_abstract_mesh().shape.get("model", 1)):
        flash = True
        out = _flash(q, k, v, window if kind == "swa" else 0)
    else:
        out = full_attention(q, k, v, causal=True, softcap=softcap)
    from repro.core.telemetry import tally
    tally("attn.kernel" if flash else "attn.xla")
    out = out.reshape(b, s, n_heads * head_dim)
    return out @ cast(p["wo"], compute_dtype), new_cache
