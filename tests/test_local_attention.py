"""Sliding-window attention past its window (``local_attention``, the XLA
path ``attn_block`` takes for ``s > window``) against a plain float32
softmax under the sliding-window mask."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import local_attention

KEY = jax.random.PRNGKey(5)


def plain_swa(q, k, v, window):
    """softmax(q kᵀ / √d) v over keys j with 0 <= i - j < window, in
    float32 NumPy; GQA by repeating each KV head over its query heads."""
    q, k, v = (np.asarray(t, np.float32) for t in (q, k, v))
    s, hq, d = q.shape[1], q.shape[2], q.shape[3]
    k = np.repeat(k, hq // k.shape[2], axis=2)
    v = np.repeat(v, hq // v.shape[2], axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.float32(np.sqrt(d))
    dist = np.arange(s)[:, None] - np.arange(s)[None, :]
    scores = np.where((dist >= 0) & (dist < window), scores, -np.inf)
    probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("s,window,hq,hkv", [
    (256, 64, 4, 4),      # S a multiple of the window
    (200, 64, 4, 4),      # S % window != 0: the padded tail
    (192, 48, 8, 2),      # GQA, 4 query heads a KV head
], ids=["whole-chunks", "padded", "gqa"])
def test_matches_plain_sliding_window_softmax(s, window, hq, hkv):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, s, hq, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, s, hkv, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, s, hkv, 32), jnp.float32)
    out = jax.jit(local_attention, static_argnums=3)(q, k, v, window)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), plain_swa(q, k, v, window),
                               atol=2e-5, rtol=2e-5)
