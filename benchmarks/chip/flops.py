"""Model FLOPs of one training token, forward and backward.

    train FLOPs / token = 6 * N_matmul + 3 * sum over layers of the
                          layer's sequence-mixing FLOPs per token (forward)

``N_matmul`` counts every weight that multiplies activations: the
attention and MLP projections, the SSD in/out projections and the
output head (the tied embedding counts once, as the head). The
embedding lookup is a gather and counts nothing. Norms, rotary
embeddings and softmax are not counted. Recomputation (remat) is not
counted: these are the FLOPs the model needs, not the FLOPs the program
spends.

Sequence mixing, forward, per token and layer:

* attention: QK^T and PV, ``4 * n_heads * head_dim * ctx``, where ctx is
  the mean number of keys a causal query sees, clipped to the window:
  ``mean over i < S of min(i + 1, window)``.
* SSD (chunked, chunk Q, heads H, head dim P, state N): the causal half
  of the intra-chunk C.B^T and scores.X products,
  ``2 * (N + P) * (Q + 1) / 2`` per head, plus the chunk-state and
  inter-chunk output products, ``2 * P * N`` each; and the depthwise
  causal conv, ``2 * K * conv_channels``.

The configuration is the benchmark's own JSON model block; nothing is
read from the program.
"""
from __future__ import annotations

from typing import Any, Mapping

ATTN_KINDS = ("attn", "swa")


def mean_causal_context(seq: int, window: int = 0) -> float:
    """Mean keys per query under a causal mask clipped to ``window``."""
    w = window if window and window < seq else seq
    # queries 0..w-1 see i+1 keys; the rest see w
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def matmul_params(m: Mapping[str, Any]) -> int:
    """Weights that multiply activations, head included, lookup excluded."""
    d, n = m["d_model"], 0
    for kind in m["layer_types"]:
        if kind in ATTN_KINDS:
            n += 2 * d * m["n_heads"] * m["head_dim"]          # wq, wo
            n += 2 * d * m["n_kv_heads"] * m["head_dim"]       # wk, wv
            n += 3 * d * m["d_ff"]                             # SwiGLU
        elif kind == "ssm":
            di = m["ssm_expand"] * d
            gn = m["ssm_groups"] * m["ssm_state"]
            heads = di // m["ssm_head_dim"]
            n += d * (2 * di + 2 * gn + heads) + di * d        # in, out
        else:
            raise ValueError(f"no FLOP count for layer kind {kind!r}")
    return n + d * m["vocab_size"]                             # head


def mixing_flops_forward(m: Mapping[str, Any], seq: int) -> float:
    """Sequence-mixing FLOPs per token of one forward pass."""
    total = 0.0
    d = m["d_model"]
    for kind in m["layer_types"]:
        if kind in ATTN_KINDS:
            window = m.get("window", 0) if kind == "swa" else 0
            total += (4 * m["n_heads"] * m["head_dim"]
                      * mean_causal_context(seq, window))
        elif kind == "ssm":
            di = m["ssm_expand"] * d
            p, n = m["ssm_head_dim"], m["ssm_state"]
            heads = di // p
            q = min(m["ssm_chunk"], seq)
            total += heads * ((n + p) * (q + 1) + 4 * p * n)
            total += 2 * m["ssm_conv"] * (di + 2 * m["ssm_groups"] * n)
    return total


def train_flops_per_token(m: Mapping[str, Any], seq: int) -> float:
    return 6.0 * matmul_params(m) + 3.0 * mixing_flops_forward(m, seq)
