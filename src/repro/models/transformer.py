"""Model assembly: embeddings → scan-compiled layer segments → head.

Consecutive layers of the same kind are grouped into *segments*; each
segment's parameters are stacked on a leading axis and executed with
``jax.lax.scan`` (one trace per segment → fast compiles for 48-layer
models).  Heterogeneous patterns (gemma3's 5 local : 1 global, hymba's
3 global layers) become short segment lists that preserve exact layer
order.

Aux losses (MoE load-balance / router-z) are accumulated through the
scan carry.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .attention import attn_block
from .config import ArchConfig
from .layers import (
    cast, embed_tokens, layer_norm, mlp, normal_init, rms_norm, unembed,
)
from .moe import moe_block
from .ssm import init_ssm_cache, mamba2_block

ZERO_AUX = lambda: {  # noqa: E731
    "load_balance": jnp.zeros((), jnp.float32),
    "router_z": jnp.zeros((), jnp.float32),
    "dropped": jnp.zeros((), jnp.float32),
}


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------

def _init_attn(key: jax.Array, cfg: ArchConfig) -> dict[str, jax.Array]:
    ks = jax.random.split(key, 4)
    d, ad, kd = cfg.d_model, cfg.attn_dim, cfg.n_kv_heads * cfg.head_dim
    dt = cfg.param_dtype
    p = {
        "wq": normal_init(ks[0], (d, ad), dt),
        "wk": normal_init(ks[1], (d, kd), dt),
        "wv": normal_init(ks[2], (d, kd), dt),
        "wo": normal_init(ks[3], (ad, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dt)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dt)
    return p


def _init_mlp(key: jax.Array, cfg: ArchConfig) -> dict[str, jax.Array]:
    ks = jax.random.split(key, 3)
    d, ff, dt = cfg.d_model, cfg.d_ff, cfg.param_dtype
    if cfg.mlp_act == "gelu_nogate":
        return {
            "wi": normal_init(ks[0], (d, ff), dt),
            "bi": jnp.zeros((ff,), dt),
            "wo": normal_init(ks[1], (ff, d), dt),
            "bo": jnp.zeros((d,), dt),
        }
    return {
        "wi_gate": normal_init(ks[0], (d, ff), dt),
        "wi_up": normal_init(ks[1], (d, ff), dt),
        "wo": normal_init(ks[2], (ff, d), dt),
    }


def _init_moe(key: jax.Array, cfg: ArchConfig) -> dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, ffm, e, dt = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.param_dtype
    p: dict[str, Any] = {
        "router": normal_init(ks[0], (d, e), dt),
        "wi_gate": normal_init(ks[1], (e, d, ffm), dt),
        "wi_up": normal_init(ks[2], (e, d, ffm), dt),
        "wo": normal_init(ks[3], (e, ffm, d), dt),
    }
    if cfg.n_shared_experts:
        ffs = cfg.d_ff
        p["shared"] = {
            "wi_gate": normal_init(ks[4], (d, ffs), dt),
            "wi_up": normal_init(ks[5], (d, ffs), dt),
            "wo": normal_init(ks[6], (ffs, d), dt),
            "gate": normal_init(ks[7], (d, 1), dt),
        }
    return p


def _init_ssm(key: jax.Array, cfg: ArchConfig) -> dict[str, jax.Array]:
    ks = jax.random.split(key, 3)
    d, di, dt = cfg.d_model, cfg.d_inner, cfg.param_dtype
    h = cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state
    conv_ch = di + 2 * gn
    a_init = jnp.linspace(1.0, 16.0, h)
    return {
        "in_proj": normal_init(ks[0], (d, 2 * di + 2 * gn + h), dt),
        "conv_w": normal_init(ks[1], (cfg.ssm_conv, conv_ch), dt, 0.2),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "dt_bias": jnp.zeros((h,), dt),
        "A_log": jnp.log(a_init).astype(dt),
        "D": jnp.ones((h,), dt),
        "norm": jnp.zeros((di,), dt),
        "out_proj": normal_init(ks[2], (di, d), dt),
    }


def _init_layer(key: jax.Array, cfg: ArchConfig, kind: str) -> dict[str, Any]:
    ks = jax.random.split(key, 4)
    d, dt = cfg.d_model, cfg.param_dtype
    p: dict[str, Any] = {}
    if kind == "enc":
        p["norm1"] = {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
        p["norm2"] = {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
    else:
        p["norm1"] = jnp.zeros((d,), dt)
        if kind != "ssm":
            p["norm2"] = jnp.zeros((d,), dt)
    if kind in ("attn", "swa", "enc", "moe", "hyb_g", "hyb_l"):
        p["attn"] = _init_attn(ks[0], cfg)
    if kind in ("ssm", "hyb_g", "hyb_l"):
        p["ssm"] = _init_ssm(ks[1], cfg)
    if kind in ("hyb_g", "hyb_l"):
        p["branch_norm_attn"] = jnp.zeros((d,), dt)
        p["branch_norm_ssm"] = jnp.zeros((d,), dt)
    if kind == "moe":
        p["moe"] = _init_moe(ks[2], cfg)
    elif kind in ("attn", "swa", "enc", "hyb_g", "hyb_l") and cfg.d_ff:
        p["mlp"] = _init_mlp(ks[3], cfg)
    return p


def init_params(cfg: ArchConfig, key: jax.Array) -> dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 4)
    dt = cfg.param_dtype
    params: dict[str, Any] = {}
    params["embed"] = normal_init(keys[0], (cfg.vocab_size, cfg.d_model), dt)
    if cfg.input_mode in ("embeds", "mixed"):
        params["frontend_proj"] = normal_init(
            keys[1], (cfg.d_model, cfg.d_model), dt)
    # segments: stack per-layer params along a new leading axis
    segments: list[dict[str, Any]] = []
    li = 0
    for kind, count in cfg.segments():
        layers = [_init_layer(keys[2 + li + i], cfg, kind) for i in range(count)]
        li += count
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layers)
        segments.append(stacked)
    params["segments"] = segments
    if cfg.layer_types and cfg.layer_types[0] == "enc":
        params["final_norm"] = {"scale": jnp.ones((cfg.d_model,), dt),
                                "bias": jnp.zeros((cfg.d_model,), dt)}
    else:
        params["final_norm"] = jnp.zeros((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(
            keys[2 + cfg.n_layers], (cfg.d_model, cfg.vocab_size), dt)
    return params


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------

def _norm(x: jax.Array, p: Any, eps: float) -> jax.Array:
    if isinstance(p, dict):
        return layer_norm(x, p["scale"], p["bias"], eps)
    return rms_norm(x, p, eps)


def _attn_sublayer(cfg: ArchConfig, kind: str, x: jax.Array,
                   lp: dict[str, Any], positions: jax.Array,
                   cache: dict[str, jax.Array] | None
                   ) -> tuple[jax.Array, Any]:
    attn_kind = {"moe": "attn", "hyb_g": "attn", "hyb_l": "swa"}.get(kind, kind)
    theta = (cfg.rope_theta_global if attn_kind == "attn"
             else cfg.rope_theta)
    return attn_block(
        x, lp["attn"],
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, kind=attn_kind, window=cfg.window,
        positions=positions, rope_theta=theta,
        softcap=cfg.logit_softcap, qk_norm=cfg.qk_norm,
        norm_eps=cfg.norm_eps, compute_dtype=cfg.compute_dtype,
        cache=cache)


def _ffn_sublayer(cfg: ArchConfig, kind: str, x: jax.Array,
                  lp: dict[str, Any]
                  ) -> tuple[jax.Array, dict[str, jax.Array]]:
    if kind == "moe":
        return moe_block(
            x, lp["moe"],
            n_experts=cfg.n_experts, n_shared=cfg.n_shared_experts,
            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
            act=cfg.mlp_act, router_renorm=cfg.router_renorm,
            dispatch=cfg.moe_dispatch,
            compute_dtype=cfg.compute_dtype)
    return mlp(x, lp["mlp"], cfg.mlp_act, cfg.compute_dtype), ZERO_AUX()


def layer_body(
    cfg: ArchConfig,
    kind: str,
    x: jax.Array,
    lp: dict[str, Any],
    positions: jax.Array,
    cache: dict[str, jax.Array] | None = None,
) -> tuple[jax.Array, dict[str, jax.Array], Any]:
    """One layer: returns (x, aux, new_cache).

    Named scopes ``mixer`` (norm1, the attention, SSD or hybrid block
    and its residual add) and ``ffn`` (norm2, the MLP or MoE block and
    its residual add) name the layer's device work in profiles."""
    eps = cfg.norm_eps
    aux = ZERO_AUX()
    new_cache = None
    with jax.named_scope("mixer"):
        h = _norm(x, lp["norm1"], eps)

        if kind == "ssm":
            y, new_cache = mamba2_block(
                h, lp["ssm"], d_inner=cfg.d_inner, state_dim=cfg.ssm_state,
                head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
                conv_width=cfg.ssm_conv, chunk=cfg.ssm_chunk,
                compute_dtype=cfg.compute_dtype,
                cache=cache, use_kernels=cfg.use_kernels)
            return x + y.astype(x.dtype), aux, new_cache

        if kind in ("hyb_g", "hyb_l"):
            attn_cache = cache["attn"] if cache is not None else None
            ssm_cache = cache["ssm"] if cache is not None else None
            a_out, new_attn_cache = _attn_sublayer(cfg, kind, h, lp,
                                                   positions, attn_cache)
            s_out, new_ssm_cache = mamba2_block(
                h, lp["ssm"], d_inner=cfg.d_inner, state_dim=cfg.ssm_state,
                head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
                conv_width=cfg.ssm_conv, chunk=cfg.ssm_chunk,
                compute_dtype=cfg.compute_dtype,
                cache=ssm_cache, use_kernels=cfg.use_kernels)
            # Hymba output fusion: mean of per-branch normalized outputs
            y = 0.5 * (rms_norm(a_out, lp["branch_norm_attn"], eps)
                       + rms_norm(s_out.astype(a_out.dtype),
                                  lp["branch_norm_ssm"], eps))
            x = x + y.astype(x.dtype)
            if cache is not None:
                new_cache = {"attn": new_attn_cache, "ssm": new_ssm_cache}
        else:
            a_out, new_cache = _attn_sublayer(cfg, kind, h, lp, positions,
                                              cache)
            x = x + a_out.astype(x.dtype)

    with jax.named_scope("ffn"):
        h2 = _norm(x, lp["norm2"], eps)
        f_out, aux = _ffn_sublayer(cfg, kind, h2, lp)
        return x + f_out.astype(x.dtype), aux, new_cache


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ArchConfig, params: dict[str, Any],
                  batch: dict[str, jax.Array]) -> jax.Array:
    cd = cfg.compute_dtype
    if cfg.input_mode == "tokens":
        return embed_tokens(batch["tokens"], params["embed"],
                            cfg.embed_scale, cd)
    if cfg.input_mode == "embeds":
        return cast(batch["embeds"], cd) @ cast(params["frontend_proj"], cd)
    # mixed (vlm): projected patch embeddings then token embeddings
    patches = cast(batch["patch_embeds"], cd) @ cast(params["frontend_proj"], cd)
    tokens = embed_tokens(batch["tokens"], params["embed"],
                          cfg.embed_scale, cd)
    return jnp.concatenate([patches, tokens], axis=1)


def backbone(
    cfg: ArchConfig,
    params: dict[str, Any],
    batch: dict[str, jax.Array],
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Embeddings → layers → final norm.  Returns (x (B,S,d), aux).

    Each segment's scan body is rematerialized in the backward pass."""
    with jax.named_scope("embed"):
        x = _embed_inputs(cfg, params, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux_total = ZERO_AUX()

    for (kind, count), seg_params in zip(cfg.segments(), params["segments"]):
        def seg_body(carry, lp, _kind=kind):
            xc, aux_acc = carry
            xn, aux, _ = layer_body(cfg, _kind, xc, lp, positions)
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
            return (xn, aux_acc), None

        (x, aux_total), _ = jax.lax.scan(
            jax.checkpoint(seg_body), (x, aux_total), seg_params)

    with jax.named_scope("head"):
        x = _norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def _head(cfg: ArchConfig, params: dict[str, Any]) -> jax.Array:
    return (params["lm_head"] if not cfg.tie_embeddings
            else params["embed"].T)


def forward(
    cfg: ArchConfig,
    params: dict[str, Any],
    batch: dict[str, jax.Array],
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Full forward pass → (logits (B,S,V), aux losses)."""
    x, aux_total = backbone(cfg, params, batch)
    return unembed(x, _head(cfg, params), cfg.compute_dtype), aux_total


def _ce_terms(x: jax.Array, head: jax.Array, labels: jax.Array,
              compute_dtype: Any) -> jax.Array:
    """Summed masked NLL over (B,S,d) (logits never escape)."""
    logits = unembed(x, head, compute_dtype).astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask)


def loss_fn(
    cfg: ArchConfig,
    params: dict[str, Any],
    batch: dict[str, jax.Array],
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Masked causal-LM cross entropy (+ MoE aux).  labels < 0 ignored.

    Named scopes: ``embed`` (the input lookup), ``mixer`` and ``ffn``
    (each layer), ``head`` (the final norm, the LM head and the cross
    entropy)."""
    x, aux = backbone(cfg, params, batch)
    with jax.named_scope("head"):
        labels = batch["labels"]
        nll_sum = _ce_terms(x, _head(cfg, params), labels, cfg.compute_dtype)
        denom = jnp.maximum((labels >= 0).sum(), 1).astype(jnp.float32)
        ce = nll_sum / denom
        loss = (ce
                + 0.01 * aux["load_balance"]
                + 0.001 * aux["router_z"])
    metrics = {"ce": ce, "loss": loss, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serve) path
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype: Any = jnp.bfloat16) -> dict[str, Any]:
    """Per-segment stacked decode caches."""
    segments = []
    for kind, count in cfg.segments():
        def one(kind=kind):
            c: dict[str, Any] = {}
            if kind in ("attn", "moe", "enc", "hyb_g"):
                t = max_len
            elif kind in ("swa", "hyb_l"):
                t = min(cfg.window, max_len) if cfg.window else max_len
            if kind in ("attn", "swa", "moe", "enc"):
                c = {"k": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.head_dim), dtype),
                     "v": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.head_dim), dtype)}
            elif kind == "ssm":
                c = init_ssm_cache(batch, cfg.d_inner, cfg.ssm_state,
                                   cfg.ssm_head_dim, cfg.ssm_groups,
                                   cfg.ssm_conv, dtype)
                c.pop("pos")
            elif kind in ("hyb_g", "hyb_l"):
                sc = init_ssm_cache(batch, cfg.d_inner, cfg.ssm_state,
                                    cfg.ssm_head_dim, cfg.ssm_groups,
                                    cfg.ssm_conv, dtype)
                sc.pop("pos")
                c = {"attn": {"k": jnp.zeros((batch, t, cfg.n_kv_heads,
                                              cfg.head_dim), dtype),
                              "v": jnp.zeros((batch, t, cfg.n_kv_heads,
                                              cfg.head_dim), dtype)},
                     "ssm": sc}
            return c
        layers = [one() for _ in range(count)]
        segments.append(jax.tree.map(lambda *xs: jnp.stack(xs, 0), *layers))
    return {"pos": jnp.zeros((), jnp.int32), "segments": segments}


def decode_step(
    cfg: ArchConfig,
    params: dict[str, Any],
    cache: dict[str, Any],
    token: jax.Array,          # (B, 1) int32
) -> tuple[jax.Array, dict[str, Any]]:
    """One autoregressive step → (logits (B,V), new cache)."""
    if not cfg.has_decode():
        raise ValueError(f"{cfg.name} is encoder-only; no decode step")
    pos = cache["pos"]
    b = token.shape[0]
    x = embed_tokens(token, params["embed"], cfg.embed_scale, cfg.compute_dtype)
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)

    new_segments = []
    for (kind, count), seg_params, seg_cache in zip(
            cfg.segments(), params["segments"], cache["segments"]):

        def seg_body(xc, inp, _kind=kind):
            lp, lc = inp
            if _kind in ("attn", "swa", "moe", "enc"):
                lc = {**lc, "pos": pos}
            elif _kind in ("hyb_g", "hyb_l"):
                lc = {"attn": {**lc["attn"], "pos": pos},
                      "ssm": {**lc["ssm"], "pos": pos}}
            else:
                lc = {**lc, "pos": pos}
            xn, _, nc = layer_body(cfg, _kind, xc, lp, positions, cache=lc)
            # strip pos scalars so the stacked ys stay uniform
            if _kind in ("attn", "swa", "moe", "enc", "ssm"):
                nc = {k: v for k, v in nc.items() if k != "pos"}
            else:
                nc = {"attn": {k: v for k, v in nc["attn"].items() if k != "pos"},
                      "ssm": {k: v for k, v in nc["ssm"].items() if k != "pos"}}
            return xn, nc

        x, new_seg_cache = jax.lax.scan(seg_body, x, (seg_params, seg_cache))
        new_segments.append(new_seg_cache)

    x = _norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, _head(cfg, params), cfg.compute_dtype)[:, 0]
    return logits, {"pos": pos + 1, "segments": new_segments}
