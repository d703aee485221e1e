"""Mixture-of-Experts FFN: shared + routed experts, top-k routing.

Two dispatch strategies (selected by ``ArchConfig.moe_dispatch``):

* ``einsum`` — GShard-style capacity-based one-hot dispatch/combine
  einsums.  Partitions cleanly under pjit (everything is einsums) but
  pays ~2× FLOPs overhead for the dispatch tensors and drops tokens on
  capacity overflow.  This is the BASELINE.
* ``ragged`` — dropless sort-based dispatch: tokens are sorted by expert
  id and multiplied with per-expert weight slabs via
  ``jax.lax.ragged_dot``.  No dispatch-FLOPs, no drops.  Used by the
  §Perf hillclimb (and by the Pallas grouped-GEMM kernel path on TPU).

Expert weights are TP-sharded on ``moe_d_ff`` (each model shard holds a
slice of EVERY expert), so both strategies compose with the data/model
mesh without all_to_all re-sharding.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .layers import _act, cast, maybe_shard


def router_probs(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """Router softmax in fp32. x (T,d) → probs (T,E)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    return jax.nn.softmax(logits, axis=-1), logits


def load_balance_loss(probs: jax.Array, expert_mask: jax.Array,
                      n_experts: int, top_k: int) -> jax.Array:
    """Switch-style auxiliary loss: E · Σ_e f_e · p_e.

    probs (T,E) router probabilities; expert_mask (T,E) count of the
    token's k slots that chose each expert.
    """
    f = jnp.mean(expert_mask.astype(jnp.float32), axis=0) / top_k
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)


def router_z_loss(logits: jax.Array) -> jax.Array:
    return jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))


def _expert_ffn(h_in: jax.Array, p: dict[str, jax.Array], act: str,
                compute_dtype: Any) -> jax.Array:
    """Batched per-expert gated FFN: h_in (G, E, C, d) → (G, E, C, d).

    Kept 4-D end to end: folding G into C would merge a data-sharded
    axis with a model-sharded one and force GSPMD to replicate."""
    fn = _act(act)
    gate = jnp.einsum("gecd,edf->gecf", h_in, cast(p["wi_gate"], compute_dtype))
    up = jnp.einsum("gecd,edf->gecf", h_in, cast(p["wi_up"], compute_dtype))
    return jnp.einsum("gecf,efd->gecd", fn(gate) * up,
                      cast(p["wo"], compute_dtype))


def moe_einsum(
    x: jax.Array,                  # (T, d) — flattened tokens
    p: dict[str, Any],
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float,
    act: str,
    router_renorm: bool,
    compute_dtype: Any = jnp.bfloat16,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """GShard capacity dispatch over all T tokens as one group (G = 1);
    capacity is per group.  Returns (output (T,d), aux losses)."""
    t_total, d = x.shape
    g, tg = 1, t_total
    capacity = max(top_k, int(tg * top_k * capacity_factor / n_experts))
    capacity = ((capacity + 31) // 32) * 32   # model-axis shardable
    xg = x.reshape(g, tg, d)

    probs, logits = router_probs(xg.reshape(-1, d), p["router"])
    probs = probs.reshape(g, tg, n_experts)
    logits = logits.reshape(g, tg, n_experts)

    top_p, top_idx = jax.lax.top_k(probs, top_k)            # (G,Tg,K)
    if router_renorm:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(top_idx, n_experts, dtype=jnp.float32)  # (G,Tg,K,E)
    # position of each (token,k) within its expert queue, per group
    pos = jnp.cumsum(onehot.reshape(g, tg * top_k, n_experts), axis=1)
    pos = pos.reshape(g, tg, top_k, n_experts) * onehot - 1.0
    keep = ((pos >= 0) & (pos < capacity)).astype(jnp.float32)
    sel = onehot * keep                                      # (G,Tg,K,E)
    # top-k experts are distinct per token → at most one k hits each e,
    # so the k axis collapses BEFORE the capacity one-hot (avoids the
    # (G,Tg,K,E,C) rank-5 blow-up)
    pos_te = jnp.sum((pos + 1.0) * sel, axis=2) - 1.0        # (G,Tg,E)
    m_te = jnp.sum(sel, axis=2)
    w_te = jnp.sum(top_p[..., None].astype(jnp.float32) * sel, axis=2)
    cap_oh = jax.nn.one_hot(pos_te.astype(jnp.int32), capacity,
                            dtype=compute_dtype)             # (G,Tg,E,C)
    cap_oh = cap_oh * (m_te > 0)[..., None].astype(compute_dtype)
    # capacity dim sharded over the model axis: bounds every (G,Tg,E,C)
    # intermediate (incl. their f32 cotangents) to 1/TP per device
    dispatch = maybe_shard(cap_oh, "data", None, None, "model")
    combine = maybe_shard(
        cap_oh * w_te[..., None].astype(compute_dtype),
        "data", None, None, "model")

    xin = maybe_shard(
        jnp.einsum("gtec,gtd->gecd", dispatch, cast(xg, compute_dtype)),
        "data", None, "model", None)
    h = maybe_shard(_expert_ffn(xin, p, act, compute_dtype),
                    "data", None, "model", None)
    out = jnp.einsum("gtec,gecd->gtd", combine, h)

    mask = jnp.sum(onehot, axis=2)                          # (G,Tg,E)
    aux = {
        "load_balance": load_balance_loss(
            probs.reshape(-1, n_experts),
            mask.reshape(-1, n_experts), n_experts, top_k),
        "router_z": router_z_loss(logits.reshape(-1, n_experts)),
        "dropped": jnp.mean(1.0 - jnp.sum(keep, axis=(2, 3)) / top_k),
    }
    return out.reshape(t_total, d).astype(x.dtype), aux


def moe_ragged(
    x: jax.Array,                  # (T, d)
    p: dict[str, Any],
    *,
    n_experts: int,
    top_k: int,
    act: str,
    router_renorm: bool,
    compute_dtype: Any = jnp.bfloat16,
    **_: Any,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Dropless sort-based dispatch with ragged_dot grouped matmuls."""
    t, d = x.shape
    probs, logits = router_probs(x, p["router"])
    top_p, top_idx = jax.lax.top_k(probs, top_k)            # (T,K)
    if router_renorm:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_expert = top_idx.reshape(-1)                       # (T*K,)
    order = jnp.argsort(flat_expert)                        # stable
    token_of = order // top_k
    xs = jnp.take(cast(x, compute_dtype), token_of, axis=0)  # (T*K, d) sorted
    group_sizes = jnp.bincount(flat_expert, length=n_experts).astype(jnp.int32)

    fn = _act(act)
    gate = jax.lax.ragged_dot(xs, cast(p["wi_gate"], compute_dtype), group_sizes)
    up = jax.lax.ragged_dot(xs, cast(p["wi_up"], compute_dtype), group_sizes)
    h = jax.lax.ragged_dot(fn(gate) * up, cast(p["wo"], compute_dtype),
                           group_sizes)                      # (T*K, d)
    # un-sort and weight-combine
    weights = jnp.take(top_p.reshape(-1), order).astype(jnp.float32)
    h = h.astype(jnp.float32) * weights[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[token_of].add(h)

    onehot = jax.nn.one_hot(top_idx, n_experts, dtype=jnp.float32)
    aux = {
        "load_balance": load_balance_loss(
            probs, onehot.sum(axis=1), n_experts, top_k),
        "router_z": router_z_loss(logits),
        "dropped": jnp.zeros((), jnp.float32),
    }
    return out.astype(x.dtype), aux


def moe_sorted_local(
    x: jax.Array,                  # (T, d) — one device's tokens
    p: dict[str, Any],
    *,
    n_experts: int,
    top_k: int,
    act: str,
    router_renorm: bool,
    compute_dtype: Any,
    capacity_factor: float = 1.25,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Sort + capacity-padded grouped GEMM (megablox-shaped, pure XLA).

    Dispatch is gathers/scatters (zero FLOPs); expert compute is one
    MXU-aligned batched matmul of (E, Cl, d)·(E, d, f).  Cl is padded to
    a multiple of 128; overflow beyond capacity_factor× mean load drops
    (reported in aux).  On TPU the batched matmul is replaced by the
    Pallas ``grouped_matmul`` kernel."""
    t, d = x.shape
    probs, logits = router_probs(x, p["router"])
    top_p, top_idx = jax.lax.top_k(probs, top_k)            # (T,K)
    if router_renorm:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    tk = t * top_k
    cl = int(tk * capacity_factor / n_experts)
    cl = max(128, ((cl + 127) // 128) * 128)

    flat_expert = top_idx.reshape(-1)                       # (T*K,)
    order = jnp.argsort(flat_expert)
    sorted_expert = jnp.take(flat_expert, order)
    token_of = order // top_k
    # position within the expert segment (sorted → runs are contiguous)
    pos_in_run = jnp.arange(tk, dtype=jnp.int32) - jnp.searchsorted(
        sorted_expert, sorted_expert, side="left").astype(jnp.int32)
    keep = pos_in_run < cl
    dest = jnp.where(keep, sorted_expert * cl + pos_in_run, n_experts * cl)

    xs = jnp.take(cast(x, compute_dtype), token_of, axis=0)  # (T*K, d)
    xin = jnp.zeros((n_experts * cl + 1, d), compute_dtype
                    ).at[dest].set(xs)[:-1]
    xin = xin.reshape(n_experts, cl, d)

    fn = _act(act)
    gate = jnp.einsum("ecd,edf->ecf", xin, cast(p["wi_gate"], compute_dtype))
    up = jnp.einsum("ecd,edf->ecf", xin, cast(p["wi_up"], compute_dtype))
    h = jnp.einsum("ecf,efd->ecd", fn(gate) * up,
                   cast(p["wo"], compute_dtype))             # (E, Cl, d)

    h_rows = jnp.take(
        h.reshape(n_experts * cl, d),
        jnp.minimum(dest, n_experts * cl - 1), axis=0)
    w = (jnp.take(top_p.reshape(-1), order)
         * keep.astype(jnp.float32))[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[token_of].add(
        h_rows.astype(jnp.float32) * w)

    onehot = jax.nn.one_hot(top_idx, n_experts, dtype=jnp.float32)
    aux = {
        "load_balance": load_balance_loss(
            probs, onehot.sum(axis=1), n_experts, top_k),
        "router_z": router_z_loss(logits),
        "dropped": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return out.astype(x.dtype), aux


def moe_ragged_sharded(
    x: jax.Array,                  # (B, S, d)
    p: dict[str, Any],
    *,
    n_experts: int,
    top_k: int,
    act: str,
    router_renorm: bool,
    compute_dtype: Any,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Dropless ragged dispatch under ``shard_map`` (TPU-native).

    GSPMD cannot partition a *global* token sort, so the sort becomes
    per-device: each data shard sorts ITS tokens locally and runs
    ragged_dot against the ffm-TP-sliced expert slabs held by its model
    shard; one psum over "model" combines the ffm partial sums.  Per
    layer this costs one AG(x) + one psum(out) instead of the einsum
    dispatch's O(E·C) traffic — and zero dispatch FLOPs."""
    am = jax.sharding.get_abstract_mesh()
    names = am.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    P_ = jax.sharding.PartitionSpec

    def local_fn(x_loc, router, wig, wiu, wo):
        b_loc, s, d = x_loc.shape
        flat = x_loc.reshape(-1, d)
        out, aux = moe_sorted_local(
            flat, {"router": router, "wi_gate": wig, "wi_up": wiu,
                   "wo": wo},
            n_experts=n_experts, top_k=top_k, act=act,
            router_renorm=router_renorm, compute_dtype=compute_dtype)
        out = jax.lax.psum(out.astype(jnp.float32), "model")
        if dp:
            aux = jax.tree.map(lambda v: jax.lax.pmean(v, dp), aux)
        return out.reshape(b_loc, s, d).astype(x_loc.dtype), aux

    return jax.shard_map(
        local_fn, mesh=am,
        in_specs=(P_(dp_entry, None, None), P_(None, None),
                  P_(None, None, "model"), P_(None, None, "model"),
                  P_(None, "model", None)),
        out_specs=(P_(dp_entry, None, None),
                   jax.tree.map(lambda _: P_(), ZERO_AUX_SPEC)),
    )(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])


ZERO_AUX_SPEC = {"load_balance": 0, "router_z": 0, "dropped": 0}


def moe_block(
    x: jax.Array,                  # (B, S, d)
    p: dict[str, Any],
    *,
    n_experts: int,
    n_shared: int,
    top_k: int,
    capacity_factor: float,
    act: str,
    router_renorm: bool,
    dispatch: str,
    compute_dtype: Any = jnp.bfloat16,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Full MoE FFN: routed experts (+ optional fused shared expert)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    am_names = jax.sharding.get_abstract_mesh().axis_names
    if dispatch == "ragged" and "model" in am_names:
        routed_bsd, aux = moe_ragged_sharded(
            x, p, n_experts=n_experts, top_k=top_k, act=act,
            router_renorm=router_renorm, compute_dtype=compute_dtype)
        routed = routed_bsd.reshape(b * s, d)
    elif dispatch == "ragged":
        routed, aux = moe_ragged(
            flat, p, n_experts=n_experts, top_k=top_k, act=act,
            router_renorm=router_renorm, compute_dtype=compute_dtype)
    else:
        routed, aux = moe_einsum(
            flat, p, n_experts=n_experts, top_k=top_k,
            capacity_factor=capacity_factor, act=act,
            router_renorm=router_renorm, compute_dtype=compute_dtype)
    out = routed
    if n_shared:
        fn = _act(act)
        xc = cast(flat, compute_dtype)
        sp = p["shared"]
        gate = xc @ cast(sp["wi_gate"], compute_dtype)
        up = xc @ cast(sp["wi_up"], compute_dtype)
        shared = (fn(gate) * up) @ cast(sp["wo"], compute_dtype)
        # qwen2-moe gates the shared expert with a sigmoid token gate
        sg = jax.nn.sigmoid(
            (flat.astype(jnp.float32) @ sp["gate"].astype(jnp.float32)))
        out = out + (shared.astype(jnp.float32) * sg).astype(out.dtype)
    return out.reshape(b, s, d), aux
