"""vmap-stack gang training — the paper's job-batching on SPMD hardware.

``train_members``  — one compiled dispatch PER member (the paper's
                     one-job-per-task baseline).
``train_ensemble`` — ALL members folded into one compiled program via
                     ``jax.vmap`` over the member axis; hyperparameters
                     that differ (lr, seed) become per-member arrays.
                     One dispatch total: the *optimal* regime of the
                     paper's Fig. 1, unreachable for an MPI dispatcher.

Members are combo dicts from the study engine, e.g.
``{"args:lr": 3e-4, "args:seed": 1, "args:arch": "gemma3-1b", ...}``.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.core import telemetry
from repro.models.transformer import init_params, loss_fn
from repro.optim.adamw import AdamW, cosine_schedule


def _arg(m: dict[str, Any], key: str, default: Any) -> Any:
    for k in (key, f"args:{key}"):
        if k in m:
            return m[k]
    return default


def _uniform(members: Sequence[dict], key: str, default: Any) -> Any:
    vals = {repr(_arg(m, key, default)) for m in members}
    if len(vals) != 1:
        raise ValueError(
            f"gang members must share {key!r} (shape-affecting); got {vals}. "
            f"Use mesh-slice / one-per-task for heterogeneous studies.")
    return _arg(members[0], key, default)


def _train_one_factory(arch: str, steps: int, batch: int, seq: int,
                       warmup: int):
    cfg = get_smoke(arch)

    def train_one(lr: jax.Array, seed: jax.Array) -> jax.Array:
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        params = init_params(cfg, key)
        opt = AdamW(schedule=cosine_schedule(1.0, warmup, steps))
        state = opt.init(params)

        def body(carry, step_key):
            params, state = carry
            toks = jax.random.randint(step_key, (batch, seq), 0,
                                      cfg.vocab_size)
            b = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
            (loss, _), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, b), has_aux=True)(params)
            # per-member lr scales the unit-base schedule
            scaled = AdamW(schedule=lambda c, _o=opt: lr * _o.schedule(c))
            params, state, _ = scaled.update(grads, state, params)
            return (params, state), loss

        keys = jax.random.split(jax.random.fold_in(key, 1), steps)
        (_, _), losses = jax.lax.scan(body, (params, state), keys)
        return losses[-1]

    return train_one


def _common(members: Sequence[dict]):
    arch = _uniform(members, "arch", "gemma3-1b")
    steps = int(_uniform(members, "steps", 20))
    batch = int(_uniform(members, "batch", 4))
    seq = int(_uniform(members, "seq", 64))
    warmup = max(1, steps // 10)
    lrs = jnp.asarray([float(_arg(m, "lr", 1e-3)) for m in members])
    seeds = jnp.asarray([int(_arg(m, "seed", 0)) for m in members])
    return _train_one_factory(arch, steps, batch, seq, warmup), lrs, seeds


def train_members(members: Sequence[dict]) -> list[float]:
    """One dispatch per member (baseline)."""
    train_one, lrs, seeds = _common(members)
    fn = jax.jit(train_one)
    return [float(fn(lrs[i], seeds[i])) for i in range(len(members))]


def train_ensemble(members: Sequence[dict]) -> list[float]:
    """All members in ONE compiled program (vmap-stack gang).

    Spans ``papas.gang.build`` (closure and jit), ``.lower`` (trace and
    lower), ``.compile`` (backend compile, or load from the persistent
    compilation cache) and ``.run`` (dispatch and the losses' fetch).

    Tracing goes through ``trace(...).lower()``, not ``lower(...)``.
    CPython grows its frame stack in 16 KiB chunks and maps a new one,
    then unmaps it, each time a call crosses into it: about 0.17 ms a
    crossing on a TPU v5e host with the TPU runtime up, thousands of
    times a study where the trace's recursion straddles a boundary. On
    the benchmark's study path ``lower(...)`` crossed about 9,900 times
    a study and ``trace(...).lower()`` about 6,100 (counted on a CPU
    host), and studies ran ~10 % slower with the former."""
    with telemetry.span("gang.build"):
        train_one, lrs, seeds = _common(members)
        fn = jax.jit(jax.vmap(train_one))
    with telemetry.span("gang.lower"):
        lowered = fn.trace(lrs, seeds).lower()
    with telemetry.span("gang.compile"):
        compiled = lowered.compile()
    with telemetry.span("gang.run"):
        return [float(x) for x in compiled(lrs, seeds)]
