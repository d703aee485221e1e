"""Train path: the program's jitted train step, driven in a closed loop.

Set-up builds the step and its state as the program's launcher does
(``launch/train.py:train``): ``make_train_step``, the state shardings
of ``distributed/sharding.py`` over a ``(data, model)`` mesh, the state
made on the device under those shardings (here from the benchmark's
own weights), the batch shardings, the state donated. It then drives
that same compiled step through its first ``checked_steps`` steps with
the window's own feed and call, reads what the comparison needs, and
hands the step and its state on to the window.

The window: one host batch and one ``device_put`` per step, at most
``IN_FLIGHT`` steps in flight, from the first step's dispatch to
``block_until_ready`` on the last step's state. ``train_tokens_per_s``
is the tokens of every step in the window over its length.

After the window the state is freed and the plain reference trains
the same weights on the same first batches; see ``compare.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from typing import Any

import numpy as np

from .. import compare, data, flops, harness
from ..reference import model as ref_model

#: steps dispatched ahead of the oldest unfinished one. The program's
#: own loop (``launch/train.py``) waits on a step only when it logs, so
#: the chip keeps a queue; two in flight leave a 0.29 s step only 0.6 s
#: of queued work to ride out a stall of the host.
IN_FLIGHT = 8


def arch_config(conf: dict):
    """The program's config of this architecture with the file's sizes."""
    from repro.configs import get
    model = {k: tuple(v) if k == "layer_types" else v
             for k, v in conf["model"].items()}
    return dataclasses.replace(get(conf["arch"]), **model)


class Trainer:
    """The compiled step and its state, as set-up hands them on."""

    def __init__(self, spec: dict) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_local_mesh
        from repro.optim.adamw import AdamW, cosine_schedule
        from repro.train.step import abstract_train_state, make_train_step

        conf, tr = spec["config"], spec["traffic"]
        self.seed, self.tr = spec["seed"], tr
        self.shape = (tr["batch"], tr["seq"], conf["model"]["vocab_size"])
        cfg = arch_config(conf)
        self.mesh = make_local_mesh(model=conf["mesh"]["model"],
                                    devices=spec["devices"])
        opt = AdamW(schedule=cosine_schedule(tr["lr"], tr["warmup"],
                                             tr["total_steps"]),
                    **tr["adamw"])
        abstract = abstract_train_state(cfg, opt)
        self.abstract_params = abstract["params"]
        state_sh = shd.state_shardings(abstract, self.mesh)
        b, s, _ = self.shape
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch_struct = {"tokens": tok, "labels": tok}
        self.batch_sh = shd.batch_shardings(batch_struct, self.mesh)
        self.key = data.seed_key(self.seed)
        make = functools.partial(data.make_params, self.abstract_params)

        def make_state(key):
            params = make(key)
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        self.norms = jax.jit(compare.leaf_norms)
        # the starting weights are made again inside the reduction, so
        # that they are never whole beside the state
        self.change = jax.jit(lambda p, key: compare.leaf_norms(
            jax.tree.map(jnp.subtract, p, make(key))))
        with jax.set_mesh(self.mesh):
            self.state = jax.jit(make_state, out_shardings=state_sh)(self.key)
            self.step_fn = jax.jit(
                make_train_step(cfg, opt),
                in_shardings=(state_sh, self.batch_sh),
                out_shardings=(state_sh, NamedSharding(self.mesh, P())),
                donate_argnums=(0,)).lower(self.state, batch_struct).compile()
        self.next_step = 0

    def feed(self, step: int):
        import jax
        with harness.span("batch"):
            host = data.train_batch(self.seed, step, *self.shape)
        with harness.span("device_put"):
            return jax.device_put(host, self.batch_sh)

    def call(self):
        """One step through the window's feed and call; its metrics."""
        batch = self.feed(self.next_step)
        with harness.span("step"):
            self.state, metrics = self.step_fn(self.state, batch)
        self.next_step += 1
        return metrics

    def checked_steps(self) -> dict[str, Any]:
        """The first steps, and what the comparison reads of them: each
        loss, the first gradient per leaf (from Adam's first moment,
        unclipped by the clip scale of the reported gradient norm), and
        the parameters' change per leaf."""
        import jax
        opt = self.tr["adamw"]
        losses, out = [], {}
        with jax.set_mesh(self.mesh):
            for i in range(self.tr["checked_steps"]):
                metrics = self.call()
                losses.append(metrics["loss"])
                if i == 0:
                    gnorm = float(metrics["grad_norm"])
                    clip = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
                    moment = np.asarray(self.norms(self.state["opt"]["m"]))
                    out["grad_norm"] = gnorm
                    out["grad_leaves"] = moment / (1 - opt["b1"]) / clip
            out["update_leaves"] = np.asarray(
                self.change(self.state["params"], self.key))
        out["losses"] = [float(x) for x in losses]
        return out

    def window(self, seconds: float) -> tuple[int, float, list]:
        """Steps until ``seconds`` have passed; (steps, seconds, losses)."""
        import jax
        losses = []
        with jax.set_mesh(self.mesh), harness.span("window"):
            t0 = time.perf_counter()
            while True:
                metrics = self.call()
                losses.append(metrics["loss"])
                if len(losses) >= IN_FLIGHT:
                    losses[-IN_FLIGHT].block_until_ready()
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(self.state)
            elapsed = time.perf_counter() - t0
        return len(losses), elapsed, losses

    def free(self) -> None:
        del self.state, self.step_fn
        gc.collect()


def reference(spec: dict, abstract_params, lowp: str | None = None,
              rows: int | None = None) -> dict[str, Any]:
    """The plain reference over the checked steps, on the first device.
    ``rows`` keeps only the first rows of each batch (a fault)."""
    import jax
    conf, tr = spec["config"], spec["traffic"]
    b, s = tr["batch"], tr["seq"]
    vocab = conf["model"]["vocab_size"]
    batches = [data.train_batch(spec["seed"], i, b, s, vocab)
               for i in range(tr["checked_steps"])]
    if rows is not None:
        batches = [{k: v[:rows] for k, v in x.items()} for x in batches]
    key = data.seed_key(spec["seed"])
    make = jax.jit(functools.partial(data.make_params, abstract_params),
                   out_shardings=jax.sharding.SingleDeviceSharding(
                       spec["devices"][0]))
    return ref_model.first_steps(conf["model"], lambda: make(key), batches,
                                 tr, compare.leaf_norms, lowp)


def run(spec: dict) -> dict[str, Any]:
    conf = spec["config"]
    compiles = harness.Compiles()
    trainer = Trainer(spec)
    program = trainer.checked_steps()
    setup_s = time.perf_counter() - spec["t0"]
    found: dict[str, Any] = {}
    compiles.counting = True
    with harness.traced(spec["trace"], found):
        n, window_s, losses = trainer.window(spec["seconds"])
    compiles.counting = False
    compiles.close()
    losses = np.asarray([float(x) for x in losses] + program["losses"])
    peak = harness.memory_peak(spec["devices"])
    abstract = trainer.abstract_params
    trainer.free()
    ref = reference(spec, abstract)
    readings = compare.train_readings(program, ref)
    readings["window_compiles"] = compiles.backend
    b, s, _ = trainer.shape
    tokens_per_s = n * b * s / window_s
    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "context": {"tokens_per_s": tokens_per_s, "window_steps": n,
                    "window_s": window_s,
                    "flops_per_token": flops.train_flops_per_token(
                        conf["model"], s),
                    "trace": found.get("trace")},
        "attempted": int(losses.size),
        "failed": int(np.sum(~np.isfinite(losses))),
        "readings": readings,
        "memory_peak_bytes": peak,
        "trace": found.get("trace"),
    }
