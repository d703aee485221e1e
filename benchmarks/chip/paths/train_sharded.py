"""Train path of a model that needs every chip of its cell: the program's
step driven as ``train.py`` drives it, judged against the plain
reference spread over the same chips.

Set-up, the checked steps and the window are ``train.Trainer``'s: the
program's own mesh and sharding rules, from the configuration's
``mesh``. Only the reference differs. Its float32 parameters, AdamW
moments and gradients do not fit one chip, so they lie over a 1-D mesh
of the cell's devices under the benchmark's own layout, independent of
the program's rules: each leaf split along its largest axis that the
number of chips divides (the first such axis on a tie), whole on every
chip where none does. The batch is replicated. Nothing else of the
reference changes (``reference/model.py:first_steps``).
"""
from __future__ import annotations

import functools
import time
from typing import Any

import numpy as np

from .. import compare, data, flops, harness
from ..reference import model as ref_model
from .train import Trainer

AXIS = "chips"


def leaf_axis(shape: tuple[int, ...], n: int) -> int | None:
    """The largest axis of ``shape`` that ``n`` divides (None if none)."""
    fits = [i for i, size in enumerate(shape) if size % n == 0]
    return max(fits, key=lambda i: (shape[i], -i)) if fits else None


def layout(abstract_params, devices) -> Any:
    """NamedShardings of the reference's parameters over ``devices``."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), (AXIS,))

    def one(leaf):
        axis = leaf_axis(leaf.shape, len(devices))
        entries = [None] * len(leaf.shape)
        if axis is not None:
            entries[axis] = AXIS
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(one, abstract_params)


def reference(spec: dict, abstract_params, lowp: str | None = None,
              rows: int | None = None) -> dict[str, Any]:
    """The plain reference over the checked steps, spread over the
    cell's devices. ``rows`` keeps only the first rows of each batch (a
    fault), repeated to the batch's size: the mean is theirs, and the
    compiled reference of the whole batch serves."""
    import jax
    conf, tr = spec["config"], spec["traffic"]
    b, s = tr["batch"], tr["seq"]
    vocab = conf["model"]["vocab_size"]
    batches = [data.train_batch(spec["seed"], i, b, s, vocab)
               for i in range(tr["checked_steps"])]
    if rows is not None:
        batches = [{k: np.resize(v[:rows], v.shape) for k, v in x.items()}
                   for x in batches]
    key = data.seed_key(spec["seed"])
    make = jax.jit(functools.partial(data.make_params, abstract_params),
                   out_shardings=layout(abstract_params, spec["devices"]))
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
