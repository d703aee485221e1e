"""Serving: batched prefill + decode steps with KV/SSM caches.

``make_serve_step`` returns the one-token decode function;
``ServeEngine`` is the runnable batching loop used by the serving
example and ``launch/serve.py`` (continuous token-level batching over a
fixed slot pool — the inference analogue of the paper's "group many
small jobs into one allocation").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.model import Model
from repro.models.transformer import decode_step


def make_serve_step(cfg: ArchConfig) -> Callable:
    """(params, cache, token (B,1)) → (logits (B,V), new cache)."""

    def serve_step(params, cache, token):
        return decode_step(cfg, params, cache, token)

    return serve_step


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Token-level continuous batching over ``slots`` sequences."""

    def __init__(self, cfg: ArchConfig, params: Any, slots: int = 8,
                 max_len: int = 256) -> None:
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.model = Model(cfg)
        self.cache = self.model.init_cache(slots, max_len)
        self._step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        self.active: list[Request | None] = [None] * slots
        self.queue: list[Request] = []
        self.tokens = np.zeros((slots, 1), np.int32)
        #: host copy of the latest tick's (slots, vocab) logits
        self.last_logits: np.ndarray | None = None

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                self.active[i] = req
                # teacher-forced prefill: feed prompt tokens one at a time
                # through the decode path (shared cache; simple + correct)
                self.tokens[i, 0] = req.prompt[0] if req.prompt else 0
                req._fed = 1  # type: ignore[attr-defined]

    def step(self) -> list[Request]:
        """One engine tick: admit, decode one token for every live slot."""
        self._admit()
        if not any(self.active):
            return []
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(self.tokens))
        logits = self.last_logits = np.asarray(logits)
        finished: list[Request] = []
        for i, req in enumerate(self.active):
            if req is None:
                continue
            fed = getattr(req, "_fed", len(req.prompt))
            if fed < len(req.prompt):
                self.tokens[i, 0] = req.prompt[fed]
                req._fed = fed + 1  # type: ignore[attr-defined]
                continue
            nxt = int(np.argmax(logits[i]))
            req.generated.append(nxt)
            self.tokens[i, 0] = nxt
            if len(req.generated) >= req.max_new:
                req.done = True
                finished.append(req)
                self.active[i] = None
        return finished

    def run(self, on_tick: Callable[["ServeEngine"], None] | None = None
            ) -> list[Request]:
        """Tick until every request is answered; ``on_tick(self)`` runs
        after each tick."""
        done: list[Request] = []
        while self.queue or any(self.active):
            done.extend(self.step())
            if on_tick is not None:
                on_tick(self)
        return done
