"""Serving driver: batched continuous decoding over a slot pool.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \\
        --smoke --requests 8 --slots 4 --max-new 16
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.configs import get, get_smoke
from repro.launch.mesh import enable_compile_cache
from repro.models import Model
from repro.models.config import ArchConfig
from repro.serve.engine import Request, ServeEngine


def make_requests(cfg: ArchConfig, n: int, *, prompt_len: tuple[int, int],
                  max_new: int, seed: int = 0) -> list[Request]:
    """``n`` requests of random tokens, prompt lengths uniform in
    ``[prompt_len[0], prompt_len[1])``."""
    rng = np.random.default_rng(seed)
    return [Request(rid=rid,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        rng.integers(*prompt_len)).tolist(),
                    max_new=max_new)
            for rid in range(n)]


def serve(cfg: ArchConfig, params: Any, requests: list[Request], *,
          slots: int, max_len: int,
          on_tick: Callable[[ServeEngine], None] | None = None
          ) -> list[Request]:
    """Answer ``requests`` on a ``ServeEngine``; returns them finished.

    ``on_tick(engine)`` runs after every engine tick."""
    if not cfg.has_decode():
        raise ValueError(f"{cfg.name} is encoder-only; nothing to decode")
    engine = ServeEngine(cfg, params, slots=slots, max_len=max_len)
    for req in requests:
        engine.submit(req)
    return engine.run(on_tick)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if not cfg.has_decode():
        raise SystemExit(f"{cfg.name} is encoder-only; nothing to decode")
    params = jax.jit(Model(cfg).init)(jax.random.PRNGKey(args.seed))
    requests = make_requests(cfg, args.requests, prompt_len=(2, 6),
                             max_new=args.max_new, seed=args.seed)

    t0 = time.time()
    done = serve(cfg, params, requests, slots=args.slots,
                 max_len=args.max_len)
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests / {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s on {args.slots} slots)")
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: {r.prompt} -> {r.generated[:8]}...")


if __name__ == "__main__":
    main()
