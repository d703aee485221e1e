"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \\
        --smoke --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Runs on whatever devices exist (CPU in tests, a TPU host in
production): builds the mesh, the sharded train state (initialized on
the devices under its shardings), the data stream and the jitted train
step; checkpoints every ``--ckpt-every`` steps and resumes from the
latest checkpoint when restarted — kill it mid-run and rerun the same
command to see the fault-tolerance path.
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt
from repro.configs import get, get_smoke
from repro.data.pipeline import make_stream
from repro.distributed import sharding as shd
from repro.launch.mesh import enable_compile_cache, make_local_mesh
from repro.models.config import ArchConfig
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.step import (
    abstract_train_state, init_train_state, make_train_step,
)


def train(cfg: ArchConfig, mesh: jax.sharding.Mesh, *, steps: int,
          batch: int, seq: int, lr: float = 3e-4, warmup: int = 20,
          seed: int = 0, n_micro: int = 1, ckpt_dir: str | None = None,
          ckpt_every: int = 50, log_every: int = 10) -> dict[str, Any]:
    """Train ``cfg`` on ``mesh`` up to global step ``steps``.

    Returns the compile time, the first step's time, the mean time of
    the later steps (each span ends in ``block_until_ready``), and the
    loss of every step run.
    """
    opt = AdamW(schedule=cosine_schedule(lr, warmup, steps))
    step_fn = make_train_step(cfg, opt, n_micro=n_micro)
    state_sh = shd.state_shardings(abstract_train_state(cfg, opt), mesh)
    # initialize under the target shardings: each device materializes
    # only its shard, so a state that fits only when sharded still fits
    init = jax.jit(functools.partial(init_train_state, cfg, opt),
                   out_shardings=state_sh)
    state = init(jax.random.PRNGKey(seed))

    start_step = 0
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        state = ckpt.restore(state, ckpt_dir, shardings=state_sh)
        start_step = int(state["step"])
        print(f"[restore] resumed from step {start_step}")

    stream = iter(make_stream(cfg, batch, seq, seed=seed,
                              start_step=start_step))
    first = next(stream)
    batch_sh = shd.batch_shardings(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), first),
        mesh)
    replicated = NamedSharding(mesh, P())

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        jit_step = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                           out_shardings=(state_sh, replicated),
                           donate_argnums=(0,))
        compiled = jit_step.lower(state, jax.device_put(first, batch_sh)
                                  ).compile()
        compile_s = time.perf_counter() - t0

        losses, marks = [], []
        t_run = time.perf_counter()
        for step in range(start_step, steps):
            host_batch = first if step == start_step else next(stream)
            state, metrics = compiled(state,
                                      jax.device_put(host_batch, batch_sh))
            losses.append(metrics["loss"])
            if step == start_step:
                jax.block_until_ready(state)
                marks.append(time.perf_counter())
            if step % log_every == 0 or step == steps - 1:
                dt = time.perf_counter() - t_run
                tok = (step - start_step + 1) * batch * seq
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"ce={float(metrics['ce']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"tok/s={tok / max(dt, 1e-9):,.0f}")
            if ckpt_dir and (step + 1) % ckpt_every == 0:
                path = ckpt.save(state, ckpt_dir, step + 1)
                print(f"[ckpt] saved {path}")
        jax.block_until_ready(state)
        marks.append(time.perf_counter())

    if ckpt_dir:
        ckpt.save(state, ckpt_dir, int(state["step"]))
    n = len(losses)
    return {
        "compile_s": compile_s,
        "first_step_s": marks[0] - t_run if n else None,
        "steady_step_s": (marks[-1] - marks[0]) / (n - 1) if n > 1 else None,
        "losses": [float(x) for x in losses],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    out = train(cfg, make_local_mesh(), steps=args.steps, batch=args.batch,
                seq=args.seq, lr=args.lr, warmup=args.warmup,
                seed=args.seed, n_micro=args.n_micro,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                log_every=args.log_every)
    if out["losses"]:
        print(f"done: final loss {out['losses'][-1]:.4f} "
              f"(compile {out['compile_s']:.1f}s)")


if __name__ == "__main__":
    main()
