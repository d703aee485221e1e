#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, through its entry points.

    python chip_smoke.py             # one chip: train, serve, study
    python chip_smoke.py --chips 4   # four chips: the sharded train path

Model: ``h2o-danube-1.8b`` at its published widths with random weights
from a seed. One-chip training cuts the depth to 4 of the 24 layers
(every layer is sliding-window attention, so the cut drops no layer
kind); serving runs the full depth. Every phase prints one JSON line of
its own numbers; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``. A failed
check raises, so the script exits non-zero. With no TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-1.8b"

#: serve check: decode logits (bf16 KV cache, one token per step) vs
#: ``Model.forward`` over the same tokens (whole sequence at once), as a
#: share of the largest logit. In float32 the two paths agree to 4e-6 at
#: full width, so they compute the same function; in the configured
#: bf16 (8-bit mantissa) with a bf16 residual stream they sum in
#: different orders, and the rounding gap grows with depth: 1.2 % at 2
#: layers on the CPU, 3.5 % at 24 layers on a TPU v5e. 2^-4 leaves room
#: for that and still fails a lower precision or a wrong cache position.
SERVE_REL_TOL = 2.0 ** -4

#: 1-vs-4-chip check: the first train losses on one device and on a
#: (data=1, model=4) mesh. The same seed gives the same weights under
#: either layout; only the order of bf16 partial sums differs, and the
#: loss is an fp32 mean over every token, so it moves by far less than
#: 1e-3 of its value (about 10.4 at init).
SHARDED_LOSS_REL_TOL = 1e-3


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{devs[0].platform!r}); refusing to run on the CPU")
    return devs


def peak_bytes(devices) -> list[int | None]:
    """Per-device peak bytes in use so far in this process (None where
    the backend does not report it, as on the CPU)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def cut_depth(cfg, n_layers: int):
    return dataclasses.replace(cfg, n_layers=n_layers,
                               layer_types=cfg.layer_types[:n_layers])


def phase_train(cfg, mesh, *, steps: int, batch: int, seq: int,
                name: str = "train") -> dict:
    """``steps`` steps of ``launch.train.train``; losses must be finite."""
    from repro.launch.train import train
    out = train(cfg, mesh, steps=steps, batch=batch, seq=seq,
                log_every=steps)
    losses = out["losses"]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise RuntimeError(f"{name}: expected {steps} finite losses, "
                           f"got {losses}")
    rec = {"phase": name, "layers": cfg.n_layers, "d_model": cfg.d_model,
           "mesh": dict(mesh.shape), "batch": batch, "seq": seq, **out,
           "peak_bytes_in_use": peak_bytes(mesh.devices.flat)}
    emit(rec)
    return rec


def phase_serve(cfg, *, slots: int, max_len: int, n_requests: int,
                prompt_len: tuple[int, int], max_new: int,
                seed: int = 0) -> dict:
    """Answer ``n_requests`` through ``launch.serve.serve``, all admitted
    at tick 0, and check request 0's decode logits against
    ``Model.forward`` over the same tokens."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import make_requests, serve
    from repro.models import Model

    if n_requests > slots:
        raise ValueError("every request must be admitted at tick 0: the "
                         "decode cache keeps one position for all slots")
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    requests = make_requests(cfg, n_requests, prompt_len=prompt_len,
                             max_new=max_new, seed=seed)
    # request 0 sits in slot 0; its decode consumes the prompt and then
    # every generated token but the last
    n_fed = len(requests[0].prompt) + max_new - 1
    rows, tick_s = [], []

    def on_tick(engine):
        tick_s.append(time.perf_counter())
        if len(rows) < n_fed:
            rows.append(engine.last_logits[0].astype(np.float32))

    t0 = time.perf_counter()
    done = serve(cfg, params, requests, slots=slots, max_len=max_len,
                 on_tick=on_tick)
    serve_s = time.perf_counter() - t0
    if len(done) != n_requests or any(len(r.generated) != max_new
                                       for r in done):
        raise RuntimeError(
            f"serve: {len(done)}/{n_requests} requests answered, generated "
            f"{sorted(len(r.generated) for r in done)}")

    req = requests[0]
    toks = jnp.asarray([req.prompt + req.generated[:-1]], jnp.int32)
    fwd = jax.jit(model.forward)(params, {"tokens": toks})[0]
    fwd = np.asarray(fwd[0], np.float32)
    dec = np.stack(rows)
    err = float(np.abs(dec - fwd).max())
    scale = float(np.abs(fwd).max())
    argmax_agree = float(np.mean(dec.argmax(-1) == fwd.argmax(-1)))
    rec = {"phase": "serve", "layers": cfg.n_layers,
           "d_model": cfg.d_model, "slots": slots, "max_len": max_len,
           "requests": len(done),
           "tokens": sum(len(r.generated) for r in done),
           "ticks": len(tick_s), "init_s": init_s, "serve_s": serve_s,
           "first_tick_s": tick_s[0] - t0,
           "steady_tick_s": ((tick_s[-1] - tick_s[0]) / (len(tick_s) - 1)
                             if len(tick_s) > 1 else None),
           "logit_max_abs_err": err, "logit_max_abs": scale,
           "logit_rel_err": err / scale, "rel_tol": SERVE_REL_TOL,
           "argmax_agree": argmax_agree,
           "peak_bytes_in_use": peak_bytes(jax.devices()[:1])}
    emit(rec)
    if not np.isfinite(err) or err > SERVE_REL_TOL * scale:
        raise RuntimeError(f"serve: decode logits differ from forward by "
                           f"{err} (max |logit| {scale})")
    return rec


STUDY_WDL = """\
sweep:
  args:
    lr: [0.001, 0.002, 0.003, 0.004]
    seed: ["0:1"]
    arch: [{arch}]
    steps: [{steps}]
    batch: [{batch}]
    seq: [{seq}]
  command: train
"""


def phase_study(root: Path, *, steps: int, batch: int, seq: int) -> dict:
    """An 8-member lr x seed study through ``launch.sweep.main``, once
    gang-packed and once with one dispatch per member; every instance
    must finish ``ok``."""
    from repro.launch import sweep
    wdl = root / "lr_seed.yaml"
    wdl.write_text(STUDY_WDL.format(arch=ARCH, steps=steps, batch=batch,
                                    seq=seq))
    rec: dict = {"phase": "study", "members": 8, "steps": steps,
                 "batch": batch, "seq": seq}
    for mode, extra in (("gang", ["--gang"]), ("inline", [])):
        t0 = time.perf_counter()
        out = sweep.main([str(wdl), "--root", str(root / mode), *extra])
        wall = time.perf_counter() - t0
        statuses = [r.status for r in out["results"].values()]
        ok = statuses.count("ok")
        rec[mode] = {"ok": ok, "total": len(statuses),
                     "dispatches": out["dispatches"], "wall_s": wall}
        if ok != 8 or len(statuses) != 8:
            emit(rec)
            raise RuntimeError(f"study ({mode}): {ok}/{len(statuses)} ok")
    emit(rec)
    return rec


def phase_sharded(cfg_cut, cfg_full, devices, *, steps: int, batch: int,
                  seq: int) -> dict:
    """The sharded train path on a (data=1, model=4) mesh: full depth
    first (so each chip's peak is its own), then the cut model on that
    mesh and on one device, whose first losses must agree."""
    from repro.launch.mesh import make_local_mesh
    mesh4 = make_local_mesh(model=4, devices=devices[:4])
    phase_train(cfg_full, mesh4, steps=steps, batch=batch, seq=seq,
                name="train_full_depth_4chip")
    four = phase_train(cfg_cut, mesh4, steps=steps, batch=batch, seq=seq,
                       name="train_4chip")
    one = phase_train(cfg_cut, make_local_mesh(devices=devices[:1]),
                      steps=steps, batch=batch, seq=seq, name="train_1chip")
    diff = [abs(a - b) for a, b in zip(four["losses"], one["losses"])]
    rel = max(d / abs(b) for d, b in zip(diff, one["losses"]))
    rec = {"phase": "sharded_vs_one", "loss_abs_diff": diff,
           "max_rel_diff": rel, "rel_tol": SHARDED_LOSS_REL_TOL}
    emit(rec)
    if not rel <= SHARDED_LOSS_REL_TOL:
        raise RuntimeError(f"1-vs-4-chip losses differ by {rel} (relative)")
    return rec


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu()
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX reports {len(devs)}")
    from repro.configs import get
    from repro.launch.mesh import enable_compile_cache, make_local_mesh

    enable_compile_cache()
    emit({"phase": "device", "platform": devs[0].platform,
          "kind": devs[0].device_kind, "count": len(devs)})
    full = get(ARCH)
    cut = cut_depth(full, 4)
    if args.chips == 4:
        phase_sharded(cut, full, devs, steps=3, batch=4, seq=2048)
    else:
        one = make_local_mesh(devices=devs[:1])
        phase_train(cut, one, steps=5, batch=4, seq=2048)
        phase_serve(full, slots=8, max_len=1024, n_requests=8,
                    prompt_len=(16, 65), max_new=32)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            phase_study(Path(tmp), steps=20, batch=4, seq=64)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
